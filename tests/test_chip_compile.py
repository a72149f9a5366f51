"""Compile-only pre-flight: what the Pallas interpreter used to hide.

The CPU suite runs the flash kernel interpreted, as plain XLA ops that GSPMD
partitions freely, so it cannot see what Mosaic and the TPU compiler refuse.
libtpu compiles for a ``v5e:2x2`` topology with no chip attached — the same
compiler the chip machine has, so its refusals are real.  Here the real
``train_step`` of GPT-2-small at full width (768 wide, vocab 50257, seq 1024,
bf16, flash attention; depth cut to 2 to stay cheap) is lowered and compiled
for one device and for the four-chip meshes.  So is the one-chip step of
OLMoE-1B-7B at published widths (2048 wide, 64 experts of 1024, top-8, vocab
50304, seq 4096, remat; depth cut to the benchmark's one layer): the grouped
matmul's Mosaic kernels meet the compiler here, and the full compile's memory
analysis for 1, 2 and 4 rows a step is where ``olmoe-s4k-1chip``'s
``rows_per_step`` was decided.  And the one-chip step of Granite-4.0-H-Micro
(2048 wide, five Mamba-2 layers of 64 heads x 64 with a state of 128 and one
grouped-query attention layer, vocab 100,352 tied, seq 8192, remat): the
scan's Mosaic kernels meet the compiler, and no chunk-square tensor of the
scan is left in the XLA program beside 7.8 GB of state.  And the
one-chip block-diffusion step of SDAR-30B-A3B-Chat as one chip of eight holds
it (2048 wide, 32 / 4 heads of 128, 16 of 128 experts of 768 held, 18,992
vocabulary rows, six layers, two rows of 4096 tokens as 8192 positions,
remat): the flash kernels under the block mask and the grouped matmuls over a
worst-case row buffer meet the compiler, beside 10.3 GB of state.  And the
one-chip step of Laguna-XS.2 as one chip of eight holds it (2048 wide, a full
+ dense layer, three sliding + sparse layers and a full + sparse one, 48 / 64
query heads over 8 key/value heads of 128, a window of 512, 32 of 256 experts
of 512 held beside a shared one, 12,544 vocabulary rows, two rows of 8192,
remat): the flash kernels' window mode at 512-wide tiles beside the causal
ones, and the grouped matmuls at the 512-wide shape, beside 11.1 GB of state.
And the one-chip step of Kimi-VL-A3B-Instruct's decoder as one chip of eight
holds it (2048 wide, a dense layer and five sparse ones, latent attention in
each: 16 heads whose scores are 192 wide over values 128 wide with the rotary
64 of the key held once a position, 8 of 64 experts of 1408 held beside a
shared one of 2816, 20,480 vocabulary rows, one row of 16,384, remat): the
flash kernels with their two widths and the shared key part at 1024-wide
tiles, the backward's float32 dQ of 16,384 x 192 in VMEM, beside 10.7 GB of
state.  And the one-chip step of LFM2-24B-A2B as one chip of eight holds it
(2048 wide, a conv + dense layer, an attention + sparse layer and three conv +
sparse ones: the gated short convolution 3 wide over 2 x 16,384 x 2,048
channels in plain XLA, 32 / 8 heads 64 wide with the per-head norm and RoPE, 8
of 64 experts of 1536 held, chosen through a selection bias, 8,192 vocabulary
rows tied, two rows of 16,384, remat): the flash kernels at 64 lanes under
RoPE and the grouped matmuls at the 1536-wide shape, beside 7.5 GB of state.
And the one-chip step of EvaByte (4096 wide, four of 32 layers, 32
heads of 128 under EVA's mask — a window's own causal tiles and the summaries
of the windows before as a second key / value operand of both flash kernels —
SwiGLU 11,008 wide, eight heads over 320 ids, a float32 residual stream, one
row of 16,384, remat), beside 13.15 GB of state.
And the attention prelude alone (projection, heads, the per-head norm
where there is one, the rotation, and their backward) at SDAR's and at
Laguna's full layers' shapes: the bytes the compiled program moves over the
projection and the transpose are where float32 copies of q would show.

And an attention layer's layout alone: what stands between the projections
and the flash kernels at GPT-2's shape and on a Llama layer's ``v`` / ``out``
path, forward and backward, where a copy of an operand would show.

And two of a cell's blocks under remat at Mistral's and at EvaByte's width and
tokens a step: how many (tokens, d_ff) operands each backward matmul of
``gate_proj`` and ``up_proj`` reads, where ``dgate`` made twice in a prologue
would show.

Every case runs in a subprocess (this file, as a script): the libtpu client
must never meet the forced-CPU test process, and the child must NOT inherit
the request for the Pallas interpreter.

The full-compile cases take ~1 min and are ``slow``-marked: run them as the
pre-flight before spending chip time (``.claude/skills/verify/SKILL.md``).
"""

from __future__ import annotations

import functools
import json
import os
import subprocess
import sys

import pytest

N_LAYER = 2
SEQ = 1024
GLOBAL_BATCH = 32
# name -> (MeshConfig kwargs, devices of the 2x2 topology used)
CASES = {
    "one": ({}, 1),
    "dp4": ({"dp": 4}, 4),
    "fsdp4": ({"dp": 1, "fsdp": 4}, 4),
    "dp2_tp2": ({"dp": 2, "tp": 2}, 4),
}

# name -> (query heads, the part of each head the tables turn, per-head norm)
# of an attention prelude at 2 rows x 8192 positions, heads of 128, 2048 wide
PRELUDES = {
    "prelude_sdar": (32, 1.0, True),
    "prelude_laguna_full": (48, 0.5, False),
}


def _cell(name):
    """(model configuration, seq) of a one-chip cell of the benchmark:
    ``olmoe-s4k-1chip`` is OLMoE-1B-7B-0125 at published widths, one layer;
    ``granite-h-s8k-1chip`` Granite-4.0-H-Micro's first six layers."""
    from perfbench.harness import families, manifest

    cell = manifest.cell(name)
    return (families.of(cell.config).model_config(cell.config, 1),
            cell.traffic["seq"])


def _build(case: str, compile_: bool) -> dict:
    """In the child: lower (and compile) the step for one case."""
    import collections
    import re

    import jax
    import jax.numpy as jnp
    from jax.experimental import topologies

    from ray_tpu.models.gpt2 import GPT2Config
    from ray_tpu.models.pretrain import make_optimizer, sharded_train_step
    from ray_tpu.parallel.mesh import MeshConfig, build_mesh

    topo = topologies.get_topology_desc(
        platform="tpu", topology_name="v5e:2x2",
        chips_per_host_bounds=[2, 2, 1], num_slices=1)
    if case.startswith("flash_s"):
        return _build_flash(case, topo.devices[0])
    if case.startswith("kda_s"):
        return _build_kda(case, topo.devices[0])
    if case.startswith("gdn_s"):
        return _build_gdn(case, topo.devices[0])
    if case.startswith("scan_s"):
        return _build_selective_scan(case, topo.devices[0])
    if case.startswith("conv_s"):
        return _build_conv(case, topo.devices[0])
    if case.startswith("rope_b"):
        return _build_rope(case, topo.devices)
    if case.startswith("norm_s"):
        return _build_gated_norm(case, topo.devices[0])
    if case.startswith("mhc_s"):
        return _build_hyper_connection(case, topo.devices[0])
    if case in PRELUDES:
        return _build_prelude(case, topo.devices[0])
    if case in LAYOUTS:
        return _build_layout(case, topo.devices[0])
    if case in MHA_CALLS:
        return _build_mha_call(case, topo.devices[0])
    if case in MLP_BLOCKS:
        return _build_mlp_block(case, topo.devices[0])
    if case.startswith("olmoe_b"):
        mesh = build_mesh(MeshConfig(), devices=topo.devices[:1])
        (config, seq), rows = _cell("olmoe-s4k-1chip"), \
            int(case[len("olmoe_b"):])
        assert config.n_experts == 64 and config.d_model == 2048
    elif case == "sdar":
        mesh = build_mesh(MeshConfig(), devices=topo.devices[:1])
        (config, seq), rows = _cell("sdar-bd-s4k-1chip"), 2
        assert config.experts_held == (0, 16) and config.n_experts == 128
        assert config.objective == "block_diffusion" and config.n_layer == 6
    elif case == "laguna":
        mesh = build_mesh(MeshConfig(), devices=topo.devices[:1])
        (config, seq), rows = _cell("laguna-s8k-1chip"), 2
        assert config.experts_held == (0, 32) and config.n_experts == 256
        assert config.n_head_per_layer == (48, 64, 64, 64, 48)
        assert config.sliding_window == 512 and seq == 8192
    elif case == "kimi_vl":
        mesh = build_mesh(MeshConfig(), devices=topo.devices[:1])
        (config, seq), rows = _cell("kimi-vl-s16k-1chip"), 1
        assert config.experts_held == (0, 8) and config.n_experts == 64
        assert (config.kv_lora_rank, config.qk_nope_head_dim,
                config.qk_rope_head_dim, config.v_head_dim) == (
                    512, 128, 64, 128)
        assert config.n_layer == 6 and seq == 16384
    elif case == "lfm2":
        mesh = build_mesh(MeshConfig(), devices=topo.devices[:1])
        (config, seq), rows = _cell("lfm2-s16k-1chip"), 2
        assert config.experts_held == (0, 8) and config.n_experts == 64
        assert config.layer_types == ("conv", "full_attention") \
            + ("conv",) * 3
        assert (config.head_dim, config.qk_norm, config.conv_width) == (
            64, "head", 3)
        assert config.router_selection_bias and seq == 16384
    elif case == "evabyte":
        mesh = build_mesh(MeshConfig(), devices=topo.devices[:1])
        (config, seq), rows = _cell("evabyte-eva-1chip"), 1
        assert (config.eva_window, config.eva_chunk) == (2048, 16)
        assert (config.n_layer, config.n_head, config.n_kv_head) == (4, 32, 32)
        assert config.n_pred_heads == 8 and config.vocab_size == 320
        assert config.norm_unit_offset and config.residual_dtype == jnp.float32
        assert seq == 16384
    elif case == "kimi_linear":
        mesh = build_mesh(MeshConfig(), devices=topo.devices[:1])
        (config, seq), rows = _cell("kimi-linear-s16k-1chip"), 1
        assert config.experts_held == (0, 8) and config.n_experts == 256
        assert config.layer_types == ("kda",) * 3 + ("full_attention", "kda")
        assert (config.kda_n_heads, config.kda_head_dim, config.kda_chunk,
                config.kda_d_conv) == (32, 128, 64, 4)
        assert not config.rope and config.kv_lora_rank == 512
        assert (config.d_model, config.n_layer, seq) == (2304, 5, 16384)
    elif case == "phi4_flash":
        mesh = build_mesh(MeshConfig(), devices=topo.devices[:1])
        (config, seq), rows = _cell("phi4-flash-s16k-1chip"), 1
        assert config.layer_types == (
            "mamba1", "sliding_attention", "mamba1", "full_attention", "gmu",
            "cross_attention")
        assert config.producers == (2, 3) and config.diff_attn
        assert config.layer_depths == (14, 15, 16, 17, 18, 19)
        assert (config.norm, config.rope, config.tie_embeddings) == (
            "layer", False, True)
        assert (config.d_model, config.n_head, config.n_kv_head,
                config.sliding_window, seq) == (2560, 40, 20, 512, 16384)
    elif case == "smallthinker":
        mesh = build_mesh(MeshConfig(), devices=topo.devices[:1])
        (config, seq), rows = _cell("smallthinker-s16k-1chip"), 1
        assert config.experts_held == (0, 16) and config.n_experts == 64
        assert config.layer_types == ("full_attention",) \
            + ("sliding_attention",) * 3
        assert dict(config.rope_tables)["full_attention"] is None
        assert dict(config.rope_tables)["sliding_attention"].theta == 1.5e6
        assert config.router_before_attention
        assert config.expert_activation == "relu"
        assert (config.d_model, config.n_head, config.n_kv_head,
                config.sliding_window, seq) == (2560, 28, 4, 4096, 16384)
    elif case == "nemotron":
        mesh = build_mesh(MeshConfig(), devices=topo.devices[:1])
        (config, seq), rows = _cell("nemotron3-nano-s16k-1chip"), 1
        assert config.layer_types == (
            "mamba", "none", "mamba", "none", "mamba", "attention", "none",
            "mamba", "none")
        assert config.mlp_types == tuple(
            "sparse" if kind == "none" else "none"
            for kind in config.layer_types)
        assert config.experts_held == (0, 8) and config.n_experts == 128
        assert (config.expert_activation, config.d_expert,
                config.d_shared_expert) == ("relu2", 1856, 3712)
        assert (config.mamba_n_heads, config.mamba_d_head,
                config.mamba_n_groups, config.mamba_d_state,
                config.mamba_chunk) == (64, 64, 8, 128, 128)
        assert not config.rope and config.router_selection_bias
        assert (config.d_model, config.n_head, config.n_kv_head,
                config.vocab_size, seq) == (2688, 32, 2, 16384, 16384)
    elif case in ("xing4", "xing4_mtp"):
        mesh = build_mesh(MeshConfig(), devices=topo.devices[:1])
        (config, seq), rows = _cell("xing4-s8k-1chip"), 1
        assert config.experts_held == (0, 8) and config.n_experts == 64
        assert (config.hc_mult, config.hc_sinkhorn_iters,
                config.hc_res_clamp) == (4, 20, 30.0)
        assert (config.q_lora_rank, config.kv_lora_rank,
                config.qk_nope_head_dim, config.qk_rope_head_dim,
                config.v_head_dim) == (768, 512, 128, 64, 128)
        assert dict(config.rope_tables)["attention"].factor == 64.0
        assert config.mlp_types == ("dense",) + ("sparse",) * 4
        assert (config.d_model, config.n_head, config.d_ff, config.d_expert,
                config.vocab_size, seq) == (3584, 32, 9216, 1024, 16384, 8192)
        assert config.n_mtp_modules == 0 and config.residual_dtype is None
        if case == "xing4_mtp":     # the published module, on this chip too
            import dataclasses
            config = dataclasses.replace(config, n_mtp_modules=1)
    elif case == "granite":
        mesh = build_mesh(MeshConfig(), devices=topo.devices[:1])
        (config, seq), rows = _cell("granite-h-s8k-1chip"), 1
        assert config.layer_types == ("mamba",) * 5 + ("attention",)
        assert config.mamba_n_heads == 64 and config.mamba_chunk == 256
    else:
        mesh_kwargs, n_devices = CASES[case]
        mesh = build_mesh(MeshConfig(**mesh_kwargs),
                          devices=topo.devices[:n_devices])
        config, rows, seq = GPT2Config(n_layer=N_LAYER, remat=False), \
            GLOBAL_BATCH, SEQ
        assert config.vocab_size == 50257
    assert config.attention_impl == "flash"
    s = sharded_train_step(config, mesh, make_optimizer())
    batch = {k: jax.ShapeDtypeStruct((rows, seq), jnp.int32, sharding=sh)
             for k, sh in s.batch_sharding.items()}
    with jax.set_mesh(mesh):
        traced = s.step.trace(s.state, batch)
        lowered = traced.lower(lowering_platforms=("tpu",))
    # the Mosaic calls of the lowered module by their kernels' names (a call
    # the module makes twice through one function is printed once), and the
    # flash forward's calls as the step makes them: those of its jaxpr
    text = lowered.as_text()
    out = {"case": case, "lowered_kernels": dict(collections.Counter(
        re.findall(r'kernel_name = "(\w+)"', text))),
        "flash_fwd_calls": _flash_fwd_calls(traced.jaxpr.jaxpr)}
    if getattr(config, "attn_gate", False):
        # what the step does under an attention layer's ``gate`` scope, by
        # the largest operand or result: a value a head a token and no more
        out["flash_bwd_calls"] = _kernel_calls(
            traced.jaxpr.jaxpr)["flash_bwd"]
        out["gate_scope_widest"] = _widest_under(
            traced.jaxpr.jaxpr, r"/attn/(?:\w+/)*gate(?:/|$)")
    if "mamba" in getattr(config, "layer_types", ()):
        # every array of the module a scan's chunk on a side and square: the
        # decay masks, the scores and their products, where XLA holds them
        out["chunk_squares"] = sorted(set(re.findall(
            rf"tensor<(?:\d+x)*{config.mamba_chunk}x{config.mamba_chunk}"
            r"x\w+>", text)))
    if compile_:
        try:
            compiled = lowered.compile()
        except Exception as e:  # e.g. the program does not fit the chip
            out["refused"] = str(e)[-600:]
            return out
        mem = compiled.memory_analysis()
        out["tpu_custom_calls"] = len(re.findall(
            r'custom_call_target="tpu_custom_call"', compiled.as_text()))
        out["temp_bytes"] = int(mem.temp_size_in_bytes)
        out["argument_bytes"] = int(mem.argument_size_in_bytes)
        if getattr(config, "attn_gate", False):
            out.update(_attn_ops(compiled.as_text()))
    return out


def _widest_under(jaxpr, scope: str, prefix: str = "") -> int:
    """The most elements of an operand or result of any equation under
    ``jaxpr`` whose name stack matches ``scope`` (0: there is none)."""
    import re

    import jax

    widest = 0
    for eqn in jaxpr.eqns:
        name = f"{prefix}/{eqn.source_info.name_stack}"
        subs = list(jax.core.jaxprs_in_params(eqn.params))
        if eqn.primitive.name != "pallas_call":
            widest = max([widest] + [_widest_under(sub, scope, name)
                                     for sub in subs])
        if not subs and re.search(scope, name):
            widest = max([widest] + [v.aval.size for v in (
                *eqn.invars, *eqn.outvars) if hasattr(v.aval, "size")])
    return widest


def _attn_ops(hlo: str) -> dict:
    """Of a compiled step: the Mosaic calls whose ``op_name`` lies under a
    block's ``attn``, and the most elements any instruction named under an
    attention layer's ``gate`` scope reads or writes."""
    import re

    written, gated, calls = {}, [], 0
    for m in re.finditer(
            r"^\s*(?:ROOT )?(%[\w.\-]+) = (.*?) [\w\-]+\((.*?)\)(?:, .*)?"
            r"op_name=\"([^\"]*)\"", hlo, re.M):
        name, shapes, operands, path = m.groups()
        written[name] = _elements(shapes)
        if "tpu_custom_call" in m.group(0) and re.search(r"/h_\d+/attn/", path):
            calls += 1
        if re.search(r"/attn/(?:\w+/)*gate/", path):
            gated.append((name, operands))
    return {"attn_custom_calls": calls,
            "gate_ops_widest": max([0] + [
                n for name, operands in gated for ref in (
                    name, *re.findall(r"%[\w.\-]+", operands))
                for n in written.get(ref, [])])}


def _kernel_calls(jaxpr):
    """A count of the Pallas calls under ``jaxpr`` by kernel name."""
    import collections

    import jax

    n = collections.Counter()
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "pallas_call":
            n[eqn.params["name"]] += 1
        else:
            for sub in jax.core.jaxprs_in_params(eqn.params):
                n += _kernel_calls(sub)
    return n


def _flash_fwd_calls(jaxpr) -> int:
    """The ``flash_fwd`` kernel's calls under ``jaxpr``: one an attention
    layer a step, remat or not (the blocks' checkpoint keeps the kernel's
    output and logsumexp: ``models/gpt2.py::remat_block``)."""
    return _kernel_calls(jaxpr)["flash_fwd"]


def _build_flash(case: str, device) -> dict:
    """In the child: compile the flash kernels alone, forward and backward,
    for one chip at ``flash_s<seq>_d<head width>[_g<group>[_h<heads>[_w<window>]]]``
    in bf16 over 32 heads, the long cells' count (the kernel's need grows a
    little with it), or ``heads``; k and v at ``heads / group`` heads.
    ``dk_heads``: the heads of the dK the backward kernel writes."""
    import re

    import jax
    import jax.numpy as jnp
    from jax.sharding import SingleDeviceSharding

    from ray_tpu.ops.attention import flash_attention

    given = {part[0]: int(part[1:]) for part in case.split("_")[1:]}
    seq, d, rep, h, window = (given.get(key, default) for key, default in (
        ("s", 0), ("d", 0), ("g", 1), ("h", 32), ("w", 0)))

    def shape(heads):
        return jax.ShapeDtypeStruct((1, heads, seq, d), jnp.bfloat16,
                                    sharding=SingleDeviceSharding(device))

    def grads(q, k, v, g):
        return jax.vjp(functools.partial(flash_attention, window=window),
                       q, k, v)[1](g)

    try:
        text = jax.jit(grads).lower(shape(h), shape(h // rep),
                                    shape(h // rep), shape(h)
                                    ).compile().as_text()
    except Exception as e:  # what Mosaic or the TPU compiler refuses
        return {"case": case, "refused": str(e)[:600]}
    (results,) = re.findall(r"= \((.*?)\) custom-call\(.*flash_bwd", text)
    return {"case": case, "dk_heads": int(
        re.findall(r"\w+\[([\d,]+)\]", results)[1].split(",")[1])}


def _build_kda(case: str, device) -> dict:
    """In the child: compile ``ops/kda.py``'s kernels alone, forward and
    backward, for one chip at ``kda_s<seq>`` in bf16 over 32 heads of 128 at
    chunks of 64: the Mosaic calls of the compiled program."""
    import re

    import jax
    import jax.numpy as jnp
    from jax.sharding import SingleDeviceSharding

    from ray_tpu.ops.kda import kda_scan

    seq, heads, d = int(case.split("_s")[1]), 32, 128

    def shape(width, dtype=jnp.bfloat16):
        return jax.ShapeDtypeStruct((1, seq, width), dtype,
                                    sharding=SingleDeviceSharding(device))

    def grads(q, k, v, g, beta, do):
        out, vjp = jax.vjp(functools.partial(kda_scan, chunk=64),
                           q, k, v, g, beta)
        return out, vjp(do)

    wide = shape(heads * d)
    try:
        compiled = jax.jit(grads).lower(
            wide, wide, wide, shape(heads * d, jnp.float32),
            shape(heads, jnp.float32), wide).compile()
    except Exception as e:  # what Mosaic or the TPU compiler refuses
        return {"case": case, "refused": str(e)[-1500:]}
    mem = compiled.memory_analysis()
    return {"case": case, "tpu_custom_calls": len(re.findall(
        r'custom_call_target="tpu_custom_call"', compiled.as_text())),
        "temp_bytes": int(mem.temp_size_in_bytes)}


def _build_gdn(case: str, device) -> dict:
    """In the child: compile ``ops/gdn.py``'s kernels alone, forward and
    backward, for one chip at ``gdn_s<seq>`` in bf16 over 32 value heads and
    16 key heads of 128 at chunks of 64: the Mosaic calls of the compiled
    program."""
    import re

    import jax
    import jax.numpy as jnp
    from jax.sharding import SingleDeviceSharding

    from ray_tpu.ops.gdn import gdn_scan

    seq, keys, heads, d = int(case.split("_s")[1]), 16, 32, 128

    def shape(width, dtype=jnp.bfloat16):
        return jax.ShapeDtypeStruct((1, seq, width), dtype,
                                    sharding=SingleDeviceSharding(device))

    def grads(q, k, v, g, beta, do):
        out, vjp = jax.vjp(functools.partial(gdn_scan, chunk=64),
                           q, k, v, g, beta)
        return out, vjp(do)

    try:
        compiled = jax.jit(grads).lower(
            shape(keys * d), shape(keys * d), shape(heads * d),
            shape(heads, jnp.float32), shape(heads, jnp.float32),
            shape(heads * d)).compile()
    except Exception as e:  # what Mosaic or the TPU compiler refuses
        return {"case": case, "refused": str(e)[-1500:]}
    mem = compiled.memory_analysis()
    return {"case": case, "tpu_custom_calls": len(re.findall(
        r'custom_call_target="tpu_custom_call"', compiled.as_text())),
        "temp_bytes": int(mem.temp_size_in_bytes)}


def _build_selective_scan(case: str, device) -> dict:
    """In the child: compile ``ops/selective_scan.py``'s kernels alone,
    forward and backward, for one chip at ``scan_s<seq>``: one row of 5,120
    channels of 16 states, bf16 ``u``, ``B`` and ``C``, float32 step sizes,
    blocks of 256 positions."""
    import re

    import jax
    import jax.numpy as jnp
    from jax.sharding import SingleDeviceSharding

    from ray_tpu.ops.selective_scan import selective_scan

    seq, d, n = int(case.split("_s")[1]), 5120, 16

    def shape(dims, dtype=jnp.bfloat16):
        return jax.ShapeDtypeStruct(dims, dtype,
                                    sharding=SingleDeviceSharding(device))

    def grads(u, delta, a, b, c, skip, dy):
        out, vjp = jax.vjp(functools.partial(selective_scan, block=256),
                           u, delta, a, b, c, skip)
        return out, vjp(dy)

    wide, narrow = shape((1, seq, d)), shape((1, seq, n))
    try:
        compiled = jax.jit(grads).lower(
            wide, shape((1, seq, d), jnp.float32), shape((d, n), jnp.float32),
            narrow, narrow, shape((d,), jnp.float32), wide).compile()
    except Exception as e:  # what Mosaic or the TPU compiler refuses
        return {"case": case, "refused": str(e)[-1500:]}
    mem = compiled.memory_analysis()
    return {"case": case, "tpu_custom_calls": len(re.findall(
        r'custom_call_target="tpu_custom_call"', compiled.as_text())),
        "temp_bytes": int(mem.temp_size_in_bytes)}


def _build_conv(case: str, device) -> dict:
    """In the child: compile ``ops/conv.py``'s kernels alone, forward and
    backward, for one chip at ``conv_s<seq>_c<channels>[_h<unit heads>]`` in
    bf16, four taps wide: one row, with a bias where no head is normed."""
    import re

    import jax
    import jax.numpy as jnp
    from jax.sharding import SingleDeviceSharding

    from ray_tpu.ops.conv import conv_silu

    given = {part[0]: int(part[1:]) for part in case.split("_")[1:]}
    seq, channels, heads = given["s"], given["c"], given.get("h")

    def shape(*dims):
        return jax.ShapeDtypeStruct(dims, jnp.bfloat16,
                                    sharding=SingleDeviceSharding(device))

    def grads(dy, x, kernel, *bias):
        out, vjp = jax.vjp(functools.partial(
            conv_silu, unit_heads=heads, scale=0.5 if heads else 1.0),
            x, kernel, *bias)
        return out, vjp(dy)

    wide = shape(1, seq, channels)
    try:
        compiled = jax.jit(grads).lower(
            wide, wide, shape(4, channels),
            *([] if heads else [shape(channels)])).compile()
    except Exception as e:  # what Mosaic or the TPU compiler refuses
        return {"case": case, "refused": str(e)[-1500:]}
    mem = compiled.memory_analysis()
    return {"case": case, "tpu_custom_calls": len(re.findall(
        r'custom_call_target="tpu_custom_call"', compiled.as_text())),
        "temp_bytes": int(mem.temp_size_in_bytes)}


def _build_gated_norm(case: str, device) -> dict:
    """In the child: compile ``ops/gated_norm.py``'s kernels alone, forward
    and backward, for one chip at ``norm_s<rows>_c<channels>_g<groups>`` in
    bf16 under a float32 scale."""
    import re

    import jax
    import jax.numpy as jnp
    from jax.sharding import SingleDeviceSharding

    from ray_tpu.ops.gated_norm import gated_rms_norm

    given = {part[0]: int(part[1:]) for part in case.split("_")[1:]}

    def shape(dtype, *dims):
        return jax.ShapeDtypeStruct(dims, dtype,
                                    sharding=SingleDeviceSharding(device))

    def grads(dout, y, z, scale):
        out, vjp = jax.vjp(functools.partial(
            gated_rms_norm, groups=given["g"], eps=1e-5), y, z, scale)
        return out, vjp(dout)

    wide = shape(jnp.bfloat16, 1, given["s"], given["c"])
    try:
        compiled = jax.jit(grads).lower(
            wide, wide, wide, shape(jnp.float32, given["c"])).compile()
    except Exception as e:  # what Mosaic or the TPU compiler refuses
        return {"case": case, "refused": str(e)[-1500:]}
    mem = compiled.memory_analysis()
    return {"case": case, "tpu_custom_calls": len(re.findall(
        r'custom_call_target="tpu_custom_call"', compiled.as_text())),
        "temp_bytes": int(mem.temp_size_in_bytes)}


def _build_rope(case: str, devices) -> dict:
    """In the child: compile ``ops/rope.py``'s pair alone — q and k of a
    layer through one call, forward and backward — at ``rope_b<rows>_s<
    positions>_h<query heads>_k<key heads>_d<head width>_r<percent of a head
    turned>_n<per-head norm>[_f<fsdp>]`` in bf16: for one chip, or with the
    rows over ``fsdp`` chips of the topology inside the calls' ``shard_map``."""
    import re

    import jax
    import jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec as P

    from ray_tpu.models.llama import RopeTable, rope_table
    from ray_tpu.ops import rope
    from ray_tpu.parallel.mesh import MeshConfig, build_mesh

    given = {part[0]: int(part[1:]) for part in case.split("_")[1:]}
    width, seq, fsdp = given["d"], given["s"], given.get("f", 1)
    mesh = build_mesh(MeshConfig(dp=1, fsdp=fsdp), devices=devices[:fsdp])

    def shape(dtype, *dims):
        return jax.ShapeDtypeStruct(dims, dtype, sharding=NamedSharding(
            mesh, P(("dp", "fsdp"), *[None] * (len(dims) - 1))
            if len(dims) == 3 else P()))

    def layer(q, k, scale):
        cos, sin = rope_table(width, jnp.arange(seq), RopeTable(
            theta=1e6, rotary_fraction=given["r"] / 100))
        scale = scale if given["n"] else None
        return rope.rope_qk(q, k, cos, sin, scale, scale, 1e-6,
                            head_dim=width)

    def grads(dq, dk, q, k, scale):
        out, vjp = jax.vjp(layer, q, k, scale)
        return out, vjp((dq, dk))

    q, k = (shape(jnp.bfloat16, given["b"], seq, given[h] * width)
            for h in "hk")
    try:
        with jax.set_mesh(mesh):
            assert rope.takes(q.shape, k.shape, width,
                              width * given["r"] // 100)
            compiled = jax.jit(grads).lower(
                q, k, q, k, shape(jnp.float32, width)).compile()
    except Exception as e:  # what Mosaic or the TPU compiler refuses
        return {"case": case, "refused": str(e)[-1500:]}
    mem = compiled.memory_analysis()
    return {"case": case, "tpu_custom_calls": len(re.findall(
        r'custom_call_target="tpu_custom_call"', compiled.as_text())),
        "temp_bytes": int(mem.temp_size_in_bytes)}


def _build_hyper_connection(case: str, device) -> dict:
    """In the child: compile a sub-layer's hyper-connection alone —
    ``ops/hyper_connection.py``'s two calls around a branch that scales the
    mix, forward and backward — for one chip at ``mhc_s<rows>_c<a stream's
    lanes>_n<streams>`` in bf16; the compiled program's Mosaic calls, and its
    largest float32 array and its largest elementwise sum beside them."""
    import math
    import re

    import jax
    import jax.numpy as jnp
    from jax.sharding import SingleDeviceSharding

    from ray_tpu.ops.hyper_connection import (Spec, hyper_connection,
                                              write_back)

    given = {part[0]: int(part[1:]) for part in case.split("_")[1:]}
    n, c, rows = given["n"], given["c"], given["s"]
    spec = Spec(n, 20, 1e-6, 30.0, 1e-6, jnp.bfloat16)

    def shape(dtype, *dims):
        return jax.ShapeDtypeStruct(dims, dtype,
                                    sharding=SingleDeviceSharding(device))

    def sublayer(x, scale, phi, bias, alpha, w):
        mixed, coefficients, _ = hyper_connection(x, scale, phi, bias, alpha,
                                                  spec)
        return write_back(x, mixed * w, coefficients, spec)

    def grads(dout, *given):
        out, vjp = jax.vjp(sublayer, *given)
        return out, vjp(dout)

    wide = shape(jnp.bfloat16, 1, rows, n * c)
    try:
        compiled = jax.jit(grads).lower(
            wide, wide, shape(jnp.float32, n * c),
            shape(jnp.float32, n * c, spec.k), shape(jnp.float32, spec.k),
            shape(jnp.float32, 3), shape(jnp.bfloat16, c)).compile()
    except Exception as e:  # what Mosaic or the TPU compiler refuses
        return {"case": case, "refused": str(e)[-1500:]}
    text = compiled.as_text()
    # what the entry computation's instructions give: the program's arrays
    # (inside a fusion a float32 value is registers, not memory)
    results = re.findall(r"^\s+\S+ = (.*?) [\w\-]+\(",
                         text[text.index("\nENTRY "):], re.M)

    def elements(dims):
        return math.prod(int(d) for d in dims.split(",") if d)

    return {"case": case, "tpu_custom_calls": len(re.findall(
        r'custom_call_target="tpu_custom_call"', text)),
        "temp_bytes": int(compiled.memory_analysis().temp_size_in_bytes),
        "largest_f32": max(map(elements, re.findall(
            r"f32\[([\d,]*)\]", " ".join(results)))),
        "stream": rows * n * c}


def _build_prelude(case: str, device) -> dict:
    """In the child: what lies between a layer's input and its flash kernel
    for the queries — ``x @ wq``, the split into heads, the transpose to
    ``(B, H, S, D)`` and ``apply_rope`` (with the per-head norm's scale where
    the shape has one) — and its backward, compiled for one chip; and the
    same without ``apply_rope``, which a kernel in that layout costs anyway.
    The compiled programs' ``bytes accessed``."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import SingleDeviceSharding

    from ray_tpu.models.llama import RopeTable, apply_rope, rope_table

    heads, fraction, normed = PRELUDES[case]
    B, S, D, E = 2, 8192, 128, 2048

    def shape(dims, dtype=jnp.bfloat16):
        return jax.ShapeDtypeStruct(dims, dtype,
                                    sharding=SingleDeviceSharding(device))

    def gigabytes(turned: bool) -> float:
        def prelude(x, wq, scale):
            q = (x @ wq).reshape(B, S, heads, D).transpose(0, 2, 1, 3)
            if not turned:
                return q
            cos, sin = rope_table(D, jnp.arange(S), RopeTable(
                theta=1e6, rotary_fraction=fraction))
            return apply_rope(q, cos, sin, scale if normed else None)

        def both_ways(x, wq, scale, g):
            out, vjp = jax.vjp(prelude, x, wq, scale)
            return out, vjp(g)

        compiled = jax.jit(both_ways).lower(
            shape((B, S, E)), shape((E, heads * D)),
            shape((D,), jnp.float32), shape((B, heads, S, D))).compile()
        return compiled.cost_analysis()["bytes accessed"] / 1e9

    return {"case": case, "layout_only_gb": gigabytes(False),
            "prelude_gb": gigabytes(True)}


# name -> (batch, seq, query heads, head width, key/value heads, the mask) of
# an attention layer whose kernels read their operands where the projections
# wrote them
LAYOUTS = {
    "layout_gpt2": (24, 1024, 12, 64, 12, {}),
    "layout_llama_v_out": (2, 8192, 32, 128, 32, {}),
    # grouped: a sliding layer of Laguna-XS.2, a layer of SDAR (2 x 4096
    # tokens as two copies each)
    "layout_gqa_laguna": (2, 8192, 64, 128, 8, dict(window=512)),
    "layout_gqa_sdar": (2, 8192, 32, 128, 4, dict(diffusion_block=4)),
}
GQA_WIDTH = 2048    # the model's width in both grouped cells


def _build_layout(case: str, device) -> dict:
    """In the child: an attention layer's way from its projections to the
    flash kernels and back, forward and backward, compiled for one chip.
    ``layout_gpt2``: ``models/gpt2.py::Attention`` whole at the control
    cell's shape (q, k, v the thirds of ``qkv_proj``'s output, two heads to a
    column block).  The others: ``x @ wv`` -> the kernels -> ``@ wo`` with q
    and k given head-major, as a rotated layer hands them over, k and v at
    the key/value heads.  The compiled program's ``bytes accessed``, the
    shape of every ``copy`` / ``transpose`` instruction of the optimized HLO
    that holds as many elements as the smallest operand, and of the Mosaic
    calls' operands and results those as large as q."""
    import re

    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.sharding import SingleDeviceSharding

    from ray_tpu.models.gpt2 import Attention, GPT2Config
    from ray_tpu.ops.attention import attention

    B, S, H, D, KV, mask = LAYOUTS[case]
    E = H * D if KV == H else GQA_WIDTH

    def shape(*dims):
        return jax.ShapeDtypeStruct(dims, jnp.bfloat16,
                                    sharding=SingleDeviceSharding(device))

    if case == "layout_gpt2":
        layer = Attention(GPT2Config(n_embd=E, n_head=H, n_positions=S))
        params = jax.tree.map(
            lambda a: shape(*a.shape), jax.eval_shape(
                layer.init, jax.random.PRNGKey(0), shape(B, S, E)))
        f, operands = layer.apply, (params, shape(B, S, E))
    else:
        def f(q, k, x, wv, wo):
            return attention(q, k, x @ wv, impl="flash", **mask) @ wo
        operands = (shape(B, H, S, D), shape(B, KV, S, D), shape(B, S, E),
                    shape(E, KV * D), shape(H * D, E))

    def both_ways(g, *operands):
        out, vjp = jax.vjp(f, *operands)
        return out, vjp(g)

    def elements(dims):
        return np.prod([int(n) for n in dims.split(",")])

    try:
        compiled = jax.jit(both_ways).lower(
            shape(B, S, E), *operands).compile()
    except Exception as e:  # what Mosaic or the TPU compiler refuses
        return {"case": case, "refused": str(e)[:600]}
    text = compiled.as_text()
    moved = [m.group(1) for m in re.finditer(
        r"= \w+\[([\d,]+)\]\S* (?:copy|transpose)\(", text)]
    calls = [line for line in text.splitlines()
             if 'custom_call_target="tpu_custom_call"' in line]
    # (a call's results stand before ``custom-call(``, its operands' shapes
    # in ``operand_layout_constraints``)
    seen = [dims for line in calls for part in (
        line.split(" custom-call(")[0],
        line.split("operand_layout_constraints={")[1].split("}}")[0])
        for dims in re.findall(r"\w+\[([\d,]+)\]", part)]
    return {"case": case,
            "gigabytes": compiled.cost_analysis()["bytes accessed"] / 1e9,
            "mosaic_calls": len(calls),
            "operand_sized_copies": [
                dims for dims in moved
                if elements(dims) >= B * S * min(E, KV * D)],
            "as_large_as_q": [dims for dims in seen
                              if elements(dims) >= B * S * H * D]}


# name -> the lowered text of an attention call with a key/value head a query
# head, forward and backward, as the commit before grouped-query attention
# went into the kernels lowered it (PR 52's parent, ``3e9566a``): sha256 of
# the StableHLO with the Mosaic kernels' modules in it, their source
# locations aside.  A PR that changes the kernels on purpose records them
# anew: ``python tests/test_chip_compile.py lower mha_gpt2 ...``.
MHA_CALLS = {
    "mha_gpt2":
        "7842815cbbf75dcee0e92eaecf76923f9cea956a93401f2016f7f8767fa7b85d",
    "mha_olmoe":
        "6c581509c9f17ef168bdf70ab9881c88db410fb0013d72a6acdb3227094b39e9",
    "mha_kimi_vl":
        "91e5abe6a955a9d62e953a80d4f7b4177b9d9a7abdbafa778e2d113ea8588ca6",
    "mha_evabyte":
        "8d5240e2ab194f23855ddeb967e8f1d5f55b69f4e67f2df8bd07fc981cc3a537",
}


def _build_mha_call(case: str, device) -> dict:
    """In the child: ``attention`` at GPT-2's shape (24 x 1024, 12 heads of
    64, the thirds of one array), OLMoE's (1 x 4096, 16 heads of 128, q and
    k head-major, v as its projection wrote it), Kimi-VL's (1 x 16384, 16
    heads, the key's rotary part shared) or EvaByte's (1 x 16384, 32 heads
    under EVA's mask), lowered for one chip, as a digest: what at ``rep`` 1 every index map, grid and scratch shape must
    leave as it was."""
    import base64
    import hashlib
    import re

    import jax
    import jax.numpy as jnp
    from jax._src.interpreters import mlir
    from jax._src.lib.mlir import ir
    from jax.sharding import SingleDeviceSharding

    from ray_tpu.ops.attention import HeadColumns, attention

    def shape(*dims):
        return jax.ShapeDtypeStruct(dims, jnp.bfloat16,
                                    sharding=SingleDeviceSharding(device))

    if case == "mha_gpt2":
        def f(qkv):
            return attention(*(HeadColumns(qkv, 12, 64, first=i * 768)
                               for i in range(3)), impl="flash")
        operands = (shape(24, 1024, 768), shape(24, 1024, 2304))
    elif case == "mha_kimi_vl":
        def f(q, kv, kr):
            return attention(
                q, HeadColumns(kv, 16, 128, first=0, stride=256),
                HeadColumns(kv, 16, 128, first=128, stride=256),
                k_shared=kr, impl="flash")
        operands = (shape(1, 16384, 2048), shape(1, 16, 16384, 192),
                    shape(1, 16384, 4096), shape(1, 1, 16384, 64))
    elif case == "mha_evabyte":
        def f(q, k, v, kp, vp):
            return attention(q, k, v, impl="flash", eva_window=2048,
                             eva_chunk=16, k_pooled=kp, v_pooled=vp)
        operands = (shape(1, 16384, 4096), shape(1, 32, 16384, 128),
                    shape(1, 32, 16384, 128), shape(1, 16384, 4096),
                    shape(1, 32, 1024, 128), shape(1, 1024, 4096))
    else:
        def f(q, k, v):
            return attention(q, k, v, impl="flash")
        operands = (shape(1, 4096, 2048), shape(1, 16, 4096, 128),
                    shape(1, 16, 4096, 128), shape(1, 4096, 2048))

    def both_ways(g, *operands):
        out, vjp = jax.vjp(f, *operands)
        return out, vjp(g)

    def kernel(match):
        """A Mosaic module, as text without its locations."""
        context = mlir.make_ir_context()
        context.allow_unregistered_dialects = True
        with context:
            return ir.Module.parse(base64.b64decode(match.group(1))
                                   ).operation.get_asm(enable_debug_info=False)

    text, kernels = re.subn(r'(?<=body\\22: \\22)([A-Za-z0-9+/=]+)', kernel,
                            jax.jit(both_ways).lower(*operands).as_text())
    return {"case": case, "kernels": kernels,
            "sha256": hashlib.sha256(text.encode()).hexdigest()}


# name -> the cell whose layers the case compiles two of
MLP_BLOCKS = {
    "mlp_block_mistral": "mistral-s8k-1chip",
    "mlp_block_evabyte": "evabyte-eva-1chip",
}


def _build_mlp_block(case: str, device) -> dict:
    """In the child: two of the cell's ``LlamaBlock`` under remat as the
    model stacks them (norm, projections, flash attention, residual, norm,
    SwiGLU, residual) at the cell's width and tokens a step — Mistral's
    8192 x 4096 x 14336 in a bf16 stream, EvaByte's 16384 x 4096 x 11008 in a
    float32 one — forward and backward, compiled for one chip.  Of the
    optimized HLO's backward fusions (the recomputation aside) under
    ``mlp/gate_proj`` and ``mlp/up_proj``: what each writes, how many of its
    operands are (tokens, d_ff) arrays, and the compiler's estimate of its
    cycles."""
    import re

    import jax
    import jax.numpy as jnp
    from jax.sharding import SingleDeviceSharding

    from ray_tpu.models.gpt2 import remat_block
    from ray_tpu.models.llama import LlamaBlock

    cfg, seq = _cell(MLP_BLOCKS[case])
    stream = cfg.residual_dtype or cfg.dtype
    block = remat_block(LlamaBlock, cfg.remat_policy)(cfg, name="h_0")

    def shape(dims, dtype):
        return jax.ShapeDtypeStruct(dims, dtype,
                                    sharding=SingleDeviceSharding(device))

    x, positions = shape((1, seq, cfg.d_model), stream), jnp.arange(seq)
    params = jax.tree.map(
        lambda a: shape(a.shape, a.dtype),
        jax.eval_shape(block.init, jax.random.PRNGKey(0), x, positions))

    def both_ways(first, second, x, g):
        out, vjp = jax.vjp(lambda first, second, x: block.apply(
            second, block.apply(first, x, positions), positions),
            first, second, x)
        return out, vjp(g)

    text = jax.jit(both_ways).lower(params, params, x, x).compile().as_text()

    written = {m.group(1): _elements(m.group(2)) for m in re.finditer(
        r"^\s*(?:ROOT )?(%[\w.\-]+) = (.*?) [\w\-]+\(", text, re.M)}
    fusions = []
    for m in re.finditer(
            r"^\s*(?:ROOT )?%[\w.\-]+ = (.*?) fusion\((.*?)\), kind=.*"
            r"op_name=\"[^\"]*transpose\(jvp([^\"]*)/mlp/(gate_proj|up_proj)/"
            r".*\"estimated_cycles\":\"(\d+)\"", text, re.M):
        if "rematted_computation" in m.group(3):
            continue
        fusions.append({
            "under": m.group(4), "writes": _elements(m.group(1)),
            "wide_operands": sum(
                n == seq * cfg.d_ff for name in re.findall(
                    r"%[\w.\-]+", m.group(2)) for n in written.get(name, [])),
            "cycles": int(m.group(5))})
    return {"case": case, "dx_elements": seq * cfg.d_model,
            "fusions": fusions}


def _elements(shapes: str):
    """The element counts of the arrays an HLO instruction's shapes name."""
    import re

    import numpy as np

    return [int(np.prod([int(n) for n in dims.split(",") if n] or [1]))
            for dims in re.findall(r"\w+\[([\d,]*)\]", shapes)]


def _child(cases, compile_: bool) -> dict:
    env = {k: v for k, v in os.environ.items()
           if k not in ("RAY_TPU_PALLAS_INTERPRET", "XLA_FLAGS")}
    env["JAX_PLATFORMS"] = "cpu"  # no backend but the host; libtpu only compiles
    proc = subprocess.run(
        [sys.executable, os.path.abspath(__file__),
         "compile" if compile_ else "lower", *cases],
        env=env, capture_output=True, text=True, timeout=600)
    assert proc.returncode == 0, proc.stderr[-3000:]
    rows = [json.loads(line[len("CASE="):])
            for line in proc.stdout.splitlines() if line.startswith("CASE=")]
    assert [r["case"] for r in rows] == list(cases), proc.stdout[-2000:]
    return {r["case"]: r for r in rows}


def test_flash_step_lowers_for_a_sharded_v5e_mesh():
    """The cheap guard, in tier-1: under dp=2 x tp=2 at vocab 50257 the step
    lowers for the TPU with the Mosaic kernel in it.  At the seed this raised
    twice over — 'Mosaic kernels cannot be automatically partitioned' and an
    odd vocab dim under PartitionSpec('fsdp', 'tp')."""
    row = _child(["dp2_tp2"], compile_=False)["dp2_tp2"]
    # a layer: one flash forward and the one kernel of its backward
    assert row["lowered_kernels"] == {"flash_fwd": N_LAYER,
                                      "flash_bwd": N_LAYER}, row
    assert row["flash_fwd_calls"] == N_LAYER, row


def test_flash_backward_compiles_with_its_whole_sequence_dq_in_vmem():
    """Tier-1, two seconds a shape: the one backward kernel keeps a float32
    dQ for a head's whole sequence in VMEM, and the limit it asks for
    (``_bwd_vmem_bytes``) is enough at the longest cells' shape (s 8192 x
    d 128) and at s 32768, where Mosaic's default 16 MiB scope is not; past
    the chip's 128 MiB the compiler refuses the call and says so.  Under
    grouped-query attention the dQ is the group's: eight heads' at s 8192
    (Laguna's and SDAR's shape) and at s 14336, the last that
    ``_bwd_vmem_bytes`` puts inside 128 MiB, with dK written at the four
    key/value heads; at s 16384 a head's alone, dK a query head, the sum
    beside the kernel.  At heads 64 wide a group's dQ is rows of half a lane
    tile, indexed in the accumulator itself (two query heads a key/value head
    at s 16384: differential attention's calls)."""
    rows = _child(["flash_s8192_d128", "flash_s32768_d128",
                   "flash_s131072_d128", "flash_s8192_d128_g8",
                   "flash_s14336_d128_g8", "flash_s16384_d128_g8",
                   "flash_s16384_d64_g2"],
                  compile_=True)
    assert "vmem" in rows.pop("flash_s131072_d128")["refused"], rows
    assert {case: row.get("dk_heads") for case, row in rows.items()} == {
        "flash_s8192_d128": 32, "flash_s32768_d128": 32,
        "flash_s8192_d128_g8": 4, "flash_s14336_d128_g8": 4,
        "flash_s16384_d128_g8": 32, "flash_s16384_d64_g2": 16}, rows


def test_flash_backward_holds_a_group_of_sevens_dq_at_the_whole_vmem():
    """Tier-1, a few seconds a shape: SmallThinker's 28 query heads over 4
    key/value heads at 16,384 positions.  A group of seven's float32 dQ and
    its output block are 7 x 16,384 x 128 x 8 B = 112 MiB, which with the 16
    MiB scope is the v5e's 128 MiB exactly: ``_bwd_vmem_bytes``' criterion
    passes with nothing to spare, the call asks Mosaic for the whole VMEM,
    and Mosaic takes it, causal and under the band of 4,096 — dK is written
    at the four key/value heads, no gradient a query head is summed beside
    the kernel."""
    from ray_tpu.ops.attention import _VMEM_BYTES, _bwd_vmem_bytes

    assert _bwd_vmem_bytes(7 * 16384, 128, "bfloat16") == _VMEM_BYTES
    rows = _child(["flash_s16384_d128_g7_h28", "flash_s16384_d128_g7_h28_w4096"],
                  compile_=True)
    assert {case: row.get("dk_heads") for case, row in rows.items()} == {
        "flash_s16384_d128_g7_h28": 4,
        "flash_s16384_d128_g7_h28_w4096": 4}, rows


def test_attention_prelude_moves_no_float32_copy_of_the_queries():
    """Tier-1, a few seconds a shape: over the projection and the transpose
    to ``(B, H, S, D)``, norm and rotation (``models/llama.py::apply_rope``)
    and their backward add the passes an elementwise region needs and little
    more — 1.86 GB at SDAR's shape (32 heads, per-head norm) and 1.33 GB at
    Laguna's full layers' (48 heads, half of each turned), PR 39.  The split
    / concatenate form they replaced added 3.41 and 3.70: XLA wrote q in
    float32, each 64-lane half in a 128-lane tiling, and copies between."""
    rows = _child(list(PRELUDES), compile_=True)
    over = {case: row["prelude_gb"] - row["layout_only_gb"]
            for case, row in rows.items()}
    print(rows)
    assert 0.6 < over["prelude_sdar"] < 2.0, rows
    assert 0.6 < over["prelude_laguna_full"] < 1.7, rows


def test_attention_layout_moves_no_copy_of_an_operand():
    """Tier-1, a few seconds a shape (PR 42): the flash kernels read q, k, v
    and write the output and the gradients where the projections keep them,
    so between ``qkv_proj`` / ``wv`` and the Mosaic calls, and between those
    and ``out_proj`` / ``wo``, the optimized HLO has no ``copy`` or
    ``transpose`` of an operand's size, forward or backward (what is left at
    GPT-2's shape is two copies of per-query statistics, a float32 a head a
    position).  One GPT-2 attention layer at 24 x 1024 x 768 moves 1.39 GB
    where the head-major form moved 3.39 (fourteen copies of an operand's
    size), a Llama layer's ``v`` / ``out`` path at 2 x 8192 x 32 x 128 2.64
    for 3.43 (five).

    Grouped (PR 52): a sliding layer of Laguna (64 / 8 heads of 128, 2 x
    8192, window 512) and a layer of SDAR (32 / 4, the block mask) hand the
    kernels k and v with the heads their projections gave them, and the
    backward fits VMEM with the group's dQ in it: of all the Mosaic calls'
    operands and results only q, dO, the output and dQ are as large as q —
    no K, V, dK or dV of ``heads x D`` columns — and nothing of even a
    key/value operand's size is copied beside them; 2.66 and 1.67 GB moved,
    the projections' matmuls in it."""
    rows = _child(list(LAYOUTS), compile_=True)
    print(rows)
    for row in rows.values():
        assert row["mosaic_calls"] == 2, row
        assert row["operand_sized_copies"] == [], row
    assert rows["layout_gpt2"]["gigabytes"] < 1.55, rows
    assert rows["layout_llama_v_out"]["gigabytes"] < 2.8, rows
    assert rows["layout_gqa_laguna"]["gigabytes"] < 2.8, rows
    assert rows["layout_gqa_sdar"]["gigabytes"] < 1.75, rows
    for case in ("layout_gqa_laguna", "layout_gqa_sdar"):
        # the forward's q and output, the backward's q, dO and dQ
        assert len(rows[case]["as_large_as_q"]) == 5, rows[case]


def test_an_attention_call_without_groups_lowers_to_what_it_was():
    """Tier-1, a second a shape (PR 52): whether a call is grouped is read
    from its operands' shapes, and at a key/value head a query head the
    kernels' index maps, grids and scratch shapes are what they were: the
    attention calls of the cells that have no group — GPT-2's, OLMoE's,
    Kimi-VL's, EvaByte's — lower to the text they lowered to before
    (``MHA_CALLS``), so nothing can move there."""
    rows = _child(list(MHA_CALLS), compile_=False)
    for case, row in rows.items():
        assert row["kernels"] == 2, row
        assert row["sha256"] == MHA_CALLS[case], row


@pytest.mark.parametrize("case", sorted(MLP_BLOCKS))
def test_gate_projs_backward_reads_dgate_as_an_array(case):
    """Tier-1, a quarter of a minute a shape (PR 49): behind
    ``models/moe.py::silu_mul``'s rule the backward matmuls of ``gate_proj``
    and ``up_proj`` read ``dgate`` and ``dup`` as one (tokens, d_ff) operand
    each.  Left to autodiff ``gate_proj``'s two made ``dgate`` from three —
    the cotangent, ``up`` and ``gate`` — in their operand prologues, the
    input gradient's under an epilogue that carries the first half of
    ``mlp_norm``'s backward, and the chip ran the pair at 1.4-1.6 times
    ``up_proj``'s (PERF.md section 6, PR 49).  The compiler's own estimate
    for that fusion stood at 1.9 times ``up_proj``'s input gradient at
    Mistral's shape and 2.1 at EvaByte's; it is under it now (the norm's
    epilogue rides whichever of the two comes last)."""
    row = _child([case], compile_=True)[case]
    print(row)
    of = lambda under: [f for f in row["fusions"]  # noqa: E731
                        if f["under"] == under]
    # a block: the weight's gradient and the input's, for each projection
    assert len(of("gate_proj")) == len(of("up_proj")) == 4, row
    for f in row["fusions"]:
        assert f["wide_operands"] == 1, f

    def dx_cycles(under):
        found = [f["cycles"] for f in of(under)
                 if row["dx_elements"] in f["writes"]]
        assert len(found) == 2, (under, row)
        return sum(found)

    assert dx_cycles("gate_proj") < 1.15 * dx_cycles("up_proj"), row


@pytest.mark.slow
def test_train_step_compiles_on_one_chip_and_every_four_chip_mesh():
    rows = _child(list(CASES), compile_=True)
    for case, row in rows.items():
        # per layer one flash forward and the one kernel of its backward
        # (dK, dV and dQ), as Mosaic calls, not interpreted
        assert row["tpu_custom_calls"] == 2 * N_LAYER, (case, row)
    # the fsdp axis splits the batch's compute, not only parameter storage:
    # at the same global batch a device holds about what it holds under dp
    assert rows["fsdp4"]["temp_bytes"] <= 1.3 * rows["dp4"]["temp_bytes"], rows
    # ... and it does shard the parameters
    assert rows["fsdp4"]["argument_bytes"] < 0.5 * rows["dp4"]["argument_bytes"]


def test_olmoe_step_lowers_for_one_v5e_chip():
    """Tier-1: the one-chip OLMoE step at published widths (one layer, seq
    4096 x 2 rows) lowers for the TPU with its Mosaic kernels in it: the flash
    attention's and the routed experts' grouped matmul."""
    row = _child(["olmoe_b2"], compile_=False)["olmoe_b2"]
    kernels = row["lowered_kernels"]
    # the one layer under remat: the flash forward once (its output and
    # logsumexp are kept) and its backward's one kernel, beside the grouped
    # matmul's (megablox names them "kernel")
    assert kernels.pop("kernel") > 0
    # and the rotation's call over q and k where ``wq`` / ``wk`` wrote them
    # (``ops/rope.py``, PR 70): forward, again under remat, and — the
    # rotation alone being its own transpose at the negated sine — backward
    assert kernels == {"flash_fwd": 1, "flash_bwd": 1,
                       "rope_fwd": 3}, kernels
    assert row["flash_fwd_calls"] == 1, row


@pytest.mark.slow
def test_olmoe_step_compiles_and_says_how_many_rows_fit():
    """The TPU compiler takes the grouped matmul's shapes, and its memory
    analysis says what a step holds at 1, 2 and 4 rows of 4096 (PR 25:
    7.51 GB of arguments + 2.43 / 3.40 / 5.72 GB of temporaries; PR 38, the
    block keeping its flash kernel's output and logsumexp: 7.51 + 1.87 /
    2.68 / 4.07)."""
    rows = _child(["olmoe_b1", "olmoe_b2", "olmoe_b4"], compile_=True)
    for case, row in rows.items():
        print(case, {k: row.get(k) for k in
                     ("argument_bytes", "temp_bytes", "refused")})
    for case in ("olmoe_b1", "olmoe_b2"):
        row = rows[case]
        assert "refused" not in row, row
        # flash forward and the backward's one kernel (no recomputation:
        # the block keeps the forward's output and logsumexp); the grouped
        # matmul: gate, up, down forward, recomputed, and two backward calls
        # each; the rotation's call over q and k: forward, again under
        # remat, backward (PR 70)
        assert row["tpu_custom_calls"] == 2 + 12 + 3, row
        assert row["argument_bytes"] + row["temp_bytes"] < 14e9, row


def test_granite_step_lowers_for_one_v5e_chip():
    """Tier-1: the one-chip Granite-4.0-H-Micro step at published widths
    (five Mamba-2 layers and one attention layer, seq 8192 x 1 row) lowers
    for the TPU with its Mosaic calls in it: the flash kernels, the scan's
    (``ops/ssd.py``), the convolution's (``ops/conv.py``) and the gated
    norm's (``ops/gated_norm.py``, one group of 4,096), and nothing of
    a scan's chunk squares — the decay mask, the scores, their product, the
    cotangents of each — is an array of the XLA program: they live in the
    kernels' VMEM."""
    row = _child(["granite"], compile_=False)["granite"]
    # the one attention layer under remat: one forward, one backward kernel
    # (the block keeps the forward's output and logsumexp); a mamba layer's
    # scan, its convolution with the silu and its gated norm: forward, the
    # forward again in the block's recomputation (the scan's output and the
    # states before each chunk are the backward's residuals; the
    # convolution's and the norm's are their inputs), backward
    assert row["lowered_kernels"] == {
        "flash_fwd": 1, "flash_bwd": 1, "ssd_fwd": 2 * 5, "ssd_bwd": 5,
        "conv_silu_fwd": 2 * 5, "conv_silu_bwd": 5,
        "gated_norm_fwd": 2 * 5, "gated_norm_bwd": 5}, row
    assert row["flash_fwd_calls"] == 1, row
    # (the parent's step had eight kinds of them, up to 32 x 64 x 256 x 256)
    assert row["chunk_squares"] == [], row


@pytest.mark.slow
def test_granite_step_compiles_and_fits_the_chip():
    """The TPU compiler takes the scan's kernels at 64 heads x 32 chunks of
    256 (eight heads a grid step, X and y turned in VMEM), and its memory
    analysis says the step fits (PR 29, the scan in plain XLA: 7.77 GB of
    arguments + 3.98 GB of temporaries; PR 38: 7.77 + 3.97; PR 43, the
    kernels: 7.77 + 3.72)."""
    row = _child(["granite"], compile_=True)["granite"]
    print({k: row.get(k) for k in ("argument_bytes", "temp_bytes", "refused")})
    assert "refused" not in row, row
    # the one attention layer: flash forward and the backward's one kernel;
    # a mamba layer's scan, its convolution and its gated norm: forward, the
    # forward recomputed, backward
    assert row["tpu_custom_calls"] == 2 + 5 * 9, row
    assert row["argument_bytes"] + row["temp_bytes"] < 14e9, row


def test_sdar_step_lowers_for_one_v5e_chip():
    """Tier-1: the one-chip block-diffusion step of SDAR-30B-A3B-Chat at
    published widths (six layers, 16 of 128 experts held, two rows of 4096
    tokens as a noised and a clean copy) lowers for the TPU with its Mosaic
    kernels in it: the flash kernels under the block mask, the grouped
    matmuls of the held experts."""
    row = _child(["sdar"], compile_=False)["sdar"]
    kernels = row["lowered_kernels"]
    assert kernels.pop("kernel") > 0
    # six layers under remat (a kernel called by every layer through one
    # function is printed once per trace: forward, recomputation, backward);
    # ``onto_tokens`` adds a piece's rows into their tokens (PR 36)
    # and since PR 70 the norm-and-rotate pass of q and k, one call a
    # direction (``ops/rope.py``)
    assert set(kernels) == {"flash_fwd", "flash_bwd", "onto_tokens",
                            "rope_fwd", "rope_bwd"}, kernels
    assert (kernels["rope_fwd"], kernels["rope_bwd"]) == (12, 6), kernels
    assert row["flash_fwd_calls"] == 6, row


@pytest.mark.slow
def test_sdar_step_compiles_and_fits_the_chip():
    """The TPU compiler takes the block-mask kernels at 2 x 4096 queries over
    4096 keys and the grouped matmuls over the 16,384 rows of a piece of the
    buffer, inside the loops whose trips the device counts, and its memory
    analysis says six layers fit one chip (PR 31, PR 32: see PERF.md; PR 38,
    six layers' outputs and logsumexps kept: 7.75 GB of arguments + 3.39 GB
    of temporaries, 4.00 at PR 36; 3.33 with PR 39's rotation)."""
    row = _child(["sdar"], compile_=True)["sdar"]
    print({k: row.get(k) for k in ("argument_bytes", "temp_bytes", "refused")})
    assert "refused" not in row, row
    # a layer: flash forward and the backward's one kernel (the forward's
    # output and logsumexp are kept: no recomputation since PR 38);
    # the grouped matmul: gate, up, down in the forward's loop, and in the
    # backward's the three recomputed and two transposes each — the
    # checkpoint's recomputation of the forward's loop is dropped, nothing
    # reads it (PR 32); and in either loop the one call that adds the
    # piece's rows into their tokens (PR 36): 2 + 12 + 2 a layer; and the
    # norm-and-rotate pass of q and k as ``ops/rope.py``'s kernels, forward,
    # again under remat and backward (PR 70): 3 more
    assert row["tpu_custom_calls"] == 6 * (2 + 12 + 2 + 3), row
    assert row["argument_bytes"] + row["temp_bytes"] < 15.5e9, row


def test_laguna_step_lowers_for_one_v5e_chip():
    """Tier-1: the one-chip step of Laguna-XS.2 at published widths (five
    layers of three kinds, 32 of 256 experts held, two rows of 8192) lowers
    for the TPU with its Mosaic kernels in it: the flash kernels, causal and
    under the window, and the grouped matmuls of the held experts."""
    row = _child(["laguna"], compile_=False)["laguna"]
    kernels = row["lowered_kernels"]
    assert kernels.pop("kernel") > 0
    # and since PR 70 the rotation of q and k (``ops/rope.py``): the forward
    # kernel three times a layer, its third call the backward
    assert set(kernels) == {"flash_fwd", "flash_bwd", "onto_tokens",
                            "rope_fwd"}, kernels
    assert kernels["rope_fwd"] == 15, kernels
    assert row["flash_fwd_calls"] == 5 and row["flash_bwd_calls"] == 5, row
    # the gate's own ops are a sigmoid of one value a head a token (2 rows x
    # 8192 x 64 heads): the multiply is the kernels', not a pass over
    # (B, S, H * D)
    assert 0 < row["gate_scope_widest"] <= 2 * 8192 * 64, row


@pytest.mark.slow
def test_laguna_step_compiles_and_fits_the_chip():
    """The TPU compiler takes the window kernels (tiles of 512, two steps of
    the reduction axis) beside the causal ones and the grouped matmuls over
    512-wide experts, and its memory analysis says the five layers fit one
    chip at two rows of 8192 (PR 35: 8.30 GB of arguments + 4.54 GB of
    temporaries; PR 38, five layers' outputs and logsumexps kept: 8.30 +
    4.56; 4.38 with PR 39's rotation)."""
    row = _child(["laguna"], compile_=True)["laguna"]
    print({k: row.get(k) for k in ("argument_bytes", "temp_bytes", "refused")})
    assert "refused" not in row, row
    # a layer: flash forward and the backward's one kernel; a sparse layer's
    # held experts: twelve grouped-matmul calls and the two that add rows
    # into tokens, as SDAR's: 5 x 2 + 4 x (12 + 2); the rotation of q and k,
    # forward, again under remat and backward (PR 70): 5 x 3
    assert row["tpu_custom_calls"] == 5 * (2 + 3) + 4 * (12 + 2), row
    assert row["argument_bytes"] + row["temp_bytes"] < 15.5e9, row
    # under ``attn`` the flash pair and the rotation's three calls a layer
    # (the benchmark's forward selectors take ``flash_fwd``'s scope alone
    # since PR 67); and the gate's four fusions over (B, S, H * D) are gone, not renamed: no
    # instruction named under a ``gate`` scope is wider than a value a head
    # a token
    assert row["attn_custom_calls"] == 5 * (2 + 3), row
    assert row["gate_ops_widest"] <= 2 * 8192 * 64, row


def test_kimi_vl_step_lowers_for_one_v5e_chip():
    """Tier-1: the one-chip step of Kimi-VL-A3B-Instruct's decoder at
    published widths (a dense layer and five sparse ones, 8 of 64 experts
    held, one row of 16,384) lowers for the TPU with its Mosaic kernels in
    it: the flash kernels with scores 192 wide over values 128 wide and the
    key's shared rotary part, the grouped matmuls of the held experts and the
    sum of their rows into the tokens, and no other."""
    row = _child(["kimi_vl"], compile_=False)["kimi_vl"]
    kernels = row["lowered_kernels"]
    assert kernels.pop("kernel") > 0
    assert set(kernels) == {"flash_fwd", "flash_bwd", "onto_tokens"}, kernels
    assert row["flash_fwd_calls"] == 6, row


@pytest.mark.slow
def test_kimi_vl_step_compiles_and_fits_the_chip():
    """The TPU compiler takes the two-width kernels at tiles of 1024 (the
    backward with a float32 dQ of 16,384 x 192 in VMEM) and the grouped
    matmuls over 1408-wide experts, and its memory analysis says the six
    layers fit one chip at one row of 16,384 (PR 37: see PERF.md; PR 38, six
    layers' outputs and logsumexps kept: 8.03 GB of arguments + 3.82 GB of
    temporaries, as at PR 37)."""
    row = _child(["kimi_vl"], compile_=True)["kimi_vl"]
    print({k: row.get(k) for k in ("argument_bytes", "temp_bytes", "refused")})
    assert "refused" not in row, row
    # a layer: flash forward and the backward's one kernel; a sparse layer's
    # held experts: twelve grouped-matmul calls and the two that add rows
    # into tokens, as Laguna's: 6 x 2 + 5 x (12 + 2)
    assert row["tpu_custom_calls"] == 6 * 2 + 5 * (12 + 2), row
    assert row["argument_bytes"] + row["temp_bytes"] < 15.5e9, row


def test_lfm2_step_lowers_for_one_v5e_chip():
    """Tier-1: the one-chip step of LFM2-24B-A2B at published widths (a conv
    + dense layer, an attention + sparse layer and three conv + sparse ones,
    8 of 64 experts held, two rows of 16,384) lowers for the TPU with its
    Mosaic kernels in it: the flash kernels of the one attention layer, the
    grouped matmuls of the held experts and the sum of their rows into the
    tokens, and no other — the short convolution is plain XLA."""
    row = _child(["lfm2"], compile_=False)["lfm2"]
    kernels = row["lowered_kernels"]
    assert kernels.pop("kernel") > 0
    # and since PR 70 the norm-and-rotate pass of q and k (``ops/rope.py``),
    # two heads of 64 to a block of lanes
    assert set(kernels) == {"flash_fwd", "flash_bwd", "onto_tokens",
                            "rope_fwd", "rope_bwd"}, kernels
    assert (kernels["rope_fwd"], kernels["rope_bwd"]) == (2, 1), kernels
    assert row["flash_fwd_calls"] == 1, row


@pytest.mark.slow
def test_lfm2_step_compiles_and_fits_the_chip():
    """The TPU compiler takes the step — the flash kernels 64 lanes wide
    under the norm-and-rotate pass, the grouped matmuls over 1536-wide
    experts under a contraction of 2048 — and its memory analysis says the
    five layers fit one chip at two rows of 16,384 (PR 41: see PERF.md)."""
    row = _child(["lfm2"], compile_=True)["lfm2"]
    print({k: row.get(k) for k in ("argument_bytes", "temp_bytes", "refused")})
    assert "refused" not in row, row
    # the attention layer: flash forward and the backward's one kernel; a
    # sparse layer's held experts: twelve grouped-matmul calls and the two
    # that add rows into tokens, as Kimi's: 2 + 4 x (12 + 2); the
    # norm-and-rotate pass of q and k, three times (PR 70): 3
    assert row["tpu_custom_calls"] == 2 + 3 + 4 * (12 + 2), row
    assert row["argument_bytes"] + row["temp_bytes"] < 15.5e9, row


def test_kimi_linear_step_lowers_for_one_v5e_chip():
    """Tier-1: the one-chip step of Kimi-Linear-48B-A3B at published widths
    (KDA + dense; KDA, KDA, MLA, KDA sparse; 8 of 256 experts held, one row
    of 16,384) lowers for the TPU with its Mosaic kernels in it: the scan's
    three (``ops/kda.py``) in the four KDA layers, the flash pair of the one
    latent-attention layer, the grouped matmuls of the held experts under a
    contraction of 2,304 and the sum of their rows into the tokens, and the
    convolutions' pair (``ops/conv.py``: q and k with a head's unit norm
    inside, v without), and no other — the gates and the output's norm are
    plain XLA."""
    row = _child(["kimi_linear"], compile_=False)["kimi_linear"]
    kernels = row["lowered_kernels"]
    assert kernels.pop("kernel") > 0
    assert set(kernels) == {"kda_solve", "kda_fwd", "kda_bwd", "flash_fwd",
                            "flash_bwd", "onto_tokens", "conv_silu_fwd",
                            "conv_silu_bwd"}, kernels
    # q, k and v of a KDA layer: forward, again under remat, backward
    assert (kernels["conv_silu_fwd"], kernels["conv_silu_bwd"]) \
        == (2 * 3 * 4, 3 * 4), kernels
    assert kernels["flash_fwd"] == kernels["flash_bwd"] == 1, kernels
    # a KDA layer under remat: one solve, kept by name; the forward twice
    assert (kernels["kda_solve"], kernels["kda_fwd"], kernels["kda_bwd"]) \
        == (4, 8, 4), kernels
    assert row["flash_fwd_calls"] == 1, row


@pytest.mark.slow
def test_kimi_linear_step_compiles_and_fits_the_chip():
    """The TPU compiler takes the step — the scan's three kernels with
    their float32 solve and the state riding the grid, the flash kernels at
    32 heads with an un-rotated shared key part, the grouped matmuls under a
    contraction of 2,304 cut in two — and its memory analysis says five
    layers fit one chip at one row of 16,384 beside 9.64 GB of state (PR 56:
    7.23 GB of arguments + 6.48 GB of temporaries with the four layers'
    inverses, 0.13 GB each, kept from the forward to each layer's backward;
    6.51 at PR 54, where one layer's lived at a time, 6.38 at PR 53 before
    the inverse was a residual; see PERF.md)."""
    row = _child(["kimi_linear"], compile_=True)["kimi_linear"]
    print({k: row.get(k) for k in ("argument_bytes", "temp_bytes", "refused")})
    assert "refused" not in row, row
    # a KDA layer: the scan's solve, its forward, the forward again under
    # remat (the solve is kept by name) and its backward; the MLA layer:
    # flash forward and the backward's one kernel; a sparse layer's held
    # experts: twelve grouped-matmul calls and the two that add rows into
    # tokens, as Kimi-VL's; q, k and v of a KDA layer through the
    # convolution's pair, the forward twice: 4 x 4 + 2 + 4 x (12 + 2) + 36
    assert row["tpu_custom_calls"] == 4 * 4 + 2 + 4 * (12 + 2) + 36, row
    assert row["argument_bytes"] + row["temp_bytes"] < 15.5e9, row


def test_kda_kernels_compile_for_one_v5e_chip():
    """Tier-1, fifteen seconds: Mosaic takes ``ops/kda.py``'s three kernels
    at the cell's shape (1 x 16,384 x 32 heads x 128, chunks of 64, bf16) —
    the levels' matmuls, two heads' pairs and solves in one 128-wide matrix,
    the float32 sums as three bfloat16 passes —, which the interpreter on
    the CPU cannot say."""
    row = _child(["kda_s16384"], compile_=True)["kda_s16384"]
    assert "refused" not in row, row
    assert row["tpu_custom_calls"] == 3, row    # solve, forward, backward


def test_gdn_kernels_compile_for_one_v5e_chip():
    """Tier-1, ten seconds: Mosaic takes ``ops/gdn.py``'s three kernels at
    ``qwen3-next-s16k-1chip``'s shape (1 x 16,384 x 32 value heads over 16 key
    heads x 128, chunks of 64, bf16) — a key head's block of q and k beside
    its two value heads' block of v, the mask of exponents from a column and
    its transpose through the identity, two value heads' solves in one
    128-wide matrix —, which the interpreter on the CPU cannot say; beside
    the calls the program holds the chunks' inverses (0.13 GB) and the states
    before the chunks (0.54 GB) and no array as wide as ``g`` broadcast to a
    head's channels (0.27 GB in float32 each time it is made)."""
    row = _child(["gdn_s16384"], compile_=True)["gdn_s16384"]
    assert "refused" not in row, row
    assert row["tpu_custom_calls"] == 3, row    # solve, forward, backward
    assert row["temp_bytes"] < 0.95e9, row


def test_flash_pair_compiles_at_heads_256_wide():
    """Tier-1, five seconds: Mosaic takes the whole-row causal flash pair at
    ``qwen3-next-s16k-1chip``'s shape — 16 query heads 256 wide over 2
    key/value heads, 16,384 positions, the first cell with a head of two lane
    tiles —, the backward writing dK at the query heads (a group of eight's
    dQ at 256 does not fit the VMEM) and the sum beside the kernel."""
    row = _child(["flash_s16384_d256_g8_h16"],
                 compile_=True)["flash_s16384_d256_g8_h16"]
    assert "refused" not in row, row
    assert row["dk_heads"] == 16, row


def test_conv_kernels_compile_for_one_v5e_chip():
    """Tier-1, ten seconds: Mosaic takes ``ops/conv.py``'s pair at the three
    cells' shapes — Kimi-Linear's 1 x 16,384 x 4,096 with 32 heads' unit
    norm, Granite's 1 x 8,192 x 4,352 and Phi-4's 1 x 16,384 x 5,120 with a
    bias — the strided loads and stores from a dynamic start, the reduction
    along a head's lanes —, which the interpreter on the CPU cannot say."""
    cases = ["conv_s16384_c4096_h32", "conv_s8192_c4352", "conv_s16384_c5120"]
    for case, row in _child(cases, compile_=True).items():
        assert "refused" not in row, row
        assert row["tpu_custom_calls"] == 2, row    # a forward and a backward


def test_gated_norm_kernels_compile_for_one_v5e_chip():
    """Tier-1, ten seconds: Mosaic takes ``ops/gated_norm.py``'s pair at the
    two cells' shapes — Nemotron-3-Nano's 1 x 16,384 x 4,096 in eight groups
    of 512 and Granite's 1 x 8,192 x 4,096 in one — the loads and stores
    from a dynamic row and lane, the one reduction along a group's folded
    lanes —, which the interpreter on the CPU cannot say; and beside the
    calls the program holds next to nothing (``dscale``'s eight rows)."""
    cases = ["norm_s16384_c4096_g8", "norm_s8192_c4096_g1"]
    for case, row in _child(cases, compile_=True).items():
        assert "refused" not in row, row
        assert row["tpu_custom_calls"] == 2, row    # a forward and a backward
        assert row["temp_bytes"] < 1 << 20, row


def test_rope_kernels_compile_for_one_v5e_chip_and_under_fsdp():
    """Tier-1, fifteen seconds: Mosaic takes ``ops/rope.py``'s pair for q and
    k of a layer together at the cells' shapes — SDAR's 2 x 8,192 x 32 / 4
    heads of 128 under the per-head norm, Laguna's 2 x 8,192 x 64 / 8 with
    half of each head turned, LFM2's 2 x 16,384 x 32 / 8 heads of 64, two to
    a block, under the norm, Mistral's 1 x 8,192 x 32 / 8, SmallThinker's 1 x
    16,384 x 28 / 4 (seven blocks of q to one of k a group) — the loads and
    stores from a dynamic lane, the pieces side by side along a matmul's
    contraction, the sum down a block's registers —, which the interpreter on
    the CPU cannot say; and at ``mistral-fsdp4-s4k``'s four rows of 4,096
    over ``fsdp=4`` of a ``v5e:2x2``, where the calls stand inside a
    ``shard_map`` that GSPMD leaves whole.  Beside the calls the program holds
    the tables and the scales' rows, no copy of an operand."""
    cases = ["rope_b2_s8192_h32_k4_d128_r100_n1",
             "rope_b2_s8192_h64_k8_d128_r50_n0",
             "rope_b2_s16384_h32_k8_d64_r100_n1",
             "rope_b1_s8192_h32_k8_d128_r100_n0",
             "rope_b1_s16384_h28_k4_d128_r100_n0",
             "rope_b4_s4096_h32_k8_d128_r100_n0_f4"]
    for case, row in _child(cases, compile_=True).items():
        assert "refused" not in row, row
        # q and k through one call a direction
        assert row["tpu_custom_calls"] == 2, row
        assert row["temp_bytes"] < 24 << 20, row


def test_hyper_connection_kernels_compile_for_one_v5e_chip():
    """Tier-1, ten seconds: Mosaic takes ``ops/hyper_connection.py``'s three
    kernels at Xing4's shape (1 x 8,192 positions, four streams of 3,584
    lanes, bf16) — blocks of 128 rows by 14,336 lanes, the projection's
    pieces on the MXU, the planes' single-sublane loads and stores, the
    square transposes between planes and columns, 100 MiB of VMEM —, which
    the interpreter on the CPU cannot say; and beside the calls the compiled
    program holds no float32 array of a stream's size (the widest: the
    pieces' cotangent, 96 x 14,336) and under two streams of temporaries
    (``H_res^T dX'`` and the mix's cotangent, in bf16)."""
    row = _child(["mhc_s8192_c3584_n4"], compile_=True)["mhc_s8192_c3584_n4"]
    assert "refused" not in row, row
    # the stream's pass, the write-back's backward, the stream's pass' own
    assert row["tpu_custom_calls"] == 3, row
    assert row["largest_f32"] <= 3 * 32 * 4 * 3584 < row["stream"], row
    assert row["temp_bytes"] < 2 * 2 * row["stream"], row


def test_phi4_flash_step_lowers_for_one_v5e_chip():
    """Tier-1: the one-chip step of Phi-4-mini-flash-reasoning at published
    widths (published layers 14 to 19: Mamba-1, window attention, Mamba-1
    handing on its scan, full attention handing on K and V, a gated memory
    unit, cross-attention; one row of 16,384) lowers for the TPU with its
    Mosaic kernels in it: the selective scan's pair
    (``ops/selective_scan.py``) in the two Mamba-1 layers and the flash pair
    twice a layer in the three differential attention layers, scores 64 wide
    over values 128 wide, the convolution's pair (``ops/conv.py``) in the
    Mamba-1 layers, and no other — the gates, the gated memory unit, lam and
    the sub-layer norm are plain XLA."""
    row = _child(["phi4_flash"], compile_=False)["phi4_flash"]
    kernels = row["lowered_kernels"]
    assert set(kernels) == {"selective_scan_fwd", "selective_scan_bwd",
                            "flash_fwd", "flash_bwd", "conv_silu_fwd",
                            "conv_silu_bwd"}, kernels
    assert (kernels["conv_silu_fwd"], kernels["conv_silu_bwd"]) == (4, 2), \
        kernels
    assert kernels["flash_fwd"] == kernels["flash_bwd"] == 6, kernels
    assert kernels["selective_scan_bwd"] == 2, kernels
    assert row["flash_fwd_calls"] == 6, row


@pytest.mark.slow
def test_phi4_flash_step_compiles_and_fits_the_chip():
    """The TPU compiler takes the step — the scan's two kernels with 16
    states of 1,024 channels in registers and the state riding the grid, the
    flash kernels with a key/value head to two query heads at scores 64 wide
    (a group's dQ indexed in its accumulator) — and its memory analysis says
    six layers fit one chip at one row of 16,384 beside 11.16 GB of state
    (PR 55: 8.37 GB of arguments + 5.82 GB of temporaries; see PERF.md)."""
    row = _child(["phi4_flash"], compile_=True)["phi4_flash"]
    print({k: row.get(k) for k in ("argument_bytes", "temp_bytes", "refused")})
    assert "refused" not in row, row
    # a Mamba-1 layer: the scan's forward, again under remat, and its
    # backward, and the convolution's the same; an attention layer: two
    # flash forwards and two backwards
    assert row["tpu_custom_calls"] == 2 * 6 + 3 * 4, row
    assert row["argument_bytes"] + row["temp_bytes"] < 15.5e9, row


def test_smallthinker_step_lowers_for_one_v5e_chip():
    """Tier-1: the one-chip step of SmallThinker-21BA3B-Instruct at published
    widths (published layers 0 to 3: a whole-row layer without rotation and
    three under a window of 4,096 with RoPE, 28 query heads over 4, the
    router reading the block's input, 16 of 64 ReLU-gated experts held, one
    row of 16,384) lowers for the TPU with its Mosaic kernels in it: the flash
    pair once a layer and the held experts' grouped matmuls and sums into
    tokens, and no other."""
    row = _child(["smallthinker"], compile_=False)["smallthinker"]
    kernels = row["lowered_kernels"]
    assert kernels.pop("kernel") > 0
    # and since PR 70 the rotation of q and k in the three window layers
    # (``ops/rope.py``): forward, again under remat, backward
    assert kernels == {"flash_fwd": 4, "flash_bwd": 4, "onto_tokens": 2,
                       "rope_fwd": 9}, kernels
    assert row["flash_fwd_calls"] == 4, row


@pytest.mark.slow
def test_smallthinker_step_compiles_and_fits_the_chip():
    """The TPU compiler takes the step — the flash backward with a group of
    seven's dQ at the whole of the VMEM, the band of 4,096 at tiles of 1,024,
    the grouped matmuls over 768-wide experts — and its memory analysis says
    four layers fit one chip at one row of 16,384 beside 10.5 GB of state
    (PR 59: 7.88 GB of arguments + 2.82 GB of temporaries; see PERF.md)."""
    row = _child(["smallthinker"], compile_=True)["smallthinker"]
    print({k: row.get(k) for k in ("argument_bytes", "temp_bytes", "refused")})
    assert "refused" not in row, row
    # a layer: flash forward and the backward's one kernel; its held
    # experts: twelve grouped-matmul calls and the two that add rows into
    # tokens, as SDAR's and Laguna's; the three window layers' rotation of
    # q and k, three times (PR 70)
    assert row["tpu_custom_calls"] == 4 * (2 + 12 + 2) + 3 * 3, row
    assert row["argument_bytes"] + row["temp_bytes"] < 15.5e9, row


def test_nemotron_step_lowers_for_one_v5e_chip():
    """Tier-1: the one-chip step of NVIDIA-Nemotron-3-Nano-30B-A3B at
    published widths (published layers 0 to 8, ``MEMEM*EME``, each one branch
    behind one norm: four Mamba-2 layers in eight groups at chunk 128, four
    layers of squared-ReLU experts without a gate 1,856 wide of which 8 of
    128 are held, one attention layer of 32 query heads over 2 without
    rotation; one row of 16,384) lowers for the TPU with its Mosaic kernels
    in it: the scan's pair, the convolution's pair and the gated norm's pair
    (eight groups of 512) for the Mamba layers, the flash pair once, the held experts' grouped matmuls and sums into
    tokens, and no other; and no chunk-square tensor of the scan is left to
    XLA."""
    row = _child(["nemotron"], compile_=False)["nemotron"]
    kernels = row["lowered_kernels"]
    assert kernels.pop("kernel") > 0
    # (a Mamba layer's scan, convolution and gated norm: forward, the forward
    # again in the layer's recomputation, backward)
    assert kernels == {"flash_fwd": 1, "flash_bwd": 1, "onto_tokens": 2,
                       "ssd_fwd": 2 * 4, "ssd_bwd": 4, "conv_silu_fwd": 2 * 4,
                       "conv_silu_bwd": 4, "gated_norm_fwd": 2 * 4,
                       "gated_norm_bwd": 4}, kernels
    assert row["flash_fwd_calls"] == 1, row
    # (a row is 128 chunks of 128: dt and the running sums, (1, chunks, chunk,
    # heads), are no square; a square's last two dimensions are the chunk's)
    import re
    assert [t for t in row["chunk_squares"]
            if re.search(r"x128x128x[a-z]+\d+>$", t)] == [], row


def test_flash_backward_sums_a_group_of_sixteens_dk_beside_the_kernel():
    """Tier-1, a few seconds: Nemotron-H's 32 query heads over 2 key/value
    heads at 16,384 positions.  A group of sixteen's float32 dQ and its
    output block would be 16 x 16,384 x 128 x 8 B = 256 MiB, twice the v5e's
    VMEM: ``_bwd_vmem_bytes``' criterion fails, the backward walks the grid
    of query heads with one head's dQ in VMEM (32 MiB), writes dK and dV a
    query head, and the sum over each group of sixteen runs beside the
    kernel.  The key/value-head grid is not to be had at this length on
    this chip: the last length at which a group of sixteen's dQ fits is
    ``(128 MiB - 16 MiB) / (16 x 128 x 8 B)`` = 7,168 positions."""
    from ray_tpu.ops.attention import _VMEM_BYTES, _bwd_vmem_bytes

    assert _bwd_vmem_bytes(16 * 16384, 128, "bfloat16") > _VMEM_BYTES
    assert _bwd_vmem_bytes(16 * 7168, 128, "bfloat16") <= _VMEM_BYTES \
        < _bwd_vmem_bytes(16 * 7168 + 16 * 128, 128, "bfloat16")
    rows = _child(["flash_s16384_d128_g16_h32"], compile_=True)
    assert {case: row.get("dk_heads") for case, row in rows.items()} == {
        "flash_s16384_d128_g16_h32": 32}, rows


@pytest.mark.slow
def test_nemotron_step_compiles_and_fits_the_chip():
    """The TPU compiler takes the step — the grouped matmuls over experts
    1,856 wide, 14.5 lanes, their tiles the whole dimension; the scan's two
    kernels at eight groups of eight heads and chunk 128; the flash backward
    at a group of sixteen on the query-head grid — and its memory analysis
    says nine one-branch layers fit one chip at one row of 16,384 beside
    10.67 GB of state (667.0M parameters x 16 B; PR 63: see PERF.md for the
    arguments' and the temporaries' bytes)."""
    row = _child(["nemotron"], compile_=True)["nemotron"]
    print({k: row.get(k) for k in ("argument_bytes", "temp_bytes", "refused",
                                   "tpu_custom_calls")})
    assert "refused" not in row, row
    # 4 x (3 + 3) scan and convolution calls, the flash pair's 2, 4 x (8 + 2)
    # grouped-matmul and sum-into-tokens calls: 66 up to PR 63; the gated
    # norm's 4 x 3
    assert row["tpu_custom_calls"] == 66 + 12, row
    assert row["argument_bytes"] + row["temp_bytes"] < 15.5e9, row


def test_xing4_step_lowers_for_one_v5e_chip():
    """Tier-1: the one-chip step of Xing4.0-29B-A4B at published widths
    (published layers 1 to 5: a dense layer and four sparse ones over a
    residual path four streams wide, ``HyperConnection`` around both branches
    of each; latent attention with a query latent under YaRN at 32 heads,
    scores 192 wide over values 128; 8 of 64 experts 1,024 wide held under a
    contraction of 3,584; one row of 8,192) lowers for the TPU with its
    Mosaic kernels in it — the flash pair five times, the held experts'
    grouped matmuls and sums into tokens, and since PR 66 the
    hyper-connections' three (``ops/hyper_connection.py``): of ten
    sub-layers the stream's pass forward and again under remat, the
    write-back's backward and the stream's pass' backward — and no other."""
    row = _child(["xing4"], compile_=False)["xing4"]
    kernels = row["lowered_kernels"]
    assert kernels.pop("kernel") > 0
    assert kernels == {"flash_fwd": 5, "flash_bwd": 5, "onto_tokens": 2,
                       "hc_mix_fwd": 20, "hc_write_bwd": 10,
                       "hc_mix_bwd": 10}, kernels
    assert row["flash_fwd_calls"] == 5, row


@pytest.mark.slow
@pytest.mark.parametrize("case,calls,most", [("xing4", 82 + 40, 13.6e9),
                                             ("xing4_mtp", 102 + 48, 15.6e9)])
def test_xing4_step_compiles_and_fits_the_chip(case, calls, most):
    """The TPU compiler takes the step — the grouped matmuls at a contraction
    of 3,584 as 2 x 1,792, the flash kernels' two widths at 32 heads, the
    hyper-connections' three kernels a sub-layer (since PR 66: 40 calls, 48
    with the module's block; the temporaries below are PR 65's, with them in
    plain XLA: 3.781 GB and 4.381 GB now) — and its
    memory analysis says five four-stream layers fit one chip at one row of
    8,192 beside 12.15 GB of state (759.5M parameters x 16 B): 9.114 GB of
    arguments (12 B a parameter) + 4.314 GB of temporaries, PR 65.  **And the
    same step with the published prediction module on this chip**
    (``xing4_mtp``: 913.6M parameters, 14.6 GB of state): 10.964 GB of
    arguments + 4.520 GB of temporaries = 15.48 GB, which the compiler still
    takes and which leaves under 1.5 GB of a 16 GiB chip — no room for the
    agreement check's float32 reference and its gradients beside it: why the
    cell leaves the module to the last pipeline stage (PERF.md section 4)."""
    row = _child([case], compile_=True)[case]
    print({k: row.get(k) for k in ("argument_bytes", "temp_bytes", "refused",
                                   "tpu_custom_calls")})
    assert "refused" not in row, row
    assert row["tpu_custom_calls"] == calls, row
    assert row["argument_bytes"] + row["temp_bytes"] < most, row


def test_selective_scan_kernels_compile_for_one_v5e_chip():
    """Tier-1, fifteen seconds: Mosaic takes ``ops/selective_scan.py``'s two
    kernels at the cell's shape (1 x 16,384 x 5,120 channels x 16 states,
    blocks of 256, bf16 u, B and C) — scalars from SMEM against whole
    registers, the block's states made again in VMEM, the sums over a
    channel block's sublanes —, which the interpreter on the CPU cannot
    say."""
    row = _child(["scan_s16384"], compile_=True)["scan_s16384"]
    assert "refused" not in row, row
    assert row["tpu_custom_calls"] == 2, row    # a forward and a backward


def test_evabyte_step_lowers_for_one_v5e_chip():
    """Tier-1: the one-chip step of EvaByte at published widths (four layers,
    32 heads of 128 under EVA's mask with windows of 2,048 and chunks of 16,
    eight heads over 320 ids, one row of 16,384) lowers for the TPU with its
    Mosaic kernels in it: the flash pair of each layer, the summaries a
    second key / value operand of both, and the pooling's pair
    (``ops/pooling.py``), and no other."""
    row = _child(["evabyte"], compile_=False)["evabyte"]
    kernels = row["lowered_kernels"]
    assert set(kernels) == {"flash_fwd", "flash_bwd", "pool_fwd",
                            "pool_bwd", "rope_fwd"}, kernels
    # (since PR 70 the rotation of q and k, ``ops/rope.py``: three a layer)
    assert kernels["rope_fwd"] == 12, kernels
    assert kernels["flash_fwd"] == kernels["flash_bwd"] == 4, kernels
    assert row["flash_fwd_calls"] == 4, row


@pytest.mark.slow
def test_evabyte_step_compiles_and_fits_the_chip():
    """The TPU compiler takes the step — both kernels with the summaries'
    tiles on their grids, dQ's accumulator spanning 16,384 queries — and its
    memory analysis says four layers fit one chip at one row of 16,384 beside
    13.15 GB of state (PR 47: 9.86 GB of arguments + 4.81 GB of temporaries;
    see PERF.md)."""
    row = _child(["evabyte"], compile_=True)["evabyte"]
    print({k: row.get(k) for k in ("argument_bytes", "temp_bytes", "refused")})
    assert "refused" not in row, row
    # a layer: flash forward and the backward's one kernel, no second
    # forward under remat; the pooling forward, again under remat, and its
    # backward; the rotation of q and k, three times (PR 70)
    assert row["tpu_custom_calls"] == 4 * (2 + 3 + 3), row
    assert row["argument_bytes"] + row["temp_bytes"] < 15.5e9, row


if __name__ == "__main__":
    sys.path.insert(0, os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))
    for name in sys.argv[2:]:
        print("CASE=" + json.dumps(_build(name, sys.argv[1] == "compile")),
              flush=True)

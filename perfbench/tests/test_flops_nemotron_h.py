"""``families/nemotron_h.py::shape`` (what ``flops.train_flops_per_token``
counts ``nemotron3-nano-s16k-1chip`` from) against a hand count and the
parameter tree's leaves, ``flash_work.py`` and ``ssd_work.py`` at its sizes
against sums written out by hand, and the cell's metrics on a synthetic trace
whose name paths are as the chip's trace prints them."""

import json
import os

import numpy as np
import pytest

from perfbench.harness import flash_work, flops, manifest, ssd_work
from perfbench.harness.families import nemotron_h
from perfbench.tests.manifest_lists import on_at_least
from perfbench.harness.readers import kernel_roofline, scope_roofline, trace_ops
from perfbench.harness.readers.context import Context
from perfbench.harness.trace_reduce import Op, Trace

NAME = "nemotron3-nano-s16k-1chip"
CELL = manifest.cell(NAME)
CONFIG = CELL.config
PEAK = manifest.peaks()["TPU v5 lite"]
SEQ = 16384
# PR 63's ten; since PR 67 the five that were copies of an older entry's
# selection are that entry (mamba8g_* -> mamba_*, ssd8g_scan_ms_per_step ->
# ssd_scan_ms_per_step, relu2_* -> moe_*), which lists this cell too; since
# PR 71 its three rooflines are the entries of one work function a kernel
# family (ssd8g_scan_roofline -> ssd_scan_roofline, gqa16_attn_*_roofline ->
# flash_*_roofline) and gqa16_attn_ms_per_step, the cell's one flash pair by
# another path, is gone
NEW = ["mamba_scope_share_pct", "ssd_scan_ms_per_step",
       "ssd_scan_roofline", "mamba_proj_ms_per_step",
       "grouped_gated_norm_ms_per_step", "moe_experts_ms_per_step",
       "moe_shared_ms_per_step", "flash_fwd_roofline", "flash_bwd_roofline"]
READERS = {"trace_ops": trace_ops, "kernel_roofline": kernel_roofline,
           "scope_roofline": scope_roofline}
TRIANGLE = SEQ * (SEQ + 1) // 2    # what mfu_pct charges this family: shape()
HALF_SQUARE = SEQ * SEQ // 2     # what flash_work.py charges a whole-row call
D = 2688


def test_nemotron_is_2_355_gflop_a_token_at_the_cut():
    # a Mamba layer forward, FLOPs a token: in_proj to z | xBC | dt, out_proj,
    # four taps over the 6,144 convolved channels, the scan's matmuls
    scan = 8 * 2 * 128 * 128 + 64 * (2 * 128 * 64 + 4 * 128 * 64)
    mamba = 2 * D * 10_304 + 2 * 4096 * D + 2 * 4 * 6144 + scan
    assert (scan, mamba) == (3_407_872, 80_871_424)
    assert nemotron_h.scan_flops_per_token(CONFIG) == scan
    attn = 2 * (2 * D * 4096 + 2 * D * 256)
    scores = 2 * 2 * TRIANGLE * 32 * 128 / SEQ
    shared, expert, router = 2 * 2 * D * 3712, 2 * 2 * D * 1856, 2 * D * 128
    # top_k * 8 / 128 = three eighths of a held expert a token
    held = 6 * 8 * expert / 128
    head = 2 * D * 16_384
    forward = 4 * mamba + attn + scores + 4 * (shared + held + router) + head
    assert forward == pytest.approx(784.9e6, rel=1e-4)
    got = flops.train_flops_per_token(CONFIG, 1, SEQ)
    assert got == pytest.approx(3 * forward, rel=1e-9)
    assert got == pytest.approx(2.355e9, rel=1e-3)
    parts = nemotron_h.forward_flops_per_token(CONFIG)
    assert sum(parts.values()) + head == pytest.approx(forward, rel=1e-12)
    # the shares cut_why states
    for part, share in ((4 * mamba, 0.412), (scores, 0.171), (attn, 0.060),
                        (4 * shared, 0.203), (4 * held, 0.038),
                        (4 * router, 0.004), (head, 0.112),
                        (4 * scan, 0.0174)):
        assert part / forward == pytest.approx(share, abs=0.001)


def test_shape_counts_the_parameter_trees_leaves():
    """Every leaf of the program's own tree at the cut (shapes only: nothing
    is initialised): 667.0M parameters, 10.67 GB of training state, no
    ``gate_proj`` among them; and the count's matmuls are the tree's."""
    import jax

    from ray_tpu.models.llama import LlamaLMModel

    cfg = nemotron_h.model_config(CONFIG, 1)
    tree = jax.eval_shape(
        lambda: LlamaLMModel(cfg).init(jax.random.PRNGKey(0),
                                       jax.numpy.zeros((1, 8), "int32")))
    sizes = {jax.tree_util.keystr(path): int(np.prod(leaf.shape)) for
             path, leaf in jax.tree_util.tree_flatten_with_path(
                 tree["params"])[0]}
    total = sum(sizes.values())
    assert total == nemotron_h.n_params(CONFIG) == 666_963_456
    assert 16 * total == pytest.approx(10.67e9, rel=1e-3)
    assert not any("gate_proj" in k for k in sizes)
    per = nemotron_h.layer_params(CONFIG)
    assert (per["M"], per["*"], per["E"]) == (38_744_896, 23_399_040,
                                              100_125_440)
    experts = sum(n for k, n in sizes.items()
                  if "_proj" in k and "kernel" not in k)
    assert experts == 4 * 8 * 2 * D * 1856
    matmuls = sum(n for k, n in sizes.items() if "kernel']" in k
                  and "lm_head" not in k and "conv" not in k) \
        + experts * 6 // 128
    parts = nemotron_h.forward_flops_per_token(CONFIG)
    assert 2 * matmuls == sum(v for k, v in parts.items()
                              if k not in ("attn_scores", "mamba_conv",
                                           "mamba_scan"))
    s = nemotron_h.shape(CONFIG, 1)
    assert (s["n_layer"], s["n_head"], s["n_kv_head"], s["head_dim"],
            s["vocab"]) == (1, 32, 2, 128, 16384)


def test_kernel_work():
    fwd = flash_work.fwd_call(CONFIG, 1, rows=1, seq=SEQ)
    assert fwd["flops"] == 2 * 2 * 32 * HALF_SQUARE * 128
    # Q, O at 32 heads and K, V at 2, bf16
    assert fwd["bytes"] == 2 * SEQ * 128 * (32 + 32 + 2 + 2)
    assert flops.roofline_seconds(fwd, PEAK)[1] == "compute"
    bwd = flash_work.bwd_call(CONFIG, 1, rows=1, seq=SEQ)
    assert bwd["flops"] == 5 * 2 * 32 * HALF_SQUARE * 128
    assert bwd["bytes"] == 2 * SEQ * 128 * (3 * 32 + 4 * 2)
    scan = ssd_work.scan_step(CONFIG, 1, rows=1, seq=SEQ)
    assert scan["flops"] == 3 * 4 * SEQ * 3_407_872
    # X, y 4096 wide, B, C 1024 wide, dt 64, bf16; a float32 state of 64 x 64
    # x 128 a chunk of 128, written and read
    assert scan["bytes"] == 3 * 4 * (
        2 * SEQ * (2 * 4096 + 2 * 1024 + 64) + 2 * 4 * 128 * 64 * 64 * 128)
    # the states of chunks half as long as Granite's are most of the bytes
    assert flops.roofline_seconds(scan, PEAK)[1] == "memory"


def _ctx():
    """Two steps on one device: a Mamba layer, an expert layer and the
    attention layer, forward and backward, as the chip's trace names them."""
    ops, t = [], 0.0
    stack = "jit(pretrain_step)/jvp(LlamaLMModel)/"
    back = "jit(pretrain_step)/transpose(jvp(LlamaLMModel))/" \
        "jvp(LlamaLMModel)/checkpoint/"
    call, fusion = "custom-call:tpu_custom_call", "fusion"
    for step in range(2):
        for i, (kind, path, secs) in enumerate([
                (fusion, stack + "h_0/mamba/in_proj/dot_general", 8e-3),
                (call, stack + "h_0/mamba/conv/conv_silu_fwd/pallas_call", 1e-3),
                (call, stack + "h_0/mamba/ssd/ssd_fwd/pallas_call", 10e-3),
                (call, back + "h_0/mamba/ssd/ssd_bwd/pallas_call", 20e-3),
                (fusion, stack + "h_0/mamba/gated_norm/mul", 1e-3),
                (fusion, back + "h_0/mamba/out_proj/dot_general", 4e-3),
                (fusion, stack + "h_1/moe/router/router/dot_general", 4e-4),
                (call, stack + "h_1/moe/while/body/h_1/moe/experts/pallas_call", 3e-3),
                (fusion, back + "h_1/moe/while/body/h_1/moe/experts/mul", 1e-3),
                (fusion, stack + "h_1/moe/shared/up_proj/dot_general", 6e-3),
                (fusion, back + "h_1/moe/shared/down_proj/dot_general", 9e-3),
                (call, stack + "h_5/attn/flash_fwd/flash_fwd/pallas_call", 30e-3),
                (call, back + "h_5/attn/flash_bwd/flash_bwd/pallas_call", 80e-3),
                (fusion, back + "h_5/attn/flash_bwd/reduce_sum", 2e-3)]):
            ops.append(Op(f"op.{step}.{i}", kind, path, t, t + secs))
            t += secs
    trace = Trace(ops={0: ops}, spans=[("window", 0.0, t)])
    return Context(CELL, PEAK, {}, trace, traced_steps=2), t


def _metric(name):
    with open(os.path.join(manifest.BENCH_DIR, "layer_metrics",
                           name + ".json")) as f:
        return json.load(f)


def _read(ctx, name):
    metric = _metric(name)
    return READERS[metric["reader"]].read(ctx, **metric["args"])


def test_the_new_metrics_on_a_synthetic_trace():
    ctx, busy = _ctx()
    got = {name: _read(ctx, name) for name in NEW}
    assert got["mamba_scope_share_pct"] == pytest.approx(
        100 * 2 * 44e-3 / busy)
    assert got["ssd_scan_ms_per_step"] == pytest.approx(30.0)
    scan = ssd_work.scan_step(CONFIG, 1, 1, SEQ)
    assert got["ssd_scan_roofline"] == pytest.approx(
        100 * (scan["bytes"] / PEAK["hbm_bytes_per_s"]) / 30e-3)
    assert got["mamba_proj_ms_per_step"] == pytest.approx(12.0)
    assert got["grouped_gated_norm_ms_per_step"] == pytest.approx(1.0)
    assert got["moe_experts_ms_per_step"] == pytest.approx(4.0)
    assert got["moe_shared_ms_per_step"] == pytest.approx(15.0)
    # the kernel call, not the sum of dK beside the backward kernel
    least = {fn: getattr(flash_work, fn)(CONFIG, 1, 1, SEQ)["flops"]
             / 197e12 for fn in ("fwd_call", "bwd_call")}
    assert got["flash_fwd_roofline"] == pytest.approx(
        100 * least["fwd_call"] / 30e-3)
    assert got["flash_bwd_roofline"] == pytest.approx(
        100 * least["bwd_call"] / 80e-3)
    # no roofline over 100% at these times, which are about the chip's
    assert all(0 < v <= 100 for k, v in got.items() if k.endswith("roofline"))


def test_a_program_without_the_scopes_reports_nothing():
    """A program with no Mamba layer, no routed layer and no attention scope
    (the parent under a trace of another cell): the readers give None and do
    not raise."""
    path = "jit(pretrain_step)/jvp(GPT2LMModel)/h_0/mlp/dot_general"
    trace = Trace(ops={0: [Op("op", "fusion", path, 0.0, 1e-3)]},
                  spans=[("window", 0.0, 1e-3)])
    ctx = Context(CELL, PEAK, {}, trace, traced_steps=1)
    for name in NEW:
        assert _read(ctx, name) is None, name


def test_the_cell_as_the_manifest_has_it():
    bench = manifest.benchmark()
    entry = next(c for c in bench["configs"]
                 if c["name"] == "nemotron-3-nano-30b-a3b")
    assert entry["reduced"] == CONFIG["reduced"] == [
        "num_hidden_layers", "hybrid_override_pattern", "n_routed_experts",
        "vocab_size"]
    assert entry["file"] == "perfbench/configs/nemotron-3-nano-30b-a3b.json"
    assert CELL.chips == 1 and CELL.traffic["kind"] == "train_loop"
    assert (CELL.traffic["seq"], CELL.traffic["rows_per_step"]) == (SEQ, 1)
    assert CONFIG["flops_counted_at_seq"] == SEQ
    # the very traffic file of the two Kimi cells, Phi-4-flash's and
    # SmallThinker's
    assert next(w for w in bench["workloads"] if w["name"] == NAME)[
        "traffic"] == next(w for w in bench["workloads"]
                           if w["name"] == "kimi-vl-s16k-1chip")["traffic"]
    assert NAME in [w["name"] for w in bench["workloads"]]
    # by name, and at least these: later PRs list the cell under more
    ours = on_at_least(bench, NAME, NEW)
    assert set(NEW) <= {m["name"] for m in CELL.per_layer}
    assert all(m["moves"] == "tokens_per_s_per_chip" for m in ours)
    assert CONFIG["published_counts"]["n_routed_experts"] == 128
    assert nemotron_h.held(CONFIG) == (0, 8)
    assert nemotron_h.pattern(CONFIG) == "MEMEM*EME"
    assert CONFIG["published_counts"]["hybrid_override_pattern"].startswith(
        CONFIG["hybrid_override_pattern"])
    assert CONFIG["deployment"]["chips_sharing_a_layer"] == 16
    for key in ("cut_why", "assumed", "program_departures", "dtypes",
                "reference"):
        assert CONFIG[key]
    for limit in ("logits_rel_rms_max", "loss_rel_max", "grad_norm_rel_max"):
        assert 0 < CONFIG["reference"][limit] < 1


def test_every_catalog_number_stands_in_the_file():
    path = "/opt/skills/guides/model-configs/architectures.jsonl"
    if not os.path.exists(path):
        pytest.skip("the catalog is not on this machine")
    with open(path) as f:
        row = next(r for r in map(json.loads, f)
                   if r["name"] == "NVIDIA-Nemotron-3-Nano-30B-A3B-BF16")
    assert row["source_url"] == CONFIG["source"]
    for key, value in row["config"].items():
        if key not in CONFIG["reduced"]:
            assert CONFIG[key] == value, key
        else:
            assert CONFIG["published_counts"][key] == value
    # the widths: none is cut
    assert (CONFIG["hidden_size"], CONFIG["mamba_num_heads"],
            CONFIG["mamba_head_dim"], CONFIG["ssm_state_size"],
            CONFIG["n_groups"], CONFIG["num_attention_heads"],
            CONFIG["num_key_value_heads"], CONFIG["head_dim"],
            CONFIG["moe_intermediate_size"],
            CONFIG["moe_shared_expert_intermediate_size"],
            CONFIG["num_experts_per_tok"], CONFIG["routed_scaling_factor"]
            ) == (2688, 64, 64, 128, 8, 32, 2, 128, 1856, 3712, 6, 2.5)

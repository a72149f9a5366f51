"""Partition rules: map parameter names to PartitionSpecs over the mesh.

Regex-rule matching in the t5x/EasyLM style (public pattern; see SNIPPETS.md [3]
for the shape of the idea): each rule is (name_regex, PartitionSpec); the first
match wins; scalars are replicated.  This is the TP/FSDP machinery the reference
delegates to DeepSpeed/Accelerate (SURVEY §2.3 'TP: absent from Ray itself') —
here it is first-class and compiler-driven (GSPMD inserts the collectives).
"""

from __future__ import annotations

import re
from typing import Any, List, Optional, Sequence, Tuple

import numpy as np


def _spec(*axes):
    from jax.sharding import PartitionSpec

    return PartitionSpec(*axes)


class PartitionRules:
    def __init__(self, rules: Sequence[Tuple[str, Any]]):
        self.rules = list(rules)

    def spec_for(self, path: str, shape: Tuple[int, ...]):
        from jax.sharding import PartitionSpec

        if len(shape) == 0 or int(np.prod(shape)) == 1:
            return PartitionSpec()
        for pattern, spec in self.rules:
            if re.search(pattern, path):
                return spec
        return PartitionSpec()  # replicate by default


def gpt_partition_rules() -> PartitionRules:
    """Megatron-style TP + FSDP sharding for the GPT family (ray_tpu.models.gpt2).

    Weight matrices split on 'tp'; the remaining big dimension is sharded over
    'fsdp' so parameters also scale with the fsdp axis (ZeRO-3-like).  XLA turns
    these into all-gather on use + reduce-scatter on grad, over ICI.
    """
    return PartitionRules([
        # embeddings: (vocab, embed) — vocab on tp, embed on fsdp
        (r"wte/embedding", _spec("tp", "fsdp")),
        (r"wpe/embedding", _spec(None, "fsdp")),
        # attention qkv: (embed, heads*head_dim) — split heads over tp
        (r"attn/(q|k|v|qkv)_proj/kernel", _spec("fsdp", "tp")),
        (r"attn/out_proj/kernel", _spec("tp", "fsdp")),
        # mlp: (embed, 4*embed) in, (4*embed, embed) out
        (r"mlp/fc_in/kernel", _spec("fsdp", "tp")),
        (r"mlp/fc_out/kernel", _spec("tp", "fsdp")),
        # biases/layernorms replicated
        (r"bias|scale|ln", _spec()),
        # lm head (embed, vocab)
        (r"lm_head/kernel", _spec("fsdp", "tp")),
        # MoE experts: leading expert dim over ep (models/moe.py)
        (r"router/kernel", _spec()),
        (r"moe_mlp/w_in", _spec("ep", "fsdp", "tp")),
        (r"moe_mlp/w_out", _spec("ep", "tp", "fsdp")),
    ])


def llama_partition_rules() -> PartitionRules:
    """Megatron-style TP + FSDP sharding for the Llama family
    (ray_tpu.models.llama): same recipe as gpt_partition_rules, names
    matched to the RoPE/RMSNorm/SwiGLU module layout."""
    return PartitionRules([
        # hyper-connections (HyperConnection as h_<n>/hc_attn, hc_mlp; first,
        # so that no rule for ``attn/`` takes them): the coefficients'
        # projection is 2 n + n^2 columns, whole on every device
        (r"hc_(attn|mlp)/", _spec()),
        (r"wte/embedding", _spec("tp", "fsdp")),
        (r"attn/(wq|wk|wv)/kernel", _spec("fsdp", "tp")),
        # the gate on the attention's output: a column a query head
        (r"attn/wg/kernel", _spec("fsdp", "tp")),
        (r"attn/wo/kernel", _spec("tp", "fsdp")),
        # EVA's pooling vectors, (heads, head_dim): a row a head
        (r"attn/(phi|mu)$", _spec("tp", None)),
        # latent attention: the down-projection to the latent and the shared
        # rotary key belongs to no head; the up-projection's columns are the
        # heads' (kv_norm's scale: replicated, below)
        (r"attn/wdkv/kernel", _spec("fsdp", None)),
        (r"attn/wukv/kernel", _spec("fsdp", "tp")),
        # ... and the query's latent likewise (q_norm's scale: replicated)
        (r"attn/wq_a/kernel", _spec("fsdp", None)),
        (r"attn/wq_b/kernel", _spec("fsdp", "tp")),
        # a prediction module's projection of [h ; emb] (mtp_<k>/proj)
        (r"mtp_\d+/proj/kernel", _spec("fsdp", "tp")),
        (r"mlp/(gate_proj|up_proj)/kernel", _spec("fsdp", "tp")),
        (r"mlp/down_proj/kernel", _spec("tp", "fsdp")),
        # routed layers (models/moe.py::RoutedSwiGLU as h_<n>/moe): the
        # router replicated; expert arrays (E, ., .) over ep, then as the
        # dense MLP's
        (r"moe/router/kernel", _spec()),
        # the bias an expert of the selection: state, 'n_experts' wide
        (r"moe/selection_bias", _spec()),
        # the expert every token passes, as the dense MLP
        (r"moe/shared/(gate_proj|up_proj)/kernel", _spec("fsdp", "tp")),
        (r"moe/shared/down_proj/kernel", _spec("tp", "fsdp")),
        (r"moe/shared/gate/kernel", _spec("fsdp", None)),
        (r"moe/(gate_proj|up_proj)", _spec("ep", "fsdp", "tp")),
        (r"moe/down_proj", _spec("ep", "tp", "fsdp")),
        # mamba layers (models/mamba.py::Mamba2Mixer as h_<n>/mamba): the two
        # projections as the attention's; the convolution's kernel and bias
        # and the per-head dt_bias, A_log and D are small and replicated
        (r"mamba/in_proj/kernel", _spec("fsdp", "tp")),
        (r"mamba/out_proj/kernel", _spec("tp", "fsdp")),
        (r"mamba/(conv_kernel|conv_bias|dt_bias|A_log|D)$", _spec()),
        # short-convolution layers (models/llama.py::ShortConvMixer as
        # h_<n>/conv): in_proj's kernel is (embed, 3, embed), its three parts
        # B, C and u each cut by channel, and so is the depthwise kernel: the
        # pass between the projections needs no collective under tp
        (r"conv/in_proj/kernel", _spec("fsdp", None, "tp")),
        (r"conv/out_proj/kernel", _spec("tp", "fsdp")),
        (r"conv/conv_kernel", _spec(None, "tp")),
        # Kimi Delta Attention layers (models/kda.py::KDAMixer as h_<n>/kda):
        # what lies between the projections is a head's own, so the three
        # projections, the up side of the two low-rank maps (and g_b's bias),
        # the write strengths, the convolutions' kernels, A_log and dt_bias
        # are cut by head and the scan needs no collective under tp; the
        # low-rank maps' down side belongs to no head (o_norm's scale, one for
        # all heads: replicated, below)
        (r"kda/(q_proj|k_proj|v_proj|b_proj)/kernel", _spec("fsdp", "tp")),
        (r"kda/(f_a|g_a)/kernel", _spec("fsdp", None)),
        (r"kda/(f_b|g_b)/kernel", _spec(None, "tp")),
        (r"kda/g_b/bias", _spec("tp")),
        (r"kda/o_proj/kernel", _spec("tp", "fsdp")),
        (r"kda/(q|k|v)_conv", _spec(None, "tp")),
        (r"kda/(A_log|dt_bias)$", _spec("tp")),
        # Gated DeltaNet layers (models/gdn.py::GDNMixer as h_<n>/gdn): the two
        # input projections and the convolution's kernel hold their columns a
        # key head at a time, (in, key heads, columns a key head), and are cut
        # between key heads, each with its value heads; A_log and dt_bias are
        # a value head's, in the key heads' order; the scan needs no
        # collective under tp (a tp that does not divide the key heads is
        # refused by the mixer)
        (r"gdn/(in_proj_qkvz|in_proj_ba)/kernel", _spec("fsdp", "tp", None)),
        (r"gdn/conv_kernel", _spec(None, "tp", None)),
        (r"gdn/out_proj/kernel", _spec("tp", "fsdp")),
        (r"gdn/(A_log|dt_bias)$", _spec("tp")),
        # Mamba-1 layers (models/mamba.py::Mamba1Mixer as h_<n>/mamba1): what
        # lies between in_proj and out_proj is a channel's own — in_proj's
        # kernel is (embed, 2, channels), u and z each cut by channel, and so
        # are the convolution, the step sizes' up-projection and its bias, A
        # (channels, states) and D — but for B and C, which every channel
        # shares: x_proj is row-parallel and its output whole on every device
        (r"mamba1/in_proj/kernel", _spec("fsdp", None, "tp")),
        (r"mamba1/x_proj/kernel", _spec("tp", None)),
        (r"mamba1/dt_proj/kernel", _spec(None, "tp")),
        (r"mamba1/out_proj/kernel", _spec("tp", "fsdp")),
        (r"mamba1/conv_kernel", _spec(None, "tp")),
        (r"mamba1/(conv_bias|dt_bias|D)$", _spec("tp")),
        (r"mamba1/A_log", _spec("tp", None)),
        # gated memory units (GatedMemoryUnit as h_<n>/gmu): the gate by
        # channel, as the scan output it multiplies is cut
        (r"gmu/in_proj/kernel", _spec("fsdp", "tp")),
        (r"gmu/out_proj/kernel", _spec("tp", "fsdp")),
        # differential attention (models/llama.py::DifferentialAttention):
        # wq and wo as the attention's above; wqkv's columns are the query
        # heads, then the key heads, then the values', which no one cut of
        # the columns keeps apart: over fsdp alone (lambda_*: replicated)
        (r"attn/wqkv/kernel", _spec("fsdp", None)),
        # attn_norm, mlp_norm, norm_f, the q_norm / k_norm scales and the
        # mixer's norm_scale
        (r"norm|scale", _spec()),
        (r"lm_head/kernel", _spec("fsdp", "tp")),
    ])


def _flatten_with_paths(tree):
    import jax

    flat, treedef = jax.tree_util.tree_flatten_with_path(tree)
    out = []
    for path, leaf in flat:
        name = "/".join(
            str(getattr(k, "key", getattr(k, "idx", getattr(k, "name", k))))
            for k in path)
        out.append((name, leaf))
    return out, treedef


def match_partition_rules(rules, params):
    """Pytree of params → pytree of PartitionSpec.  ``rules`` is a
    PartitionRules or a raw ``[(regex, PartitionSpec), ...]`` sequence."""
    import jax

    if not isinstance(rules, PartitionRules):
        rules = PartitionRules(rules)
    flat, treedef = _flatten_with_paths(params)
    specs = [rules.spec_for(name, getattr(leaf, "shape", ())) for name, leaf in flat]
    return jax.tree_util.tree_unflatten(treedef, specs)


def host_to_global(x, sharding):
    """Host value -> global jax.Array under ``sharding``.

    Single-process meshes take the plain ``device_put`` path.  When the
    sharding spans processes, ``device_put`` of a host value is not a
    supported multi-controller transfer (on the CPU/gloo backend it issues
    mismatched point-to-point ops that abort the whole gang); the supported
    construction is per-process assembly from addressable shards.  Every
    caller here holds the SAME full host value on every process (seeded init,
    seeded batches), so each process can slice its own shards locally and no
    bytes cross the wire.
    """
    import jax

    if all(d.process_index == jax.process_index()
           for d in sharding.device_set):
        return jax.device_put(x, sharding)
    arr = np.asarray(x)
    return jax.make_array_from_callback(
        arr.shape, sharding, lambda idx: arr[idx])


def shard_pytree(params, specs, mesh):
    """Device-put a pytree with NamedShardings built from specs (multi-
    process safe: see host_to_global)."""
    import jax
    from jax.sharding import NamedSharding

    return jax.tree_util.tree_map(
        lambda x, s: host_to_global(x, NamedSharding(mesh, s)), params, specs)


def constrain_residual(x, channels=None):
    """Pin a (batch, seq, embed) activation to the batch's own layout,
    ``P(("dp", "fsdp"), "sp", None)``, under the ambient mesh
    (``jax.set_mesh``); identity without one.  ``channels``: the mesh axis
    the last dimension is cut over, where the mesh has it (``"tp"`` for what
    lies between a column- and a row-parallel projection).

    The model stacks call this on the residual stream.  Without it GSPMD
    follows the fsdp-sharded *weights* and replicates the batch's compute
    over ``fsdp`` — parameters stored in quarters, every chip doing the whole
    batch.  With it the weights are all-gathered on use and the activations
    stay split, which is what the axis is for.
    """
    import jax

    from ray_tpu.parallel.mesh import ambient_mesh

    mesh = ambient_mesh()
    if mesh is None or mesh.size == 1:
        return x
    batch = tuple(a for a in ("dp", "fsdp") if a in mesh.shape) or None
    seq = "sp" if "sp" in mesh.shape else None
    if channels not in mesh.shape:
        channels = None
    return jax.lax.with_sharding_constraint(x, _spec(batch, seq, channels))

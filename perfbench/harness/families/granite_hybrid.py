"""Granite 4.0-H (``ibm-granite/granite-4.0-h-micro``, ``model_type``
``granitemoehybrid``): Mamba-2 layers (Dao and Gu 2024, "Transformers are
SSMs") beside grouped-query attention layers without position encoding, a
dense SwiGLU in every layer, a tied head, and three constant multipliers.  The
program's side is ``ray_tpu/models/llama.py`` with ``layer_types`` and
``models/mamba.py`` over ``ops/ssd.py``'s chunked scan.  ``layer_types`` says
which layer is which.  With ``n = RMSNorm(x)``, a ``mamba`` layer is

    z | xBC | dt = W_in n                      (4096 | 4096 + 2*128 | 64 columns)
    xBC = silu(causal depthwise conv1d(xBC, width 4) + b)
    X | B | C = xBC                            (X: 64 heads x 64; B, C: 128, shared by all heads)
    dt = softplus(dt + dt_bias);  a_t = exp(dt_t * A),  A = -exp(A_log)   (one scalar a head)
    h_t = a_t h_{t-1} + dt_t X_t B_t^T         (a 64 x 128 state a head)
    y_t = h_t C_t + D X_t
    m = W_out RMSNorm_w(y * silu(z))           (the norm over all 4096, after the gate)
    x <- x + 0.22 m;   x <- x + 0.22 SwiGLU(RMSNorm(x))

and an ``attention`` layer is the same block with ``m = W_o Attn(Q, K, V)``:
32 query heads over 8 key/value heads, no rotation, the scores times
``attention_multiplier`` = 1/64 where 1/sqrt(64) would be 1/8.  The embedding
is multiplied by 12 and the logits are ``norm_f(x) E^T / 8`` with ``E`` the
embedding table itself.

Plain on purpose: the recurrence is a ``lax.scan`` over single positions that
carries ``h_t`` — no chunks, no masks, no running sums —, the convolution is
four shifted multiply-adds, the attention is a full masked softmax.  One
thing is not mathematics: each Mamba layer's recurrence is under
``jax.checkpoint``, because its backward pass keeps the state of every
position (2 MB a position a layer at published widths) and the chip holds the
program's training state beside it; recomputation in the same precision
changes no value.  Where the program departs from the published code
(``program_departures`` in the configuration file: the initialisers) this
follows the program.

**The FLOP count.**  ``flops.train_flops_per_token`` is
``6 * (n_layer * layer_mm_params + d_model * vocab) + 6 * n_layer * seq *
d_model``: its second term charges causal attention to every layer, and a
``mamba`` layer has none.  ``shape`` therefore hands it ``n_layer`` = the
number of ``attention`` layers of the cut and ``layer_mm_params`` = the
matmul parameters of *all* layers of the cut divided by that number, so that
the first term is what it should be and the second charges attention only
where there is attention.  The matmul parameters of a Mamba layer include its
depthwise convolution (4 multiply-adds a channel a token: the kernel's 4 x
4352 entries) and the recurrence as equivalent parameters, operations a token
forward over two: ``2*Q*N`` a group for ``C B^T`` inside a chunk of ``Q``
positions, ``2*Q*P`` a head for the masked product with ``X``, ``2*N*P`` a
head each for the chunk's state and its read-out — 4,259,840 FLOPs at the
published sizes, 2,129,920 equivalent parameters — counted as the chunked
algorithm's dense matmuls, as ``harness/ssd_work.py`` counts them for the
scan's roofline.  The depth the program runs is
``published(config, chips, "num_hidden_layers")``, not ``shape``'s
``n_layer``.
"""

from __future__ import annotations

from typing import Any, Dict

from perfbench.harness import ssd_work
from perfbench.harness.families import published


def _sizes(config: Dict[str, Any]):
    heads, p = config["mamba_n_heads"], config["mamba_d_head"]
    assert heads * p == config["mamba_expand"] * config["hidden_size"]
    return heads, p, config["mamba_n_groups"], config["mamba_d_state"]


def scan_flops_per_token(config: Dict[str, Any]) -> int:
    """One Mamba layer's recurrence, forward, one token, as the chunked
    algorithm's matmuls (see the module's docstring)."""
    return ssd_work.scan_flops_per_token(*_sizes(config),
                                         config["mamba_chunk_size"])


def shape(config: Dict[str, Any], chips: int) -> Dict[str, int]:
    d, h = config["hidden_size"], config["num_attention_heads"]
    kv, ff = config["num_key_value_heads"], config["shared_intermediate_size"]
    hd = d // h
    heads, p, groups, n = _sizes(config)
    inner = heads * p
    kinds = published(config, chips, "layer_types")
    assert len(kinds) == published(config, chips, "num_hidden_layers")
    attention = sum(1 for kind in kinds if kind == "attention")
    mlp = 3 * d * ff
    mamba = (d * (2 * inner + 2 * groups * n + heads)       # in_proj
             + config["mamba_d_conv"] * (inner + 2 * groups * n)
             + scan_flops_per_token(config) // 2
             + inner * d)                                    # out_proj
    total = (attention * (2 * d * h * hd + 2 * d * kv * hd + mlp)
             + (len(kinds) - attention) * (mamba + mlp))
    assert attention > 0 and total % attention == 0
    return {"d_model": d, "n_layer": attention, "n_head": h, "n_kv_head": kv,
            "head_dim": hd, "vocab": config["vocab_size"],
            "layer_mm_params": total // attention,
            # the scan's own sizes, for ``ssd_work.py``
            "ssd_heads": heads, "ssd_head_dim": p, "ssd_groups": groups,
            "ssd_state": n, "ssd_chunk": config["mamba_chunk_size"],
            "ssd_layers": len(kinds) - attention}


def model_config(config: Dict[str, Any], chips: int):
    """Activations bf16, parameters float32, flash attention, the scan's
    decays float32: the program's defaults, stated in the configuration
    file."""
    from ray_tpu.models.llama import LlamaConfig

    remat = config["remat"]
    heads, p, groups, n = _sizes(config)
    # the program's mixer has the convolution's bias and no other
    assert config["mamba_conv_bias"] and not config["mamba_proj_bias"]
    return LlamaConfig(
        vocab_size=config["vocab_size"],
        n_positions=config["max_position_embeddings"],
        d_model=config["hidden_size"],
        n_layer=published(config, chips, "num_hidden_layers"),
        layer_types=tuple(published(config, chips, "layer_types")),
        n_head=config["num_attention_heads"],
        n_kv_head=config["num_key_value_heads"],
        d_ff=config["shared_intermediate_size"],
        rope=config["position_embedding_type"] == "rope",
        rope_theta=float(config["rope_theta"]),
        attn_scale=float(config["attention_multiplier"]),
        rms_eps=float(config["rms_norm_eps"]), remat=bool(remat),
        remat_policy=remat or "full",
        mamba_n_heads=heads, mamba_d_head=p, mamba_n_groups=groups,
        mamba_d_state=n, mamba_d_conv=config["mamba_d_conv"],
        mamba_chunk=config["mamba_chunk_size"],
        embedding_multiplier=float(config["embedding_multiplier"]),
        residual_multiplier=float(config["residual_multiplier"]),
        logits_scaling=float(config["logits_scaling"]),
        tie_embeddings=bool(config["tie_word_embeddings"]))


def _delayed(x, k: int):
    """``x`` (B, S, C) ``k`` positions later, zeros moving in."""
    import jax.numpy as jnp

    return x if k == 0 else jnp.pad(x, ((0, 0), (k, 0), (0, 0)))[:, :-k]


def _mamba(p, n, config: Dict[str, Any], gate_after_norm: bool, decay_dtype):
    import jax
    import jax.numpy as jnp

    from perfbench.harness.reference import dense, rms_norm

    heads, width, groups, state = _sizes(config)
    inner, bc = heads * width, groups * state
    z, xbc, dt = jnp.split(dense(n, p["in_proj"]),
                           [inner, 2 * inner + 2 * bc], axis=-1)
    taps = config["mamba_d_conv"]
    conv = sum(p["conv_kernel"][taps - 1 - k] * _delayed(xbc, k)
               for k in range(taps))
    x, b, c = jnp.split(jax.nn.silu(conv + p["conv_bias"]),
                        [inner, inner + bc], axis=-1)
    batch, seq, _ = x.shape
    x = x.reshape(batch, seq, heads, width)
    # B and C of a head's group, for every head
    b, c = (jnp.repeat(t.reshape(batch, seq, groups, state), heads // groups,
                       axis=2) for t in (b, c))
    dt = jax.nn.softplus(dt + p["dt_bias"])                 # (B, S, heads)
    log_a = dt * -jnp.exp(p["A_log"])
    if decay_dtype is not None:
        log_a = _through_running_sums(log_a, decay_dtype,
                                      config["mamba_chunk_size"])

    @jax.checkpoint
    def recurrence(x, dt, log_a, b, c):
        def step(h, at):
            x_t, dt_t, log_a_t, b_t, c_t = at
            h = jnp.exp(log_a_t)[..., None, None] * h \
                + (dt_t[..., None] * x_t)[..., None] * b_t[:, :, None, :]
            return h, jnp.einsum("bhpn,bhn->bhp", h, c_t)

        _, y = jax.lax.scan(
            step, jnp.zeros((batch, heads, width, state), x.dtype),
            tuple(jnp.moveaxis(t, 1, 0) for t in (x, dt, log_a, b, c)))
        return jnp.moveaxis(y, 0, 1)

    y = recurrence(x, dt, log_a, b, c) + p["D"][:, None] * x
    y, gate = y.reshape(batch, seq, inner), jax.nn.silu(z)
    scale = {"scale": p["norm_scale"]}
    if gate_after_norm:     # a wrong model, for the controls
        y = rms_norm(y, scale, config["rms_norm_eps"]) * gate
    else:
        y = rms_norm(y * gate, scale, config["rms_norm_eps"])
    return dense(y, p["out_proj"])


def _through_running_sums(log_a, dtype, chunk: int):
    """A wrong model, for the controls: each step's log-decay as the
    difference of running sums kept in ``dtype`` inside chunks of ``chunk``
    positions — what a chunked scan computes if its sums are not float32."""
    import jax.numpy as jnp

    batch, seq, heads = log_a.shape
    pad = -seq % chunk
    sums = jnp.cumsum(
        jnp.pad(log_a, ((0, 0), (0, pad), (0, 0))).reshape(
            batch, -1, chunk, heads).astype(dtype), axis=2).astype(log_a.dtype)
    steps = jnp.diff(sums, axis=2, prepend=jnp.zeros_like(sums[:, :, :1]))
    return steps.reshape(batch, seq + pad, heads)[:, :seq]


def _attention(p, n, config: Dict[str, Any]):
    import jax
    import jax.numpy as jnp

    from perfbench.harness.reference import (NEG, dense, heads, merge, rope)

    h, kv = config["num_attention_heads"], config["num_key_value_heads"]
    q, k, v = (heads(dense(n, p[name]), count) for name, count in
               (("wq", h), ("wk", kv), ("wv", kv)))
    if config["position_embedding_type"] == "rope":
        q, k = (rope(t, float(config["rope_theta"])) for t in (q, k))
    batch, _, seq, width = q.shape
    # query head j reads key/value head j // (h // kv)
    q = q.reshape(batch, kv, h // kv, seq, width)
    scores = jnp.einsum("bgrqd,bgkd->bgrqk", q, k) \
        * config["attention_multiplier"]
    mask = jnp.arange(seq)[:, None] >= jnp.arange(seq)[None, :]
    weights = jax.nn.softmax(jnp.where(mask, scores, NEG), axis=-1)
    return dense(merge(jnp.einsum("bgrqk,bgkd->bgrqd", weights, v)), p["wo"])


def logits(params, ids, config: Dict[str, Any], *,
           gate_after_norm: bool = False, decay_dtype=None):
    """The two keywords make wrong models, for the controls (tests and
    ``perfbench/tests/granite_on_chip.py``); the published model has
    neither."""
    import jax

    from perfbench.harness.reference import dense, rms_norm

    eps, mult = config["rms_norm_eps"], config["residual_multiplier"]
    n_layer = sum(1 for name in params if name.startswith("h_"))
    x = params["wte"]["embedding"][ids] * config["embedding_multiplier"]
    # the cut keeps the first layers of the published list
    for i, kind in enumerate(config["layer_types"][:n_layer]):
        p = params[f"h_{i}"]
        n = rms_norm(x, p["attn_norm"], eps)
        if kind == "mamba":
            m = _mamba(p["mamba"], n, config, gate_after_norm, decay_dtype)
        else:
            m = _attention(p["attn"], n, config)
        x = x + mult * m
        n = rms_norm(x, p["mlp_norm"], eps)
        x = x + mult * dense(
            jax.nn.silu(dense(n, p["mlp"]["gate_proj"]))
            * dense(n, p["mlp"]["up_proj"]), p["mlp"]["down_proj"])
    x = rms_norm(x, params["norm_f"], eps)
    return (x @ params["wte"]["embedding"].T / config["logits_scaling"])[
        ..., : config["vocab_size"]]

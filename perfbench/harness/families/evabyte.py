"""EvaByte (``EvaByte/EvaByte``, ``model_type: evabyte``, ``attention_class:
eva``): a byte-level decoder whose attention is exact inside aligned windows
and goes through learned-pooled chunk summaries before them, with eight
next-byte heads, a float32 residual stream and unit-offset norms.  The
program's side is ``ray_tpu/models/llama.py`` with ``eva_window``,
``eva_chunk``, ``norm_unit_offset``, ``residual_dtype``, ``logits_dtype`` and
``n_pred_heads`` over ``ops/attention.py``'s ``eva_mask`` kernels, and
``models/gpt2.py::shifted_heads_loss``.

Per layer, ``N(x) = x / rms(x) * (1 + g)`` with eps 1e-5, no bias anywhere, 32
heads 128 wide (no grouping), ``s = 1 / sqrt(128)``, window ``W`` = 2048,
chunk ``c`` = 16:

    q, k, v   = heads_32(Wq N(x)), heads_32(Wk N(x)), heads_32(Wv N(x));  q, k <- R(q), R(k)   RoPE theta 1e5, whole head
    pooling   chunk j = positions [c j, c j + c):   a_m = softmax_{m in chunk j}(s * phi_h . k_m)
              kt_j = sum_m a_m k_m + mu_h           vt_j = sum_m a_m v_m
    attention query i of window w = i // W sees   L_i = {m : w W <= m <= i}   and   R_i = {j : j < w W / c}
              o_i = softmax over [s q_i . kt_j, j in R_i ; s q_i . k_m, m in L_i] of [vt_j ; v_m]
    block     h = x + Wo o        y = h + W2 (silu(W1 N'(h)) * W3 N'(h))       both adds in float32
    head      z = Whead N''(y) in R^(8 x 320), float32; head r at position t scores byte t + 1 + r
    loss      the mean over r and the positions t that have such a byte of -log softmax(z_t[r])[byte_(t+1+r)]

Plain on purpose: the pooling as an explicit softmax over a ``(chunks, c,
128)`` view, one dense boolean mask over ``[summaries ; positions]`` built
from the two index rules, a head at a time (checkpointed, so that the
backward holds one head's scores too), float32 at matmul precision
``highest``, nothing of ``ray_tpu``.

**The FLOP count** (``train_flops_per_token``): six a matmul parameter a
token — a layer's four projections and SwiGLU, the head's ``8 x 320``
columns — and ``12 * layers * hidden`` a live (query, key) pair a head,
the pairs counted by ``live_pairs``; the pooling is no matmul and is not
counted, and neither is recomputation.
"""

from __future__ import annotations

from typing import Any, Dict, Optional

WRONG = ("no_mu", "mean_pooling", "no_pool_scale", "own_window_summaries",
         "chunkwise_summaries", "window_halved", "chunk_doubled",
         "no_unit_offset", "heads_next_byte", "no_rope", "bf16_residual")
# Of those, what the comparison on the chip cannot see, though the float32
# tests on the CPU do (configs/evabyte.json, reference.why, has the
# readings): four layers' adds rounded to bf16 move the logits from
# 0.0101-0.0110 to 0.0112-0.0118, under any limit that admits the program,
# and the loss and the gradient norm not at all.  (On 4,096 positions of
# independent ids ``no_mu`` and ``heads_next_byte`` were unseen too; on 8,192
# positions of ids held for runs the first reads 0.025-0.029 of the logits —
# the later windows meet two and three tiles of summaries — and the second
# 0.025-0.032 of the gradient norm, since a head's target now depends on how
# far ahead it lies.)
UNSEEN_IN_BF16 = ("bf16_residual",)
# not a wrong model but the right one in the nearest precision below the
# configuration's bf16 activations: every activation that the program holds in
# bf16 rounded to float8 (e4m3) instead.  The limits must refuse it too.
PRECISION_BELOW = "fp8_activations"


def _rounded(wrong: Optional[str]):
    if wrong != PRECISION_BELOW:
        return lambda x: x
    import jax.numpy as jnp

    return lambda x: x.astype(jnp.float8_e4m3fn).astype(jnp.float32)


def head_dim(config: Dict[str, Any]) -> int:
    return config["hidden_size"] // config["num_attention_heads"]


def layer_mm_params(config: Dict[str, Any]) -> int:
    d = config["hidden_size"]
    return 4 * d * d + 3 * d * config["intermediate_size"]


def shape(config: Dict[str, Any], chips: int) -> Dict[str, int]:
    from perfbench.harness.families import published

    return {"d_model": config["hidden_size"],
            "n_layer": published(config, chips, "num_hidden_layers"),
            "n_head": config["num_attention_heads"],
            "n_kv_head": config["num_key_value_heads"],
            "head_dim": head_dim(config),
            "vocab": config["num_pred_heads"] * config["vocab_size"],
            "layer_mm_params": layer_mm_params(config)}


def live_pairs(seq: int, window: int, chunk: int) -> int:
    """(query, key) pairs a head a row of ``seq`` positions: a query's own
    window up to itself, and one summary for every ``chunk`` positions of the
    windows before its own."""
    full, rest = divmod(seq, window)
    own = full * window * (window + 1) // 2 + rest * (rest + 1) // 2
    per = window // chunk
    pooled = sum(window * w * per for w in range(full)) + rest * full * per
    return own + pooled


def train_flops_per_token(config: Dict[str, Any], chips: int,
                          seq: int) -> float:
    s = shape(config, chips)
    pairs = live_pairs(seq, config["window_size"], config["chunk_size"]) / seq
    return 6.0 * (s["n_layer"] * s["layer_mm_params"]
                  + s["d_model"] * s["vocab"]) \
        + 12.0 * s["n_layer"] * s["d_model"] * pairs


def model_config(config: Dict[str, Any], chips: int):
    """Activations bf16, the residual stream and the logits float32,
    parameters float32, flash attention: stated in the configuration file's
    ``dtypes``."""
    import jax.numpy as jnp

    from perfbench.harness.families import published
    from ray_tpu.models.llama import LlamaConfig

    assert config["attention_class"] == "eva" and not config["attention_bias"]
    assert config["rope_scaling"] is None and config["num_chunks"] is None
    assert not config["tie_word_embeddings"]
    remat = config["remat"]
    return LlamaConfig(
        vocab_size=config["vocab_size"],
        n_positions=config["max_position_embeddings"],
        d_model=config["hidden_size"],
        n_layer=published(config, chips, "num_hidden_layers"),
        n_head=config["num_attention_heads"],
        n_kv_head=config["num_key_value_heads"],
        d_ff=config["intermediate_size"],
        rope_theta=float(config["rope_theta"]),
        rms_eps=float(config["rms_norm_eps"]), remat=bool(remat),
        remat_policy=remat or "full",
        eva_window=config["window_size"], eva_chunk=config["chunk_size"],
        norm_unit_offset=bool(config["norm_add_unit_offset"]),
        residual_dtype=jnp.float32 if config["fp32_skip_add"] else None,
        logits_dtype=jnp.float32 if config["fp32_logits"] else None,
        n_pred_heads=config["num_pred_heads"])


# ---------------------------------------------------------------- the layer
def norm(x, p, config: Dict[str, Any], wrong: Optional[str] = None):
    import jax

    offset = 1.0 if config["norm_add_unit_offset"] \
        and wrong != "no_unit_offset" else 0.0
    return x * jax.lax.rsqrt((x * x).mean(-1, keepdims=True)
                             + config["rms_norm_eps"]) * (offset + p["scale"])


def pool(k, v, phi, mu, chunk: int, scale: float,
         wrong: Optional[str] = None):
    """One head's summaries: k, v (S, D) -> (S // chunk, D) each, over the
    whole chunks of the row."""
    import jax
    import jax.numpy as jnp

    n = k.shape[0] // chunk
    kc = k[:n * chunk].reshape(n, chunk, -1)
    vc = v[:n * chunk].reshape(n, chunk, -1)
    if wrong == "mean_pooling":
        phi = jnp.zeros_like(phi)
    if wrong == "no_pool_scale":
        scale = 1.0
    a = jax.nn.softmax(jnp.einsum("jmd,d->jm", kc, phi) * scale, axis=-1)
    kt = jnp.einsum("jm,jmd->jd", a, kc)
    if wrong != "no_mu":
        kt = kt + mu
    return kt, jnp.einsum("jm,jmd->jd", a, vc)


def mask(seq: int, window: int, chunk: int, wrong: Optional[str] = None):
    """(seq, seq // chunk + seq) booleans over ``[summaries ; positions]``:
    the two index rules."""
    import jax.numpy as jnp

    i = jnp.arange(seq)[:, None]
    first = i // window * window        # w W
    j = jnp.arange(seq // chunk)[None, :]
    m = jnp.arange(seq)[None, :]
    own = (m >= first) & (m <= i)
    if wrong == "own_window_summaries":
        summaries = j * chunk < first + window
    elif wrong == "chunkwise_summaries":
        summaries = (j + 1) * chunk <= i
    else:
        summaries = j * chunk < first
    return jnp.concatenate([summaries, own], axis=1)


def eva_head(q, k, v, phi, mu, config: Dict[str, Any],
             wrong: Optional[str] = None):
    """One head of one row: q, k, v (S, D), rotated -> (S, D)."""
    import jax
    import jax.numpy as jnp

    from perfbench.harness.reference import NEG

    r = _rounded(wrong)
    window, chunk = config["window_size"], config["chunk_size"]
    if wrong == "window_halved":
        window //= 2
    if wrong == "chunk_doubled":
        chunk *= 2
    scale = q.shape[-1] ** -0.5
    kt, vt = pool(k, v, phi, mu, chunk, scale, wrong)
    keys = jnp.concatenate([r(kt), k], axis=0)
    values = jnp.concatenate([r(vt), v], axis=0)
    scores = jnp.where(mask(q.shape[0], window, chunk, wrong),
                       (q @ keys.T) * scale, NEG)
    return jax.nn.softmax(scores, axis=-1) @ values


def attention(y, a, config: Dict[str, Any], wrong: Optional[str] = None):
    """One layer's attention on its normed input ``y`` (B, S, hidden),
    through ``Wo``: a head of a row at a time."""
    import jax

    from perfbench.harness.reference import heads, rope

    h, theta = config["num_attention_heads"], float(config["rope_theta"])
    r = _rounded(wrong)
    q, k, v = (heads(y @ a[w]["kernel"], h) for w in ("wq", "wk", "wv"))
    if wrong != "no_rope":
        q, k = rope(q, theta), rope(k, theta)
    q, k, v = r(q), r(k), r(v)
    b, _, s, d = q.shape
    flat = lambda x: x.reshape(b * h, s, d)     # noqa: E731
    tiled = lambda p: jax.numpy.tile(p, (b, 1))     # noqa: E731
    one = jax.checkpoint(
        lambda args: eva_head(*args, config=config, wrong=wrong))
    out = jax.lax.map(one, (flat(q), flat(k), flat(v), tiled(a["phi"]),
                            tiled(a["mu"])))
    out = r(out).reshape(b, h, s, d).transpose(0, 2, 1, 3).reshape(b, s, h * d)
    return out @ a["wo"]["kernel"]


def swiglu(y, m, wrong: Optional[str] = None):
    import jax

    r = _rounded(wrong)
    return r(r(jax.nn.silu(r(y @ m["gate_proj"]["kernel"])))
             * r(y @ m["up_proj"]["kernel"])) @ m["down_proj"]["kernel"]


def _forward(params, ids, config: Dict[str, Any],
             wrong: Optional[str] = None):
    """``ids`` (B, S) -> logits over the columns the head has, padding
    included."""
    import jax
    import jax.numpy as jnp

    r = _rounded(wrong)

    def add(x, branch):
        x = x + r(branch)       # the stream itself is float32
        if wrong == "bf16_residual":
            x = x.astype(jnp.bfloat16).astype(jnp.float32)
        return x

    # (a layer is made again in the backward pass from its input, so that
    # the backward holds one layer's float32 activations and not four: the
    # same numbers, and a prefix of four windows fits beside the program's
    # training state)
    @jax.checkpoint
    def layer(x, p):
        x = add(x, attention(r(norm(x, p["attn_norm"], config, wrong)),
                             p["attn"], config, wrong))
        return add(x, swiglu(r(norm(x, p["mlp_norm"], config, wrong)),
                             p["mlp"], wrong))

    x = r(params["wte"]["embedding"][ids])
    layers = sum(1 for name in params if name.startswith("h_"))
    for i in range(layers):
        x = layer(x, params[f"h_{i}"])
    return r(norm(x, params["norm_f"], config, wrong)) \
        @ params["lm_head"]["kernel"]


def columns(config: Dict[str, Any]) -> int:
    return config["num_pred_heads"] * config["vocab_size"]


def logits(params, ids, config: Dict[str, Any]):
    """(B, S, 8 * 320): head ``r`` in columns ``[320 r, 320 r + 320)``."""
    return _forward(params, ids, config)[..., :columns(config)]


def heads_loss(out, targets, config: Dict[str, Any],
               wrong: Optional[str] = None):
    """The eight-head loss of ``out`` (B, S, 8 * 320) on a row whose
    ``targets`` are the ids rolled left by one: head ``r`` at ``t`` against
    ``targets[t + r]``, over the ``t`` with ``t + r < S``."""
    import jax
    import jax.numpy as jnp

    b, s = targets.shape
    n, vocab = config["num_pred_heads"], config["vocab_size"]
    logp = jax.nn.log_softmax(out.reshape(b, s, n, vocab), axis=-1)
    total, count = 0.0, 0
    for r in range(n):
        ahead = 0 if wrong == "heads_next_byte" else r
        nll = -jnp.take_along_axis(
            logp[:, :s - r, r], targets[:, ahead:s - r + ahead, None],
            axis=-1)
        total, count = total + nll.sum(), count + nll.size
    return total / count


def logits_loss_gradnorm(params, ids, targets, config: Dict[str, Any],
                         wrong: Optional[str] = None):
    """Float32 logits of all eight heads ``(B, S, 8 * 320)``, the eight-head
    loss and the global L2 norm of its gradient, under a wrong model or the
    precision below where one is named."""
    import jax
    import jax.numpy as jnp

    from perfbench.harness.reference import global_norm

    def loss_of(p):
        out = _forward(p, ids, config, wrong)[..., :columns(config)]
        return heads_loss(out, targets, config, wrong), out

    with jax.default_matmul_precision("highest"):
        params = jax.tree_util.tree_map(
            lambda a: a.astype(jnp.float32), params)
        (loss, out), grads = jax.value_and_grad(loss_of, has_aux=True)(params)
    return out, loss, global_norm(grads)

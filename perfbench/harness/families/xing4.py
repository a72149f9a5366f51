"""Xing4.0-29B-A4B (``XingChen-AGI/Xing4.0-29B-A4B``, ``model_type: xing4_0``):
a DeepSeek-V3-shaped decoder — latent attention with a query latent under
YaRN, leading dense layers and then sigmoid-routed experts beside a shared
one, one multi-token-prediction module behind the trunk — whose residual path
is ``hc_mult`` = 4 streams wide, mixed around every attention and every
feed-forward by manifold-constrained hyper-connections (mHC, arXiv:2512.24880,
over Hyper-Connections, arXiv:2409.19606).  The program's side is
``ray_tpu/models/llama.py`` (``HyperConnection`` as ``hc_attn`` / ``hc_mlp``
of a block, ``LatentAttention`` with ``q_lora_rank`` and a rotary table of
``rope_tables``, ``PredictionModule``) over ``models/moe.py`` and
``models/pretrain.py``'s ``L_main + MTP_WEIGHT * L_mtp``.

The stream is ``X`` in R^{n x C} a position (n = 4, C = 3584), ``X_0`` the
embedding copied n times.  A sub-layer ``F`` (a layer has two: attention
behind ``attn_norm``, the feed-forward behind ``mlp_norm``) with its own
``phi`` (n C x (2 n + n^2), columns ``[pre ; post ; res]``), ``bias``, three
``alpha`` and a norm scale n C wide:

    x' = RMSNorm(vec(X))                                  eps rms_norm_eps
    H_pre  = sigmoid(alpha_0 (x' phi_pre) + b_pre)                  in R^n
    H_post = 2 sigmoid(alpha_1 (x' phi_post) + b_post)              in R^n
    H_res  = Sinkhorn(exp(clip(alpha_2 mat(x' phi_res) + b_res, -30, 30)))
             20 times: every row / (its sum + hc_eps), then every column
    X <- H_res X + H_post^T F(norm(H_pre X))

After the last layer the streams are summed, then ``norm_f`` and the head.
Attention, with ``n1`` the sub-layer's normed input, 32 heads:

    cq = RMSNorm_q(Wqa n1) in R^768;  q_h = [qn_h ; R(qr_h)] = heads(Wqb cq)
    [c ; kr] = Wdkv n1;  c <- RMSNorm_kv(c);  [kn_h ; v_h] = heads(Wukv c)
    a_h = softmax_causal(q_h . [kn_h ; R(kr)] * 192^-0.5 * m^2) v_h
    m = 0.1 mscale_all_dim ln(factor) + 1 = 1.4159          (m^2 = 2.0047)

``R``: rotate-half RoPE over the 64 rotary lanes with YaRN's frequencies
(``yarn_inverse_frequencies``: the published ``find_correction_range`` and
linear ramp; cos and sin times ``m(mscale) / m(mscale_all_dim)`` = 1).  The
feed-forward: layers below ``first_k_dense_replace`` a SwiGLU 9216 wide, the
others

    s = sigmoid(Wr n2) in R^64 (float32);  S = top4(s + bias)
    w_e = 2 s_e / sum_{e' in S} s_e'
    F = Shared(n2) + sum_{e in S, e held here} w_e E_e(n2)     both 1024 wide

The prediction module: ``h' = M [RMSNorm(h) ; RMSNorm(Emb(t_{i+1}))]`` of the
summed stream ``h`` before ``norm_f``, ``h'`` copied to the n streams, one
block of the last layer's kind, the streams summed, the module's own final
norm, the shared head; its cross entropy scores ``t_{i+2}``, and the objective
is ``L_main + lambda L_mtp`` (``mtp_lambda`` of the file, which has to be
the program's one value, ``models/pretrain.py::MTP_WEIGHT``).

Plain on purpose: the stream an explicit (B, S, n, C) array, ``phi`` applied
to its flattened rows, the Sinkhorn a Python loop over (B, S, n, n), a dense
boolean mask, the rotary key copied to the heads, every held expert on every
token; nothing of ``ray_tpu``.  The chip's share (``deployment``): experts
``held(config)`` of every sparse layer and the first ``vocab_size`` rows of
the tables; what the absent experts would add is left out, as the program
leaves it out.

**The FLOP count** (``shape``): as ``families/kimi_vl.py`` — the scores of a
layer are 32 x (192 + 128) / 2 = 5,120 wide against ``hidden_size`` 3,584, five
layers 25,600 = 7 x 3,584 + 512: the formula gets ``n_layer`` 7 and the 512 x
seq ride in ``layer_mm_params`` as equivalent parameters, beside the
projections of MLA, the feed-forwards at ``top_k * held / n_experts`` = 0.5
held experts a token, and the hyper-connections' own multiply-adds a position
(``hc_mm_per_sublayer``: the projection n C x 24 and the mixes n C + (n^2 + n)
C), 0.7% of the whole.
"""

from __future__ import annotations

import math
from typing import Any, Dict, Optional, Tuple

# the wrong models the on-chip script and the CPU tests hold the limits
# against, one a mechanism
WRONG = ("sinkhorn_1", "post_without_2", "res_identity", "no_stream_norm",
         "no_q_norm", "plain_rope", "scores_without_m2", "top_3",
         "routed_scale_1")
# the objective's (the prediction module has to be on)
WRONG_OBJECTIVE = ("mtp_scores_next", "lambda_1")
# float32 where the file states it, computed in bf16 instead
BF16_WHERE_FLOAT32 = ("sinkhorn_bf16", "coeff_bf16")
# what the bf16 program on the chip cannot be told from at the initial
# weights (configs/xing4.0-29b-a4b.json, reference.why: alpha starts at 0.01,
# so the coefficients are their biases to a hundredth); the float32
# comparisons of tests/test_xing4.py (c) see each.  (A Sinkhorn in bf16 is
# seen: by the gradient's norm.)
UNSEEN_IN_BF16 = ("sinkhorn_1", "no_stream_norm", "coeff_bf16")
PRECISION_BELOW = "fp8_activations"
# not a wrong model either: the right one with the stream alone rounded to
# bf16 after every sub-layer, as the program holds it.  What the bf16 program
# reads against it, beside what it reads against the float32 stream, says how
# much of a reading is the stream's own rounding
STREAM_AS_HELD = "stream_bf16"


def _rounded(wrong: Optional[str]):
    if wrong != PRECISION_BELOW:
        return lambda x: x
    import jax.numpy as jnp

    return lambda x: x.astype(jnp.float8_e4m3fn).astype(jnp.float32)


def n_experts(config: Dict[str, Any]) -> int:
    """The router's width: the published count, whatever part is held."""
    return config["published_counts"]["n_routed_experts"]


def held(config: Dict[str, Any]) -> Tuple[int, int]:
    count = config["n_routed_experts"]
    return config["deployment"]["this_chip"] * count, count


def shared_width(config: Dict[str, Any]) -> int:
    return config["moe_intermediate_size"] * config["n_shared_experts"]


def is_dense(config: Dict[str, Any], layer: int) -> bool:
    return layer < config["first_k_dense_replace"] \
        or layer % config["moe_layer_freq"] != 0


def score_width(config: Dict[str, Any]) -> int:
    return config["qk_nope_head_dim"] + config["qk_rope_head_dim"]


def yarn_m(scale: float, factor: float) -> float:
    return 0.1 * scale * math.log(factor) + 1.0 if factor > 1 else 1.0


def score_scale(config: Dict[str, Any]) -> float:
    """``192 ** -0.5 * m(mscale_all_dim) ** 2``: the DeepSeek-V3 form."""
    scaling = config["rope_scaling"]
    m = yarn_m(scaling["mscale_all_dim"], scaling["factor"]) \
        if scaling and scaling["mscale_all_dim"] else 1.0
    return score_width(config) ** -0.5 * m * m


def table_factor(config: Dict[str, Any]) -> float:
    """What cos and sin are multiplied by: ``m(mscale) / m(mscale_all_dim)``."""
    scaling = config["rope_scaling"]
    return yarn_m(scaling["mscale"], scaling["factor"]) \
        / yarn_m(scaling["mscale_all_dim"], scaling["factor"])


def attention_params(config: Dict[str, Any]) -> int:
    """wq_a, wq_b, wdkv, wukv, wo."""
    d, h = config["hidden_size"], config["num_attention_heads"]
    dn, dv = config["qk_nope_head_dim"], config["v_head_dim"]
    rank, dr = config["kv_lora_rank"], config["qk_rope_head_dim"]
    return (d * config["q_lora_rank"]
            + config["q_lora_rank"] * h * (dn + dr) + d * (rank + dr)
            + rank * h * (dn + dv) + h * dv * d)


def hc_mm_per_sublayer(config: Dict[str, Any]) -> int:
    """Multiply-adds a position of one sub-layer's hyper-connection: the
    projection of the n C values to 2 n + n^2 coefficients, ``H_pre X`` and
    ``H_res X + H_post^T f``."""
    n, c = config["hc_mult"], config["hidden_size"]
    return n * c * (2 * n + n * n) + n * c + (n * n + n) * c


def shape(config: Dict[str, Any], chips: int) -> Dict[str, int]:
    d, h = config["hidden_size"], config["num_attention_heads"]
    seq, layers = config["flops_counted_at_seq"], config["num_hidden_layers"]
    sparse = (d * n_experts(config) + 3 * d * shared_width(config)
              + config["num_experts_per_tok"] * held(config)[1]
              * 3 * d * config["moe_intermediate_size"] // n_experts(config))
    total = sum(attention_params(config) + 2 * hc_mm_per_sublayer(config)
                + (3 * d * config["intermediate_size"]
                   if is_dense(config, i) else sparse)
                for i in range(layers))
    scores = layers * h * (score_width(config) + config["v_head_dim"]) // 2
    n_layer = scores // d
    total += (scores - n_layer * d) * seq
    return {"d_model": d, "n_layer": n_layer, "n_head": h,
            "n_kv_head": config["num_key_value_heads"],
            "head_dim": score_width(config), "vocab": config["vocab_size"],
            "layer_mm_params": total // n_layer}


def model_config(config: Dict[str, Any], chips: int):
    """Activations and the stream bf16, parameters, the router and the
    hyper-connections' coefficients float32, flash attention, the Pallas
    grouped matmul: the program's defaults, stated in the configuration
    file."""
    import jax.numpy as jnp

    from ray_tpu.models.llama import LlamaConfig, RopeTable
    from ray_tpu.models.pretrain import MTP_WEIGHT

    assert config["mtp_lambda"] == MTP_WEIGHT
    assert config["n_group"] == config["topk_group"] == 1
    assert config["mhc_h_res_clamp_max"] == -config["mhc_h_res_clamp_min"]
    scaling = config["rope_scaling"]
    assert scaling["type"] == "yarn"
    remat, layers = config["remat"], config["num_hidden_layers"]
    table = RopeTable(
        theta=float(config["rope_theta"]), factor=float(scaling["factor"]),
        original_positions=scaling["original_max_position_embeddings"],
        beta_fast=float(scaling["beta_fast"]),
        beta_slow=float(scaling["beta_slow"]),
        attention_factor=table_factor(config))
    return LlamaConfig(
        vocab_size=config["vocab_size"],
        n_positions=config["max_position_embeddings"],
        d_model=config["hidden_size"], n_layer=layers,
        n_head=config["num_attention_heads"],
        n_kv_head=config["num_key_value_heads"],
        d_ff=config["intermediate_size"],
        rope_theta=float(config["rope_theta"]),
        rms_eps=float(config["rms_norm_eps"]), remat=bool(remat),
        remat_policy=remat or "full",
        kv_lora_rank=config["kv_lora_rank"],
        q_lora_rank=config["q_lora_rank"],
        qk_nope_head_dim=config["qk_nope_head_dim"],
        qk_rope_head_dim=config["qk_rope_head_dim"],
        v_head_dim=config["v_head_dim"],
        rope_tables=(("attention", table),), attn_scale=score_scale(config),
        mlp_types=tuple("dense" if is_dense(config, i) else "sparse"
                        for i in range(layers)),
        n_experts=n_experts(config), moe_top_k=config["num_experts_per_tok"],
        d_expert=config["moe_intermediate_size"],
        norm_topk_prob=bool(config["norm_topk_prob"]),
        experts_held=held(config), router_scoring=config["scoring_func"],
        routed_scale=float(config["routed_scaling_factor"]),
        d_shared_expert=shared_width(config),
        router_selection_bias=config["topk_method"] == "noaux_tc",
        router_aux_weight=0.0, router_z_weight=0.0,
        hc_mult=config["hc_mult"],
        hc_sinkhorn_iters=config["hc_sinkhorn_iters"],
        hc_eps=float(config["hc_eps"]),
        hc_res_clamp=float(config["mhc_h_res_clamp_max"]),
        # (the four streams' dtype: what harness/mhc_work.py counts bytes at)
        residual_dtype={"bfloat16": None, "float32": jnp.float32}[
            config.get("stream_dtype", "bfloat16")],
        n_mtp_modules=config["num_nextn_predict_layers"])


# ------------------------------------------------------------ the rotation
def find_correction_dim(turns, dim, base, positions):
    return dim * math.log(positions / (turns * 2 * math.pi)) \
        / (2 * math.log(base))


def find_correction_range(low_turns, high_turns, dim, base, positions):
    low = math.floor(find_correction_dim(low_turns, dim, base, positions))
    high = math.ceil(find_correction_dim(high_turns, dim, base, positions))
    return max(low, 0), min(high, dim - 1)


def yarn_inverse_frequencies(config: Dict[str, Any], plain: bool = False):
    """The ``qk_rope_head_dim / 2`` inverse frequencies, as the published
    YaRN has them: those of the dimensions below the correction range's low
    end kept, those above its high end divided by ``factor``, a linear ramp
    between.  ``plain``: ``rope_theta``'s own."""
    import numpy as np

    dim, base = config["qk_rope_head_dim"], float(config["rope_theta"])
    extra = 1.0 / base ** (np.arange(0, dim, 2, dtype=np.float64) / dim)
    scaling = config["rope_scaling"]
    if plain or not scaling:
        return extra.astype(np.float32)
    inter = extra / scaling["factor"]
    low, high = find_correction_range(
        scaling["beta_fast"], scaling["beta_slow"], dim, base,
        scaling["original_max_position_embeddings"])
    if low == high:
        high += 0.001
    ramp = np.clip((np.arange(dim // 2, dtype=np.float64) - low)
                   / (high - low), 0.0, 1.0)
    return (inter * ramp + extra * (1.0 - ramp)).astype(np.float32)


def rotate(x, inv, factor: float = 1.0):
    """Rotate-half over the whole last dimension at the inverse frequencies
    ``inv``, positions 0, 1, ... along the axis before it."""
    import jax.numpy as jnp

    angle = jnp.arange(x.shape[-2], dtype=jnp.float32)[:, None] \
        * jnp.asarray(inv)[None, :]
    cos, sin = jnp.cos(angle) * factor, jnp.sin(angle) * factor
    x1, x2 = jnp.split(x, 2, axis=-1)
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


# ---------------------------------------------------------------- the layer
def swiglu(y, m):
    import jax

    gate, up, down = (m[name]["kernel"] for name in
                      ("gate_proj", "up_proj", "down_proj"))
    return (jax.nn.silu(y @ gate) * (y @ up)) @ down


def latent_attention(y, a, config: Dict[str, Any],
                     wrong: Optional[str] = None):
    """One layer's attention on its normed input ``y`` (B, S, hidden), ``wo``
    applied: (B, S, hidden)."""
    import jax
    import jax.numpy as jnp

    from perfbench.harness.reference import NEG, heads, rms_norm

    h, rank = config["num_attention_heads"], config["kv_lora_rank"]
    dn, eps = config["qk_nope_head_dim"], config["rms_norm_eps"]
    r = _rounded(wrong)
    cq = y @ a["wq_a"]["kernel"]
    if wrong != "no_q_norm":
        cq = rms_norm(cq, a["q_norm"], eps)
    q = heads(r(cq) @ a["wq_b"]["kernel"], h)               # (B, H, S, 192)
    down = y @ a["wdkv"]["kernel"]
    c, kr = down[..., :rank], down[:, None, :, rank:]       # kr: (B, 1, S, 64)
    kv = heads(r(rms_norm(c, a["kv_norm"], eps)) @ a["wukv"]["kernel"], h)
    kn, v = kv[..., :dn], kv[..., dn:]
    inv = yarn_inverse_frequencies(config, plain=wrong == "plain_rope")
    factor = table_factor(config)
    q = r(jnp.concatenate([q[..., :dn], rotate(q[..., dn:], inv, factor)],
                          axis=-1))
    kr = rotate(kr, inv, factor)
    k = r(jnp.concatenate([kn, kr[:, jnp.zeros(h, jnp.int32)]], axis=-1))
    scale = score_width(config) ** -0.5 if wrong == "scores_without_m2" \
        else score_scale(config)
    s = q.shape[2]
    seen = jnp.arange(s)[:, None] >= jnp.arange(s)[None, :]
    scores = jnp.einsum("bhqd,bhkd->bhqk", q, k) * scale
    att = jnp.einsum("bhqk,bhkd->bhqd", jax.nn.softmax(
        jnp.where(seen, scores, NEG), axis=-1), r(v))
    att = r(att).transpose(0, 2, 1, 3).reshape(y.shape[0], s, -1)
    return r(att @ a["wo"]["kernel"])


def sparse_parts(y, m, config: Dict[str, Any], first: int,
                 wrong: Optional[str] = None):
    """One sparse layer's feed-forward on ``y`` as the chip holding experts
    ``first .. first + count - 1`` computes it, in the published order: the
    sigmoid, the bias for the choice alone, the gather, the normalisation,
    the scale; every held expert on every token, weighted.  -> (the routed
    part, the shared expert's, the 0/1 choices over all experts)."""
    import jax
    import jax.numpy as jnp

    k, scale = config["num_experts_per_tok"], config["routed_scaling_factor"]
    if wrong == "top_3":
        k -= 1
    if wrong == "routed_scale_1":
        scale = 1.0
    count = m["gate_proj"].shape[0]
    score = jax.nn.sigmoid(y @ m["router"]["kernel"])
    for_choice = score + m["selection_bias"] if "selection_bias" in m \
        else score
    _, idx = jax.lax.top_k(for_choice, k)
    chosen = jnp.sum(jax.nn.one_hot(idx, n_experts(config)), axis=-2)
    weight = score * chosen
    if config["norm_topk_prob"]:
        weight = weight / jnp.sum(weight, axis=-1, keepdims=True)
    mine = (scale * weight)[..., first:first + count]
    r = _rounded(wrong)
    hidden = r(jax.nn.silu(jnp.einsum("bsd,edf->bsef", y, m["gate_proj"]))
               * jnp.einsum("bsd,edf->bsef", y, m["up_proj"]))
    routed = r(jnp.einsum("bsef,efd,bse->bsd", hidden, m["down_proj"], mine))
    return routed, r(swiglu(y, m["shared"])), chosen


def sinkhorn(logits, config: Dict[str, Any], iters: int):
    """``logits`` (..., n, n): exp of the clipped logits, then ``iters``
    times each row over its sum + eps, then each column."""
    import jax.numpy as jnp

    m = jnp.exp(jnp.clip(logits, config["mhc_h_res_clamp_min"],
                         config["mhc_h_res_clamp_max"]))
    for _ in range(iters):
        m = m / (m.sum(-1, keepdims=True) + config["hc_eps"])
        m = m / (m.sum(-2, keepdims=True) + config["hc_eps"])
    return m


def coefficients(X, p, config: Dict[str, Any], wrong: Optional[str] = None):
    """``X`` (B, S, n, C) and one sub-layer's hyper-connection parameters ->
    ``H_pre`` (B, S, n), ``H_post`` (B, S, n), ``H_res`` (B, S, n, n)."""
    import jax
    import jax.numpy as jnp

    from perfbench.harness.reference import rms_norm

    n = config["hc_mult"]
    flat = X.reshape(*X.shape[:2], -1)
    if wrong != "no_stream_norm":
        flat = rms_norm(flat, p, config["rms_norm_eps"])
    phi, bias = p["phi"], p["bias"]
    if wrong == "coeff_bf16":
        flat, phi = (a.astype(jnp.bfloat16).astype(jnp.float32)
                     for a in (flat, phi))
    z = flat @ phi
    if wrong == "coeff_bf16":
        z = z.astype(jnp.bfloat16).astype(jnp.float32)
    pre = jax.nn.sigmoid(p["alpha"][0] * z[..., :n] + bias[:n])
    post = jax.nn.sigmoid(p["alpha"][1] * z[..., n:2 * n] + bias[n:2 * n])
    if wrong != "post_without_2":
        post = 2.0 * post
    logits = (p["alpha"][2] * z[..., 2 * n:] + bias[2 * n:]).reshape(
        *z.shape[:2], n, n)
    if wrong == "res_identity":
        return pre, post, jnp.broadcast_to(jnp.eye(n), logits.shape)
    if wrong == "sinkhorn_bf16":
        res = _sinkhorn_bf16(logits, config)
    else:
        res = sinkhorn(logits, config, 1 if wrong == "sinkhorn_1"
                       else config["hc_sinkhorn_iters"])
    return pre, post, res


def _sinkhorn_bf16(logits, config):
    """The same loop with every intermediate rounded to bf16."""
    import jax.numpy as jnp

    def b(x):
        return x.astype(jnp.bfloat16).astype(jnp.float32)

    m = b(jnp.exp(jnp.clip(b(logits), config["mhc_h_res_clamp_min"],
                           config["mhc_h_res_clamp_max"])))
    for _ in range(config["hc_sinkhorn_iters"]):
        m = b(m / b(m.sum(-1, keepdims=True) + config["hc_eps"]))
        m = b(m / b(m.sum(-2, keepdims=True) + config["hc_eps"]))
    return m


def sublayer(X, p, branch, config: Dict[str, Any],
             wrong: Optional[str] = None):
    """``X <- H_res X + H_post^T branch(H_pre X)``, ``p`` the sub-layer's
    hyper-connection parameters."""
    import jax.numpy as jnp

    r = _rounded(wrong)
    pre, post, res = coefficients(X, p, config, wrong)
    f = branch(r(jnp.einsum("bsn,bsnc->bsc", pre, X)))
    out = r(jnp.einsum("bsjk,bskc->bsjc", res, X)
            + post[..., None] * f[:, :, None, :])
    if wrong == STREAM_AS_HELD:
        out = out.astype(jnp.bfloat16).astype(jnp.float32)
    return out


def block(X, p, config: Dict[str, Any], dense: bool, first: int,
          wrong: Optional[str] = None):
    """One layer on the stream ``X`` (B, S, n, C) -> (the stream after it, the
    assignments its held experts received, None for a dense layer)."""
    import jax.numpy as jnp

    from perfbench.harness.reference import rms_norm

    eps, r = config["rms_norm_eps"], _rounded(wrong)
    rows = []

    def attention(u):
        return latent_attention(r(rms_norm(u, p["attn_norm"], eps)),
                                p["attn"], config, wrong)

    def feed_forward(u):
        y = r(rms_norm(u, p["mlp_norm"], eps))
        if dense:
            return r(swiglu(y, p["mlp"]))
        routed, shared, chosen = sparse_parts(y, p["moe"], config, first,
                                              wrong)
        count = p["moe"]["gate_proj"].shape[0]
        rows.append(jnp.sum(chosen[..., first:first + count]))
        return routed + shared

    X = sublayer(X, p["hc_attn"], attention, config, wrong)
    X = sublayer(X, p["hc_mlp"], feed_forward, config, wrong)
    return X, (rows[0] if rows else None)


def _checkpointed_block(config, dense, first, wrong):
    """``block`` keeping its input alone for the backward, so that the
    reference stands beside the trainer's state on the chip."""
    import jax

    return jax.checkpoint(
        lambda X, p: block(X, p, config, dense, first, wrong))


def _forward(params, ids, config: Dict[str, Any],
             wrong: Optional[str] = None):
    """``ids`` (B, S) -> (logits over the rows the head has, padding
    included; each sparse layer's assignments to the held experts; the
    prediction module's logits, None where the file has no module)."""
    import jax.numpy as jnp

    from perfbench.harness.reference import rms_norm

    eps, n = config["rms_norm_eps"], config["hc_mult"]
    first, _ = held(config)
    r = _rounded(wrong)
    layers = config["num_hidden_layers"]

    def streams(x):
        return jnp.repeat(x[:, :, None, :], n, axis=2)

    X = streams(r(params["wte"]["embedding"][ids]))
    rows_held = []
    for i in range(layers):
        X, rows = _checkpointed_block(config, is_dense(config, i), first,
                                      wrong)(X, params[f"h_{i}"])
        if rows is not None:
            rows_held.append(rows)
    h = r(X.sum(axis=2))
    logits = r(r(rms_norm(h, params["norm_f"], eps))
               @ params["lm_head"]["kernel"])
    ahead = None
    if config["num_nextn_predict_layers"]:
        assert config["num_nextn_predict_layers"] == 1
        m = params["mtp_0"]
        # the token after each position; the row's last position reads its
        # first token, and is not counted
        emb = r(params["wte"]["embedding"][jnp.roll(ids, -1, axis=1)])
        joined = jnp.concatenate([rms_norm(h, m["h_norm"], eps),
                                  rms_norm(emb, m["emb_norm"], eps)], axis=-1)
        X, rows = block(streams(r(joined @ m["proj"]["kernel"])), m["block"],
                        config, is_dense(config, layers - 1), first, wrong)
        if rows is not None:
            rows_held.append(rows)
        ahead = r(r(rms_norm(r(X.sum(axis=2)), m["norm_f"], eps))
                  @ params["lm_head"]["kernel"])
    return logits, rows_held, ahead


def logits(params, ids, config: Dict[str, Any]):
    return _forward(params, ids, config)[0][..., :config["vocab_size"]]


def losses(out, ahead, targets, config: Dict[str, Any],
           wrong: Optional[str] = None):
    """(``L_main + lambda L_mtp``, ``L_main``, ``L_mtp``): the mean next-token
    cross entropy of ``out``, and of ``ahead`` at position ``i`` against the
    token two after it, ``targets[i + 1]``, over the positions that have one
    (0 where the file has no module)."""
    import jax
    import jax.numpy as jnp

    def nll(logits, targets):
        logp = jax.nn.log_softmax(logits, axis=-1)
        return -jnp.take_along_axis(logp, targets[..., None], axis=-1)[..., 0]

    main = nll(out, targets).mean()
    if ahead is None:
        return main, main, jnp.float32(0)
    vocab = config["vocab_size"]
    if wrong == "mtp_scores_next":
        mtp = nll(ahead[..., :vocab], targets).mean()
    else:
        mtp = nll(ahead[:, :-1, :vocab], targets[:, 1:]).mean()
    weight = 1.0 if wrong == "lambda_1" else config["mtp_lambda"]
    return main + weight * mtp, main, mtp


def logits_loss_gradnorm(params, ids, targets, config: Dict[str, Any],
                         wrong: Optional[str] = None):
    """Float32 logits, the objective (``losses``), the global L2 norm of its
    gradient, and the held experts' assignments a sparse layer (their
    mean)."""
    import jax
    import jax.numpy as jnp

    from perfbench.harness.reference import global_norm

    def loss_of(p):
        out, rows_held, ahead = _forward(p, ids, config, wrong)
        out = out[..., :config["vocab_size"]]
        return losses(out, ahead, targets, config, wrong)[0], (out, rows_held)

    with jax.default_matmul_precision("highest"):
        params = jax.tree_util.tree_map(
            lambda a: a.astype(jnp.float32), params)
        (loss, (out, rows_held)), grads = jax.value_and_grad(
            loss_of, has_aux=True)(params)
    return out, loss, global_norm(grads), sum(rows_held) / len(rows_held)

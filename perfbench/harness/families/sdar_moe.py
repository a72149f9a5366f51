"""SDAR-MoE (``JetLM/SDAR-30B-A3B-Chat``, ``model_type: sdar_moe``): a
Qwen3-style routed block trained as a block-diffusion denoiser (BD3-LMs,
Arriola et al. 2025).  The program's side is ``ray_tpu/models/llama.py`` with
``objective="block_diffusion"``, ``qk_norm="head"``, ``head_dim`` and
``experts_held``, over ``models/moe.py``'s ``RoutedSwiGLU`` and
``ops/attention.py``'s block mask; the objective is ``models/pretrain.py``'s.

Per layer, ``n1 = RMSNorm(x)``, ``n2 = RMSNorm(h)``, eps 1e-6:

    q = RoPE(RMSNorm_128(heads_32(Wq n1))),  k = RoPE(RMSNorm_128(heads_4(Wk n1))),
    v = heads_4(Wv n1)
    h = x + Wo Attn_M(q, k, v)           scores / sqrt(128), 8 query heads a key/value head
    p = softmax(W_r n2) over all 128, float32;  S = top8(p);  w_e = p_e / sum_{e' in S} p_e'
    y = h + sum_{e in S, e held here} w_e W_down^e( silu(W_gate^e n2) * W_up^e n2 )

then the final RMSNorm and the untied head over the held rows of the
vocabulary.  ``w_e`` is normalised over all eight chosen experts, held or not;
what the absent experts would add is left out and the partial ``y`` goes on
(the chip's share of a layer that eight chips hold: model-configs guide,
section 4).

A row ``x`` of L tokens is run as ``[x_t ; x]``, 2L positions with the RoPE
positions ``0..L-1`` twice, under the mask ``M`` written out here as a
(2L, 2L) boolean from block indices: noised block b sees itself and the clean
blocks before b, clean block b the clean blocks up to and including b.  The
logits are the noised half's, each predicting the token at its own position,
and the loss is ``(1/L) sum_b (1/t_b) sum_{i in b, masked} -log p(x_i)``.
``x_t``, the masked set and the weights ``1/t_b`` are the program's own draw
(``models/pretrain.py::noise_blocks``), handed to both sides.

Plain on purpose: a dense mask, no kernel, no sort, no grouped matmul — every
held expert on every token, masked by the top-8 set.  ``wrong`` names the
wrong models the on-chip script and the CPU tests hold the limits against,
or ``PRECISION_BELOW``: this reference with its activations in float8, the
second of the two readings a limit is set between.
"""

from __future__ import annotations

from typing import Any, Dict, Optional, Tuple

from perfbench.harness.families import published

WRONG = ("noised_sees_own_clean_block", "noised_block_causal",
         "renormalised_over_held", "top_7", "no_inverse_t")
# not a wrong model but the right one in the nearest precision below the
# configuration's bf16 activations: every activation that the program holds in
# bf16 rounded to float8 (e4m3) instead.  The limits must refuse it too.
PRECISION_BELOW = "fp8_activations"


def _rounded(wrong: Optional[str]):
    """The rounding of an activation under ``wrong``: none, but for
    ``PRECISION_BELOW``."""
    if wrong != PRECISION_BELOW:
        return lambda x: x
    import jax.numpy as jnp

    return lambda x: x.astype(jnp.float8_e4m3fn).astype(jnp.float32)


def held(config: Dict[str, Any], chips: int) -> Tuple[int, int]:
    """(first index, count) of the experts this chip holds of every layer."""
    count = published(config, chips, "num_experts")
    return config["deployment"]["this_chip"] * count, count


def shape(config: Dict[str, Any], chips: int) -> Dict[str, int]:
    d, hd = config["hidden_size"], config["head_dim"]
    h, kv = config["num_attention_heads"], config["num_key_value_heads"]
    return {"d_model": d,
            "n_layer": published(config, chips, "num_hidden_layers"),
            "n_head": h, "n_kv_head": kv, "head_dim": hd,
            "vocab": published(config, chips, "vocab_size"),
            # wq, wo; wk, wv at the kv heads: one pass of one copy
            "attn_mm_params": 2 * d * h * hd + 2 * d * kv * hd,
            "router_mm_params": d * config["num_experts"],
            "expert_mm_params": 3 * d * config["moe_intermediate_size"],
            "n_experts": config["num_experts"],
            "n_held": held(config, chips)[1],
            "top_k": config["num_experts_per_tok"],
            "block": config["assumed"]["block_length"]["value"]}


def train_flops_per_token(config: Dict[str, Any], chips: int, seq: int) -> int:
    """Required FLOPs a *data* token, forward + backward.  Both copies of a
    token pass every layer's attention projections and router; of its
    ``top_k`` experts ``top_k * held / n_experts`` are held here at balance
    (routing at balance: stated, not measured), for each copy; the head sees
    the noised copy alone.  Scores: the ``L**2 + L * block`` live pairs of a
    row, QK^T and PV, over ``n_head * head_dim``."""
    s = shape(config, chips)
    per_layer = 2 * (s["attn_mm_params"] + s["router_mm_params"]) \
        + 2 * s["top_k"] * s["n_held"] * s["expert_mm_params"] // s["n_experts"]
    return (6 * (s["n_layer"] * per_layer + s["d_model"] * s["vocab"])
            + 12 * s["n_layer"] * s["n_head"] * s["head_dim"]
            * (seq + s["block"]))


def model_config(config: Dict[str, Any], chips: int):
    """Activations bf16, parameters and the router float32, flash attention,
    the Pallas grouped matmul: the program's defaults, stated in the
    configuration file."""
    from ray_tpu.models.llama import LlamaConfig

    s, assumed, remat = shape(config, chips), config["assumed"], config["remat"]
    return LlamaConfig(
        vocab_size=s["vocab"], n_positions=config["max_position_embeddings"],
        d_model=s["d_model"], n_layer=s["n_layer"], n_head=s["n_head"],
        n_kv_head=s["n_kv_head"], head_dim=s["head_dim"],
        rope_theta=float(config["rope_theta"]),
        rms_eps=float(config["rms_norm_eps"]), remat=bool(remat),
        remat_policy=remat or "full", qk_norm="head",
        moe_every=config["decoder_sparse_step"], n_experts=s["n_experts"],
        moe_top_k=s["top_k"], d_expert=config["moe_intermediate_size"],
        norm_topk_prob=bool(config["norm_topk_prob"]),
        experts_held=held(config, chips),
        router_aux_weight=assumed["auxiliary_router_loss"]["weight"],
        router_z_weight=0.0,
        objective="block_diffusion", diffusion_block=s["block"],
        diffusion_t_min=assumed["t_min"]["value"],
        mask_token_id=assumed["mask_token_id"]["value"])


def block_mask(length: int, block: int, wrong: Optional[str] = None):
    """The (2L, 2L) boolean mask, from block indices: row = query, column =
    key, positions ``0..L-1`` noised and ``L..2L-1`` clean."""
    import jax.numpy as jnp

    pos = jnp.arange(2 * length) % length
    clean = jnp.arange(2 * length) >= length
    q_blk, k_blk = (pos // block)[:, None], (pos // block)[None, :]
    q_clean, k_clean = clean[:, None], clean[None, :]
    noised_to_noised = ~q_clean & ~k_clean & (q_blk == k_blk)
    if wrong == "noised_block_causal":
        noised_to_noised &= pos[None, :] <= pos[:, None]
    before = k_blk <= q_blk if wrong == "noised_sees_own_clean_block" \
        else k_blk < q_blk
    return noised_to_noised | (~q_clean & k_clean & before) \
        | (q_clean & k_clean & (k_blk <= q_blk))


def masked_attention(q, k, v, mask):
    """q: (B, KV, R, S, D); k, v: (B, KV, S, D); ``mask`` (S, S) boolean."""
    import jax
    import jax.numpy as jnp

    from perfbench.harness.reference import NEG

    scores = jnp.einsum("bgrqd,bgkd->bgrqk", q, k) * q.shape[-1] ** -0.5
    weights = jax.nn.softmax(jnp.where(mask, scores, NEG), axis=-1)
    return jnp.einsum("bgrqk,bgkd->bgrqd", weights, v)


def routed_part(y, m, config: Dict[str, Any], first: int,
                wrong: Optional[str] = None):
    """The part of the routed layer's result that the experts
    ``first .. first + count - 1`` give, ``m`` holding their matrices
    (count, ., .) and the whole router: every one of them on every token,
    masked by the top-k set.  -> (the part, which experts each token chose
    as 0/1 over all of them)."""
    import jax
    import jax.numpy as jnp

    n_experts, k = config["num_experts"], config["num_experts_per_tok"]
    if wrong == "top_7":
        k -= 1
    count = m["gate_proj"].shape[0]
    prob = jax.nn.softmax(y @ m["router"]["kernel"], axis=-1)
    _, idx = jax.lax.top_k(prob, k)
    chosen = jnp.sum(jax.nn.one_hot(idx, n_experts), axis=-2)
    weight = prob * chosen
    mine = weight[..., first:first + count]
    if config["norm_topk_prob"]:
        total = jnp.sum(mine if wrong == "renormalised_over_held" else weight,
                        axis=-1, keepdims=True)
        # (a token none of whose chosen experts is held has nothing to
        # renormalise over in that wrong model)
        mine = jnp.where(total > 0, mine / jnp.where(total > 0, total, 1.0),
                         0.0)
    r = _rounded(wrong)
    hidden = r(jax.nn.silu(jnp.einsum("bsd,edf->bsef", y, m["gate_proj"]))
               * jnp.einsum("bsd,edf->bsef", y, m["up_proj"]))
    return r(jnp.einsum("bsef,efd,bse->bsd", hidden, m["down_proj"], mine)), \
        chosen


def _forward(params, ids, config: Dict[str, Any], first: int = 0,
             wrong: Optional[str] = None):
    """``ids`` (B, 2L) = ``[x_t ; x]`` -> (the noised half's logits over the
    rows the head has, padding included; each layer's assignments to the held
    experts)."""
    import jax.numpy as jnp

    from perfbench.harness.reference import (dense, heads, merge, rms_norm,
                                             rope)

    eps, theta = config["rms_norm_eps"], float(config["rope_theta"])
    h, kv = config["num_attention_heads"], config["num_key_value_heads"]
    hd = config["head_dim"]
    length = ids.shape[1] // 2
    mask = block_mask(length, config["assumed"]["block_length"]["value"],
                      wrong)
    n_layer = sum(1 for name in params if name.startswith("h_"))

    def rope_twice(x):      # positions 0..L-1 for each copy
        return jnp.concatenate([rope(x[..., :length, :], theta),
                                rope(x[..., length:, :], theta)], axis=-2)

    r = _rounded(wrong)
    x = r(params["wte"]["embedding"][ids])
    rows_held = []
    for i in range(n_layer):
        p = params[f"h_{i}"]
        a, y = p["attn"], r(rms_norm(x, p["attn_norm"], eps))
        # the norm over each head's own width, one scale for all heads
        q = r(rope_twice(rms_norm(heads(dense(y, a["wq"]), h), a["q_norm"],
                                  eps)))
        k = r(rope_twice(rms_norm(heads(dense(y, a["wk"]), kv), a["k_norm"],
                                  eps)))
        v = r(heads(dense(y, a["wv"]), kv))
        b, _, s, _ = q.shape
        att = r(masked_attention(q.reshape(b, kv, h // kv, s, hd), k, v,
                                 mask))
        x = r(x + r(dense(merge(att), a["wo"])))

        y = r(rms_norm(x, p["mlp_norm"], eps))
        part, chosen = routed_part(y, p["moe"], config, first, wrong)
        count = p["moe"]["gate_proj"].shape[0]
        rows_held.append(jnp.sum(chosen[..., first:first + count]))
        x = r(x + part)
    x = r(rms_norm(x[:, :length], params["norm_f"], eps))
    return r(x @ params["lm_head"]["kernel"]), rows_held


def logits(params, ids, config: Dict[str, Any], chips: int = 1):
    """``ids`` = ``[x_t ; x]``, (B, 2L) -> (B, L, held vocabulary)."""
    out, _ = _forward(params, ids, config, held(config, chips)[0])
    return out[..., :published(config, chips, "vocab_size")]


def logits_loss_gradnorm(params, ids, x_t, weights, config: Dict[str, Any],
                         total: int, chips: int = 1,
                         wrong: Optional[str] = None):
    """Float32 noised-half logits (B, L, held vocabulary), the objective
    ``sum(weights * nll) / total`` with ``nll`` the cross entropy of ``ids``
    at each position under those logits, the global L2 norm of its gradient,
    and the held experts' assignments a layer (mean over the layers)."""
    import jax
    import jax.numpy as jnp

    from perfbench.harness.reference import global_norm

    vocab = published(config, chips, "vocab_size")
    first = held(config, chips)[0]
    if wrong == "no_inverse_t":
        weights = (weights > 0).astype(jnp.float32)

    def loss_of(p):
        out, rows_held = _forward(
            p, jnp.concatenate([x_t, ids], axis=1), config, first, wrong)
        out = out[..., :vocab]
        logp = jax.nn.log_softmax(out, axis=-1)
        nll = -jnp.take_along_axis(logp, ids[..., None], axis=-1)[..., 0]
        return jnp.sum(nll * weights) / total, (out, rows_held)

    with jax.default_matmul_precision("highest"):
        params = jax.tree_util.tree_map(
            lambda a: a.astype(jnp.float32), params)
        (loss, (out, rows_held)), grads = jax.value_and_grad(
            loss_of, has_aux=True)(params)
    return out, loss, global_norm(grads), sum(rows_held) / len(rows_held)

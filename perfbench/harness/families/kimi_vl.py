"""Kimi-VL-A3B (``moonshotai/Kimi-VL-A3B-Instruct``), the language model: a
DeepSeek-V3-shaped decoder with multi-head latent attention (MLA), one leading
dense layer and then sigmoid-routed experts beside a shared one.  The vision
tower and its projector are not here: the catalog row holds no key of theirs
(``program_departures`` of the configuration file).  The program's side is
``ray_tpu/models/llama.py`` with ``kv_lora_rank`` (``LatentAttention``) and
``mlp_types``, over ``ops/attention.py``'s ``k_shared`` and ``models/moe.py``'s
``RoutedSwiGLU`` with ``scoring="sigmoid"``, ``routed_scale``, ``d_shared``
and ``experts_held``.

Per layer, ``n1 = RMSNorm(x)``, ``n2 = RMSNorm(h)``, eps 1e-5, no bias, 16
heads, no query latent (``q_lora_rank`` null):

    q_h = [qn_h ; R(qr_h)] = heads_16(Wq n1)            qn 128, qr 64: 192 a head
    [c ; kr] = Wdkv n1                                  c in R^512, kr in R^64
    c <- RMSNorm_kv(c) ;  kr <- R(kr)                   one kr a position, all heads
    [kn_h ; v_h] = heads_16(Wukv c)                     kn 128, v 128
    k_h = [kn_h ; kr]
    a_h = softmax_causal(q_h . k_h / sqrt(192)) v_h     a_h in R^128
    h = x + Wo [a_1 .. a_16]                            Wo: 2048 x 2048
    layer 0:      y = h + Wdown(silu(Wgate n2) * Wup n2)                  width 11264
    layers 1..:   s = sigmoid(Wr n2) in R^64;  S = top6(s)
                  w_e = 2.446 s_e / sum_{e' in S} s_e'
                  y = h + Shared(n2) + sum_{e in S, e held here} w_e E_e(n2)
                  Shared: SwiGLU 2816 wide; E_e: SwiGLU 1408 wide

``R``: rotate-half RoPE at theta 800,000 over the 64 rotary dimensions, no
scaling, so the scores' scale is ``192 ** -0.5`` alone.  ``w_e`` is normalised
over all six chosen experts, held or not; what the absent experts would add is
left out and the partial ``y`` goes on (the chip's share of a layer that eight
chips hold: model-configs guide, section 4).  The selection bias of
``noaux_tc`` is a buffer that is zero at initialisation and that nothing here
moves: nothing is added to ``s``.  Then the final RMSNorm and the untied head
over the held rows of the vocabulary; next-token cross entropy.

Plain on purpose: a dense boolean mask from indices, the shared rotary key
given to the heads by indexing, every held expert on every token masked by the
top-6 set; no kernel, no sort, no grouped matmul, nothing of ``ray_tpu``.
``WRONG`` names the wrong models the on-chip script and the CPU tests hold the
limits against (``UNSEEN_IN_BF16``: those of them that only the CPU's float32
comparison can see; none here), ``PRECISION_BELOW`` this reference with its activations
in float8: the second of the two readings a limit is set between.

**The FLOP count.**  ``flops.train_flops_per_token`` is ``6 * (n_layer *
layer_mm_params + d_model * vocab) + 6 * n_layer * seq * d_model``; its second
term is the causal scores of ``n_layer`` layers whose q.k and p.v are each
``d_model`` wide.  Here a layer's are 16 x 192 and 16 x 128, (3072 + 2048) / 2
= 2,560 wide against ``hidden_size`` 2,048, and six layers are 15,360 = 7.5 x
2,048: ``shape`` hands the formula ``n_layer`` = 7 and folds the remaining
1,024 x seq into ``layer_mm_params`` as equivalent parameters (a matmul
parameter is 6 FLOPs a token) at the cell's length (``flops_counted_at_seq``),
beside every other matmul of the cut: the four projections of MLA, layer 0's
dense feed-forward, the router, the shared expert and ``top_k * held /
n_experts`` = 0.75 held experts a token (routing at balance: stated, not
measured).  (The sum over 7 is floored: at most 36 of 3.4e9 FLOPs a token.)
"""

from __future__ import annotations

from typing import Any, Dict, Optional, Tuple

WRONG = ("no_latent_norm", "no_rope_on_shared_key", "scale_128",
         "values_from_key_half", "one_shared_expert", "routed_scale_1",
         "top_5", "softmax_scores")
# Of those, what the comparison on the chip cannot see, though the float32
# tests on the CPU do: none.  The one that looked likely — the latent's norm
# left out, since at initialisation the latent is already of unit RMS
# (``wdkv`` is lecun-normal on a normed input, ``kv_norm``'s scale is 1) and
# the norm divides 512 values by 1 +- 0.03 — reads 0.157 to 0.171 on the chip
# against the program's 0.047 to 0.063: every later layer's router sees the
# 3% and flips experts (configs/kimi-vl-a3b-instruct.json, reference.why).
UNSEEN_IN_BF16 = ()
# not a wrong model but the right one in the nearest precision below the
# configuration's bf16 activations: every activation that the program holds in
# bf16 rounded to float8 (e4m3) instead.  The limits must refuse it too.
PRECISION_BELOW = "fp8_activations"


def _rounded(wrong: Optional[str]):
    if wrong != PRECISION_BELOW:
        return lambda x: x
    import jax.numpy as jnp

    return lambda x: x.astype(jnp.float8_e4m3fn).astype(jnp.float32)


def n_experts(config: Dict[str, Any]) -> int:
    """The router's width: the published count, whatever part is held."""
    return config["published_counts"]["n_routed_experts"]


def held(config: Dict[str, Any]) -> Tuple[int, int]:
    """(first index, count) of the experts this chip holds of every sparse
    layer: ``n_routed_experts`` of the file is the count held."""
    count = config["n_routed_experts"]
    return config["deployment"]["this_chip"] * count, count


def shared_width(config: Dict[str, Any]) -> int:
    """The published form: one MLP of ``moe_intermediate_size *
    n_shared_experts``."""
    return config["moe_intermediate_size"] * config["n_shared_experts"]


def is_dense(config: Dict[str, Any], layer: int) -> bool:
    return layer < config["first_k_dense_replace"] \
        or layer % config["moe_layer_freq"] != 0


def score_width(config: Dict[str, Any]) -> int:
    return config["qk_nope_head_dim"] + config["qk_rope_head_dim"]


def shape(config: Dict[str, Any], chips: int) -> Dict[str, int]:
    d, h = config["hidden_size"], config["num_attention_heads"]
    dn, dv, rank = (config["qk_nope_head_dim"], config["v_head_dim"],
                    config["kv_lora_rank"])
    dr, seq = config["qk_rope_head_dim"], config["flops_counted_at_seq"]
    layers = config["num_hidden_layers"]
    # wq, wdkv, wukv, wo
    attention = d * h * (dn + dr) + d * (rank + dr) + rank * h * (dn + dv) \
        + h * dv * d
    sparse = (d * n_experts(config) + 3 * d * shared_width(config)
              + config["num_experts_per_tok"] * held(config)[1]
              * 3 * d * config["moe_intermediate_size"] // n_experts(config))
    total = sum(attention + (3 * d * config["intermediate_size"]
                             if is_dense(config, i) else sparse)
                for i in range(layers))
    # q.k and p.v of a layer, as a multiple of the d_model the formula counts
    scores = layers * h * (score_width(config) + dv) // 2
    n_layer = scores // d
    total += (scores - n_layer * d) * seq
    return {"d_model": d, "n_layer": n_layer, "n_head": h,
            "n_kv_head": config["num_key_value_heads"],
            "head_dim": score_width(config), "vocab": config["vocab_size"],
            "layer_mm_params": total // n_layer}


def model_config(config: Dict[str, Any], chips: int):
    """Activations bf16, parameters and the router float32, flash attention,
    the Pallas grouped matmul: the program's defaults, stated in the
    configuration file."""
    from ray_tpu.models.llama import LlamaConfig

    assert config["q_lora_rank"] is None and config["rope_scaling"] is None
    assert config["n_group"] == config["topk_group"] == 1
    remat, layers = config["remat"], config["num_hidden_layers"]
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
        qk_nope_head_dim=config["qk_nope_head_dim"],
        qk_rope_head_dim=config["qk_rope_head_dim"],
        v_head_dim=config["v_head_dim"],
        mlp_types=tuple("dense" if is_dense(config, i) else "sparse"
                        for i in range(layers)),
        n_experts=n_experts(config), moe_top_k=config["num_experts_per_tok"],
        d_expert=config["moe_intermediate_size"],
        norm_topk_prob=bool(config["norm_topk_prob"]),
        experts_held=held(config), router_scoring=config["scoring_func"],
        routed_scale=float(config["routed_scaling_factor"]),
        d_shared_expert=shared_width(config),
        router_aux_weight=0.0, router_z_weight=0.0)


# ---------------------------------------------------------------- the layer
def swiglu(y, m, width: Optional[int] = None):
    """``width``: only the first so many of the hidden units."""
    import jax

    gate, up, down = (m[name]["kernel"] for name in
                      ("gate_proj", "up_proj", "down_proj"))
    return (jax.nn.silu(y @ gate[:, :width]) * (y @ up[:, :width])
            ) @ down[:width]


def latent_attention(y, a, config: Dict[str, Any],
                     wrong: Optional[str] = None):
    """One layer's MLA on its normed input ``y`` (B, S, hidden) with the
    layer's ``attn`` parameters ``a``, before ``Wo``: (B, S, 16 x 128)."""
    import jax
    import jax.numpy as jnp

    from perfbench.harness.reference import NEG, heads, rms_norm, rope

    h, rank = config["num_attention_heads"], config["kv_lora_rank"]
    dn, theta = config["qk_nope_head_dim"], float(config["rope_theta"])
    r = _rounded(wrong)
    q = heads(y @ a["wq"]["kernel"], h)                    # (B, H, S, 192)
    down = y @ a["wdkv"]["kernel"]                         # (B, S, 512 + 64)
    c, kr = down[..., :rank], down[:, None, :, rank:]      # kr: (B, 1, S, 64)
    if wrong != "no_latent_norm":
        c = rms_norm(c, a["kv_norm"], config["rms_norm_eps"])
    kv = heads(r(c) @ a["wukv"]["kernel"], h)              # (B, H, S, 256)
    kn, v = kv[..., :dn], kv[..., dn:]
    if wrong == "values_from_key_half":
        v = kn
    if wrong != "no_rope_on_shared_key":
        kr = rope(kr, theta)
    q = r(jnp.concatenate([q[..., :dn], rope(q[..., dn:], theta)], axis=-1))
    # the one rotary key of a position, for every head
    k = r(jnp.concatenate([kn, kr[:, jnp.zeros(h, jnp.int32)]], axis=-1))
    scale = (dn if wrong == "scale_128" else q.shape[-1]) ** -0.5
    s = q.shape[2]
    seen = jnp.arange(s)[:, None] >= jnp.arange(s)[None, :]
    scores = jnp.einsum("bhqd,bhkd->bhqk", q, k) * scale
    att = jnp.einsum("bhqk,bhkd->bhqd", jax.nn.softmax(
        jnp.where(seen, scores, NEG), axis=-1), r(v))
    return r(att).transpose(0, 2, 1, 3).reshape(y.shape[0], s, -1)


def sparse_parts(y, m, config: Dict[str, Any], first: int,
                 wrong: Optional[str] = None):
    """One sparse layer's feed-forward on ``y`` as the chip holding experts
    ``first .. first + count - 1`` computes it, ``m`` holding their matrices
    (count, ., .), the whole router and the shared expert: every held expert
    on every token, masked by the top-k set.  -> (the routed part, the shared
    expert's, which experts each token chose as 0/1 over all of them)."""
    import jax
    import jax.numpy as jnp

    k, scale = config["num_experts_per_tok"], config["routed_scaling_factor"]
    if wrong == "top_5":
        k -= 1
    if wrong == "routed_scale_1":
        scale = 1.0
    count = m["gate_proj"].shape[0]
    router = y @ m["router"]["kernel"]
    score = jax.nn.softmax(router, axis=-1) if wrong == "softmax_scores" \
        else jax.nn.sigmoid(router)
    _, idx = jax.lax.top_k(score, k)
    chosen = jnp.sum(jax.nn.one_hot(idx, n_experts(config)), axis=-2)
    weight = score * chosen
    weight = scale * weight / jnp.sum(weight, axis=-1, keepdims=True)
    mine = weight[..., first:first + count]
    r = _rounded(wrong)
    hidden = r(jax.nn.silu(jnp.einsum("bsd,edf->bsef", y, m["gate_proj"]))
               * jnp.einsum("bsd,edf->bsef", y, m["up_proj"]))
    routed = r(jnp.einsum("bsef,efd,bse->bsd", hidden, m["down_proj"], mine))
    shared = r(swiglu(y, m["shared"], config["moe_intermediate_size"]
                      if wrong == "one_shared_expert" else None))
    return routed, shared, chosen


def _forward(params, ids, config: Dict[str, Any],
             wrong: Optional[str] = None):
    """``ids`` (B, S) -> (logits over the rows the head has, padding
    included; each sparse layer's assignments to the held experts)."""
    import jax.numpy as jnp

    from perfbench.harness.reference import rms_norm

    eps = config["rms_norm_eps"]
    first, count = held(config)
    r = _rounded(wrong)
    x = r(params["wte"]["embedding"][ids])
    rows_held = []
    for i in range(config["num_hidden_layers"]):
        p = params[f"h_{i}"]
        y = r(rms_norm(x, p["attn_norm"], eps))
        x = r(x + r(latent_attention(y, p["attn"], config, wrong)
                    @ p["attn"]["wo"]["kernel"]))
        y = r(rms_norm(x, p["mlp_norm"], eps))
        if is_dense(config, i):
            x = r(x + r(swiglu(y, p["mlp"])))
        else:
            routed, shared, chosen = sparse_parts(y, p["moe"], config, first,
                                                  wrong)
            rows_held.append(jnp.sum(chosen[..., first:first + count]))
            x = r(x + routed + shared)
    x = r(rms_norm(x, params["norm_f"], eps))
    return r(x @ params["lm_head"]["kernel"]), rows_held


def logits(params, ids, config: Dict[str, Any]):
    return _forward(params, ids, config)[0][..., :config["vocab_size"]]


def logits_loss_gradnorm(params, ids, targets, config: Dict[str, Any],
                         wrong: Optional[str] = None):
    """``reference.logits_loss_gradnorm`` under a wrong model or the
    precision below, with the held experts' assignments a sparse layer (their
    mean) beside it."""
    import jax
    import jax.numpy as jnp

    from perfbench.harness.reference import global_norm

    def loss_of(p):
        out, rows_held = _forward(p, ids, config, wrong)
        out = out[..., :config["vocab_size"]]
        logp = jax.nn.log_softmax(out, axis=-1)
        nll = -jnp.take_along_axis(logp, targets[..., None], axis=-1)
        return nll.mean(), (out, rows_held)

    with jax.default_matmul_precision("highest"):
        params = jax.tree_util.tree_map(
            lambda a: a.astype(jnp.float32), params)
        (loss, (out, rows_held)), grads = jax.value_and_grad(
            loss_of, has_aux=True)(params)
    return out, loss, global_norm(grads), sum(rows_held) / len(rows_held)

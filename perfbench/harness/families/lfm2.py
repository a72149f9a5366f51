"""LFM2-MoE (``LiquidAI/LFM2-24B-A2B``, ``model_type: lfm2_moe``): gated
short-convolution mixers in three layers of four beside grouped-query
attention with RoPE and per-head q / k norms, leading dense feed-forward
layers and then sigmoid-routed experts chosen through a per-expert selection
bias.  The program's side is ``ray_tpu/models/llama.py`` with ``layer_types``
(``conv``: ``ShortConvMixer``; ``full_attention``), ``qk_norm="head"`` and
``mlp_types``, over ``models/moe.py``'s ``RoutedSwiGLU`` with
``scoring="sigmoid"``, ``selection_bias``, ``norm_topk_eps`` and
``experts_held``.

Per layer, ``n1 = RMSNorm(x)``, ``n2 = RMSNorm(h)``, eps 1e-5, no bias
anywhere, 32 query and 8 key/value heads 64 wide:

    conv layer:   [B ; C ; u] = Win n1            Win: 2048 -> 3 x 2048
                  m_t = sum_{j=0..2} k_j * (B * u)_{t-2+j}      depthwise, causal, zeros before t = 0
                  h = x + Wout (C * m)
    attn layer:   q, k, v = heads_32(Wq n1), heads_8(Wk n1), heads_8(Wv n1)
                  q, k <- R(RMSNorm_64(q)), R(RMSNorm_64(k))    one scale for all heads, then RoPE (theta 1e6, whole head)
                  h = x + Wo softmax_causal(q . k / 8) v        each key/value head serves 4 query heads
    dense ffn:    y = h + W2 (silu(W1 n2) * W3 n2)              11776 wide
    sparse ffn:   s = sigmoid(Wr n2) in R^64;  S = top4(s + b);  w_e = s_e / (sum_{e' in S} s_e' + 1e-6)
                  y = h + sum_{e in S, e held here} w_e E_e(n2) E_e: SwiGLU 1536 wide

``b`` enters the selection only: the weights are the scores without it,
normalised over all four chosen experts, held or not; what the absent experts
would add is left out and the partial ``y`` goes on (the chip's share of a
layer that eight chips hold: model-configs guide, section 4).  Then the final
RMSNorm and the head, which is the embedding table (tied), over the held rows
of the vocabulary; next-token cross entropy.

Plain on purpose: the convolution as an explicit sum over three shifted
copies, a dense boolean mask from indices, every held expert on every token
masked by the top-4 set; no kernel, no sort, no grouped matmul, nothing of
``ray_tpu``.  ``WRONG`` names the wrong models the on-chip script and the CPU
tests hold the limits against (``UNSEEN_IN_BF16``: those of them that only
the CPU's float32 comparison can see), ``PRECISION_BELOW`` this reference with
its activations in float8: the second of the two readings a limit is set
between.

**The FLOP count.**  ``flops.train_flops_per_token`` is ``6 * (n_layer *
layer_mm_params + d_model * vocab) + 6 * n_layer * seq * d_model``; its second
term is the causal scores of ``n_layer`` layers whose heads together are
``d_model`` wide.  Here only the ``full_attention`` layers have scores, 32 x
64 = ``hidden_size`` wide: ``shape`` hands the formula their count as
``n_layer`` and every matmul of the cut — the ``conv`` mixers' two
projections (the depthwise convolution and the gates are no matmul and are
not counted), attention's four, each dense feed-forward, the router and
``top_k * held / n_experts`` = 0.5 held experts a token (routing at balance:
stated, not measured) — as ``layer_mm_params`` over that count.
"""

from __future__ import annotations

from typing import Any, Dict, Optional, Tuple

WRONG = ("no_gate_b", "no_gate_c", "conv_4_wide", "conv_sees_ahead",
         "no_qk_norm", "no_rope", "top_3", "softmax_scores", "no_renorm",
         "bias_in_weights")
# Of those, what the comparison on the chip cannot see, though the float32
# tests on the CPU do (configs/lfm2-24b-a2b.json, reference.why, has the
# readings).  ``no_qk_norm``: at initialisation ``wq`` and ``wk`` are
# lecun-normal on a normed input and the norms' scales are 1, so a head is of
# unit RMS already and the norm divides its 64 values by 1 +- 0.09; that moves
# the one attention layer's scores by a tenth and the logits by 0.077 to 0.088
# relative RMS where bf16 (the routers' flips) moves them by 0.044 to 0.060.
# ``bias_in_weights``: a bias of 0.1 beside scores near 0.8 moves a chosen
# weight by an eighth before the renormalisation takes most of it back, on
# the 0.5 held experts a token: 0.049 to 0.055, which is the program's own
# reading.
UNSEEN_IN_BF16 = ("no_qk_norm", "bias_in_weights")
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
    return config["published_counts"]["num_experts"]


def held(config: Dict[str, Any]) -> Tuple[int, int]:
    """(first index, count) of the experts this chip holds of every sparse
    layer: ``num_experts`` of the file is the count held."""
    count = config["num_experts"]
    return config["deployment"]["this_chip"] * count, count


def is_dense(config: Dict[str, Any], layer: int) -> bool:
    return layer < config["num_dense_layers"]


def head_dim(config: Dict[str, Any]) -> int:
    return config["hidden_size"] // config["num_attention_heads"]


def shape(config: Dict[str, Any], chips: int) -> Dict[str, int]:
    d, hd = config["hidden_size"], head_dim(config)
    h, kv = config["num_attention_heads"], config["num_key_value_heads"]
    mixer = {"conv": d * 3 * d + d * d,                 # in_proj, out_proj
             "full_attention": 2 * d * h * hd + 2 * d * kv * hd}
    sparse = (d * n_experts(config)
              + config["num_experts_per_tok"] * held(config)[1]
              * 3 * d * config["moe_intermediate_size"] // n_experts(config))
    total = sum(mixer[kind] + (3 * d * config["intermediate_size"]
                               if is_dense(config, i) else sparse)
                for i, kind in enumerate(config["layer_types"]))
    attention = sum(kind == "full_attention"
                    for kind in config["layer_types"])
    return {"d_model": d, "n_layer": attention, "n_head": h, "n_kv_head": kv,
            "head_dim": hd, "vocab": config["vocab_size"],
            "layer_mm_params": total // attention}


def model_config(config: Dict[str, Any], chips: int):
    """Activations bf16, parameters and the router float32, flash attention,
    the Pallas grouped matmul: the program's defaults, stated in the
    configuration file."""
    from ray_tpu.models.llama import LlamaConfig

    assert not config["conv_bias"], "the program's convolution has no bias"
    assert config["rope_parameters"]["rope_type"] == "default"
    assert len(config["layer_types"]) == config["num_hidden_layers"]
    remat, router = config["remat"], config["assumed"]["router"]
    return LlamaConfig(
        vocab_size=config["vocab_size"],
        n_positions=config["max_position_embeddings"],
        d_model=config["hidden_size"], n_layer=config["num_hidden_layers"],
        n_head=config["num_attention_heads"],
        n_kv_head=config["num_key_value_heads"],
        head_dim=head_dim(config), qk_norm="head",
        d_ff=config["intermediate_size"],
        rope_theta=float(config["rope_parameters"]["rope_theta"]),
        rms_eps=float(config["norm_eps"]), remat=bool(remat),
        remat_policy=remat or "full",
        layer_types=tuple(config["layer_types"]),
        conv_width=config["conv_L_cache"],
        tie_embeddings=bool(config["assumed"]["tie_word_embeddings"]["form"]),
        mlp_types=tuple("dense" if is_dense(config, i) else "sparse"
                        for i in range(config["num_hidden_layers"])),
        n_experts=n_experts(config), moe_top_k=config["num_experts_per_tok"],
        d_expert=config["moe_intermediate_size"],
        norm_topk_prob=bool(config["norm_topk_prob"]),
        norm_topk_eps=float(router["norm_topk_eps"]),
        experts_held=held(config), router_scoring=router["scoring"],
        routed_scale=float(config["routed_scaling_factor"]),
        router_selection_bias=bool(config["use_expert_bias"]),
        router_aux_weight=0.0, router_z_weight=0.0)


# ---------------------------------------------------------------- the layer
def swiglu(y, m):
    import jax

    return (jax.nn.silu(y @ m["gate_proj"]["kernel"])
            * (y @ m["up_proj"]["kernel"])) @ m["down_proj"]["kernel"]


def short_conv(y, c, config: Dict[str, Any], wrong: Optional[str] = None):
    """One ``conv`` layer's mixer on its normed input ``y`` (B, S, hidden)
    with the layer's ``conv`` parameters ``c``: in_proj's kernel (hidden, 3,
    hidden) holds ``B``, ``C``, ``u`` side by side, ``conv_kernel`` (width,
    hidden) the taps, the last of them the position's own."""
    import jax.numpy as jnp

    r = _rounded(wrong)
    s, width = y.shape[1], config["conv_L_cache"]
    win, taps = c["in_proj"]["kernel"], c["conv_kernel"]
    b, gate, u = (r(y @ win[:, i]) for i in range(3))
    bu = r(u if wrong == "no_gate_b" else b * u)
    ahead = 1 if wrong == "conv_sees_ahead" else 0
    if wrong == "conv_4_wide":
        # one more tap, with the first one's weight, one position further back
        taps, width = jnp.concatenate([taps[:1], taps]), width + 1
    # tap j reads the position (width - 1 - j) back (``ahead``: one less)
    padded = jnp.pad(bu, ((0, 0), (width - 1, ahead), (0, 0)))
    m = r(sum(taps[j] * padded[:, j + ahead:j + ahead + s]
              for j in range(width)))
    return r(m if wrong == "no_gate_c" else gate * m) \
        @ c["out_proj"]["kernel"]


def attention(y, a, config: Dict[str, Any], wrong: Optional[str] = None):
    """One attention layer on its normed input, through ``Wo``."""
    import jax
    import jax.numpy as jnp

    from perfbench.harness.reference import NEG, heads, merge, rms_norm, rope

    h, kv = config["num_attention_heads"], config["num_key_value_heads"]
    theta, eps = float(config["rope_parameters"]["rope_theta"]), \
        config["norm_eps"]
    r = _rounded(wrong)
    q = heads(y @ a["wq"]["kernel"], h)
    k = heads(y @ a["wk"]["kernel"], kv)
    v = r(heads(y @ a["wv"]["kernel"], kv))
    if wrong != "no_qk_norm":
        q, k = rms_norm(q, a["q_norm"], eps), rms_norm(k, a["k_norm"], eps)
    if wrong != "no_rope":
        q, k = rope(q, theta), rope(k, theta)
    q, k = r(q), r(k)
    b, _, s, hd = q.shape
    scores = jnp.einsum("bgrqd,bgkd->bgrqk",
                        q.reshape(b, kv, h // kv, s, hd), k) * hd ** -0.5
    mask = jnp.arange(s)[:, None] >= jnp.arange(s)[None, :]
    att = jnp.einsum("bgrqk,bgkd->bgrqd", jax.nn.softmax(
        jnp.where(mask, scores, NEG), axis=-1), v)
    return merge(r(att)) @ a["wo"]["kernel"]


def sparse_parts(y, m, config: Dict[str, Any], first: int,
                 wrong: Optional[str] = None):
    """One sparse layer's feed-forward on ``y`` as the chip holding experts
    ``first .. first + count - 1`` computes it, ``m`` holding their matrices
    (count, ., .), the whole router and the whole selection bias: every held
    expert on every token, masked by the top-k set.  -> (the routed part,
    which experts each token chose as 0/1 over all of them)."""
    import jax
    import jax.numpy as jnp

    k = config["num_experts_per_tok"] - (wrong == "top_3")
    eps = config["assumed"]["router"]["norm_topk_eps"]
    count = m["gate_proj"].shape[0]
    router = y @ m["router"]["kernel"]
    score = jax.nn.softmax(router, axis=-1) if wrong == "softmax_scores" \
        else jax.nn.sigmoid(router)
    bias = m["selection_bias"] if config["use_expert_bias"] else 0.0
    _, idx = jax.lax.top_k(score + jax.lax.stop_gradient(bias), k)
    chosen = jnp.sum(jax.nn.one_hot(idx, n_experts(config)), axis=-2)
    weight = (score + bias if wrong == "bias_in_weights" else score) * chosen
    if config["norm_topk_prob"] and wrong != "no_renorm":
        weight = weight / (jnp.sum(weight, axis=-1, keepdims=True) + eps)
    weight = config["routed_scaling_factor"] * weight
    mine = weight[..., first:first + count]
    r = _rounded(wrong)
    hidden = r(jax.nn.silu(jnp.einsum("bsd,edf->bsef", y, m["gate_proj"]))
               * jnp.einsum("bsd,edf->bsef", y, m["up_proj"]))
    routed = r(jnp.einsum("bsef,efd,bse->bsd", hidden, m["down_proj"], mine))
    return routed, chosen


def _forward(params, ids, config: Dict[str, Any],
             wrong: Optional[str] = None):
    """``ids`` (B, S) -> (logits over the rows the head has, padding
    included; each sparse layer's assignments to the held experts)."""
    import jax.numpy as jnp

    from perfbench.harness.reference import rms_norm

    eps = config["norm_eps"]
    first, count = held(config)
    r = _rounded(wrong)
    x = r(params["wte"]["embedding"][ids])
    rows_held = []
    for i, kind in enumerate(config["layer_types"]):
        p = params[f"h_{i}"]
        y = r(rms_norm(x, p["attn_norm"], eps))
        if kind == "conv":
            x = r(x + r(short_conv(y, p["conv"], config, wrong)))
        else:
            assert kind == "full_attention", kind
            x = r(x + r(attention(y, p["attn"], config, wrong)))
        y = r(rms_norm(x, p["mlp_norm"], eps))
        if is_dense(config, i):
            x = r(x + r(swiglu(y, p["mlp"])))
        else:
            routed, chosen = sparse_parts(y, p["moe"], config, first, wrong)
            rows_held.append(jnp.sum(chosen[..., first:first + count]))
            x = r(x + routed)
    x = r(rms_norm(x, params["norm_f"], eps))
    head = params["wte"]["embedding"].T \
        if config["assumed"]["tie_word_embeddings"]["form"] \
        else params["lm_head"]["kernel"]
    return r(x @ head), rows_held


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

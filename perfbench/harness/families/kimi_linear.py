"""Kimi-Linear-48B-A3B (``moonshotai/Kimi-Linear-48B-A3B-Instruct``,
``model_type`` ``kimi_linear``; Kimi Linear, arXiv:2510.26692): Kimi Delta
Attention (KDA) in three layers of four beside latent attention (MLA) without
any rotation, one leading dense layer and then sigmoid-routed experts beside a
shared one.  The program's side is ``ray_tpu/models/llama.py`` with
``layer_types`` (``"kda"``: ``models/kda.py`` over ``ops/kda.py``'s chunked
scan), ``kv_lora_rank`` with ``rope`` off (``LatentAttention``) and
``mlp_types``, over ``models/moe.py``'s ``RoutedSwiGLU``.

``linear_attn_config`` says which layer is which (one-based lists).  With ``n
= RMSNorm(x)``, eps 1e-5, no bias but one, ``H`` = 32 heads of ``d`` = 128, a
**KDA layer** is, per head ``h`` and position ``t``:

    q_t = l2norm(silu(conv4(Wq n)))_h / sqrt(d)    k_t = l2norm(silu(conv4(Wk n)))_h    v_t = silu(conv4(Wv n))_h
          conv4: causal depthwise convolution, width 4, one kernel a channel, q, k, v each its own
    g_t = -exp(A_log_h) * softplus((Wf_b Wf_a n)_h + dt_bias_h)     in R^d: a log-decay a CHANNEL
    a_t = exp(g_t);   b_t = sigmoid(Wb n)_h                          a scalar a head
    S_t = (I - b_t k_t k_t^T) Diag(a_t) S_{t-1} + b_t k_t v_t^T      S in R^(d x d), S_0 = 0
    o_t = S_t^T q_t
    m   = Wo [RMSNorm_d(o_t) * sigmoid((Wg_b Wg_a n + bias)_h)]_h    one learned scale of d for all heads
    x <- x + m;   x <- x + FFN(RMSNorm(x))

an **MLA layer** is Kimi-VL's (``families/kimi_vl.py``) with 32 heads and no
rotation: ``q_h = heads_32(Wq n)`` 192 wide, ``[c ; kr] = Wdkv n``, ``c <-
RMSNorm_kv(c)``, ``[kn_h ; v_h] = heads_32(Wukv c)``, ``k_h = [kn_h ; kr]``
with the one ``kr`` for all heads, ``softmax_causal(q_h . k_h / sqrt(192))
v_h``, ``Wo`` from 4,096; and the feed-forward is layer 1's dense SwiGLU of
9,216 or ``s = sigmoid(Wr n2)`` in R^256, ``S = top8(s)``, ``w_e = 2.446 s_e /
sum_{e' in S} s_e'``, ``y = h + Shared(n2) + sum_{e in S, held here} w_e
E_e(n2)`` with experts 1,024 wide.  Then the final RMSNorm and the untied head
over the held rows of the vocabulary; next-token cross entropy.

Plain on purpose: the recurrence is a ``lax.scan`` over single positions that
carries ``S_t`` — no chunks, no solve, no running sums —, the convolution is
four shifted multiply-adds, MLA a dense boolean mask with ``kr`` given to the
heads by indexing, every held expert on every token masked by the top-8 set;
nothing of ``ray_tpu``.  One thing is not mathematics: each KDA layer's
recurrence is under ``jax.checkpoint``, as Granite's is and for the same
reason (its backward pass would keep a 32 x 128 x 128 state a position).
``WRONG`` names the wrong models the on-chip script and the CPU tests hold
the limits against, ``UNSEEN_IN_BF16`` those of them that only the CPU's
float32 comparison can see, ``PRECISION_BELOW`` this reference with its
activations in float8.

**The FLOP count.**  ``flops.train_flops_per_token`` is ``6 * (n_layer *
layer_mm_params + d_model * vocab) + 6 * n_layer * seq * d_model``: its second
term charges causal scores ``d_model`` wide to ``n_layer`` layers.  A KDA
layer has none; the one MLA layer's q.k and p.v are 32 x 192 and 32 x 128,
(6144 + 4096) / 2 = 5,120 wide = 2 x 2,304 + 512.  ``shape`` hands the
formula ``n_layer`` = 2 for every MLA layer of the cut and folds the
remaining 512 x seq into ``layer_mm_params`` as equivalent parameters (a
matmul parameter is 6 FLOPs a token) at the cell's length
(``flops_counted_at_seq``), beside every matmul of the cut: the mixers'
projections, the three depthwise convolutions (4 multiply-adds a channel),
the dense feed-forward, the router, the shared expert, ``top_k * held /
n_experts`` = 0.25 held experts a token (routing at balance: stated, not
measured) and each KDA layer's scan as ``scan_flops_per_token / 2``.
"""

from __future__ import annotations

from typing import Any, Dict, Optional, Tuple

WRONG = ("no_decay", "head_decay", "beta_1", "no_delta",
         "decay_after_correction", "no_l2norm", "q_unscaled", "no_conv",
         "silu_out_gate", "no_out_norm", "mla_rope", "own_kr",
         "softmax_scores", "top_6", "routed_scale_1", "no_renorm")
# Of those, what the comparison on the chip cannot see, though the float32
# tests on the CPU do (the readings are in the configuration file's
# reference.why).
UNSEEN_IN_BF16 = ()
# not a wrong model but the right one in the nearest precision below the
# configuration's bf16 activations: every activation that the program holds in
# bf16 rounded to float8 (e4m3) instead.  The limits must refuse it too.
PRECISION_BELOW = "fp8_activations"
L2_EPS = 1e-6


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


def shared_width(config: Dict[str, Any]) -> int:
    return config["moe_intermediate_size"] * config["num_shared_experts"]


def is_dense(config: Dict[str, Any], layer: int) -> bool:
    return layer < config["first_k_dense_replace"] \
        or layer % config["moe_layer_freq"] != 0


def layer_kinds(config: Dict[str, Any]) -> Tuple[str, ...]:
    """Each layer's mixer, ``"kda"`` or ``"full_attention"``, from the
    one-based lists of ``linear_attn_config``."""
    linear = config["linear_attn_config"]
    kinds = []
    for i in range(1, config["num_hidden_layers"] + 1):
        assert (i in linear["kda_layers"]) != (i in linear["full_attn_layers"])
        kinds.append("kda" if i in linear["kda_layers"] else "full_attention")
    return tuple(kinds)


def score_width(config: Dict[str, Any]) -> int:
    return config["qk_nope_head_dim"] + config["qk_rope_head_dim"]


def scan_flops_per_token(config: Dict[str, Any]) -> int:
    """One KDA layer's recurrence, forward, one token, as the chunked form's
    dense matmuls at chunks of ``C`` (``ops/kda.py``): a head's ``L`` and
    ``A`` (each ``C x C`` over ``dk``), the solve counted as one ``C x C`` by
    ``C x (dk + dv)`` product, the intra-chunk product ``A U`` (``C x C`` by
    ``C x dv``), and ``W S_0``, ``q S_0`` and the state's update (``C x dk``
    by ``dk x dv`` each): ``2 C (2 dk + (dk + dv) + dv) + 6 dk dv`` a head."""
    linear = config["linear_attn_config"]
    heads, d, c = linear["num_heads"], linear["head_dim"], config["kda_chunk"]
    return heads * (2 * c * (2 * d + 2 * d + d) + 6 * d * d)


def mixer_params(config: Dict[str, Any]) -> Dict[str, int]:
    """The matmul parameters of one layer's mixer, by kind; a KDA layer's
    include its three convolutions and no scan."""
    d, h = config["hidden_size"], config["num_attention_heads"]
    dn, dv, rank = (config["qk_nope_head_dim"], config["v_head_dim"],
                    config["kv_lora_rank"])
    dr = config["qk_rope_head_dim"]
    linear = config["linear_attn_config"]
    heads, dk = linear["num_heads"], linear["head_dim"]
    inner = heads * dk
    return {
        # wq, wdkv, wukv, wo
        "full_attention": d * h * (dn + dr) + d * (rank + dr)
        + rank * h * (dn + dv) + h * dv * d,
        # q, k, v, o; the two low-rank maps; b; the convolutions
        "kda": 4 * d * inner + 2 * (d * dk + dk * inner) + d * heads
        + 3 * linear["short_conv_kernel_size"] * inner}


def shape(config: Dict[str, Any], chips: int) -> Dict[str, int]:
    d, h = config["hidden_size"], config["num_attention_heads"]
    seq = config["flops_counted_at_seq"]
    kinds = layer_kinds(config)
    mixers = mixer_params(config)
    sparse = (d * n_experts(config) + 3 * d * shared_width(config)
              + config["num_experts_per_token"] * held(config)[1]
              * 3 * d * config["moe_intermediate_size"] // n_experts(config))
    total = sum(
        mixers[kind] + (scan_flops_per_token(config) // 2
                        if kind == "kda" else 0)
        + (3 * d * config["intermediate_size"] if is_dense(config, i)
           else sparse) for i, kind in enumerate(kinds))
    # q.k and p.v of the MLA layers, as a multiple of the d_model the formula
    # counts; the rest as equivalent parameters
    scores = sum(1 for kind in kinds if kind != "kda") \
        * h * (score_width(config) + config["v_head_dim"]) // 2
    n_layer = scores // d
    total += (scores - n_layer * d) * seq
    return {"d_model": d, "n_layer": n_layer, "n_head": h,
            "n_kv_head": config["num_key_value_heads"],
            "head_dim": score_width(config), "vocab": config["vocab_size"],
            "layer_mm_params": total // n_layer}


def model_config(config: Dict[str, Any], chips: int):
    """Activations bf16, parameters, the router, the log-decays and the
    scan's state float32, flash attention, the Pallas grouped matmul and the
    scan's kernels: the program's defaults, stated in the configuration
    file."""
    from ray_tpu.models.llama import LlamaConfig

    assert config["q_lora_rank"] is None and config["rope_scaling"] is None
    assert config["num_expert_group"] == config["topk_group"] == 1
    assert config["mla_use_nope"] and not config["num_nextn_predict_layers"]
    remat, layers = config["remat"], config["num_hidden_layers"]
    linear = config["linear_attn_config"]
    return LlamaConfig(
        vocab_size=config["vocab_size"],
        n_positions=config["model_max_length"],
        d_model=config["hidden_size"], n_layer=layers,
        n_head=config["num_attention_heads"],
        n_kv_head=config["num_key_value_heads"],
        d_ff=config["intermediate_size"],
        rope=not config["mla_use_nope"],
        rope_theta=float(config["rope_theta"]),
        rms_eps=float(config["rms_norm_eps"]), remat=bool(remat),
        remat_policy=remat or "full",
        layer_types=layer_kinds(config),
        kda_n_heads=linear["num_heads"], kda_head_dim=linear["head_dim"],
        kda_d_conv=linear["short_conv_kernel_size"],
        kda_chunk=config["kda_chunk"],
        kv_lora_rank=config["kv_lora_rank"],
        qk_nope_head_dim=config["qk_nope_head_dim"],
        qk_rope_head_dim=config["qk_rope_head_dim"],
        v_head_dim=config["v_head_dim"],
        mlp_types=tuple("dense" if is_dense(config, i) else "sparse"
                        for i in range(layers)),
        n_experts=n_experts(config),
        moe_top_k=config["num_experts_per_token"],
        d_expert=config["moe_intermediate_size"],
        norm_topk_prob=bool(config["moe_renormalize"]),
        experts_held=held(config),
        router_scoring=config["moe_router_activation_func"],
        routed_scale=float(config["routed_scaling_factor"]),
        d_shared_expert=shared_width(config),
        router_aux_weight=0.0, router_z_weight=0.0)


# --------------------------------------------------------------- the layers
def swiglu(y, m):
    import jax

    gate, up, down = (m[name]["kernel"] for name in
                      ("gate_proj", "up_proj", "down_proj"))
    return (jax.nn.silu(y @ gate) * (y @ up)) @ down


def _delayed(x, k: int):
    """``x`` (B, S, C) ``k`` positions later, zeros moving in."""
    import jax.numpy as jnp

    return x if k == 0 else jnp.pad(x, ((0, 0), (k, 0), (0, 0)))[:, :-k]


def kda(y, p, config: Dict[str, Any], wrong: Optional[str] = None):
    """One layer's KDA on its normed input ``y`` (B, S, hidden) with the
    layer's ``kda`` parameters ``p``, ``Wo`` included."""
    import jax
    import jax.numpy as jnp

    from perfbench.harness.reference import dense, rms_norm

    linear = config["linear_attn_config"]
    heads, d = linear["num_heads"], linear["head_dim"]
    taps = linear["short_conv_kernel_size"]
    batch, seq, _ = y.shape
    r = _rounded(wrong)

    def conv(x, kernel):
        if wrong == "no_conv":
            return jax.nn.silu(x)
        return jax.nn.silu(sum(kernel[taps - 1 - k] * _delayed(x, k)
                               for k in range(taps)))

    def by_head(x):
        return x.reshape(batch, seq, heads, d)

    def unit(x):
        if wrong == "no_l2norm":
            return x
        return x * jax.lax.rsqrt(jnp.sum(x * x, axis=-1, keepdims=True)
                                 + L2_EPS)

    q = unit(by_head(conv(dense(y, p["q_proj"]), p["q_conv"])))
    if wrong != "q_unscaled":
        q = q * d ** -0.5
    k = unit(by_head(conv(dense(y, p["k_proj"]), p["k_conv"])))
    v = by_head(conv(dense(y, p["v_proj"]), p["v_conv"]))
    q, k, v = r(q), r(k), r(v)
    g = -jnp.exp(p["A_log"])[:, None] * jax.nn.softplus(by_head(
        dense(r(dense(y, p["f_a"])), p["f_b"]) + p["dt_bias"]))
    if wrong == "no_decay":
        g = jnp.zeros_like(g)
    if wrong == "head_decay":       # the scalar-gated delta rule
        g = jnp.broadcast_to(jnp.mean(g, axis=-1, keepdims=True), g.shape)
    b = jax.nn.sigmoid(dense(y, p["b_proj"]))               # (B, S, heads)
    if wrong == "beta_1":
        b = jnp.ones_like(b)

    @jax.checkpoint
    def recurrence(q, k, v, g, b):
        def step(S, at):
            q_t, k_t, v_t, g_t, b_t = at
            a_t = jnp.exp(g_t)[..., None]
            bk = (b_t[..., None] * k_t)[..., None]
            if wrong == "no_delta":
                S = a_t * S + bk * v_t[..., None, :]
            elif wrong == "decay_after_correction":
                S = a_t * (S - bk * jnp.einsum(
                    "bhd,bhde->bhe", k_t, S)[..., None, :]) \
                    + bk * v_t[..., None, :]
            else:
                S = a_t * S
                S = S + bk * (v_t - jnp.einsum("bhd,bhde->bhe", k_t, S)
                              )[..., None, :]
            return S, jnp.einsum("bhde,bhd->bhe", S, q_t)

        _, o = jax.lax.scan(
            step, jnp.zeros((batch, heads, d, d), q.dtype),
            tuple(jnp.moveaxis(t, 1, 0) for t in (q, k, v, g, b)))
        return jnp.moveaxis(o, 0, 1)

    o = r(recurrence(q, k, v, g, b))
    if wrong != "no_out_norm":
        o = rms_norm(o, p["o_norm"], config["rms_norm_eps"])
    gate = by_head(dense(r(dense(y, p["g_a"])), p["g_b"]))
    gate = jax.nn.silu(gate) if wrong == "silu_out_gate" \
        else jax.nn.sigmoid(gate)
    return dense(r((o * gate).reshape(batch, seq, heads * d)), p["o_proj"])


def latent_attention(y, a, config: Dict[str, Any],
                     wrong: Optional[str] = None):
    """One layer's MLA without rotation on its normed input ``y`` with the
    layer's ``attn`` parameters ``a``, ``Wo`` included."""
    import jax
    import jax.numpy as jnp

    from perfbench.harness.reference import NEG, heads, rms_norm, rope

    h, rank = config["num_attention_heads"], config["kv_lora_rank"]
    dn = config["qk_nope_head_dim"]
    r = _rounded(wrong)
    q = heads(y @ a["wq"]["kernel"], h)                    # (B, H, S, 192)
    down = y @ a["wdkv"]["kernel"]                         # (B, S, 512 + 64)
    c, kr = down[..., :rank], down[:, None, :, rank:]      # kr: (B, 1, S, 64)
    c = rms_norm(c, a["kv_norm"], config["rms_norm_eps"])
    kv = heads(r(c) @ a["wukv"]["kernel"], h)              # (B, H, S, 256)
    kn, v = kv[..., :dn], kv[..., dn:]
    if wrong == "mla_rope":
        theta = float(config["rope_theta"])
        kr = rope(kr, theta)
        q = jnp.concatenate([q[..., :dn], rope(q[..., dn:], theta)], axis=-1)
    # the one un-rotated key part of a position, for every head
    kr = kr[:, jnp.zeros(h, jnp.int32)]
    if wrong == "own_kr":   # a head's own: the vector rolled by its index
        kr = jnp.stack([jnp.roll(kr[:, i], i, axis=-1) for i in range(h)], 1)
    q, k = r(q), r(jnp.concatenate([kn, kr], axis=-1))
    s = q.shape[2]
    seen = jnp.arange(s)[:, None] >= jnp.arange(s)[None, :]
    scores = jnp.einsum("bhqd,bhkd->bhqk", q, k) * q.shape[-1] ** -0.5
    att = jnp.einsum("bhqk,bhkd->bhqd", jax.nn.softmax(
        jnp.where(seen, scores, NEG), axis=-1), r(v))
    return r(att).transpose(0, 2, 1, 3).reshape(y.shape[0], s, -1) \
        @ a["wo"]["kernel"]


def sparse_parts(y, m, config: Dict[str, Any], first: int,
                 wrong: Optional[str] = None):
    """One sparse layer's feed-forward on ``y`` as the chip holding experts
    ``first .. first + count - 1`` computes it, ``m`` holding their matrices
    (count, ., .), the whole router and the shared expert: every held expert
    on every token, masked by the top-k set.  -> (the routed part, the shared
    expert's, which experts each token chose as 0/1 over all of them)."""
    import jax
    import jax.numpy as jnp

    k, scale = (config["num_experts_per_token"],
                config["routed_scaling_factor"])
    if wrong == "top_6":
        k = 6
    if wrong == "routed_scale_1":
        scale = 1.0
    count = m["gate_proj"].shape[0]
    router = y @ m["router"]["kernel"]
    score = jax.nn.softmax(router, axis=-1) if wrong == "softmax_scores" \
        else jax.nn.sigmoid(router)
    _, idx = jax.lax.top_k(score, k)
    chosen = jnp.sum(jax.nn.one_hot(idx, n_experts(config)), axis=-2)
    weight = score * chosen
    if wrong != "no_renorm":
        weight = weight / jnp.sum(weight, axis=-1, keepdims=True)
    mine = scale * weight[..., first:first + count]
    r = _rounded(wrong)
    hidden = r(jax.nn.silu(jnp.einsum("bsd,edf->bsef", y, m["gate_proj"]))
               * jnp.einsum("bsd,edf->bsef", y, m["up_proj"]))
    routed = r(jnp.einsum("bsef,efd,bse->bsd", hidden, m["down_proj"], mine))
    return routed, r(swiglu(y, m["shared"])), chosen


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
    for i, kind in enumerate(layer_kinds(config)):
        p = params[f"h_{i}"]
        y = r(rms_norm(x, p["attn_norm"], eps))
        if kind == "kda":
            x = r(x + r(kda(y, p["kda"], config, wrong)))
        else:
            x = r(x + r(latent_attention(y, p["attn"], config, wrong)))
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

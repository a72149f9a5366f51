"""Qwen3-Next-80B-A3B (``Qwen/Qwen3-Next-80B-A3B-Instruct``, ``model_type``
``qwen3_next``): Gated DeltaNet in three layers of four beside gated softmax
attention, every layer's feed-forward 512 softmax-routed experts (top-10)
beside one shared expert under a sigmoid gate.  The program's side is
``ray_tpu/models/llama.py`` with ``layer_types`` (``"gdn"``: ``models/gdn.py``
over ``ops/gdn.py``'s chunked scan), ``attn_gate="channel"``, ``qk_norm="head"``
under ``norm_unit_offset``, a ``rope_tables`` entry that turns a quarter of a
head, and ``models/moe.py``'s ``RoutedSwiGLU`` with ``shared_gate``.

Every RMSNorm of the stack, ``q_norm`` and ``k_norm`` are ``x * rsqrt(mean x^2
+ eps) * (1 + w)``, ``w`` from zero; eps 1e-6; no bias anywhere.  With ``n``
the block's normed input, layer ``i`` (from one) is full attention where ``i %
full_attention_interval == 0`` and else a **Gated DeltaNet layer**: ``K`` = 16
key heads, ``H`` = 32 value heads of ``d`` = 128, value head ``h`` reading key
head ``h // 2``, per position ``t``:

    [q_j | k_j | v_2j, v_2j+1 | z_2j, z_2j+1] = (W_qkvz n)_j      [b_2j, b_2j+1 | a_2j, a_2j+1] = (W_ba n)_j
    q, k, v <- silu(conv4(.))      one causal depthwise convolution, width 4, no bias, over their 8,192 channels
    q_t = l2norm(q)_j / sqrt(d)    k_t = l2norm(k)_j        l2norm(x) = x * rsqrt(sum x^2 + 1e-6)
    g_t = -exp(A_log_h) * softplus(a_h + dt_bias_h)          ONE log-decay a value head
    beta_t = sigmoid(b_h)
    S_t = (I - beta_t k_t k_t^T) e^(g_t) S_{t-1} + beta_t k_t v_t^T      S in R^(d x d), S_0 = 0
    o_t = S_t^T q_t
    m   = W_o [RMSNorm_d(o_t) * w * silu(z_h)]_h     the norm BEFORE the gate; one plain scale of d for all heads

a **gated attention layer**: ``W_q n`` is 16 heads x 512, a head's 256 of
query then 256 of gate logits; ``W_k``, ``W_v`` 2 heads x 256; ``q_norm``,
``k_norm`` a head; the first 64 of a head's 256 lanes turned (rotate-half
inside those 64, theta 1e7), the rest passed; causal softmax at ``256 **
-0.5``; ``m = W_o [attn * sigmoid(gate)]``, a gate a channel.  Every layer's
feed-forward: ``p = softmax(W_r n2)`` over all 512 in float32, ``S =
top10(p)``, ``w_e = p_e / sum_{e' in S} p_e'``, ``y = h + sigmoid(w_s . n2)
Shared(n2) + sum_{e in S, held here} w_e E_e(n2)``, SwiGLU experts 512 wide.
Then the final norm and the untied head over the held rows of the vocabulary;
next-token cross entropy.

Plain on purpose: the recurrence is a ``lax.scan`` over single positions that
carries ``S_t`` — no chunks, no solve —, the convolution four shifted
multiply-adds, attention a dense boolean mask with the key/value heads
repeated, every held expert on every token masked by the top-10 set; nothing
of ``ray_tpu``.  Each layer's recurrence is under ``jax.checkpoint`` (its
backward pass would keep a 32 x 128 x 128 state a position).  ``WRONG`` names
the wrong models the on-chip script and the CPU tests hold the limits
against, ``UNSEEN_IN_BF16`` those that only the CPU's float32 comparison can
see, ``PRECISION_BELOW`` this reference with its activations in float8.

**The FLOP count.**  ``flops.train_flops_per_token`` charges causal scores
``d_model`` wide to ``n_layer`` layers.  A ``gdn`` layer has none; the one
attention layer's are 16 x 256 = 4,096 = 2 x 2,048 wide, so ``shape`` hands
the formula ``n_layer`` = 2 an attention layer of the cut, and in
``layer_mm_params`` every matmul of the cut: the mixers' projections, the
convolution (4 multiply-adds a channel), the router, the shared expert with
its gate, ``top_k * held / n_experts`` = 0.625 held experts a token (routing
at balance: stated, not measured) and each ``gdn`` layer's scan as
``scan_flops_per_token / 2``.
"""

from __future__ import annotations

from typing import Any, Dict, Optional, Tuple

# the router's width, the experts held, a SwiGLU and float8's rounding are
# what they are in the other held-experts delta-rule family
from perfbench.harness.families.kimi_linear import (L2_EPS, PRECISION_BELOW,
                                                    _rounded, held, n_experts,
                                                    swiglu)

WRONG = ("no_decay", "beta_1", "no_delta", "decay_after_correction",
         "no_l2norm", "q_unscaled", "no_conv", "gate_before_norm",
         "sigmoid_out_gate", "key_head_mod", "whole_head_rope",
         "no_attn_gate", "head_attn_gate", "no_unit_offset",
         "sigmoid_scores", "top_8", "no_renorm", "shared_ungated")
# Of those, what the comparison on the chip cannot see, though the float32
# tests on the CPU do (the readings are in the configuration file's
# reference.why).
UNSEEN_IN_BF16 = ()
# (PRECISION_BELOW: the right model in the nearest precision below the
# configuration's bf16 activations, every activation the program holds in
# bf16 rounded to float8)


def layer_kinds(config: Dict[str, Any]) -> Tuple[str, ...]:
    """Each layer's mixer: layer ``i``, from one, is ``"full_attention"``
    where ``i % full_attention_interval == 0`` and ``"gdn"`` elsewhere."""
    every = config["full_attention_interval"]
    return tuple("full_attention" if i % every == 0 else "gdn"
                 for i in range(1, config["num_hidden_layers"] + 1))


def gdn_sizes(config: Dict[str, Any]) -> Tuple[int, int, int]:
    """(key heads, value heads, a head's width)."""
    assert config["linear_key_head_dim"] == config["linear_value_head_dim"]
    return (config["linear_num_key_heads"], config["linear_num_value_heads"],
            config["linear_key_head_dim"])


def scan_flops_per_token(config: Dict[str, Any]) -> int:
    """One ``gdn`` layer's recurrence, forward, one token, as the chunked
    form's dense matmuls at chunks of ``C`` (``ops/gdn.py``): a key head's
    ``K K^T`` and ``Q K^T`` (each ``C x C`` over ``d``), and a value head's
    solve counted as one ``C x C`` by ``C x d`` product, ``A U`` (the same),
    and ``K S_0``, ``Q S_0`` and the state's update (``C x d`` by ``d x d``
    each): ``4 C d`` a key head + ``4 C d + 6 d d`` a value head."""
    keys, heads, d = gdn_sizes(config)
    c = config["gdn_chunk"]
    return keys * 4 * c * d + heads * (4 * c * d + 6 * d * d)


def mixer_params(config: Dict[str, Any]) -> Dict[str, int]:
    """The matmul parameters of one layer's mixer, by kind; a ``gdn`` layer's
    include its convolution and no scan."""
    e, h, kv, hd = (config["hidden_size"], config["num_attention_heads"],
                    config["num_key_value_heads"], config["head_dim"])
    keys, heads, d = gdn_sizes(config)
    return {
        # wq (query and gate), wk, wv, wo
        "full_attention": e * 2 * h * hd + 2 * e * kv * hd + h * hd * e,
        # qkvz, ba, the convolution, out
        "gdn": e * (2 * keys + 2 * heads) * d + e * 2 * heads
        + config["linear_conv_kernel_dim"] * (2 * keys + heads) * d
        + heads * d * e}


def shape(config: Dict[str, Any], chips: int) -> Dict[str, int]:
    e, h = config["hidden_size"], config["num_attention_heads"]
    kinds = layer_kinds(config)
    mixers = mixer_params(config)
    sparse = (e * n_experts(config)
              + 3 * e * config["shared_expert_intermediate_size"] + e
              + config["num_experts_per_tok"] * held(config)[1]
              * 3 * e * config["moe_intermediate_size"] // n_experts(config))
    total = sum(mixers[kind] + sparse + (
        scan_flops_per_token(config) // 2 if kind == "gdn" else 0)
        for kind in kinds)
    # q.k and p.v of the attention layers, as a multiple of the d_model the
    # formula counts
    scores = sum(1 for kind in kinds if kind != "gdn") * h * config["head_dim"]
    assert scores % e == 0
    n_layer = scores // e
    return {"d_model": e, "n_layer": n_layer, "n_head": h,
            "n_kv_head": config["num_key_value_heads"],
            "head_dim": config["head_dim"], "vocab": config["vocab_size"],
            "layer_mm_params": total // n_layer}


def model_config(config: Dict[str, Any], chips: int):
    """Activations bf16, parameters, the router, the log-decays and the
    scan's state float32, flash attention, the Pallas grouped matmul and the
    scan's kernels: the program's defaults, stated in the configuration
    file."""
    from ray_tpu.models.llama import LlamaConfig, RopeTable

    assert config["rope_scaling"] is None and not config["mlp_only_layers"]
    assert config["decoder_sparse_step"] == 1
    assert not config["use_sliding_window"]
    assert not config["tie_word_embeddings"]
    remat, layers = config["remat"], config["num_hidden_layers"]
    keys, heads, d = gdn_sizes(config)
    return LlamaConfig(
        vocab_size=config["vocab_size"],
        n_positions=config["max_position_embeddings"],
        d_model=config["hidden_size"], n_layer=layers,
        n_head=config["num_attention_heads"],
        n_kv_head=config["num_key_value_heads"],
        head_dim=config["head_dim"], d_ff=config["intermediate_size"],
        rope_theta=float(config["rope_theta"]),
        rope_tables=(("full_attention", RopeTable(
            theta=float(config["rope_theta"]),
            rotary_fraction=float(config["partial_rotary_factor"]))),),
        rms_eps=float(config["rms_norm_eps"]), remat=bool(remat),
        remat_policy=remat or "full",
        qk_norm="head", norm_unit_offset=True, attn_gate="channel",
        layer_types=layer_kinds(config),
        kda_n_heads=heads, gdn_key_heads=keys, kda_head_dim=d,
        kda_d_conv=config["linear_conv_kernel_dim"],
        kda_chunk=config["gdn_chunk"],
        mlp_types=("sparse",) * layers,
        n_experts=n_experts(config),
        moe_top_k=config["num_experts_per_tok"],
        d_expert=config["moe_intermediate_size"],
        norm_topk_prob=bool(config["norm_topk_prob"]),
        experts_held=held(config), router_scoring="softmax",
        d_shared_expert=config["shared_expert_intermediate_size"],
        shared_expert_gate=True,
        router_aux_weight=0.0, router_z_weight=0.0)


# --------------------------------------------------------------- the layers
def norm(x, p, eps, wrong: Optional[str] = None):
    """``x * rsqrt(mean x^2 + eps) * (1 + w)``."""
    import jax
    import jax.numpy as jnp

    scale = p["scale"] if wrong == "no_unit_offset" else 1.0 + p["scale"]
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * scale


def _delayed(x, k: int):
    """``x`` (B, S, ..) ``k`` positions later, zeros moving in."""
    import jax.numpy as jnp

    if k == 0:
        return x
    return jnp.pad(x, ((0, 0), (k, 0)) + ((0, 0),) * (x.ndim - 2))[:, :-k]


def gdn(y, p, config: Dict[str, Any], wrong: Optional[str] = None):
    """One layer's Gated DeltaNet on its normed input ``y`` (B, S, hidden)
    with the layer's ``gdn`` parameters ``p``, ``W_o`` included.  The
    projections' and the convolution's kernels hold their columns a key head
    at a time, (., K, columns a key head), as the published checkpoint."""
    import jax
    import jax.numpy as jnp

    keys, heads, d = gdn_sizes(config)
    r, taps = heads // keys, config["linear_conv_kernel_dim"]
    batch, seq, _ = y.shape
    rnd = _rounded(wrong)
    qkvz = jnp.einsum("bse,ekc->bskc", y, p["in_proj_qkvz"]["kernel"])
    ba = jnp.einsum("bse,ekc->bskc", y, p["in_proj_ba"]["kernel"])
    qkv, z = qkvz[..., :(2 + r) * d], qkvz[..., (2 + r) * d:]
    if wrong != "no_conv":
        kernel = p["conv_kernel"]                     # (taps, K, (2 + r) d)
        qkv = sum(kernel[taps - 1 - k] * _delayed(qkv, k)
                  for k in range(taps))
    qkv = jax.nn.silu(qkv)

    def unit(x):
        if wrong == "no_l2norm":
            return x
        return x * jax.lax.rsqrt(jnp.sum(x * x, -1, keepdims=True) + L2_EPS)

    q, k = unit(qkv[..., :d]), unit(qkv[..., d:2 * d])        # (B, S, K, d)
    if wrong != "q_unscaled":
        q = q * d ** -0.5
    v = qkv[..., 2 * d:].reshape(batch, seq, heads, d)
    z = z.reshape(batch, seq, heads, d)
    b, a = (t.reshape(batch, seq, heads) for t in (ba[..., :r], ba[..., r:]))
    # value head h reads key head h // r
    of = jnp.arange(heads) % keys if wrong == "key_head_mod" \
        else jnp.arange(heads) // r
    q, k, v = rnd(q[:, :, of]), rnd(k[:, :, of]), rnd(v)
    g = -jnp.exp(p["A_log"]) * jax.nn.softplus(a + p["dt_bias"])
    if wrong == "no_decay":
        g = jnp.zeros_like(g)
    beta = jax.nn.sigmoid(b)
    if wrong == "beta_1":
        beta = jnp.ones_like(beta)

    @jax.checkpoint
    def recurrence(q, k, v, g, beta):
        def step(S, at):
            q_t, k_t, v_t, g_t, b_t = at
            a_t = jnp.exp(g_t)[..., None, None]
            bk = (b_t[..., None] * k_t)[..., None]
            if wrong == "no_delta":
                S = a_t * S + bk * v_t[..., None, :]
            elif wrong == "decay_after_correction":
                # (one decay a head commutes with the correction: the wrong
                # order decays what the step writes as well)
                S = a_t * (S + bk * (v_t - jnp.einsum(
                    "bhd,bhde->bhe", k_t, S))[..., None, :])
            else:
                S = a_t * S
                S = S + bk * (v_t - jnp.einsum("bhd,bhde->bhe", k_t, S)
                              )[..., None, :]
            return S, jnp.einsum("bhde,bhd->bhe", S, q_t)

        _, o = jax.lax.scan(
            step, jnp.zeros((batch, heads, d, d), q.dtype),
            tuple(jnp.moveaxis(t, 1, 0) for t in (q, k, v, g, beta)))
        return jnp.moveaxis(o, 0, 1)

    o = rnd(recurrence(q, k, v, g, beta))
    gate = jax.nn.sigmoid(z) if wrong == "sigmoid_out_gate" \
        else jax.nn.silu(z)

    def normed(o):
        return o * jax.lax.rsqrt(jnp.mean(o * o, -1, keepdims=True)
                                 + config["rms_norm_eps"]) \
            * p["o_norm"]["scale"]

    o = normed(o * gate) if wrong == "gate_before_norm" else normed(o) * gate
    return rnd(o.reshape(batch, seq, heads * d)) @ p["out_proj"]["kernel"]


def gated_attention(y, a, config: Dict[str, Any],
                    wrong: Optional[str] = None):
    """One layer's gated attention on its normed input ``y`` with the layer's
    ``attn`` parameters ``a``, ``W_o`` included."""
    import jax
    import jax.numpy as jnp

    from perfbench.harness.reference import NEG, heads, rope

    h, kv, d = (config["num_attention_heads"], config["num_key_value_heads"],
                config["head_dim"])
    eps, batch, s = config["rms_norm_eps"], y.shape[0], y.shape[1]
    rnd = _rounded(wrong)
    both = (y @ a["wq"]["kernel"]).reshape(batch, s, h, 2 * d)
    q = both[..., :d].transpose(0, 2, 1, 3)                  # (B, H, S, d)
    gate = both[..., d:]                                     # (B, S, H, d)
    k, v = (heads(y @ a[name]["kernel"], kv) for name in ("wk", "wv"))
    q, k = norm(q, a["q_norm"], eps, wrong), norm(k, a["k_norm"], eps, wrong)
    rot = d if wrong == "whole_head_rope" \
        else int(d * config["partial_rotary_factor"])
    theta = float(config["rope_theta"])

    def turned(x):
        return jnp.concatenate([rope(x[..., :rot], theta), x[..., rot:]], -1)

    q, k = rnd(turned(q)), rnd(turned(k))
    group = jnp.arange(h) // (h // kv)
    k, v = k[:, group], rnd(v)[:, group]
    seen = jnp.arange(s)[:, None] >= jnp.arange(s)[None, :]
    scores = jnp.einsum("bhqd,bhkd->bhqk", q, k) * d ** -0.5
    att = rnd(jnp.einsum("bhqk,bhkd->bhqd", jax.nn.softmax(
        jnp.where(seen, scores, NEG), axis=-1), v)).transpose(0, 2, 1, 3)
    if wrong == "head_attn_gate":
        gate = jnp.broadcast_to(jnp.mean(gate, -1, keepdims=True), gate.shape)
    if wrong != "no_attn_gate":
        att = att * jax.nn.sigmoid(gate)
    return rnd(att.reshape(batch, s, h * d)) @ a["wo"]["kernel"]


def sparse_parts(y, m, config: Dict[str, Any], first: int,
                 wrong: Optional[str] = None):
    """One layer's feed-forward on ``y`` as the chip holding experts ``first
    .. first + count - 1`` computes it, ``m`` holding their matrices (count,
    ., .), the whole router and the shared expert with its gate: every held
    expert on every token, masked by the top-k set.  -> (the routed part,
    the gated shared expert's, which experts each token chose as 0/1 over
    all of them)."""
    import jax
    import jax.numpy as jnp

    k = 8 if wrong == "top_8" else config["num_experts_per_tok"]
    count = m["gate_proj"].shape[0]
    router = y @ m["router"]["kernel"]
    score = jax.nn.sigmoid(router) if wrong == "sigmoid_scores" \
        else jax.nn.softmax(router, axis=-1)
    _, idx = jax.lax.top_k(score, k)
    chosen = jnp.sum(jax.nn.one_hot(idx, n_experts(config)), axis=-2)
    weight = score * chosen
    if config["norm_topk_prob"] and wrong != "no_renorm":
        weight = weight / jnp.sum(weight, axis=-1, keepdims=True)
    mine = weight[..., first:first + count]
    rnd = _rounded(wrong)
    hidden = rnd(jax.nn.silu(jnp.einsum("bsd,edf->bsef", y, m["gate_proj"]))
                 * jnp.einsum("bsd,edf->bsef", y, m["up_proj"]))
    routed = rnd(jnp.einsum("bsef,efd,bse->bsd", hidden, m["down_proj"],
                            mine))
    shared = swiglu(y, m["shared"])
    if wrong != "shared_ungated":
        shared = shared * jax.nn.sigmoid(y @ m["shared"]["gate"]["kernel"])
    return routed, rnd(shared), chosen


def _forward(params, ids, config: Dict[str, Any],
             wrong: Optional[str] = None):
    """``ids`` (B, S) -> (logits over the rows the head has, padding
    included; each layer's assignments to the held experts)."""
    import jax.numpy as jnp

    eps = config["rms_norm_eps"]
    first, count = held(config)
    rnd = _rounded(wrong)
    x = rnd(params["wte"]["embedding"][ids])
    rows_held = []
    for i, kind in enumerate(layer_kinds(config)):
        p = params[f"h_{i}"]
        y = rnd(norm(x, p["attn_norm"], eps, wrong))
        if kind == "gdn":
            x = rnd(x + rnd(gdn(y, p["gdn"], config, wrong)))
        else:
            x = rnd(x + rnd(gated_attention(y, p["attn"], config, wrong)))
        y = rnd(norm(x, p["mlp_norm"], eps, wrong))
        routed, shared, chosen = sparse_parts(y, p["moe"], config, first,
                                              wrong)
        rows_held.append(jnp.sum(chosen[..., first:first + count]))
        x = rnd(x + routed + shared)
    x = rnd(norm(x, params["norm_f"], eps, wrong))
    return rnd(x @ params["lm_head"]["kernel"]), rows_held


def logits(params, ids, config: Dict[str, Any]):
    return _forward(params, ids, config)[0][..., :config["vocab_size"]]


def logits_loss_gradnorm(params, ids, targets, config: Dict[str, Any],
                         wrong: Optional[str] = None):
    """``reference.logits_loss_gradnorm`` under a wrong model or the
    precision below, with the held experts' assignments a layer (their mean)
    beside it."""
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

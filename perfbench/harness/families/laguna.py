"""Laguna (``poolside/Laguna-XS.2``, ``model_type: laguna``): window and full
attention layers in one stack, each kind with its own head count and rotary
table, a gate on the attention's output, one leading dense layer, and then
sigmoid-routed experts beside a shared one.  The program's side is
``ray_tpu/models/llama.py`` with ``layer_types`` (``full_attention`` /
``sliding_attention``), ``n_head_per_layer``, ``rope_tables``, ``attn_gate``
and ``mlp_types``, over ``ops/attention.py``'s window and ``models/moe.py``'s
``RoutedSwiGLU`` with ``scoring="sigmoid"``, ``routed_scale``, ``d_shared``
and ``experts_held``.

Per layer ``l``, ``n1 = RMSNorm(x)``, ``n2 = RMSNorm(h)``, eps 1e-6, ``H_l =
num_attention_heads_per_layer[l]`` query heads (48 full, 64 sliding), 8
key/value heads, head width 128, no bias:

    q = R_l(heads_{H_l}(Wq n1)),  k = R_l(heads_8(Wk n1)),  v = heads_8(Wv n1)
    a = Attn_{M_l}(q, k, v)              scores / sqrt(128), H_l / 8 query heads a key/value head
    g = sigmoid(Wg n1) in R^{H_l};  a_h <- g_h a_h           (``gating``: assumed form)
    h = x + Wo a
    dense layer:   y = h + Wdown( silu(Wgate n2) * Wup n2 )                    width 8192
    sparse layer:  s = sigmoid(Wr n2) in R^256;  S = top8(s);  w_e = 2.5 s_e / sum_{e' in S} s_e'
                   y = h + Shared(n2) + sum_{e in S, e held here} w_e E_e(n2)  Shared, E_e: SwiGLU of width 512

``M_full``: key ``j <= i``.  ``M_sliding``: ``i - 512 < j <= i``.
``R_sliding``: rotate-half RoPE at theta 1e4 over all 128 dimensions.
``R_full``: the first 64 dimensions of a head rotated, the other 64 passed
through; the 32 inverse frequencies by YaRN (theta 5e5, factor 64 from 4,096
positions: a frequency that turns more than ``beta_fast`` = 64 times in 4,096
positions is kept, one that turns fewer than ``beta_slow`` = 1 times is
divided by 64, a linear ramp over the dimensions between), cos and sin times
``attention_factor``.  ``w_e`` is normalised over all eight chosen experts,
held or not, and multiplies the experts' outputs; what the absent experts
would add is left out and the partial ``y`` goes on (the chip's share of a
layer that eight chips hold: model-configs guide, section 4).  Then the final
RMSNorm and the untied head over the held rows of the vocabulary; the
objective is next-token cross entropy.

Plain on purpose: dense boolean masks from indices, the rotary tables written
out, every held expert on every token masked by the top-8 set; no kernel, no
sort, no grouped matmul.  ``WRONG`` names the wrong models the on-chip script
and the CPU tests hold the limits against (``UNSEEN_IN_BF16``: the one of
them that only the CPU's float32 comparison can see), ``PRECISION_BELOW`` this
reference with its activations in float8: the second of the two readings a
limit is set between.

**The FLOP count.**  ``flops.train_flops_per_token`` is ``6 * (n_layer *
layer_mm_params + d_model * vocab) + 6 * n_layer * seq * d_model``; its second
term is the causal scores of ``n_layer`` layers whose heads together are
``d_model`` wide.  The two full layers' heads are 6,144 wide, so ``shape``
hands it ``n_layer`` = 2 x 6144 / 2048 = 6 and the second term charges exactly
their causal triangle.  ``layer_mm_params`` is every other operation of the
cut as parameters (a matmul parameter is 6 FLOPs a token), over that 6: the
projections, the gate, layer 0's dense feed-forward, the router, the shared
expert, ``top_k * held / n_experts`` = 1 held expert a token (routing at
balance: stated, not measured), and the sliding layers' scores over the
band's **live pairs only** — ``sum_i min(i + 1, window)`` a row, which
depends on the sequence length, so it is counted at the cell's
(``flops_counted_at_seq`` in the configuration file) — as ``2 * pairs * H *
128 / seq`` equivalent parameters a layer.  (The sum is not a multiple of 6:
the floor loses 24 of 2.4e9 FLOPs a token.)
"""

from __future__ import annotations

import math
from typing import Any, Dict, Optional, Tuple

from perfbench.harness.flash_work import band_pairs

WRONG = ("no_window", "window_513", "rope_tables_swapped", "no_gate",
         "softmax_scores", "routed_scale_1", "no_shared_expert", "top_7")
# Of those, what the comparison on the chip cannot see, though the float32
# tests on the CPU do: at initialisation attention is close to uniform, so one
# key more of 513 moves a head's output by about 0.2%, a tenth of what bf16
# moves the logits of a dense-only stack (0.022 relative RMS) and a fortieth of
# what it moves this one's (0.09: configs/laguna-xs.2.json, reference.why).
UNSEEN_IN_BF16 = ("window_513",)
# not a wrong model but the right one in the nearest precision below the
# configuration's bf16 activations: every activation that the program holds in
# bf16 rounded to float8 (e4m3) instead.  The limits must refuse it too.
PRECISION_BELOW = "fp8_activations"

KINDS = ("full_attention", "sliding_attention")


def _rounded(wrong: Optional[str]):
    if wrong != PRECISION_BELOW:
        return lambda x: x
    import jax.numpy as jnp

    return lambda x: x.astype(jnp.float8_e4m3fn).astype(jnp.float32)


def n_experts(config: Dict[str, Any]) -> int:
    """The router's width: the published count, whatever part is held."""
    return config["published_counts"]["num_experts"]


def held(config: Dict[str, Any]) -> Tuple[int, int]:
    """(first index, count) of the experts this chip holds of every layer:
    ``num_experts`` of the file is the count held."""
    count = config["num_experts"]
    return config["deployment"]["this_chip"] * count, count


def shape(config: Dict[str, Any], chips: int) -> Dict[str, int]:
    """(The per-layer lists may be longer than the depth that is run: the
    first ``num_hidden_layers`` entries count, here and in ``_forward``.)"""
    d, hd = config["hidden_size"], config["head_dim"]
    kv = config["num_key_value_heads"]
    seq = config["flops_counted_at_seq"]
    count = held(config)[1]
    sparse = (d * n_experts(config)
              + 3 * d * config["shared_expert_intermediate_size"]
              + config["num_experts_per_tok"] * count
              * 3 * d * config["moe_intermediate_size"] // n_experts(config))
    total = full_width = 0
    for kind, h, mlp in zip(config["layer_types"],
                            config["num_attention_heads_per_layer"],
                            config["mlp_layer_types"]):
        # wq, wo; wk, wv at the key/value heads; the gate
        total += 2 * d * h * hd + 2 * d * kv * hd + d * h * config["gating"]
        if kind == "sliding_attention":
            total += 2 * band_pairs(seq, config["sliding_window"]) * h * hd \
                // seq
        else:
            full_width += h * hd
        total += sparse if mlp == "sparse" else 3 * d * config[
            "intermediate_size"]
    assert full_width % d == 0, (full_width, d)
    return {"d_model": d, "n_layer": full_width // d,
            "n_head": config["num_attention_heads"],
            "n_kv_head": kv, "head_dim": hd, "vocab": config["vocab_size"],
            "layer_mm_params": total // (full_width // d),
            # a sliding layer's band and its own head count, for
            # ``flash_work.py``
            "window": config["sliding_window"],
            "window_n_head": config["num_attention_heads_per_layer"][
                config["layer_types"].index("sliding_attention")]}


def model_config(config: Dict[str, Any], chips: int):
    """Activations bf16, parameters and the router float32, flash attention,
    the Pallas grouped matmul: the program's defaults, stated in the
    configuration file."""
    from ray_tpu.models.llama import LlamaConfig, RopeTable

    remat, router = config["remat"], config["assumed"]["router"]

    def table(p):
        return RopeTable(
            theta=float(p["rope_theta"]),
            rotary_fraction=float(p["partial_rotary_factor"]),
            factor=float(p.get("factor", 1.0)),
            original_positions=p.get("original_max_position_embeddings", 0),
            beta_fast=float(p.get("beta_fast", 32)),
            beta_slow=float(p.get("beta_slow", 1)),
            attention_factor=float(p.get("attention_factor", 1.0)))

    return LlamaConfig(
        vocab_size=config["vocab_size"],
        n_positions=config["max_position_embeddings"],
        d_model=config["hidden_size"], n_layer=config["num_hidden_layers"],
        n_head=config["num_attention_heads"],
        n_kv_head=config["num_key_value_heads"], head_dim=config["head_dim"],
        d_ff=config["intermediate_size"],
        rms_eps=float(config["rms_norm_eps"]), remat=bool(remat),
        remat_policy=remat or "full",
        layer_types=tuple(config["layer_types"]),
        n_head_per_layer=tuple(config["num_attention_heads_per_layer"][
            :config["num_hidden_layers"]]),
        mlp_types=tuple(config["mlp_layer_types"]),
        sliding_window=config["sliding_window"],
        rope_tables=tuple((kind, table(config["rope_parameters"][kind]))
                          for kind in KINDS),
        attn_gate=bool(config["gating"]),
        n_experts=n_experts(config), moe_top_k=config["num_experts_per_tok"],
        d_expert=config["moe_intermediate_size"],
        norm_topk_prob=bool(router["norm_topk_prob"]),
        experts_held=held(config), router_scoring=router["scoring"],
        routed_scale=float(config["moe_routed_scaling_factor"]),
        d_shared_expert=config["shared_expert_intermediate_size"],
        router_aux_weight=0.0, router_z_weight=0.0)


# ---------------------------------------------------------------- the layer
def inverse_frequencies(p: Dict[str, Any], rot: int):
    """The ``rot / 2`` inverse frequencies of one ``rope_parameters`` group."""
    import jax.numpy as jnp

    theta = float(p["rope_theta"])
    inv = theta ** (-jnp.arange(0, rot, 2, dtype=jnp.float32) / rot)
    if p["rope_type"] == "default":
        return inv
    assert p["rope_type"] == "yarn", p["rope_type"]
    positions = p["original_max_position_embeddings"]

    def dimension_turning(times):   # so often in the original context
        return rot * math.log(positions / (times * 2 * math.pi)) \
            / (2 * math.log(theta))
    low = max(math.floor(dimension_turning(p["beta_fast"])), 0)
    high = min(math.ceil(dimension_turning(p["beta_slow"])), rot - 1)
    # 0 up to ``low`` (kept), 1 from ``high`` (divided by the factor)
    ramp = jnp.clip((jnp.arange(rot // 2, dtype=jnp.float32) - low)
                    / (high - low if high > low else 1e-3), 0.0, 1.0)
    return inv * (1.0 - ramp) + inv / p["factor"] * ramp


def rotary(x, p: Dict[str, Any]):
    """x (..., S, D) under one ``rope_parameters`` group: the first
    ``partial_rotary_factor`` of D rotated (rotate-half inside that part)."""
    import jax.numpy as jnp

    s, d = x.shape[-2], x.shape[-1]
    rot = int(d * p["partial_rotary_factor"])
    angle = jnp.arange(s, dtype=jnp.float32)[:, None] \
        * inverse_frequencies(p, rot)[None, :]
    cos = jnp.cos(angle) * p.get("attention_factor", 1.0)
    sin = jnp.sin(angle) * p.get("attention_factor", 1.0)
    x1, x2, rest = x[..., :rot // 2], x[..., rot // 2:rot], x[..., rot:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin, rest],
                           axis=-1)


def seen(s: int, window: Optional[int]):
    """The (S, S) boolean mask from indices: row = query i, column = key j."""
    import jax.numpy as jnp

    i, j = jnp.arange(s)[:, None], jnp.arange(s)[None, :]
    return (j <= i) if window is None else (j <= i) & (i - j < window)


def swiglu(y, m):
    import jax

    from perfbench.harness.reference import dense

    return dense(jax.nn.silu(dense(y, m["gate_proj"]))
                 * dense(y, m["up_proj"]), m["down_proj"])


def sparse_parts(y, m, config: Dict[str, Any], first: int,
                 wrong: Optional[str] = None):
    """One sparse layer's feed-forward on ``y`` as the chip holding experts
    ``first .. first + count - 1`` computes it, ``m`` holding their matrices
    (count, ., .), the whole router and the shared expert: every held expert
    on every token, masked by the top-k set.  -> (the routed part, the shared
    expert's, which experts each token chose as 0/1 over all of them)."""
    import jax
    import jax.numpy as jnp

    k, scale = config["num_experts_per_tok"], \
        config["moe_routed_scaling_factor"]
    if wrong == "top_7":
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
    shared = 0.0 if wrong == "no_shared_expert" else r(swiglu(y, m["shared"]))
    return routed, shared, chosen


def _forward(params, ids, config: Dict[str, Any],
             wrong: Optional[str] = None):
    """``ids`` (B, S) -> (logits over the rows the head has, padding
    included; each sparse layer's assignments to the held experts)."""
    import jax
    import jax.numpy as jnp

    from perfbench.harness.reference import (NEG, dense, heads, merge,
                                             rms_norm)

    eps, kv = config["rms_norm_eps"], config["num_key_value_heads"]
    first, count = held(config)
    r = _rounded(wrong)
    x = r(params["wte"]["embedding"][ids])
    rows_held = []
    for i, (kind, h, mlp) in enumerate(zip(
            config["layer_types"], config["num_attention_heads_per_layer"],
            config["mlp_layer_types"])):
        p = params[f"h_{i}"]
        a, y = p["attn"], r(rms_norm(x, p["attn_norm"], eps))
        sliding = kind == "sliding_attention"
        # (KINDS: full, sliding)
        table = config["rope_parameters"][
            KINDS[sliding != (wrong == "rope_tables_swapped")]]
        window = None
        if sliding and wrong != "no_window":
            window = config["sliding_window"] + (wrong == "window_513")
        q = r(rotary(heads(dense(y, a["wq"]), h), table))
        k = r(rotary(heads(dense(y, a["wk"]), kv), table))
        v = r(heads(dense(y, a["wv"]), kv))
        b, _, s, hd = q.shape
        scores = jnp.einsum("bgrqd,bgkd->bgrqk",
                            q.reshape(b, kv, h // kv, s, hd), k) * hd ** -0.5
        att = jnp.einsum("bgrqk,bgkd->bgrqd", jax.nn.softmax(
            jnp.where(seen(s, window), scores, NEG), axis=-1), v)
        if config["gating"] and wrong != "no_gate":
            gate = jax.nn.sigmoid(dense(y, a["wg"]))        # (B, S, H)
            att = att * gate.transpose(0, 2, 1).reshape(
                b, kv, h // kv, s)[..., None]
        x = r(x + r(dense(merge(r(att)), a["wo"])))

        y = r(rms_norm(x, p["mlp_norm"], eps))
        if mlp == "sparse":
            routed, shared, chosen = sparse_parts(y, p["moe"], config, first,
                                                  wrong)
            rows_held.append(jnp.sum(chosen[..., first:first + count]))
            x = r(x + routed + shared)
        else:
            x = r(x + r(swiglu(y, p["mlp"])))
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

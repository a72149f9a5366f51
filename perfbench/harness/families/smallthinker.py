"""SmallThinker (``PowerInfer/SmallThinker-21BA3B-Instruct``, ``model_name:
smallthinker_21b_instruct``): a decoder built for inference where the experts
do not fit fast memory.  Every layer's feed-forward is routed, and **the
router reads the block's input before attention**, so a token's experts are
known while attention runs; the experts are gated by a ReLU (ReGLU); a layer
at every fourth index from 0 attends over the whole row without rotation
(NoPE), the three after it under a window with RoPE; 28 query heads over 4
key/value heads.  The program's side is ``ray_tpu/models/llama.py`` with
``layer_types`` (``full_attention`` / ``sliding_attention``), ``rope_tables``
with no table for the whole-row kind, ``router_before_attention`` and
``expert_activation="relu"``, over ``models/moe.py``'s ``RoutedSwiGLU``
(``router_input``, ``activation``, ``experts_held``) and
``ops/attention.py``'s window and grouped-query form.

Per layer ``l`` (``n1``, ``n2`` RMSNorm with a learned scale, eps 1e-6; no
bias anywhere; ``H`` = 28 query heads, 4 key/value heads, heads 128 wide):

    n1 = RMSNorm(x)
    r  = Wr n1                            (hidden -> 64, float32)   <- the router reads n1, not n2
    idx = top6(r);  w = softmax(r[idx])   over the six chosen logits
    q = Wq n1 (28 x 128),  k = Wk n1,  v = Wv n1 (4 x 128 each)
    rope_layout[l] == 0:  q, k unrotated
    rope_layout[l] == 1:  q, k <- RoPE(theta 1.5e6, rotate-half, all 128)
    sliding_window_layout[l] == 0:  a = softmax_causal(q k^T / sqrt(128)) v     every earlier position
    sliding_window_layout[l] == 1:  the same over the query and the 4,095 before it
    query head h reads key/value head h // 7
    x <- x + Wo a
    n2 = RMSNorm(x)
    x <- x + sum_{e in idx, e held here} w_e Wdown_e( relu(Wgate_e n2) * Wup_e n2 )
    logits = Whead RMSNorm(x_L)           over the held rows of the vocabulary

``w`` is normalised over all six chosen experts, held or not; what the absent
experts would add is left out and the partial sum goes on (the chip's share of
a layer that four chips hold: model-configs guide, section 4).  The objective
is next-token cross entropy.

Plain on purpose, and nothing of ``ray_tpu``: the router's top-k then softmax
as published (the program takes a softmax over all 64, its top-6, and divides
by their sum: the same six and the same weights, which a tier-1 test holds),
a dense boolean mask from indices, rotate-half written out
(``reference.rope``), key/value head ``h // 7`` by indexing, every held expert
on every token with the absent 48 left out (as the Laguna family writes its
own: a loop over sixteen bodies compiled for five minutes and kept sixteen
(tokens, 2560) results for the weights' gradient).  Two things are not
mathematics, both for memory at 8,192 positions on the chip beside the
trainer's state: the scores are taken ``Q_BLOCK`` queries at a time
(``lax.map``: each block against all the keys, under its rows of the mask),
and a block of queries, like a layer, is under ``jax.checkpoint`` so that its
backward keeps no (queries, keys) array of an earlier block.  ``WRONG`` names
the wrong models the on-chip script and the CPU tests hold the limits
against, ``UNSEEN_IN_BF16`` those of them that only the CPU's float32
comparison can see, ``PRECISION_BELOW`` this reference with its activations
in float8: the second of the two readings a limit is set between.

**The FLOP count.**  ``flops.train_flops_per_token`` is ``6 * (n_layer *
layer_mm_params + d_model * vocab) + 6 * n_layer * seq * d_model``; its second
term is the causal scores of ``n_layer`` layers whose heads together are
``d_model`` wide.  No layer here has heads ``d_model`` wide (28 x 128 = 3,584
against 2,560), so ``shape`` hands the formula ``n_layer`` = 1 and folds
everything into ``layer_mm_params`` as equivalent parameters (a matmul
parameter is 6 FLOPs a token) at the cell's length
(``flops_counted_at_seq``): every matmul of the cut — the projections, the
router, ``top_k * held / n_experts`` = 1.5 held experts a token (routing at
balance: stated, not measured) — and each layer's scores over its own mask's
live pairs, ``2 * pairs * H * 128 / seq`` a layer, ``band_pairs(seq, 4096)``
under the window and the causal triangle ``seq * (seq + 1) / 2`` over the
whole row, less the ``seq * d_model`` the formula's second term already
charges.
"""

from __future__ import annotations

from typing import Any, Dict, Optional, Tuple

from perfbench.harness.families.laguna import band_pairs

WRONG = ("router_from_n2", "router_from_x", "silu", "gelu",
         "softmax_all_no_renorm", "sigmoid_scores", "top_5", "no_window",
         "window_4097", "rope_everywhere", "no_rope", "theta_1e4",
         "layouts_shifted", "kv_head_mod", "eighth_vocab")
# Of those, what the comparison on the chip cannot see, though the float32
# tests on the CPU do (the readings are in the configuration file's
# ``reference.why``).
UNSEEN_IN_BF16 = ("window_4097",)
# not a wrong model but the right one in the nearest precision below the
# configuration's bf16 activations: every activation that the program holds in
# bf16 rounded to float8 (e4m3) instead.  The limits must refuse it too.
PRECISION_BELOW = "fp8_activations"

Q_BLOCK = 256       # queries a block of the scores; a shorter row is one block


def _rounded(wrong: Optional[str]):
    if wrong != PRECISION_BELOW:
        return lambda x: x
    import jax.numpy as jnp

    return lambda x: x.astype(jnp.float8_e4m3fn).astype(jnp.float32)


def n_experts(config: Dict[str, Any]) -> int:
    """The router's width: the published count, whatever part is held."""
    return config["published_counts"]["moe_num_primary_experts"]


def held(config: Dict[str, Any]) -> Tuple[int, int]:
    """(first index, count) of the experts this chip holds of every layer:
    ``moe_num_primary_experts`` of the file is the count held."""
    count = config["moe_num_primary_experts"]
    return config["deployment"]["this_chip"] * count, count


def layer_pairs(config: Dict[str, Any], layer: int, seq: int) -> int:
    """Live (query, key) pairs of one row in ``layer``, by its own mask."""
    if config["sliding_window_layout"][layer]:
        return band_pairs(seq, config["sliding_window_size"])
    return seq * (seq + 1) // 2


def shape(config: Dict[str, Any], chips: int) -> Dict[str, int]:
    d, hd = config["hidden_size"], config["head_dim"]
    h, kv = config["num_attention_heads"], config["num_key_value_heads"]
    seq, layers = config["flops_counted_at_seq"], config["num_hidden_layers"]
    # wq, wo; wk, wv at the key/value heads; the router; the held experts a
    # token meets at balance
    matmuls = 2 * d * h * hd + 2 * d * kv * hd + d * n_experts(config) \
        + config["moe_num_active_primary_experts"] * held(config)[1] \
        * 3 * d * config["moe_ffn_hidden_size"] // n_experts(config)
    scores = sum(2 * layer_pairs(config, layer, seq) * h * hd // seq
                 for layer in range(layers))
    # (the formula's own second term, 6 * 1 * seq * d_model, is taken off)
    return {"d_model": d, "n_layer": 1, "n_head": h, "n_kv_head": kv,
            "head_dim": hd, "vocab": config["vocab_size"],
            "layer_mm_params": layers * matmuls + scores - seq * d,
            # a window layer's band, for ``flash_work.py``
            "window": config["sliding_window_size"]}


def layer_kinds(config: Dict[str, Any]) -> Tuple[str, ...]:
    return tuple("sliding_attention" if windowed else "full_attention"
                 for windowed in config["sliding_window_layout"][
                     :config["num_hidden_layers"]])


def model_config(config: Dict[str, Any], chips: int):
    """Activations bf16, parameters and the router float32, flash attention,
    the Pallas grouped matmul: the program's defaults, stated in the
    configuration file.  A kind of layer is turned or not as a whole
    (``rope_tables``): the two layouts name the same layers."""
    from ray_tpu.models.llama import LlamaConfig, RopeTable

    layers = config["num_hidden_layers"]
    if config["rope_layout"][:layers] != config["sliding_window_layout"][
            :layers]:
        raise NotImplementedError(
            "the program turns a kind of layer, windowed or whole-row; "
            "rope_layout and sliding_window_layout name different layers")
    if config["rope_scaling"] is not None or config["tie_word_embeddings"] \
            or not config["moe_primary_router_apply_softmax"]:
        raise NotImplementedError(
            "rope_scaling, a tied head or a router without its softmax")
    remat = config["remat"]
    return LlamaConfig(
        vocab_size=config["vocab_size"],
        n_positions=config["max_position_embeddings"],
        d_model=config["hidden_size"], n_layer=layers,
        n_head=config["num_attention_heads"],
        n_kv_head=config["num_key_value_heads"], head_dim=config["head_dim"],
        rms_eps=float(config["rms_norm_eps"]), remat=bool(remat),
        remat_policy=remat or "full",
        layer_types=layer_kinds(config),
        mlp_types=("sparse",) * layers,
        sliding_window=config["sliding_window_size"],
        rope_tables=(("full_attention", None),
                     ("sliding_attention",
                      RopeTable(theta=float(config["rope_theta"])))),
        n_experts=n_experts(config),
        moe_top_k=config["moe_num_active_primary_experts"],
        d_expert=config["moe_ffn_hidden_size"],
        norm_topk_prob=bool(config["norm_topk_prob"]),
        experts_held=held(config), router_scoring="softmax",
        router_before_attention=True, expert_activation="relu",
        router_aux_weight=0.0, router_z_weight=0.0)


# ---------------------------------------------------------------- the layer
def seen(first: int, rows: int, s: int, window: Optional[int]):
    """Rows ``first .. first + rows - 1`` of the (S, S) boolean mask from
    indices: row = query i, column = key j."""
    import jax.numpy as jnp

    i = first + jnp.arange(rows)[:, None]
    j = jnp.arange(s)[None, :]
    return (j <= i) if window is None else (j <= i) & (i - j < window)


def attend(q, k, v, window: Optional[int], of_head):
    """q (B, H, S, D), k and v (B, KV, S, D): query head ``h`` against
    key/value head ``of_head(h)``, softmax over the keys the mask leaves,
    ``Q_BLOCK`` queries at a time."""
    import jax
    import jax.numpy as jnp

    from perfbench.harness.reference import NEG

    h, s, d = q.shape[1], q.shape[2], q.shape[3]
    which = jnp.asarray([of_head(i) for i in range(h)])
    k, v = k[:, which], v[:, which]            # (B, H, S, D), by indexing

    @jax.checkpoint
    def block(first):
        q_rows = jax.lax.dynamic_slice_in_dim(q, first, step, axis=2)
        scores = jnp.einsum("bhqd,bhkd->bhqk", q_rows, k) * d ** -0.5
        mask = seen(first, step, s, window)
        return jnp.einsum("bhqk,bhkd->bhqd", jax.nn.softmax(
            jnp.where(mask, scores, NEG), axis=-1), v)

    step = Q_BLOCK if s % Q_BLOCK == 0 else s
    blocks = jax.lax.map(block, jnp.arange(0, s, step))  # (n, B, H, step, D)
    return jnp.moveaxis(blocks, 0, 2).reshape(q.shape)


def routing(r, config: Dict[str, Any], wrong: Optional[str] = None):
    """The router's logits (..., 64) -> each token's weight on every expert,
    zero on those it did not choose, and the choice as 0/1: top-k of the
    logits, then a softmax over the chosen (the published order)."""
    import jax
    import jax.numpy as jnp

    k = config["moe_num_active_primary_experts"] - (wrong == "top_5")
    n = n_experts(config)
    if wrong == "softmax_all_no_renorm":
        top, idx = jax.lax.top_k(jax.nn.softmax(r, axis=-1), k)
    elif wrong == "sigmoid_scores":
        top, idx = jax.lax.top_k(jax.nn.sigmoid(r), k)
        top = top / jnp.sum(top, axis=-1, keepdims=True)
    else:
        top, idx = jax.lax.top_k(r, k)
        top = jax.nn.softmax(top, axis=-1)
    chosen = jax.nn.one_hot(idx, n)                     # (..., k, 64)
    return jnp.einsum("...k,...ke->...e", top, chosen), jnp.sum(chosen, -2)


def held_experts(y, weight, m, first: int, wrong: Optional[str] = None):
    """``sum_e w_e Wdown_e(relu(Wgate_e y) * Wup_e y)`` over the experts
    ``m`` holds (count, ., .), the ``e``-th of them the layer's expert
    ``first + e``: every held expert on every token, weighted by what the
    token's routing gave it (zero where it was not chosen); no sort, no
    grouped matmul.  -> (the sum, the share of the chosen tokens' gate units
    that are exactly zero)."""
    import jax
    import jax.numpy as jnp

    r = _rounded(wrong)
    act = getattr(jax.nn, wrong) if wrong in ("silu", "gelu") else jax.nn.relu
    count = m["gate_proj"].shape[0]
    mine = weight[..., first:first + count]
    gate = jnp.einsum("bsd,edf->bsef", y, m["gate_proj"])
    hidden = r(act(gate) * jnp.einsum("bsd,edf->bsef", y, m["up_proj"]))
    out = r(jnp.einsum("bsef,efd,bse->bsd", hidden, m["down_proj"], mine))
    took = (mine > 0)[..., None]
    zeros = jnp.sum((gate <= 0) * took) / jnp.maximum(
        jnp.sum(took) * gate.shape[-1], 1)
    return out, zeros


def _forward(params, ids, config: Dict[str, Any],
             wrong: Optional[str] = None):
    """``ids`` (B, S) -> (logits over the rows the head has, padding
    included; each layer's assignments to the held experts; each layer's
    share of the held rows' gate units at zero)."""
    import jax
    import jax.numpy as jnp

    from perfbench.harness.reference import dense, heads, rms_norm, rope

    eps, kv = config["rms_norm_eps"], config["num_key_value_heads"]
    h = config["num_attention_heads"]
    theta = 1e4 if wrong == "theta_1e4" else float(config["rope_theta"])
    first, count = held(config)
    r = _rounded(wrong)

    def of_head(i):
        return i % kv if wrong == "kv_head_mod" else i // (h // kv)

    def layer(x, p, turned: bool, window: Optional[int]):
        a, m = p["attn"], p["moe"]
        n1 = r(rms_norm(x, p["attn_norm"], eps))
        q, k, v = (heads(dense(n1, a[name]), n)
                   for name, n in (("wq", h), ("wk", kv), ("wv", kv)))
        if turned:
            q, k = rope(q, theta), rope(k, theta)
        att = r(attend(r(q), r(k), r(v), window, of_head))
        b, _, s, hd = att.shape
        after = r(x + r(dense(att.transpose(0, 2, 1, 3).reshape(b, s, h * hd),
                              a["wo"])))
        n2 = r(rms_norm(after, p["mlp_norm"], eps))
        # the router reads the attention's own input
        reads = {"router_from_n2": n2, "router_from_x": x}.get(wrong, n1)
        weight, chosen = routing(reads @ m["router"]["kernel"], config, wrong)
        routed, zero = held_experts(n2, weight, m, first, wrong)
        return (r(after + routed), jnp.sum(chosen[..., first:first + count]),
                zero)

    x = r(params["wte"]["embedding"][ids])
    rows_held, gate_zero = [], []
    for i in range(config["num_hidden_layers"]):
        at = (i + 1) % len(config["rope_layout"]) \
            if wrong == "layouts_shifted" else i
        turned = bool(config["rope_layout"][at])
        windowed = bool(config["sliding_window_layout"][at])
        if wrong in ("rope_everywhere", "no_rope"):
            turned = wrong == "rope_everywhere"
        window = None
        if windowed and wrong != "no_window":
            window = config["sliding_window_size"] + (wrong == "window_4097")
        x, rows, zero = jax.checkpoint(
            lambda x, p: layer(x, p, turned, window))(x, params[f"h_{i}"])
        rows_held.append(rows)
        gate_zero.append(zero)
    x = r(rms_norm(x, params["norm_f"], eps))
    out = r(x @ params["lm_head"]["kernel"])
    if wrong == "eighth_vocab":
        # a chip of eight: the second half of these rows is not its own
        out = jnp.where(jnp.arange(out.shape[-1]) < config["vocab_size"] // 2,
                        out, out - 30.0)
    return out, rows_held, gate_zero


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
        out, rows_held, _ = _forward(p, ids, config, wrong)
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

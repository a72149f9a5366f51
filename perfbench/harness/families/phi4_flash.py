"""Phi-4-mini-flash-reasoning (``microsoft/Phi-4-mini-flash-reasoning``,
``model_type`` ``phi4flash``; SambaY, arXiv:2507.06607, at 3.8B with
differential attention): a *self-decoder* of Mamba-1 layers and differential
attention under a window, one Mamba-1 layer that hands on its scan's output
``m``, one differential attention layer over the whole row that hands on its
keys and values, and a *cross-decoder* of gated memory units that read ``m``
and differential cross-attention layers that have a query projection alone
and read those keys and values.  No rotation anywhere, LayerNorm, a head tied
to the table.  The program's side is ``ray_tpu/models/llama.py`` with
``layer_types`` (``"mamba1"``, ``"gmu"``: ``models/mamba.py`` over
``ops/selective_scan.py``; ``"cross_attention"``), ``producers``,
``diff_attn`` (``DifferentialAttention``), ``norm="layer"`` and
``attn_bias``.

By published layer index ``i`` of ``L`` = 32 layers (zero-based; ``half`` =
``L / 2``): ``i`` even and ``i <= half``: Mamba-1, ``i == half`` handing on
``m``; ``i`` odd and ``i < half``: window attention; ``i == half + 1``: full
attention handing on K and V; ``i`` even and ``i > half``: a gated memory
unit; ``i`` odd and ``i > half + 1``: cross-attention.  With ``n =
LayerNorm(x)``, ``E`` the model's width, ``d = 2 E`` channels, ``N`` = 16
states a channel, ``R = ceil(E / 16)``, heads ``D`` = 64 wide:

    Mamba-1:   [u ; z] = W_in n
               u = silu(conv4(u) + b_conv)        causal depthwise, 4 wide, one kernel a channel
               [r ; B_t ; C_t] = W_x u            B_t, C_t in R^N, shared by all channels
               D_t = softplus(W_dt r + b_dt)      one step size a CHANNEL
               h_t[c, j] = exp(D_t[c] A[c, j]) h_{t-1}[c, j] + D_t[c] B_t[j] u_t[c]     A = -exp(A_log), h_0 = 0
               y_t[c] = sum_j C_t[j] h_t[c, j] + Dskip[c] u_t[c]
               out = W_out (y * silu(z));         the layer at ``half`` hands on m = y
    GMU:       out = W_out2 (silu(W_in2 n) * m)
    diff attn: [q ; k ; v] = W_qkv n + b;  a cross layer has W_q alone and takes k, v from layer half + 1
               q1, q2 = the even, the odd query heads;  k1, k2 = the even, the odd key heads;  V_g = [v_2g ; v_2g+1]
               a1 = softmax_mask(q1 k1^T / sqrt(D)) V,  a2 = softmax_mask(q2 k2^T / sqrt(D)) V
                    mask: causal, or causal within the window (the query and the window - 1 before it)
               lam = exp(lq1 . lk1) - exp(lq2 . lk2) + lam0,   lam0 = 0.8 - 0.6 exp(-0.3 i)
               o = (1 - lam0) RMSNorm_2D(a1 - lam a2);   out = W_o o + b_o
    every layer:  x <- x + mixer(LayerNorm(x));   x <- x + W_down(silu(W_gate n2) * W_up n2),  n2 = LayerNorm(x)

Plain on purpose: the recurrence is a ``lax.scan`` over single positions that
carries ``h`` (d x N) — no blocks, no running products —, the convolution is
four shifted multiply-adds, each softmax a dense boolean mask over (S, S), q1,
q2, k1, k2 and V taken by indexing, LayerNorm written out; nothing of
``ray_tpu``.  One thing is not mathematics: each recurrence is under
``jax.checkpoint``, as Granite's and Kimi-Linear's are and for the same
reason (its backward pass would keep a d x N state a position).  ``WRONG``
names the wrong models the on-chip script and the CPU tests hold the limits
against, ``UNSEEN_IN_BF16`` those of them that only the CPU's float32
comparison can see, ``PRECISION_BELOW`` this reference with its activations
in float8.

The cut keeps the layers ``layers_kept`` names by their published indices
(which the kinds and ``lam0`` are read from); the parameter tree's ``h_<k>``
is the ``k``-th of them.

**The FLOP count.**  ``flops.train_flops_per_token`` is ``6 * (n_layer *
layer_mm_params + d_model * vocab) + 6 * n_layer * seq * d_model``: its second
term charges causal scores ``d_model`` wide to ``n_layer`` layers.  A
differential attention layer over the whole row has two softmaxes of ``H / 2``
heads, each with scores ``D`` wide over values ``2 D`` wide: ``2 x H/2 x (D +
2 D) / 2 = 1.5 H D`` = 1.5 ``d_model`` of the formula's width; a window layer
has the share of the causal triangle its band leaves.  ``shape`` hands the
formula ``n_layer`` = the whole multiples of ``d_model`` those come to and
folds the rest into ``layer_mm_params`` as equivalent parameters (a matmul
parameter is 6 FLOPs a token) at the cell's length
(``flops_counted_at_seq``), beside every matmul of the cut: the mixers'
projections, the depthwise convolutions (4 multiply-adds a channel) and each
scan as ``scan_ops_per_token / 2`` (its elementwise operations, counted as
the matmuls' FLOPs are: a multiply-add is two).
"""

from __future__ import annotations

import math
from typing import Any, Dict, Optional, Tuple

WRONG = ("scalar_decay", "head_dt", "no_softplus", "no_skip", "no_conv",
         "gate_before_scan", "m_after_gate", "m_from_first_scan",
         "gmu_sigmoid", "cross_kv_window_layer", "cross_own_kv", "lam_zero",
         "lam_lam0", "no_sub_norm", "no_one_minus_lam0", "lam0_by_kept_index",
         "v_one_head", "window_513", "rope", "rms_norm", "quarter_vocab")
# Of those, what the comparison on the chip cannot see, though the float32
# tests on the CPU do (the readings are in the configuration file's
# reference.why).
UNSEEN_IN_BF16 = ("window_513",)
# not a wrong model but the right one in the nearest precision below the
# configuration's bf16 activations: every activation that the program holds in
# bf16 rounded to float8 (e4m3) instead.  The limits must refuse it too.
PRECISION_BELOW = "fp8_activations"
NEG = -1e30
DT_HEAD = 64    # the channels a step size is shared by under ``head_dt``


def _rounded(wrong: Optional[str]):
    if wrong != PRECISION_BELOW:
        return lambda x: x
    import jax.numpy as jnp

    return lambda x: x.astype(jnp.float8_e4m3fn).astype(jnp.float32)


def n_published(config: Dict[str, Any]) -> int:
    return config["published_counts"]["num_hidden_layers"]


def layer_kind(i: int, layers: int, mb_per_layer: int = 2) -> str:
    """The kind of published layer ``i`` of ``layers``: ``"mamba1"``,
    ``"sliding_attention"``, ``"full_attention"``, ``"gmu"`` or
    ``"cross_attention"``."""
    half = layers // 2
    if i % mb_per_layer == 0:
        return "mamba1" if i <= half else "gmu"
    if i < half:
        return "sliding_attention"
    return "full_attention" if i == half + 1 else "cross_attention"


def layers_kept(config: Dict[str, Any]) -> Tuple[int, ...]:
    """The published indices of the layers the file keeps, in order."""
    kept = tuple(config.get("layers_kept")
                 or range(config["num_hidden_layers"]))
    assert len(kept) == config["num_hidden_layers"], kept
    return kept


def layer_kinds(config: Dict[str, Any]) -> Tuple[str, ...]:
    return tuple(layer_kind(i, n_published(config), config["mb_per_layer"])
                 for i in layers_kept(config))


def producers(config: Dict[str, Any]) -> Tuple[int, ...]:
    """Where among the kept layers the two that hand on stand: the Mamba-1
    layer at ``half`` and the full attention layer at ``half + 1``."""
    half, kept = n_published(config) // 2, layers_kept(config)
    return tuple(kept.index(i) for i in (half, half + 1) if i in kept)


def sizes(config: Dict[str, Any]) -> Dict[str, int]:
    e = config["hidden_size"]
    mamba = config["assumed"]["mamba"]
    return {"e": e, "d": mamba["expand"] * e, "n": mamba["d_state"],
            "rank": -(-e // 16), "taps": mamba["d_conv"],
            "h": config["num_attention_heads"],
            "kv": config["num_key_value_heads"],
            "hd": e // config["num_attention_heads"]}


def scan_ops_per_token(config: Dict[str, Any]) -> int:
    """One Mamba-1 layer's recurrence, forward, one token, as elementwise
    operations: a state cell takes ``delta * A`` (1), the ``exp`` (1), the
    decay's product with the state (1), ``B_t`` times the written value (1),
    the sum (1), ``C_t`` times the state and its sum into ``y`` (2): 7 a cell;
    a channel ``delta * u`` and ``D u`` with its sum (3)."""
    s = sizes(config)
    return s["d"] * (7 * s["n"] + 3)


def mixer_params(config: Dict[str, Any]) -> Dict[str, int]:
    """The matmul parameters of one layer's mixer, by kind; a Mamba-1
    layer's include its convolution and no scan."""
    s = sizes(config)
    e, d, hd = s["e"], s["d"], s["hd"]
    attention = e * (s["h"] + 2 * s["kv"]) * hd + s["h"] * hd * e
    return {
        # in_proj, the convolution, x_proj, dt_proj, out_proj
        "mamba1": e * 2 * d + s["taps"] * d + d * (s["rank"] + 2 * s["n"])
        + s["rank"] * d + d * e,
        "gmu": 2 * e * d,
        "sliding_attention": attention, "full_attention": attention,
        "cross_attention": 2 * e * s["h"] * hd}


def live_pairs(seq: int, window: int = 0) -> int:
    """(query, key) pairs a causal mask leaves of a row of ``seq``, under a
    window the query and the ``window - 1`` before it."""
    if not window or window >= seq:
        return seq * (seq + 1) // 2
    return window * (window + 1) // 2 + (seq - window) * window


def score_widths(config: Dict[str, Any]) -> Dict[str, float]:
    """Each attention kind's q.k and p.v a token at ``flops_counted_at_seq``
    as a multiple of what the formula charges a layer (``seq * d_model`` wide
    over half the square): two softmaxes of H / 2 heads, D wide over 2 D."""
    s, seq = sizes(config), config["flops_counted_at_seq"]
    wide = 2 * (s["h"] // 2) * (s["hd"] + 2 * s["hd"]) / 2     # in lanes
    full = wide / s["e"]
    # the formula's half square is seq^2 / 2 pairs
    band = full * live_pairs(seq, config["sliding_window"]) / (seq * seq / 2)
    return {"full_attention": full, "cross_attention": full,
            "sliding_attention": band}


def shape(config: Dict[str, Any], chips: int) -> Dict[str, int]:
    s, seq = sizes(config), config["flops_counted_at_seq"]
    kinds, mixers = layer_kinds(config), mixer_params(config)
    widths = score_widths(config)
    total = sum(mixers[kind] + 3 * s["e"] * config["intermediate_size"]
                + (scan_ops_per_token(config) // 2 if kind == "mamba1" else 0)
                for kind in kinds)
    scores = sum(widths.get(kind, 0.0) for kind in kinds)   # x d_model
    n_layer = int(scores)
    total += int((scores - n_layer) * s["e"] * seq)
    return {"d_model": s["e"], "n_layer": n_layer, "n_head": s["h"],
            "n_kv_head": s["kv"], "head_dim": s["hd"],
            "vocab": config["vocab_size"],
            "layer_mm_params": total // n_layer,
            # a sliding layer's kernel call, for ``flash_work.py``: one
            # softmax of the two, half the heads, values two heads wide
            "window": config["sliding_window"],
            "window_n_head": s["h"] // 2, "window_n_kv_head": s["kv"] // 2,
            "v_head_dim": 2 * s["hd"]}


def model_config(config: Dict[str, Any], chips: int):
    """Activations bf16, parameters, the step sizes, the decays and the
    scan's state float32, flash attention and the scan's kernels: the
    program's defaults, stated in the configuration file."""
    from ray_tpu.models.llama import LlamaConfig

    assert config["tie_word_embeddings"] and not config["mlp_bias"]
    assert not config["lm_head_bias"]
    assert not (config["embd_pdrop"] or config["resid_pdrop"])
    s, remat = sizes(config), config["remat"]
    # the program's mamba1 layer has two channels a model dimension
    assert s["d"] == 2 * s["e"]
    return LlamaConfig(
        vocab_size=config["vocab_size"],
        n_positions=config["max_position_embeddings"],
        d_model=s["e"], n_layer=config["num_hidden_layers"],
        n_head=s["h"], n_kv_head=s["kv"], d_ff=config["intermediate_size"],
        rope=False, rms_eps=float(config["layer_norm_eps"]),
        remat=bool(remat), remat_policy=remat or "full",
        layer_types=layer_kinds(config), layer_depths=layers_kept(config),
        producers=producers(config), sliding_window=config["sliding_window"],
        tie_embeddings=True, norm="layer", attn_bias=True, diff_attn=True,
        mamba_d_state=s["n"], mamba_d_conv=s["taps"],
        mamba_chunk=config["scan_block"])


# --------------------------------------------------------------- the layers
def layer_norm(x, p, eps, wrong: Optional[str] = None):
    from perfbench.harness import reference

    if wrong == "rms_norm":
        return reference.rms_norm(x, p, eps)
    return reference.layer_norm(x, p, eps)


def swiglu(y, m):
    import jax

    gate, up, down = (m[name]["kernel"] for name in
                      ("gate_proj", "up_proj", "down_proj"))
    return (jax.nn.silu(y @ gate) * (y @ up)) @ down


def _delayed(x, k: int):
    """``x`` (B, S, C) ``k`` positions later, zeros moving in."""
    import jax.numpy as jnp

    return x if k == 0 else jnp.pad(x, ((0, 0), (k, 0), (0, 0)))[:, :-k]


def mamba1(y, p, config: Dict[str, Any], wrong: Optional[str] = None):
    """One layer's Mamba-1 mixer on its normed input ``y`` (B, S, E) with the
    layer's ``mamba1`` parameters ``p``.  -> (the mixer's output, the scan's
    output before the gate)."""
    import jax
    import jax.numpy as jnp

    s, r = sizes(config), _rounded(wrong)
    taps, rank, n = s["taps"], s["rank"], s["n"]
    batch = y.shape[0]
    u, z = (y @ p["in_proj"]["kernel"][:, i] for i in range(2))
    u, z = r(u), r(z)
    if wrong != "no_conv":
        u = sum(p["conv_kernel"][taps - 1 - k] * _delayed(u, k)
                for k in range(taps)) + p["conv_bias"]
    u = r(jax.nn.silu(u))
    if wrong == "gate_before_scan":
        u = u * jax.nn.silu(z)
    proj = r(u @ p["x_proj"]["kernel"])
    rr, b, c = proj[..., :rank], proj[..., rank:rank + n], proj[..., rank + n:]
    delta = rr @ p["dt_proj"]["kernel"] + p["dt_bias"]
    if wrong != "no_softplus":
        delta = jax.nn.softplus(delta)
    if wrong == "head_dt":      # Mamba-2's form: a step size a head
        delta = jnp.repeat(delta.reshape(*delta.shape[:2], -1, DT_HEAD
                                         ).mean(-1), DT_HEAD, axis=-1)
    a = -jnp.exp(p["A_log"])                                # (d, N)
    if wrong == "scalar_decay":
        a = jnp.broadcast_to(a.mean(-1, keepdims=True), a.shape)

    @jax.checkpoint
    def recurrence(u, delta, b, c):
        def step(h, at):
            u_t, d_t, b_t, c_t = at
            h = jnp.exp(d_t[..., None] * a) * h \
                + (d_t * u_t)[..., None] * b_t[:, None, :]
            return h, jnp.einsum("bdn,bn->bd", h, c_t)

        _, out = jax.lax.scan(
            step, jnp.zeros((batch, *a.shape), u.dtype),
            tuple(jnp.moveaxis(t, 1, 0) for t in (u, delta, b, c)))
        return jnp.moveaxis(out, 0, 1)

    out = recurrence(u, delta, b, c)
    if wrong != "no_skip":
        out = out + p["D"] * u
    out = r(out)
    gated = out if wrong == "gate_before_scan" else r(out * jax.nn.silu(z))
    return gated @ p["out_proj"]["kernel"], \
        (gated if wrong == "m_after_gate" else out)


def gmu(y, p, m, wrong: Optional[str] = None):
    import jax

    r = _rounded(wrong)
    gate = r(y @ p["in_proj"]["kernel"])
    gate = jax.nn.sigmoid(gate) if wrong == "gmu_sigmoid" \
        else jax.nn.silu(gate)
    return r(gate * m) @ p["out_proj"]["kernel"]


def keys_and_values(y, a, config: Dict[str, Any]):
    """A layer's keys and values of its normed input ``y`` by ``wqkv`` of the
    attention parameters ``a``: (B, S, 2 KV D), keys then values."""
    s = sizes(config)
    return (y @ a["wqkv"]["kernel"] + a["wqkv"]["bias"])[..., s["h"] * s["hd"]:]


def diff_attention(y, a, config: Dict[str, Any], depth: int, window: int,
                   kv=None, wrong: Optional[str] = None):
    """One layer's differential attention on its normed input ``y`` with the
    layer's ``attn`` parameters ``a``; ``kv``: another layer's keys and
    values, for a layer that has ``wq`` alone.  -> (the output, the keys and
    values it used)."""
    import jax
    import jax.numpy as jnp

    from perfbench.harness.reference import rope

    s, r = sizes(config), _rounded(wrong)
    h, n_kv, d = s["h"], s["kv"], s["hd"]
    batch, seq, _ = y.shape
    if "wq" in a:
        q = y @ a["wq"]["kernel"] + a["wq"]["bias"]
    else:
        q = (y @ a["wqkv"]["kernel"] + a["wqkv"]["bias"])[..., :h * d]
        kv = keys_and_values(y, a, config)
    q = q.reshape(batch, seq, h, d)
    k = kv[..., :n_kv * d].reshape(batch, seq, n_kv, d)
    v = kv[..., n_kv * d:].reshape(batch, seq, n_kv // 2, 2 * d)
    if wrong == "v_one_head":
        v = jnp.concatenate([v[..., :d], v[..., :d]], axis=-1)
    if wrong == "rope":
        q, k = (rope(t.transpose(0, 2, 1, 3), 10000.0).transpose(0, 2, 1, 3)
                for t in (q, k))
    q, k, v = r(q), r(k), r(v)
    rep = h // n_kv
    at = jnp.arange(seq)
    seen = at[:, None] >= at[None, :]
    if window:
        seen &= at[:, None] - at[None, :] < window
    # query head j of a half reads key head j // rep and value pair j // rep
    group = jnp.arange(h // 2) // rep

    def softmaxed(which):
        scores = jnp.einsum("bqhd,bkhd->bhqk", q[:, :, which::2],
                            k[:, :, which::2][:, :, group]) * d ** -0.5
        return jnp.einsum(
            "bhqk,bkhd->bqhd",
            jax.nn.softmax(jnp.where(seen, scores, NEG), axis=-1),
            v[:, :, group])

    a1, a2 = r(softmaxed(0)), r(softmaxed(1))               # (B, S, H/2, 2D)
    lam0 = 0.8 - 0.6 * math.exp(-0.3 * depth)
    lam = jnp.exp(jnp.sum(a["lambda_q1"] * a["lambda_k1"])) \
        - jnp.exp(jnp.sum(a["lambda_q2"] * a["lambda_k2"])) + lam0
    if wrong == "lam_zero":
        lam = 0.0
    if wrong == "lam_lam0":
        lam = lam0
    o = a1 - lam * a2
    if wrong != "no_sub_norm":
        o = o * jax.lax.rsqrt((o * o).mean(-1, keepdims=True)
                              + config["layer_norm_eps"]) \
            * a["sub_norm"]["scale"]
    if wrong != "no_one_minus_lam0":
        o = o * (1.0 - lam0)
    return r(o).reshape(batch, seq, -1) @ a["wo"]["kernel"] \
        + a["wo"]["bias"], kv


def layers(params, x, config: Dict[str, Any], wrong: Optional[str] = None,
           taps: Optional[Dict[int, Any]] = None):
    """The kept layers on the residual stream ``x`` (B, S, E); ``taps``, where
    given, is filled with each kept layer's input by its published index."""
    eps, r = config["layer_norm_eps"], _rounded(wrong)
    kept, kinds = layers_kept(config), layer_kinds(config)
    window = config["sliding_window"] + (wrong == "window_513")
    m = kv = first_m = window_kv = own = None
    for k, (depth, kind) in enumerate(zip(kept, kinds)):
        p = params[f"h_{k}"]
        if taps is not None:
            taps[depth] = x
        if wrong == "lam0_by_kept_index":
            depth = k
        y = r(layer_norm(x, p["attn_norm"], eps, wrong))
        if kind == "mamba1":
            out, scanned = mamba1(y, p["mamba1"], config, wrong)
            first_m = scanned if first_m is None else first_m
            if k in producers(config):
                m = first_m if wrong == "m_from_first_scan" else scanned
        elif kind == "gmu":
            out = gmu(y, p["gmu"], m, wrong)
        elif kind == "cross_attention":
            given = kv
            if wrong == "cross_kv_window_layer":
                given = window_kv
            if wrong == "cross_own_kv":   # the producer's projection, on
                given = keys_and_values(y, own, config)  # this layer's input
            out, _ = diff_attention(y, p["attn"], config, depth, 0, given,
                                    wrong)
        else:
            out, made = diff_attention(
                y, p["attn"], config, depth,
                window if kind == "sliding_attention" else 0, wrong=wrong)
            if kind == "sliding_attention":
                window_kv = made
            if k in producers(config):
                kv, own = made, p["attn"]
        x = r(x + r(out))
        y = r(layer_norm(x, p["mlp_norm"], eps, wrong))
        x = r(x + r(swiglu(y, p["mlp"])))
    return x


def _forward(params, ids, config: Dict[str, Any],
             wrong: Optional[str] = None):
    """``ids`` (B, S) -> logits over the rows the table has, padding
    included."""
    import jax.numpy as jnp

    r = _rounded(wrong)
    table = params["wte"]["embedding"]
    x = layers(params, r(table[ids]), config, wrong)
    x = r(layer_norm(x, params["norm_f"], config["layer_norm_eps"], wrong))
    out = r(x @ table.T)
    if wrong == "quarter_vocab":
        out = jnp.where(jnp.arange(out.shape[-1]) < config["vocab_size"] // 4,
                        out, out - 30.0)
    return out


def logits(params, ids, config: Dict[str, Any]):
    return _forward(params, ids, config)[..., :config["vocab_size"]]


def logits_loss_gradnorm(params, ids, targets, config: Dict[str, Any],
                         wrong: Optional[str] = None):
    """``reference.logits_loss_gradnorm`` under a wrong model or the
    precision below."""
    import jax
    import jax.numpy as jnp

    from perfbench.harness.reference import global_norm

    def loss_of(p):
        out = _forward(p, ids, config, wrong)[..., :config["vocab_size"]]
        logp = jax.nn.log_softmax(out, axis=-1)
        nll = -jnp.take_along_axis(logp, targets[..., None], axis=-1)
        return nll.mean(), out

    with jax.default_matmul_precision("highest"):
        params = jax.tree_util.tree_map(
            lambda a: a.astype(jnp.float32), params)
        (loss, out), grads = jax.value_and_grad(loss_of, has_aux=True)(params)
    return out, loss, global_norm(grads)

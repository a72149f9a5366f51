"""Nemotron-H as NVIDIA-Nemotron-3-Nano-30B-A3B runs it (``model_type:
nemotron_h``): a stack whose every layer is ONE branch behind one norm,

    x <- x + f(RMSNorm_w(x))          eps 1e-5, no bias but the convolution's

with ``f`` by the letter of ``hybrid_override_pattern``: ``M`` a Mamba-2 mixer,
``*`` attention, ``E`` routed experts beside a shared one; after the last
layer ``norm_f`` and an untied head.  The program's side is
``ray_tpu/models/llama.py`` with ``layer_types`` / ``mlp_types`` entries
``"none"`` (a layer without a mixer, a layer without a feed-forward),
``models/mamba.py``'s mixer with its gated norm a group over ``ops/ssd.py``,
``models/moe.py``'s ``RoutedSwiGLU`` under ``activation="relu2"`` and
``ops/attention.py``'s grouped form.  With ``n`` the layer's normed input:

``M`` (64 heads x 64 = 4096 inner channels, state 128, 8 groups, conv 4):

    z | xBC | dt = W_in n                      4096 | 4096 + 2 x 8 x 128 | 64 columns
    xBC = silu(conv1d(xBC) + b)                causal, depthwise, 4 taps
    X | B | C = xBC                            X: 64 heads x 64; B, C: 8 groups x 128
    dt = softplus(dt + dt_bias)                no clamp (time_step_limit (0, inf))
    a_t = exp(dt_t A),  A = -exp(A_log)        one scalar a head
    h_t = a_t h_{t-1} + dt_t X_t B_t^T         a 64 x 128 state a head; head j reads group j // 8
    y_t = h_t C_t + D X_t
    g = y * silu(z)                            the gate first (norm_before_gate false)
    g <- g / rms(g over each group's 512 channels) * w       w 4096 wide
    f = W_out g

``*`` (32 query heads over 2 key/value heads of 128; nothing is rotated):

    q = Wq n (32 x 128),  k = Wk n,  v = Wv n (2 x 128 each)
    a = softmax_causal(q k^T / sqrt(128)) v    query head j reads key/value head j // 16
    f = Wo a

``E`` (128 experts 1856 wide, top-6; one shared expert 3712 wide):

    r = Wr n (128, float32);  s = sigmoid(r)
    chosen = top6(s + e_score_correction_bias)           the bias for the choice alone
    w_e = 2.5 * s_e / (sum_{chosen} s + 1e-20)           at the chosen, else 0
    f = sum_{e chosen, e held here} w_e Wdown_e relu(Wup_e n)^2  +  Wdown_s relu(Wup_s n)^2

An expert is two matrices: no gate.  ``w`` is normalised over all six chosen,
held or not; what the absent experts would add is left out and the partial
sum goes on (the chip's share of a layer that sixteen chips hold:
model-configs guide, section 4).  The objective is next-token cross entropy
over the held rows of the vocabulary.

Plain on purpose, and nothing of ``ray_tpu``: the recurrence is a ``lax.scan``
over single positions that carries ``h_t`` — no chunk, no mask, no running
sum —, the convolution four shifted multiply-adds, the attention a full masked
softmax with each key/value head copied to its sixteen query heads, every held
expert applied to every token and weighted by what the token's routing gave it
(zero where it was not chosen), the router in the published order (sigmoid,
bias for the choice alone, gather, normalise, scale).  Three things are not
mathematics, all for memory on the chip beside the trainer's state: the
recurrence's positions are walked ``SCAN_BLOCK`` at a time under
``jax.checkpoint`` (its backward would keep a 2 MB state a position a layer),
the scores are taken ``Q_BLOCK`` queries at a time, and each layer is under
``jax.checkpoint``; recomputation in the same precision changes no value.
``WRONG`` names the wrong models that the on-chip script and the CPU tests
hold the limits against, ``UNSEEN_IN_BF16`` those of them that only the CPU's
float32 comparison can see, ``PRECISION_BELOW`` this reference with its
activations in float8: the second of the two readings a limit is set between.

**The FLOP count.**  ``flops.train_flops_per_token`` is ``6 * (n_layer *
layer_mm_params + d_model * vocab) + 6 * n_layer * seq * d_model``, whose
second term is the causal scores of layers whose heads together are
``d_model`` wide.  No layer here is that (32 x 128 = 4,096 against 2,688, and
most layers have no scores), so ``shape`` hands the formula ``n_layer`` = 1 and
folds everything into ``layer_mm_params`` as equivalent parameters (a matmul
parameter is 6 FLOPs a token trained) at the cell's length
(``flops_counted_at_seq``): per ``M`` layer the two projections, the
convolution's 4 multiply-adds a channel and the recurrence as the chunked
algorithm's matmuls (``scan_flops_per_token``: ``C B^T`` once a group, the
masked product with ``X``, the chunk's state and its read-out, over two); per
``*`` layer the four projections and the causal triangle's scores,
``2 * seq * (seq + 1) / 2 * 32 * 128 / seq``; per ``E`` layer the router, the
shared expert and ``top_k * held / n_experts`` = 0.375 held experts a token
(routing at balance: stated, not measured), each two matrices at their
published 1,856; less the ``seq * d_model`` the formula's second term charges.
Recomputation and whatever padding the program chooses are not counted.
"""

from __future__ import annotations

from typing import Any, Dict, Optional, Tuple

from perfbench.harness import ssd_work
# (a shift of positions and a wrong model's running sums, as Granite's
# reference writes them)
from perfbench.harness.families.granite_hybrid import (_delayed,
                                                       _through_running_sums)

KINDS = {"M": "mamba", "*": "attention", "E": "experts"}

WRONG = ("norm_over_all", "one_bc_group", "plain_relu", "gated_expert",
         "parallel_halves", "no_routed_scale", "choice_without_bias",
         "rope_on", "gate_after_norm", "softmax_scores", "top_5",
         "kv_head_mod", "router_bf16", "decays_bf16")
# Of those, what the comparison on the chip cannot see, though the float32
# tests on the CPU do (the readings are in the configuration file's
# ``reference.why``): the selection bias is zeros at the initial weights, and
# the other three move the logits by less than the bf16 program's own flips
# of a sixth and seventh expert do.
UNSEEN_IN_BF16 = ("choice_without_bias", "rope_on", "router_bf16",
                  "decays_bf16")
# not a wrong model but the right one in the nearest precision below the
# configuration's bf16 activations: every activation that the program holds in
# bf16 rounded to float8 (e4m3) instead.  The limits must refuse it too.
PRECISION_BELOW = "fp8_activations"

Q_BLOCK = 256       # queries a block of the scores; a shorter row is one block
SCAN_BLOCK = 64     # positions of the recurrence under one checkpoint


def _rounded(wrong: Optional[str]):
    if wrong != PRECISION_BELOW:
        return lambda x: x
    import jax.numpy as jnp

    return lambda x: x.astype(jnp.float8_e4m3fn).astype(jnp.float32)


def pattern(config: Dict[str, Any]) -> str:
    """The layers this cut runs, a letter each."""
    letters = config["hybrid_override_pattern"][:config["num_hidden_layers"]]
    assert len(letters) == config["num_hidden_layers"] \
        and set(letters) <= set(KINDS), letters
    return letters


def n_experts(config: Dict[str, Any]) -> int:
    """The router's width: the published count, whatever part is held."""
    return config["published_counts"]["n_routed_experts"]


def held(config: Dict[str, Any]) -> Tuple[int, int]:
    """(first index, count) of the experts this chip holds of every ``E``
    layer: ``n_routed_experts`` of the file is the count held."""
    count = config["n_routed_experts"]
    return config["deployment"]["this_chip"] * count, count


def mamba_sizes(config: Dict[str, Any]):
    """(heads, a head's width, groups, the state's width)."""
    return (config["mamba_num_heads"], config["mamba_head_dim"],
            config["n_groups"], config["ssm_state_size"])


def scan_flops_per_token(config: Dict[str, Any]) -> int:
    """One Mamba layer's recurrence, forward, one token, as the chunked
    algorithm's matmuls (``ssd_work.scan_flops_per_token``)."""
    return ssd_work.scan_flops_per_token(*mamba_sizes(config),
                                         config["chunk_size"])


def layer_params(config: Dict[str, Any]) -> Dict[str, int]:
    """Parameters of one layer of each kind as this chip holds it."""
    d, hd = config["hidden_size"], config["head_dim"]
    h, kv = config["num_attention_heads"], config["num_key_value_heads"]
    heads, p, groups, n = mamba_sizes(config)
    inner, xbc = heads * p, heads * p + 2 * groups * n
    f, shared = config["moe_intermediate_size"], \
        config["n_shared_experts"] * config["moe_shared_expert_intermediate_size"]
    return {
        "M": d + d * (inner + xbc + heads) + (config["conv_kernel"] + 1) * xbc
        + 3 * heads + inner + inner * d,
        "*": d + 2 * d * h * hd + 2 * d * kv * hd,
        # the norm, the router and its bias, the held experts, the shared one
        "E": d + d * n_experts(config) + n_experts(config)
        + held(config)[1] * 2 * d * f + 2 * d * shared,
    }


def n_params(config: Dict[str, Any]) -> int:
    """Every parameter of the cut: the training state is 16 bytes each."""
    per = layer_params(config)
    return sum(per[letter] for letter in pattern(config)) \
        + 2 * config["vocab_size"] * config["hidden_size"] \
        + config["hidden_size"]


def forward_flops_per_token(config: Dict[str, Any]) -> Dict[str, int]:
    """Required forward FLOPs a token at ``flops_counted_at_seq``, by part,
    the head left out (``flops.py`` adds it)."""
    d, hd = config["hidden_size"], config["head_dim"]
    h, kv = config["num_attention_heads"], config["num_key_value_heads"]
    heads, p, groups, n = mamba_sizes(config)
    inner, xbc = heads * p, heads * p + 2 * groups * n
    seq, letters = config["flops_counted_at_seq"], pattern(config)
    m, a, e = (letters.count(letter) for letter in "M*E")
    f, top_k = config["moe_intermediate_size"], config["num_experts_per_tok"]
    shared = config["n_shared_experts"] \
        * config["moe_shared_expert_intermediate_size"]
    return {
        "mamba_proj": m * 2 * d * (inner + xbc + heads + inner),
        "mamba_conv": m * 2 * config["conv_kernel"] * xbc,
        "mamba_scan": m * scan_flops_per_token(config),
        "attn_proj": a * 2 * (2 * d * h * hd + 2 * d * kv * hd),
        # QK^T and PV over the causal triangle, seq * (seq + 1) / 2 pairs
        "attn_scores": a * 2 * (seq + 1) * h * hd,
        "router": e * 2 * d * n_experts(config),
        "shared": e * 2 * 2 * d * shared,
        # top_k * held / n_experts held experts a token, at balance
        "held": e * top_k * held(config)[1] * 2 * 2 * d * f
        // n_experts(config),
    }


def shape(config: Dict[str, Any], chips: int) -> Dict[str, int]:
    d, seq = config["hidden_size"], config["flops_counted_at_seq"]
    forward = sum(forward_flops_per_token(config).values())
    assert forward % 2 == 0
    heads, p, groups, n = mamba_sizes(config)
    # (the formula's own second term, 6 * 1 * seq * d_model, is taken off)
    return {"d_model": d, "n_layer": 1,
            "n_head": config["num_attention_heads"],
            "n_kv_head": config["num_key_value_heads"],
            "head_dim": config["head_dim"], "vocab": config["vocab_size"],
            "layer_mm_params": forward // 2 - seq * d,
            # the scan's own sizes, for ``ssd_work.py``
            "ssd_heads": heads, "ssd_head_dim": p, "ssd_groups": groups,
            "ssd_state": n, "ssd_chunk": config["chunk_size"],
            "ssd_layers": pattern(config).count("M")}


def model_config(config: Dict[str, Any], chips: int):
    """Activations bf16, parameters, the router and the scan's decays
    float32, flash attention, the Pallas grouped matmul: the program's
    defaults, stated in the configuration file."""
    from ray_tpu.models.llama import LlamaConfig

    letters = pattern(config)
    if config["mlp_hidden_act"] != "relu2" or config["tie_word_embeddings"] \
            or config["n_group"] != 1 or config["topk_group"] != 1 \
            or config["mamba_proj_bias"] or not config["use_conv_bias"] \
            or config["attention_bias"] or config["mlp_bias"] \
            or config["mamba_hidden_act"] != "silu":
        raise NotImplementedError(
            "a gated expert, a tied head, a group-limited choice or a bias "
            "other than the convolution's")
    heads, p, groups, n = mamba_sizes(config)
    remat = config["remat"]
    return LlamaConfig(
        vocab_size=config["vocab_size"],
        n_positions=config["max_position_embeddings"],
        d_model=config["hidden_size"], n_layer=len(letters),
        # a layer is one branch: its mixer alone, or its feed-forward alone
        layer_types=tuple("none" if letter == "E" else KINDS[letter]
                          for letter in letters),
        mlp_types=tuple("sparse" if letter == "E" else "none"
                        for letter in letters),
        n_head=config["num_attention_heads"],
        n_kv_head=config["num_key_value_heads"], head_dim=config["head_dim"],
        rope=False, rope_theta=float(config["rope_theta"]),
        rms_eps=float(config["norm_eps"]), remat=bool(remat),
        remat_policy=remat or "full",
        mamba_n_heads=heads, mamba_d_head=p, mamba_n_groups=groups,
        mamba_d_state=n, mamba_d_conv=config["conv_kernel"],
        mamba_chunk=config["chunk_size"],
        n_experts=n_experts(config), moe_top_k=config["num_experts_per_tok"],
        d_expert=config["moe_intermediate_size"],
        norm_topk_prob=bool(config["norm_topk_prob"]), norm_topk_eps=1e-20,
        experts_held=held(config), router_scoring="sigmoid",
        routed_scale=float(config["routed_scaling_factor"]),
        router_selection_bias=True,
        d_shared_expert=config["n_shared_experts"]
        * config["moe_shared_expert_intermediate_size"],
        expert_activation="relu2",
        router_aux_weight=0.0, router_z_weight=0.0)


# --------------------------------------------------------------- the layers
def _rms(x, eps):
    import jax
    import jax.numpy as jnp

    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps)


def recurrence(x, dt, log_a, b, c):
    """``h_t = exp(log_a_t) h_{t-1} + dt_t x_t b_t^T;  y_t = h_t c_t``,
    position by position.  x (B, S, H, P); dt, log_a (B, S, H); b, c
    (B, S, H, N), a head's own."""
    import jax
    import jax.numpy as jnp

    batch, seq, heads, width = x.shape

    def step(h, at):
        x_t, dt_t, log_a_t, b_t, c_t = at
        h = jnp.exp(log_a_t)[..., None, None] * h \
            + (dt_t[..., None] * x_t)[..., None] * b_t[:, :, None, :]
        return h, jnp.einsum("bhpn,bhn->bhp", h, c_t)

    @jax.checkpoint
    def block(h, ats):
        return jax.lax.scan(step, h, ats)

    size = SCAN_BLOCK if seq % SCAN_BLOCK == 0 else seq
    ats = tuple(jnp.moveaxis(t, 1, 0).reshape(seq // size, size, *t.shape[:1],
                                              *t.shape[2:])
                for t in (x, dt, log_a, b, c))
    _, y = jax.lax.scan(block, jnp.zeros((batch, heads, width, b.shape[-1]),
                                         x.dtype), ats)
    return jnp.moveaxis(y.reshape(seq, batch, heads, width), 0, 1)


def mamba(p, n, config: Dict[str, Any], wrong: Optional[str] = None):
    import jax
    import jax.numpy as jnp

    r = _rounded(wrong)
    heads, width, groups, state = mamba_sizes(config)
    inner, bc = heads * width, groups * state
    z, xbc, dt = jnp.split(r(n @ p["in_proj"]["kernel"]),
                           [inner, 2 * inner + 2 * bc], axis=-1)
    taps = config["conv_kernel"]
    conv = sum(p["conv_kernel"][taps - 1 - k] * _delayed(xbc, k)
               for k in range(taps))
    x, b, c = jnp.split(r(jax.nn.silu(conv + p["conv_bias"])),
                        [inner, inner + bc], axis=-1)
    batch, seq, _ = x.shape
    x = x.reshape(batch, seq, heads, width)
    b, c = (t.reshape(batch, seq, groups, state) for t in (b, c))
    if wrong == "one_bc_group":     # the first group's, for every head
        b, c = (jnp.repeat(t[:, :, :1], heads, axis=2) for t in (b, c))
    else:                           # head j reads group j // (heads / groups)
        b, c = (jnp.repeat(t, heads // groups, axis=2) for t in (b, c))
    dt = jax.nn.softplus(dt + p["dt_bias"])
    log_a = dt * -jnp.exp(p["A_log"])
    if wrong == "decays_bf16":
        log_a = _through_running_sums(log_a, jnp.bfloat16,
                                      config["chunk_size"])
    y = r(recurrence(x, dt, log_a, b, c) + p["D"][:, None] * x)
    y, gate = y.reshape(batch, seq, inner), jax.nn.silu(z)
    eps = config["norm_eps"]

    def by_group(t):
        over = 1 if wrong == "norm_over_all" else groups
        return _rms(t.reshape(batch, seq, over, inner // over),
                    eps).reshape(batch, seq, inner) * p["norm_scale"]

    g = by_group(y) * gate if wrong == "gate_after_norm" else by_group(y * gate)
    return r(r(g) @ p["out_proj"]["kernel"])


def attention(p, n, config: Dict[str, Any], wrong: Optional[str] = None):
    import jax
    import jax.numpy as jnp

    r = _rounded(wrong)
    h, kv = config["num_attention_heads"], config["num_key_value_heads"]
    hd = config["head_dim"]
    batch, seq, _ = n.shape

    def heads(name, count):
        return r(n @ p[name]["kernel"]).reshape(
            batch, seq, count, hd).transpose(0, 2, 1, 3)

    q, k, v = heads("wq", h), heads("wk", kv), heads("wv", kv)
    if wrong == "rope_on":      # rotate-half over the whole head
        from perfbench.harness.reference import rope

        q, k = (rope(t, float(config["rope_theta"])) for t in (q, k))
    # each key/value head copied to the query heads that read it
    which = jnp.asarray([i % kv if wrong == "kv_head_mod" else i // (h // kv)
                         for i in range(h)])
    k, v = k[:, which], v[:, which]
    size = Q_BLOCK if seq % Q_BLOCK == 0 else seq

    @jax.checkpoint
    def block(first):
        rows = jax.lax.dynamic_slice_in_dim(q, first, size, axis=2)
        scores = jnp.einsum("bhqd,bhkd->bhqk", rows, k) * hd ** -0.5
        seen = jnp.arange(seq)[None, :] <= first + jnp.arange(size)[:, None]
        return jnp.einsum("bhqk,bhkd->bhqd", jax.nn.softmax(
            jnp.where(seen, scores, -1e30), axis=-1), v)

    out = jax.lax.map(block, jnp.arange(0, seq, size))  # (blocks, B, H, size, D)
    out = r(jnp.moveaxis(out, 0, 2).reshape(batch, h, seq, hd))
    return r(out.transpose(0, 2, 1, 3).reshape(batch, seq, h * hd)
             @ p["wo"]["kernel"])


def routing(r, bias, config: Dict[str, Any], wrong: Optional[str] = None):
    """The router's logits (..., 128) -> each token's weight on every expert,
    zero on those it did not choose, and the choice as 0/1: the published
    order — sigmoid, the bias for the choice alone, the scores gathered at
    the chosen, divided by their sum + 1e-20, times the routed scale."""
    import jax
    import jax.numpy as jnp

    k = config["num_experts_per_tok"] - (wrong == "top_5")
    s = jax.nn.softmax(r, axis=-1) if wrong == "softmax_scores" \
        else jax.nn.sigmoid(r)
    _, idx = jax.lax.top_k(s if wrong == "choice_without_bias" else s + bias,
                           k)
    top = jnp.take_along_axis(s, idx, axis=-1)
    if config["norm_topk_prob"]:
        top = top / (jnp.sum(top, axis=-1, keepdims=True) + 1e-20)
    if wrong != "no_routed_scale":
        top = top * config["routed_scaling_factor"]
    chosen = jax.nn.one_hot(idx, n_experts(config))     # (..., k, 128)
    return jnp.einsum("...k,...ke->...e", top, chosen), jnp.sum(chosen, -2)


def _squared_relu(a, wrong: Optional[str] = None):
    import jax

    if wrong == "plain_relu":
        return jax.nn.relu(a)
    if wrong == "gated_expert":     # a gate where the model has none
        return jax.nn.silu(a) * a
    return jax.nn.relu(a) ** 2


def experts(p, n, config: Dict[str, Any], wrong: Optional[str] = None):
    """-> (the held experts' part of the layer's result plus the shared
    expert's, the assignments the held experts received)."""
    import jax.numpy as jnp

    r = _rounded(wrong)
    first, count = held(config)
    reads, kernel = n, p["router"]["kernel"]
    if wrong == "router_bf16":
        reads, kernel = (t.astype(jnp.bfloat16).astype(jnp.float32)
                         for t in (reads, kernel))
        weight, chosen = routing(
            (reads @ kernel).astype(jnp.bfloat16).astype(jnp.float32),
            p["selection_bias"], config)
    else:
        weight, chosen = routing(reads @ kernel, p["selection_bias"], config,
                                 wrong)
    mine = weight[..., first:first + count]
    hidden = r(_squared_relu(jnp.einsum("bsd,edf->bsef", n, p["up_proj"]),
                             wrong))
    routed = r(jnp.einsum("bsef,efd,bse->bsd", hidden, p["down_proj"], mine))
    shared = r(r(_squared_relu(n @ p["shared"]["up_proj"]["kernel"], wrong))
               @ p["shared"]["down_proj"]["kernel"])
    return routed + shared, jnp.sum(chosen[..., first:first + count])


def _forward(params, ids, config: Dict[str, Any],
             wrong: Optional[str] = None):
    """``ids`` (B, S) -> (logits over the rows the head has, padding
    included; each ``E`` layer's assignments to the held experts)."""
    import jax

    r = _rounded(wrong)
    eps = config["norm_eps"]

    def layer(x, p, letter, reads):
        """``reads``: what the layer's norm reads (the stream, but under
        ``parallel_halves``)."""
        if letter == "E":
            n = r(_rms(reads, eps) * p["mlp_norm"]["scale"])
            f, rows = experts(p["moe"], n, config, wrong)
            return r(x + f), rows
        n = r(_rms(reads, eps) * p["attn_norm"]["scale"])
        f = mamba(p["mamba"], n, config, wrong) if letter == "M" \
            else attention(p["attn"], n, config, wrong)
        return r(x + f), 0.0

    x = before = r(params["wte"]["embedding"][ids])
    rows_held = []
    for i, letter in enumerate(pattern(config)):
        # a wrong model: a mixer and the feed-forward after it as the two
        # halves of one parallel block, both reading the block's input
        reads = before if wrong == "parallel_halves" and letter == "E" else x
        before = x
        x, rows = jax.checkpoint(
            lambda x, p, reads, letter=letter: layer(x, p, letter, reads))(
                x, params[f"h_{i}"], reads)
        if letter == "E":
            rows_held.append(rows)
    x = r(_rms(x, eps) * params["norm_f"]["scale"])
    return r(x @ params["lm_head"]["kernel"]), rows_held


def logits(params, ids, config: Dict[str, Any]):
    return _forward(params, ids, config)[0][..., :config["vocab_size"]]


def logits_loss_gradnorm(params, ids, targets, config: Dict[str, Any],
                         wrong: Optional[str] = None):
    """Float32 logits, the mean next-token cross entropy, the global L2 norm
    of its gradient, and the held experts' assignments a layer (their mean),
    under a wrong model or the precision below where one is named."""
    import jax
    import jax.numpy as jnp

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
    norm = jnp.sqrt(sum(jnp.sum(jnp.square(g))
                        for g in jax.tree_util.tree_leaves(grads)))
    return out, loss, norm, sum(rows_held) / len(rows_held)

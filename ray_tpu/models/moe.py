"""Mixture-of-Experts feed-forward layers: two forms, one module each.

``RoutedSwiGLU`` — the dropless layer of today's open sparse models: the
router's softmax over all experts in float32, ``top_k`` experts per token,
the ``T * k`` (token, expert) rows
sorted by expert, three grouped matmuls over the contiguous groups of uneven
size (SwiGLU experts: gate, up, down), the gate weights applied on the way
back, rows returned to token order and summed over their ``k`` slots.  No
``(S, E, C)`` one-hot tensor, no capacity, no dropped token.  The grouped
matmul is the Pallas ``gmm`` / ``tgmm`` of
``jax.experimental.pallas.ops.tpu.megablox``, everywhere: rows are padded to
the row tile, so ``init_params``'s 8 positions go through it too (on the CPU
it is interpreted when the process asked for that, as the flash kernel is).
``models/llama.py`` puts it into the shared block as ``moe``.  On a mesh each
device routes its own share of the tokens (``shard_map``: the batch over
``dp`` / ``fsdp``, the sequence over ``sp`` / ``tp``, so no device repeats
another's rows) through all the experts, whose weights are stored sharded
(``fsdp`` / ``tp``) and gathered whole, in bf16, by every routed layer's
forward, recomputation and backward: 0.8 GB a gather at OLMoE's widths,
which is the price of this form and has not been measured on a multi-chip
mesh.

A layer may be told which experts it holds (``RoutedConfig.experts_held``:
first index, count), as one chip of an expert-parallel group is: it has
those experts' matrices and no others.  The router, its softmax, the top-k
and the renormalisation stay over all ``n_experts``; the assignments bound
for absent experts get no row, and the layer returns its own experts' part of
the result (``held_experts``).  The assignments are counted and given their
buffer rows at ``T * k``, as indices (a counting sort over the held experts).
Everything as wide as the model or an expert — the gather of token rows, the
three grouped matmuls, ``silu * up``, each result row times its gate weight
added in float32 into its token — runs on pieces of the balance share ``T * k
* held / n_experts`` rows, in a loop on the device that makes as many trips
as the rows that came need: the layer runs at the smallest capacity of a
ladder the configuration gives (``capacity_ladder``: the share and its
multiples up to ``T * k``) that holds them, each device of a mesh for its own
rows.  The last rung is the whole buffer, so nothing is dropped, nothing is
approximated and nothing stands in for the absent chips or for their
exchange.  A piece's rows reach their tokens by one Pallas pass
(``_onto_tokens``): an assignment is ``token * k + slot``, so the rows by
assignment are in token order and a row's place there is a running count, not
a sort; a tile of tokens then owns one run of rows, which the kernel reads as
the grouped matmul wrote them and adds into the float32 sum in place, a
(tokens x rows) selection matrix on the MXU, the forward's gate weights as
three bf16 parts — an XLA scatter-add of the same rows ran at a twentieth of
the memory's speed (``PERF.md``, PR 36).  The backward's sum of the rows'
gradients into their tokens is the same pass without gates.
The loop has a differentiation rule of its own (``_through_held``):
reverse mode cannot pass through a trip count found on the device, and the
rule's backward is the same loop with a piece's forward recomputed and
transposed.  It is one body whatever the rows: a ``lax.switch`` over a body a
capacity made the cell's programs outgrow the compile cache (``PERF.md``,
PR 32).  What ``ep > 1`` on a mesh still lacks is that exchange — the
all-to-all that sends each token's rows to the device holding its expert and
brings the results back — and it raises ``NotImplementedError``.

The scores may be sigmoids in place of the softmax (``scoring``), the chosen
weights scaled (``routed_scale``, after the renormalisation), and a shared
expert added that every token passes: a SwiGLU of width ``d_shared``, the
child ``shared`` under its own scope, whole on every chip whatever part of
the routed experts the layer holds.  ``selection_bias`` gives each expert a
float32 bias that is added to its score where the ``top_k`` are chosen and
nowhere else: the weights come from the scores without it (DeepSeek-V3's
auxiliary-loss-free selection; LFM2's ``use_expert_bias``).  It is state, not
a weight: no gradient reaches it, the train step hands it on as it came
(``models/pretrain.py``), and what would move it by the experts' load is a
job's rule that this layer does not have.  ``norm_topk_eps`` is added to the
sum the chosen scores are divided by.  ``activation`` names what gates an
expert's hidden units: ``"silu"``, or ``"relu"`` (ReGLU, ``relu(gate) * up``:
SmallThinker's experts), whose derivative the held loop's backward writes
out; or ``"relu2"``, an expert WITHOUT a gate, ``W_down relu(W_up n)^2`` (the
squared ReLU of Nemotron-H's experts): two matrices an expert and no
``gate_proj`` parameter, in the routed layer, in the held loop (two grouped
matmuls forward where a gated expert has three, four backward where it has
six, the derivative ``2 relu(a)`` written out) and in the shared expert alike.
The router may read another tensor than the experts do
(``RoutedSwiGLU.__call__``'s ``router_input``).

``MoEMlpBlock`` — the older GShard / Switch form, wired into GPT-2 only
(``GPT2Config.moe_every``): top-k routing as DENSE dispatch / combine einsums
against one-hot capacity tensors, GELU experts, tokens over capacity dropped;
with the expert dimension sharded on ``ep`` GSPMD lowers the einsums into
all-to-alls.

Both sow their auxiliary terms into the ``intermediates`` collection;
``collect_aux`` turns them into the objective's extra term and a step's
statistics (``models/pretrain.py``).
"""

from __future__ import annotations

import functools
import operator
from dataclasses import dataclass
from typing import Any, NamedTuple, Optional, Tuple

import flax.linen as nn
import jax
import jax.numpy as jnp

from ray_tpu.ops.attention import _interpret, _round_up


@dataclass(frozen=True)
class MoEConfig:
    n_experts: int = 8
    top_k: int = 2
    capacity_factor: float = 1.25
    d_model: int = 768
    d_ff: int = 3072
    dtype: Any = jnp.bfloat16
    router_aux_weight: float = 0.01
    router_z_weight: float = 1e-3


def _router_probs(logits: jnp.ndarray) -> jnp.ndarray:
    # f32 softmax: router numerics decide token placement — bf16 rounding
    # here causes expert flapping between steps.
    return jax.nn.softmax(logits.astype(jnp.float32), axis=-1)


def compute_routing(logits: jnp.ndarray, n_experts: int, top_k: int,
                    capacity: int) -> Tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """Dense Switch/GShard routing.

    Args:  logits (G, S, E) per-token expert scores (G = routing groups).
    Returns (dispatch (G, S, E, C) one-hot, combine (G, S, E, C) weighted,
    aux_loss scalar).
    """
    G, S, E = logits.shape
    probs = _router_probs(logits)                      # (G, S, E)
    # iterative top-k: mask out chosen experts each round (k is tiny: 1 or 2)
    remaining = probs
    dispatch = jnp.zeros((G, S, E, capacity), jnp.float32)
    combine = jnp.zeros((G, S, E, capacity), jnp.float32)
    # slots an expert's queue already consumed in earlier rounds: round r+1
    # positions must start AFTER round r's, or 2nd-choice tokens collide with
    # 1st-choice tokens in the same capacity slot (GShard offsets exactly so).
    occupancy = jnp.zeros((G, 1, E), jnp.float32)
    for _ in range(top_k):
        idx = jnp.argmax(remaining, axis=-1)           # (G, S)
        onehot = jax.nn.one_hot(idx, E, dtype=jnp.float32)  # (G, S, E)
        # position of each token within its expert's queue (-1 where unrouted)
        pos = (jnp.cumsum(onehot, axis=1) + occupancy) * onehot - 1.0
        keep = (pos >= 0) & (pos < capacity)
        pos_oh = jax.nn.one_hot(pos.astype(jnp.int32), capacity,
                                dtype=jnp.float32) * keep[..., None]
        gate = jnp.sum(remaining * onehot, axis=-1)[..., None, None]  # (G,S,1,1)
        dispatch = dispatch + onehot[..., None] * pos_oh
        combine = combine + gate * onehot[..., None] * pos_oh
        occupancy = occupancy + jnp.sum(onehot, axis=1, keepdims=True)
        remaining = remaining * (1.0 - onehot)
    # load-balancing loss (Switch eq.4): frac of tokens per expert x mean prob
    me = jnp.mean(probs, axis=(0, 1))                              # (E,)
    ce = jnp.mean(jnp.sum(dispatch, axis=-1), axis=(0, 1))         # (E,)
    aux = jnp.sum(me * ce) * E
    return dispatch, combine, aux


class MoEMlpBlock(nn.Module):
    """Expert-parallel FFN.  Call with x of shape (B, S, D)."""

    config: MoEConfig

    @nn.compact
    def __call__(self, x):
        cfg = self.config
        B, S, D = x.shape
        E = cfg.n_experts
        # Capacity is PER routing group (each batch row routes its S tokens
        # independently): sizing it from B*S would inflate the dispatch
        # tensors and expert FFN compute by a factor of B.
        capacity = max(int(cfg.capacity_factor * S * cfg.top_k / E), 1)

        router = nn.Dense(E, use_bias=False, dtype=jnp.float32,
                          name="router")(x.astype(jnp.float32))  # (B,S,E)
        dispatch, combine, aux = compute_routing(
            router, E, cfg.top_k, capacity)
        # router z-loss keeps logits bounded (public GShard/ST-MoE practice)
        z = jnp.mean(jax.nn.logsumexp(router.astype(jnp.float32),
                                      axis=-1) ** 2)
        self.sow("intermediates", "moe_aux_loss",
                 cfg.router_aux_weight * aux + cfg.router_z_weight * z)

        # dense dispatch: (B,S,D) x (B,S,E,C) -> (E, B, C, D); with the
        # expert dim sharded on ep, GSPMD lowers this einsum chain into the
        # all-to-all pair the reference would hand-write with NCCL.
        expert_in = jnp.einsum("bsd,bsec->ebcd", x.astype(cfg.dtype),
                               dispatch.astype(cfg.dtype))
        w_in = self.param(
            "w_in", nn.initializers.normal(0.02 / (D ** 0.5)),
            (E, D, cfg.d_ff), jnp.float32).astype(cfg.dtype)
        w_out = self.param(
            "w_out", nn.initializers.normal(0.02 / (cfg.d_ff ** 0.5)),
            (E, cfg.d_ff, D), jnp.float32).astype(cfg.dtype)
        h = jnp.einsum("ebcd,edf->ebcf", expert_in, w_in)
        h = jax.nn.gelu(h)
        expert_out = jnp.einsum("ebcf,efd->ebcd", h, w_out)
        out = jnp.einsum("ebcd,bsec->bsd", expert_out,
                         combine.astype(cfg.dtype))
        return out.astype(cfg.dtype)


def moe_partition_rules():
    """Extra rules for MoE params: experts over ep, then fsdp/tp within."""
    from ray_tpu.parallel.sharding import PartitionRules, _spec

    return PartitionRules([
        (r"router/kernel", _spec()),
        (r"w_in", _spec("ep", "fsdp", "tp")),
        (r"w_out", _spec("ep", "tp", "fsdp")),
    ])


# ============================================ the dropless routed layer
@dataclass(frozen=True)
class RoutedConfig:
    n_experts: int
    top_k: int
    d_model: int
    d_ff: int                       # one expert's SwiGLU hidden width
    norm_topk_prob: bool = False    # renormalise the chosen k probabilities
    dtype: Any = jnp.bfloat16
    # (first index, count) of the experts this layer holds; None: all of them
    experts_held: Optional[Tuple[int, int]] = None
    # an expert's score: its "softmax" probability over all the experts, or
    # the "sigmoid" of its own logit
    scoring: str = "softmax"
    routed_scale: float = 1.0       # x the chosen weights, once normalised
    d_shared: int = 0               # the shared expert's width; 0: none
    # the shared expert x sigmoid(w_s . x), one gate a token (``shared/gate``)
    shared_gate: bool = False
    # a bias an expert (``selection_bias``, float32, zeros) added to the
    # scores for the choice of the ``top_k`` alone: state, not a weight
    selection_bias: bool = False
    norm_topk_eps: float = 0.0      # + the chosen scores' sum it divides by
    # what gates an expert's hidden units: "silu" (SwiGLU) or "relu" (ReGLU:
    # ``relu(gate) * up``, exact zeros where the gate is not positive); or
    # "relu2", which has no gate: ``relu(up) ** 2``, two matrices an expert
    activation: str = "silu"


# the activations of an expert without a gate: ``(up_proj, down_proj)`` are
# its matrices, where a gated expert has ``(gate_proj, up_proj, down_proj)``
GATELESS = ("relu2",)


def _gated(a, activation: str):
    """``act(a)``: what an expert's ``up`` projection is multiplied by."""
    if activation not in ("silu", "relu"):
        raise ValueError(f"unknown activation {activation!r} (expected "
                         "'silu', 'relu' or 'relu2')")
    return jax.nn.silu(a) if activation == "silu" else jax.nn.relu(a)


def _hidden(pre, activation: str):
    """An expert's hidden units from what its first matrices made of its
    rows: ``act(gate) * up`` of ``(gate, up)``, ``relu(up) ** 2`` of
    ``(up,)``."""
    if activation in GATELESS:
        (a,) = pre
        return jnp.square(jax.nn.relu(a))
    a, b = pre
    return _gated(a, activation) * b


def _hidden_bwd(pre, d_h, activation: str):
    """``_hidden``'s transpose: a cotangent for each of ``pre`` under
    ``d_h``."""
    if activation in GATELESS:
        # written out: 2 relu(a), which is 0 where a is not positive
        (a,) = pre
        return (2 * jax.nn.relu(a) * d_h,)
    a, b = pre
    if activation == "relu":
        # written out: the gate passes where it is positive (0 at 0, as
        # ``jax.nn.relu``'s own rule has it), ``up`` takes the gated units
        return jnp.where(a > 0, d_h * b, 0), d_h * jax.nn.relu(a)
    return jax.vjp(lambda a, b: jax.nn.silu(a) * b, a, b)[1](d_h)


def _even_tile(width: int, most: int) -> int:
    """``min(width, most)``, but for a width past 2,048 that ``most`` does not
    divide the most whole lanes under ``most`` that do divide it (every width
    up to 2,048 keeps the tile the chip's clock chose for it)."""
    if width <= 2048 or width % most == 0:
        return min(width, most)
    return max(t for t in range(128, most + 1, 128) if width % t == 0)


def _gmm_tiling(m: int, k: int, n: int):
    """(m, k, n) tile of the grouped matmul ``(m, k) x (E, k, n)``, by the
    chip's clock at OLMoE's shapes (PERF.md, PR 25): 256 rows, the whole
    contraction and as much of the output's width as keeps one expert's
    weight tile at 2M elements (4 MB in bf16, double-buffered, beside the
    float32 accumulator in 16 MB of scoped VMEM).  Rows of a group that do
    not fill a row tile cost a whole one, and each row tile reads its weight
    tile again, which is what the 256 trades off.  A width that does not fit
    is cut into whole lanes, as evenly as they allow (an expert 1,408 wide:
    2,048 columns as 2 x 1,024 under a contraction of 1,408, its own 1,408 as
    768 + 640 under one of 2,048).  A contraction past 2,048 that 2,048 does
    not divide (2,304: Kimi-Linear's model width) is cut into whole lanes
    that do, 2 x 1,152, where a tile of 2,048 and a masked one of 256 would
    stream 4,096."""
    tk = _even_tile(k, 2048)
    most = (2 << 20) // tk
    if n > most:
        tiles = -(-n // (most // 128 * 128))
        n = _round_up(-(-n // tiles), 128)
    return min(256, _round_up(m, 8)), tk, n


def _tgmm_tiling(m: int, k: int, n: int):
    """Tile of ``(m, k).T x (m, n) -> (E, k, n)``: 256 rows of the reduction,
    a 1024 x 1024 float32 accumulator (two operands' tiles in float32 for
    the kernel's masks sit beside it); a width past 2,048 in whole lanes
    that divide it (2,304 as 3 x 768), as ``_gmm_tiling`` cuts it."""
    return (min(256, _round_up(m, 8)), _even_tile(k, 1024),
            _even_tile(n, 1024))


def _gmm(lhs, rhs, sizes, *, transpose_rhs=False):
    """``lhs[rows of group e] @ rhs[e]`` (``rhs[e].T`` if ``transpose_rhs``)
    by the megablox kernel.  It wants the row count a multiple of the row
    tile: rows are padded behind the last group and cut off again.  Rows
    past the last group are not visited: what comes back there is not
    written."""
    from jax.experimental.pallas.ops.tpu.megablox.gmm import gmm

    m, k = lhs.shape
    tiling = _gmm_tiling(m, k, rhs.shape[1 if transpose_rhs else 2])
    if m % tiling[0]:
        lhs = jnp.pad(lhs, ((0, -m % tiling[0]), (0, 0)))
    out = gmm(lhs, rhs, sizes, preferred_element_type=lhs.dtype, tiling=tiling,
              transpose_rhs=transpose_rhs, interpret=_interpret())
    return out[:m]


def _tgmm(lhs, grad, sizes, dtype, n_groups, onto=None):
    """Per group ``lhs[rows].T @ grad[rows]``: the weights' gradient,
    ``(n_groups, k, n)``, for the first ``n_groups`` of ``sizes``; added to
    ``onto`` where that is given."""
    from jax.experimental.pallas.ops.tpu.megablox.gmm import tgmm

    m, k = lhs.shape
    tiling = _tgmm_tiling(m, k, grad.shape[1])
    if m % tiling[0]:
        lhs, grad = (jnp.pad(a, ((0, -m % tiling[0]), (0, 0)))
                     for a in (lhs, grad))
    return tgmm(lhs.T, grad, sizes, preferred_element_type=dtype,
                tiling=tiling, num_actual_groups=n_groups, existing_out=onto,
                interpret=_interpret())


@jax.custom_vjp
def grouped_matmul(lhs, rhs, sizes):
    """``lhs`` (N, k) in contiguous groups of ``sizes`` (E,) rows, each group
    times its own ``rhs[e]`` (E, k, n) -> (N, n)."""
    return _gmm(lhs, rhs, sizes)


def _grouped_matmul_fwd(lhs, rhs, sizes):
    return _gmm(lhs, rhs, sizes), (lhs, rhs, sizes)


def _grouped_matmul_bwd(res, grad):
    lhs, rhs, sizes = res
    return (_gmm(grad, rhs, sizes, transpose_rhs=True),
            _tgmm(lhs, grad, sizes, rhs.dtype, rhs.shape[0]), None)


grouped_matmul.defvjp(_grouped_matmul_fwd, _grouped_matmul_bwd)


# Rows are moved by permutations, so the transpose of each gather is the
# gather by the inverse permutation and no scatter-add is emitted.
@functools.partial(jax.custom_vjp, nondiff_argnums=(3,))
def _rows_to_expert_order(x, order, inverse, k):
    """(T, D) -> (T * k, D): row ``j`` is the token of sorted slot ``j``."""
    return x[order // k]


def _rows_to_expert_order_fwd(x, order, inverse, k):
    return x[order // k], inverse


def _rows_to_expert_order_bwd(k, inverse, g):
    g = g[inverse].reshape(-1, k, g.shape[-1])
    return jnp.sum(g, axis=1, dtype=jnp.float32).astype(g.dtype), None, None


_rows_to_expert_order.defvjp(_rows_to_expert_order_fwd,
                             _rows_to_expert_order_bwd)


@jax.custom_vjp
def _permute_rows(rows, perm, inverse):
    return rows[perm]


_permute_rows.defvjp(lambda rows, perm, inverse: (rows[perm], inverse),
                     lambda inverse, g: (g[inverse], None, None))


def routed_experts(x, weights, idx, mats, cfg: RoutedConfig):
    """One device's tokens through all the experts.  x (..., D); weights, idx
    (..., k): each token's gate weights and chosen experts; ``mats``: the
    experts' matrices in the compute dtype, gate, up (E, D, F) and down
    (E, F, D), or up and down alone under an activation without a gate.
    Every one of the ``T * k`` buffer rows holds a token: one pass over the
    whole of it."""
    *first, down = mats
    lead, d = x.shape[:-1], x.shape[-1]
    k = cfg.top_k
    x = x.reshape(-1, d)
    with jax.named_scope("dispatch"):
        flat = idx.reshape(-1)
        order = jnp.argsort(flat, stable=True)
        inverse = jnp.zeros_like(order).at[order].set(
            jnp.arange(order.shape[0], dtype=order.dtype))
        sizes = jnp.sum(flat[:, None] == jnp.arange(cfg.n_experts), axis=0,
                        dtype=jnp.int32)
        rows = _rows_to_expert_order(x, order, inverse, k)
    with jax.named_scope("experts"):
        if cfg.activation in GATELESS:
            h = _hidden((grouped_matmul(rows, first[0], sizes),),
                        cfg.activation)
        else:   # (traced in the order it always was: gate, act, up)
            gate, up = first
            h = _gated(grouped_matmul(rows, gate, sizes), cfg.activation) \
                * grouped_matmul(rows, up, sizes)
        rows = grouped_matmul(h, down, sizes)
    with jax.named_scope("combine"):
        rows = _permute_rows(rows, inverse, order).reshape(-1, k, d)
        out = jnp.sum(rows.astype(jnp.float32)
                      * weights.reshape(-1, k, 1), axis=1)
    return out.astype(cfg.dtype).reshape(*lead, d)


# ------------------------------------- a layer that holds a part of its experts
def capacity_ladder(n_rows: int, n_held: int, n_experts: int):
    """The row capacities a layer holding ``n_held`` of ``n_experts`` may run
    at, ascending, from the configuration alone: the balance share of the
    ``n_rows`` = ``T * k`` assignments (whole row tiles of the grouped
    matmul) and its multiples up to the first that holds ``n_rows`` — every
    assignment may fall to a held expert, so no routing is ever refused."""
    share = max(n_rows * n_held // n_experts, 1)
    share = _round_up(share, _gmm_tiling(share, 1, 1)[0])
    return tuple(share * (i + 1) for i in range(-(-n_rows // share)))


class _Route(NamedTuple):
    """Where the held experts' assignments stand: indices, at ``T * k``."""
    order: Any      # the assignment (token * k + slot) in buffer row j: the
                    # held experts' alone, by expert, a group in token order
    sizes: Any      # (n_held,): the assignments each held expert received
    row: Any        # (T * k,) each assignment's buffer row; past the buffer's
                    # end for an absent expert's


class _Piece(NamedTuple):
    """One share-sized piece of the buffer: its ``c`` rows."""
    slots: Any      # (c,) the assignments of the piece's rows
    live: Any       # (c,) the rows an assignment stands in: the first ones
    sizes: Any      # (n_held,) each held expert's rows inside the piece
    mine: Any       # (T * k,) the assignments that stand in the piece


def _route(flat, n_held: int, n_rows: int) -> _Route:
    """``flat`` (T * k,): each assignment's held expert by its local index,
    ``n_held`` for an absent one -> the held assignments in expert order, in
    a buffer of ``n_rows`` rows.  A counting sort, a key a held expert: an
    assignment's row is its expert's first row plus the assignments to that
    expert before it."""
    hot = flat[:, None] == jnp.arange(n_held)
    sizes = jnp.sum(hot, axis=0, dtype=jnp.int32)
    row = jnp.sum(jnp.where(hot, jnp.cumsum(sizes) - sizes + jnp.cumsum(
        hot, axis=0, dtype=jnp.int32) - 1, 0), axis=1)
    # (an absent expert's assignment has no row: past the end, dropped)
    row = jnp.where(flat < n_held, row, n_rows)
    order = jnp.zeros((n_rows,), jnp.int32).at[row].set(
        jnp.arange(flat.shape[0], dtype=jnp.int32), mode="drop")
    return _Route(order, sizes, row)


def _piece(route: _Route, i, c: int) -> _Piece:
    """Piece ``i`` of the buffer, rows ``i * c`` to ``i * c + c``."""
    lo, ends = i * c, jnp.cumsum(route.sizes)
    return _Piece(
        jax.lax.dynamic_slice(route.order, (lo,), (c,)),
        lo + jnp.arange(c) < ends[-1],
        jnp.clip(ends, lo, lo + c) - jnp.clip(ends - route.sizes, lo, lo + c),
        (route.row >= lo) & (route.row < lo + c))


def _beside(scope: str, name: str):
    """The scope ``name`` of a piece's work, spelled with the module's whole
    path (``scope``: ``h_<n>/moe``) where the layer gave it.  JAX prints a
    loop body's operations under ``<where the loop stands>/while/body/``, so
    a plain ``experts`` inside the loop would read ``moe/while/body/experts``;
    with the path spelled out a profile shows ``h_<n>/moe/dispatch``,
    ``h_<n>/moe/experts`` and ``h_<n>/moe/combine`` behind the loop's prefix
    as a layer without a loop has them, and whatever selects the grouped
    matmuls by ``h_<n>/moe/experts/`` finds them and nothing else."""
    return jax.named_scope(f"{scope}/{name}" if scope else name)


def _piece_forward(x, weights, route: _Route, i, c: int, k: int, scope: str,
                   activation: str):
    """The piece's rows through their experts: the token rows gathered, the
    grouped matmuls over ``c`` rows (three, or two where the experts have no
    gate), ``act(gate) * up`` or ``relu(up) ** 2``.  Rows past the live ones
    belong to no group: the kernels leave them unwritten."""
    *first, down = weights
    piece = _piece(route, i, c)
    with _beside(scope, "dispatch"):
        rows = x[piece.slots // k]
    with _beside(scope, "experts"):
        pre = tuple(_gmm(rows, w, piece.sizes) for w in first)
        h = _hidden(pre, activation)
        return piece, rows, pre, h, _gmm(h, down, piece.sizes)


def _tile(n: int, most: int) -> int:
    """The largest tile of at most ``most`` (halved down to 8) that divides
    ``n``; ``n`` itself where none does."""
    while most >= 8:
        if n % most == 0:
            return most
        most //= 2
    return n


# The sum of a piece's rows into their tokens goes tile of tokens by tile of
# tokens: 128 tokens against blocks of 128 rows, by the chip's clock at the
# cells' shapes (PERF.md, PR 36).
_TOKEN_TILE, _ROW_BLOCK = 128, 128


class _ByToken(NamedTuple):
    """A piece's live rows in token order: a token's rows stand together, a
    tile of tokens owns one run of places."""
    perm: Any       # (c,) the piece's row that stands in each place
    slots: Any      # (c,) and its assignment
    tokens: Any     # (1, c) the token of each place; past the live ones, none
    tiles: Any      # (steps,) the tile of tokens a step of the sum adds into
    blocks: Any     # (steps,) the block of places it reads
    n_steps: Any    # (1,) the steps that add anything: the others are idle


def _by_token(piece: _Piece, k: int, n_tokens: int, tile: int,
              block: int) -> _ByToken:
    """An assignment is ``token * k + slot``: the piece's live rows by
    assignment are in token order, and a row's place is a running count of
    the piece's assignments over ``T * k`` — no sort.  The steps of the sum
    pair each tile of ``tile`` tokens with the blocks of ``block`` places its
    run touches, tiles ascending; a tile with no row has no step."""
    c = piece.slots.shape[0]
    n_tiles, n_blocks = n_tokens // tile, c // block
    upto = jnp.cumsum(piece.mine, dtype=jnp.int32)
    rows = jnp.arange(c, dtype=jnp.int32)
    # (a row no assignment stands in keeps its place, past the live ones; the
    # place reads row 0 then, which is written whenever any row is)
    perm = jnp.zeros((c,), jnp.int32).at[
        jnp.where(piece.live, upto[piece.slots] - 1, rows)].set(
            rows, unique_indices=True)
    perm = jnp.where(piece.live, perm, 0)
    slots = piece.slots[perm]
    tokens = jnp.where(piece.live, slots // k, n_tokens)
    ends = upto[tile * k - 1::tile * k]            # where each tile's run ends
    starts = jnp.concatenate([jnp.zeros((1,), jnp.int32), ends[:-1]])
    first = starts // block
    n_of = jnp.where(ends > starts, (ends - 1) // block - first + 1, 0)
    done = jnp.cumsum(n_of)                        # steps up to and with a tile
    # (past the last step that adds anything the grid stays where it is)
    step = jnp.minimum(jnp.arange(n_tiles + n_blocks - 1),
                       jnp.maximum(done[-1] - 1, 0))
    tiles = jnp.minimum(jnp.sum(done[None, :] <= step[:, None], axis=1),
                        n_tiles - 1).astype(jnp.int32)
    blocks = (first - (done - n_of))[tiles] + step
    return _ByToken(perm, slots, tokens[None, :], tiles, blocks, done[-1:])


def _onto_tokens_kernel(tiles, blocks, n_steps, tokens, *refs, tile: int,
                        gated: bool):
    """One step: ``out`` (a tile of tokens, float32) plus the rows of one
    block of places that belong to the tile's tokens, as a (tokens x places)
    selection matrix times the block's rows on the MXU.  A float32 gate
    enters as three bf16 parts, whose products with bf16 rows are exact."""
    from jax.experimental import pallas as pl

    gates, rows, acc, out = refs if gated else (None,) + refs
    s = pl.program_id(1)

    @pl.when((s == 0) | (tiles[s] != tiles[jnp.maximum(s - 1, 0)]))
    def _():
        out[...] = acc[...]

    @pl.when(s < n_steps[0])
    def _():
        block = rows.shape[0]
        own = tokens[...] - tiles[s] * tile == jax.lax.broadcasted_iota(
            jnp.int32, (tile, block), 0)

        def add(pick):      # (a mask is laid out as the int32 it came from)
            out[...] += jnp.dot(
                jnp.where(own, pick, 0.0).astype(rows.dtype), rows[...],
                precision=None if rows.dtype == jnp.bfloat16
                else jax.lax.Precision.HIGHEST,
                preferred_element_type=jnp.float32)

        if not gated:
            add(1.0)
        elif rows.dtype != jnp.bfloat16:    # float32 rows: a float32 matmul
            add(gates[...])
        else:
            left = gates[...]
            for _ in range(3):
                part = left.astype(jnp.bfloat16).astype(jnp.float32)
                add(part)
                left = left - part


@functools.partial(jax.jit, static_argnames=("k",))
def _onto_tokens(acc, rows, gates, piece: _Piece, k: int):
    """``acc`` (T, D) float32 plus each token's live rows of the piece
    (``rows`` (c, D), in the piece's order and the grouped matmul's dtype),
    each times its assignment's gate weight (``gates`` (T * k,) float32)
    where there are gates: the rows brought into token order, then one
    Pallas pass over them, tile of tokens by tile of tokens, ``acc`` updated
    in place.  Every layer calls it at the same shapes: jitted, it is traced
    and lowered once for them all (a trace a call cost the cell 7 s of
    set-up: ``PERF.md``, PR 36)."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    n_tokens, d = acc.shape
    tile, block = _tile(n_tokens, _TOKEN_TILE), _tile(rows.shape[0],
                                                      _ROW_BLOCK)
    wide, gated = _tile(d, 2048), gates is not None
    by = _by_token(piece, k, n_tokens, tile, block)
    by_block = pl.BlockSpec((1, block), lambda j, s, tiles, blocks, n:
                            (0, blocks[s]))
    by_tile = pl.BlockSpec((tile, wide), lambda j, s, tiles, blocks, n:
                           (tiles[s], j))
    return pl.pallas_call(
        functools.partial(_onto_tokens_kernel, tile=tile, gated=gated),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3, grid=(d // wide, by.tiles.shape[0]),
            in_specs=[by_block] * (1 + gated) + [
                pl.BlockSpec((block, wide), lambda j, s, tiles, blocks, n:
                             (blocks[s], j)), by_tile],
            out_specs=by_tile),
        out_shape=jax.ShapeDtypeStruct(acc.shape, acc.dtype),
        input_output_aliases={5 + gated: 0},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=_interpret(), name="onto_tokens",
    )(by.tiles, by.blocks, by.n_steps, by.tokens,
      *((gates[by.slots][None, :],) if gated else ()), rows[by.perm], acc)


def _n_pieces(route: _Route, c: int):
    return (jnp.sum(route.sizes) + c - 1) // c


# The pieces are a loop on the device whose trip count follows the rows that
# came, which reverse mode cannot pass through; and its residuals would be
# every piece's.  So the held experts' part has a rule of its own, which
# saves what it was given and runs the loop again in its backward: a piece's
# forward recomputed, then transposed.
@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5, 6, 7))
def _through_held(x, gates, weights, route: _Route, c: int, k: int,
                  scope: str, activation: str = "silu"):
    """(T, D) tokens -> (T, D): each token's rows through the held experts,
    times their gate weights (``gates`` (T * k,), by assignment), added in
    float32.  ``c`` rows at a time, as many times as the rows that came
    need."""
    def body(i, acc):
        piece, _, _, _, out = _piece_forward(x, weights, route, i, c, k,
                                             scope, activation)
        with _beside(scope, "combine"):
            return _onto_tokens(acc, out, gates, piece, k)

    return jax.lax.fori_loop(
        0, _n_pieces(route, c), body,
        jnp.zeros(x.shape, jnp.float32)).astype(x.dtype)


def _through_held_fwd(x, gates, weights, route, c, k, scope, activation):
    return (_through_held(x, gates, weights, route, c, k, scope, activation),
            (x, gates, weights, route))


def _through_held_bwd(c, k, scope, activation, res, g):
    x, gates, weights, route = res
    *first, down = weights
    n_held = down.shape[0]

    def body(i, carry):
        dx, dgates, (*d_first, ddown) = carry
        piece, rows, pre, h, out = _piece_forward(x, weights, route, i, c,
                                                  k, scope, activation)
        with _beside(scope, "combine"):
            # the transpose of the weighted sum: a live row's gradient is its
            # gate times its token's ``g``, a gate's its row times ``g``
            g_rows = g[piece.slots // k].astype(jnp.float32)
            dgates = dgates.at[piece.slots].add(jnp.where(
                piece.live, jnp.sum(out.astype(jnp.float32) * g_rows, axis=1),
                0))
            d_out = (g_rows * jnp.where(piece.live, gates[piece.slots], 0)[
                :, None]).astype(out.dtype)
        with _beside(scope, "experts"):
            # (an expert's gradient is one kernel's float32 sum, rounded
            # once, unless its rows straddle two pieces: then once more in
            # between)
            d_h = _gmm(d_out, down, piece.sizes, transpose_rhs=True)
            ddown = _tgmm(h, d_out, piece.sizes, down.dtype, n_held,
                          onto=ddown)
            d_pre = _hidden_bwd(pre, d_h, activation)
            d_rows = functools.reduce(operator.add, (
                _gmm(d, w, piece.sizes, transpose_rhs=True)
                for d, w in zip(d_pre, first)))
            d_first = tuple(
                _tgmm(rows, d, piece.sizes, w.dtype, n_held, onto=onto)
                for d, w, onto in zip(d_pre, first, d_first))
        with _beside(scope, "dispatch"):
            # a token's gradient: the float32 sum of its live rows'
            dx = _onto_tokens(dx, d_rows, None, piece, k)
        return dx, dgates, (*d_first, ddown)

    dx, dgates, dweights = jax.lax.fori_loop(
        0, _n_pieces(route, c), body,
        (jnp.zeros(x.shape, jnp.float32), jnp.zeros_like(gates),
         tuple(jnp.zeros_like(w) for w in weights)))
    return dx.astype(x.dtype), dgates, dweights, None


_through_held.defvjp(_through_held_fwd, _through_held_bwd)


def held_experts(x, weights, idx, mats, cfg: RoutedConfig, scope: str = ""):
    """One device's tokens through the experts the layer holds
    (``cfg.experts_held``; ``mats``: their matrices, as ``routed_experts``
    takes them) -> (their part of the result, the rows the layer ran at).
    The assignments are counted and placed at ``T * k``, as
    indices; everything as wide as the model or an expert — the gather of
    token rows, the grouped matmuls, ``act(gate) * up``, the weighted sum into
    the tokens — runs on pieces of ``capacity_ladder``'s first rung, the balance
    share, in a loop on the device that makes as many trips as the rows that
    came need: the layer runs at the smallest rung that holds them."""
    lead, d = x.shape[:-1], x.shape[-1]
    k, (first, n_held) = cfg.top_k, cfg.experts_held
    x = x.reshape(-1, d)
    with jax.named_scope("dispatch"):
        flat = idx.reshape(-1)
        # held experts by their local index; every absent expert's
        # assignments are no row of the buffer
        flat = jnp.where((flat >= first) & (flat < first + n_held),
                         flat - first, n_held)
        ladder = capacity_ladder(flat.shape[0], n_held, cfg.n_experts)
        route = _route(flat, n_held, ladder[-1])
    out = _through_held(x, weights.reshape(-1), tuple(mats), route,
                        ladder[0], k, scope, cfg.activation)
    return (out.reshape(*lead, d),
            (_n_pieces(route, ladder[0]) * ladder[0]).astype(
                jnp.float32).reshape((1,) * len(lead) + (1,)))


def token_spec(mesh):
    """How the routed layer splits a (batch, seq, .) array over ``mesh``:
    the batch over ``dp`` / ``fsdp`` as the residual stream is, the sequence
    over ``sp`` and over ``tp`` too — routing is per token, and a ``tp``
    group that kept the same tokens would do the same experts' work ``tp``
    times."""
    from jax.sharding import PartitionSpec as P

    return P(tuple(a for a in ("dp", "fsdp") if a in mesh.shape) or None,
             tuple(a for a in ("sp", "tp") if a in mesh.shape) or None, None)


@jax.custom_vjp
def silu_mul(gate, up):
    """``silu(gate) * up``, the middle of a dense SwiGLU, whose backward
    makes ``dgate`` and ``dup`` once, as arrays, for the two matmuls each
    feeds."""
    return jax.nn.silu(gate) * up


def _silu_mul_fwd(gate, up):
    return silu_mul(gate, up), (gate, up)


def _silu_mul_bwd(res, g):
    # Left to itself XLA makes dgate = g * up * silu'(gate) from the three
    # (tokens, d_ff) arrays in the operand prologue of both of gate_proj's
    # backward matmuls, and the input gradient's carries the next norm's
    # backward in its epilogue besides: on one chip that matmul ran at
    # 1.4-1.6 times up_proj's (PERF.md section 6, PR 49).  Behind the barrier
    # both gradients are written once, under remat by the recomputed
    # gate_proj's epilogue, and the four matmuls read them as they read any
    # operand.  Both, not dgate alone: where the matmuls are loops over a
    # sharded weight's parts (fsdp) XLA writes dup anyway, and parted from
    # dgate's pass that cost the four-chip cell 2% of its step.  float32
    # inside, rounded as the operands were.
    gate, up = res
    g, a, b = (t.astype(jnp.float32) for t in (g, gate, up))
    sig = jax.nn.sigmoid(a)
    d_gate = g * b * (sig * (1.0 + a * (1.0 - sig)))
    d_up = g * (a * sig)
    return jax.lax.optimization_barrier(
        (d_gate.astype(gate.dtype), d_up.astype(up.dtype)))


silu_mul.defvjp(_silu_mul_fwd, _silu_mul_bwd)


class SharedSwiGLU(nn.Module):
    """The expert every token passes: a dense SwiGLU, or, under an
    ``activation`` without a gate, ``down_proj(relu(up_proj x) ** 2)``;
    ``gated``: times ``sigmoid(gate x)``, one number a token (the sigmoid in
    float32)."""

    d_model: int
    d_ff: int
    dtype: Any = jnp.bfloat16
    activation: str = "silu"
    gated: bool = False

    @nn.compact
    def __call__(self, x):
        def dense(n, name):
            return nn.Dense(n, use_bias=False, dtype=self.dtype, name=name)
        if self.activation in GATELESS:
            out = dense(self.d_model, "down_proj")(
                _hidden((dense(self.d_ff, "up_proj")(x),), self.activation))
        else:
            out = dense(self.d_model, "down_proj")(
                silu_mul(dense(self.d_ff, "gate_proj")(x),
                         dense(self.d_ff, "up_proj")(x)))
        if self.gated:
            out = out * jax.nn.sigmoid(
                dense(1, "gate")(x).astype(jnp.float32)).astype(out.dtype)
        return out


class RoutedSwiGLU(nn.Module):
    """Dropless top-k routed SwiGLU experts.  Call with x of shape (B, S, D).

    Sows, per call, into ``intermediates``: ``moe_load_balance``
    (``E * sum_e f_e P_e``: ``f_e`` the assignments expert ``e`` received per
    token — its share of the (token, slot) assignments times ``top_k`` —
    ``P_e`` its mean router probability, or under sigmoid scores, which do not
sum to one over the experts, its mean share of a token's scores; ``top_k`` at
balance), ``moe_z`` (mean of ``logsumexp(router logits) ** 2``) and
    ``moe_max_load`` (the busiest expert's assignments over the mean), each
    over all ``n_experts``; and, where the layer holds a part of them,
    ``moe_rows_held``: the assignments its own experts received, and
    ``moe_buffer_rows``: the capacity the layer ran at for them (over a mesh,
    the devices' capacities together).

    ``router_input``, where given, is what the router reads in place of
    ``x`` — the logits, the scores, the choice, the weights and the counters
    above all come from it — and the experts still take ``x``: a block that
    routes from its attention's input (``LlamaConfig.router_before_attention``)
    knows each token's experts before attention has run."""

    config: RoutedConfig

    @nn.compact
    def __call__(self, x, router_input=None):
        from ray_tpu.parallel.mesh import ambient_mesh

        cfg = self.config
        n_experts, k, d, f = cfg.n_experts, cfg.top_k, cfg.d_model, cfg.d_ff
        mesh = ambient_mesh()
        if mesh is not None and mesh.shape.get("ep", 1) > 1:
            raise NotImplementedError(
                "RoutedSwiGLU on a mesh with ep > 1: experts sharded over "
                "'ep' need the all-to-all that sends each token's rows to "
                "the device holding its expert and brings the results back, "
                "which this layer does not have; run it with ep=1 (dp / fsdp "
                "/ tp), where every device holds the layer's experts_held")
        with jax.named_scope("router"):
            # float32 at full precision: the router's rounding decides which
            # experts a token gets
            logits = nn.Dense(n_experts, use_bias=False, dtype=jnp.float32,
                              precision=jax.lax.Precision.HIGHEST,
                              name="router")(
                (x if router_input is None else router_input).astype(
                    jnp.float32))
            if cfg.scoring == "softmax":
                probs = shares = jax.nn.softmax(logits, axis=-1)
            elif cfg.scoring == "sigmoid":
                probs = jax.nn.sigmoid(logits)
                shares = probs / jnp.sum(probs, axis=-1, keepdims=True)
            else:
                raise ValueError(f"unknown scoring {cfg.scoring!r} (expected "
                                 "'softmax' or 'sigmoid')")
            if cfg.selection_bias:
                # chosen by score + bias, weighted by the score itself
                bias = self.param("selection_bias", nn.initializers.zeros,
                                  (n_experts,), jnp.float32)
                _, idx = jax.lax.top_k(
                    probs + jax.lax.stop_gradient(bias), k)
                weights = jnp.take_along_axis(probs, idx, axis=-1)
            else:
                weights, idx = jax.lax.top_k(probs, k)
            if cfg.norm_topk_prob:
                total = jnp.sum(weights, axis=-1, keepdims=True)
                if cfg.norm_topk_eps:
                    total = total + cfg.norm_topk_eps
                weights = weights / total
            if cfg.routed_scale != 1.0:
                weights = weights * cfg.routed_scale
            counts = jnp.sum(idx[..., None] == jnp.arange(n_experts),
                             axis=tuple(range(idx.ndim)), dtype=jnp.float32)
            share = counts / idx.size
            self.sow("intermediates", "moe_load_balance",
                     n_experts * k * jnp.sum(share * jnp.mean(
                         shares.reshape(-1, n_experts), axis=0)))
            self.sow("intermediates", "moe_z", jnp.mean(
                jax.nn.logsumexp(logits, axis=-1) ** 2))
            self.sow("intermediates", "moe_max_load",
                     jnp.max(share) * n_experts)
            if cfg.experts_held is not None:
                first, n_held = cfg.experts_held
                self.sow("intermediates", "moe_rows_held",
                         jnp.sum(counts[first:first + n_held]))

        # each expert's own matrix as nn.Dense would initialise it
        init = nn.initializers.lecun_normal(in_axis=-2, out_axis=-1,
                                            batch_axis=(0,))
        n_held = cfg.experts_held[1] if cfg.experts_held else n_experts
        # (an expert without a gate has no gate_proj)
        names = ("gate_proj", "up_proj", "down_proj")[
            cfg.activation in GATELESS:]
        mats = tuple(
            self.param(name, init, (n_held, f, d) if name == "down_proj"
                       else (n_held, d, f), jnp.float32) for name in names)
        with jax.named_scope("experts"):    # the casts are the experts' cost
            mats = tuple(w.astype(cfg.dtype) for w in mats)

        # a layer that holds all its experts fills its buffer by construction
        held = n_held < n_experts

        def experts(x, weights, idx, *mats):
            if held:    # (its loop's scopes carry the module's path)
                return held_experts(x, weights, idx, mats, cfg,
                                    "/".join(self.path))
            return routed_experts(x, weights, idx, mats, cfg)

        if mesh is None or mesh.size == 1:
            out = experts(x, weights, idx, *mats)
        else:
            # each device routes its own tokens through all the experts: the
            # weights come in whole (GSPMD gathers their fsdp / tp shards)
            from jax.sharding import PartitionSpec as P

            tokens = token_spec(mesh)
            out = jax.shard_map(
                experts, mesh=mesh,
                in_specs=(tokens,) * 3 + (P(),) * len(mats),
                out_specs=(tokens, tokens) if held else tokens,
                check_vma=False)(x, weights, idx, *mats)
        if held:    # every device's own capacity, from its own rows
            out, buffer_rows = out
            self.sow("intermediates", "moe_buffer_rows", jnp.sum(buffer_rows))
        if cfg.d_shared:
            out = out + SharedSwiGLU(d, cfg.d_shared, cfg.dtype,
                                     cfg.activation, cfg.shared_gate,
                                     name="shared")(x)
        return out


def collect_aux(intermediates, aux_weight: float = 0.0, z_weight: float = 0.0):
    """What the MoE layers sowed in one forward pass -> (the objective's
    extra term, the step's statistics).  ``MoEMlpBlock`` sows its term
    already weighted; ``RoutedSwiGLU``'s two losses are averaged over the
    routed layers and weighted here; ``max_load`` is the worst layer's and
    ``moe_rows_held`` and ``moe_buffer_rows``, where the layers hold a part
    of their experts, a layer's, averaged over them."""
    by_name: dict = {}
    # sow keeps a tuple under each name: (..., "h_3", "moe", "moe_z", 0)
    for path, leaf in jax.tree_util.tree_flatten_with_path(intermediates)[0]:
        by_name.setdefault(path[-2].key, []).append(jnp.sum(leaf))
    total = sum(by_name.get("moe_aux_loss", []), jnp.float32(0))
    stats = {}
    if "moe_load_balance" in by_name:
        n = len(by_name["moe_load_balance"])
        stats = {"load_balance": sum(by_name["moe_load_balance"]) / n,
                 "z": sum(by_name["moe_z"]) / n,
                 "max_load": jnp.max(jnp.stack(by_name["moe_max_load"]))}
        for name in ("moe_rows_held", "moe_buffer_rows"):
            if name in by_name:    # a layer's, averaged over them
                stats[name] = sum(by_name[name]) / n
        total = total + aux_weight * stats["load_balance"] \
            + z_weight * stats["z"]
    # the hyper-connections' (models/llama.py::HyperConnection): the step's
    # worst |row sum - 1| of a streams' mixing matrix, and largest H_pre
    for name in ("hc_res_row_err", "hc_pre_max"):
        if name in by_name:
            stats[name] = jnp.max(jnp.stack(by_name[name]))
    return total, stats


def collect_moe_aux_loss(intermediates) -> jnp.ndarray:
    """The objective's extra term alone (0 when no layer sowed one)."""
    return collect_aux(intermediates)[0]

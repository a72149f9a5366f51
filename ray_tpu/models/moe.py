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
and the renormalisation stay over all ``n_experts``; the rows bound for
absent experts sort behind every held group, as one last group the grouped
matmuls have no matrix for and never visit, and come back as zeros: the layer
returns its own experts' part of the result.  The row buffer stays ``T * k``
(every assignment may fall to a held expert), the kernels' work follows the
rows that came; nothing is dropped and nothing stands in for the absent chips
or for their exchange.  What ``ep > 1`` on a mesh still lacks is that
exchange — the all-to-all that sends each token's rows to the device holding
its expert and brings the results back — and it raises
``NotImplementedError``.

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
from dataclasses import dataclass
from typing import Any, Optional, Tuple

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


def _gmm_tiling(m: int, k: int, n: int):
    """(m, k, n) tile of the grouped matmul ``(m, k) x (E, k, n)``, by the
    chip's clock at OLMoE's shapes (PERF.md, PR 25): 256 rows, the whole
    contraction and as much of the output's width as keeps one expert's
    weight tile at 2M elements (4 MB in bf16, double-buffered, beside the
    float32 accumulator in 16 MB of scoped VMEM).  Rows of a group that do
    not fill a row tile cost a whole one, and each row tile reads its weight
    tile again, which is what the 256 trades off."""
    tk = min(k, 2048)
    return min(256, _round_up(m, 8)), tk, min(n, (2 << 20) // tk)


def _tgmm_tiling(m: int, k: int, n: int):
    """Tile of ``(m, k).T x (m, n) -> (E, k, n)``: 256 rows of the reduction,
    a 1024 x 1024 float32 accumulator (two operands' tiles in float32 for
    the kernel's masks sit beside it)."""
    return min(256, _round_up(m, 8)), min(k, 1024), min(n, 1024)


def _gmm(lhs, rhs, sizes, *, transpose_rhs=False):
    """``lhs[rows of group e] @ rhs[e]`` (``rhs[e].T`` if ``transpose_rhs``)
    by the megablox kernel.  It wants the row count a multiple of the row
    tile: rows are padded behind the last group and cut off again.  Where
    ``sizes`` counts more groups than ``rhs`` has matrices, the rows of the
    groups past the last matrix are not visited and come back zero."""
    from jax.experimental.pallas.ops.tpu.megablox.gmm import gmm

    m, k = lhs.shape
    tiling = _gmm_tiling(m, k, rhs.shape[1 if transpose_rhs else 2])
    if m % tiling[0]:
        lhs = jnp.pad(lhs, ((0, -m % tiling[0]), (0, 0)))
    out = gmm(lhs, rhs, sizes, preferred_element_type=lhs.dtype, tiling=tiling,
              transpose_rhs=transpose_rhs, interpret=_interpret())
    return out[:m]


def _tgmm(lhs, grad, sizes, dtype, n_groups):
    """Per group ``lhs[rows].T @ grad[rows]``: the weights' gradient,
    ``(n_groups, k, n)``, for the first ``n_groups`` of ``sizes``."""
    from jax.experimental.pallas.ops.tpu.megablox.gmm import tgmm

    m, k = lhs.shape
    tiling = _tgmm_tiling(m, k, grad.shape[1])
    if m % tiling[0]:
        lhs, grad = (jnp.pad(a, ((0, -m % tiling[0]), (0, 0)))
                     for a in (lhs, grad))
    return tgmm(lhs.T, grad, sizes, preferred_element_type=dtype,
                tiling=tiling, num_actual_groups=n_groups,
                interpret=_interpret())


@jax.custom_vjp
def grouped_matmul(lhs, rhs, sizes):
    """``lhs`` (N, k) in contiguous groups of ``sizes`` (E,) rows, each group
    times its own ``rhs[e]`` (E, k, n) -> (N, n).  ``sizes`` may count one
    group more than ``rhs`` holds: see ``_gmm``."""
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


def routed_experts(x, weights, idx, gate, up, down, cfg: RoutedConfig):
    """One device's tokens through their experts.  x (..., D); weights, idx
    (..., k): each token's gate weights and chosen experts; gate, up
    (E, D, F) and down (E, F, D) in the compute dtype — E the experts held
    (``cfg.experts_held``), whose part of the result this is."""
    lead, d = x.shape[:-1], x.shape[-1]
    k, n_groups = cfg.top_k, cfg.n_experts
    x = x.reshape(-1, d)
    with jax.named_scope("dispatch"):
        flat = idx.reshape(-1)
        if cfg.experts_held is not None:
            # held experts by their local index; every absent expert's rows
            # in one last group, behind them, that has no matrix
            first, n_held = cfg.experts_held
            flat = jnp.where((flat >= first) & (flat < first + n_held),
                             flat - first, n_held)
            n_groups = n_held + 1
        order = jnp.argsort(flat, stable=True)
        inverse = jnp.zeros_like(order).at[order].set(
            jnp.arange(order.shape[0], dtype=order.dtype))
        sizes = jnp.sum(flat[:, None] == jnp.arange(n_groups), axis=0,
                        dtype=jnp.int32)
        rows = _rows_to_expert_order(x, order, inverse, k)
        if cfg.experts_held is not None:
            # the buffer holds T * k rows, the worst case; past the held
            # experts' rows it is empty, not other chips' tokens
            rows = jnp.where((jnp.arange(rows.shape[0]) < jnp.sum(
                sizes[:-1]))[:, None], rows, jnp.zeros_like(rows))
    with jax.named_scope("experts"):
        h = jax.nn.silu(grouped_matmul(rows, gate, sizes)) \
            * grouped_matmul(rows, up, sizes)
        rows = grouped_matmul(h, down, sizes)
    with jax.named_scope("combine"):
        rows = _permute_rows(rows, inverse, order).reshape(-1, k, d)
        out = jnp.sum(rows.astype(jnp.float32)
                      * weights.reshape(-1, k, 1), axis=1)
    return out.astype(cfg.dtype).reshape(*lead, d)


def token_spec(mesh):
    """How the routed layer splits a (batch, seq, .) array over ``mesh``:
    the batch over ``dp`` / ``fsdp`` as the residual stream is, the sequence
    over ``sp`` and over ``tp`` too — routing is per token, and a ``tp``
    group that kept the same tokens would do the same experts' work ``tp``
    times."""
    from jax.sharding import PartitionSpec as P

    return P(tuple(a for a in ("dp", "fsdp") if a in mesh.shape) or None,
             tuple(a for a in ("sp", "tp") if a in mesh.shape) or None, None)


class RoutedSwiGLU(nn.Module):
    """Dropless top-k routed SwiGLU experts.  Call with x of shape (B, S, D).

    Sows, per call, into ``intermediates``: ``moe_load_balance``
    (``E * sum_e f_e P_e``: ``f_e`` the assignments expert ``e`` received per
    token — its share of the (token, slot) assignments times ``top_k`` —
    ``P_e`` its mean router probability; ``top_k`` at balance), ``moe_z`` (mean of ``logsumexp(router logits) ** 2``) and
    ``moe_max_load`` (the busiest expert's assignments over the mean), each
    over all ``n_experts``; and, where the layer holds a part of them,
    ``moe_rows_held``: the assignments its own experts received."""

    config: RoutedConfig

    @nn.compact
    def __call__(self, x):
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
                              name="router")(x.astype(jnp.float32))
            probs = jax.nn.softmax(logits, axis=-1)
            weights, idx = jax.lax.top_k(probs, k)
            if cfg.norm_topk_prob:
                weights = weights / jnp.sum(weights, axis=-1, keepdims=True)
            counts = jnp.sum(idx[..., None] == jnp.arange(n_experts),
                             axis=tuple(range(idx.ndim)), dtype=jnp.float32)
            share = counts / idx.size
            self.sow("intermediates", "moe_load_balance",
                     n_experts * k * jnp.sum(share * jnp.mean(
                         probs.reshape(-1, n_experts), axis=0)))
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
        gate, up, down = (
            self.param(name, init, shape, jnp.float32)
            for name, shape in (("gate_proj", (n_held, d, f)),
                                ("up_proj", (n_held, d, f)),
                                ("down_proj", (n_held, f, d))))
        with jax.named_scope("experts"):    # the casts are the experts' cost
            gate, up, down = (w.astype(cfg.dtype) for w in (gate, up, down))

        def experts(x, weights, idx, gate, up, down):
            return routed_experts(x, weights, idx, gate, up, down, cfg)

        if mesh is None or mesh.size == 1:
            return experts(x, weights, idx, gate, up, down)
        # each device routes its own tokens through all the experts: the
        # weights come in whole (GSPMD gathers their fsdp / tp shards)
        from jax.sharding import PartitionSpec as P

        tokens = token_spec(mesh)
        return jax.shard_map(
            experts, mesh=mesh, in_specs=(tokens,) * 3 + (P(),) * 3,
            out_specs=tokens, check_vma=False)(
                x, weights, idx, gate, up, down)


def collect_aux(intermediates, aux_weight: float = 0.0, z_weight: float = 0.0):
    """What the MoE layers sowed in one forward pass -> (the objective's
    extra term, the step's statistics).  ``MoEMlpBlock`` sows its term
    already weighted; ``RoutedSwiGLU``'s two losses are averaged over the
    routed layers and weighted here; ``max_load`` is the worst layer's and
    ``moe_rows_held``, where the layers hold a part of their experts, a
    layer's, averaged over them."""
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
        if "moe_rows_held" in by_name:    # a layer's, averaged over them
            stats["moe_rows_held"] = sum(by_name["moe_rows_held"]) / n
        total = total + aux_weight * stats["load_balance"] \
            + z_weight * stats["z"]
    return total, stats


def collect_moe_aux_loss(intermediates) -> jnp.ndarray:
    """The objective's extra term alone (0 when no layer sowed one)."""
    return collect_aux(intermediates)[0]

"""Learned pooling of keys and values over chunks of positions (Pallas/TPU):
the summaries of EVA attention (``pool_chunks``: what
``models/llama.py::LlamaAttention`` calls under the scope ``attn/pool``).

For every ``chunk`` positions of a head, ``a_m = softmax_m(scale * phi_h .
k_m)`` over the chunk's own positions, the summary key ``sum_m a_m k_m +
mu_h`` and the summary value ``sum_m a_m v_m``.  As XLA fuses the plain form
— a reduction along the lanes for the scores, two along the sublanes for the
sums, in float32 — the pass runs at a sixth of what its bytes take (PERF.md,
PR 47), so it is two Mosaic kernels, a forward and a backward, under one
``custom_vjp``:

- a grid step is one head's tile of ``128 * chunk`` positions, so the tile's
  128 chunks fill the lanes: the weights are a ``(positions, chunks)`` array
  ``w`` that is zero outside a position's own chunk — the scores a
  lane-replicated column, the softmax's max and sum reductions down the
  columns — and both sums are one matmul each, ``w^T k`` and ``w^T v``, on
  the MXU with operands in the inputs' dtype and float32 accumulation, as the
  flash kernels take ``p``;
- k is read head-major, ``(B, H, S, D)`` as the rotation leaves it, v
  token-major, ``(B, S, H * D)`` as its projection wrote it, a head a
  128-lane column block (``ops/attention.py``), and the summaries leave the
  same way; nothing is transposed or copied;
- the backward makes ``w`` again from k and ``phi`` and takes every gradient
  from it in matmuls: ``dv = w dvt``, ``dk = w dkt + scale * dl phi`` with
  ``dl = sum_j w * (da - sum_m w * da)``, ``da = k dkt^T + v dvt^T``; ``phi``'s
  and ``mu``'s gradients leave a row a grid step and are summed beside it.

Under an ambient mesh the calls run in a ``shard_map`` (batch over dp / fsdp,
heads over tp), as the flash kernels do.  The kernels compile for the TPU
unless the process asked for the Pallas interpreter (``ops/attention.py::
_interpret``).
"""

from __future__ import annotations

import functools
import logging

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.sharding import PartitionSpec as P

from ray_tpu.ops.attention import (LANES, NEG_INF, _NN, _NT, _TN, _bhsd_spec,
                                   _dot, _interpret, _pad, _round_up)
from ray_tpu.parallel.mesh import ambient_mesh

logger = logging.getLogger(__name__)


def _weights(k, phi, chunk: int, scale: float):
    """A tile's ``(positions, LANES)`` float32 weights: column ``j`` holds
    the softmax of chunk ``j`` on the rows of its own positions, zero on all
    others."""
    score = jnp.sum(k.astype(jnp.float32) * phi, axis=1, keepdims=True) * scale
    shape = (k.shape[0], LANES)
    row = lax.broadcasted_iota(jnp.int32, shape, 0)
    first = lax.broadcasted_iota(jnp.int32, shape, 1) * chunk
    own = jnp.logical_and(row >= first, row < first + chunk)
    score = jnp.where(own, jnp.broadcast_to(score, shape), NEG_INF)
    e = jnp.exp(score - jnp.max(score, axis=0, keepdims=True))
    return e / jnp.sum(e, axis=0, keepdims=True)


def _fwd_kernel(k_ref, v_ref, phi_ref, mu_ref, kt_ref, vt_ref, *, chunk,
                scale):
    k, v = k_ref[...], v_ref[...]
    w = _weights(k, phi_ref[...], chunk, scale)
    kt_ref[...] = (_dot(w.astype(k.dtype), k, _TN) + mu_ref[...]
                   ).astype(kt_ref.dtype)
    vt_ref[...] = _dot(w.astype(v.dtype), v, _TN).astype(vt_ref.dtype)


def _bwd_kernel(k_ref, v_ref, phi_ref, dkt_ref, dvt_ref, dk_ref, dv_ref,
                dphi_ref, dmu_ref, *, chunk, scale):
    k, v, phi = k_ref[...], v_ref[...], phi_ref[...]
    dkt, dvt = dkt_ref[...], dvt_ref[...]
    w = _weights(k, phi, chunk, scale)
    dv_ref[...] = _dot(w.astype(dvt.dtype), dvt, _NN).astype(dv_ref.dtype)
    # each position's weight's cotangent, on its own chunk's column
    da = _dot(k, dkt, _NT) + _dot(v, dvt, _NT)
    along = jnp.sum(w * da, axis=0, keepdims=True)
    dscore = jnp.sum(w * (da - along), axis=1, keepdims=True) * scale
    dk_ref[...] = (_dot(w.astype(dkt.dtype), dkt, _NN) + dscore * phi
                   ).astype(dk_ref.dtype)
    dphi_ref[...] = jnp.sum(dscore * k.astype(jnp.float32), axis=0,
                            keepdims=True)
    dmu_ref[...] = jnp.sum(dkt.astype(jnp.float32), axis=0, keepdims=True)


def _specs(width: int):
    """BlockSpecs of a grid step (b, h, t): a ``(B, H, ., D)`` operand's
    tile of so many rows, a ``(B, ., H * D)`` one's, and a head's row of
    ``(H, 1, D)``."""
    def major(rows):
        return pl.BlockSpec((None, None, rows, width),
                            lambda b, h, t: (b, h, t, 0))

    def tokens(rows):
        return pl.BlockSpec((None, rows, width), lambda b, h, t: (b, t, h))

    return major, tokens, pl.BlockSpec((None, 1, width),
                                       lambda b, h, t: (h, 0, 0))


def _call(kernel, name, grid, in_specs, out_specs, out_shape, operands):
    from jax.experimental.pallas import tpu as pltpu

    return pl.pallas_call(
        kernel, grid=grid, in_specs=in_specs, out_specs=out_specs,
        out_shape=out_shape,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",) * 3),
        interpret=_interpret(), name=name)(*operands)


def _tiling(s: int, chunk: int):
    """(positions a tile, the row's positions in whole tiles, tiles): a
    tile's ``LANES`` chunks fill the weights' lanes."""
    tile = LANES * chunk
    s_pad = _round_up(s, tile)
    return tile, s_pad, s_pad // tile


@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5))
def _pool(k, v, phi, mu, chunk, scale):
    return _pool_fwd(k, v, phi, mu, chunk, scale)[0]


def _pool_fwd(k, v, phi, mu, chunk, scale):
    b, h, s, d = k.shape
    tile, s_pad, tiles = _tiling(s, chunk)
    major, tokens, row = _specs(d)
    with jax.named_scope("pool_fwd"):
        kt, vt = _call(
            functools.partial(_fwd_kernel, chunk=chunk, scale=scale),
            "pool_fwd", (b, h, tiles),
            [major(tile), tokens(tile), row, row],
            [major(LANES), tokens(LANES)],
            [jax.ShapeDtypeStruct((b, h, tiles * LANES, d), k.dtype),
             jax.ShapeDtypeStruct((b, tiles * LANES, h * d), v.dtype)],
            (_pad(k, 2, s_pad), _pad(v, 1, s_pad), phi[:, None], mu[:, None]))
    n = s // chunk
    return (kt[:, :, :n], vt[:, :n]), (k, v, phi)


def _pool_bwd(chunk, scale, residuals, g):
    k, v, phi = residuals
    dkt, dvt = g
    b, h, s, d = k.shape
    tile, s_pad, tiles = _tiling(s, chunk)
    major, tokens, row = _specs(d)
    part = pl.BlockSpec((None, None, None, 1, d),
                        lambda b, h, t: (b, h, t, 0, 0))
    with jax.named_scope("pool_bwd"):
        dk, dv, dphi, dmu = _call(
            functools.partial(_bwd_kernel, chunk=chunk, scale=scale),
            "pool_bwd", (b, h, tiles),
            [major(tile), tokens(tile), row, major(LANES), tokens(LANES)],
            [major(tile), tokens(tile), part, part],
            [jax.ShapeDtypeStruct((b, h, s_pad, d), k.dtype),
             jax.ShapeDtypeStruct((b, s_pad, h * d), v.dtype)]
            + [jax.ShapeDtypeStruct((b, h, tiles, 1, d), jnp.float32)] * 2,
            (_pad(k, 2, s_pad), _pad(v, 1, s_pad), phi[:, None],
             _pad(dkt, 2, tiles * LANES), _pad(dvt, 1, tiles * LANES)))
        return (dk[:, :, :s], dv[:, :s],
                jnp.sum(dphi, axis=(0, 2, 3)).astype(phi.dtype),
                jnp.sum(dmu, axis=(0, 2, 3)).astype(phi.dtype))


_pool.defvjp(_pool_fwd, _pool_bwd)


def fits(k, v) -> bool:
    """Whether the kernels address the operands as they lie: k head-major, v
    token-major, heads whole lane tiles wide."""
    return k.ndim == 4 and v.ndim == 3 and k.shape[-1] % LANES == 0


def pool_reference(k, v, phi, mu, chunk: int, scale: float):
    """The plain form, the kernels' ground truth: k (B, H, S, D), v (B, S,
    H * D) or (B, H, S, D); the softmax over a chunk and both sums in
    float32, the summaries in the operands' dtypes and ranks."""
    B, H, S, D = k.shape
    n = S // chunk
    if S % chunk:       # a last chunk that is partial has no summary
        k = k[:, :, :n * chunk]
        v = v[:, :, :n * chunk] if v.ndim == 4 else v[:, :n * chunk]
    kc = k.reshape(B, H, n, chunk, D).astype(jnp.float32)
    a = jax.nn.softmax(
        jnp.sum(kc * phi[None, :, None, None, :], axis=-1) * scale, axis=-1)
    k_pooled = jnp.sum(a[..., None] * kc, axis=3) + mu[None, :, None, :]
    if v.ndim == 4:
        v_pooled = jnp.sum(
            a[..., None] * v.reshape(B, H, n, chunk, D).astype(jnp.float32),
            axis=3)
    else:
        vc = v.reshape(B, n, chunk, H, D).astype(jnp.float32)
        v_pooled = jnp.sum(a.transpose(0, 2, 3, 1)[..., None] * vc,
                           axis=2).reshape(B, n, H * D)
    return k_pooled.astype(k.dtype), v_pooled.astype(v.dtype)


def pool_chunks(k, v, phi, mu, chunk: int, scale: float, *, impl: str):
    """What a model's EVA layer calls: one summary key and value for every
    ``chunk`` positions.  k (B, H, S, D), v (B, S, H * D) or (B, H, S, D);
    ``phi``, ``mu`` (H, D) -> the summaries of the row's whole chunks, (B, H,
    S // chunk, D) and v's rank, in the operands' dtypes.  Which form runs is
    decided here and nowhere else, as ``ops.attention.attention`` decides the
    attention's: ``impl`` is a config's ``attention_impl``, and "flash" is
    the kernels wherever they address the operands as they lie (``fits``).
    Where they do not (heads narrower than a lane tile, v head-major)
    "flash" runs the plain form, which XLA runs at a sixth of
    the kernels' speed (PERF.md, PR 47), and says so once in the log; no
    configuration of the benchmark lies there."""
    if impl != "flash":
        return pool_reference(k, v, phi, mu, chunk, scale)
    if not fits(k, v):
        logger.warning(
            "EVA pooling: the kernels do not address k %s / v %s as they lie;"
            " the plain form runs in their place", k.shape, v.shape)
        return pool_reference(k, v, phi, mu, chunk, scale)
    f = functools.partial(_pool, chunk=int(chunk), scale=float(scale))
    phi, mu = phi.astype(jnp.float32), mu.astype(jnp.float32)
    mesh = ambient_mesh()
    if mesh is None or mesh.size == 1:
        return f(k, v, phi, mu)
    major = _bhsd_spec(mesh, ("dp", "fsdp"), "tp")
    tokens = _bhsd_spec(mesh, ("dp", "fsdp"), "tp", tokens=True)
    head = P("tp" if "tp" in mesh.shape else None, None)
    return jax.shard_map(f, mesh=mesh, in_specs=(major, tokens, head, head),
                         out_specs=(major, tokens), check_vma=False)(
                             k, v, phi, mu)

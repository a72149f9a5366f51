"""One sub-layer's manifold-constrained hyper-connection (mHC,
arXiv:2512.24880) around a branch ``F`` of a block whose residual stream ``X``
is ``n`` streams of ``C`` values side by side, (.., n C) — a position:

    x' = RMSNorm(vec(X))                          over all n C values
    H_pre  = sigmoid(alpha_pre (x' phi_pre) + b_pre)             (n)
    H_post = 2 sigmoid(alpha_post (x' phi_post) + b_post)        (n)
    H_res  = Sinkhorn(exp(clip(alpha_res mat(x' phi_res) + b_res)))  (n, n)
    X <- H_res X + H_post^T F(H_pre X)

— as Pallas (Mosaic) passes under one ``jax.custom_vjp`` a call.  In plain
XLA (``hyper_connection_xla``, the lines ``models/llama.py::HyperConnection``
had) the statistic is a pass of its own beside the projection's, the
projection's backward at ``HIGHEST`` six bf16 passes over a result as wide as
the streams, the Sinkhorn 2,470 small operations a step and the streams'
cotangents float32 arrays summed in HBM: 92 ms of Xing4's step at a third of
the memory's speed (``PERF.md``, PR 66).  Here a block is ``_T`` rows by all
``n C`` lanes of a position, so the statistic and the contraction over a row
never leave VMEM, and the passes are:

1. ``hc_mix_fwd`` (the call on the stream alone): reads X once; the sum of
   squares in float32 and ``z = x (scale * phi)`` on the MXU with ``phi`` as
   three bfloat16 pieces (a bfloat16 stream's products are then ``HIGHEST``'s;
   a float32 stream is cut in three too and the six products of ``HIGHEST``
   are taken); the gates and the Sinkhorn a coefficient a plane of the
   block's rows; then ``H_pre X``.  Writes the mix, the coefficients (32
   planes of float32 a position: ``2 n + n^2`` and padding) and, for the
   backward, ``z`` before the statistic and the statistic.
2. the write-back ``H_res X + H_post^T F`` is the plain lines (XLA's fusion
   reads and writes at 87% of the memory's speed, ``PERF.md``, PR 65) under
   the second ``custom_vjp``, whose backward is
3. ``hc_write_bwd``: reads dX', X, F and the coefficients; writes dF, the
   coefficients' cotangents (float32) and ``H_res^T dX'``, X's cotangent, in
   the stream's dtype.
4. ``hc_mix_bwd``: reads X, the mix's cotangent, 3's two results and 1's
   ``z`` and statistic; goes forward through the Sinkhorn keeping every half
   iteration, and back through every one; ``dX = H_res^T dX' + H_pre dmix +
   dz phi'^T + the statistic's term``, summed in VMEM and written once in the
   stream's dtype — ``dz phi'^T`` as one contraction of the six ``HIGHEST``
   products —; ``d(scale * phi)``, ``dbias`` and ``dalpha`` summed in float32
   output blocks that stay in VMEM over the grid.

So that JAX adds no stream-sized ``add_any`` between 3 and 4, call 1 hands X
on as an output that call 2 reads: 3's cotangent of X arrives in 4 as an
operand.  Everything between a load and a store is float32; the coefficients
and their cotangents are never bfloat16; reverse mode goes through every
iteration (``tests/test_hyper_connection_kernel.py``).

``hyper_connection`` and ``write_back`` take the kernels where ``C`` is whole
tiles of 128 lanes, the coefficients fit ``_K`` planes and the stream is
bfloat16 or float32 (``_kernels_apply``); everywhere else (the toy's 64-wide
stream) ``hyper_connection_xla`` and ``write_back_xla`` run: one algorithm,
chosen by shape, here and nowhere else.  Under an
ambient mesh of more than one device the calls run inside a ``shard_map``,
rows over dp/fsdp and the ``n C`` lanes whole.  The kernels lower through
Mosaic unless the process asked for the Pallas interpreter
(``ops/attention.py::_interpret``).
"""

from __future__ import annotations

import functools
import operator
from typing import Any, NamedTuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.experimental import pallas as pl
from jax.sharding import PartitionSpec as P

from ray_tpu.ops.attention import _NN, LANES, _interpret
from ray_tpu.ops.conv import _sigmoid
from ray_tpu.ops.kda import _pieces
from ray_tpu.parallel.mesh import ambient_mesh

# rows of a block: a coefficient's plane of a block is one register's lanes,
# and the transposes between planes and columns are square
_T = LANES
# planes of the coefficients' arrays: 2 n + n^2 and padding, whole bfloat16
# registers for the pieces that go to the MXU
_K = 32
# rows a trip of the write-back's backward takes: one packed bfloat16 register
_ROWS = 16
# rows a trip of the mix's backward sums the gates' cotangents over
_SUM_ROWS = 32
# the calls' VMEM: the backward's three stream-sized blocks twice, the
# weights and their cotangent, with room (a v5e core has 128 MiB)
_VMEM_LIMIT = 100 << 20


class Spec(NamedTuple):
    """What a call's arithmetic is fixed by, beside its operands: the streams,
    the Sinkhorn's iterations, its ``eps`` and the logits' clamp, the
    stream norm's ``eps`` and the dtype of the mix."""
    n: int
    iters: int
    eps: float
    clamp: float
    rms_eps: float
    dtype: Any

    @property
    def k(self) -> int:
        return 2 * self.n + self.n * self.n


class Handed(NamedTuple):
    """What the call on the stream alone hands the write-back where the
    kernels run: ``post`` (n, ..) and ``res`` (n, n, ..) as the plain form
    has them, for whoever reads them; the coefficients' planes and the stream
    as the kernels take them (rows padded to whole blocks)."""
    post: Any
    res: Any
    planes: Any
    x: Any


# ----------------------------------------------------------- jax.numpy form
def sinkhorn(logits, iters: int, eps: float, clamp: float):
    """``exp(clip(logits))`` made (nearly) doubly stochastic: ``iters`` times
    every row divided by its sum + ``eps``, then every column.  ``logits``:
    (rows, columns, ...), a matrix an element of what follows; unrolled, and
    reverse mode goes through every iteration."""
    m = jnp.exp(jnp.clip(logits, -clamp, clamp))
    for _ in range(iters):
        m = m / (jnp.sum(m, axis=1, keepdims=True) + eps)
        m = m / (jnp.sum(m, axis=0, keepdims=True) + eps)
    return m


def streams(x, n: int):
    """The ``n`` streams of (..., n x C), each (..., C): whole lanes where
    ``C`` is."""
    width = x.shape[-1] // n
    return [x[..., j * width:(j + 1) * width] for j in range(n)]


def _rstd(x, eps):
    x = x.astype(jnp.float32)
    return lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps)


def _statistics(pre, res):
    """What ``HyperConnection`` sows: how far ``H_res``'s rows are from
    summing to 1, and the largest gate."""
    return (jnp.max(jnp.abs(jnp.sum(res, axis=1) - 1.0)), jnp.max(pre))


def _streams_float32(x, n: int):
    return [part.astype(jnp.float32) for part in streams(x, n)]


def _mixed(weights, parts):
    return sum(w[..., None] * part for w, part in zip(weights, parts))


def hyper_connection_xla(x, scale, phi, bias, alpha, spec: Spec):
    """``hyper_connection`` as ``jax.numpy`` under reverse mode (with
    ``write_back_xla``): the yardstick of the tests and of the on-chip timing,
    and what runs where the kernels do not apply.  The statistic, the
    projection (``HIGHEST``), the gates and the Sinkhorn are float32 whatever
    the dtypes around; the coefficients are laid out a coefficient a (B, S)
    plane, so that a matrix's row and column sums are adds of planes."""
    n = spec.n
    xs = _streams_float32(x, n)
    with jax.named_scope("coeff"):
        # the norm's scale goes into phi and its statistic multiplies
        # the 2 n + n^2 products: the stream is read, never rewritten
        xf = x.astype(jnp.float32)
        z = jnp.einsum("bsc,ck->kbs", xf, scale[:, None] * phi,
                       precision=jax.lax.Precision.HIGHEST)
        z = z * _rstd(xf, spec.rms_eps)[..., 0]
        z = z * jnp.repeat(alpha, np.array([n, n, n * n]),
                           total_repeat_length=spec.k)[:, None, None] \
            + bias[:, None, None]
        pre = jax.nn.sigmoid(z[:n])
        post = 2.0 * jax.nn.sigmoid(z[n:2 * n])
    with jax.named_scope("sinkhorn"):
        res = sinkhorn(z[2 * n:].reshape(n, n, *z.shape[1:]), spec.iters,
                       spec.eps, spec.clamp)
    statistics = _statistics(pre, res)
    with jax.named_scope("pre"):
        return _mixed(pre, xs).astype(spec.dtype), (post, res), statistics


def write_back_xla(x, branch, coefficients, spec: Spec):
    """``write_back`` as ``jax.numpy``: ``H_res X + H_post^T branch`` from
    ``hyper_connection_xla``'s coefficients (any leading axes)."""
    xs = _streams_float32(x, spec.n)
    post, res = coefficients
    with jax.named_scope("post"):
        f = branch.astype(jnp.float32)
        return jnp.concatenate(
            [_mixed(res[j], xs) + post[j][..., None] * f
             for j in range(spec.n)], axis=-1).astype(x.dtype)


# ------------------------------------------------------------------ kernels
def _parts(x):
    """A block of the stream as the bfloat16 arrays the MXU takes: itself,
    or a float32 one's three pieces."""
    return [x] if x.dtype == jnp.bfloat16 else list(_pieces(x))


def _add(values):
    return functools.reduce(operator.add, values)


def _dot(a, b):
    return lax.dot_general(a, b, _NN, preferred_element_type=jnp.float32)


def _column(i):
    """The ``i``-th 128 lanes of a stream."""
    return pl.multiple_of(i * LANES, LANES)


def _trip(tiles: int, most: int) -> int:
    """The 128-lane tiles a trip of a loop takes: the most that divide a
    stream's, up to ``most``.  (Wide trips are what keeps the MXUs and the
    load slots busy: a tile a trip ran the forward at 25% of the memory's
    speed, sixteen at 71%, ``PERF.md``, PR 66.)"""
    return next(t for t in range(most, 0, -1) if tiles % t == 0)


def _spread(sq, bc, planes):
    """The planes ``planes`` of ``sq`` (plane, row), each as a (row, lane)
    array with a row's coefficient on all its lanes, into ``bc[plane]``: how a
    pass over the streams multiplies by a row's coefficient."""
    columns = sq[...].T
    for p in planes:
        bc[p] = jnp.broadcast_to(columns[:, p:p + 1], bc.shape[1:])


def _gather(sums, first: int):
    """(rows, 128) arrays, each to be summed along its lanes -> one (rows,
    128) array with the ``j``-th's sums on lane ``first + j`` and zeros
    elsewhere: a transpose from the planes' layout."""
    lane = lax.broadcasted_iota(jnp.int32, sums[0].shape, 1)
    out = jnp.zeros_like(sums[0])
    for j, t in enumerate(sums):
        out = jnp.where(lane == first + j,
                        jnp.sum(t, axis=-1, keepdims=True), out)
    return out


def _gates(z, n: int):
    """``H_pre`` and ``H_post`` of the first ``2 n`` planes, and the factor
    that makes them of the sigmoids."""
    sig = _sigmoid(z[:2 * n])
    two = jnp.where(
        lax.broadcasted_iota(jnp.int32, sig.shape, 0) < n, 1.0, 2.0)
    return sig, two


def _groups(n: int):
    """The planes of each row of an (n, n) matrix, then of each column."""
    return ([[n * i + j for j in range(n)] for i in range(n)],
            [[n * i + j for i in range(n)] for j in range(n)])


def _sinkhorn_planes(logits, spec: Spec, kept=None):
    """``sinkhorn`` on a matrix's ``n^2`` planes, each (1, rows); a loop over
    the iterations.  A sum's reciprocal multiplies its row (a division a row
    and not an entry).  ``kept``: two scratch buffers that take every half
    iteration's result and reciprocals, for the way back.  -> the result's
    planes, and ``exp(clip(.))``'s."""
    rows, columns = _groups(spec.n)
    first = tuple(jnp.exp(jnp.clip(p, -spec.clamp, spec.clamp))
                  for p in logits)

    def half(m, groups, at):
        m = list(m)
        for g, members in enumerate(groups):
            r = 1.0 / (_add([m[s] for s in members]) + spec.eps)
            for s in members:
                m[s] = m[s] * r
            if kept is not None:
                kept[1][at, pl.ds(g, 1), :] = r
        if kept is not None:
            for s, plane in enumerate(m):
                kept[0][at, pl.ds(s, 1), :] = plane
        return m

    def iteration(t, m):
        return tuple(half(half(m, rows, 2 * t), columns, 2 * t + 1))

    return lax.fori_loop(0, spec.iters, iteration, first), first


def _sinkhorn_planes_back(dm, logits, first, spec: Spec, kept):
    """The cotangents of ``_sinkhorn_planes``' logits from its result's:
    back through every half iteration — ``out = m r`` with ``r = 1 / (sum
    + eps)`` a group, so ``dm = r (dout - sum over the group of dout out)``
    —, then through ``exp(clip(.))``."""
    rows, columns = _groups(spec.n)
    n2 = spec.n * spec.n

    def half(dout, groups, at):
        out = [kept[0][at, pl.ds(s, 1), :] for s in range(n2)]
        dm = list(dout)
        for g, members in enumerate(groups):
            r = kept[1][at, pl.ds(g, 1), :]
            inner = _add([dout[s] * out[s] for s in members])
            for s in members:
                dm[s] = (dout[s] - inner) * r
        return dm

    def iteration(u, dm):
        t = spec.iters - 1 - u
        return tuple(half(half(dm, columns, 2 * t + 1), rows, 2 * t))

    dm = lax.fori_loop(0, spec.iters, iteration, tuple(dm))
    return [jnp.where(jnp.abs(p) < spec.clamp, d * m, 0.0)
            for d, m, p in zip(dm, first, logits)]


def _mix_kernel(x_ref, w_ref, gain_ref, mix_ref, c_ref, kept_ref, sq, bc, *,
                spec: Spec):
    """A block of rows, the stream alone.  ``x_ref``: (rows, n C);
    ``w_ref``: (pieces of x, n C, 128) bfloat16, ``scale * phi``'s three
    pieces, ``_K`` columns each; ``gain_ref``: (2, _K, rows), ``alpha`` a
    coefficient and the bias; ``mix_ref``: (rows, C); ``c_ref``: (_K, rows),
    the coefficients; ``kept_ref``: (_K + 8, rows), ``z`` before the
    statistic and the statistic.  ``sq``: (128, rows), ``bc``: (n, rows,
    128) scratch."""
    rows, width = x_ref.shape
    n = spec.n
    c = width // n

    wide = _trip(width // LANES, 16)

    def columns(i, carry):
        z, squares = carry
        terms = []
        for l in range(wide):
            cols = pl.ds(_column(i * wide + l), LANES)
            x = x_ref[:, cols]
            terms += [_dot(piece, w_ref[p, cols, :])
                      for p, piece in enumerate(_parts(x))]
            xf = x.astype(jnp.float32)
            squares = squares + xf * xf
        while len(terms) > 1:       # a tree: the dots stay apart, an MXU each
            terms = [_add(terms[k:k + 2]) for k in range(0, len(terms), 2)]
        return z + terms[0], squares

    z, squares = lax.fori_loop(
        0, width // LANES // wide, columns,
        (jnp.zeros((rows, LANES), jnp.float32),) * 2)
    z = z.T
    rstd = lax.rsqrt(jnp.sum(squares.T, axis=0, keepdims=True) / width
                     + spec.rms_eps)
    z = z[:_K] + z[_K:2 * _K] + z[2 * _K:3 * _K]
    kept_ref[:_K, :] = z
    kept_ref[_K:, :] = jnp.broadcast_to(rstd, (8, rows))
    c_ref[...] = z * rstd * gain_ref[0] + gain_ref[1]
    sig, two = _gates(c_ref[...], n)
    res, _ = _sinkhorn_planes(
        [c_ref[pl.ds(2 * n + s, 1), :] for s in range(n * n)], spec)
    c_ref[:2 * n, :] = sig * two
    for s, plane in enumerate(res):
        c_ref[pl.ds(2 * n + s, 1), :] = plane

    sq[...] = jnp.zeros_like(sq)
    sq[:2 * n, :] = sig * two
    _spread(sq, bc, range(n))

    def mix(i, carry):
        at = _column(i)
        mix_ref[:, pl.ds(at, LANES)] = _add(
            [bc[j] * x_ref[:, pl.ds(j * c + at, LANES)].astype(jnp.float32)
             for j in range(n)]).astype(mix_ref.dtype)
        return carry

    lax.fori_loop(0, c // LANES, mix, 0, unroll=True)


def _write_bwd_kernel(dy_ref, x_ref, f_ref, c_ref, g_ref, df_ref, dc_ref, sq,
                      bc, *, spec: Spec):
    """The write-back's backward on a block of rows.  ``dy_ref``, ``x_ref``,
    ``g_ref``: (rows, n C), the result's cotangent, the stream and
    ``H_res^T dy``; ``f_ref``, ``df_ref``: (rows, C); ``c_ref``, ``dc_ref``:
    (_K, rows).  ``sq``: (128, rows), ``bc``: (_K, rows, 128) scratch."""
    rows, width = x_ref.shape
    n = spec.n
    c = width // n
    sq[...] = jnp.zeros_like(sq)
    sq[:_K, :] = c_ref[...]
    _spread(sq, bc, range(n, spec.k))

    def some_rows(r, carry):
        at = pl.ds(pl.multiple_of(r * _ROWS, _ROWS), _ROWS)

        def load(ref, first, j=0):
            return ref[at, pl.ds(j * c + first, LANES)].astype(jnp.float32)

        def column(i, sums):
            first = _column(i)
            dy = [load(dy_ref, first, j) for j in range(n)]
            xs = [load(x_ref, first, j) for j in range(n)]
            f = load(f_ref, first)
            df_ref[at, pl.ds(first, LANES)] = _add(
                [bc[n + j, at, :] * dy[j] for j in range(n)]).astype(
                    df_ref.dtype)
            for k in range(n):
                g_ref[at, pl.ds(k * c + first, LANES)] = _add(
                    [bc[2 * n + n * j + k, at, :] * dy[j]
                     for j in range(n)]).astype(g_ref.dtype)
            products = [dy[j] * f for j in range(n)] + [
                dy[j] * xs[k] for j in range(n) for k in range(n)]
            return tuple(s + p for s, p in zip(sums, products))

        sums = lax.fori_loop(
            0, c // LANES, column,
            (jnp.zeros((_ROWS, LANES), jnp.float32),) * (n + n * n))
        sq[at, :] = _gather(sums, n)
        return carry

    lax.fori_loop(0, rows // _ROWS, some_rows, 0)
    dc_ref[...] = sq[...].T[:_K]


def _mix_bwd_kernel(x_ref, dmix_ref, g_ref, dc_ref, kept_ref, wb_ref,
                    gain_ref, dx_ref, dw_ref, dgain_ref, sq, bc, states,
                    recips, zs, *, spec: Spec):
    """The backward of ``_mix_kernel`` on its block.  ``dmix_ref``: (rows,
    C); ``g_ref``: (rows, n C), the write-back's cotangent of the stream;
    ``dc_ref``: (_K, rows), the coefficients' cotangents; ``wb_ref``: (256,
    n C) bfloat16, the pieces of ``scale * phi`` as the six ``HIGHEST``
    products take them; ``dx_ref``: (rows, n C); ``dw_ref``: (3 _K, n C)
    float32, a piece of ``dz`` against the stream a row, and ``dgain_ref``:
    (2 _K, rows), ``dbias``'s and ``dalpha``'s terms a row — both one block
    for the whole grid, their own accumulators.  Scratch: ``sq``, ``bc`` as
    in ``_mix_kernel`` (one plane more), ``states`` (2 iters, n^2, rows) and
    ``recips`` (2 iters, 8, rows) the Sinkhorn's half iterations, ``zs``
    (_K, rows)."""
    rows, width = x_ref.shape
    n = spec.n
    c = width // n

    @pl.when(pl.program_id(0) == 0)
    def _():
        dw_ref[...] = jnp.zeros_like(dw_ref)
        dgain_ref[...] = jnp.zeros_like(dgain_ref)

    # the gates' cotangents from the mix: sum over a stream of dmix x
    sq[...] = jnp.zeros_like(sq)

    def some_rows(r, carry):
        at = pl.ds(pl.multiple_of(r * _SUM_ROWS, _SUM_ROWS), _SUM_ROWS)

        wide = _trip(c // LANES, 7)

        def columns(i, sums):
            for l in range(wide):
                first = _column(i * wide + l)
                dmix = dmix_ref[at, pl.ds(first, LANES)].astype(jnp.float32)
                sums = tuple(
                    s + dmix * x_ref[at, pl.ds(j * c + first, LANES)].astype(
                        jnp.float32) for j, s in enumerate(sums))
            return sums

        sums = lax.fori_loop(
            0, c // LANES // wide, columns,
            (jnp.zeros((_SUM_ROWS, LANES), jnp.float32),) * n)
        sq[at, :] = _gather(sums, 0)
        return carry

    lax.fori_loop(0, rows // _SUM_ROWS, some_rows, 0)
    dpre = sq[...].T[:2 * n]

    # the coefficients again, and back through them
    z0, rstd = kept_ref[:_K, :], kept_ref[_K:_K + 1, :]
    z1 = z0 * rstd
    zs[...] = z1 * gain_ref[0] + gain_ref[1]
    sig, two = _gates(zs[...], n)
    logits = [zs[pl.ds(2 * n + s, 1), :] for s in range(n * n)]
    _, first = _sinkhorn_planes(logits, spec, (states, recips))
    dres = _sinkhorn_planes_back(
        [dc_ref[pl.ds(2 * n + s, 1), :] for s in range(n * n)], logits,
        first, spec, (states, recips))
    zs[:2 * n, :] = (dc_ref[:2 * n, :] + dpre) * two * sig * (1.0 - sig)
    for s, plane in enumerate(dres):
        zs[pl.ds(2 * n + s, 1), :] = plane
    dz = zs[...]            # (the padding's planes are zeros: their z is)
    dgain_ref[:_K, :] += dz
    dgain_ref[_K:, :] += dz * z1
    dz1 = dz * gain_ref[0]
    dz0 = dz1 * rstd
    # d rstd / dx = -rstd^3 x / (n C)
    back = jnp.sum(dz1 * z0, axis=0, keepdims=True) * (
        -rstd * rstd * rstd / width)

    sq[...] = jnp.zeros_like(sq)
    sq[:2 * n, :] = sig * two
    sq[pl.ds(2 * n, 1), :] = back
    _spread(sq, bc, list(range(n)) + [2 * n])

    # dz's three pieces: against the stream (d(scale * phi), a piece a
    # product that HIGHEST takes with the stream's piece) and, as columns,
    # against scale * phi's (the six products in one contraction)
    d = [p.astype(jnp.float32) for p in _pieces(dz0)]
    none = jnp.zeros_like(d[0])
    pieces = 1 if x_ref.dtype == jnp.bfloat16 else 3
    against_x = [jnp.concatenate([d[j] if j + p < 3 else none
                                  for j in range(3)]).astype(jnp.bfloat16)
                 for p in range(pieces)]
    six = jnp.concatenate([d[0], d[0], d[0], d[1], d[1], d[2], none, none])
    against_w = jnp.concatenate(
        [six[:LANES].T, six[LANES:].T], axis=1).astype(jnp.bfloat16)

    wide = _trip(c // LANES, 4)
    span = wide * LANES

    for j in range(n):
        def columns(i, carry, j=j):
            at = pl.multiple_of(i * span, span)
            cols = pl.ds(j * c + at, span)
            x = x_ref[:, cols]
            projected = _dot(against_w, wb_ref[:, cols])
            dw_ref[:, cols] += _add(
                [_dot(lhs, piece) for lhs, piece in zip(against_x, _parts(x))])
            for l in range(wide):
                sub = pl.ds(j * c + at + l * LANES, LANES)
                dx = (g_ref[:, sub].astype(jnp.float32)
                      + bc[j] * dmix_ref[:, pl.ds(at + l * LANES, LANES)
                                         ].astype(jnp.float32)
                      + x_ref[:, sub].astype(jnp.float32) * bc[2 * n]
                      + projected[:, l * LANES:(l + 1) * LANES])
                dx_ref[:, sub] = dx.astype(dx_ref.dtype)
            return carry

        lax.fori_loop(0, c // span, columns, 0)


# ------------------------------------------------------------------- calls
def _params():
    from jax.experimental.pallas import tpu as pltpu

    return pltpu.CompilerParams(dimension_semantics=("arbitrary",),
                                vmem_limit_bytes=_VMEM_LIMIT)


def _scratch(*shapes):
    from jax.experimental.pallas import tpu as pltpu

    return [pltpu.VMEM(shape, jnp.float32) for shape in shapes]


def _specs(rows: int, width: int, c: int):
    """Block specs over a grid of row blocks: the stream's, the branch's, the
    planes' of ``k`` coefficients, and a whole array's."""
    def planes(k):
        return pl.BlockSpec((k, _T), lambda i: (0, i))

    def whole(*shape):
        return pl.BlockSpec(shape, lambda i: (0,) * len(shape))

    return (pl.BlockSpec((_T, width), lambda i: (i, 0)),
            pl.BlockSpec((_T, c), lambda i: (i, 0)), planes, whole)


def _weight_pieces(scale, phi):
    """``scale * phi`` (n C, k) as its three bfloat16 pieces, a coefficient a
    row, padded to ``_K`` rows: (_K, n C) each, and as many zeros."""
    w = [jnp.pad(p.T, [(0, _K - phi.shape[1]), (0, 0)])
         for p in _pieces(scale[:, None] * phi)]
    return w, jnp.zeros_like(w[0])


def _forward_weights(scale, phi, pieces: int):
    """(pieces of x, n C, 128) for the forward's MXU passes: the ``p``-th
    against the stream's ``p``-th piece, ``_K`` columns a piece of ``scale *
    phi`` (those whose product ``HIGHEST`` drops are zeros)."""
    w, none = _weight_pieces(scale, phi)
    return jnp.pad(
        jnp.stack([jnp.concatenate(
            [w[j] if j + p < 3 else none for j in range(3)]).T
            for p in range(pieces)]), [(0, 0), (0, 0), (0, LANES - 3 * _K)])


def _six_weights(scale, phi):
    """(256, n C) for ``dz phi'^T``: a piece of ``scale * phi`` a product of
    the six ``HIGHEST`` takes, in the order ``_mix_bwd_kernel`` lays ``dz``'s
    pieces out."""
    w, none = _weight_pieces(scale, phi)
    return jnp.concatenate([w[0], w[1], w[2], w[0], w[1], w[0], none, none])


def _gains(bias, alpha, spec: Spec):
    """(2, _K, _T): ``alpha`` a coefficient and the bias, along a block's
    rows."""
    n = spec.n
    both = jnp.stack([
        jnp.repeat(alpha, np.array([n, n, n * n]), total_repeat_length=spec.k),
        bias])
    return jnp.broadcast_to(
        jnp.pad(both, [(0, 0), (0, _K - spec.k)])[..., None], (2, _K, _T))


@functools.partial(jax.jit, static_argnums=(3,), inline=True)
def _mix_forward(x, forward, gains, spec: Spec):
    """Jitted and inlined, as ``ops/gated_norm.py``'s ``_forward``: a model's
    sub-layers share one trace of the kernel, and the equations land in the
    caller's jaxpr under the caller's scopes."""
    rows, width = x.shape
    c = width // spec.n
    stream, branch, planes, whole = _specs(rows, width, c)
    return pl.pallas_call(
        functools.partial(_mix_kernel, spec=spec),
        grid=(rows // _T,),
        in_specs=[stream, whole(*forward.shape), whole(2, _K, _T)],
        out_specs=[branch, planes(_K), planes(_K + 8)],
        out_shape=[jax.ShapeDtypeStruct((rows, c), spec.dtype),
                   jax.ShapeDtypeStruct((_K, rows), jnp.float32),
                   jax.ShapeDtypeStruct((_K + 8, rows), jnp.float32)],
        scratch_shapes=_scratch((LANES, _T), (spec.n, _T, LANES)),
        compiler_params=_params(), interpret=_interpret(),
        name="hc_mix_fwd")(x, forward, gains)


@functools.partial(jax.jit, static_argnums=(7,), inline=True)
def _mix_backward(x, dmix, g, dc, kept, six, gains, spec: Spec):
    rows, width = x.shape
    c = width // spec.n
    stream, branch, planes, whole = _specs(rows, width, c)
    return pl.pallas_call(
        functools.partial(_mix_bwd_kernel, spec=spec),
        grid=(rows // _T,),
        in_specs=[stream, branch, stream, planes(_K), planes(_K + 8),
                  whole(*six.shape), whole(2, _K, _T)],
        out_specs=[stream, whole(3 * _K, width), whole(2 * _K, _T)],
        out_shape=[jax.ShapeDtypeStruct(x.shape, x.dtype),
                   jax.ShapeDtypeStruct((3 * _K, width), jnp.float32),
                   jax.ShapeDtypeStruct((2 * _K, _T), jnp.float32)],
        scratch_shapes=_scratch(
            (LANES, _T), (2 * spec.n + 1, _T, LANES),
            (2 * spec.iters, spec.n * spec.n, _T), (2 * spec.iters, 8, _T),
            (_K, _T)),
        compiler_params=_params(), interpret=_interpret(),
        name="hc_mix_bwd")(x, dmix, g, dc, kept, six, gains)


@functools.partial(jax.jit, static_argnums=(4,), inline=True)
def _write_backward(dy, x, f, planes_, spec: Spec):
    rows, width = x.shape
    c = width // spec.n
    stream, branch, planes, _ = _specs(rows, width, c)
    return pl.pallas_call(
        functools.partial(_write_bwd_kernel, spec=spec),
        grid=(rows // _T,),
        in_specs=[stream, stream, branch, planes(_K)],
        out_specs=[stream, branch, planes(_K)],
        out_shape=[jax.ShapeDtypeStruct(x.shape, x.dtype),
                   jax.ShapeDtypeStruct(f.shape, f.dtype),
                   jax.ShapeDtypeStruct((_K, rows), jnp.float32)],
        scratch_shapes=_scratch((LANES, _T), (_K, _T, LANES)),
        compiler_params=_params(), interpret=_interpret(),
        name="hc_write_bwd")(dy, x, f, planes_)


# ------------------------------------------------- the differentiation rules
@functools.partial(jax.custom_vjp, nondiff_argnums=(5,))
def _mix(x, scale, phi, bias, alpha, spec: Spec):
    """The call on the stream alone.  ``x``: (rows, n C), rows whole blocks
    -> the mix (rows, C), the coefficients' planes (_K, rows) and ``x``
    again: what the write-back reads, so that its cotangent of the stream is
    an operand of this call's backward and not a term JAX adds."""
    return _mix_fwd(x, scale, phi, bias, alpha, spec)[0]


def _mix_fwd(x, scale, phi, bias, alpha, spec):
    forward = _forward_weights(scale, phi,
                               1 if x.dtype == jnp.bfloat16 else 3)
    mix, planes, kept = _mix_forward(x, forward, _gains(bias, alpha, spec),
                                     spec)
    return (mix, planes, x), (x, scale, phi, bias, alpha, kept)


def _mix_bwd(spec, residuals, cotangents):
    x, scale, phi, bias, alpha, kept = residuals
    dmix, dplanes, g = cotangents
    n, k = spec.n, spec.k
    dx, dw, dgain = _mix_backward(x, dmix, g, dplanes, kept,
                                  _six_weights(scale, phi),
                                  _gains(bias, alpha, spec), spec)
    dw = (dw[:k] + dw[_K:_K + k] + dw[2 * _K:2 * _K + k]).T     # (n C, k)
    dgain = jnp.sum(dgain, axis=1)
    dalpha = dgain[_K:_K + k]
    return (dx, jnp.sum(phi * dw, axis=1), scale[:, None] * dw, dgain[:k],
            jnp.stack([jnp.sum(dalpha[:n]), jnp.sum(dalpha[n:2 * n]),
                       jnp.sum(dalpha[2 * n:])]))


_mix.defvjp(_mix_fwd, _mix_bwd)


def _coefficients(planes, spec: Spec):
    """``H_pre``, ``H_post`` (n, ..) and ``H_res`` (n, n, ..) of the planes
    (_K, ..)."""
    n = spec.n
    return (planes[:n], planes[n:2 * n],
            planes[2 * n:spec.k].reshape(n, n, *planes.shape[1:]))


@functools.partial(jax.custom_vjp, nondiff_argnums=(3,))
def _write(x, f, planes, spec: Spec):
    """The write-back: ``x`` (rows, n C), ``f`` (rows, C), the coefficients'
    planes (_K, rows) -> the stream after the branch.  Forward the plain
    lines, backward one pass."""
    return write_back_xla(x, f, _coefficients(planes, spec)[1:], spec)


def _write_fwd(x, f, planes, spec):
    return _write(x, f, planes, spec), (x, f, planes)


def _write_bwd(spec, residuals, dy):
    return _write_backward(dy, *residuals, spec)


_write.defvjp(_write_fwd, _write_bwd)


# -------------------------------------------------------------- entry point
def _kernels_apply(x, spec: Spec) -> bool:
    return (x.shape[-1] % (spec.n * LANES) == 0 and spec.k <= _K
            and x.dtype in (jnp.bfloat16, jnp.float32))


def _sharded(fn, mesh, in_specs, out_specs):
    if mesh is None:
        return fn
    return jax.shard_map(fn, mesh=mesh, in_specs=in_specs,
                         out_specs=out_specs, check_vma=False)


def _layout():
    """The ambient mesh, if it is more than one device, and its axes that
    the rows go over."""
    mesh = ambient_mesh()
    if mesh is None or mesh.size == 1:
        return None, None
    return mesh, tuple(a for a in ("dp", "fsdp") if a in mesh.shape) or None


def _blocks(t):
    """(batch, seq, lanes) -> (rows in whole blocks, lanes)."""
    t = t.reshape(-1, t.shape[-1])
    return jnp.pad(t, [(0, -t.shape[0] % _T), (0, 0)])


def hyper_connection(x, scale, phi, bias, alpha, spec: Spec):
    """The call on the stream alone.  ``x``: (batch, seq, n C), the streams
    side by side; ``scale`` (n C,), ``phi`` (n C, 2 n + n^2), its columns
    ``[pre ; post ; res]`` (``res`` row by row), ``bias`` the same, ``alpha``
    the three scalars, all float32.  -> ``(H_pre X`` (batch, seq, C) in
    ``spec.dtype``, the coefficients — what ``write_back`` takes, ``[0]`` and
    ``[1]`` of which are ``H_post`` (n, batch, seq) and ``H_res`` (n, n,
    batch, seq) —, (the largest error of ``H_res``'s row sums, the largest
    ``H_pre``)``)``.  Any number of rows (they are padded to whole blocks,
    and the padding's outputs dropped)."""
    if not _kernels_apply(x, spec):
        return hyper_connection_xla(x, scale, phi, bias, alpha, spec)
    mesh, rows = _layout()
    seq = x.shape[1]

    def mix(x, scale, phi, bias, alpha):
        local = x.shape[0] * seq
        with jax.named_scope("coeff"):
            mixed, planes, handed = _mix(_blocks(x), scale, phi, bias, alpha,
                                         spec)
        return (mixed[:local].reshape(*x.shape[:2], -1),
                planes[:, :local].reshape(_K, *x.shape[:2]), planes, handed)

    mixed, seen, planes, handed = _sharded(
        mix, mesh, (P(rows, None, None), P(), P(), P(), P()),
        (P(rows, None, None), P(None, rows, None), P(None, rows),
         P(rows, None)))(x, scale, phi, bias, alpha)
    pre, post, res = _coefficients(seen, spec)
    return mixed, Handed(post, res, planes, handed), _statistics(pre, res)


def write_back(x, branch, coefficients, spec: Spec):
    """``H_res X + H_post^T branch``: the stream after the branch, from
    ``hyper_connection``'s coefficients of the same ``x`` (where the kernels
    run, the stream is read as that call handed it on)."""
    if not _kernels_apply(x, spec):
        return write_back_xla(x, branch, coefficients[:2], spec)
    mesh, rows = _layout()

    def write(x, f, planes):
        with jax.named_scope("post"):
            out = _write(x, _blocks(f), planes, spec)
        return out[:f.shape[0] * f.shape[1]].reshape(*f.shape[:2], -1)

    return _sharded(
        write, mesh, (P(rows, None), P(rows, None, None), P(None, rows)),
        P(rows, None, None))(coefficients.x, branch, coefficients.planes)

"""Mamba-1's selective scan (Gu and Dao 2023, "Mamba: Linear-Time Sequence
Modeling with Selective State Spaces"): a diagonal recurrence with a decay a
channel a state,

    h_t[c, j] = exp(delta_t[c] A[c, j]) h_{t-1}[c, j] + delta_t[c] B_t[j] u_t[c]
    y_t[c]    = sum_j C_t[j] h_t[c, j] + D[c] u_t[c]                  h_0 = 0

with ``d`` channels beside each other, ``N`` states a channel, ``B_t`` and
``C_t`` shared by all channels and the step size ``delta_t`` a channel's own.
The decay differs for each of the ``d x N`` state cells, so no chunk of it is
a matmul shared across channels (``ops/ssd.py``'s scalar decay a head and
``ops/kda.py``'s decay a channel of a head's key both are): the work is
elementwise, on the vector and transcendental units, and the sequence is cut
into blocks of ``block`` positions only so that the backward can start again
from the state kept at each block's end instead of keeping every ``h_t``.

Two forms under the same differentiation rule (``_scan``'s ``custom_vjp``):
the forward walks the blocks up, keeping the float32 state before each; the
backward walks them down, makes a block's states again from the kept one and
runs the reverse recurrence for ``dh``.

- ``selective_scan_xla``: plain ``jax.numpy``.  A block is a
  ``lax.associative_scan`` over its positions (products of decays, never a
  quotient, so a product that underflows is a zero and nothing else), its
  backward XLA's own derivative of that; the walk over the blocks is a
  ``lax.scan``.  The yardstick of the tests and of the on-chip timing, and
  what runs where the kernels cannot address the channels (``d`` no multiple
  of 128).
- ``selective_scan``: two Pallas (Mosaic) kernels.  The grid is (batch,
  block of positions, channel block): the walk over the positions sequential
  — the forward's up, the backward's down —, the channel blocks its inner
  axis, and the float32 state of every channel (the backward: its cotangent)
  in VMEM for the whole walk, ``d x N`` x 4 bytes.  A channel block is up to
  1,024 channels held as ``(8, 128)``: eight sublanes of 128 lanes, one
  register a state, so the 16 states of 1,024 channels are 16 registers that
  never leave the core between positions.  ``u``, ``delta`` and ``y`` are
  addressed as ``(batch, seq, d / 128, 128)`` — the channels as the
  projections wrote them, only seen as rows of 128 — so that one position's
  1,024 channels are one register; ``B_t[j]`` and ``C_t[j]`` are scalars in
  SMEM that multiply whole registers.  A position costs a channel block 16 x
  (``exp`` on the EUP, five multiplies and two adds on the VPU) and no
  reduction across lanes or sublanes: ``y_t`` is a sum of 16 registers.  The
  backward makes the block's ``h_t`` again into VMEM (``block`` x 16
  registers), walks the positions down with ``dh`` and ``dA`` in registers,
  and leaves ``dB`` and ``dC`` — sums over ALL channels — summed over a
  channel block's sublanes in the kernel, over the channel blocks by
  revisiting the output block (the channel blocks are the inner axis), and
  over the 128 lanes beside it, in XLA.

``delta``, ``delta * A``, the ``exp``, the state and its cotangent are float32
whatever the activations are; ``u``, ``B``, ``C`` come in the activations'
dtype and ``y`` leaves in it.  Under an ambient mesh of more than one device
the kernels run inside a ``shard_map`` — batch over dp/fsdp, channels over tp
with ``B`` and ``C`` whole on every device of a tp group (their gradients
summed over it) — since GSPMD cannot partition a Mosaic call.  The kernels
lower through Mosaic unless the process asked for the Pallas interpreter
(``ops/attention.py::_interpret``).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.sharding import PartitionSpec as P

from ray_tpu.ops.attention import LANES, _interpret
from ray_tpu.parallel.mesh import ambient_mesh

SUBLANES = 8
# positions a trip of the kernels' loops takes, written out in the trip's body
# (Mosaic's own ``unroll`` is all or nothing).  By the chip's clock at 1 x
# 16,384 x 5,120 x 16 (PERF.md, PR 55): forward 6.18 / 5.69 / 5.48 / 5.36 ms
# and forward + backward 18.89 / 17.44 / 16.66 / 16.29 ms at 1 / 2 / 4 / 8,
# the step's compile 31.6 / 38.8 / 45.8 / 48.1 s
_UNROLL = 4


def _positions(block: int, body, carry):
    """``lax.fori_loop(0, block, body, carry)`` in trips of ``_UNROLL``."""
    trip = _UNROLL if block % _UNROLL == 0 else 1
    if trip == 1:
        return lax.fori_loop(0, block, body, carry)

    def several(i, carry):
        for k in range(trip):
            carry = body(i * trip + k, carry)
        return carry

    return lax.fori_loop(0, block // trip, several, carry)


# ------------------------------------------------------------ jax.numpy form
def _block_xla(h0, u, delta, at, b, c):
    """One block from the state before it.  ``h0``: (b, N, d) float32;
    ``u``, ``delta``: (b, T, d); ``at``: (N, d), ``A`` with the channels
    along the lanes; ``b``, ``c``: (b, T, N).  -> (y (b, T, d) float32, the
    state after the block)."""
    f32 = jnp.float32
    u, b, c = u.astype(f32), b.astype(f32), c.astype(f32)
    decay = jnp.exp(delta[:, :, None, :] * at)                # (b, T, N, d)
    wrote = (delta * u)[:, :, None, :] * b[..., None]

    def then(first, second):
        (a1, w1), (a2, w2) = first, second
        return a1 * a2, a2 * w1 + w2

    decays, written = lax.associative_scan(then, (decay, wrote), axis=1)
    h = decays * h0[:, None] + written
    return jnp.sum(h * c[..., None], axis=2), h[:, -1]


def _blocks(x, block: int):
    """(b, S, w) -> (S / block, b, block, w): what a ``lax.scan`` walks."""
    b, s, w = x.shape
    return x.reshape(b, s // block, block, w).swapaxes(0, 1)


def _unblocks(x):
    n, b, t, w = x.shape
    return x.swapaxes(0, 1).reshape(b, n * t, w)


def _forward_xla(u, delta, at, b, c, block: int):
    def step(h, xs):
        y, after = _block_xla(h, *xs[:2], at, *xs[2:])
        return after, (y, h)

    h0 = jnp.zeros((u.shape[0], *at.shape), jnp.float32)
    _, (y, before) = lax.scan(
        step, h0, tuple(_blocks(t, block) for t in (u, delta, b, c)))
    return _unblocks(y).astype(u.dtype), before


def _backward_xla(u, delta, at, b, c, before, dy, block: int):
    def step(carry, xs):
        dh, dat = carry
        h, u_k, delta_k, b_k, c_k, dy_k = xs
        _, pull = jax.vjp(_block_xla, h, u_k, delta_k, at, b_k, c_k)
        dh, du, ddelta, dat_k, db, dc = pull((dy_k.astype(jnp.float32), dh))
        return (dh, dat + dat_k), (du, ddelta, db, dc)

    zero = jnp.zeros((u.shape[0], *at.shape), jnp.float32)
    (_, dat), parts = lax.scan(
        step, (zero, jnp.zeros_like(at)),
        (before, *(_blocks(t, block) for t in (u, delta, b, c, dy))),
        reverse=True)
    du, ddelta, db, dc = map(_unblocks, parts)
    return du, ddelta, dat, db, dc


# ------------------------------------------------------------------- kernels
def _rows(d: int) -> int:
    """Rows of 128 channels a channel block: eight where ``d`` has them."""
    rows = d // LANES
    return SUBLANES if rows % SUBLANES == 0 else rows


def _advanced(h, t, a, b_ref, u_ref, delta_ref):
    """The states of a channel block one position on: ``h`` and ``a`` one
    (rows, 128) register a state, position ``t`` of the block's refs."""
    n = len(h)
    delta = delta_ref[t]
    wrote = delta * u_ref[t].astype(jnp.float32)
    return tuple(jnp.exp(delta * a[j]) * h[j] + wrote * b_ref[t * n + j]
                 for j in range(n))


def _scan_fwd_kernel(b_ref, c_ref, u_ref, delta_ref, a_ref, y_ref, h_ref,
                     state, *, block: int, n: int):
    """One block of positions of one channel block.  ``b_ref``, ``c_ref``:
    (block * n,) float32 in SMEM; ``u_ref``, ``delta_ref``, ``y_ref``:
    (block, rows, 128); ``a_ref``: (n, rows, 128); ``h_ref``: (n, rows, 128),
    the state before this block; ``state``: (channel blocks, n, rows, 128),
    every channel's, carried from block to block."""
    cb = pl.program_id(2)

    @pl.when(pl.program_id(1) == 0)
    def _():
        state[cb] = jnp.zeros(state.shape[1:], state.dtype)

    h_ref[...] = state[cb]
    a = [a_ref[j] for j in range(n)]

    def position(t, h):
        h = _advanced(h, t, a, b_ref, u_ref, delta_ref)
        y = h[0] * c_ref[t * n]
        for j in range(1, n):
            y = y + h[j] * c_ref[t * n + j]
        y_ref[t] = y.astype(y_ref.dtype)
        return h

    h = _positions(block, position, tuple(state[cb, j] for j in range(n)))
    for j in range(n):
        state[cb, j] = h[j]


def _scan_bwd_kernel(b_ref, c_ref, u_ref, delta_ref, a_ref, h_ref, dy_ref,
                     du_ref, ddelta_ref, da_ref, db_ref, dc_ref, kept, dstate,
                     *, block: int, n: int):
    """The same block, walked down.  ``h_ref``: the state before it, as the
    forward kept it; ``kept``: (block + 1, n, rows, 128), the block's states
    made again; ``dstate``: (channel blocks, n, rows, 128), the cotangent of
    the state after the block, carried down; ``da_ref``: the same shape,
    ``A``'s gradient, one block of the output for the whole walk and so its
    own accumulator; ``db_ref``, ``dc_ref``: (block, n, 128), the sums over
    the channels less the one along the lanes: a channel block's eight rows
    are summed here, the channel blocks by revisiting the output."""
    cb = pl.program_id(2)

    @pl.when(pl.program_id(1) == 0)      # the walk is down: the last block
    def _():
        dstate[cb] = jnp.zeros(dstate.shape[1:], dstate.dtype)
        da_ref[cb] = jnp.zeros(da_ref.shape[1:], da_ref.dtype)

    @pl.when(cb == 0)
    def _():
        db_ref[...] = jnp.zeros_like(db_ref)
        dc_ref[...] = jnp.zeros_like(dc_ref)

    a = [a_ref[j] for j in range(n)]
    kept[0] = h_ref[...]

    def again(t, h):
        h = _advanced(h, t, a, b_ref, u_ref, delta_ref)
        for j in range(n):
            kept[t + 1, j] = h[j]
        return h

    _positions(block, again, tuple(h_ref[j] for j in range(n)))

    def position(i, carry):
        dh, da = carry
        t = block - 1 - i
        delta = delta_ref[t]
        u = u_ref[t].astype(jnp.float32)
        dy = dy_ref[t].astype(jnp.float32)
        wrote = delta * u
        dwrote = jnp.zeros_like(delta)
        ddelta = jnp.zeros_like(delta)
        dh_out, da_out = [], []
        for j in range(n):
            row = pl.ds(j, 1)
            dh_j = dh[j] + dy * c_ref[t * n + j]
            decay = jnp.exp(delta * a[j])
            # the decay's cotangent is dh_j h_{t-1}; through the exp: x decay
            through = dh_j * kept[t, j] * decay
            ddelta = ddelta + through * a[j]
            da_out.append(da[j] + through * delta)
            dwrote = dwrote + dh_j * b_ref[t * n + j]
            dc_ref[t, row, :] += jnp.sum(dy * kept[t + 1, j], axis=0,
                                         keepdims=True)
            db_ref[t, row, :] += jnp.sum(dh_j * wrote, axis=0, keepdims=True)
            dh_out.append(dh_j * decay)
        du_ref[t] = (dwrote * delta).astype(du_ref.dtype)
        ddelta_ref[t] = ddelta + dwrote * u
        return tuple(dh_out), tuple(da_out)

    dh, da = _positions(
        block, position, (tuple(dstate[cb, j] for j in range(n)),
                          tuple(da_ref[cb, j] for j in range(n))))
    for j in range(n):
        dstate[cb, j] = dh[j]
        da_ref[cb, j] = da[j]


class _Shape:
    """One call's sizes and BlockSpecs.  The grid is (batch, blocks of
    positions, channel blocks): the walk over the positions sequential, the
    channel blocks its inner axis."""

    def __init__(self, u, a, block: int):
        self.batch, self.seq, self.d = u.shape
        self.n = a.shape[1]
        self.block = block
        self.rows = _rows(self.d)
        self.cblocks = self.d // (LANES * self.rows)
        self.nblocks = self.seq // block
        self.carried = (self.cblocks, self.n, self.rows, LANES)

    def wide(self, x):
        """(batch, seq, d) as rows of 128 channels."""
        return x.reshape(self.batch, self.seq, self.d // LANES, LANES)

    def flat(self, x):
        return x.reshape(self.batch, self.seq, self.d)

    def states(self, a):
        """(d, n) -> (n, d / 128, 128): a state's channels as ``wide``."""
        return a.T.reshape(self.n, self.d // LANES, LANES)

    def specs(self, down: bool):
        from jax.experimental.pallas import tpu as pltpu

        def at(k):
            return self.nblocks - 1 - k if down else k

        scalars = pl.BlockSpec(
            (None, self.block * self.n), lambda b, k, cb: (b, at(k)),
            memory_space=pltpu.SMEM)
        wide = pl.BlockSpec((None, self.block, self.rows, LANES),
                            lambda b, k, cb: (b, at(k), cb, 0))
        a = pl.BlockSpec((self.n, self.rows, LANES),
                         lambda b, k, cb: (0, cb, 0))
        state = pl.BlockSpec((None, None, self.n, self.rows, LANES),
                             lambda b, k, cb: (b, at(k), 0, cb, 0))
        da = pl.BlockSpec((None, *self.carried),
                          lambda b, k, cb: (b, 0, 0, 0, 0))
        sums = pl.BlockSpec((None, self.block, self.n, LANES),
                            lambda b, k, cb: (b, at(k), 0, 0))
        return scalars, wide, a, state, da, sums

    def call(self, kernel, name, **kwargs):
        from jax.experimental.pallas import tpu as pltpu

        return pl.pallas_call(
            functools.partial(kernel, block=self.block, n=self.n),
            grid=(self.batch, self.nblocks, self.cblocks),
            compiler_params=pltpu.CompilerParams(
                dimension_semantics=("parallel", "arbitrary", "arbitrary"),
                vmem_limit_bytes=64 << 20),
            interpret=_interpret(), name=name, **kwargs)


def _scalars(x):
    """(batch, seq, n) -> (batch, seq * n) float32: SMEM's."""
    return x.astype(jnp.float32).reshape(x.shape[0], -1)


def _forward_kernels(u, delta, a, b, c, block: int):
    from jax.experimental.pallas import tpu as pltpu

    s = _Shape(u, a, block)
    scalars, wide, a_spec, state, _, _ = s.specs(False)
    y, before = s.call(
        _scan_fwd_kernel, "selective_scan_fwd",
        in_specs=[scalars, scalars, wide, wide, a_spec],
        out_specs=[wide, state],
        out_shape=[
            jax.ShapeDtypeStruct(s.wide(u).shape, u.dtype),
            jax.ShapeDtypeStruct((s.batch, s.nblocks, s.n, s.d // LANES,
                                  LANES), jnp.float32)],
        scratch_shapes=[pltpu.VMEM(s.carried, jnp.float32)],
    )(_scalars(b), _scalars(c), s.wide(u), s.wide(delta), s.states(a))
    return s.flat(y), before


def _backward_kernels(u, delta, a, b, c, before, dy, block: int):
    from jax.experimental.pallas import tpu as pltpu

    s = _Shape(u, a, block)
    scalars, wide, a_spec, state, da_spec, sums = s.specs(True)
    part = jax.ShapeDtypeStruct((s.batch, s.seq, s.n, LANES), jnp.float32)
    du, ddelta, da, db, dc = s.call(
        _scan_bwd_kernel, "selective_scan_bwd",
        in_specs=[scalars, scalars, wide, wide, a_spec, state, wide],
        out_specs=[wide, wide, da_spec, sums, sums],
        out_shape=[
            jax.ShapeDtypeStruct(s.wide(u).shape, u.dtype),
            jax.ShapeDtypeStruct(s.wide(u).shape, jnp.float32),
            jax.ShapeDtypeStruct((s.batch, *s.carried), jnp.float32),
            part, part],
        scratch_shapes=[
            pltpu.VMEM((block + 1, s.n, s.rows, LANES), jnp.float32),
            pltpu.VMEM(s.carried, jnp.float32)],
    )(_scalars(b), _scalars(c), s.wide(u), s.wide(delta), s.states(a),
      before, s.wide(dy))
    # (channel blocks, n, rows, 128) -> (d, n)
    da = jnp.sum(da, axis=0).transpose(1, 0, 2, 3).reshape(s.n, s.d).T
    db, dc = (jnp.sum(t, axis=-1) for t in (db, dc))
    return s.flat(du), s.flat(ddelta), da, db, dc


# ------------------------------------------------------ the rule they share
@functools.partial(jax.custom_vjp, nondiff_argnums=(5, 6))
def _scan(u, delta, a, b, c, block, kernels):
    """(batch, seq, d) operands, seq whole blocks, ``delta`` float32, ``a``
    (d, n) float32: the recurrence without ``D``."""
    return _scan_fwd(u, delta, a, b, c, block, kernels)[0]


def _scan_fwd(u, delta, a, b, c, block, kernels):
    if kernels:
        y, before = _forward_kernels(u, delta, a, b, c, block)
    else:
        y, before = _forward_xla(u, delta, a.T, b, c, block)
    return y, (u, delta, a, b, c, before)


def _scan_bwd(block, kernels, residuals, dy):
    u, delta, a, b, c, before = residuals
    if kernels:
        du, ddelta, da, db, dc = _backward_kernels(*residuals, dy, block)
    else:
        du, ddelta, dat, db, dc = _backward_xla(u, delta, a.T, b, c, before,
                                                dy, block)
        da = dat.T
    return (du.astype(u.dtype), ddelta, da, db.astype(b.dtype),
            dc.astype(c.dtype))


_scan.defvjp(_scan_fwd, _scan_bwd)


def _tp() -> int:
    mesh = ambient_mesh()
    return 1 if mesh is None else mesh.shape.get("tp", 1)


def _sharded(u, delta, a, b, c, block: int):
    """The kernels' call under an ambient mesh: batch over dp/fsdp, channels
    over tp, ``B`` and ``C`` whole on every device of a tp group."""
    scan = functools.partial(_scan, block=block, kernels=True)
    mesh = ambient_mesh()
    if mesh is None or mesh.size == 1:
        return scan(u, delta, a, b, c)
    tp = "tp" if _tp() > 1 else None
    rows = tuple(axis for axis in ("dp", "fsdp") if axis in mesh.shape) or None
    by_channel = P(rows, None, tp)
    return jax.shard_map(
        scan, mesh=mesh,
        in_specs=(by_channel, by_channel, P(tp, None), P(rows), P(rows)),
        out_specs=by_channel, check_vma=False)(u, delta, a, b, c)


def _whole_blocks(scan, u, delta, a, b, c, d_skip, block: int):
    """``scan`` on the operands padded at the sequence's end to whole blocks
    with ``delta = 0`` — a step that neither decays the state nor writes to
    it —, the padding's outputs dropped, and ``D u`` added."""
    seq = u.shape[1]
    block = min(block, -(-seq // SUBLANES) * SUBLANES)
    pad = -seq % block
    delta, a = delta.astype(jnp.float32), a.astype(jnp.float32)
    operands = (u, delta, b, c)
    if pad:
        operands = tuple(jnp.pad(t, [(0, 0), (0, pad), (0, 0)])
                         for t in operands)
    y = scan(operands[0], operands[1], a, *operands[2:], block)[:, :seq]
    if d_skip is None:
        return y
    return y + u * d_skip.astype(u.dtype)


def selective_scan(u, delta, a, b, c, d_skip=None, *, block: int = 256):
    """``u``: (batch, seq, d); ``delta``: (batch, seq, d), the step sizes,
    positive; ``a``: (d, n), never positive; ``b``, ``c``: (batch, seq, n);
    ``d_skip``: (d,) or None.  Returns ``y`` (batch, seq, d) in ``u``'s dtype,
    from a zero state; a sequence may be any length (``_whole_blocks``).
    A device's channels that are no whole rows of 128 take the ``jax.numpy``
    form."""
    if u.shape[-1] % (LANES * _tp()):
        return selective_scan_xla(u, delta, a, b, c, d_skip, block=block)
    return _whole_blocks(_sharded, u, delta, a, b, c, d_skip, block)


def selective_scan_xla(u, delta, a, b, c, d_skip=None, *, block: int = 256):
    """``selective_scan`` as ``jax.numpy`` under the same rule: the yardstick
    of the tests and of the on-chip timing."""
    return _whole_blocks(
        functools.partial(_scan, kernels=False), u, delta, a, b, c, d_skip,
        block)

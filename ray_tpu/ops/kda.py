"""Kimi Delta Attention's recurrence (Kimi Linear, arXiv:2510.26692) in
chunks: a gated delta rule whose decay is a vector a position, one factor a
channel of the key.  Per head, with ``a_t = exp(g_t)`` in (0, 1]^dk, a scalar
``b_t`` and a ``dk x dv`` float32 state from zero:

    S_t = (I - b_t k_t k_t^T) Diag(a_t) S_{t-1} + b_t k_t v_t^T      o_t = S_t^T q_t

The sequence is cut into chunks of ``chunk`` positions.  With ``u_t = b_t (v_t
- k_t^T Diag(a_t) S_{t-1})`` the update is ``S_t = Diag(a_t) S_{t-1} + k_t
u_t^T``, so inside a chunk that starts from ``S_0``, with the running
log-decays ``G_r = sum_{i <= r} g_i``,

    (I + L) U = Diag(b) (V - (K * e^G) S_0)       L_rj = b_r sum_d k_rd k_jd e^(G_rd - G_jd),  j < r
    O   = (Q * e^G) S_0 + A U                      A_rj = sum_d q_rd k_jd e^(G_rd - G_jd),      j <= r
    S_C = Diag(e^(G_C)) S_0 + (K * e^(G_C - G))^T U

``I + L`` is unit lower triangular: the solve is a forward substitution.  The
decay sits inside the contraction over ``dk``, so ``L`` and ``A`` are no
masked outer products as ``ops/ssd.py``'s are, and ``e^(G_r) * e^(-G_j)``
overflows within one chunk at a small ``a``.  Every exponent here is a
difference that is never positive.

``kda_scan`` (what the mixer calls): three Mosaic kernels under one
``custom_vjp``.  A pair
of positions goes through a position between them, ``(G_r - G_m) + (G_m -
G_j)`` with both parts <= 0, which leaves a matmul of two scaled operands;
which ``m`` is the pair's level of the chunk's binary tree (see ``_tree``).
The running sums and every level's differences come from one exact matmul,
``(I + L)^-1`` from float32 matmuls (``_unit_lower_inverses``): ten, each
waiting for the one before, 30 of a forward head-chunk's some 50 MXU passes
and of a backward's 86, from ``k``, ``g`` and ``beta`` alone.  So it is made
once, by ``kda_solve``, whose grid has no chunk waiting for another, and
read by ``kda_fwd`` and ``kda_bwd``, the state riding their grids as
``ops/ssd.py``'s does: 16 KB a head at a chunk of 64, a head block's side by
side; 0.13 GB a layer at 32 heads x 256 chunks.  It carries a name
(``KDA_RESIDUALS``), by which a rematerialised block keeps it from its
forward to its backward (``models/gpt2.py::remat_block``): the block's
second forward is ``kda_fwd`` alone, and a stack's layers' inverses are
alive together.  The backward makes the chunk's forward again from it and
from the rule's other float32 residual, which ``kda_fwd`` writes a chunk at
a time and the recomputation makes again: the state before the chunk (64 KB
a head a chunk at 128 x 128; 0.54 GB a layer).  ``U``, ``A`` and ``L``'s
pairs together are 56 KB a head a chunk for a third of the solve's passes,
and the levels' ``E`` the gradients need anyway.

``kda_scan_xla``, a yardstick that no model calls (the tests hold it to the
recurrence, and ``perfbench/tests/kimi_linear_on_chip.py`` times the kernels
against it): the same chunks as ``jax.numpy`` under a
``lax.scan``, each chunk's body under ``jax.checkpoint`` so that reverse mode
keeps the state before a chunk and the operands and nothing else.  A chunk is
cut into sub-blocks of ``_SUB`` positions; a pair in two different sub-blocks
goes through the later block's start, a pair inside one takes its own
difference ``G_r - G_j``, channel by channel, before the ``exp`` and is
summed over the channels without a matmul; the solve is XLA's triangular
solve.

Layout: ``q``, ``k``, ``v`` and ``g`` are ``(batch, seq, heads * 128)`` as the
projections wrote them, a head a block of columns; ``beta`` is ``(batch, seq,
heads)``.  Matmul operands are in the activations' dtype and accumulate in
float32; ``g``, the running sums, every ``exp``, the solve and the state are
float32 whatever the activations are.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.ad_checkpoint import checkpoint_name
from jax.experimental import pallas as pl
from jax.sharding import PartitionSpec as P

from ray_tpu.ops.attention import _NN, _NT, _TN, _interpret
from ray_tpu.parallel.mesh import ambient_mesh

# what a block's ``jax.checkpoint`` keeps of the scan by name
# (``models/gpt2.py::remat_block``): every chunk's ``(I + L)^-1``
KDA_RESIDUALS = ("kda_inverse",)

# positions of a sub-block: pairs inside one take their exponent's difference
# channel by channel; pairs across two go through the MXU
_SUB = 16
_MASKED = -1e30


def _mm(a, b, spec, dtype):
    """An einsum of two float32 arrays with its operands in ``dtype`` and a
    float32 sum, as the MXU takes them."""
    return jnp.einsum(spec, a.astype(dtype), b.astype(dtype),
                      preferred_element_type=jnp.float32)


def _pairs(rows, cols, G, sub: int, dtype):
    """``sum_d rows_rd cols_jd e^(G_rd - G_jd)`` for ``j <= r`` and zero for
    ``j > r``: (..., C, C) from (..., C, d) operands, every exponent a
    difference that is not positive."""
    *lead, C, d = G.shape
    n = C // sub
    blocks = lambda t: t.reshape(*lead, n, sub, d)
    Gb, rb, cb = blocks(G), blocks(rows), blocks(cols)
    # a sub-block's running sum before its first position
    start = jnp.concatenate(
        [jnp.zeros_like(Gb[..., :1, -1:, :]), Gb[..., :-1, -1:, :]], axis=-3)
    # across sub-blocks: a row from its block's start, a column up to the
    # row's block's start (clipped where the column is not before it: masked)
    to_row = rb * jnp.exp(Gb - start)
    to_start = cols[..., None, :, :] * jnp.exp(jnp.minimum(
        start - G[..., None, :, :], 0.0))               # (..., n, C, d)
    across = _mm(to_row, to_start, "...isd,...ijd->...isj", dtype)
    before = (jnp.arange(C)[None, None, :] // sub) < jnp.arange(n)[:, None, None]
    across = jnp.where(before, across, 0.0).reshape(*lead, C, C)
    # inside a sub-block: the difference itself, masked before the exp
    seen = jnp.arange(sub)[:, None] >= jnp.arange(sub)[None, :]
    decay = jnp.exp(jnp.where(
        seen[:, :, None], Gb[..., :, None, :] - Gb[..., None, :, :], _MASKED))
    inside = jnp.sum(rb[..., :, None, :] * cb[..., None, :, :] * decay,
                     axis=-1)                           # (..., n, sub, sub)
    eye = jnp.eye(n, dtype=inside.dtype)
    inside = (inside[..., :, :, None, :] * eye[:, None, :, None]
              ).reshape(*lead, C, C)
    return across + inside


def _chunk(state, q, k, v, g, beta, sub: int, dtype):
    """One chunk of every head from the state before it: ``state`` (B, H, dk,
    dv), ``q``, ``k``, ``v``, ``g`` (B, H, C, d) and ``beta`` (B, H, C), all
    float32 -> (the state after it, the outputs (B, H, C, dv))."""
    C = q.shape[-2]
    G = jnp.cumsum(g, axis=-2)
    end = G[..., -1:, :]
    strict = jnp.arange(C)[:, None] > jnp.arange(C)[None, :]
    L = jnp.where(strict, _pairs(k, k, G, sub, dtype), 0.0) \
        * beta[..., None]
    A = _pairs(q, k, G, sub, dtype)
    from_start = jnp.exp(G)
    rhs = beta[..., None] * (v - _mm(k * from_start, state,
                                     "...cd,...de->...ce", dtype))
    U = jax.scipy.linalg.solve_triangular(
        L + jnp.eye(C, dtype=L.dtype), rhs, lower=True, unit_diagonal=True)
    out = _mm(q * from_start, state, "...cd,...de->...ce", dtype) \
        + _mm(A, U, "...cj,...je->...ce", dtype)
    state = jnp.swapaxes(jnp.exp(end), -1, -2) * state \
        + _mm(k * jnp.exp(end - G), U, "...cd,...ce->...de", dtype)
    return state, out


def _scan_xla(q, k, v, g, beta, chunk: int):
    """(B, S, H * d) operands, S whole chunks -> (B, S, H * dv) float32."""
    batch, seq, heads = beta.shape
    dtype = q.dtype
    sub = _SUB if chunk % _SUB == 0 else chunk

    def chunks(t):      # (B, S, H * d) -> (chunks, B, H, C, d)
        t = t.astype(jnp.float32).reshape(batch, seq // chunk, chunk, heads,
                                          -1)
        return t.transpose(1, 0, 3, 2, 4)

    body = jax.checkpoint(functools.partial(_chunk, sub=sub, dtype=dtype))

    def step(state, at):
        return body(state, *at[:4], at[4][..., 0])

    dk, dv = k.shape[-1] // heads, v.shape[-1] // heads
    _, out = lax.scan(
        step, jnp.zeros((batch, heads, dk, dv), jnp.float32),
        tuple(map(chunks, (q, k, v, g, beta))))
    return out.transpose(1, 0, 3, 2, 4).reshape(batch, seq, heads * dv)


# ------------------------------------------------------------ the kernels
# A pair of positions r > j of a chunk splits at exactly one level of the
# chunk's binary tree: the level whose block of 2s positions holds both, r in
# its second half and j in its first.  Through the first half's last position
# m the exponent is (G_r - G_m) + (G_m - G_j), both parts <= 0, so a level is
# one matmul of the operands scaled by E = exp(-|X|), X = N g, with N's rows
# +1 over (m, r] for a row of a second half and -1 over (r, m] for one of a
# first half, under the level's mask M.  log2(chunk) levels and the diagonal
# (exponent 0) are the whole lower triangle, every exponent a difference
# that is not positive, nothing but whole-tile matmuls and elementwise
# passes.


def _tree(chunk: int):
    """(N, M, sign): the matrices of the running sum (C, C) and of each
    level's differences, in {0, 1, -1}, one under the other ((1 + levels) *
    C, C); per level the pairs' mask M (levels, C, C) and each row's half
    (levels, C, 1), +1 in a second half and -1 in a first."""
    import numpy as np

    r, t = np.arange(chunk)[:, None], np.arange(chunk)[None, :]
    tri = (t <= r).astype(np.float32)
    ns, ms, signs = [tri], [], []
    s = chunk // 2
    while s >= 1:
        mid = r // (2 * s) * (2 * s) + s - 1
        ns.append(tri - (t <= mid))
        ms.append(((r // (2 * s) == t // (2 * s)) & (r % (2 * s) >= s)
                   & (t % (2 * s) < s)).astype(np.float32))
        signs.append(np.where(r % (2 * s) >= s, 1.0, -1.0))
        s //= 2
    return (jnp.asarray(np.concatenate(ns), jnp.bfloat16), np.stack(ms),
            np.stack(signs).astype(np.float32))


_HIGHEST = lax.Precision.HIGHEST


def _dotf(a, b, dims):
    """A float32 matmul, whole: the running sums, the solve."""
    return lax.dot_general(a, b, dims, precision=_HIGHEST,
                           preferred_element_type=jnp.float32)


def _pieces(x):
    """A float32 array as three bfloat16 ones whose sum is it, bit for
    bit."""
    hi = x.astype(jnp.bfloat16)
    rest = x - hi.astype(jnp.float32)
    mid = rest.astype(jnp.bfloat16)
    return hi, mid, (rest - mid.astype(jnp.float32)).astype(jnp.bfloat16)


def _sums(n, x, dims):
    """``n`` (entries 0, 1 and -1, bfloat16) against a float32 ``x``, a
    float32 matmul at a half of its passes: every product is exact and the
    MXU adds in float32, so only ``x`` is cut into pieces, once for all the
    levels' matrices."""
    return sum(lax.dot_general(n, piece, dims,
                               preferred_element_type=jnp.float32)
               for piece in _pieces(x))


def _dotl(a, b, dims, dtype):
    """A matmul with its operands in the activations' dtype."""
    return lax.dot_general(
        a.astype(dtype), b.astype(dtype), dims,
        precision=_HIGHEST if dtype == jnp.float32 else None,
        preferred_element_type=jnp.float32)


def _beside(parts):
    """A head block's arrays, one a head, side by side along the lanes."""
    return parts[0] if len(parts) == 1 else jnp.concatenate(parts, axis=1)


def _blocks(chunk: int, size: int):
    """The mask of the diagonal blocks of ``size`` (a power of two)."""
    shift = size.bit_length() - 1
    row = lax.broadcasted_iota(jnp.int32, (chunk, chunk), 0)
    col = lax.broadcasted_iota(jnp.int32, (chunk, chunk), 1)
    return jnp.right_shift(row, shift) == jnp.right_shift(col, shift)


def _unit_lower_inverses(Ls, chunk: int):
    """``(I + L)^-1`` for each strictly lower triangular ``L`` of a list,
    float32; an ``L`` may hold several (C, C) down the diagonal of one matrix
    (n C, n C): the MXU's pass takes the wider matrix at the price of the
    narrower.  Inside diagonal blocks of ``_SUB`` the nilpotent product ``(I
    - L)(I + L^2)(I + L^4) ...``; then blocks are merged two by two up to
    ``chunk``, ``[[A, 0], [B, D]]^-1 = [[A^-1, 0], [-D^-1 B A^-1, D^-1]]``.
    A step of every system is written before the next step of any."""
    size = min(_SUB, chunk)

    def blocks(size):
        return [_blocks(L.shape[0], size) for L in Ls]

    inside = blocks(size)
    powers = [jnp.where(own, L, 0.0) for own, L in zip(inside, Ls)]
    Xs = [eye.astype(jnp.float32) - Ld for eye, Ld in zip(blocks(1), powers)]
    p = 1
    while 2 * p < size:
        powers = [_dotf(power, power, _NN) for power in powers]
        Xs = [X + _dotf(X, power, _NN) for X, power in zip(Xs, powers)]
        p *= 2
    while size < chunk:
        wider = blocks(2 * size)
        off = [jnp.where(jnp.logical_and(new, jnp.logical_not(own)), L, 0.0)
               for new, own, L in zip(wider, inside, Ls)]
        below = [_dotf(B, X, _NN) for B, X in zip(off, Xs)]
        Xs = [X - _dotf(X, B, _NN) for X, B in zip(Xs, below)]
        inside, size = wider, 2 * size
    return Xs


def _decays(gs, n_ref, s_ref):
    """Heads' chunks of log-decays (C, d) float32 -> (each head's running
    sums ``G``, each level's ``E`` of each head): a head's sums and all its
    levels' differences in one matmul."""
    chunk = n_ref.shape[1]
    sums = [_sums(n_ref[...], g, _NN) for g in gs]
    return [s[:chunk] for s in sums], [
        [jnp.exp(jnp.minimum(
            s_ref[i] * s[(i + 1) * chunk:(i + 2) * chunk], 0.0))
         for s in sums] for i in range(s_ref.shape[0])]


class _Inside:
    """What a head block's chunk is made of before the states come into it,
    as the backward needs it, a list over the heads each: the running sums
    ``G``, ``A`` and ``L``'s pairs and, a level, its ``E`` and scaled
    operands; a step of every head before the next step of any.  ``qs``,
    ``ks``, ``gs``: the heads' (C, d) float32."""

    def __init__(self, qs, ks, gs, n_ref, m_ref, s_ref, dtype):
        chunk = qs[0].shape[0]
        row = lax.broadcasted_iota(jnp.int32, (chunk, chunk), 0)
        col = lax.broadcasted_iota(jnp.int32, (chunk, chunk), 1)
        self.eye = (row == col).astype(jnp.float32)
        self.strict = (row > col).astype(jnp.float32)
        self.Gs, decays = _decays(gs, n_ref, s_ref)
        self.levels = []
        Akks = [jnp.zeros((chunk, chunk), jnp.float32) for _ in qs]
        Aqks = [_dotl(q, k, _NT, dtype) * self.eye for q, k in zip(qs, ks)]
        for i, Es in enumerate(decays):
            M = m_ref[i]
            boths = [jnp.concatenate([q * E, k * E])
                     for q, k, E in zip(qs, ks, Es)]
            pairs = [_dotl(both, both[chunk:], _NT, dtype)  # q.k over k.k
                     for both in boths]
            Aqks = [Aqk + M * p[:chunk] for Aqk, p in zip(Aqks, pairs)]
            Akks = [Akk + M * p[chunk:] for Akk, p in zip(Akks, pairs)]
            self.levels.append((s_ref[i], M, Es, boths))
        self.Akks, self.Aqks = Akks, Aqks
        self.from_start = [jnp.exp(G) for G in self.Gs]
        self.ends = [G[chunk - 1:chunk] for G in self.Gs]   # (1, d)
        self.to_end = [jnp.exp(end - G)
                       for end, G in zip(self.ends, self.Gs)]


# Mosaic issues a kernel's matmuls in the order they are written, and most of
# these wait for the one before (a solve is a chain of ten).  What does not
# wait for each other — the systems of a solve, the heads of a forward or a
# backward — is therefore written a stage of all before the next stage of
# any: at the cell's shape two solves one after the other take 13.76 ms a
# layer and in step 9.91, a forward's four heads 6.36 and 4.30 (``PERF.md``,
# PR 56), a backward's 14.59 and 10.49 (PR 58).  ``tests/test_kda_scan.py``
# holds the order by the bodies' jaxprs.


def _kda_solve_kernel(k_ref, g_ref, b_ref, n_ref, m_ref, s_ref, inverse_ref,
                      *, hb: int, d: int):
    """Some chunks of one head block: the heads' ``(I + L)^-1``, ``L = beta
    * Akk``, side by side, (C, hb * C) a chunk.  Two heads go through the MXU
    as one system: their ``k * E`` one over the other give both heads' pairs
    on the diagonal blocks of one product, which a level's mask twice down
    the diagonal (``m_ref``) keeps and nothing else, and that matrix twice as
    wide is what ``_unit_lower_inverses`` takes at the price of the narrower;
    an odd last head goes alone."""
    dtype = k_ref.dtype
    chunk = n_ref.shape[1]
    heads = [(slice(top, top + chunk), h)
             for top in range(0, k_ref.shape[1], chunk) for h in range(hb)]
    # a system: a chunk's pair of heads, or its odd last head
    systems = [range(first, min(first + 2, top + hb))
               for top in range(0, len(heads), hb)
               for first in range(top, top + hb, 2)]
    _, decays = _decays([g_ref[0, rows, h * d:(h + 1) * d]
                         for rows, h in heads], n_ref, s_ref)
    ks = [k_ref[0, rows, h * d:(h + 1) * d].astype(jnp.float32)
          for rows, h in heads]
    Ls = [jnp.zeros((len(system) * chunk,) * 2, jnp.float32)
          for system in systems]
    for i, Es in enumerate(decays):
        scaled = [jnp.concatenate([ks[j] * Es[j] for j in system])
                  for system in systems]
        Ls = [L + m_ref[i, :len(L), :len(L)] * _dotl(ke, ke, _NT, dtype)
              for L, ke in zip(Ls, scaled)]
    betas = [b_ref[0, 0, rows, h:h + 1] for rows, h in heads]
    Xs = _unit_lower_inverses(
        [jnp.concatenate([betas[j] for j in system]) * L
         for L, system in zip(Ls, systems)], chunk)
    inverses = [X[at:at + chunk, at:at + chunk]
                for X in Xs for at in range(0, len(X), chunk)]
    for top in range(0, len(heads), hb):
        inverse_ref[0, 0, heads[top][0]] = _beside(inverses[top:top + hb])


def _kda_fwd_kernel(q_ref, k_ref, v_ref, g_ref, b_ref, n_ref, m_ref, s_ref,
                    inverse_ref, o_ref, before_ref, state, *, hb: int,
                    d: int):
    """A chunk of one head block.  ``state``: the block's heads' states,
    each (dv, dk) — the key's channels along the lanes, where a decay a
    channel is a row — carried from chunk to chunk.  The heads' ``(I +
    L)^-1`` are read (``kda_solve`` made them) and the states before the
    chunk written, the backward's residual."""
    dtype = q_ref.dtype
    chunk = q_ref.shape[1]

    @pl.when(pl.program_id(2) == 0)
    def _():
        state[...] = jnp.zeros_like(state)

    before_ref[0, 0] = state[...]
    ats = [slice(h * d, (h + 1) * d) for h in range(hb)]
    qs, ks, vs = ([r[0, :, at].astype(jnp.float32) for at in ats]
                  for r in (q_ref, k_ref, v_ref))
    Gs, decays = _decays([g_ref[0, :, at] for at in ats], n_ref, s_ref)
    row = lax.broadcasted_iota(jnp.int32, (chunk, chunk), 0)
    col = lax.broadcasted_iota(jnp.int32, (chunk, chunk), 1)
    eye = (row == col).astype(jnp.float32)
    Aqks = [_dotl(q, k, _NT, dtype) * eye for q, k in zip(qs, ks)]
    for i, Es in enumerate(decays):
        Aqks = [Aqk + m_ref[i] * _dotl(q * E, k * E, _NT, dtype)
                for Aqk, q, k, E in zip(Aqks, qs, ks, Es)]
    from_start = [jnp.exp(G) for G in Gs]
    ends = [G[chunk - 1:chunk] for G in Gs]                 # (1, d)
    Ss = [state[at, :] for at in ats]
    rhs = [b_ref[0, 0, :, h:h + 1] * (v - _dotl(k * e, S, _NT, dtype))
           for h, (v, k, e, S) in enumerate(zip(vs, ks, from_start, Ss))]
    Us = [_dotf(inverse_ref[0, 0, :, h * chunk:(h + 1) * chunk], r, _NN)
          for h, r in enumerate(rhs)]
    o_ref[0] = _beside([
        (_dotl(q * e, S, _NT, dtype) + _dotl(Aqk, U, _NN, dtype)
         ).astype(o_ref.dtype)
        for q, e, S, Aqk, U in zip(qs, from_start, Ss, Aqks, Us)])
    for at, G, end, S, U, k in zip(ats, Gs, ends, Ss, Us, ks):
        state[at, :] = jnp.exp(end) * S \
            + _dotl(U, k * jnp.exp(end - G), _TN, dtype)


def _kda_bwd_kernel(q_ref, k_ref, v_ref, g_ref, b_ref, n_ref, m_ref, s_ref,
                    before_ref, inverse_ref, do_ref, dq_ref, dk_ref, dv_ref,
                    dg_ref, db_ref, dstate, *, hb: int, d: int):
    """A chunk of one head block, the chunks visited last to first (the index
    maps turn the axis): ``dstate`` holds the cotangent of the states after
    the chunk.  The chunk's forward is made again from the state before it
    and from the forward's ``(I + L)^-1``: the levels, which the gradients
    need, are made here; the solve's thirty passes a head are not.  A head's
    chain — the levels, ``U``, ``dU``, ``drhs``, ``dL``, the levels'
    gradients, ``dg``'s sums — waits for nothing of another head's: every
    quantity is a list over the block's heads."""
    dtype = q_ref.dtype
    chunk = q_ref.shape[1]

    @pl.when(pl.program_id(2) == 0)
    def _():
        dstate[...] = jnp.zeros_like(dstate)

    ats = [slice(h * d, (h + 1) * d) for h in range(hb)]
    qs, ks, vs, dos = ([r[0, :, at].astype(jnp.float32) for at in ats]
                       for r in (q_ref, k_ref, v_ref, do_ref))
    c = _Inside(qs, ks, [g_ref[0, :, at] for at in ats], n_ref, m_ref, s_ref,
                dtype)
    betas = [b_ref[0, 0, :, h:h + 1] for h in range(hb)]
    inverses = [inverse_ref[0, 0, :, h * chunk:(h + 1) * chunk]
                for h in range(hb)]
    Ss = [before_ref[0, 0, at, :] for at in ats]
    dSs = [dstate[at, :] for at in ats]
    kgs = [k * e for k, e in zip(ks, c.from_start)]
    qgs = [q * e for q, e in zip(qs, c.from_start)]
    kends = [k * e for k, e in zip(ks, c.to_end)]
    rests = [v - _dotl(kg, S, _NT, dtype) for v, kg, S in zip(vs, kgs, Ss)]
    Us = [_dotf(inverse, beta * rest, _NN)
          for inverse, beta, rest in zip(inverses, betas, rests)]
    # the read-out and the state's update
    dUs = [_dotl(Aqk, do, _TN, dtype) + _dotl(kend, dS, _NT, dtype)
           for Aqk, do, kend, dS in zip(c.Aqks, dos, kends, dSs)]
    dAqks = [(c.strict + c.eye) * _dotl(do, U, _NT, dtype)
             for do, U in zip(dos, Us)]
    dqgs = [_dotl(do, S, _NN, dtype) for do, S in zip(dos, Ss)]
    dkends = [_dotl(U, dS, _NN, dtype) for U, dS in zip(Us, dSs)]
    e_ends = [jnp.exp(end) for end in c.ends]
    dS_before = [e_end * dS + _dotl(do, qg, _TN, dtype)
                 for e_end, dS, do, qg in zip(e_ends, dSs, dos, qgs)]
    d_ends = [jnp.sum(e_end * S * dS, axis=0, keepdims=True)
              + jnp.sum(dkend * kend, axis=0, keepdims=True)
              for e_end, S, dS, dkend, kend
              in zip(e_ends, Ss, dSs, dkends, kends)]
    # the solve
    drhs = [_dotf(inverse, dU, _TN) for inverse, dU in zip(inverses, dUs)]
    dLs = [-c.strict * _dotf(dr, U, _NT) for dr, U in zip(drhs, Us)]
    dbeta = jnp.zeros((chunk, hb), jnp.float32)
    lane = lax.broadcasted_iota(jnp.int32, (chunk, hb), 1)
    for h, (dr, rest, dL, Akk) in enumerate(zip(drhs, rests, dLs, c.Akks)):
        dbeta = jnp.where(
            lane == h, jnp.sum(dr * rest, axis=1, keepdims=True)
            + jnp.sum(dL * Akk, axis=1, keepdims=True), dbeta)
    drests = [beta * dr for beta, dr in zip(betas, drhs)]
    dkgs = [-_dotl(drest, S, _NN, dtype) for drest, S in zip(drests, Ss)]
    dS_before = [dS - _dotl(drest, kg, _TN, dtype)
                 for dS, drest, kg in zip(dS_before, drests, kgs)]
    dAkks = [beta * dL for beta, dL in zip(betas, dLs)]
    # the operands through the running sums from the chunk's start and to its
    # end, and the diagonal
    on_diagonal = [jnp.sum(c.eye * dAqk, axis=1, keepdims=True)
                   for dAqk in dAqks]
    dqs = [dqg * e + on * k
           for dqg, e, on, k in zip(dqgs, c.from_start, on_diagonal, ks)]
    dks = [dkg * e + dkend * to + on * q for dkg, e, dkend, to, on, q
           in zip(dkgs, c.from_start, dkends, c.to_end, on_diagonal, qs)]
    dsums = [[dkg * kg + dqg * qg - dkend * kend] for dkg, kg, dqg, qg, dkend,
             kend in zip(dkgs, kgs, dqgs, qgs, dkends, kends)]
    for sign, M, Es, boths in c.levels:
        # the level's q.k pairs over its k.k pairs, as the forward's
        dpairs = [jnp.concatenate([M * dAqk, M * dAkk])
                  for dAqk, dAkk in zip(dAqks, dAkks)]
        by_rows = [_dotl(dp, both[chunk:], _NN, dtype)
                   for dp, both in zip(dpairs, boths)]
        dKes = [by[chunk:] + _dotl(dp, both, _TN, dtype)
                for by, dp, both in zip(by_rows, dpairs, boths)]
        dQes = [by[:chunk] for by in by_rows]
        dqs = [dq + dQe * E for dq, dQe, E in zip(dqs, dQes, Es)]
        dks = [dk + dKe * E for dk, dKe, E in zip(dks, dKes, Es)]
        for dsum, E, dQe, q, dKe, k in zip(dsums, Es, dQes, qs, dKes, ks):
            dsum.append(sign * E * (dQe * q + dKe * k))
    dgs = [_sums(n_ref[...], jnp.concatenate(dsum), _TN) + d_end
           for dsum, d_end in zip(dsums, d_ends)]
    for at, dS in zip(ats, dS_before):
        dstate[at, :] = dS
    for ref, parts in ((dq_ref, dqs), (dk_ref, dks), (dv_ref, drests),
                       (dg_ref, dgs)):
        ref[0] = _beside([part.astype(ref.dtype) for part in parts])
    db_ref[0, 0] = dbeta


# the most chunks a grid step of the solve takes: more systems in step
# (9.11 ms a layer at one, 8.77 at two, 8.65 at four: ``PERF.md``, PR 56)
_SOLVE_SPAN = 2

# the most heads a grid step takes: its share of a step's fixed cost against
# the size of the unrolled body
_HEAD_BLOCK = 4


class _Shape:
    """The sizes of one device's call and the blocks of its grid: (batch,
    head block, chunk), the chunks in sequence — or, where no chunk waits for
    another (``order="parallel"``), ``span`` of them a grid step."""

    def __init__(self, q, beta, chunk: int):
        self.batch, self.seq, width = q.shape
        self.heads = beta.shape[-1]
        self.d, self.chunk = width // self.heads, chunk
        self.hb = max(n for n in range(1, _HEAD_BLOCK + 1)
                      if self.heads % n == 0)
        self.blocks, self.chunks = self.heads // self.hb, self.seq // chunk
        self.tree = tuple(map(jnp.asarray, _tree(chunk)))

    def specs(self, turned: bool, span: int = 1):
        last = self.chunks - 1

        def at(ic):
            return last - ic if turned else ic

        wide = pl.BlockSpec((1, span * self.chunk, self.hb * self.d),
                            lambda ib, ih, ic: (ib, at(ic), ih))
        column = pl.BlockSpec((1, 1, span * self.chunk, self.hb),
                              lambda ib, ih, ic: (ib, ih, at(ic), 0))
        before = pl.BlockSpec((1, 1, self.hb * self.d, self.d),
                              lambda ib, ih, ic: (ib, at(ic), ih, 0))
        inverse = pl.BlockSpec(
            (1, 1, span * self.chunk, self.hb * self.chunk),
            lambda ib, ih, ic: (ib, ih, at(ic), 0))
        whole = [pl.BlockSpec(t.shape, lambda ib, ih, ic, n=t.ndim: (0,) * n)
                 for t in self.tree]
        return wide, column, before, inverse, whole

    def columns(self, t):
        """(batch, seq, heads) as (batch, blocks, seq, hb)."""
        return t.reshape(self.batch, self.seq, self.blocks, self.hb
                         ).transpose(0, 2, 1, 3)

    def rows(self, t):
        return t.transpose(0, 2, 1, 3).reshape(self.batch, self.seq,
                                               self.heads)

    def call(self, kernel, name, order="arbitrary", span=1, **kwargs):
        from jax.experimental.pallas import tpu as pltpu

        return pl.pallas_call(
            functools.partial(kernel, hb=self.hb, d=self.d),
            grid=(self.batch, self.blocks, self.chunks // span),
            compiler_params=pltpu.CompilerParams(
                dimension_semantics=("parallel", "parallel", order),
                vmem_limit_bytes=64 << 20),
            interpret=_interpret(), name=name, **kwargs)


@functools.partial(jax.jit, static_argnums=(3,), inline=True)
def _solve(k, g, beta, chunk: int):
    """Every chunk's ``(I + L)^-1``, a head block's side by side: (batch,
    head blocks, seq, hb * chunk) float32.  No ``q``, no ``v`` and no state:
    no chunk waits for another."""
    s = _Shape(k, beta, chunk)
    span = max(n for n in range(1, _SOLVE_SPAN + 1) if s.chunks % n == 0)
    wide, column, _, inverse, whole = s.specs(False, span)
    n, m, sign = s.tree
    twice = jnp.kron(jnp.eye(2, dtype=m.dtype), m)  # a pair of heads' masks
    whole[1] = pl.BlockSpec(twice.shape, lambda ib, ih, ic: (0, 0, 0))
    return s.call(
        _kda_solve_kernel, "kda_solve", order="parallel", span=span,
        in_specs=[wide] * 2 + [column] + whole, out_specs=inverse,
        out_shape=jax.ShapeDtypeStruct(
            (s.batch, s.blocks, s.seq, s.hb * chunk), jnp.float32),
    )(k, g, s.columns(beta), n, twice, sign)


@functools.partial(jax.jit, static_argnums=(6,), inline=True)
def _forward(q, k, v, g, beta, inverse, chunk: int):
    """``o`` and the states before every chunk, (batch, chunks, heads * dv,
    dk) float32, from ``_solve``'s ``inverse``.  Jitted and inlined as
    ``ops/ssd.py``'s."""
    from jax.experimental.pallas import tpu as pltpu

    s = _Shape(q, beta, chunk)
    wide, column, before, solved, whole = s.specs(False)
    return s.call(
        _kda_fwd_kernel, "kda_fwd",
        in_specs=[wide] * 4 + [column] + whole + [solved],
        out_specs=[wide, before],
        out_shape=[jax.ShapeDtypeStruct(v.shape, v.dtype),
                   jax.ShapeDtypeStruct(
                       (s.batch, s.chunks, s.heads * s.d, s.d), jnp.float32)],
        scratch_shapes=[pltpu.VMEM((s.hb * s.d, s.d), jnp.float32)],
    )(q, k, v, g, s.columns(beta), *s.tree, inverse)


@functools.partial(jax.jit, static_argnums=(8,), inline=True)
def _backward(q, k, v, g, beta, before, inverse, do, chunk: int):
    from jax.experimental.pallas import tpu as pltpu

    s = _Shape(q, beta, chunk)
    wide, column, state, solved, whole = s.specs(True)
    dq, dk, dv, dg, db = s.call(
        _kda_bwd_kernel, "kda_bwd",
        in_specs=[wide] * 4 + [column] + whole + [state, solved, wide],
        out_specs=[wide] * 4 + [column],
        out_shape=[jax.ShapeDtypeStruct(t.shape, t.dtype)
                   for t in (q, k, v, g)]
        + [jax.ShapeDtypeStruct((s.batch, s.blocks, s.seq, s.hb),
                                jnp.float32)],
        scratch_shapes=[pltpu.VMEM((s.hb * s.d, s.d), jnp.float32)],
    )(q, k, v, g, s.columns(beta), *s.tree, before, inverse, do)
    return dq, dk, dv, dg, s.rows(db)


@functools.partial(jax.custom_vjp, nondiff_argnums=(5,))
def _scan_kernels(q, k, v, g, beta, chunk):
    """The kernels under their one differentiation rule: (batch, seq, heads *
    d) operands, seq whole chunks; ``g`` and ``beta`` float32."""
    return _scan_kernels_fwd(q, k, v, g, beta, chunk)[0]


def _scan_kernels_fwd(q, k, v, g, beta, chunk):
    inverse = checkpoint_name(_solve(k, g, beta, chunk), *KDA_RESIDUALS)
    o, before = _forward(q, k, v, g, beta, inverse, chunk)
    return o, (q, k, v, g, beta, before, inverse)


def _scan_kernels_bwd(chunk, residuals, do):
    return _backward(*residuals, do, chunk)


_scan_kernels.defvjp(_scan_kernels_fwd, _scan_kernels_bwd)


def _scan_pallas(q, k, v, g, beta, chunk: int):
    """Under an ambient mesh of more than one device the calls run inside a
    ``shard_map`` — batch over dp/fsdp, heads over tp — since GSPMD cannot
    partition a Mosaic call."""
    if chunk & (chunk - 1):
        raise ValueError(f"the kernels' chunk is a power of two, not {chunk}")
    mesh = ambient_mesh()
    if mesh is None or mesh.size == 1:
        return _scan_kernels(q, k, v, g, beta, chunk)
    tp = mesh.shape.get("tp", 1)
    if beta.shape[-1] % tp:
        raise ValueError(f"{beta.shape[-1]} heads over tp={tp}")
    rows = tuple(a for a in ("dp", "fsdp") if a in mesh.shape) or None
    by_head = P(rows, None, "tp" if tp > 1 else None)
    return jax.shard_map(
        functools.partial(_scan_kernels, chunk=chunk), mesh=mesh,
        in_specs=(by_head,) * 5, out_specs=by_head, check_vma=False,
    )(q, k, v, g, beta)


def _whole_chunks(scan, q, k, v, g, beta, chunk: int):
    """``scan`` on the operands padded at the sequence's end to whole chunks
    with ``g = 0`` and ``beta = 0`` — a step that neither decays the state
    nor writes to it —, the padding's outputs dropped; ``g`` and ``beta``
    float32, the result in ``v``'s dtype."""
    seq = q.shape[1]
    pad = -seq % chunk
    g, beta = g.astype(jnp.float32), beta.astype(jnp.float32)
    if pad:
        q, k, v, g, beta = (jnp.pad(t, [(0, 0), (0, pad), (0, 0)])
                            for t in (q, k, v, g, beta))
    return scan(q, k, v, g, beta, chunk)[:, :seq].astype(v.dtype)


def kda_scan(q, k, v, g, beta, *, chunk: int = 64):
    """``q``, ``k``: (batch, seq, heads * dk); ``v``: (batch, seq, heads *
    dv); ``g``: (batch, seq, heads * dk), the log-decays, never positive;
    ``beta``: (batch, seq, heads).  Returns ``o`` (batch, seq, heads * dv) in
    ``v``'s dtype, from a zero state; a sequence may be any length (see
    ``_whole_chunks``)."""
    return _whole_chunks(_scan_pallas, q, k, v, g, beta, chunk)


def kda_scan_xla(q, k, v, g, beta, *, chunk: int = 64):
    """``kda_scan`` as ``jax.numpy`` with XLA's own derivative: the yardstick
    of the tests and of the on-chip timing."""
    return _whole_chunks(_scan_xla, q, k, v, g, beta, chunk)

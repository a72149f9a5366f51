"""Gated DeltaNet's recurrence (Yang et al., arXiv:2412.06464, as
Qwen3-Next runs it) in chunks: a gated delta rule whose decay is ONE number a
value head a position, and whose key heads each serve ``r`` value heads
(grouped, as GQA groups queries).  Per value head ``h``, which reads the
query and key of key head ``h // r``, with ``a_t = exp(g_t)`` in (0, 1], a
scalar ``b_t`` and a ``dk x dv`` float32 state from zero:

    S_t = (I - b_t k_t k_t^T) a_t S_{t-1} + b_t k_t v_t^T        o_t = S_t^T q_t

The chunked form is ``ops/kda.py``'s with the decay taken out of the
contraction over ``dk``.  With ``G_r = sum_{i <= r} g_i`` inside a chunk that
starts from ``S_0``, and ``D_rj = e^(G_r - G_j)`` for ``j <= r`` (never a
positive exponent; zero above the diagonal):

    (I + L) U = Diag(b) (V - e^G * (K S_0))       L = Diag(b) (K K^T * D),  j < r
    O   = e^G * (Q S_0) + (Q K^T * D) U
    S_C = e^(G_C) S_0 + K^T (e^(G_C - G) * U)

``K K^T`` and ``Q K^T`` are plain (C, C) products of a KEY head, made once
for its ``r`` value heads; a value head's own are the mask of exponents
``D``, one ``exp`` over (C, C), the solve and the products with its state.
Where Kimi Delta Attention sends every pair of positions through a level of a
binary tree of per-channel differences (``ops/kda.py::_tree``), nothing here
is scaled before a matmul: ``g`` is (batch, seq, value heads) float32, never
broadcast to a head's channels, and ``q`` and ``k`` stay at their key heads
in HBM.

``gdn_scan`` (what ``models/gdn.py`` calls): three Mosaic kernels under one
``custom_vjp`` — ``gdn_solve`` (every chunk's ``(I + L)^-1`` by
``ops/kda.py::_unit_lower_inverses``, two value heads a system, no chunk
waiting for another; its result carries ``KDA_RESIDUALS``' name, so a
rematerialised block keeps it and its second forward solves nothing),
``gdn_fwd`` and ``gdn_bwd``, the float32 state riding their grids (batch, key
head block, chunk) as KDA's does, the state before each chunk the rule's
other residual.  The running sums ``G`` are a chunk's own cumulative sum,
made in ``jax.numpy`` outside the rule ((batch, seq, value heads) float32: a
five-hundredth of ``v``), and so is their transpose.

``gdn_scan_xla``, a yardstick that no model calls: the same chunks as
``jax.numpy`` under a ``lax.scan``, each chunk under ``jax.checkpoint``, XLA's
triangular solve and XLA's own derivative; the tests hold it and the kernels
to the recurrence position by position, and
``perfbench/tests/qwen3_next_on_chip.py`` times the kernels against it and
against ``kda_scan`` fed the broadcast ``g`` and the repeated ``q`` and ``k``.

Layout: ``q``, ``k``: (batch, seq, key heads * 128); ``v``: (batch, seq,
value heads * 128), value head ``h`` beside its key head's others; ``g``,
``beta``: (batch, seq, value heads).  Matmul operands are in the activations'
dtype and accumulate in float32; ``g``, the running sums, every ``exp``, the
solve and the state are float32 whatever the activations are.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.ad_checkpoint import checkpoint_name
from jax.experimental import pallas as pl
from jax.sharding import PartitionSpec as P

from ray_tpu.ops.attention import _NN, _NT, _TN
from ray_tpu.ops.kda import (_HEAD_BLOCK, _SOLVE_SPAN, KDA_RESIDUALS, _Shape,
                             _beside, _dotf, _dotl, _mm, _unit_lower_inverses,
                             _whole_chunks)
from ray_tpu.parallel.mesh import ambient_mesh


def _running(g, chunk: int):
    """(B, S, H) log-decays -> each chunk's own running sums, as ``g``."""
    batch, seq, heads = g.shape
    return jnp.cumsum(g.reshape(batch, seq // chunk, chunk, heads),
                      axis=2).reshape(g.shape)


# ------------------------------------------------------------ the yardstick
def _chunk(state, q, k, v, G, beta, dtype):
    """One chunk of every value head from the state before it: ``state`` (B,
    H, dk, dv), ``q``, ``k``, ``v`` (B, H, C, d) — ``q`` and ``k`` a value
    head's view of its key head's —, ``G`` and ``beta`` (B, H, C), all
    float32 -> (the state after it, the outputs (B, H, C, dv))."""
    C = q.shape[-2]
    seen = jnp.arange(C)[:, None] >= jnp.arange(C)[None, :]
    D = jnp.where(seen, jnp.exp(jnp.where(
        seen, G[..., :, None] - G[..., None, :], 0.0)), 0.0)
    strict = jnp.arange(C)[:, None] > jnp.arange(C)[None, :]
    L = jnp.where(strict, _mm(k, k, "...rd,...jd->...rj", dtype) * D, 0.0) \
        * beta[..., None]
    A = _mm(q, k, "...rd,...jd->...rj", dtype) * D
    from_start = jnp.exp(G)[..., None]
    rhs = beta[..., None] * (
        v - from_start * _mm(k, state, "...cd,...de->...ce", dtype))
    U = jax.scipy.linalg.solve_triangular(
        L + jnp.eye(C, dtype=L.dtype), rhs, lower=True, unit_diagonal=True)
    out = from_start * _mm(q, state, "...cd,...de->...ce", dtype) \
        + _mm(A, U, "...cj,...je->...ce", dtype)
    end = G[..., -1:]
    state = jnp.exp(end)[..., None] * state + _mm(
        k, jnp.exp(end - G)[..., None] * U, "...cd,...ce->...de", dtype)
    return state, out


def _scan_xla(q, k, v, g, beta, chunk: int):
    """(B, S, .) operands, S whole chunks -> (B, S, H * dv) float32."""
    batch, seq, heads = beta.shape
    dtype = q.dtype
    d = v.shape[-1] // heads
    r = heads * d // k.shape[-1]

    def chunks(t, n):   # (B, S, n * w) -> (chunks, B, n, C, w)
        t = t.astype(jnp.float32).reshape(batch, seq // chunk, chunk, n, -1)
        return t.transpose(1, 0, 3, 2, 4)

    def of_value_heads(t):      # a key head's, seen by each of its value heads
        return jnp.repeat(chunks(t, heads // r), r, axis=2)

    body = jax.checkpoint(functools.partial(_chunk, dtype=dtype))

    def step(state, at):
        return body(state, *at[:3], at[3][..., 0], at[4][..., 0])

    _, out = lax.scan(
        step, jnp.zeros((batch, heads, d, d), jnp.float32),
        (of_value_heads(q), of_value_heads(k), chunks(v, heads),
         chunks(_running(g, chunk), heads), chunks(beta, heads)))
    return out.transpose(1, 0, 3, 2, 4).reshape(batch, seq, heads * d)


# ------------------------------------------------------------ the kernels
# As in ``ops/kda.py``, Mosaic issues a kernel's matmuls in the order they
# are written, so what does not wait for each other — the value heads of a
# grid step — is written a stage of all before the next stage of any.


def _masks(chunk: int):
    """(eye, lower with the diagonal, strictly lower), (C, C) float32."""
    row = lax.broadcasted_iota(jnp.int32, (chunk, chunk), 0)
    col = lax.broadcasted_iota(jnp.int32, (chunk, chunk), 1)
    return ((row == col).astype(jnp.float32),
            (row >= col).astype(jnp.float32),
            (row > col).astype(jnp.float32))


def _pair_decays(G, eye, lower):
    """A value head's running sums down a column, (C, 1) -> ``D`` (C, C):
    ``e^(G_r - G_j)`` where ``j <= r`` and 0 above the diagonal.  The sums
    along a row are the column through the identity (a multiply and a sum
    down the sublanes: exact)."""
    along = jnp.sum(eye * G, axis=0, keepdims=True)         # (1, C)
    return lower * jnp.exp(jnp.minimum(G - along, 0.0))


def _end(G, d: int):
    """The chunk's last running sum along ``d`` lanes, (1, d): a state's
    rows take it as they take a decay a channel (a (1, 1) value Mosaic does
    not spread both ways)."""
    return jnp.broadcast_to(G, (G.shape[0], d))[G.shape[0] - 1:]


def _down(row, eye):
    """(1, C) along a row -> (C, 1) down a column, exact."""
    return jnp.sum(eye * row, axis=1, keepdims=True)


def _gdn_solve_kernel(k_ref, g_ref, b_ref, inverse_ref, *, hb: int, d: int,
                      r: int):
    """Some chunks of one head block: the value heads' ``(I + L)^-1`` side by
    side, (C, hb * C) a chunk.  A key head's ``K K^T`` is made once; two
    value heads go through ``_unit_lower_inverses`` as one system twice as
    wide, at the price of the narrower."""
    dtype = k_ref.dtype
    chunk = inverse_ref.shape[-1] // hb
    eye, lower, strict = _masks(chunk)
    tops = range(0, k_ref.shape[1], chunk)
    KKs = [[_dotl(k, k, _NT, dtype)
            for k in (k_ref[0, top:top + chunk, j * d:(j + 1) * d]
                      for j in range(hb // r))] for top in tops]
    Ls = [b_ref[0, 0, top:top + chunk, h:h + 1] * strict * KK[h // r]
          * _pair_decays(g_ref[0, 0, top:top + chunk, h:h + 1], eye, lower)
          for top, KK in zip(tops, KKs) for h in range(hb)]
    zero = jnp.zeros((chunk, chunk), jnp.float32)
    systems = [Ls[at] if at + 1 == len(Ls) else jnp.concatenate(
        [jnp.concatenate([Ls[at], zero], axis=1),
         jnp.concatenate([zero, Ls[at + 1]], axis=1)])
        for at in range(0, len(Ls), 2)]
    Xs = _unit_lower_inverses(systems, chunk)
    inverses = [X[at:at + chunk, at:at + chunk]
                for X in Xs for at in range(0, len(X), chunk)]
    for n, top in enumerate(tops):
        inverse_ref[0, 0, top:top + chunk] = _beside(
            inverses[n * hb:(n + 1) * hb])


def _gdn_fwd_kernel(q_ref, k_ref, v_ref, g_ref, b_ref, inverse_ref, o_ref,
                    before_ref, state, *, hb: int, d: int, r: int):
    """A chunk of one head block.  ``state``: the block's value heads'
    states, each (dk, dv), one under the other, carried from chunk to chunk.
    The heads' ``(I + L)^-1`` are read (``gdn_solve`` made them) and the
    states before the chunk written, the backward's residual."""
    dtype = q_ref.dtype
    chunk = q_ref.shape[1]

    @pl.when(pl.program_id(2) == 0)
    def _():
        state[...] = jnp.zeros_like(state)

    before_ref[0, 0] = state[...]
    eye, lower, _ = _masks(chunk)
    ats = [slice(h * d, (h + 1) * d) for h in range(hb)]
    qs, ks = ([ref[0, :, at] for at in ats[:hb // r]]
              for ref in (q_ref, k_ref))
    QKs = [_dotl(q, k, _NT, dtype) for q, k in zip(qs, ks)]
    Gs = [g_ref[0, 0, :, h:h + 1] for h in range(hb)]
    As = [QKs[h // r] * _pair_decays(G, eye, lower)
          for h, G in enumerate(Gs)]
    from_start = [jnp.exp(G) for G in Gs]
    to_end = [jnp.exp(G[chunk - 1:chunk] - G) for G in Gs]
    Ss = [state[at, :] for at in ats]
    rhs = [b_ref[0, 0, :, h:h + 1]
           * (v_ref[0, :, at].astype(jnp.float32)
              - e * _dotl(ks[h // r], S, _NN, dtype))
           for h, (at, e, S) in enumerate(zip(ats, from_start, Ss))]
    Us = [_dotf(inverse_ref[0, 0, :, h * chunk:(h + 1) * chunk], x, _NN)
          for h, x in enumerate(rhs)]
    o_ref[0] = _beside([
        (e * _dotl(qs[h // r], S, _NN, dtype) + _dotl(A, U, _NN, dtype)
         ).astype(o_ref.dtype)
        for h, (e, S, A, U) in enumerate(zip(from_start, Ss, As, Us))])
    for h, (at, G, to, S, U) in enumerate(zip(ats, Gs, to_end, Ss, Us)):
        state[at, :] = jnp.exp(_end(G, d)) * S \
            + _dotl(ks[h // r], to * U, _TN, dtype)


def _gdn_bwd_kernel(q_ref, k_ref, v_ref, g_ref, b_ref, before_ref,
                    inverse_ref, do_ref, dq_ref, dk_ref, dv_ref, dg_ref,
                    db_ref, dstate, *, hb: int, d: int, r: int):
    """A chunk of one head block, the chunks visited last to first (the index
    maps turn the axis): ``dstate`` holds the cotangent of the states after
    the chunk.  The chunk's forward is made again from the state before it
    and from the forward's ``(I + L)^-1``.  ``dg_ref`` takes the cotangent of
    the chunk's running sums ``G``; a key head's ``dq`` and ``dk`` are the
    sums over its value heads, their (C, C) parts summed before the one
    matmul each."""
    dtype = q_ref.dtype
    chunk = q_ref.shape[1]

    @pl.when(pl.program_id(2) == 0)
    def _():
        dstate[...] = jnp.zeros_like(dstate)

    eye, lower, strict = _masks(chunk)
    ats = [slice(h * d, (h + 1) * d) for h in range(hb)]
    key = [h // r for h in range(hb)]
    qs, ks = ([ref[0, :, at] for at in ats[:hb // r]]
              for ref in (q_ref, k_ref))
    vs, dos = ([ref[0, :, at].astype(jnp.float32) for at in ats]
               for ref in (v_ref, do_ref))
    KKs = [_dotl(k, k, _NT, dtype) for k in ks]
    QKs = [_dotl(q, k, _NT, dtype) for q, k in zip(qs, ks)]
    Gs = [g_ref[0, 0, :, h:h + 1] for h in range(hb)]
    betas = [b_ref[0, 0, :, h:h + 1] for h in range(hb)]
    inverses = [inverse_ref[0, 0, :, h * chunk:(h + 1) * chunk]
                for h in range(hb)]
    Ds = [_pair_decays(G, eye, lower) for G in Gs]
    from_start = [jnp.exp(G) for G in Gs]
    to_end = [jnp.exp(G[chunk - 1:chunk] - G) for G in Gs]
    e_ends = [jnp.exp(_end(G, d)) for G in Gs]               # (1, d)
    Ss = [before_ref[0, 0, at, :] for at in ats]
    dSs = [dstate[at, :] for at in ats]
    # the forward again
    KSs = [_dotl(ks[j], S, _NN, dtype) for j, S in zip(key, Ss)]
    QSs = [_dotl(qs[j], S, _NN, dtype) for j, S in zip(key, Ss)]
    rests = [v - e * KS for v, e, KS in zip(vs, from_start, KSs)]
    Us = [_dotf(inverse, beta * rest, _NN)
          for inverse, beta, rest in zip(inverses, betas, rests)]
    As = [QKs[j] * D for j, D in zip(key, Ds)]
    # the read-out and the state's update
    kdSs = [_dotl(ks[j], dS, _NN, dtype) for j, dS in zip(key, dSs)]
    dUs = [_dotl(A, do, _TN, dtype) + to * kdS
           for A, do, to, kdS in zip(As, dos, to_end, kdSs)]
    dQSs = [e * do for e, do in zip(from_start, dos)]
    toUs = [to * U for to, U in zip(to_end, Us)]
    # the solve
    drhs = [_dotf(inverse, dU, _TN) for inverse, dU in zip(inverses, dUs)]
    # (through the masks of exponents: the cotangents of K K^T before beta,
    # and of Q K^T)
    dLDs = [-strict * _dotf(dr, U, _NT) * D
            for dr, U, D in zip(drhs, Us, Ds)]
    dQKs = [_dotl(do, U, _NT, dtype) * D for do, U, D in zip(dos, Us, Ds)]
    drests = [beta * dr for beta, dr in zip(betas, drhs)]
    dKSs = [-e * drest for e, drest in zip(from_start, drests)]
    dKKs = [beta * dLD for beta, dLD in zip(betas, dLDs)]
    # the state before the chunk
    for at, j, e_end, dS, dQS, dKS in zip(ats, key, e_ends, dSs, dQSs, dKSs):
        dstate[at, :] = e_end * dS + _dotl(qs[j], dQS, _TN, dtype) \
            + _dotl(ks[j], dKS, _TN, dtype)
    # the running sums: through D, e^G, e^(G_C - G) and e^(G_C)
    Ws = [dQK * QKs[j] + dKK * KKs[j]
          for j, dQK, dKK in zip(key, dQKs, dKKs)]
    d_from = [jnp.sum(do * QS, axis=1, keepdims=True) * e
              - jnp.sum(drest * KS, axis=1, keepdims=True) * e
              for do, QS, drest, KS, e
              in zip(dos, QSs, drests, KSs, from_start)]
    d_to = [jnp.sum(kdS * U, axis=1, keepdims=True) * to
            for kdS, U, to in zip(kdSs, Us, to_end)]
    d_ends = [jnp.sum(d, axis=0, keepdims=True)
              + jnp.sum(jnp.sum(e_end * S * dS, axis=1, keepdims=True),
                        axis=0, keepdims=True)
              for d, e_end, S, dS in zip(d_to, e_ends, Ss, dSs)]
    last = lax.broadcasted_iota(jnp.int32, (chunk, 1), 0) == chunk - 1
    dGs = [jnp.sum(W, axis=1, keepdims=True)
           - _down(jnp.sum(W, axis=0, keepdims=True), eye)
           + d_f - d_t + jnp.where(last, d_end, 0.0)
           for W, d_f, d_t, d_end in zip(Ws, d_from, d_to, d_ends)]
    dbetas = [jnp.sum(dr * rest, axis=1, keepdims=True)
              + jnp.sum(dLD * KKs[j], axis=1, keepdims=True)
              for j, dr, rest, dLD in zip(key, drhs, rests, dLDs)]
    # a key head's q and k: its value heads' parts
    dqs, dks = [], []
    for j in range(hb // r):
        mine = range(j * r, (j + 1) * r)
        dQK = sum(dQKs[h] for h in mine)
        dKK = sum(dKKs[h] for h in mine)
        dqs.append(_dotl(dQK, ks[j], _NN, dtype) + sum(
            _dotl(dQSs[h], Ss[h], _NT, dtype) for h in mine))
        dks.append(_dotl(dQK, qs[j], _TN, dtype)
                   + _dotl(dKK, ks[j], _NN, dtype)
                   + _dotl(dKK, ks[j], _TN, dtype) + sum(
                       _dotl(dKSs[h], Ss[h], _NT, dtype)
                       + _dotl(toUs[h], dSs[h], _NT, dtype) for h in mine))
    for ref, parts in ((dq_ref, dqs), (dk_ref, dks), (dv_ref, drests)):
        ref[0] = _beside([part.astype(ref.dtype) for part in parts])
    lane = lax.broadcasted_iota(jnp.int32, (chunk, hb), 1)
    for ref, columns in ((dg_ref, dGs), (db_ref, dbetas)):
        out = jnp.zeros((chunk, hb), jnp.float32)
        for h, column in enumerate(columns):
            out = jnp.where(lane == h, column, out)
        ref[0, 0] = out


class _Grouped(_Shape):
    """``ops/kda.py::_Shape``'s grid over the VALUE heads, a head block whole
    groups of ``r`` of them, and beside its specs the ``narrow`` one of the
    block's key heads, for ``q`` and ``k``."""

    def __init__(self, k, beta, chunk: int, d: int):
        heads = beta.shape[-1]
        super().__init__(jax.ShapeDtypeStruct((*k.shape[:2], heads * d),
                                              k.dtype), beta, chunk)
        if k.shape[-1] % d or heads % (k.shape[-1] // d):
            raise ValueError(f"{heads} value heads of {d} over keys "
                             f"{k.shape[-1]} wide")
        self.r = heads * d // k.shape[-1]
        groups = self.heads // self.r
        self.hb = self.r * max(
            n for n in range(1, max(_HEAD_BLOCK // self.r, 1) + 1)
            if groups % n == 0)
        self.blocks = self.heads // self.hb

    def narrow(self, turned: bool, span: int = 1):
        last = self.chunks - 1
        return pl.BlockSpec(
            (1, span * self.chunk, self.hb // self.r * self.d),
            lambda ib, ih, ic: (ib, last - ic if turned else ic, ih))

    def call(self, kernel, name, **kwargs):
        return super().call(functools.partial(kernel, r=self.r), name,
                            **kwargs)


@functools.partial(jax.jit, static_argnums=(3, 4), inline=True)
def _solve(k, G, beta, chunk: int, d: int):
    """Every chunk's ``(I + L)^-1``, a head block's side by side: (batch,
    head blocks, seq, hb * chunk) float32.  ``d``: a head's width."""
    s = _Grouped(k, beta, chunk, d)
    span = max(n for n in range(1, _SOLVE_SPAN + 1) if s.chunks % n == 0)
    _, column, _, inverse, _ = s.specs(False, span)
    return s.call(
        _gdn_solve_kernel, "gdn_solve", order="parallel", span=span,
        in_specs=[s.narrow(False, span), column, column], out_specs=inverse,
        out_shape=jax.ShapeDtypeStruct(
            (s.batch, s.blocks, s.seq, s.hb * chunk), jnp.float32),
    )(k, s.columns(G), s.columns(beta))


@functools.partial(jax.jit, static_argnums=(6,), inline=True)
def _forward(q, k, v, G, beta, inverse, chunk: int):
    """``o`` and the states before every chunk, (batch, chunks, value heads *
    dk, dv) float32, from ``_solve``'s ``inverse``."""
    from jax.experimental.pallas import tpu as pltpu

    s = _Grouped(k, beta, chunk, v.shape[-1] // beta.shape[-1])
    wide, column, before, solved, _ = s.specs(False)
    narrow = s.narrow(False)
    return s.call(
        _gdn_fwd_kernel, "gdn_fwd",
        in_specs=[narrow, narrow, wide, column, column, solved],
        out_specs=[wide, before],
        out_shape=[jax.ShapeDtypeStruct(v.shape, v.dtype),
                   jax.ShapeDtypeStruct(
                       (s.batch, s.chunks, s.heads * s.d, s.d), jnp.float32)],
        scratch_shapes=[pltpu.VMEM((s.hb * s.d, s.d), jnp.float32)],
    )(q, k, v, s.columns(G), s.columns(beta), inverse)


@functools.partial(jax.jit, static_argnums=(8,), inline=True)
def _backward(q, k, v, G, beta, before, inverse, do, chunk: int):
    from jax.experimental.pallas import tpu as pltpu

    s = _Grouped(k, beta, chunk, v.shape[-1] // beta.shape[-1])
    wide, column, state, solved, _ = s.specs(True)
    narrow = s.narrow(True)
    dq, dk, dv, dG, db = s.call(
        _gdn_bwd_kernel, "gdn_bwd",
        in_specs=[narrow, narrow, wide, column, column, state, solved, wide],
        out_specs=[narrow, narrow, wide, column, column],
        out_shape=[jax.ShapeDtypeStruct(t.shape, t.dtype)
                   for t in (q, k, v)]
        + [jax.ShapeDtypeStruct((s.batch, s.blocks, s.seq, s.hb),
                                jnp.float32)] * 2,
        scratch_shapes=[pltpu.VMEM((s.hb * s.d, s.d), jnp.float32)],
    )(q, k, v, s.columns(G), s.columns(beta), before, inverse, do)
    return dq, dk, dv, s.rows(dG), s.rows(db)


@functools.partial(jax.custom_vjp, nondiff_argnums=(5,))
def _scan_kernels(q, k, v, G, beta, chunk):
    """The kernels under their one differentiation rule, over the chunks'
    running sums ``G``: ``seq`` whole chunks; ``G`` and ``beta`` float32."""
    return _scan_kernels_fwd(q, k, v, G, beta, chunk)[0]


def _scan_kernels_fwd(q, k, v, G, beta, chunk):
    inverse = checkpoint_name(
        _solve(k, G, beta, chunk, v.shape[-1] // beta.shape[-1]),
        *KDA_RESIDUALS)
    o, before = _forward(q, k, v, G, beta, inverse, chunk)
    return o, (q, k, v, G, beta, before, inverse)


def _scan_kernels_bwd(chunk, residuals, do):
    return _backward(*residuals, do, chunk)


_scan_kernels.defvjp(_scan_kernels_fwd, _scan_kernels_bwd)


def _scan_pallas(q, k, v, g, beta, chunk: int):
    """Under an ambient mesh of more than one device the calls run inside a
    ``shard_map`` — batch over dp/fsdp, key heads (each with its value
    heads) over tp — since GSPMD cannot partition a Mosaic call."""
    if chunk & (chunk - 1):
        raise ValueError(f"the kernels' chunk is a power of two, not {chunk}")

    def scan(q, k, v, g, beta):
        return _scan_kernels(q, k, v, _running(g, chunk), beta, chunk)

    mesh = ambient_mesh()
    if mesh is None or mesh.size == 1:
        return scan(q, k, v, g, beta)
    tp = mesh.shape.get("tp", 1)
    key_heads = k.shape[-1] * beta.shape[-1] // v.shape[-1]
    if key_heads % tp:
        raise ValueError(f"{key_heads} key heads over tp={tp}")
    rows = tuple(a for a in ("dp", "fsdp") if a in mesh.shape) or None
    by_head = P(rows, None, "tp" if tp > 1 else None)
    return jax.shard_map(
        scan, mesh=mesh, in_specs=(by_head,) * 5, out_specs=by_head,
        check_vma=False)(q, k, v, g, beta)


def gdn_scan(q, k, v, g, beta, *, chunk: int = 64):
    """``q``, ``k``: (batch, seq, key heads * 128); ``v``: (batch, seq, value
    heads * 128); ``g``: (batch, seq, value heads), the log-decays, never
    positive; ``beta``: (batch, seq, value heads).  Returns ``o`` as ``v``,
    from a zero state; a sequence may be any length (``ops/kda.py``'s
    ``_whole_chunks``: padded with steps that neither decay the state nor
    write to it)."""
    return _whole_chunks(_scan_pallas, q, k, v, g, beta, chunk)


def gdn_scan_xla(q, k, v, g, beta, *, chunk: int = 64):
    """``gdn_scan`` as ``jax.numpy`` with XLA's own derivative: the yardstick
    of the tests and of the on-chip timing."""
    return _whole_chunks(_scan_xla, q, k, v, g, beta, chunk)

"""Attention kernels: blockwise flash attention (Pallas/TPU) + ring attention.

Greenfield relative to the reference — it has no sequence parallelism anywhere
(SURVEY §5.7; no ring/blockwise attention hits in the reference tree).  Design:

- ``flash_attention``: online-softmax blockwise attention as two Pallas
  (Mosaic) kernels, one forward and one backward: grids over (batch, heads,
  q blocks, k blocks) — the backward's with the q axis innermost — K / V (or
  Q / dO) streamed from HBM tile by tile through their BlockSpecs, the
  running (m, l, acc) of the flash recurrence in VMEM scratch.  Operands go
  to the MXU in the inputs' dtype, accumulation is float32, no tile above the
  causal diagonal — or, under ``window``, below the band — is fetched or
  computed.  The backward recomputes P and dS
  once a tile from the saved logsumexp and takes dV, dK and dQ from them, so
  memory stays O(S·d) rather than O(S²); the one thing in VMEM that scales
  with the sequence is dQ's float32 accumulator (S·d), and nothing but the
  output, one float32 per query and the gradients is written to HBM.
- ``ring_attention``: shard_map over the ``sp`` mesh axis; each step computes
  blockwise attention of the local Q shard against the resident KV shard, then
  rotates KV around the ring with ``jax.lax.ppermute`` (ICI neighbor traffic),
  merging partial results with the online-softmax combine.  Causal masking uses
  global offsets so the math matches unsharded attention exactly.
- An operand has one of two ranks, and the rank says where its heads are.
  Rank 4 is ``(B, H, S, D)``.  Rank 3 is ``(B, S, H * D)``, the heads side by
  side in the columns as the projection that made it wrote them (or, a
  ``HeadColumns``, some columns of a wider array: GPT-2's q, k, v in the one
  output of ``qkv_proj``): the kernels' BlockSpecs address a head as a
  128-lane column block of it — two heads to a block at width 64, the pair
  run in one grid step — and write the output, and the backward the
  gradients, the same way, so nothing is transposed or copied between a
  matmul and a kernel (``_Tiles``).  The logsumexp and ``delta`` stay a row
  a head.  A rank-3 operand whose head width is no lane tile or half of one
  is turned head-major at the entry, as the models used to do for all.
- A key-side operand — k, v — keeps the heads its projection gave it: under
  grouped-query attention ``n_kv`` where q has ``heads``, ``rep = heads /
  n_kv`` query heads to a key/value head, of either rank, read from the
  operands' shapes and from nothing else.  The forward's grid walks the
  query heads and addresses a key-side block at head ``h // rep``; the
  backward's walks the key/value heads, a tile's dK and dV stay in their
  float32 accumulators across the whole group and are written once, at
  ``n_kv`` heads, and dQ's accumulator is the group's (where that does not
  fit VMEM: a gradient a query head, summed beside the kernel).  No ``(B,
  heads, S, D)`` copy of K, V, dK or dV exists.  Where the kernels have no
  grouped form — the pair form of 64-wide heads, ``k_shared``, the EVA mask,
  a ``tp`` share that is no whole key/value heads — ``flash_attention``
  copies k and v to the query heads under the scope ``kv_repeat``, as it does
  for "reference" and "ring", and says so in the log (``_Tiles.rep``).
- The values may be another width than the scores, and the last dimensions
  of every head's key may be one vector a position that all heads share
  (``flash_attention``'s ``k_shared``: latent attention's rotary key part):
  q, dQ, K and dK are as wide as the scores, the forward's accumulator and
  output and the backward's dV as wide as the values, the shared part is read
  through a BlockSpec that drops the head, and nothing is padded, broadcast
  or joined in HBM.  With one width and no shared part the traced calls are
  what they were.
- Under the EVA mask (``flash_attention``'s ``eva_window``; ``eva_mask``) a
  query sees the keys of its own aligned window up to itself and, of a second
  key / value operand that holds one summary a chunk of positions, those of
  the windows before its own.  Both kernels meet the two operands in the same
  online softmax: a grid's inner axis runs over the window's own causal
  tiles and then over the tiles of summaries that the queries see, and the
  backward hands the summaries their gradients as it does dK and dV
  (``_Tiles.eva``).  A row of at most one window is the causal call.
- A sigmoid gate a head a query on the output (``attention``'s ``gate``, the
  logits as (B, S, H): Laguna's) is taken inside the kernels' own passes.
  The forward kernel gets the gates as one more row a head, laid out as the
  logsumexp's, and its last step writes ``acc * (g / l)`` where it wrote
  ``acc / l``, in float32 before the output's one rounding, so the output,
  and the kept residual, is the gated one.  The backward kernel is the ungated one, handed the gated output's
  cotangent dY for dO and two rows of statistics that carry the gates: with
  ``lse - log g`` for the logsumexp it makes ``g P`` where it made P, which
  is what dV takes (``(g P)^T dY``), and with ``delta / g`` for ``delta`` its
  ``g P (dY V^T - delta / g)`` is the dS of the ungated output's cotangent
  ``g dY``; the logits' gradient is ``delta * (1 - g)``, ``delta`` being the
  row sums of ``dY * out`` the backward makes anyway.  No pass over ``(B, S,
  H * D)`` is made for the gate in either direction, and no tile of the
  backward does more work.  Without a gate the kernels are traced as they
  were (``_Tiles.gated``).
- Under an ambient mesh (``jax.set_mesh``) ``flash_attention`` runs the kernel
  inside a ``shard_map`` (batch over dp/fsdp, heads over tp — a rank-3
  operand's columns by whole heads): Mosaic kernels cannot be partitioned by
  GSPMD, so each device must see a whole local call.
- The kernel compiles for the TPU unless the process ASKED for the Pallas
  interpreter (``RAY_TPU_PALLAS_INTERPRET=1``, set by the CPU test substrate in
  ``_private/platform.py``); it never falls into interpret mode on its own.
  ``mha_reference`` is the ground truth.

The kernels share one tile edge, picked from the sequence length, the head
width and the dtype by what the chip's clock chose (``_block``: 1024 where it
fits); the public op's ``block_q`` / ``block_k`` override it for the forward
(tests force many tiles at small sizes that way).  head_dim should be a
multiple of 128 for peak MXU utilization but any size compiles.
"""

from __future__ import annotations

import contextlib
import functools
import logging
import os
from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.ad_checkpoint import checkpoint_name
from jax.experimental import pallas as pl
from jax.sharding import PartitionSpec as P

from ray_tpu.parallel.mesh import ambient_mesh

logger = logging.getLogger(__name__)

NEG_INF = -1e30
# Mosaic tiles the last two dims of every block as (8, 128): every tile edge
# along a sequence is a multiple of LANES, and a per-query statistic lives in
# VMEM as a (rows, LANES) column with every lane the same.
LANES = 128
# The names the forward rules give their output and logsumexp: the two
# residuals that only the forward kernel can make again.  A block built under
# ``jax.checkpoint(policy=save_only_these_names(*FLASH_RESIDUALS))`` keeps
# them, and its recomputation for the backward runs no forward kernel;
# outside a checkpoint a name lowers to nothing.
FLASH_RESIDUALS = ("flash_out", "flash_lse")


def _interpret() -> bool:
    """Whether this process asked for the Pallas interpreter (read at trace
    time).  Without the request the kernel lowers through Mosaic, and on a
    backend that cannot run it Pallas raises — a CPU run never passes for a
    kernel run by accident."""
    return os.environ.get("RAY_TPU_PALLAS_INTERPRET") == "1"


# =========================================================== XLA reference
def block_diffusion_mask(length: int, block: int):
    """The (2L, 2L) boolean mask of block-diffusion training, written out:
    positions ``0..L-1`` are the noised copy of a row and ``L..2L-1`` its
    clean copy, both cut into blocks of ``block``.  Noised block ``b`` sees
    itself, in both directions, and the clean blocks before ``b``; clean block
    ``b`` sees the clean blocks up to and including ``b``; nothing sees
    another noised block.  ``L**2 + L * block`` of the ``4 L**2`` pairs."""
    pos = jnp.arange(2 * length)
    clean, blk = pos >= length, (pos % length) // block
    q_clean, k_clean = clean[:, None], clean[None, :]
    q_blk, k_blk = blk[:, None], blk[None, :]
    return jnp.where(
        k_clean, jnp.where(q_clean, k_blk <= q_blk, k_blk < q_blk),
        ~q_clean & (k_blk == q_blk))


def eva_mask(length: int, window: int, chunk: int, n_pooled: int):
    """The (S, n_pooled + S) boolean mask of EVA attention over
    ``[summaries ; positions]``, written out: query ``i`` of window
    ``w = i // window`` sees the positions of its own window up to itself and
    the summary of every ``chunk`` positions of the windows before,
    ``j < w * window / chunk``."""
    i = jnp.arange(length)[:, None]
    first = i // window * window
    j, m = jnp.arange(n_pooled)[None, :], jnp.arange(length)[None, :]
    return jnp.concatenate([j * chunk < first, (m >= first) & (m <= i)],
                           axis=1)


def mha_reference(q, k, v, *, causal: bool = True, sm_scale: Optional[float] = None,
                  q_offset: int = 0, k_offset: int = 0, mask=None,
                  window: int = 0, k_shared=None, eva=(), pooled=None):
    """Naive attention; ground truth for kernels. q,k,v: (B, H, S, D); v may
    be another width than q and k, and is the output's.
    ``mask``: a boolean (S_q, S_k) array of the pairs that are seen, in place
    of the causal one.  ``window`` > 0: under the causal mask a query sees
    itself and the ``window - 1`` positions before it.  ``k_shared`` (B, 1,
    S, D_s): the last D_s dimensions of every head's key, held once a
    position; k is then D - D_s wide.  ``eva`` (window, chunk) with
    ``pooled`` (keys, values), each (B, H, n, D), one summary a chunk:
    ``eva_mask`` over ``[summaries ; positions]``, in one softmax."""
    if sm_scale is None:
        sm_scale = q.shape[-1] ** -0.5
    if eva:
        if mask is not None or window or k_shared is not None or not causal:
            raise ValueError("the EVA mask is its own")
        mask = eva_mask(q.shape[2], *eva, pooled[0].shape[2])
        k, v = (jnp.concatenate([p.astype(x.dtype), x], axis=2)
                for p, x in zip(pooled, (k, v)))
    if window and (mask is not None or not causal):
        raise ValueError("a window belongs to the causal mask")
    if k_shared is not None:
        k = jnp.concatenate([k, jnp.broadcast_to(
            k_shared, (*k.shape[:-1], k_shared.shape[-1]))], axis=-1)
    logits = jnp.einsum("bhqd,bhkd->bhqk", q, k).astype(jnp.float32) * sm_scale
    if mask is not None:
        logits = jnp.where(mask, logits, NEG_INF)
    elif causal:
        qi = jnp.arange(q.shape[2])[:, None] + q_offset
        ki = jnp.arange(k.shape[2])[None, :] + k_offset
        seen = qi >= ki
        if window:
            seen &= qi - ki < window
        logits = jnp.where(seen, logits, NEG_INF)
    w = jax.nn.softmax(logits, axis=-1)
    return jnp.einsum("bhqk,bhkd->bhqd", w.astype(v.dtype), v)


# ========================================================== pallas kernels
# Flash attention as two Mosaic kernels over (block_q, block_k) tiles of the
# score square: the forward, and one backward for dK, dV and dQ.  MXU operands
# stay in the inputs' dtype, every dot accumulates in float32, and sm_scale
# meets S after its dot (and dQ / dK once, as the accumulator is written out).
#
# - ``flash_fwd``: grid (b, h, q blocks, k blocks).  Works on S
#   (block_q, block_k); the running max, the running sum and the output
#   accumulator of the online softmax live in VMEM scratch, the statistics as
#   lane-replicated columns; on the last k block the output is normalised and
#   the logsumexp written as a (1, block_q) row, the layout the backward reads.
# - ``flash_bwd``: grid (b, h, k blocks, q blocks).  Recomputes
#   P = exp(S - lse) on the transposed tile S^T (block_k, block_q), so
#   dV += P^T dO and dK += dS^T Q (dS = P * (dP - delta)) are plain matmuls and
#   lse / delta are (1, block_q) rows that broadcast down sublanes; the same
#   dS^T, contracted over its keys, gives dQ[q tile] += dS K: five matmuls and
#   one mask / exp / dS pass a tile.  dK / dV reduce over the inner axis; dQ
#   reduces over the outer one, into a float32 accumulator that holds one
#   head's whole sequence and is written out once, on that head's last step.
# (``h`` counts grid steps: a head, or a pair of 64-wide heads: ``_Tiles``.)
#
# The forward's last grid axis is its reduction ("arbitrary"): the accumulators
# are reset on its first step and written out on its last, and K / V arrive
# tile by tile through their BlockSpecs, so nothing there scales with the
# sequence.  Under a causal mask a tile wholly above the diagonal does no work,
# and the index maps clamp its block index to the nearest live tile, so nothing
# is fetched for it either (``_Tiles.tile_of``).  Tiles that the diagonal or
# the key padding crosses take a masked body (on the diagonal, where the tiling
# allows, chunk by chunk: ``_on_tiles``), all others the bare one.  The mask is
# a function of the tile's indices alone.  Under a window the tiles below the
# band are skipped as those above the diagonal are, and more: the reduction
# axis of either grid is only as long as the band is wide in tiles, and starts
# at the band's first tile (``_Tiles.walk``).
_NT = (((1,), (1,)), ((), ()))  # a @ b.T
_NN = (((1,), (0,)), ((), ()))  # a @ b
_TN = (((0,), (0,)), ((), ()))  # a.T @ b


def _dot(a, b, dims):
    return lax.dot_general(a, b, dims, preferred_element_type=jnp.float32)


def _round_up(x: int, m: int) -> int:
    return (x + m - 1) // m * m


# Queries of a diagonal tile are taken this many at a time, each chunk against
# only the keys up to its last query: at 128, 10/16 of a 512 tile's square and
# 36/64 of a 1024 one.  By the chip's clock (PERF.md, PRs 24, 26 and 30): 128
# for the backward, 256 for the forward, where a chunk's fixed cost weighs more
# than the keys it spares.
_FWD_DIAG_CHUNK = 2 * LANES
_BWD_DIAG_CHUNK = LANES


def _block(s: int, d: int, dtype, block: Optional[int] = None) -> int:
    """Edge of the kernels' tiles along a sequence of length ``s``: a multiple
    of LANES, so every tile is whole.  The largest the chip had room for ran
    fastest at every shape tried, in both directions (1024 at head widths 64
    and 128 in bf16: PERF.md, PR 24 and PR 26), so take it unless padding ``s``
    to it adds more than an eighth to the rows the lanes force anyway.  An
    explicit ``block`` is taken, rounded up to whole lanes."""
    if block is not None:
        return _round_up(min(block, s), LANES)
    rows = _round_up(s, LANES)
    for block in (1024, 512, 256) if d * jnp.dtype(dtype).itemsize <= 512 \
            else (512, 256):
        if _round_up(s, block) * 8 <= rows * 9:
            return block
    return LANES


class _Cols(NamedTuple):
    """Where a token-major operand ``(B, S, C)`` keeps its heads, as the
    ``Dense`` that wrote it left them: head ``h`` is the ``width`` columns
    from ``first + h * stride``."""
    width: int
    first: int
    stride: int

    def fits(self, group: int) -> bool:
        """Whether a block of ``group`` heads is whole lane tiles that an
        index map can name: Mosaic wants a block's last dimension in 128s (or
        the array's own), and a block index counts in whole blocks."""
        lanes = group * self.width
        return lanes % LANES == 0 and self.first % lanes == 0 \
            and (group * self.stride) % lanes == 0 \
            and (group == 1 or self.stride == self.width)


class _Tiles(NamedTuple):
    """How one call's score square is cut, and how its operands are addressed:
    shared by both kernels.

    The grid is (batch, head group, tiles, tiles), and an operand is one of
    two things, told by its rank (``_Layout``).  Rank 4, ``(B, H, S, D)``: a
    block is a group's heads, each its own ``(tile, D)``.  Rank 3,
    ``(B, S, C)`` with the heads side by side in the columns as a projection
    writes them (``_Cols``): a block is ``(tile, lanes)``, the group's
    columns, and nothing is transposed between the matmul and the kernel.
    That takes whole 128-lane tiles: a head width in 128s is a block a head
    (``group`` 1: the bodies see what a rank-4 operand gives them), and
    heads 64 wide go two to a block (``group`` 2: the bodies run the pair in
    one grid step, each head on its own 64 lanes of the block, and every
    rank-4 operand of the call hands over two heads a step as well).  An odd
    number of heads at width 64, any other width (latent attention's 192-wide
    queries, the tests' 16 and 32) and a 64-wide head under the block mask
    keep the rank-4 path: ``flash_attention`` turns such an operand
    head-major first, as the models used to."""
    causal: bool
    offset: int      # q_offset - k_offset: query r sees key c iff r + offset >= c
    block_q: int
    block_k: int
    nq: int
    nk: int
    k_pad_from: Optional[int]   # first padding key, None if there is none
    tri: int         # chunk of a diagonal tile's queries; 0: whole-tile mask
    # Block diffusion (``bd`` > 0): queries and keys are two halves of
    # ``nq / 2 == nk`` tiles each, the noised copy and then the clean copy of
    # the same ``s_k`` positions, in blocks of ``bd``; ``nk``, ``block_k`` and
    # the walk are over the clean keys.  Query r of half h (0 noised, 1 clean)
    # sees clean key c iff c < (r // bd + h) * bd: the clean blocks before its
    # own, and for a clean query its own too.  It is the causal mask with the
    # query moved to the last position of its block (clean) or of the block
    # before (noised), so tiles are skipped, clamped and chunked as under the
    # causal diagonal.  A noised query also sees the noised keys of its own
    # block: those squares lie on the diagonal of the noised tile at the query
    # tile's own index, and each kernel takes them in one more step there, a
    # lane-wide chunk of positions against itself (``_same_block``).  0: no
    # such mask.
    bd: int = 0
    # A window under the causal mask: query r sees key c iff
    # 0 <= r + offset - c < window, a band ``window`` wide under the diagonal.
    # Tiles below the band do no more work than tiles above the diagonal, and
    # neither kind is a step of the grid: its inner axis has only the
    # ``steps`` tiles a band can meet in one row (column) of tiles, counted
    # from the band's first (``walk``).  The tile edge is no more than the
    # window in whole lanes, so that a 512-wide band is not cut from
    # 1024-wide tiles.  The tile the diagonal crosses and the tiles the band's
    # lower edge crosses are masked (chunk by chunk under ``tri``, each chunk
    # against only the keys its queries can see), everything between is bare.
    # 0: no window.
    window: int = 0
    steps_k: int = 0    # the inner axis with the keys inner (window only)
    steps_q: int = 0    # ... and with the queries inner
    group: int = 1      # heads a grid step
    # EVA (``eva`` > 0, the window): under the causal mask a query sees the
    # keys of its own aligned window of ``eva`` positions alone, and beside
    # them a second key / value operand, one summary a chunk of positions,
    # ``per`` a window, of which it sees those of the windows before its own:
    # summary c iff c < (r // eva) * per.  Tiles are square and lie whole in
    # a window, so the window's own tiles are the causal ones (the diagonal
    # tile chunked under ``tri``, the tiles before it in the window bare) and
    # a tile of ``block_s`` summaries is bare, or cut by the key limit where
    # a window's summaries end inside it.  With the keys inner a query tile
    # walks its window's ``eva / block`` tiles and then the ``ns`` tiles of
    # summaries, of which it stops at its last visible one; with the queries
    # inner the outer axis is the ``nk`` tiles of positions and then the
    # ``ns`` of summaries, the inner one a position tile's queries to the end
    # of its window or a summary tile's from the first window after its
    # first chunk's.  0: no such mask.
    eva: int = 0
    per: int = 0
    block_s: int = 0
    ns: int = 0
    # Grouped-query attention (``rep`` > 1): a key/value head serves ``rep``
    # query heads, and K and V come with the heads their projection gave
    # them.  The forward's grid walks the query heads and reads a key-side
    # block at head ``h // rep``.  The backward's walks the key/value heads
    # (``kv_grid``) and has one more axis, the group's query heads, between
    # the outer tiles and the walk: dK and dV of a tile stay in their
    # accumulators across the whole group and are written once, at the
    # key/value heads, and dQ's accumulator and output block are the
    # group's.  Where the group's dQ does not fit VMEM
    # the backward's grid walks the query heads as the forward's does, and
    # writes a dK and dV a query head (``_flash_backward`` sums them).
    rep: int = 1
    kv_grid: bool = False
    # A gate a head a query on the output (``flash_attention``'s ``gate``):
    # one more input of the forward kernel, the (b, h / group, group, s_q)
    # rows of sigmoid(gate) laid out as the logsumexp's are, and its last
    # step writes ``acc * (g / l)`` where it wrote ``acc / l``.  (The
    # backward kernel needs no word of it: ``_flash_backward`` folds the
    # gates into the two rows of statistics it hands over anyway.)
    gated: bool = False

    @classmethod
    def of(cls, s_q, s_k, d, dtype, causal, offset, diag_chunk,
           block_q=None, block_k=None, bd=0, window=0, eva=()):
        """``s_q``: the queries' length; under ``bd`` one half's, which is
        ``s_k``.  ``eva``: (window, chunk), the window in whole lanes.  (One
        head a grid step: a caller with pairs replaces ``group``.)"""
        if eva:
            assert causal and not (bd or window or offset) and s_q == s_k \
                and eva[0] % LANES == 0 and eva[0] % eva[1] == 0, \
                (causal, bd, window, offset, s_q, s_k, eva)
            # the largest tile edge asked for or picked that cuts a window
            # into whole tiles
            edge = _block(s_q, d, dtype, block_q or block_k)
            while eva[0] % edge:
                edge -= LANES
            block_q = block_k = edge
        if window:
            assert causal and not bd and window > 0, (causal, bd, window)
            edge = _round_up(window, LANES)
            block_q = block_q or min(_block(s_q, d, dtype), edge)
            block_k = block_k or min(_block(s_k, d, dtype), edge)
        block_q = _block(s_q, d, dtype, block_q)
        block_k = _block(s_k, d, dtype, block_k)
        s_k_pad = _round_up(s_k, block_k)
        if bd:
            # a block never straddles a tile, a chunk or the keys' end, so no
            # real query sees a padding key; a query tile's own positions are
            # one key tile
            assert not causal and s_q == s_k and LANES % bd == 0 \
                and s_k % bd == 0 and block_q == block_k, \
                (causal, s_q, s_k, bd, block_q, block_k)
            return cls(False, 0, block_q, block_k, 2 * (s_k_pad // block_k),
                       s_k_pad // block_k, None if s_k_pad == s_k else s_k,
                       diag_chunk if block_q % diag_chunk == 0 else LANES, bd)
        # Square tiles that the diagonal meets corner to corner, and no real
        # query that sees a padding key: a tile on the diagonal can go chunk
        # by chunk.
        tri = 0
        if (causal and block_q == block_k and offset % block_q == 0
                and (s_k_pad == s_k or s_q + offset <= s_k)):
            tri = diag_chunk if block_q % diag_chunk == 0 else LANES
        t = cls(causal, offset, block_q, block_k,
                _round_up(s_q, block_q) // block_q, s_k_pad // block_k,
                None if s_k_pad == s_k else s_k, tri)
        if eva:
            per = eva[0] // eva[1]
            block_s = min(_round_up(per, LANES), t.block_k)
            return t._replace(
                eva=eva[0], per=per, block_s=block_s,
                ns=-(-((s_q - 1) // eva[0] * per) // block_s))
        if not window:
            return t
        t = t._replace(window=window)
        # the most tiles the band meets in a row (a column) of tiles
        iq, ik = np.arange(t.nq), np.arange(t.nk)
        return t._replace(
            steps_k=int(np.max(t._last_k(iq, np)
                               - np.minimum(t._first_k(iq, np), t.nk - 1))
                        ) + 1,
            steps_q=int(np.max(t._last_q(ik, np)
                               - np.minimum(t._first_q(ik, np), t.nq - 1))
                        ) + 1)

    # The band's first and last tile along the inner axis, for traced grid
    # indices (``jnp``) and, to count the steps, for all of them (``np``);
    # the last ones clamped into the grid.
    def _first_k(self, iq, xp=jnp):
        return xp.maximum(iq * self.block_q + self.offset - self.window + 1,
                          0) // self.block_k

    def _last_k(self, iq, xp=jnp):
        return xp.minimum(xp.maximum(
            iq * self.block_q + self.block_q - 1 + self.offset, 0)
            // self.block_k, self.nk - 1)

    def _first_q(self, ik, xp=jnp):
        return xp.maximum(ik * self.block_k - self.offset, 0) // self.block_q

    def _last_q(self, ik, xp=jnp):
        return xp.minimum(xp.maximum(
            ik * self.block_k + self.block_k + self.window - 2 - self.offset,
            0) // self.block_q, self.nq - 1)

    @property
    def tps(self) -> int:
        """Under ``eva``: tiles a window."""
        return self.eva // self.block_q

    def eva_walk(self, i, j, q_is_inner: bool):
        """Under ``eva``: grid position -> (iq, ik, js, whether the step is
        a tile of positions and within its walk, whether it is a tile of
        summaries that query tile ``iq`` sees), nothing clamped: the tile of
        positions ``ik`` or of summaries ``js`` that the step stands for."""
        tps, bs = self.tps, self.block_s
        if not q_is_inner:
            window = i // tps
            js = j - tps
            return (i, window * tps + j, js, j < tps, jnp.logical_and(
                js >= 0, js * bs < window * self.per))
        is_pos = i < self.nk
        # a tile of positions: its own query tile and those after it in its
        # window; a tile of summaries: from the first window after the one
        # its first chunk lies in
        last = jnp.minimum((i // tps + 1) * tps, self.nq) - 1
        js = jnp.maximum(i - self.nk, 0)
        iq = jnp.where(is_pos, i + j, (js * bs // self.per + 1) * tps + j)
        return (iq, i, js, jnp.logical_and(is_pos, iq <= last),
                jnp.logical_and(jnp.logical_not(is_pos), iq < self.nq))

    def pooled_of(self, i, j, q_is_inner: bool):
        """Under ``eva``: grid position -> the tile of summaries, clamped to
        one the step's queries see (the first, where they see none)."""
        if q_is_inner:
            return jnp.maximum(i - self.nk, 0)
        seen = -(-(i // self.tps * self.per) // self.block_s)
        return jnp.clip(j - self.tps, 0, jnp.maximum(seen - 1, 0))

    def pooled_spec(self, width: int, cols, q_is_inner: bool):
        """Of a ``(.., summaries, .)`` operand, following ``pooled_of``."""
        return self.blocks(
            (self.block_s,), width, cols,
            lambda i, j: (self.pooled_of(i, j, q_is_inner),))

    def steps(self, q_is_inner: bool) -> int:
        """The length of the grid's inner axis."""
        if self.eva:
            return max(self.tps, self.nq - self.tps) if q_is_inner \
                else self.tps + self.ns
        if self.window:
            return self.steps_q if q_is_inner else self.steps_k
        return self.nq if q_is_inner else self.nk

    def walk(self, i, j, q_is_inner: bool):
        """Grid position -> the tile (iq, ik) the step stands for, not
        clamped: under a window the inner axis counts from the band's first
        tile, so a step past the band's last names a tile that does no work
        (``_on_tiles``) or none at all."""
        if not self.window:
            return (j, i) if q_is_inner else (i, j)
        if q_is_inner:
            return self._first_q(i) + j, i
        return i, self._first_k(i) + j

    def tile_of(self, i, j, q_is_inner: bool):
        """Grid position -> (iq, ik), the inner one clamped to the nearest
        tile that does work."""
        if self.eva:
            tps = self.tps
            if not q_is_inner:
                return i, jnp.minimum(i // tps * tps + jnp.minimum(j, tps - 1),
                                      i)
            iq, ik, *_ = self.eva_walk(i, j, True)
            last = jnp.where(i < self.nk, jnp.minimum(
                (i // tps + 1) * tps, self.nq) - 1, self.nq - 1)
            return jnp.minimum(iq, last), jnp.minimum(ik, self.nk - 1)
        iq, ik = self.walk(i, j, q_is_inner)
        if self.window:
            if q_is_inner:
                return jnp.minimum(iq, self._last_q(ik)), ik
            return iq, jnp.minimum(ik, self._last_k(iq))
        if self.bd:
            half, iq_l = self.half_of(iq)
            if q_is_inner:
                # key tile ik's first live query tile is the one at its own
                # index, in both copies: the clean copy's diagonal, the
                # noised copy's own squares
                return half * self.nk + jnp.maximum(iq_l, ik), ik
            last = lax.div(jnp.maximum(
                iq_l * self.block_q + self.block_q - 1 + (half - 1) * self.bd,
                0), jnp.int32(self.block_k))
            return iq, jnp.minimum(ik, jnp.minimum(last, self.nk - 1))
        if not self.causal:
            return iq, ik
        if q_is_inner:
            first = lax.div(jnp.maximum(ik * self.block_k - self.offset, 0),
                            jnp.int32(self.block_q))
            return jnp.clip(iq, first, self.nq - 1), ik
        last = lax.div(
            jnp.maximum(iq * self.block_q + self.block_q - 1 + self.offset, 0),
            jnp.int32(self.block_k))
        return iq, jnp.minimum(ik, jnp.minimum(last, self.nk - 1))

    def half_of(self, iq):
        """Under ``bd``: which copy query tile ``iq`` belongs to (0 noised, 1
        clean) and its index inside it."""
        half = lax.div(iq, jnp.int32(self.nq // 2))
        return half, iq - half * (self.nq // 2)

    def head_of(self, h, member, keys: bool):
        """Grid position -> the head of an operand on the keys' side or on
        the queries' (``rep``, above; the grid's own where a head has its
        own key/value head)."""
        if self.rep == 1:
            return h
        if self.kv_grid:
            return h if keys else h * self.rep + member
        return lax.div(h, jnp.int32(self.rep)) if keys else h

    def at(self, place):
        """``place(h, i, j, member)`` as an index map of the grid: (batch,
        head, outer tile, inner tile), and under ``kv_grid`` (batch,
        key/value head, outer tile, the group's member, inner tile)."""
        if self.kv_grid:
            return lambda b, h, i, member, j: (b, *place(h, i, j, member))
        return lambda b, h, i, j: (b, *place(h, i, j, None))

    def blocks(self, dims, width: int, cols: Optional[_Cols], index,
               keys: bool = False):
        """BlockSpec of the grid step's heads' block of an operand ``width``
        lanes a head: ``dims`` the block between the heads and the lanes (a
        tile of the sequence, behind the copies' axis where there is one)
        and ``index(i, j)`` where it is.  ``cols`` None: of a
        ``(b, h / group, [group,] ..., width)`` array, rank 4 as the caller
        sees it; else of a ``(b, ..., C)`` one.  ``keys``: the operand's
        heads are the key/value heads (``head_of``)."""
        g = self.group
        if cols is None:
            lead, zero = ((None, None, g), (0,)) if g > 1 \
                else ((None, None), ())
            return pl.BlockSpec(lead + dims + (width,), self.at(
                lambda h, i, j, member: (self.head_of(h, member, keys),)
                + zero + index(i, j) + (0,)))
        lanes = g * width
        first, step = cols.first // lanes, g * cols.stride // lanes
        return pl.BlockSpec((None,) + dims + (lanes,), self.at(
            lambda h, i, j, member: index(i, j) + (
                first + self.head_of(h, member, keys) * step,)))

    def q_spec(self, width: int, cols, q_is_inner: bool):
        """Of a ``(.., s_q, .)`` operand, following ``tile_of``."""
        return self.blocks(
            (self.block_q,), width, cols,
            lambda i, j: (self.tile_of(i, j, q_is_inner)[0],))

    def row_spec(self, q_is_inner: bool):
        """Of the ``(b, h / group, group, s_q)`` rows of a per-query
        statistic: a row a head, whatever the operands' ranks."""
        return pl.BlockSpec(
            (None, None, self.group, self.block_q), self.at(
                lambda h, i, j, member: (self.head_of(h, member, False), 0,
                                         self.tile_of(i, j, q_is_inner)[0])))

    def k_specs(self, width: int, cols, q_is_inner: bool):
        """Of a ``(.., s_k, .)`` operand, following ``tile_of``, as a list.
        Under ``bd`` the operand is ``(.., 2, s_k, .)``, the two copies:
        with the queries inner, both copies' tile ik as one block; with the
        keys inner, the clean tile of the walk, and a second spec, the
        noised tile at the query tile's own index, which stays where it is
        while the clean copy's queries run and so is fetched once a noised
        query tile."""
        def at(i, j):
            return self.tile_of(i, j, q_is_inner)[1]
        if not self.bd:
            return [self.blocks((self.block_k,), width, cols,
                                 lambda i, j: (at(i, j),), True)]
        if q_is_inner:
            return [self.blocks((2, self.block_k), width, cols,
                                 lambda i, j: (0, i), True)]
        return [self.blocks((None, self.block_k), width, cols,
                             lambda i, j: (1, at(i, j)), True),
                self.blocks((None, self.block_k), width, cols,
                             lambda i, j: (0, jnp.minimum(i, self.nk - 1)),
                             True)]

    def shared_spec(self, width: int, q_is_inner: bool):
        """Of a (b, s_k, width) operand that the heads of a batch row share,
        following ``tile_of``."""
        return pl.BlockSpec(
            (None, self.block_k, width), lambda b, h, i, j: (
                b, self.tile_of(i, j, q_is_inner)[1], 0))

    def array(self, b: int, h: int, mid, width: int, tokens: bool):
        """The shape of an operand that ``blocks`` addresses, ``mid`` its
        dimensions between the heads and the lanes."""
        if tokens:
            return (b, *mid, h * width)
        return (b, h // self.group, *self.per_head(*mid, width))

    def per_head(self, *dims):
        """``dims`` for each of a grid step's heads: the shape of a rank-4
        operand's block, and of an accumulator in VMEM."""
        return ((self.group,) if self.group > 1 else ()) + dims


class _Layout(NamedTuple):
    """A call's operands, statically: its heads, and where a rank-3 operand
    keeps them (``_Cols``; None: the operand is rank 4, ``(B, H, S, D)``).
    ``out`` stands for dO as well, and q, k, v for their gradients, which
    the backward writes with the heads side by side from column 0.  ``heads``
    are the queries'; k and v have ``heads / rep`` (grouped-query attention:
    ``_Tiles.rep``), which ``flash_attention`` reads from their shapes."""
    heads: int
    q: Optional[_Cols] = None
    k: Optional[_Cols] = None
    v: Optional[_Cols] = None
    out: Optional[_Cols] = None
    rep: int = 1

    @property
    def group(self) -> int:
        """Heads a grid step: two where a rank-3 operand's heads are half a
        lane tile (``_Tiles``)."""
        return 2 if any(c is not None and c.width % LANES
                        for c in self[1:5]) else 1


def _seen(x, cols: Optional[_Cols]):
    """(length, head width) of an operand of either rank."""
    return (x.shape[2], x.shape[3]) if cols is None \
        else (x.shape[1], cols.width)


def _dense(width: int) -> _Cols:
    """The heads side by side from column 0."""
    return _Cols(width, 0, width)


def _take(x, cols: _Cols, heads: int):
    """(B, S, C) -> (B, S, heads * width): the columns ``cols`` names, as an
    array of their own."""
    if cols == _dense(cols.width) and x.shape[-1] == heads * cols.width:
        return x
    within = cols.first % cols.stride
    x = lax.slice_in_dim(x, cols.first - within,
                         cols.first - within + heads * cols.stride, axis=2)
    x = x.reshape(*x.shape[:2], heads, cols.stride)
    return lax.slice_in_dim(x, within, within + cols.width, axis=3).reshape(
        *x.shape[:2], heads * cols.width)


def _to_heads(x, cols: Optional[_Cols], heads: int):
    """An operand of either rank as (B, H, S, D)."""
    if cols is None:
        return x
    x = _take(x, cols, heads)
    return x.reshape(*x.shape[:2], heads, cols.width).transpose(0, 2, 1, 3)


def _to_tokens(x):
    """(B, H, S, D) -> (B, S, H * D)."""
    b, h, s, d = x.shape
    return x.transpose(0, 2, 1, 3).reshape(b, s, h * d)


def _pad(x, axis: int, to: int, fill=0.0):
    widths = [(0, 0)] * x.ndim
    widths[axis] = (0, to - x.shape[axis])
    return jnp.pad(x, widths, constant_values=fill)


def _rows(x, s_pad: int, tokens: bool = False, group: int = 1):
    """(b, h, s, d) -> (b, h / group, [group,] s_pad, d), or a token-major
    (b, s, c) -> (b, s_pad, c), zero padded up to whole blocks: a padded key
    is masked by the real length, a padded query is dropped (the forward) or
    has p == 0 through its lse (the backward)."""
    if tokens:
        return _pad(x, 1, s_pad)
    x = _pad(x, 2, s_pad)
    return x if group == 1 else x.reshape(
        x.shape[0], x.shape[1] // group, group, *x.shape[2:])


def _stat_rows(x, b: int, h: int, n: int, s_q_pad: int, bd: int, fill):
    """A statistic a query a head, (b*h, 1, s_q), as the kernels' (b, h / n,
    n, s_q_pad) rows, ``n`` heads a grid step, padded with ``fill``."""
    if bd:      # laid out as the queries are
        x = _halves(x.reshape(b, h, x.shape[-1], 1), s_q_pad, fill=fill)
    else:
        x = _pad(x, 2, s_q_pad, fill)
    return x.reshape(b, h // n, n, s_q_pad)


def _halves(x, s_pad: int, tokens: bool = False, fill=0.0):
    """(b, h, 2 l, d) or (b, 2 l, c), two copies of ``l`` positions ->
    the same with ``s_pad`` positions, each copy padded with ``fill`` up to
    ``s_pad / 2``, whole blocks, so that a tile belongs to one copy."""
    return _merged(_copies(x, s_pad // 2, tokens, fill), tokens)


def _copies(x, l_pad: int, tokens: bool = False, fill=0.0):
    """``_halves`` with the copies apart: (b, h, 2, l_pad, d) or
    (b, 2, l_pad, c)."""
    axis = 1 if tokens else 2
    shape = x.shape
    x = x.reshape(*shape[:axis], 2, shape[axis] // 2, *shape[axis + 1:])
    return _pad(x, axis + 1, l_pad, fill)


def _merged(x, tokens: bool, heads: int = 0):
    """A block-addressed array as (b, s, c), or as (b, heads, s, d): the
    group's axis back into the heads, the copies' into the sequence."""
    if tokens:
        return x.reshape(x.shape[0], -1, x.shape[-1])
    return x.reshape(x.shape[0], heads or x.shape[1], -1, x.shape[-1])


def _unhalved(x, l: int, axis: int):
    """The inverse of ``_halves`` along ``axis`` of length 2 l_pad: the two
    copies' first ``l`` positions, side by side."""
    shape = x.shape
    x = x.reshape(*shape[:axis], 2, shape[axis] // 2, *shape[axis + 1:])
    x = lax.slice_in_dim(x, 0, l, axis=axis + 1)
    return x.reshape(*shape[:axis], 2 * l, *shape[axis + 1:])


def _masked(s, q_dim: int, thresh, k_limit, bd: int = 0, below=None):
    """A tile of scaled scores whose dim ``q_dim`` runs over queries r and
    whose other dim runs over keys c, with NEG_INF where r - c < thresh (the
    key is past the query), r - c >= below (the key is under the window) or
    c >= k_limit (the key is padding); None: no such
    mask.  Under ``bd`` r counts in whole blocks: r - r % bd.  (``jax.lax``
    throughout the tile bodies: they are traced once per chunk of every
    diagonal tile, and ``jnp``'s wrappers cost several times the primitive to
    trace.)"""
    if thresh is None and k_limit is None and below is None:
        return s
    c = lax.broadcasted_iota(jnp.int32, s.shape, 1 - q_dim)
    valid = None
    if thresh is not None or below is not None:
        r = lax.broadcasted_iota(jnp.int32, s.shape, q_dim)
        if bd > 1:      # a power of two: it divides LANES
            r = lax.bitwise_and(r, jnp.int32(-bd))
        ahead = lax.sub(r, c)
        if thresh is not None:
            valid = lax.ge(ahead, thresh)
        if below is not None:
            in_w = lax.lt(ahead, below)
            valid = in_w if valid is None else lax.bitwise_and(valid, in_w)
    if k_limit is not None:
        in_k = lax.lt(c, k_limit)
        valid = in_k if valid is None else lax.bitwise_and(valid, in_k)
    return lax.select(valid, s, lax.full_like(s, NEG_INF))


def _same_block(s, bd: int):
    """A square chunk of scaled scores, positions against themselves, with
    NEG_INF outside the ``bd`` x ``bd`` squares on its diagonal (``bd`` a
    power of two: r and c are in one block iff r ^ c < bd)."""
    if bd >= s.shape[0]:
        return s
    r = lax.broadcasted_iota(jnp.int32, s.shape, 0)
    c = lax.broadcasted_iota(jnp.int32, s.shape, 1)
    return lax.select(lax.lt(lax.bitwise_xor(r, c), jnp.int32(bd)), s,
                      lax.full_like(s, NEG_INF))


def _lane_chunks(block: int):
    """A tile's positions a lane-wide chunk at a time, as slices: how a
    noised tile's own squares are taken, 128 / ``bd`` of them a chunk (3% of
    it at ``bd`` 4: what that wastes on the MXU is nothing beside a pass over
    the output in HBM)."""
    return [slice(j, j + LANES) for j in range(0, block, LANES)]


def _band_chunks(t: _Tiles, m: int):
    """Under a window and ``t.tri``: the chunks of the tile ``m`` tiles under
    the diagonal (thresh == -m * block), as ``part``'s arguments: ``t.tri``
    queries at a time against only the lanes of keys that one of them sees,
    with the diagonal's mask on the diagonal tile and the lower edge's where
    it passes through the chunk."""
    b, c = t.block_q, t.tri
    for r0 in range(0, b, c):
        # a query r of the tile sees its keys r + m b - window < c <= r + m b
        lo = max(0, (r0 + m * b - t.window + 1) // LANES * LANES)
        hi = min(b, r0 + c + m * b)
        if lo >= hi:
            continue
        thresh = lo - r0 - m * b        # slice-local: r - c >= thresh
        below = thresh + t.window       # ... and r - c < below
        yield (slice(r0, r0 + c), slice(lo, hi),
               thresh if thresh > lo - hi + 1 else None, None,
               below if below <= c - 1 else None)


def _on_tiles(t: _Tiles, iq, ik, part, also=None):
    """Run ``part(q_slice, k_slice, thresh, k_limit)`` (see ``_masked``) over
    tile (iq, ik) as its kind needs: not at all above the diagonal, bare below
    it, masked where the diagonal or the key padding passes through.  With
    ``t.tri``, a tile on the diagonal is square and aligned to it, and is done
    in chunks of ``t.tri`` queries against only the keys up to each chunk's
    last query.  Under ``t.window`` a tile below the band does nothing either,
    and a tile the band's lower edge crosses is masked as well, with ``part``'s
    fifth argument ``below``.  ``also``: one more condition of a causal
    tile's doing anything."""
    whole = slice(None)

    def bare():
        part(whole, whole, None, None)

    k_limit = None if t.k_pad_from is None else t.k_pad_from - ik * t.block_k
    padded = False if k_limit is None else k_limit < t.block_k
    if not t.causal and not t.bd:
        if padded is False:
            return bare()
        pl.when(padded)(lambda: part(whole, whole, None, k_limit))
        pl.when(jnp.logical_not(padded))(bare)
        return
    if t.bd:
        # a query at the first position of its block r sees c iff
        # r - c >= 1 - half * bd, in the positions of its own copy
        half, iq_l = t.half_of(iq)
        shift = 1 - half * t.bd
        thresh = ik * t.block_k - iq_l * t.block_q + shift
        live = thresh <= t.block_q - t.bd
    else:
        shift = 0
        thresh = ik * t.block_k - iq * t.block_q - t.offset
        live = thresh <= t.block_q - 1
        if also is not None:
            live = jnp.logical_and(live, also)
    crossing = thresh > 1 - t.block_k
    if t.window:
        below = thresh + t.window
        # (a step past the band's last tile may name a tile outside the grid)
        live = functools.reduce(jnp.logical_and, (
            live, below > 1 - t.block_k, iq < t.nq, ik < t.nk))
        is_masked = jnp.logical_or(crossing, below <= t.block_q - 1)
        if t.tri:
            # thresh is a multiple of the tile edge: the diagonal tile and
            # the one or two tiles the lower edge crosses, each its own body
            b = t.block_q
            # (the tiles m below the diagonal with 1 - b < window - m b < b)
            edge = range(max(0, -(-(t.window - b + 1) // b)),
                         (t.window + b - 2) // b + 1)
            for m in sorted({0, *edge}):
                def chunked(m=m):
                    for args in _band_chunks(t, m):
                        part(*args)
                pl.when(jnp.logical_and(live, thresh == -m * b))(chunked)
        else:
            is_masked = jnp.logical_or(is_masked, padded)
            pl.when(jnp.logical_and(live, is_masked))(
                lambda: part(whole, whole, thresh, k_limit, below))
        pl.when(jnp.logical_and(live, jnp.logical_not(is_masked)))(bare)
        return
    if t.tri:
        def masked():
            for j in range(t.block_q // t.tri):
                part(slice(j * t.tri, (j + 1) * t.tri),
                     slice(0, (j + 1) * t.tri), shift - j * t.tri, None)
        is_masked = crossing
    else:
        def masked():
            part(whole, whole, thresh, k_limit)
        is_masked = jnp.logical_or(crossing, padded)
    pl.when(jnp.logical_and(live, is_masked))(masked)
    pl.when(jnp.logical_and(live, jnp.logical_not(is_masked)))(bare)


def _on_summaries(t: _Tiles, iq, js, seen, part):
    """Under ``t.eva``: run ``part`` over the tile ``js`` of summaries for
    query tile ``iq``, where ``seen``: bare, or under the key limit where
    the summaries of the windows before the queries' end inside the tile."""
    whole, ks = slice(None), slice(0, t.block_s)
    limit = iq // t.tps * t.per - js * t.block_s
    pl.when(jnp.logical_and(seen, limit >= t.block_s))(
        lambda: part(whole, ks, None, None, pooled=True))
    if t.per % t.block_s:
        pl.when(functools.reduce(jnp.logical_and, (
            seen, limit > 0, limit < t.block_s)))(
                lambda: part(whole, ks, None, limit, pooled=True))


def _across(col, n: int):
    """A lane-replicated (rows, LANES) column as (rows, n)."""
    if n > LANES:
        col = jnp.tile(col, (1, -(-n // LANES)))
    return col if col.shape[1] == n else col[:, :n]


# -------------------------------------------------------------- forward
class _Head:
    """One head's part of a pair's block, indexed as a (rows, lanes) ref of
    its own by ``[rows, lanes]`` (slices) or ``[...]``: block ``lead`` of a
    (2, rows, width) block, or the ``width`` lanes from lane ``first`` of a
    (rows, 2 * width) one.  Every access is one index into the block itself:
    Mosaic loads and stores at a lane offset, but cuts no ref inside a lane
    tile."""

    def __init__(self, ref, lead: tuple, first: int, width: int):
        self.ref, self.lead, self.first = ref, lead, first
        self.shape, self.dtype = (ref.shape[-2], width), ref.dtype

    def _at(self, index):
        rows, lanes = (slice(None),) * 2 if index is Ellipsis else index
        start, stop, _ = lanes.indices(self.shape[-1])
        return (*self.lead, rows, slice(self.first + start, self.first + stop))

    def __getitem__(self, index):
        return self.ref[self._at(index)]

    def __setitem__(self, index, value):
        self.ref[self._at(index)] = value


def _per_head(ref, group: int, tokens: bool):
    """A grid step's block as one ref a head: the block itself at one head a
    step, else the pair's two, a rank-4 operand's by its leading axis and a
    rank-3 operand's by their 64 lanes of the block."""
    if group == 1:
        return [ref]
    width = ref.shape[-1] // group if tokens else ref.shape[-1]
    return [_Head(ref, (), p * width, width) if tokens
            else _Head(ref, (p,), 0, width) for p in range(group)]


def _scores(q_ref, qs, k_ref, ks, ks_ref):
    """Unscaled scores (queries, keys) of queries ``qs`` against keys ``ks``;
    ``ks_ref``: the keys' shared part (``flash_attention``'s ``k_shared``),
    which the queries' last dimensions meet in a contraction of their own."""
    if ks_ref is None:
        return _dot(q_ref[qs, :], k_ref[ks, :], _NT)
    d = k_ref.shape[-1]
    return lax.add(_dot(q_ref[qs, :d], k_ref[ks, :], _NT),
                   _dot(q_ref[qs, d:], ks_ref[ks, :], _NT))


def _flash_fwd_kernel(q_ref, k_ref, v_ref, *rest, sm_scale: float, t: _Tiles,
                      tokens):
    # under ``t.bd`` two more inputs: the noised K and V tile of the query
    # tile's own positions; under ``t.eva`` two: the summaries' keys and
    # values; without either, at most one: the keys' shared part; under
    # ``t.gated`` one more, last: the heads' rows of gates.
    # ``tokens``: which of q, k, v and the output are rank 3 (``_per_head``)
    *own_refs, o_ref, lse_ref, m_col, l_col, acc = rest
    gate_ref = own_refs.pop() if t.gated else None
    row, step = pl.program_id(2), pl.program_id(3)
    if t.eva:
        iq, ik, js, _, seen = t.eva_walk(row, step, False)
    else:
        iq, ik = t.walk(row, step, False)
    ks_ref = own_refs[0] if own_refs and not (t.bd or t.eva) else None
    heads = list(zip(*(
        _per_head(ref, t.group, rank3) for ref, rank3 in zip(
            (q_ref, k_ref, v_ref, o_ref, m_col, l_col, acc),
            (*tokens, False, False, False)))))
    d = heads[0][2].shape[-1]

    from_nothing = step == 0
    if t.bd:
        # a noised query tile starts from its own squares, where another
        # starts from nothing: every query sees itself, so its running max
        # is finite from there on
        kn_ref, vn_ref = own_refs
        from_own = jnp.logical_and(from_nothing, iq < t.nk)
        from_nothing = jnp.logical_and(from_nothing, iq >= t.nk)

        @pl.when(from_own)
        def _():
            for rows in _lane_chunks(t.block_q):
                s = _same_block(lax.mul(_dot(
                    q_ref[rows, :], kn_ref[rows, :], _NT), sm_scale), t.bd)
                m = jnp.broadcast_to(jnp.max(s, axis=1, keepdims=True),
                                     s.shape)
                m_col[rows, :] = m
                p = lax.exp(lax.sub(s, m))
                l_col[rows, :] = jnp.broadcast_to(
                    jnp.sum(p, axis=1, keepdims=True), s.shape)
                acc[rows, :] = _dot(p.astype(vn_ref.dtype), vn_ref[rows, :],
                                    _NN)

    @pl.when(from_nothing)
    def _():
        m_col[...] = jnp.full_like(m_col, NEG_INF)
        l_col[...] = jnp.zeros_like(l_col)
        acc[...] = jnp.zeros_like(acc)

    def part(qs, ks, thresh, k_limit, below=None, pooled=False):
        for q_ref, k_ref, v_ref, _, m_col, l_col, acc in heads:
            if pooled:      # (one head a step under ``t.eva``)
                k_ref, v_ref = own_refs
            v = v_ref[ks, :]
            s = _masked(lax.mul(_scores(q_ref, qs, k_ref, ks, ks_ref),
                                sm_scale), 0, thresh, k_limit, t.bd, below)
            lanes = (s.shape[0], LANES)
            m_old = m_col[qs, :]
            m_new = lax.max(m_old, jnp.broadcast_to(
                jnp.max(s, axis=1, keepdims=True), lanes))
            m_col[qs, :] = m_new
            alpha = lax.exp(lax.sub(m_old, m_new))
            if not (thresh is None and k_limit is None and below is None):
                # A row with every key masked so far has m == NEG_INF, and
                # exp(s - m) would be 1 per column: take its exp against 0.
                m_new = lax.select(lax.gt(m_new, NEG_INF / 2), m_new,
                                   lax.full_like(m_new, 0.0))
            p = lax.exp(lax.sub(s, _across(m_new, s.shape[1])))
            l_col[qs, :] = lax.add(
                lax.mul(alpha, l_col[qs, :]),
                jnp.broadcast_to(jnp.sum(p, axis=1, keepdims=True), lanes))
            acc[qs, :] = lax.add(lax.mul(_across(alpha, d), acc[qs, :]),
                                 _dot(p.astype(v.dtype), v, _NN))

    _on_tiles(t, iq, ik, part)
    if t.eva:
        _on_summaries(t, iq, js, seen, part)

    @pl.when(step == t.steps(False) - 1)
    def _():
        for p, (_, _, _, o_ref, m_col, l_col, acc) in enumerate(heads):
            l = l_col[...]
            empty = lax.eq(l, 0.0)  # no key seen: output 0, lse NEG_INF
            l = lax.select(empty, lax.full_like(l, 1.0), l)
            if gate_ref is None:
                o_ref[...] = lax.div(acc[...], _across(l, d)
                                     ).astype(o_ref.dtype)
            else:
                # the head's (1, block_q) row of gates as a lane-replicated
                # column (what ``lse.T[:1, :]`` below undoes); they meet the
                # output in float32, before its cast
                g = jnp.broadcast_to(gate_ref[p:p + 1, :],
                                     (LANES, t.block_q)).T
                o_ref[...] = lax.mul(acc[...], _across(lax.div(g, l), d)
                                     ).astype(o_ref.dtype)
            lse = lax.select(empty, lax.full_like(l, NEG_INF),
                             lax.add(m_col[...], lax.log(l)))
            # (block_q, LANES) column, every lane the same -> the head's
            # (1, block_q) row
            lse_ref[p:p + 1, :] = lse.T[:1, :]


@functools.partial(jax.jit,
                   static_argnums=(3, 4, 5, 6, 7, 8, 9, 10, 11, 13, 15),
                   inline=True)
def _flash_forward(q, k, v, causal: bool, sm_scale: float, q_offset: int,
                   k_offset: int, block_q: Optional[int],
                   block_k: Optional[int], interpret: bool, bd: int = 0,
                   window: int = 0, k_shared=None,
                   lay: Optional[_Layout] = None, pooled=None,
                   eva: tuple = (), gate=None):
    """``out`` — (b, h, s_q, v's width), or (b, s_q, h * v's width) under
    ``lay.out`` — and the logsumexp of every query's scaled scores as
    (b*h, 1, s_q) rows, NEG_INF where a query sees no key.  ``lay``: the
    operands' ranks (None: all of rank 4).  Under ``bd`` (see ``_Tiles``) q,
    k and v are the two copies of ``s_q / 2`` positions, and every query sees
    a key.  ``k_shared`` (b, 1, s_k, .): see ``flash_attention``.  ``eva``
    (window, chunk) with ``pooled``, the summaries' keys and values laid out
    as k and v are: see ``_Tiles``.  ``gate`` (b, s_q, h), logits: each
    head's output times the sigmoid of its logit of the query, in the
    kernel's last step (``_Tiles.gated``); ``out`` is then the gated output.

    Jitted and inlined so that a model's layers, which call it with the same
    shapes, share one trace of the kernel: the equations land in the caller's
    jaxpr under the caller's scopes, as if written there."""
    from jax.experimental.pallas import tpu as pltpu

    lay = lay or _Layout(q.shape[1])
    b, h, g = q.shape[0], lay.heads, lay.group
    (s_q, d), (s_k, d_k), (_, d_v) = map(_seen, (q, k, v), lay[1:4])
    s_k = s_k // 2 if bd else s_k       # one copy's
    t = _Tiles.of(s_k if bd else s_q, s_k, d, q.dtype, causal,
                  q_offset - k_offset, _FWD_DIAG_CHUNK, block_q, block_k, bd,
                  window, eva)._replace(group=g, rep=lay.rep,
                                        gated=gate is not None)
    s_q_pad, s_k_pad = t.nq * t.block_q, t.nk * t.block_k
    if t.gated:     # once a traced shape: the mechanism engages
        logger.debug(
            "flash attention: %d heads' outputs gated a query inside the "
            "kernels (%d queries, tiles of %d)", h, s_q, t.block_q)
    tokens = tuple(c is not None for c in lay[1:5])
    k_spec, *kn_spec = t.k_specs(d_k, lay.k, False)
    v_spec, *vn_spec = t.k_specs(d_v, lay.v, False)
    rows = functools.partial(_rows, group=g)
    q_rows, k_rows = (_halves, _copies) if bd else (rows, rows)
    if t.gated:
        # what a gate still is beside the kernels, under the trace's name
        # for it: the sigmoid of a value a head a query, laid out as a row a
        # head, zero where padded
        with jax.named_scope("gate"):
            gate = _stat_rows(
                jax.nn.sigmoid(gate.astype(jnp.float32)).transpose(
                    0, 2, 1).reshape(b * h, 1, s_q), b, h, g, s_q_pad, bd, 0.0)
    with jax.named_scope("flash_fwd"):
        q = q_rows(q, s_q_pad, tokens[0])
        k = k_rows(k, s_k_pad, tokens[1])
        v = k_rows(v, s_k_pad, tokens[2])
        own, own_spec = (k, v) * len(kn_spec), kn_spec + vn_spec
        if k_shared is not None:
            own = (_pad(k_shared[:, 0], 1, s_k_pad),)
            own_spec = [t.shared_spec(k_shared.shape[-1], False)]
        if eva:
            own = tuple(_rows(x, t.ns * t.block_s, rank3)
                        for x, rank3 in zip(pooled, tokens[1:3]))
            own_spec = [t.pooled_spec(d_k, lay.k and _dense(d_k), False),
                        t.pooled_spec(d_v, lay.v and _dense(d_v), False)]
        if t.gated:
            own, own_spec = own + (gate,), own_spec + [t.row_spec(False)]
        def scratch(width):
            return pltpu.VMEM(t.per_head(t.block_q, width), jnp.float32)
        out, lse = pl.pallas_call(
            functools.partial(_flash_fwd_kernel, sm_scale=sm_scale, t=t,
                              tokens=tokens),
            grid=(b, h // g, t.nq, t.steps(False)),
            in_specs=[t.q_spec(d, lay.q, False), k_spec, v_spec] + own_spec,
            out_specs=[t.q_spec(d_v, lay.out, False), t.row_spec(False)],
            out_shape=[
                jax.ShapeDtypeStruct(
                    t.array(b, h, (s_q_pad,), d_v, tokens[3]), q.dtype),
                jax.ShapeDtypeStruct((b, h // g, g, s_q_pad), jnp.float32)],
            scratch_shapes=[scratch(LANES), scratch(LANES), scratch(d_v)],
            compiler_params=pltpu.CompilerParams(
                dimension_semantics=("parallel", "parallel", "parallel",
                                     "arbitrary")),
            interpret=interpret,
            name="flash_fwd",
        )(q, k, v, *own)
    out, lse = _merged(out, tokens[3], h), lse.reshape(b * h, 1, s_q_pad)
    if bd:
        return (_unhalved(out, s_k, 1 if tokens[3] else 2),
                _unhalved(lse, s_k, 2))
    return (lax.slice_in_dim(out, 0, s_q, axis=1 if tokens[3] else 2),
            lse[:, :, :s_q])


# ------------------------------------------------------------- backward
# What Mosaic gives a kernel's blocks, scratch and temporaries unless told
# otherwise; a tile's share of the backward fits in it at every tile edge
# ``_block`` picks, as it did before dQ had to span the sequence.
_MOSAIC_SCOPE_BYTES = 16 << 20


# The v5e's VMEM: what a grid step's heads' share of dQ must fit in.
_VMEM_BYTES = 128 << 20


def _bwd_vmem_bytes(s_q_pad: int, d: int, dtype) -> int:
    """The backward kernel's VMEM limit: the default scope for what belongs to
    a tile, and beside it what spans the sequence — dQ's float32 accumulator
    and its output block, which the pipeline holds twice.  (A block's last dim
    fills whole lanes.)  ``s_q_pad``: the queries of every head whose dQ a
    grid step holds — a pair's, a group's (``_Tiles.rep``).  Past the chip's
    VMEM — 128 MiB on the v5e, about 118,000 queries at d <= 128 in bf16 —
    the compiler refuses the call."""
    return _MOSAIC_SCOPE_BYTES + s_q_pad * _round_up(d, LANES) * (
        4 + 2 * jnp.dtype(dtype).itemsize)


def _flash_bwd_kernel(q_ref, do_ref, lse_ref, delta_ref, k_ref, v_ref, *rest,
                      sm_scale: float, t: _Tiles, tokens):
    # with the keys' shared part (``flash_attention``'s ``k_shared``) one
    # more input, output and accumulator: that part, and its gradient from
    # this head's queries.  ``tokens``: which of q, dO, k and v are rank 3
    # (``_per_head``); a gradient is laid out as what it is the gradient of
    ks_ref, dks_ref, dks_acc = None, None, None
    if t.eva:
        # the summaries' keys and values, and their gradients
        kp_ref, vp_ref, dq_ref, dk_ref, dv_ref, dkp_ref, dvp_ref, dq_acc, \
            dk_acc, dv_acc = rest
    elif len(rest) == 9:
        ks_ref, dq_ref, dk_ref, dv_ref, dks_ref, dq_acc, dk_acc, dv_acc, \
            dks_acc = rest
    else:
        dq_ref, dk_ref, dv_ref, dq_acc, dk_acc, dv_acc = rest
    ik, step = pl.program_id(2), pl.program_id(3)
    # what the first and last steps zero and write, whole; below, the names
    # stand for the part of each that a step's head works on
    dq_out, dq_all, key_side = dq_ref, dq_acc, (
        (dk_ref, dk_acc), (dv_ref, dv_acc))
    if t.kv_grid:
        # one more axis, the group's query heads: this step's head's rows of
        # the group's dQ
        member, step = step, pl.program_id(4)
        # (rows narrower than a lane tile — scores 64 wide on heads that lie
        # 128 apart — are indexed in the accumulator itself: Mosaic cuts no
        # ref inside a lane tile)
        dq_acc = _Head(dq_acc, (member,), 0, dq_acc.shape[-1]) \
            if dq_acc.shape[-1] % LANES else dq_acc.at[member]
    if t.eva:
        iq, _, js, in_window, seen = t.eva_walk(ik, step, True)
    else:
        iq = t.walk(ik, step, True)[0]
    last_k, last_step = pl.num_programs(2) - 1, pl.num_programs(
        4 if t.kv_grid else 3) - 1

    def first():
        """A key tile's first step, of its first head."""
        return jnp.logical_and(member == 0, step == 0) if t.kv_grid \
            else step == 0

    def last():
        return jnp.logical_and(member == t.rep - 1, step == last_step) \
            if t.kv_grid else step == last_step

    if t.bd:
        # both copies' tile ik: the clean one takes the walk and the
        # accumulators, the noised one the own squares of query tile ik,
        # which one head's queries alone reach — or a group's, and then
        # through accumulators of their own
        (kn_ref, k_ref), (vn_ref, v_ref), (dkn_ref, dk_ref), \
            (dvn_ref, dv_ref) = ((r.at[0], r.at[1])
                                 for r in (k_ref, v_ref, dk_ref, dv_ref))
        if t.kv_grid:
            (dkn_acc, dk_acc), (dvn_acc, dv_acc) = (
                (a.at[0], a.at[1]) for a in (dk_acc, dv_acc))
    q_is, do_is, k_is, v_is = tokens
    heads = list(zip(*(
        _per_head(ref, t.group, rank3) for ref, rank3 in (
            (q_ref, q_is), (do_ref, do_is), (k_ref, k_is), (v_ref, v_is),
            (dq_ref, q_is), (dk_ref, k_is), (dv_ref, v_is),
            (dq_acc, False), (dk_acc, False), (dv_acc, False))
        + (((dks_ref, False), (dks_acc, False)) if ks_ref is not None
           else ()))))

    @pl.when(jnp.logical_and(ik == 0, first()))
    def _():
        dq_all[...] = jnp.zeros_like(dq_all)

    @pl.when(first())
    def _():
        for _, acc in key_side:
            acc[...] = jnp.zeros_like(acc)
        if dks_acc is not None:
            dks_acc[...] = jnp.zeros_like(dks_acc)

    def part(qs, ks, thresh, k_limit, below=None, own=False, pooled=False):
        for p, (q_ref, do_ref, k_ref, v_ref, _, _, _, dq_acc, dk_acc, dv_acc,
                *shared) in enumerate(heads):
            kr, vr = (kn_ref, vn_ref) if own else (kp_ref, vp_ref) if pooled \
                else (k_ref, v_ref)
            q, do, k = q_ref[qs, :], do_ref[qs, :], kr[ks, :]
            if ks_ref is None:
                st = _dot(k, q, _NT)
            else:
                # the queries' last dimensions against the shared part
                d = k.shape[1]
                ks_part, q, q_shared = ks_ref[ks, :], q[:, :d], q[:, d:]
                st = lax.add(_dot(k, q, _NT), _dot(ks_part, q_shared, _NT))
            st, lse = lax.mul(st, sm_scale), lse_ref[p:p + 1, qs]
            st = _same_block(st, t.bd) if own \
                else _masked(st, 1, thresh, k_limit, t.bd, below)
            pt = lax.exp(lax.sub(st, lse))      # P^T, zero where masked
            if own and t.kv_grid:
                dvn_acc[ks, :] += _dot(pt.astype(do.dtype), do, _NN)
            elif own:   # nothing else reaches these keys: no sum over steps
                dvn_ref[ks, :] = _dot(pt.astype(do.dtype), do, _NN
                                      ).astype(dvn_ref.dtype)
            else:
                dv_acc[ks, :] += _dot(pt.astype(do.dtype), do, _NN)
            dst = lax.mul(pt, lax.sub(_dot(vr[ks, :], do, _NT),
                                      delta_ref[p:p + 1, qs])).astype(q.dtype)
            if own and t.kv_grid:
                dkn_acc[ks, :] += _dot(dst, q, _NN)
            elif own:
                dkn_ref[ks, :] = (_dot(dst, q, _NN) * sm_scale
                                  ).astype(dkn_ref.dtype)
            else:
                dk_acc[ks, :] += _dot(dst, q, _NN)
            # these queries' rows of the whole-sequence accumulator
            start, stop, _ = qs.indices(t.block_q)
            rows = pl.ds(pl.multiple_of(iq * t.block_q + start, LANES),
                         stop - start)
            if ks_ref is None:
                dq_acc[rows, :] += _dot(dst, k, _TN)
            else:
                shared[1][ks, :] += _dot(dst, q_shared, _NN)
                dq_acc[rows, :d] += _dot(dst, k, _TN)
                dq_acc[rows, d:] += _dot(dst, ks_part, _TN)

    if t.bd:
        @pl.when(iq == ik)
        def _():
            for rows in _lane_chunks(t.block_q):
                part(rows, rows, None, None, own=True)
    if t.eva:
        _on_tiles(t, iq, ik, part, in_window)
        _on_summaries(t, iq, js, seen, part)

        # (one head a step) a tile of summaries is the accumulators' first
        # ``block_s`` rows
        @pl.when(jnp.logical_and(step == last_step, ik >= t.nk))
        def _():
            rows = slice(0, t.block_s)
            dkp_ref[...] = (dk_acc[rows, :] * sm_scale).astype(dkp_ref.dtype)
            dvp_ref[...] = dv_acc[rows, :].astype(dvp_ref.dtype)
    else:
        _on_tiles(t, iq, ik, part)

    @pl.when(jnp.logical_and(step == last_step, ik < t.nk) if t.eva
             else last())
    def _():
        if t.bd and t.kv_grid:      # both copies' tile, as accumulated
            (dk_out, dk_all), (dv_out, dv_all) = key_side
            dk_out[...] = (dk_all[...] * sm_scale).astype(dk_out.dtype)
            dv_out[...] = dv_all[...].astype(dv_out.dtype)
            return
        for _, _, _, _, _, dk_ref, dv_ref, _, dk_acc, dv_acc, *shared in heads:
            dk_ref[...] = (dk_acc[...] * sm_scale).astype(dk_ref.dtype)
            dv_ref[...] = dv_acc[...].astype(dv_ref.dtype)
            if shared:
                shared[0][...] = (shared[1][...] * sm_scale
                                  ).astype(shared[0].dtype)

    @pl.when(jnp.logical_and(ik == last_k, last()))
    def _():
        if t.kv_grid:
            for p, dq in enumerate(_per_head(dq_out, t.rep, q_is)):
                dq[...] = (dq_all[p] * sm_scale).astype(dq_out.dtype)
            return
        for _, _, _, _, dq_ref, _, _, dq_acc, *_ in heads:
            dq_ref[...] = (dq_acc[...] * sm_scale).astype(dq_ref.dtype)


@functools.partial(jax.jit, static_argnums=(6, 7, 8, 9, 10, 11, 12, 14, 16),
                   inline=True)
def _flash_backward(q, k, v, out, lse, g, causal: bool, sm_scale: float,
                    q_offset: int, k_offset: int, interpret: bool,
                    bd: int = 0, window: int = 0, k_shared=None,
                    lay: Optional[_Layout] = None, pooled=None,
                    eva: tuple = (), gate=None):
    """dq, dk, dv of ``_flash_attention`` from its residuals (``lse`` as the
    forward leaves it: (b*h, 1, s_q) rows) and ``g``; with ``k_shared``, its
    gradient too, summed over the heads; with ``pooled``, the summaries'
    keys' and values' (last); with ``gate`` (b, s_q, h), the logits of the
    gates the forward took — ``out`` is the gated output, ``g`` its
    cotangent — the logits' gradient, (b, s_q, h) in float32 (last).  Each
    other gradient has the rank of what
    it is the gradient of; one of rank 3 is (b, s, h * width), the heads side
    by side from column 0, whatever columns the operand itself was read at.

    Jitted and inlined for the reason ``_flash_forward`` is."""
    from jax.experimental.pallas import tpu as pltpu

    lay = lay or _Layout(q.shape[1])
    b, h, n, rep = q.shape[0], lay.heads, lay.group, lay.rep
    (s_q, d), (s_k, d_k), (_, d_v) = map(_seen, (q, k, v), lay[1:4])
    s_k = s_k // 2 if bd else s_k       # one copy's
    t = _Tiles.of(s_k if bd else s_q, s_k, d, q.dtype, causal,
                  q_offset - k_offset, _BWD_DIAG_CHUNK, bd=bd, window=window,
                  eva=eva)._replace(group=n, rep=rep)
    s_q_pad, s_k_pad = t.nq * t.block_q, t.nk * t.block_k
    # a group's dK and dV are summed in VMEM where the group's dQ fits
    # there, else beside the kernel (``_Tiles.rep``)
    t = t._replace(kv_grid=rep > 1 and _bwd_vmem_bytes(
        rep * s_q_pad, d, q.dtype) <= _VMEM_BYTES)
    # the heads of a key-side gradient, and of a grid step's dQ
    h_k, whole = h // rep if t.kv_grid else h, t._replace(
        group=n * rep if t.kv_grid else n)
    if rep > 1:     # once a traced shape: which grid the group's backward took
        logger.debug(
            "flash backward: %d query heads a key/value head, %d queries: %s "
            "(vmem_limit_bytes %d of %d)", rep, s_q_pad,
            "the key/value-head grid, the group's dQ in VMEM" if t.kv_grid
            else "a gradient a query head, summed beside the kernel",
            _bwd_vmem_bytes(whole.group * s_q_pad, d, q.dtype), _VMEM_BYTES)
    q_is, k_is, v_is, o_is = (c is not None for c in lay[1:5])

    row = functools.partial(_stat_rows, b=b, h=h, n=n, s_q_pad=s_q_pad, bd=bd)
    rows = functools.partial(_rows, group=n)
    q_rows, k_rows = (_halves, _copies) if bd else (rows, rows)
    delta = out.astype(jnp.float32) * g.astype(jnp.float32)
    if o_is:
        # each head's columns summed on the MXU, by a matrix of ones where a
        # column is the head's, at float32's precision: the product goes into
        # the contraction as it is made, and the sums come out a row a head.
        # (As a reshape to (b, s, h, d) and a sum XLA first writes the
        # float32 product out in another tiling: 0.53 GB a layer for 0.08 at
        # GPT-2's shape, 1.36 for 0.27 at 2 x 8192 x 32 x 128, compiled.)
        of_head = (lax.broadcasted_iota(jnp.int32, (h * d_v, h), 0) // d_v
                   == lax.broadcasted_iota(jnp.int32, (h * d_v, h), 1))
        delta = jnp.einsum("bsc,ch->bhs", delta, of_head.astype(jnp.float32),
                           precision=lax.Precision.HIGHEST)
    else:
        delta = jnp.sum(delta, axis=-1)
    # A row with an empty (fully masked) softmax has lse == NEG_INF, and
    # exp(s - lse) would blow up: such rows, like the padding rows, get an lse
    # under which every p is zero.
    lse = jnp.where(lse > NEG_INF / 2, lse, -NEG_INF)
    dgate = ()
    if gate is not None:
        # ``out`` is g o and the cotangent handed in is dY, the gated
        # output's; the ungated output's is g dY.  The kernel takes dY as it
        # comes and meets the gates in its two rows of statistics:
        # exp(S - (lse - log g)) = g P, whose transpose by dY is dV, and
        # g P (dY V^T - delta / g) = P (g dY V^T - delta), which is dS.
        # delta = rowsum(dY out) = g rowsum(dY o), so the logit's gradient
        # g (1 - g) rowsum(dY o) is delta (1 - g): nothing is divided for
        # it.  Where g underflows, P is 0 under its lse and delta / g is
        # taken for 0.
        x = gate.astype(jnp.float32).transpose(0, 2, 1)     # as delta
        gates = jax.nn.sigmoid(x)
        dgate = ((delta * (1.0 - gates)).transpose(0, 2, 1),)
        lse = lse - jax.nn.log_sigmoid(x).reshape(lse.shape)
        seen = gates >= jnp.finfo(jnp.float32).tiny
        delta = jnp.where(seen, delta / jnp.where(seen, gates, 1.0), 0.0)
    (k_spec,), (v_spec,) = (t.k_specs(d_k, lay.k, True),
                            t.k_specs(d_v, lay.v, True))
    row_spec = t.row_spec(True)

    def dk_of(width, cols):
        """(spec, shape) of a key-side gradient: its tile, written once the
        inner axis is through; under ``bd`` both copies' tile, as the keys
        come."""
        cols = cols and _dense(width)
        mid = (2, s_k_pad) if bd else (s_k_pad,)
        # (its heads are the grid's: the key/value heads under ``kv_grid``)
        return (t.blocks((2, t.block_k) if bd else (t.block_k,), width, cols,
                          lambda i, j: (0, i) if bd
                          else (jnp.minimum(i, t.nk - 1),) if eva else (i,),
                          t.kv_grid),
                jax.ShapeDtypeStruct(
                    t.array(b, h_k, mid, width, cols is not None), q.dtype))

    def scratch(rows, width):
        return pltpu.VMEM(rows + (width,), jnp.float32)

    # dQ sums over the k blocks, the outer axis: its block is the whole
    # sequence of the step's heads, written back once when they are done.
    dq_spec = whole.blocks((s_q_pad,), d, lay.q and _dense(d),
                           lambda i, j: (0,), t.kv_grid)
    in_specs = [t.q_spec(d, lay.q, True), t.q_spec(d_v, lay.out, True),
                row_spec, row_spec, k_spec, v_spec]
    out_specs, out_shape = map(list, zip(
        (dq_spec, jax.ShapeDtypeStruct(
            whole.array(b, h, (s_q_pad,), d, q_is), q.dtype)),
        dk_of(d_k, lay.k), dk_of(d_v, lay.v)))
    # (under ``bd`` a group's noised squares have accumulators of their own)
    tile = t.per_head(*((2,) if bd and t.kv_grid else ()), t.block_k)
    scratches = [scratch(whole.per_head(s_q_pad), d), scratch(tile, d_k),
                 scratch(tile, d_v)]
    operands = [q_rows(q, s_q_pad, q_is), q_rows(g, s_q_pad, o_is),
                row(lse, fill=-NEG_INF),
                row(delta.reshape(b * h, 1, s_q), fill=0.0),
                k_rows(k, s_k_pad, k_is), k_rows(v, s_k_pad, v_is)]
    if k_shared is not None:
        # each head's own gradient of the shared part, summed over the heads
        # beside the kernel
        d_s = k_shared.shape[-1]
        in_specs.append(t.shared_spec(d_s, True))
        spec, shape = dk_of(d_s, None)
        out_specs.append(spec)
        out_shape.append(shape)
        scratches.append(scratch(tile, d_s))
        operands.append(_pad(k_shared[:, 0], 1, s_k_pad))
    if eva:
        # the summaries' tiles follow the positions' on the outer axis and
        # take their turn in the same two accumulators
        n_pooled = pooled[0].shape[1 if k_is else 2]
        for x, width, cols in ((pooled[0], d_k, lay.k), (pooled[1], d_v, lay.v)):
            cols = cols and _dense(width)
            spec = t.pooled_spec(width, cols, True)
            in_specs.append(spec)
            out_specs.append(spec)
            out_shape.append(jax.ShapeDtypeStruct(t.array(
                b, h, (t.ns * t.block_s,), width, cols is not None), q.dtype))
            operands.append(_rows(x, t.ns * t.block_s, cols is not None))
    dq, dk, dv, *dks = pl.pallas_call(
        functools.partial(_flash_bwd_kernel, sm_scale=sm_scale, t=t,
                          tokens=(q_is, o_is, k_is, v_is)),
        grid=(b, h_k // n, t.nk + t.ns, *((rep,) if t.kv_grid else ()),
              t.steps(True)),
        in_specs=in_specs, out_specs=out_specs, out_shape=out_shape,
        scratch_shapes=scratches,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel") + ("arbitrary",) * (
                3 if t.kv_grid else 2),
            vmem_limit_bytes=_bwd_vmem_bytes(whole.group * s_q_pad, d,
                                             q.dtype)),
        interpret=interpret,
        name="flash_bwd",
    )(*operands)

    def back(dx, x, s, tokens, heads=h):
        dx = _merged(dx, tokens, heads)
        axis = 1 if tokens else 2
        return (_unhalved(dx, s_k, axis) if bd
                else lax.slice_in_dim(dx, 0, s, axis=axis)).astype(x.dtype)

    def key_grad(dx, x, tokens):
        dx = back(dx, x, s_k, tokens, h_k)
        if h_k * rep == h:
            return dx
        # a gradient a query head: the group's sum, beside the kernel
        if tokens:
            return jnp.sum(
                dx.reshape(*dx.shape[:2], h // rep, rep, -1), axis=3,
                dtype=jnp.float32).astype(dx.dtype).reshape(*dx.shape[:2], -1)
        return jnp.sum(dx.reshape(b, h // rep, rep, *dx.shape[2:]), axis=2,
                       dtype=jnp.float32).astype(dx.dtype)

    grads = (back(dq, q, s_q, q_is), key_grad(dk, k, k_is),
             key_grad(dv, v, v_is))
    if eva:
        grads += tuple(back(dx, x, n_pooled, rank3) for dx, x, rank3
                       in zip(dks, pooled, (k_is, v_is)))
    if k_shared is not None:
        grads += (jnp.sum(
            _merged(dks[0], False, h)[:, :, :s_k], axis=1, keepdims=True,
            dtype=jnp.float32).astype(k_shared.dtype),)
    return grads + dgate


# ============================================================= public op
def _named_forward(*args):
    """``_flash_forward``'s ``out`` and ``lse`` under ``FLASH_RESIDUALS``."""
    return tuple(map(checkpoint_name, _flash_forward(*args),
                     FLASH_RESIDUALS))


@functools.partial(jax.custom_vjp, nondiff_argnums=tuple(range(6, 16)))
def _flash_attention(q, k, v, k_shared=None, pooled=None, gate=None,
                     causal=True, sm_scale=1.0, q_offset=0, k_offset=0,
                     block_q=None, block_k=None, window=0, bd=0, lay=None,
                     eva=()):
    """The flash pair under its one differentiation rule, for every mask and
    layout the kernels take.  ``lay``: the operands' ranks and the output's
    (``_Layout``, from ``flash_attention``).  ``k_shared``: the last
    dimensions of every head's key held once a position
    (``flash_attention``), or None — an empty pytree, whose cotangent is
    None.  ``bd`` > 0, attention under
    ``block_diffusion_mask``: q, k, v (b, h, 2 l, d), the noised copy of ``l``
    positions and then the clean one, in blocks of ``bd``.  One kernel call,
    one online softmax over every live pair: both copies' queries over the
    clean keys, tiles skipped and masked as under a causal diagonal, and a
    noised block against itself (``l / bd`` squares of ``bd`` x ``bd``
    scores: 0.1% of the pairs at l 4096 and bd 4) as one more step of a
    noised tile (``_Tiles``).  ``eva`` (window, chunk) with ``pooled``, the
    keys and values of one summary a chunk (or None, as ``k_shared``): the
    window's own causal tiles and the summaries of the windows before, in
    that one softmax.  ``gate`` (b, s_q, h), or None as ``k_shared``: the
    logits of a sigmoid gate a head a query on the output, taken inside the
    kernels — the forward's last division, the backward's two rows of
    statistics — with the logits' gradient read off the backward's ``delta``;
    the output, and the residual ``flash_out``, is the gated one."""
    return _flash_fwd_rule(q, k, v, k_shared, pooled, gate, causal, sm_scale,
                           q_offset, k_offset, block_q, block_k, window, bd,
                           lay, eva)[0]


def _flash_fwd_rule(q, k, v, k_shared, pooled, gate, causal, sm_scale,
                    q_offset, k_offset, block_q, block_k, window, bd, lay,
                    eva):
    out, lse = _named_forward(q, k, v, causal, sm_scale, q_offset, k_offset,
                              block_q, block_k, _interpret(), bd, window,
                              k_shared, lay, pooled, eva, gate)
    return out, (q, k, v, k_shared, pooled, gate, out, lse)


def _flash_bwd_rule(causal, sm_scale, q_offset, k_offset, block_q, block_k,
                    window, bd, lay, eva, residuals, g):
    q, k, v, k_shared, pooled, gate, out, lse = residuals
    with jax.named_scope("flash_bwd"):
        dq, dk, dv, *dks = _flash_backward(
            q, k, v, out, lse, g, causal, sm_scale, q_offset, k_offset,
            _interpret(), bd, window, k_shared, lay, pooled, eva, gate)
        dgate = None if gate is None else dks.pop().astype(gate.dtype)
        # a rank-3 operand read at some columns of its array: the gradient
        # is that array's, zero elsewhere (three operands of one array add up
        # to its whole gradient in one pass)
        dq, dk, dv = (
            dx if cols is None else jax.linear_transpose(
                functools.partial(_take, cols=cols, heads=heads), x)(dx)[0]
            for dx, x, cols, heads in zip(
                (dq, dk, dv), (q, k, v), lay[1:4],
                (lay.heads,) + (lay.heads // lay.rep,) * 2))
    if eva:
        return dq, dk, dv, None, tuple(dks), dgate
    return dq, dk, dv, (dks[0] if dks else None), None, dgate


_flash_attention.defvjp(_flash_fwd_rule, _flash_bwd_rule)


class HeadColumns(NamedTuple):
    """A rank-3 operand of ``attention`` whose heads are some columns of a
    wider array, read where they lie: ``heads`` heads ``width`` wide, head
    ``h`` the columns of ``x`` (B, S, C) from ``first + h * stride``
    (``stride`` 0: ``width``, the heads side by side).  GPT-2's q, k and v
    are the three thirds of ``qkv_proj``'s output, latent attention's keys
    and values the two halves of each head's columns of ``wukv``'s."""
    x: jax.Array
    heads: int
    width: int
    first: int = 0
    stride: int = 0


def _operand(x, heads: int):
    """An operand as ``attention`` takes it -> (array, its ``_Cols`` or
    None)."""
    if isinstance(x, HeadColumns):
        cols = _Cols(x.width, x.first, x.stride or x.width)
        within = cols.first % cols.stride
        if x.heads != heads or within + cols.width > cols.stride or \
                cols.first - within + heads * cols.stride > x.x.shape[-1]:
            raise ValueError(f"{heads} heads against {x._replace(x=None)} "
                             f"of {x.x.shape}")
        return x.x, cols
    if x.ndim == 4:
        return x, None
    if x.shape[-1] % heads:
        raise ValueError(f"{heads} heads in {x.shape[-1]} columns")
    return x, _dense(x.shape[-1] // heads)


def _heads_in(q, head_dim: Optional[int]) -> int:
    if isinstance(q, HeadColumns):
        return q.heads
    if q.ndim == 4:
        return q.shape[1]
    if not head_dim:
        raise ValueError("a rank-3 (B, S, H * D) query comes with head_dim")
    return q.shape[-1] // head_dim


def _operands(q, k, v, head_dim: Optional[int], shared: int = 0):
    """q, k, v as ``attention`` takes them -> (the query heads, the
    key/value heads, the three arrays, their ``_Cols``).  The key/value
    heads are what k's shape says — a rank-3 k is as wide a head as the
    queries less the ``shared`` part — and v has as many."""
    heads = _heads_in(q, head_dim)
    q, q_cols = _operand(q, heads)
    n_kv = _heads_in(k, _seen(q, q_cols)[1] - shared)
    if n_kv < 1 or heads % n_kv:
        raise ValueError(f"{heads} query heads over {n_kv} key/value heads")
    (k, k_cols), (v, v_cols) = _operand(k, n_kv), _operand(v, n_kv)
    if v.ndim == 4 and v.shape[1] != n_kv:
        raise ValueError(f"{n_kv} key heads against {v.shape[1]} of values")
    return heads, n_kv, (q, k, v), (q_cols, k_cols, v_cols)


def _repeated(x, cols: Optional[_Cols], n_kv: int, rep: int):
    """A key-side operand of either rank as (B, H, S, D), each key/value
    head copied to its ``rep`` query heads: what an implementation with no
    grouped form takes.  The scope is the trace's name for these copies, and
    for the sum over a group that is their transpose."""
    x = _to_heads(x, cols, n_kv)
    if rep == 1:
        return x
    with jax.named_scope("kv_repeat"):
        return jnp.repeat(x, rep, axis=1)


def _settled(lay: _Layout, alone: bool) -> _Layout:
    """``lay`` with the rank-3 operands left that the kernels can address as
    they lie (``_Tiles``), the others None: those go head-major.  ``alone``:
    the mask's kernels take a head a block, never two."""
    def kept(group):
        return lay._replace(**{
            name: cols if cols is not None and cols.fits(group) else None
            for name, cols in zip(lay._fields[1:5], lay[1:5])})
    if not (alone or lay.heads % 2) and kept(2).group == 2:
        return kept(2)
    return kept(1)


def _eva(window: int, chunk: int, length: int) -> tuple:
    """(window, chunk) of the EVA mask over a row of ``length`` positions,
    or (): no such mask, and a row of at most one window, which sees its own
    keys alone, has none either: it is the causal call."""
    return (int(window), int(chunk)) if 0 < window < length else ()


def _pooled_as(x, heads: int, seen: int, tokens: bool):
    """A summaries' operand of either rank -> its first ``seen`` summaries,
    (B, n, H * D) if ``tokens`` else (B, H, n, D)."""
    if (x.ndim == 3) != tokens:
        x = _to_tokens(x) if tokens else _to_heads(
            x, _dense(x.shape[-1] // heads), heads)
    return lax.slice_in_dim(x, 0, seen, axis=1 if tokens else 2)


def _bhsd_spec(mesh, batch_axes, head_axis, seq_axis=None, tokens=False):
    """PartitionSpec of a (B, H, S, D) tensor — or, ``tokens``, a (B, S,
    H * D) one, its columns cut by whole heads — over whichever of the named
    axes ``mesh`` has."""
    batch = tuple(a for a in batch_axes if a in mesh.shape) or None
    head = head_axis if head_axis in mesh.shape else None
    return P(batch, seq_axis, head) if tokens \
        else P(batch, head, seq_axis, None)


def flash_attention(q, k, v, *, causal: bool = True, sm_scale: Optional[float] = None,
                    q_offset: int = 0, k_offset: int = 0,
                    block_q: Optional[int] = None, block_k: Optional[int] = None,
                    diffusion_block: int = 0, window: int = 0, k_shared=None,
                    head_dim: Optional[int] = None,
                    tokens_out: Optional[bool] = None,
                    eva_window: int = 0, eva_chunk: int = 0,
                    k_pooled=None, v_pooled=None, gate=None):
    """Blockwise (flash) attention.  Each of q, k, v is rank 4, (B, H, S, D),
    or rank 3, (B, S, H * D) as a projection wrote it (or a ``HeadColumns``
    of such an array); a rank-3 q comes with ``head_dim``.  k and v may have
    fewer heads than q, a whole number of query heads to each (grouped-query
    attention: a rank-4 k's second dimension, a rank-3 k's columns over the
    queries' head width), and are read where they lie, nothing repeated
    (``_Tiles.rep``; the module's docstring says where not).  The output has
    q's rank, or ``tokens_out`` says: (B, S, H * Dv) if true, (B, H, S, Dv)
    if not.  The kernels read a rank-3 operand and write a rank-3 output
    where they lie, no transpose between, wherever its head width is whole
    lane tiles or half of one (``_Tiles``); elsewhere the operand is turned
    head-major here, and the output back.

    v may be another width than q and k (latent attention's 128 under scores
    192 wide): the output is v's, nothing is padded, and the accumulators
    that belong to the values (the forward's output, the backward's dV) are
    as wide as they.  ``k_shared`` (B, 1, S, D_s): the last D_s dimensions of
    every head's key, held once a position — a rotary part that all heads
    share; k is then D - D_s wide, the kernels take the two parts as they
    are (a query's first dimensions against k, its last against the shared
    part, one sum of scores), and the shared part's gradient is summed over
    the heads.

    ``window`` > 0, under the causal mask: a query sees itself and the
    ``window - 1`` positions before it, and no tile outside that band is
    fetched or visited (``_Tiles``).

    ``diffusion_block`` > 0: the mask of block-diffusion training in place of
    the causal one (``block_diffusion_mask``; S is a noised and a clean copy
    of S / 2 positions, in blocks of that many; a power of two up to 128).

    ``eva_window`` > 0, under the causal mask (``eva_mask``): a query sees
    the positions of its own aligned window of that many up to itself, and of
    ``k_pooled`` / ``v_pooled`` — one summary key and value for every
    ``eva_chunk`` positions, rank 4 or rank 3 as k and v — those of the
    windows before its own, all in one online softmax; no tile outside the
    window and the visible summaries is visited, and the summaries get their
    gradients.  The window is whole lanes and whole chunks.

    ``gate`` (B, S, H): the logits of a sigmoid gate a head a query; head
    ``h``'s output at query ``s`` is multiplied by ``sigmoid(gate[b, s, h])``
    in float32 before the output's one rounding, inside the forward kernel;
    the backward kernel meets the gates in the logsumexp and ``delta`` it is
    handed, and the logits' gradient comes off ``delta``
    (``_flash_backward``) — no pass over the output is made for the gate,
    forward or backward.

    Under an ambient mesh of more than one device the kernel runs inside a
    ``shard_map`` — batch over dp/fsdp, heads over tp (the gate's as the
    columns of a rank-3 operand), the sequence whole on
    every device (sequence sharding is ring attention's job).  Without one,
    or on a one-device mesh, it is the plain call.
    """
    shared = 0 if k_shared is None else k_shared.shape[-1]
    heads, n_kv, (q, k, v), cols = _operands(q, k, v, head_dim, shared)
    (_, d), (s_k, d_k), (_, d_v) = map(_seen, (q, k, v), cols)
    if tokens_out is None:
        tokens_out = cols[0] is not None
    if sm_scale is None:
        sm_scale = d ** -0.5
    # These three guard the kernels against a direct caller (the tests are
    # one); ``attention`` calls through and lets them speak.
    if window and (diffusion_block or not causal):
        raise ValueError("a window belongs to the causal mask")
    eva = _eva(eva_window, eva_chunk, s_k)
    if eva and (window or diffusion_block or shared or not causal
                or q_offset or k_offset):
        raise ValueError("the EVA mask is its own, under the causal one")
    if eva and (eva[0] % LANES or eva[0] % eva[1]):
        raise NotImplementedError(
            f"the kernels take an EVA window in whole lanes ({LANES}) and "
            f"whole chunks, not {eva}")
    if d != d_k + shared:
        raise ValueError(f"queries {d} wide against keys {d_k} + {shared}")
    if diffusion_block and (shared or d != d_v):
        raise NotImplementedError(
            "the block mask's kernels take one width for scores and values")
    mesh = ambient_mesh()
    if mesh is not None and mesh.size == 1:
        mesh = None
    tp = mesh.shape.get("tp", 1) if mesh is not None else 1
    if tp > 1:
        # a device's share of the columns is whole heads only where they lie
        # side by side and alone: such an operand as an array of its own
        q, k, v = (x if c is None else _take(x, c, n)
                   for x, c, n in zip((q, k, v), cols, (heads, n_kv, n_kv)))
        cols = tuple(c and _dense(c.width) for c in cols)
    alone = bool(diffusion_block or eva)
    lay = _Layout(heads // tp, *cols, _dense(d_v) if tokens_out else None)
    if n_kv != heads and (shared or eva or n_kv % tp
                          or _settled(lay, alone).group > 1):
        # the kernels address a group's key/value head for a head a grid
        # step, of one kind of key, a device's share whole key/value heads
        logger.warning(
            "flash attention: %d query heads over %d key/value heads (k %s, "
            "v %s, tp %d) are not addressed as they lie; k and v are copied "
            "to the query heads", heads, n_kv, k.shape, v.shape, tp)
        k, v = (_repeated(x, c, n_kv, heads // n_kv)
                for x, c in zip((k, v), cols[1:]))
        n_kv, cols, lay = heads, (cols[0], None, None), lay._replace(
            k=None, v=None)
    # what one device's call can address as it lies; the rest head-major
    lay = _settled(lay._replace(rep=heads // n_kv), alone)
    q, k, v = (x if kept is not None else _to_heads(x, c, n)
               for x, c, kept, n in zip((q, k, v), cols, lay[1:4],
                                        (heads, n_kv, n_kv)))
    pooled = None
    if eva:
        # the summaries that some query sees, laid out as k and v are
        seen = (s_k - 1) // eva[0] * (eva[0] // eva[1])
        pooled = tuple(_pooled_as(x, heads, seen, kept is not None)
                       for x, kept in zip((k_pooled, v_pooled), lay[2:4]))
    # (what a call does not have is None: an empty pytree)
    operands = (q, k, v, k_shared, pooled, gate)
    # (the block mask is its own: causal is not asked, the offsets not read)
    f = functools.partial(
        _flash_attention, causal=causal and not diffusion_block,
        sm_scale=float(sm_scale), q_offset=int(q_offset),
        k_offset=int(k_offset), block_q=block_q, block_k=block_k,
        window=int(window), bd=int(diffusion_block), lay=lay, eva=eva)
    if mesh is None:
        out = f(*operands)
    else:
        spec = functools.partial(_bhsd_spec, mesh, ("dp", "fsdp"))
        in_specs = [spec("tp", tokens=c is not None) for c in lay[1:4]]
        # the shared part's one head: whole on every device of a tp group;
        # the summaries as k and v; the gate's heads as a rank-3 operand's
        in_specs += [x if have else None for x, have in zip(
            (spec(None), tuple(in_specs[1:3]), spec("tp", tokens=True)),
            (k_shared is not None, eva, gate is not None))]
        out = jax.shard_map(
            f, mesh=mesh, in_specs=tuple(in_specs),
            out_specs=_bhsd_spec(mesh, ("dp", "fsdp"), "tp",
                                 tokens=lay.out is not None),
            check_vma=False)(*operands)
    return _to_tokens(out) if tokens_out and lay.out is None else out


# ======================================================== ring attention
def _online_merge(m_a, l_a, acc_a, m_b, l_b, acc_b):
    m = jnp.maximum(m_a, m_b)
    ea = jnp.exp(m_a - m)
    eb = jnp.exp(m_b - m)
    l = l_a * ea + l_b * eb
    acc = acc_a * ea[..., None] + acc_b * eb[..., None]
    return m, l, acc


def _chunk_attention(q, k, v, sm_scale, causal, q_off, k_off):
    """Unnormalized blockwise attention of one (q shard, kv chunk) pair.
    Returns (m, l, acc) partials for online merging.  Pure XLA: inside
    shard_map+jit, XLA fuses this well; a fully fused Pallas ring kernel with
    RDMA is the planned upgrade (pallas_guide ring-collective pattern)."""
    s = jnp.einsum("bhqd,bhkd->bhqk", q, k).astype(jnp.float32) * sm_scale
    if causal:
        q_pos = jnp.arange(q.shape[2])[:, None] + q_off
        k_pos = jnp.arange(k.shape[2])[None, :] + k_off
        s = jnp.where(q_pos >= k_pos, s, NEG_INF)
    m = jnp.max(s, axis=-1)
    p = jnp.exp(s - m[..., None])
    l = jnp.sum(p, axis=-1)
    acc = jnp.einsum("bhqk,bhkd->bhqd", p, v.astype(jnp.float32))
    # A fully-masked row (m == NEG_INF) contributes nothing.
    dead = m <= NEG_INF / 2
    return jnp.where(dead, NEG_INF, m), jnp.where(dead, 0.0, l), \
        jnp.where(dead[..., None], 0.0, acc)


def ring_attention(q, k, v, *, axis_name: str = "sp", causal: bool = True,
                   sm_scale: Optional[float] = None):
    """Ring attention over a sequence-parallel mesh axis.

    Call INSIDE shard_map (or jit with sharded inputs + manual axis): each
    device holds the (B, H, S/ring, D) shard of q/k/v; KV rotates around the
    ring via ppermute (ICI neighbor exchange) while partial attention results
    merge with the online-softmax combine.  Matches unsharded causal attention
    exactly (global positions reconstructed from the axis index).
    """
    # (guards a direct caller and ``attention`` alike, which calls through)
    if q.shape[-1] != v.shape[-1]:
        raise NotImplementedError(
            "ring attention takes one width for scores and values")
    if sm_scale is None:
        sm_scale = q.shape[-1] ** -0.5
    ring = jax.lax.axis_size(axis_name)
    me = jax.lax.axis_index(axis_name)
    chunk = q.shape[2]
    b, h, _, d = q.shape

    q_off = me * chunk

    def step(carry, i):
        kv, m, l, acc = carry
        k_cur, v_cur = kv
        src = (me - i) % ring  # whose kv chunk we now hold
        k_off = src * chunk
        mc, lc, accc = _chunk_attention(q, k_cur, v_cur, sm_scale, causal,
                                        q_off, k_off)
        m, l, acc = _online_merge(m, l, acc, mc, lc, accc)
        # rotate kv to the next device (skip the final, unused rotation is
        # harmless and keeps the loop shape static)
        perm = [(j, (j + 1) % ring) for j in range(ring)]
        k_nxt = jax.lax.ppermute(k_cur, axis_name, perm)
        v_nxt = jax.lax.ppermute(v_cur, axis_name, perm)
        return ((k_nxt, v_nxt), m, l, acc), None

    m0 = jnp.full((b, h, chunk), NEG_INF, jnp.float32)
    l0 = jnp.zeros((b, h, chunk), jnp.float32)
    acc0 = jnp.zeros((b, h, chunk, d), jnp.float32)
    (_, m, l, acc), _ = jax.lax.scan(step, ((k, v), m0, l0, acc0),
                                     jnp.arange(ring))
    l_safe = jnp.where(l == 0.0, 1.0, l)
    return (acc / l_safe[..., None]).astype(q.dtype)


def ring_attention_sharded(q, k, v, *, mesh=None, causal: bool = True,
                           sm_scale: Optional[float] = None,
                           batch_axes=("dp", "fsdp"), head_axis: str = "tp",
                           seq_axis: str = "sp"):
    """Ring attention under plain jit/GSPMD: wraps ``ring_attention`` in a
    shard_map over the mesh so the sequence axis becomes a manual (named) axis.

    q,k,v: (B, H, S, D) sharded (batch_axes, head_axis, seq_axis, None).
    Differentiable (shard_map + ppermute have transposition rules).
    """
    mesh = mesh or ambient_mesh()
    if mesh is None:
        raise ValueError("ring_attention_sharded needs a mesh (pass mesh= or "
                         "activate one with `jax.set_mesh`)")
    spec = _bhsd_spec(mesh, batch_axes, head_axis, seq_axis)
    f = jax.shard_map(
        functools.partial(ring_attention, axis_name=seq_axis, causal=causal,
                          sm_scale=sm_scale),
        mesh=mesh, in_specs=(spec, spec, spec), out_specs=spec, check_vma=False)
    return f(q, k, v)


# ================================================================ the entry
# What was asked (a keyword of ``attention``) -> the implementations that have
# nothing for it, and the words for it: the one table of what is refused.  A
# new mask or layout is a row here and a field of ``_Tiles``.  What an
# implementation's own guard covers is not said again: ``attention`` calls
# through and lets it speak (a window without the causal mask, the block mask
# over two widths, two widths around the ring).
_REFUSED = {
    "window": (("ring",), "window"),
    "diffusion_block": (("ring",), "block mask"),
    "k_shared": (("ring",), "key part that the heads share (latent "
                 "attention): it takes one width for scores and values"),
    "eva_window": (("ring",), "EVA mask (a window's own keys and the "
                   "summaries of the windows before)"),
}


def attention(q, k, v, *, impl: str, causal: bool = True, window: int = 0,
              diffusion_block: int = 0, sm_scale: Optional[float] = None,
              k_shared=None, head_dim: Optional[int] = None,
              ring_axis: str = "sp", eva_window: int = 0, eva_chunk: int = 0,
              k_pooled=None, v_pooled=None, gate=None):
    """What a model's attention layer calls.  Each of q, k, v is rank 4,
    (B, H, S, D), or rank 3, (B, S, H * D) as its projection wrote it (or a
    ``HeadColumns`` of a wider array); a rank-3 q comes with ``head_dim``.
    Under grouped-query attention k and v come with their own, fewer heads.
    The result is (B, S, H * Dv), what the output projection takes, whatever
    the operands' ranks.  The mask's parameters are as ``flash_attention`` and
    ``mha_reference`` take them
    (``diffusion_block`` > 0: ``block_diffusion_mask`` in place of the causal
    mask; ``eva_window`` > 0 with ``eva_chunk`` and the summaries ``k_pooled``,
    ``v_pooled``: ``eva_mask``, and a row of at most one window is the causal
    call, the summaries unread; ``gate`` (B, S, H): the logits of a sigmoid
    gate, one a head a query, on the result — the kernels take it in their
    own passes, "reference" and "ring" as the multiply it is).  Which
    implementation runs is decided here
    and nowhere else: ``impl`` is a config's ``attention_impl`` — "reference", "ring" or "flash",
    and "flash" under an ambient mesh that shards the sequence
    (``ring_axis`` > 1) is the ring, since the kernels want the sequence whole.
    The kernels read rank-3 operands and write the result where they lie
    (``flash_attention``); "reference" and "ring" want (B, H, S, D) with K
    and V at the query heads and get it by a transpose and, under the scope
    ``kv_repeat``, a copy here.
    What the implementation has nothing for is refused, here (``_REFUSED``)
    or by its own guard."""
    mesh = ambient_mesh()
    sharded = impl == "flash" and mesh is not None \
        and mesh.shape.get(ring_axis, 1) > 1
    if sharded:
        impl = "ring"
    heads, n_kv, operands, cols = _operands(
        q, k, v, head_dim, 0 if k_shared is None else k_shared.shape[-1])
    eva_window, eva_chunk = _eva(
        eva_window, eva_chunk, _seen(operands[1], cols[1])[0]) or (0, 0)
    asked = {"window": bool(window), "diffusion_block": bool(diffusion_block),
             "k_shared": k_shared is not None, "eva_window": bool(eva_window)}
    for name, (impls, words) in _REFUSED.items():
        if asked[name] and impl in impls:
            raise NotImplementedError(
                f"attention_impl={impl!r}" + (
                    f" (\"flash\" over a sequence sharded on {ring_axis!r})"
                    if sharded else "") + f" has no {words}")
    if impl == "flash":
        # the scope tells a window's calls, and an EVA layer's, from the full
        # layers' in a trace
        scope = "window" if window else "eva" if eva_window else None
        with jax.named_scope(scope) if scope else contextlib.nullcontext():
            return flash_attention(
                q, k, v, causal=causal, sm_scale=sm_scale,
                diffusion_block=diffusion_block, window=window,
                k_shared=k_shared, head_dim=head_dim, tokens_out=True,
                gate=gate,
                **(dict(eva_window=eva_window, eva_chunk=eva_chunk,
                        k_pooled=k_pooled, v_pooled=v_pooled)
                   if eva_window else {}))
    # (B, H, S, D), K and V copied to the query heads
    q = _to_heads(operands[0], cols[0], heads)
    k, v = (_repeated(x, c, n_kv, heads // n_kv)
            for x, c in zip(operands[1:], cols[1:]))
    if impl == "reference":
        mask = block_diffusion_mask(q.shape[2] // 2, diffusion_block) \
            if diffusion_block else None
        eva = dict(eva=(eva_window, eva_chunk), pooled=tuple(
            _pooled_as(x, heads, x.shape[1 if x.ndim == 3 else 2], False)
            for x in (k_pooled, v_pooled))) if eva_window else {}
        out = mha_reference(
            q, k, v, causal=causal, sm_scale=sm_scale, mask=mask,
            window=window, k_shared=k_shared, **eva)
    elif impl == "ring":
        out = ring_attention_sharded(
            q, k, v, causal=causal, sm_scale=sm_scale, seq_axis=ring_axis)
    else:
        raise ValueError(f"unknown attention_impl {impl!r} (expected "
                         "'flash', 'ring' or 'reference')")
    if gate is not None:    # what a gate is: each head's output by its own
        out = out * jax.nn.sigmoid(gate.astype(jnp.float32)).astype(
            out.dtype).transpose(0, 2, 1)[..., None]
    return _to_tokens(out)

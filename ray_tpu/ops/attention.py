"""Attention kernels: blockwise flash attention (Pallas/TPU) + ring attention.

Greenfield relative to the reference — it has no sequence parallelism anywhere
(SURVEY §5.7; no ring/blockwise attention hits in the reference tree).  Design:

- ``flash_attention``: online-softmax blockwise attention.  Forward is a Pallas
  kernel (grid over (batch*heads, q blocks); KV streamed from VMEM block by
  block with running (m, l, acc) accumulators — the standard flash recurrence).
  Backward recomputes attention blockwise in XLA using the saved logsumexp, so
  memory stays O(S·d) rather than O(S²).
- ``ring_attention``: shard_map over the ``sp`` mesh axis; each step computes
  blockwise attention of the local Q shard against the resident KV shard, then
  rotates KV around the ring with ``jax.lax.ppermute`` (ICI neighbor traffic),
  merging partial results with the online-softmax combine.  Causal masking uses
  global offsets so the math matches unsharded attention exactly.
- Under an ambient mesh (``jax.set_mesh``) ``flash_attention`` runs the kernel
  inside a ``shard_map`` (batch over dp/fsdp, heads over tp): Mosaic kernels
  cannot be partitioned by GSPMD, so each device must see a whole local call.
- The kernel compiles for the TPU unless the process ASKED for the Pallas
  interpreter (``RAY_TPU_PALLAS_INTERPRET=1``, set by the CPU test substrate in
  ``_private/platform.py``); it never falls into interpret mode on its own.
  ``mha_reference`` is the ground truth.

Block sizes default to MXU-friendly (128, 128); head_dim should be a multiple
of 128 for peak MXU utilization but any size compiles.
"""

from __future__ import annotations

import functools
import os
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.sharding import PartitionSpec as P

from ray_tpu.parallel.mesh import ambient_mesh

DEFAULT_BLOCK_Q = 128
DEFAULT_BLOCK_K = 128
NEG_INF = -1e30
# Mosaic requires the last two dims of every block to tile as (8, 128) (or
# equal the full array dim).  The lse output is logically (b*h, s_q) — rank-1
# per grid step — so it is materialized with a trailing 128-lane dim and
# sliced back to lane 0 after the call (same layout trick as
# jax.experimental.pallas.ops.tpu.flash_attention's l/m residuals).
LANES = 128


def _interpret() -> bool:
    """Whether this process asked for the Pallas interpreter (read at trace
    time).  Without the request the kernel lowers through Mosaic, and on a
    backend that cannot run it Pallas raises — a CPU run never passes for a
    kernel run by accident."""
    return os.environ.get("RAY_TPU_PALLAS_INTERPRET") == "1"


# =========================================================== XLA reference
def mha_reference(q, k, v, *, causal: bool = True, sm_scale: Optional[float] = None,
                  q_offset: int = 0, k_offset: int = 0):
    """Naive attention; ground truth for kernels. q,k,v: (B, H, S, D)."""
    if sm_scale is None:
        sm_scale = q.shape[-1] ** -0.5
    logits = jnp.einsum("bhqd,bhkd->bhqk", q, k).astype(jnp.float32) * sm_scale
    if causal:
        qi = jnp.arange(q.shape[2])[:, None] + q_offset
        ki = jnp.arange(k.shape[2])[None, :] + k_offset
        logits = jnp.where(qi >= ki, logits, NEG_INF)
    w = jax.nn.softmax(logits, axis=-1)
    return jnp.einsum("bhqk,bhkd->bhqd", w.astype(v.dtype), v)


# ======================================================== pallas forward
def _flash_fwd_kernel(q_ref, k_ref, v_ref, qo_ref, ko_ref, o_ref, lse_ref,
                      *, block_k: int, sm_scale: float, causal: bool,
                      s_k_real: int):
    # q_ref: (block_q, d); k_ref/v_ref: (S_k padded, d) for this (b,h).
    # s_k_real: the unpadded key length — columns >= s_k_real are padding and
    # always masked out (the S_k buffer is padded to a block_k multiple so
    # pl.ds never clamps/re-reads earlier keys).
    block_q, d = q_ref.shape
    s_k = k_ref.shape[0]
    iq = pl.program_id(1)
    q = q_ref[:].astype(jnp.float32) * sm_scale
    q_pos = jax.lax.broadcasted_iota(jnp.int32, (block_q, block_k), 0) \
        + iq * block_q + qo_ref[0]

    num_kv = pl.cdiv(s_k, block_k)

    def body(j, carry):
        m_prev, l_prev, acc = carry
        k = k_ref[pl.ds(j * block_k, block_k), :].astype(jnp.float32)
        vblk = v_ref[pl.ds(j * block_k, block_k), :].astype(jnp.float32)
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32)
        k_idx = jax.lax.broadcasted_iota(jnp.int32, (block_q, block_k), 1) \
            + j * block_k
        valid = k_idx < s_k_real
        if causal:
            k_pos = k_idx + ko_ref[0]
            valid = valid & (q_pos >= k_pos)
        s = jnp.where(valid, s, NEG_INF)
        m_cur = jnp.max(s, axis=-1)
        m_new = jnp.maximum(m_prev, m_cur)
        # Fully-masked row so far (m_new == NEG_INF): exp(s - m) would be
        # exp(0) = 1 per column; force p = 0 so such rows stay empty.
        p = jnp.where(m_new[:, None] <= NEG_INF / 2, 0.0,
                      jnp.exp(s - m_new[:, None]))
        alpha = jnp.exp(m_prev - m_new)
        l_new = l_prev * alpha + jnp.sum(p, axis=-1)
        acc = acc * alpha[:, None] + jax.lax.dot_general(
            p, vblk, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32)
        return m_new, l_new, acc

    m0 = jnp.full((block_q,), NEG_INF, jnp.float32)
    l0 = jnp.zeros((block_q,), jnp.float32)
    acc0 = jnp.zeros((block_q, d), jnp.float32)

    if causal:
        # Only kv blocks at or before the diagonal contribute; assumes the
        # common layout q_global >= k_global within a shard pair (ring steps
        # with kv entirely after q are skipped by the caller).
        def guarded(j, carry):
            first_q_pos = iq * block_q + qo_ref[0]
            blk_start_kpos = j * block_k + ko_ref[0]
            return jax.lax.cond(
                blk_start_kpos <= first_q_pos + block_q - 1,
                lambda c: body(j, c), lambda c: c, carry)

        m, l, acc = jax.lax.fori_loop(0, num_kv, guarded, (m0, l0, acc0))
    else:
        m, l, acc = jax.lax.fori_loop(0, num_kv, body, (m0, l0, acc0))

    l_safe = jnp.where(l == 0.0, 1.0, l)
    o_ref[:] = (acc / l_safe[:, None]).astype(o_ref.dtype)
    lse = jnp.where(l == 0.0, NEG_INF, m + jnp.log(l_safe))
    lse_ref[:] = jnp.broadcast_to(lse[:, None], (block_q, LANES))


def _round_up(x: int, m: int) -> int:
    return (x + m - 1) // m * m


def _flash_forward(q, k, v, causal: bool, sm_scale: float, q_offset, k_offset,
                   block_q: int, block_k: int, interpret: bool):
    b, h, s_q, d = q.shape
    s_k = k.shape[2]
    block_q = min(block_q, s_q)
    block_k = min(block_k, s_k)
    # Pad both sequence dims to block multiples: pl.ds with a clamped start
    # would silently re-read earlier rows under mislabeled positions (the
    # round-1 advisor bug).  Padded q rows are dropped on return; padded kv
    # columns are masked inside the kernel via s_k_real.
    s_q_pad = _round_up(s_q, block_q)
    s_k_pad = _round_up(s_k, block_k)
    qr = q.reshape(b * h, s_q, d)
    kr = k.reshape(b * h, s_k, d)
    vr = v.reshape(b * h, s_k, d)
    if s_q_pad != s_q:
        qr = jnp.pad(qr, ((0, 0), (0, s_q_pad - s_q), (0, 0)))
    if s_k_pad != s_k:
        kr = jnp.pad(kr, ((0, 0), (0, s_k_pad - s_k), (0, 0)))
        vr = jnp.pad(vr, ((0, 0), (0, s_k_pad - s_k), (0, 0)))
    qo = jnp.asarray([q_offset], jnp.int32)
    ko = jnp.asarray([k_offset], jnp.int32)

    from jax.experimental.pallas import tpu as pltpu

    grid = (b * h, s_q_pad // block_q)
    with jax.named_scope("flash_fwd"):
        out, lse = pl.pallas_call(
            functools.partial(_flash_fwd_kernel, block_k=block_k,
                              sm_scale=sm_scale, causal=causal, s_k_real=s_k),
            grid=grid,
            in_specs=[
                pl.BlockSpec((None, block_q, d), lambda bh, iq: (bh, iq, 0)),
                pl.BlockSpec((None, s_k_pad, d), lambda bh, iq: (bh, 0, 0)),
                pl.BlockSpec((None, s_k_pad, d), lambda bh, iq: (bh, 0, 0)),
                pl.BlockSpec(memory_space=pltpu.SMEM),
                pl.BlockSpec(memory_space=pltpu.SMEM),
            ],
            out_specs=[
                pl.BlockSpec((None, block_q, d), lambda bh, iq: (bh, iq, 0)),
                pl.BlockSpec((None, block_q, LANES),
                             lambda bh, iq: (bh, iq, 0)),
            ],
            out_shape=[
                jax.ShapeDtypeStruct((b * h, s_q_pad, d), q.dtype),
                jax.ShapeDtypeStruct((b * h, s_q_pad, LANES), jnp.float32),
            ],
            interpret=interpret,
            name="flash_fwd",
        )(qr, kr, vr, qo, ko)
    out = out[:, :s_q]
    lse = lse[:, :s_q, 0]
    return out.reshape(b, h, s_q, d), lse.reshape(b, h, s_q)


# ===================================================== blockwise backward
def _flash_backward(q, k, v, out, lse, g, causal, sm_scale, q_offset, k_offset,
                    block_k: int):
    """Memory-efficient backward: recompute P blockwise from saved lse (XLA;
    scan over kv blocks keeps peak memory at O(S·block)."""
    b, h, s_q, d = q.shape
    s_k = k.shape[2]
    qf = q.astype(jnp.float32) * sm_scale
    gf = g.astype(jnp.float32)
    of = out.astype(jnp.float32)
    delta = jnp.sum(of * gf, axis=-1)  # (b,h,s_q)

    # Mirror the forward's clamping, and pad s_k to a block multiple so the
    # reshape below is always valid (the round-1 advisor crash: any s_k not a
    # multiple of the user block_k, e.g. every sequence shorter than 128).
    block_k = min(block_k, s_k)
    s_k_pad = _round_up(s_k, block_k)
    if s_k_pad != s_k:
        k = jnp.pad(k, ((0, 0), (0, 0), (0, s_k_pad - s_k), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, 0), (0, s_k_pad - s_k), (0, 0)))
    num_kv = s_k_pad // block_k
    kb = k.reshape(b, h, num_kv, block_k, d).astype(jnp.float32)
    vb = v.reshape(b, h, num_kv, block_k, d).astype(jnp.float32)

    q_pos = jnp.arange(s_q) + q_offset
    # Rows with an empty (fully-masked) softmax have lse == NEG_INF; their
    # exp(s - lse) would blow up — zero them instead.
    live_row = (lse > NEG_INF / 2)[..., None]

    def one_block(j):
        kj = kb[:, :, j]  # (b,h,block_k,d)
        vj = vb[:, :, j]
        s = jnp.einsum("bhqd,bhkd->bhqk", qf, kj)
        k_idx = jnp.arange(block_k) + j * block_k
        valid = (k_idx < s_k)[None, :]
        if causal:
            k_pos = k_idx + k_offset
            valid = valid & (q_pos[:, None] >= k_pos[None, :])
        s = jnp.where(valid, s, NEG_INF)
        p = jnp.where(live_row, jnp.exp(s - lse[..., None]), 0.0)  # (b,h,q,block_k)
        dv_j = jnp.einsum("bhqk,bhqd->bhkd", p, gf)
        dp = jnp.einsum("bhqd,bhkd->bhqk", gf, vj)
        ds = p * (dp - delta[..., None])
        dq_j = jnp.einsum("bhqk,bhkd->bhqd", ds, kj)
        dk_j = jnp.einsum("bhqk,bhqd->bhkd", ds, qf)
        return dq_j, dk_j, dv_j

    def scan_body(carry, j):
        dq = carry
        dq_j, dk_j, dv_j = one_block(j)
        return dq + dq_j, (dk_j, dv_j)

    dq0 = jnp.zeros((b, h, s_q, d), jnp.float32)
    dq, (dk_blocks, dv_blocks) = jax.lax.scan(scan_body, dq0, jnp.arange(num_kv))
    dk = jnp.moveaxis(dk_blocks, 0, 2).reshape(b, h, s_k_pad, d)[:, :, :s_k]
    # s = (q*sm_scale)·kᵀ, so dL/dq needs the extra sm_scale while dL/dk
    # already carries it through qf.
    dq = dq * sm_scale
    dv = jnp.moveaxis(dv_blocks, 0, 2).reshape(b, h, s_k_pad, d)[:, :, :s_k]
    return dq.astype(q.dtype), dk.astype(k.dtype), dv.astype(v.dtype)


# ============================================================= public op
@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7, 8))
def _flash_attention(q, k, v, causal, sm_scale, q_offset, k_offset,
                     block_q, block_k):
    out, _ = _flash_forward(q, k, v, causal, sm_scale, q_offset, k_offset,
                            block_q, block_k, interpret=_interpret())
    return out


def _flash_fwd_rule(q, k, v, causal, sm_scale, q_offset, k_offset, block_q, block_k):
    out, lse = _flash_forward(q, k, v, causal, sm_scale, q_offset, k_offset,
                              block_q, block_k, interpret=_interpret())
    return out, (q, k, v, out, lse)


def _flash_bwd_rule(causal, sm_scale, q_offset, k_offset, block_q, block_k,
                    residuals, g):
    q, k, v, out, lse = residuals
    with jax.named_scope("flash_bwd"):
        return _flash_backward(q, k, v, out, lse, g, causal, sm_scale,
                               q_offset, k_offset, block_k)


_flash_attention.defvjp(_flash_fwd_rule, _flash_bwd_rule)


def _bhsd_spec(mesh, batch_axes, head_axis, seq_axis=None):
    """PartitionSpec of a (B, H, S, D) tensor over whichever of the named
    axes ``mesh`` has."""
    return P(tuple(a for a in batch_axes if a in mesh.shape) or None,
             head_axis if head_axis in mesh.shape else None,
             seq_axis, None)


def flash_attention(q, k, v, *, causal: bool = True, sm_scale: Optional[float] = None,
                    q_offset: int = 0, k_offset: int = 0,
                    block_q: int = DEFAULT_BLOCK_Q, block_k: int = DEFAULT_BLOCK_K):
    """Blockwise (flash) attention. q,k,v: (B, H, S, D) -> (B, H, S, D).

    Under an ambient mesh of more than one device the kernel runs inside a
    ``shard_map`` — batch over dp/fsdp, heads over tp, the sequence whole on
    every device (sequence sharding is ring attention's job).  Without one,
    or on a one-device mesh, it is the plain call.
    """
    if sm_scale is None:
        sm_scale = q.shape[-1] ** -0.5
    f = functools.partial(
        _flash_attention, causal=causal, sm_scale=float(sm_scale),
        q_offset=int(q_offset), k_offset=int(k_offset),
        block_q=block_q, block_k=block_k)
    mesh = ambient_mesh()
    if mesh is None or mesh.size == 1:
        return f(q, k, v)
    spec = _bhsd_spec(mesh, ("dp", "fsdp"), "tp")
    return jax.shard_map(f, mesh=mesh, in_specs=(spec, spec, spec),
                         out_specs=spec, check_vma=False)(q, k, v)


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

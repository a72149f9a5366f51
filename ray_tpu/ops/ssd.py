"""The Mamba-2 recurrence (Dao and Gu 2024, "Transformers are SSMs") in chunks.

Per head, with a scalar decay ``a_t = exp(dt_t * A)`` and a ``P x N`` state:

    h_t = a_t h_{t-1} + dt_t x_t B_t^T        y_t = h_t C_t

``B`` and ``C`` belong to a group of heads, as keys and queries of grouped
attention do.  The sequence is cut into chunks of ``chunk`` positions.  Inside
a chunk the recurrence unrolls into a masked, decay-weighted attention,
``y_i += sum_{j <= i} (C_i . B_j) exp(l_i - l_j) dt_j x_j`` with ``l`` the
running sum of ``dt * A`` inside the chunk: three matmuls.  Between chunks
only the state at each chunk's end is carried, by a ``lax.scan`` over the
chunks, and read out through ``C_i exp(l_i)``.

Plain ``jax.numpy``: XLA's fusions and matmuls, no kernel.  Matmul operands
are in the activations' dtype and accumulate in float32; everything that
decays — ``dt``, ``dt * A``, its running sums, the ``exp`` of their
differences, the carried state — is float32 whatever the activations are.  A
bf16 running sum of log-decays over 256 steps is another model, not a faster
one (tests/test_mamba.py holds this).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax


def _dot(subscripts: str, a, b):
    return jnp.einsum(subscripts, a, b, preferred_element_type=jnp.float32)


def ssd_scan(x, dt, a_log_rate, b, c, *, chunk: int = 256):
    """``x``: (batch, seq, heads, P); ``dt``: (batch, seq, heads), positive;
    ``a_log_rate``: (heads,), the negative ``A`` of ``a_t = exp(dt_t * A)``;
    ``b``, ``c``: (batch, seq, groups, N) with heads a multiple of groups.
    Returns ``y`` (batch, seq, heads, P) in ``x``'s dtype, from a zero state.

    A sequence that is no multiple of ``chunk`` is padded at its end with
    ``dt = 0`` — a step that neither decays the state nor adds to it — and
    the padding's outputs are dropped.
    """
    batch, seq, heads, p = x.shape
    groups, n = b.shape[-2:]
    pad = -seq % chunk
    if pad:
        x, dt, b, c = (jnp.pad(t, [(0, 0), (0, pad)] + [(0, 0)] * (t.ndim - 2))
                       for t in (x, dt, b, c))
    chunks = (seq + pad) // chunk
    dtype = x.dtype
    # (batch, chunks, chunk, ...); heads as (groups, heads per group)
    x = x.reshape(batch, chunks, chunk, groups, heads // groups, p)
    b = b.reshape(batch, chunks, chunk, groups, n)
    c = c.reshape(batch, chunks, chunk, groups, n)
    dt = dt.astype(jnp.float32).reshape(batch, chunks, chunk, groups, -1)
    rate = a_log_rate.astype(jnp.float32).reshape(groups, -1)
    log_a = jnp.cumsum(dt * rate, axis=2)           # l_i, inclusive
    log_a = jnp.moveaxis(log_a, 2, -1)              # (b, c, g, r, chunk)
    dt = jnp.moveaxis(dt, 2, -1)

    # inside a chunk: position i reads j <= i through exp(l_i - l_j) dt_j
    lower = jnp.tril(jnp.ones((chunk, chunk), bool))
    decay = jnp.exp(jnp.where(lower, log_a[..., :, None] - log_a[..., None, :],
                              -jnp.inf)) * dt[..., None, :]
    scores = _dot("bcign,bcjgn->bcgij", c, b)       # once a group
    y = _dot("bcgrij,bcjgrp->bcigrp",
             (scores[:, :, :, None] * decay).astype(dtype), x)

    # each chunk's own contribution to the state at its end
    to_end = jnp.exp(log_a[..., -1:] - log_a) * dt  # (b, c, g, r, chunk)
    states = _dot("bcjgrp,bcjgn->bcgrpn",
                  (x * jnp.moveaxis(to_end, -1, 2)[..., None]).astype(dtype), b)
    chunk_decay = jnp.exp(log_a[..., -1])           # (b, c, g, r)

    def carry(h, inputs):
        own, a = inputs
        return a[..., None, None] * h + own, h      # emits the state before

    _, before = lax.scan(
        carry, jnp.zeros_like(states[:, 0]),
        (jnp.moveaxis(states, 1, 0), jnp.moveaxis(chunk_decay, 1, 0)))
    before = jnp.moveaxis(before, 0, 1)             # (b, c, g, r, p, n)
    y = y + _dot("bcign,bcgrpn->bcigrp", c, before.astype(dtype)) \
        * jnp.moveaxis(jnp.exp(log_a), -1, 2)[..., None]
    return y.reshape(batch, seq + pad, heads, p)[:, :seq].astype(dtype)

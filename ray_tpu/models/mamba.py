"""The Mamba-2 mixer (Dao and Gu 2024) as the published hybrid models run it
(``GraniteMoeHybridMambaLayer``): a layer's token mixer in place of attention,
inside ``models/llama.py``'s block.  With ``n`` the block's normed input:

    z | xBC | dt = W_in n
    xBC = silu(causal depthwise conv1d(xBC, width d_conv) + b)    (no other bias)
    X | B | C = xBC                      X: heads x d_head; B, C: groups x d_state
    dt = softplus(dt + dt_bias);  a_t = exp(dt_t * A),  A = -exp(A_log)
    h_t = a_t h_{t-1} + dt_t X_t B_t^T;  y_t = h_t C_t + D X_t
    out = W_out RMSNorm_w(y * silu(z))   the norm after the gate, over each
                                         group's heads apart (one group: all)

The recurrence is ``ops/ssd.py``'s chunked scan, two Pallas kernels, the
convolution with its bias and silu ``ops/conv.py::conv_silu``'s two (here as
in ``Mamba1Mixer`` and ``models/kda.py``), and the gated norm a group
``ops/gated_norm.py::gated_rms_norm``'s two, for every group count.
``causal_conv`` below, the same convolution as shifted multiply-adds in
plain XLA, is ``conv_silu``'s reference and its fallback, and what
``gated_short_conv`` is built on.  The
scopes ``conv``, ``ssd`` and ``gated_norm`` and the ``Dense`` children
``in_proj`` and ``out_proj`` are what the benchmark's per-layer metrics read.

Beside it the Mamba-1 mixer (Gu and Dao 2023) as SambaY's decoder-hybrid-decoder
runs it (``Mamba1Mixer``; arXiv:2507.06607), ``d = expand * d_model`` channels
of ``N`` states each, ``R`` the step sizes' rank:

    [u ; z] = W_in n
    u = silu(causal depthwise conv1d(u, width d_conv) + b)
    [r ; B ; C] = W_x u                  r: R; B, C: N, shared by all channels
    delta = softplus(W_dt r + dt_bias)   a step size a CHANNEL, float32
    h_t = exp(delta_t A) h_{t-1} + delta_t B_t u_t;  y_t = h_t C_t + D u_t
    out = W_out (y * silu(z))            A = -exp(A_log), a decay a channel a state

over ``ops/selective_scan.py``, and the gated memory unit that reads one such
layer's ``y`` in later layers (``GatedMemoryUnit``: ``W_out (silu(W_in n) *
y)``).  Their scopes: ``conv``, ``dt`` (the step sizes), ``scan``, ``gate``.
"""

from __future__ import annotations

import functools
import math
from typing import Any

import flax.linen as nn
import jax
import jax.numpy as jnp

from ray_tpu.ops.conv import conv_silu
from ray_tpu.ops.gated_norm import gated_rms_norm
from ray_tpu.ops.selective_scan import selective_scan
from ray_tpu.ops.ssd import ssd_scan
from ray_tpu.parallel.mesh import ambient_mesh
from ray_tpu.parallel.sharding import constrain_residual


def _taps(x, width: int, ahead: bool = False):
    """``x`` (B, S, C) as each tap of a causal convolution ``width`` wide
    reads it, tap by tap: ``x_{t - (width-1) + k}`` for ``k`` = 0 .. width-1,
    zeros before the sequence's start; ``ahead``: as the convolution's
    transpose reads it, ``x_{t + (width-1) - k}``, zeros past its end."""
    seq = x.shape[1]
    padded = jnp.pad(x, ((0, 0), (0, width - 1) if ahead else (width - 1, 0),
                         (0, 0)))
    for k in range(width):
        at = width - 1 - k if ahead else k
        yield padded[:, at:at + seq]


def _weighted(taps, kernel):
    return sum(tap * kernel[k] for k, tap in enumerate(taps))


def causal_conv(x, kernel, bias=None):
    """Depthwise causal convolution over the sequence, as shifted
    multiply-adds: ``y_t = bias + sum_k kernel[k] * x_{t - (width-1) + k}``,
    positions before the sequence's start reading zero.  ``x``: (B, S, C);
    ``kernel``: (width, C); no ``bias``: none is added."""
    out = _weighted(_taps(x, kernel.shape[0]), kernel)
    return out if bias is None else bias + out


@jax.custom_vjp
def gated_short_conv(b, c, u, kernel):
    """``c * causal_conv(b * u, kernel)``: LFM2's gate, short convolution
    and gate, (B, S, D) each and ``kernel`` (width, D).  The backward is
    written out, the convolution's transpose as the same shifted
    multiply-adds over the cotangent: the whole step read 0.65% faster with
    it than by reverse mode through the padded slices (``PERF.md``, PR 41)."""
    return c * causal_conv(b * u, kernel)


def _gated_short_conv_fwd(b, c, u, kernel):
    return gated_short_conv(b, c, u, kernel), (b, c, u, kernel)


def _gated_short_conv_bwd(res, g):
    b, c, u, kernel = res
    width = kernel.shape[0]
    taps = list(_taps(b * u, width))
    d_m = g * c
    d_bu = _weighted(_taps(d_m, width, ahead=True), kernel)
    d_kernel = jnp.stack([
        jnp.sum(d_m.astype(jnp.float32) * tap.astype(jnp.float32),
                axis=(0, 1)) for tap in taps])
    d_c = g * _weighted(taps, kernel)
    return d_bu * u, d_c, d_bu * b, d_kernel.astype(kernel.dtype)


gated_short_conv.defvjp(_gated_short_conv_fwd, _gated_short_conv_bwd)


def _dt_bias_init(key, shape, dtype=jnp.float32):
    """The published Mamba-2 convention: ``softplus(dt_bias)`` log-uniform in
    [0.001, 0.1], with a floor of 1e-4."""
    low, high = math.log(1e-3), math.log(0.1)
    dt = jnp.maximum(jnp.exp(jax.random.uniform(key, shape, dtype, low, high)),
                     1e-4)
    return dt + jnp.log(-jnp.expm1(-dt))    # softplus's inverse


def _conv_init(width: int):
    """PyTorch's ``Conv1d`` default for a depthwise kernel and its bias:
    uniform in +-1/sqrt(width)."""
    bound = 1.0 / math.sqrt(width)

    def init(key, shape, dtype=jnp.float32):
        return jax.random.uniform(key, shape, dtype, -bound, bound)
    return init


def _a_log_init(key, shape, dtype=jnp.float32):
    """``A = -exp(A_log)`` with ``-A`` uniform in [1, 16]."""
    return jnp.log(jax.random.uniform(key, shape, dtype, 1.0, 16.0))


class Mamba2Mixer(nn.Module):
    config: Any     # LlamaConfig: d_model, dtype, rms_eps and the mamba_* sizes

    @nn.compact
    def __call__(self, x):
        cfg = self.config
        heads, p = cfg.mamba_n_heads, cfg.mamba_d_head
        groups, n = cfg.mamba_n_groups, cfg.mamba_d_state
        inner, bc = heads * p, groups * n
        batch, seq, _ = x.shape
        z, xbc, dt = jnp.split(
            nn.Dense(2 * inner + 2 * bc + heads, use_bias=False,
                     dtype=cfg.dtype, name="in_proj")(x),
            [inner, 2 * inner + 2 * bc], axis=-1)
        conv_init = _conv_init(cfg.mamba_d_conv)
        kernel = self.param("conv_kernel", conv_init,
                            (cfg.mamba_d_conv, inner + 2 * bc))
        bias = self.param("conv_bias", conv_init, (inner + 2 * bc,))
        dt_bias = self.param("dt_bias", _dt_bias_init, (heads,))
        a_log = self.param("A_log", _a_log_init, (heads,))
        skip = self.param("D", nn.initializers.ones, (heads,))
        with jax.named_scope("conv"):
            xbc = conv_silu(xbc, kernel.astype(cfg.dtype),
                            bias.astype(cfg.dtype))
        xs, b, c = jnp.split(xbc, [inner, inner + bc], axis=-1)
        xs = xs.reshape(batch, seq, heads, p)
        with jax.named_scope("ssd"):
            # the step sizes and the decay rates: float32 from here on
            y = ssd_scan(xs, jax.nn.softplus(dt.astype(jnp.float32) + dt_bias),
                         -jnp.exp(a_log.astype(jnp.float32)),
                         b.reshape(batch, seq, groups, n),
                         c.reshape(batch, seq, groups, n),
                         chunk=cfg.mamba_chunk)
            y = y + xs * skip.astype(cfg.dtype)[:, None]
        scale = self.param("norm_scale", nn.initializers.ones, (inner,))
        with jax.named_scope("gated_norm"):
            y = gated_rms_norm(y.reshape(batch, seq, inner), z, scale,
                               groups, cfg.rms_eps)
        return nn.Dense(cfg.d_model, use_bias=False, dtype=cfg.dtype,
                        name="out_proj")(y)


def _a_log_states(key, shape, dtype=jnp.float32):
    """Mamba-1's start: ``-A`` = 1 .. N along a channel's states."""
    return jnp.broadcast_to(jnp.log(jnp.arange(1, shape[1] + 1, dtype=dtype)),
                            shape)


class SplitDense(nn.Module):
    """``x -> (x W_0, .., x W_{parts-1})``, the kernel (in, parts, out)
    holding the matrices side by side: one ``Dense`` to ``parts * out`` whose
    parts a ``tp`` axis cuts each by its own columns (``P("fsdp", None,
    "tp")``), and whose results are arrays of their own, never joined or
    split in HBM, forward or backward."""
    features: int
    dtype: Any = jnp.bfloat16
    parts: int = 3

    @nn.compact
    def __call__(self, x):
        kernel = self.param(
            "kernel", nn.initializers.lecun_normal(in_axis=0, out_axis=(1, 2)),
            (x.shape[-1], self.parts, self.features),
            jnp.float32).astype(self.dtype)
        x = x.astype(self.dtype)
        return tuple(jnp.einsum("...e,ed->...d", x, kernel[:, i])
                     for i in range(self.parts))


def mamba1_sizes(cfg):
    """(channels, the step sizes' rank) of a ``mamba1`` layer: two channels a
    model dimension, the rank a sixteenth of it, rounded up."""
    return 2 * cfg.d_model, -(-cfg.d_model // 16)


class Mamba1Mixer(nn.Module):
    """-> (the mixer's output, ``y`` before its gate: what a layer that hands
    its scan on gives the gated memory units)."""
    config: Any     # LlamaConfig: d_model, dtype, mamba_d_state, mamba_d_conv,
    #                 mamba_chunk (the scan's block of positions)

    @nn.compact
    def __call__(self, x):
        cfg = self.config
        (d, rank), n = mamba1_sizes(cfg), cfg.mamba_d_state
        mesh = ambient_mesh()
        if mesh is not None and mesh.shape.get("sp", 1) > 1:
            raise NotImplementedError(
                "a sequence sharded on 'sp' has no 'mamba1' layer (the "
                "recurrence carries its state across every position)")

        def by_channel(t):  # between a column- and a row-parallel projection
            return constrain_residual(t, channels="tp")

        u, z = (by_channel(part) for part in
                SplitDense(d, cfg.dtype, 2, name="in_proj")(x))
        conv_init = _conv_init(cfg.mamba_d_conv)
        kernel = self.param("conv_kernel", conv_init, (cfg.mamba_d_conv, d))
        bias = self.param("conv_bias", conv_init, (d,))
        with jax.named_scope("conv"):
            u = conv_silu(u, kernel.astype(cfg.dtype), bias.astype(cfg.dtype))
        r, b, c = jnp.split(
            nn.Dense(rank + 2 * n, use_bias=False, dtype=cfg.dtype,
                     name="x_proj")(u), [rank, rank + n], axis=-1)
        # the step sizes leave their matmul in float32 and stay so
        dt = by_channel(nn.Dense(
            d, use_bias=False, dtype=cfg.dtype, name="dt_proj",
            dot_general=functools.partial(
                jax.lax.dot_general,
                preferred_element_type=jnp.float32))(r))
        dt_bias = self.param("dt_bias", _dt_bias_init, (d,))
        a_log = self.param("A_log", _a_log_states, (d, n))
        skip = self.param("D", nn.initializers.ones, (d,))
        with jax.named_scope("dt"):
            delta = jax.nn.softplus(dt + dt_bias)
        with jax.named_scope("scan"):
            y = selective_scan(u, delta, -jnp.exp(a_log.astype(jnp.float32)),
                               b, c, skip, block=cfg.mamba_chunk)
        with jax.named_scope("gate"):
            gated = y * jax.nn.silu(z)
        return nn.Dense(cfg.d_model, use_bias=False, dtype=cfg.dtype,
                        name="out_proj")(gated), y


class GatedMemoryUnit(nn.Module):
    """SambaY's gated memory unit: the layer's own input gates, channel by
    channel, the scan output ``m`` that an earlier ``mamba1`` layer handed on
    for the same position; no token is mixed here."""
    config: Any

    @nn.compact
    def __call__(self, x, m):
        cfg = self.config
        gate = constrain_residual(
            nn.Dense(m.shape[-1], use_bias=False, dtype=cfg.dtype,
                     name="in_proj")(x), channels="tp")
        with jax.named_scope("gate"):
            gated = jax.nn.silu(gate) * m
        return nn.Dense(cfg.d_model, use_bias=False, dtype=cfg.dtype,
                        name="out_proj")(gated)

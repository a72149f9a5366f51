"""The Kimi Delta Attention mixer (Kimi Linear, arXiv:2510.26692): a layer's
token mixer in place of attention, inside ``models/llama.py``'s block.  With
``n`` the block's normed input, ``H`` heads of ``d`` and per head ``h``:

    q = l2norm(silu(conv(Wq n)))_h / sqrt(d)    k = l2norm(silu(conv(Wk n)))_h
    v = silu(conv(Wv n))_h           conv: causal, depthwise, no bias, one kernel each
    g = -exp(A_log_h) * softplus((Wf_b Wf_a n)_h + dt_bias_h)     a log-decay a CHANNEL
    b = sigmoid(Wb n)_h                                           a scalar a head
    S_t = (I - b_t k_t k_t^T) Diag(e^(g_t)) S_{t-1} + b_t k_t v_t^T;   o_t = S_t^T q_t
    out = Wo [RMSNorm_d(o)_h * sigmoid((Wg_b Wg_a n + bias)_h)]

The recurrence is ``ops/kda.py``'s chunked scan (three Mosaic kernels: the
chunks' solve, which a rematerialised block keeps by name, the forward and
the backward) and
each convolution with its silu — for q and k with the head's unit norm and
q's ``1 / sqrt(d)`` — one call of ``ops/conv.py::conv_silu`` (a forward and a
backward Mosaic kernel; ``models/mamba.py::causal_conv``'s shifted
multiply-adds in plain XLA where a head is not 128 columns).  The ``Dense``
children
``q_proj``, ``k_proj``, ``v_proj``, ``f_a``, ``f_b``, ``g_a``, ``g_b``,
``b_proj``, ``o_proj``, the norm ``o_norm`` and the scopes ``conv`` (the
three convolutions, silu and the two unit norms), ``gate`` (softplus and
``A_log``, float32 from there on), ``scan`` and ``out_gate`` are what the
benchmark's per-layer metrics read.  Under ``tp`` everything between the
projections is a head's own: the projections, the convolutions' kernels,
``A_log`` and ``dt_bias`` are cut by head (``parallel/sharding.py``).
``HeadNorm`` is also differential attention's ``sub_norm``
(``models/llama.py``) and the Gated DeltaNet mixer's ``o_norm``
(``models/gdn.py``); ``_unit`` is ``ops/conv.py``'s ``jax.numpy`` unit norm,
for either mixer.
"""

from __future__ import annotations

from typing import Any

import flax.linen as nn
import jax
import jax.numpy as jnp

from ray_tpu.models.mamba import _a_log_init, _conv_init, _dt_bias_init
from ray_tpu.ops.conv import L2_EPS, conv_silu
from ray_tpu.ops.kda import kda_scan
from ray_tpu.parallel.mesh import ambient_mesh
from ray_tpu.parallel.sharding import constrain_residual


def _of_head(width: int, heads: int):
    """(width, heads): 1 where a column is the head's."""
    return (jnp.arange(width)[:, None] // (width // heads)
            == jnp.arange(heads)[None, :]).astype(jnp.float32)


def _head_sums(t, heads: int):
    """(B, S, H * d) float32 -> each head's sum, (B, S, H).  A matmul with a
    0 / 1 matrix, exact at ``HIGHEST``, whose prologue takes what makes
    ``t``: as a reshape to (B, S, H, d) the float32 array changed its tiling
    in a pass of its own, 6.5 ms a layer on the chip (``PERF.md``, PR 53)."""
    return jnp.einsum("bsc,ch->bsh", t, _of_head(t.shape[-1], heads),
                      precision=jax.lax.Precision.HIGHEST)


def _widened(r, width: int):
    """(B, S, H) -> (B, S, H * d): each head's number on its columns."""
    return jnp.einsum("bsh,ch->bsc", r, _of_head(width, r.shape[-1]),
                      precision=jax.lax.Precision.HIGHEST)


def _unit(x, heads: int, scale: float = 1.0):
    """Each head's columns of (B, S, H * d) at unit length, times ``scale``:
    the statistic and the product in float32.  ``ops/conv.py::conv_silu``'s
    epilogue where its kernels run; its ``jax.numpy`` form calls this."""
    t = x.astype(jnp.float32)
    r = jax.lax.rsqrt(_head_sums(t * t, heads) + L2_EPS) * scale
    return (t * _widened(r, x.shape[-1])).astype(x.dtype)


class HeadNorm(nn.Module):
    """RMSNorm over each head's ``d`` columns of (B, S, H * d) with one
    learned scale of ``d`` for all heads, under the path and with the shape
    and start ``nn.RMSNorm`` gives its own (``<name>/scale``, ones)."""
    heads: int
    epsilon: float
    dtype: Any

    @nn.compact
    def __call__(self, x):
        d = x.shape[-1] // self.heads
        scale = self.param("scale", nn.initializers.ones, (d,), jnp.float32)
        t = x.astype(jnp.float32)
        r = jax.lax.rsqrt(_head_sums(t * t, self.heads) / d + self.epsilon)
        return (t * _widened(r, x.shape[-1]) * jnp.tile(scale, self.heads)
                ).astype(self.dtype)


class KDAMixer(nn.Module):
    config: Any     # LlamaConfig: d_model, dtype, rms_eps and the kda_* sizes

    @nn.compact
    def __call__(self, x):
        cfg = self.config
        heads, d = cfg.kda_n_heads, cfg.kda_head_dim
        rank = d        # the two low-rank maps go through a head's width
        inner = heads * d
        mesh = ambient_mesh()
        if mesh is not None and mesh.shape.get("sp", 1) > 1:
            raise NotImplementedError(
                "a sequence sharded on 'sp' has no 'kda' layer (the "
                "recurrence carries its state across every position)")

        def dense(width, name, bias=False):
            return nn.Dense(width, use_bias=bias, dtype=cfg.dtype, name=name)

        def by_head(t):     # between a column- and a row-parallel projection
            return constrain_residual(t, channels="tp")

        q, k, v = (by_head(dense(inner, name)(x))
                   for name in ("q_proj", "k_proj", "v_proj"))
        conv_init = _conv_init(cfg.kda_d_conv)
        kernels = [self.param(f"{name}_conv", conv_init,
                              (cfg.kda_d_conv, inner)) for name in "qkv"]
        with jax.named_scope("conv"):
            q_conv, k_conv, v_conv = (t.astype(cfg.dtype) for t in kernels)
            q = conv_silu(q, q_conv, unit_heads=heads, scale=d ** -0.5)
            k = conv_silu(k, k_conv, unit_heads=heads)
            v = conv_silu(v, v_conv)
        a_log = self.param("A_log", _a_log_init, (heads,))
        dt_bias = self.param("dt_bias", _dt_bias_init, (inner,))
        decay = by_head(dense(inner, "f_b")(dense(rank, "f_a")(x)))
        beta = dense(heads, "b_proj")(x)
        with jax.named_scope("gate"):
            # the log-decays and the write strengths: float32 from here on
            rate = jnp.repeat(-jnp.exp(a_log.astype(jnp.float32)), d)
            g = rate * jax.nn.softplus(decay.astype(jnp.float32) + dt_bias)
            beta = jax.nn.sigmoid(beta.astype(jnp.float32))
        with jax.named_scope("scan"):
            o = kda_scan(q, k, v, g, beta, chunk=cfg.kda_chunk)
        gate = by_head(dense(inner, "g_b", bias=True)(dense(rank, "g_a")(x)))
        o = HeadNorm(heads, cfg.rms_eps, cfg.dtype, name="o_norm")(o)
        with jax.named_scope("out_gate"):
            o = o * jax.nn.sigmoid(gate.astype(jnp.float32)).astype(cfg.dtype)
        return dense(cfg.d_model, "o_proj")(o)

"""The Gated DeltaNet mixer (Yang et al., arXiv:2412.06464) as Qwen3-Next
runs it: a layer's token mixer in place of attention, inside
``models/llama.py``'s block.  With ``n`` the block's normed input, ``K`` key
heads and ``H = r K`` value heads of ``d``, value head ``h`` reading key head
``h // r``:

    [q | k | v | z] = W_qkvz n      [b | a] = W_ba n          each a key head's own columns
    q = l2norm(silu(conv(q)))_j / sqrt(d)    k = l2norm(silu(conv(k)))_j    v = silu(conv(v))_h
          conv: causal, depthwise, no bias, ONE kernel over the channels of q, k and v
    g = -exp(A_log_h) * softplus(a_h + dt_bias_h)     ONE log-decay a value head
    beta = sigmoid(b_h)
    S_t = (I - beta_t k_t k_t^T) e^(g_t) S_{t-1} + beta_t k_t v_t^T;   o_t = S_t^T q_t
    out = W_o [RMSNorm_d(o)_h * w * silu(z_h)]        the norm BEFORE the gate, silu

The recurrence is ``ops/gdn.py``'s chunked scan (three Mosaic kernels: the
chunks' solve, which a rematerialised block keeps by name, the forward and
the backward; ``g`` stays one number a head and ``q``, ``k`` at their key
heads), each convolution with its silu — for q and k with the head's unit
norm — a call of ``ops/conv.py::conv_silu`` on its own columns of the one
kernel.  ``q``'s ``1 / sqrt(d)`` multiplies the scan's output, which is linear
in ``q``, in the output norm's own pass.  The gate is not
``ops/gated_norm.py``'s, which is Mamba-2's gate-then-norm.

``in_proj_qkvz``, ``in_proj_ba`` and ``conv_kernel`` hold their columns a key
head at a time, ``[q | k | v .. | z ..]`` of key head ``j`` side by side as
the published checkpoint has them (``KeyHeadDense``: the kernel (in, K,
columns a key head)), so that a ``tp`` axis cuts them between key heads
(``parallel/sharding.py``) and everything between the projections is a
device's own; each part leaves as an array of its own, (B, S, K * width),
never split in HBM.  The children ``in_proj_qkvz``, ``in_proj_ba``,
``out_proj``, the norm ``o_norm`` and the scopes ``conv`` (the three
convolutions, silu and the two unit norms), ``gate`` (softplus and ``A_log``,
float32 from there on), ``scan`` and ``out_gate`` are what the benchmark's
per-layer metrics read.  ``A_log`` and ``dt_bias`` start as
``models/mamba.py`` starts Mamba-2's (``-A`` in [1, 16], a step log-uniform
in [1e-3, 0.1]), not as the published code does (``-A`` uniform in (0, 16],
``dt_bias`` 1: a decay of ``e^-10`` a position at the median, under which a
state holds nothing, ``o_t`` is ``beta_t (q_t . k_t) v_t`` and the output
norm leaves of it the SIGN of ``q_t . k_t`` — a function that bf16's
rounding of q and k flips: at the toy's widths the bf16 program's gradient
norm then lies 19% from the float32 reference's, against 1.1% from these
starts; the float32 scan holds either).
"""

from __future__ import annotations

from typing import Any, Tuple

import flax.linen as nn
import jax
import jax.numpy as jnp

from ray_tpu.models.kda import HeadNorm
from ray_tpu.models.mamba import _a_log_init, _conv_init, _dt_bias_init
from ray_tpu.ops.conv import conv_silu
from ray_tpu.ops.gdn import gdn_scan
from ray_tpu.parallel.mesh import ambient_mesh
from ray_tpu.parallel.sharding import constrain_residual


def _parts(t, widths: Tuple[int, ...]):
    """(.., K, sum(widths)) -> each part's columns of every key head, (.., K
    * width)."""
    out, at = [], 0
    for width in widths:
        out.append(t[..., at:at + width].reshape(*t.shape[:-2], -1))
        at += width
    return out


class KeyHeadDense(nn.Module):
    """``x -> (x W_0, .., x W_n)``: one projection whose kernel, (in, key
    heads, sum(widths)), holds every part's columns of a key head side by
    side.  Each part is a matmul of its own slice of the kernel and an array
    of its own: nothing is split or joined in HBM but the weights."""
    key_heads: int
    widths: Tuple[int, ...]
    dtype: Any = jnp.bfloat16

    @nn.compact
    def __call__(self, x):
        kernel = self.param(
            "kernel", nn.initializers.lecun_normal(in_axis=0, out_axis=(1, 2)),
            (x.shape[-1], self.key_heads, sum(self.widths)),
            jnp.float32).astype(self.dtype)
        x = x.astype(self.dtype)
        return [jnp.einsum("...e,ed->...d", x, part)
                for part in _parts(kernel, self.widths)]


class GDNMixer(nn.Module):
    config: Any     # LlamaConfig: d_model, dtype, rms_eps, gdn_key_heads and
    #                 the kda_* sizes (value heads, head width, taps, chunk)

    @nn.compact
    def __call__(self, x):
        cfg = self.config
        keys, heads, d = cfg.gdn_key_heads, cfg.kda_n_heads, cfg.kda_head_dim
        mesh = ambient_mesh()
        if mesh is not None and mesh.shape.get("sp", 1) > 1:
            raise NotImplementedError(
                "a sequence sharded on 'sp' has no 'gdn' layer (the "
                "recurrence carries its state across every position)")
        if not keys or heads % keys:
            raise ValueError(f"a 'gdn' layer's {heads} value heads over "
                             f"{keys} key heads")
        tp = mesh.shape.get("tp", 1) if mesh is not None else 1
        if keys % tp:
            raise ValueError(f"a 'gdn' layer's {keys} key heads over tp={tp}")
        r = heads // keys

        def by_head(t):     # between a column- and a row-parallel projection
            return constrain_residual(t, channels="tp")

        q, k, v, z = map(by_head, KeyHeadDense(
            keys, (d, d, r * d, r * d), cfg.dtype, name="in_proj_qkvz")(x))
        b, a = KeyHeadDense(keys, (r, r), cfg.dtype, name="in_proj_ba")(x)
        kernel = self.param("conv_kernel", _conv_init(cfg.kda_d_conv),
                            (cfg.kda_d_conv, keys, (2 + r) * d))
        with jax.named_scope("conv"):
            q_conv, k_conv, v_conv = _parts(kernel.astype(cfg.dtype),
                                            (d, d, r * d))
            q = conv_silu(q, q_conv, unit_heads=keys)
            k = conv_silu(k, k_conv, unit_heads=keys)
            v = conv_silu(v, v_conv)
        a_log = self.param("A_log", _a_log_init, (heads,))
        dt_bias = self.param("dt_bias", _dt_bias_init, (heads,))
        with jax.named_scope("gate"):
            # the log-decays and the write strengths: float32 from here on
            g = -jnp.exp(a_log.astype(jnp.float32)) * jax.nn.softplus(
                a.astype(jnp.float32) + dt_bias)
            beta = jax.nn.sigmoid(b.astype(jnp.float32))
        with jax.named_scope("scan"):
            o = gdn_scan(q, k, v, g, beta, chunk=cfg.kda_chunk)
        # (q's 1 / sqrt(d): the scan is linear in q)
        o = HeadNorm(heads, cfg.rms_eps, cfg.dtype, name="o_norm")(
            o.astype(jnp.float32) * d ** -0.5)
        with jax.named_scope("out_gate"):
            o = o * jax.nn.silu(z.astype(jnp.float32)).astype(cfg.dtype)
        return nn.Dense(cfg.d_model, use_bias=False, dtype=cfg.dtype,
                        name="out_proj")(o)

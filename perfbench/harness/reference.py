"""The plain reference: forward, loss and gradients in straightforward
float32 ``jax.numpy``.

No kernel, no flax module, no remat, no sharding rule; every matmul at
``jax.default_matmul_precision("highest")`` (on a TPU a float32 matmul is
otherwise computed in bf16 passes).  It reads the program's parameter tree by
name, so program and reference see the same weights.  The pieces every
architecture is made of are here; each family's forward,
``families/<family>.py::logits``, is made of them and follows the published
architecture.  Where the program departs from it (``program_departures`` in
the configuration file) the reference follows the program, because this check
is about precision and dropped terms, and the departure is listed for a later
PR.
"""

from __future__ import annotations

from typing import Any, Dict

import jax
import jax.numpy as jnp

from perfbench.harness import families

NEG = -1e30


def causal_attention(q, k, v):
    """q: (B, KV, R, S, D) — R query heads share each of the KV key/value
    heads; k, v: (B, KV, S, D).  Plain softmax(QK^T / sqrt(D)) V."""
    s = q.shape[-2]
    scores = jnp.einsum("bgrqd,bgkd->bgrqk", q, k) * q.shape[-1] ** -0.5
    mask = jnp.arange(s)[:, None] >= jnp.arange(s)[None, :]
    weights = jax.nn.softmax(jnp.where(mask, scores, NEG), axis=-1)
    return jnp.einsum("bgrqk,bgkd->bgrqd", weights, v)


def layer_norm(x, p, eps):
    mean = x.mean(-1, keepdims=True)
    var = ((x - mean) ** 2).mean(-1, keepdims=True)
    return (x - mean) * jax.lax.rsqrt(var + eps) * p["scale"] + p["bias"]


def rms_norm(x, p, eps):
    return x * jax.lax.rsqrt((x * x).mean(-1, keepdims=True) + eps) * p["scale"]


def dense(x, p):
    y = x @ p["kernel"]
    return y + p["bias"] if "bias" in p else y


def heads(x, n):
    b, s, _ = x.shape
    return x.reshape(b, s, n, -1).transpose(0, 2, 1, 3)


def merge(x):
    b, g, r, s, d = x.shape
    return x.transpose(0, 3, 1, 2, 4).reshape(b, s, g * r * d)


def rope(x, theta):
    """Rotate-half convention (the published Hugging Face one): the pair
    ``(x_i, x_{i + D/2})`` turns by ``position * theta ** (-2i / D)``."""
    s, d = x.shape[-2], x.shape[-1]
    inv = 1.0 / theta ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    angle = jnp.arange(s, dtype=jnp.float32)[:, None] * inv[None, :]
    x1, x2 = jnp.split(x, 2, axis=-1)
    return jnp.concatenate([x1 * jnp.cos(angle) - x2 * jnp.sin(angle),
                            x2 * jnp.cos(angle) + x1 * jnp.sin(angle)], -1)


def logits_loss_gradnorm(params, ids, targets, config: Dict[str, Any]):
    """Float32 logits ``(B, S, vocab)``, the mean next-token cross entropy
    over all of them, and the global L2 norm of its gradient."""
    forward = families.of(config).logits

    def loss_of(p):
        logits = forward(p, ids, config)
        logp = jax.nn.log_softmax(logits, axis=-1)
        nll = -jnp.take_along_axis(logp, targets[..., None], axis=-1)
        return nll.mean(), logits

    with jax.default_matmul_precision("highest"):
        params = jax.tree_util.tree_map(
            lambda a: a.astype(jnp.float32), params)
        (loss, logits), grads = jax.value_and_grad(loss_of, has_aux=True)(
            params)
    return logits, loss, global_norm(grads)


def global_norm(tree: Any):
    return jnp.sqrt(sum(jnp.sum(jnp.square(g.astype(jnp.float32)))
                        for g in jax.tree_util.tree_leaves(tree)))

"""``models/gpt2.py::lm_loss`` (ISSUE 28): the cross entropy as one
``custom_vjp`` that reads the logits in the dtype they arrive in and keeps
nothing vocabulary-sized in float32, against the plain formula it replaced.

CPU only: values, gradients and what the backward is handed — never a time.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ray_tpu.models.gpt2 import (NEG_INF, lm_loss, mask_vocab_padding,
                                 padded_vocab)

VOCAB = 250          # padded to 256: six pad columns at NEG_INF
SHAPES = {"2d": (24,), "3d": (3, 8)}
# the gradient is rounded once, to the logits' dtype: bf16 keeps 8 bits
GRAD_RTOL = {"bfloat16": 2.0 ** -7, "float32": 1e-5}


def plain_loss(logits, targets, mask=None):
    """What ``lm_loss`` was before it had rules of its own."""
    logp = jax.nn.log_softmax(logits.astype(jnp.float32), axis=-1)
    ll = jnp.take_along_axis(logp, targets[..., None], axis=-1)[..., 0]
    if mask is None:
        return -jnp.mean(ll)
    mask = mask.astype(jnp.float32)
    return -jnp.sum(ll * mask) / jnp.maximum(jnp.sum(mask), 1.0)


def _case(shape, dtype, mask_kind):
    """(logits with their pad columns masked, targets, mask) from one seed."""
    rng = np.random.default_rng(28)
    rows = SHAPES[shape]
    logits = mask_vocab_padding(
        jnp.asarray(4.0 * rng.standard_normal(rows + (padded_vocab(VOCAB),)),
                    dtype), VOCAB)
    targets = jnp.asarray(rng.integers(0, VOCAB, rows), jnp.int32)
    if mask_kind == "none":
        return logits, targets, None
    mask = np.zeros(rows, np.float32)
    if mask_kind == "prefix":   # the reference check's kind: leading positions
        mask[..., : rows[-1] // 2] = 1.0
    return logits, targets, jnp.asarray(mask)


@pytest.mark.parametrize("mask_kind", ["none", "prefix", "zero"])
@pytest.mark.parametrize("dtype", ["bfloat16", "float32"])
@pytest.mark.parametrize("shape", ["2d", "3d"])
def test_value_and_gradient_are_the_plain_formulas(shape, dtype, mask_kind):
    logits, targets, mask = _case(shape, dtype, mask_kind)
    got, got_grad = jax.jit(jax.value_and_grad(lm_loss))(logits, targets, mask)
    want, want_grad = jax.value_and_grad(plain_loss)(logits, targets, mask)
    assert got.dtype == jnp.float32 and got_grad.dtype == logits.dtype
    np.testing.assert_allclose(got, want, rtol=2e-6, atol=0)
    got_grad, want_grad = (np.asarray(g, np.float32)
                           for g in (got_grad, want_grad))
    assert np.isfinite(got_grad).all()
    np.testing.assert_allclose(got_grad, want_grad, rtol=GRAD_RTOL[dtype],
                               atol=1e-9)
    # pad columns sit at NEG_INF: no probability, so no gradient
    assert float(logits[..., VOCAB:].max()) < 0.5 * NEG_INF
    assert not got_grad[..., VOCAB:].any()
    if mask_kind == "zero":
        assert float(got) == 0.0 and not got_grad.any()
    else:
        assert got_grad[..., :VOCAB].any()
    if mask_kind == "prefix":   # a row the mask drops gets no gradient
        assert not got_grad[..., SHAPES[shape][-1] // 2:, :].any()


@pytest.mark.parametrize("dtype", ["bfloat16", "float32"])
def test_vocabulary_split_over_tp(dtype):
    """Under a mesh the rules are plain ``jnp``: with the vocabulary split
    over ``tp`` and the rows over ``dp`` the two reductions cross shards, the
    target's logit is a select (no gather across shards), and the gradient
    comes back in the logits' layout."""
    logits, targets, mask = _case("3d", dtype, "prefix")
    mesh = Mesh(np.array(jax.devices()[:4]).reshape(1, 4), ("dp", "tp"))
    split = NamedSharding(mesh, P("dp", None, "tp"))
    rows = NamedSharding(mesh, P("dp", None))
    args = (jax.device_put(logits, split), jax.device_put(targets, rows),
            jax.device_put(mask, rows))
    step = jax.jit(jax.value_and_grad(lm_loss),
                   in_shardings=(split, rows, rows))
    got, got_grad = step(*args)
    want, want_grad = jax.value_and_grad(plain_loss)(logits, targets, mask)
    assert got_grad.sharding.is_equivalent_to(split, got_grad.ndim)
    np.testing.assert_allclose(got, want, rtol=2e-6)
    np.testing.assert_allclose(np.asarray(got_grad, np.float32),
                               np.asarray(want_grad, np.float32),
                               rtol=GRAD_RTOL[dtype], atol=1e-9)
    assert "gather" not in step.lower(*args).compile().as_text().replace(
        "all-gather", "")


def _vocab_sized_float32(residuals, vocab):
    return [aval for aval, _ in residuals
            if aval.dtype == jnp.float32 and vocab in aval.shape]


@pytest.mark.parametrize("mask_kind", ["none", "prefix"])
def test_the_backward_is_handed_nothing_vocabulary_sized_in_float32(mask_kind):
    from jax._src.ad_checkpoint import saved_residuals

    logits, targets, mask = _case("2d", "bfloat16", mask_kind)
    vocab = logits.shape[-1]
    kept = saved_residuals(lm_loss, logits, targets, mask)
    assert not _vocab_sized_float32(kept, vocab), kept
    # the logits as they came, and per-row float32 beside them
    assert any(aval.dtype == jnp.bfloat16 and aval.shape == logits.shape
               for aval, _ in kept)
    assert any(aval.dtype == jnp.float32 and aval.shape == targets.shape
               for aval, _ in kept)
    # the yardstick sees what it is meant to: the plain formula keeps one
    assert _vocab_sized_float32(
        saved_residuals(plain_loss, logits, targets, mask), vocab)

"""Mamba-1's selective scan (``ops/selective_scan.py``) on the CPU in float32:
(a) both forms under their one differentiation rule — the blocked
``jax.numpy`` form and the two Pallas kernels under the interpreter — against
the recurrence written position by position, outputs and every gradient (u,
the step sizes, A, B, C, D), at one block, several, a last block that is
partial, a batch of two, at ``delta * A`` so large that a product of
decays underflows, and the kernels over two channel blocks of eight rows (the
grid's form at the published width); and no position depends on a later
input."""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_tpu.ops.selective_scan import selective_scan, selective_scan_xla

FORMS = {"xla": selective_scan_xla, "kernels": selective_scan}
D, N = 256, 16


def position_by_position(u, delta, a, b, c, skip):
    """``h_t = exp(delta_t A) h_{t-1} + delta_t B_t u_t``, ``y_t = h_t C_t +
    D u_t``, one position at a time."""
    def step(h, at):
        u_t, d_t, b_t, c_t = at
        h = jnp.exp(d_t[..., None] * a) * h \
            + (d_t * u_t)[..., None] * b_t[:, None, :]
        return h, jnp.einsum("bdn,bn->bd", h, c_t) + skip * u_t

    _, y = jax.lax.scan(step, jnp.zeros((u.shape[0], *a.shape)),
                        tuple(jnp.moveaxis(t, 1, 0) for t in (u, delta, b, c)))
    return jnp.moveaxis(y, 0, 1)


def operands(batch: int, seq: int, rate: float = 1.0, seed: int = 0,
             d: int = D, n: int = N):
    keys = jax.random.split(jax.random.PRNGKey(seed), 7)
    return (jax.random.normal(keys[0], (batch, seq, d)),
            rate * jax.nn.softplus(jax.random.normal(keys[1], (batch, seq, d))),
            -jnp.exp(jax.random.normal(keys[2], (d, n))),
            jax.random.normal(keys[3], (batch, seq, n)),
            jax.random.normal(keys[4], (batch, seq, n)),
            jax.random.normal(keys[5], (d,))), \
        jax.random.normal(keys[6], (batch, seq, d))


@functools.lru_cache(maxsize=None)
def _with_grads(form: str, block: int):
    """(operands, the output's weights) -> (y, the six gradients), compiled
    once a shape: the cases that differ in their values alone share it."""
    f = functools.partial(FORMS[form], block=block) if form in FORMS \
        else position_by_position
    return jax.jit(lambda args, weight: (f(*args), jax.grad(
        lambda *x: jnp.sum(f(*x) * weight), argnums=range(6))(*args)))


def _both_ways(form: str, batch: int, seq: int, block: int, rate: float,
               d: int, n: int):
    given = operands(batch, seq, rate, d=d, n=n)
    return _with_grads(form, block)(*given), _with_grads("plain", 0)(*given)


# (at 256 channels the kernels' grid has one channel block, of two rows of
# 128 lanes; at 2,048 two blocks of eight rows, the form the published width's
# five have: a channel block's own part of the carried state and of its
# cotangent, dA a channel block, dB and dC summed over the channel blocks by
# revisiting their output block.  The kernels write a position's states out
# one by one, and 16 of them are three times the program to interpret that 4
# are: the cases at 256 channels have 4, the one at 2,048 the published 16)
@pytest.mark.parametrize("form,batch,seq,block,rate,d,n", [
    *((form, *case) for form in sorted(FORMS) for case in [
        (1, 16, 16, 1.0, D, 4), (2, 29, 8, 1.0, D, 4),
        (2, 29, 8, 40.0, D, 4)]),
    ("kernels", 2, 16, 8, 1.0, 2048, N)],
    ids=[*(f"{form}-{case}" for form in sorted(FORMS) for case in [
        "one-block", "four-blocks-the-last-partial-batch-2",
        "four-blocks-decays-underflow"]),
        "kernels-two-channel-blocks-of-eight-rows"])
def test_a_the_blocked_scan_is_the_recurrence(form, batch, seq, block, rate,
                                              d, n):
    """Outputs and all six gradients.  At ``rate`` 40 a step's ``delta * A``
    reaches the hundreds: every decay but a few is exactly zero, a product
    of them underflows, and nothing may be a quotient of such products."""
    (out, got), (plain_out, want) = _both_ways(form, batch, seq, block, rate,
                                               d, n)
    assert bool(jnp.all(jnp.isfinite(out)))
    scale = float(jnp.max(jnp.abs(plain_out)))
    np.testing.assert_allclose(out, plain_out, atol=2e-6 * scale)
    for name, g, w in zip("u delta A B C D".split(), got, want):
        assert bool(jnp.all(jnp.isfinite(g))), name
        np.testing.assert_allclose(
            g, w, atol=5e-6 * float(jnp.max(jnp.abs(w))), err_msg=name)


@pytest.mark.parametrize("form", sorted(FORMS))
def test_a_no_position_depends_on_a_later_input(form):
    """Every input changed from position 13 on: the outputs before it are
    bit for bit what they were, those from it on are not."""
    args, _ = operands(1, 24)
    other, _ = operands(1, 24, seed=1)
    later = tuple(jnp.concatenate([x[:, :13], y[:, 13:]], axis=1)
                  if x.ndim == 3 else x for x, y in zip(args, other))
    scan = jax.jit(functools.partial(FORMS[form], block=8))
    first, second = scan(*args), scan(*later)
    np.testing.assert_array_equal(first[:, :13], second[:, :13])
    assert float(jnp.max(jnp.abs(first[:, 13:] - second[:, 13:]))) > 0.1


def test_a_channels_that_are_no_whole_lanes_take_the_plain_form():
    """96 channels are no rows of 128: ``selective_scan`` is the ``jax.numpy``
    form there, and bf16 operands give a bf16 ``y`` from a float32 state."""
    args, _ = operands(1, 16)
    narrow = tuple(x[..., :96] if x.shape[-1] == D else x[:96]
                   if x.shape[0] == D else x for x in args)
    np.testing.assert_array_equal(selective_scan(*narrow, block=8),
                                  selective_scan_xla(*narrow, block=8))
    u, delta, a, b, c, skip = args
    half = selective_scan(u.astype(jnp.bfloat16), delta, a,
                          b.astype(jnp.bfloat16), c.astype(jnp.bfloat16),
                          skip, block=8)
    assert half.dtype == jnp.bfloat16
    want = position_by_position(*args)
    assert float(jnp.sqrt(jnp.sum((half - want) ** 2) / jnp.sum(want ** 2))
                 ) < 0.02

"""``ops/kda.py``'s chunked scan against Kimi Delta Attention's recurrence one
position at a time, in float32 on the CPU: outputs and the gradient of every
operand, for the ``jax.numpy`` form and for the Mosaic kernels under the
Pallas interpreter, at one chunk, several, a partial last chunk and two rows,
at decays so small that ``e^(G_r) * e^(-G_j)`` would overflow inside a chunk,
and that no position reads a later one; at every shape of a head block (one
head, a pair and an odd head, several blocks); ``kda_solve``'s ``(I + L)^-1``
by its definition and as the forward and the backward read it; and outputs
and gradients bit for bit what they were before the solve was a call of its
own.

Tolerances.  Float32 against float32 at matmul precision 'highest' differ by
summation order and by the solve's: 1e-4 of the largest value for outputs,
1e-3 of a gradient's largest value (``tests/test_mamba.py``'s, for the same
reason).
"""

import functools
import hashlib

import jax
import jax.extend
import jax.numpy as jnp
import numpy as np
import pytest

from ray_tpu.ops import kda

# the yardstick as ``jax.numpy`` and the Mosaic kernels the mixer calls
FORMS = {"xla": kda.kda_scan_xla, "pallas": kda.kda_scan}
IMPLS = tuple(FORMS)


def recurrence(q, k, v, g, beta):
    """``S_t = (I - b_t k_t k_t^T) Diag(e^(g_t)) S_{t-1} + b_t k_t v_t^T``,
    ``o_t = S_t^T q_t``, one position at a time from a zero state.  (B, S,
    H * d) operands as ``kda_scan`` takes them."""
    batch, seq, heads = beta.shape
    q, k, v, g = (t.reshape(batch, seq, heads, -1) for t in (q, k, v, g))

    def step(S, at):
        q_t, k_t, v_t, g_t, b_t = at
        S = jnp.exp(g_t)[..., None] * S
        S = S + (b_t[..., None] * k_t)[..., None] * (
            v_t - jnp.einsum("bhd,bhde->bhe", k_t, S))[..., None, :]
        return S, jnp.einsum("bhde,bhd->bhe", S, q_t)

    _, o = jax.lax.scan(
        step, jnp.zeros((batch, heads, k.shape[-1], v.shape[-1])),
        tuple(jnp.moveaxis(t, 1, 0) for t in (q, k, v, g, beta)))
    return jnp.moveaxis(o, 0, 1).reshape(batch, seq, -1)


def operands(seq, batch=1, heads=2, d=16, decay=1.0, seed=0):
    """q and k of unit length a head (q scaled as the mixer scales it), as
    the layer makes them; ``decay`` multiplies the log-decays."""
    ks = jax.random.split(jax.random.PRNGKey(seed * 1000 + seq), 5)

    def unit(key):
        t = jax.random.normal(key, (batch, seq, heads, d))
        return (t / jnp.linalg.norm(t, axis=-1, keepdims=True)).reshape(
            batch, seq, heads * d)

    g = -decay * jax.nn.softplus(jax.random.normal(ks[3],
                                                   (batch, seq, heads * d)))
    return (unit(ks[0]) * d ** -0.5, unit(ks[1]),
            jax.random.normal(ks[2], (batch, seq, heads * d)), g,
            jax.nn.sigmoid(jax.random.normal(ks[4], (batch, seq, heads))))


def _close(got, want, rel, what):
    scale = float(jnp.max(jnp.abs(want)))
    assert np.isfinite(np.asarray(got)).all(), what
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=0,
                               atol=rel * max(scale, 1e-30), err_msg=what)


# one chunk; several; a partial last chunk; shorter than a chunk; two rows
SHAPES = [(8, 1), (32, 1), (37, 1), (5, 1), (24, 2)]


@functools.lru_cache(maxsize=None)
def _by_the_recurrence(seq, batch=1, decay=1.0, seed=0, heads=2):
    """(operands, the weights of the loss, the recurrence's outputs, its
    gradients), once for both forms."""
    args = operands(seq, batch, heads, decay=decay, seed=seed)
    weight = jax.random.normal(jax.random.PRNGKey(7), args[2].shape)
    with jax.default_matmul_precision("highest"):
        out, grads = jax.jit(lambda *a: (recurrence(*a), jax.grad(
            lambda *b: jnp.sum(recurrence(*b) * weight),
            argnums=range(5))(*a)))(*args)
    return args, weight, out, grads


def _held_to_the_recurrence(impl, chunk, *key):
    args, weight, out, want = _by_the_recurrence(*key)
    scan = functools.partial(FORMS[impl], chunk=chunk)
    with jax.default_matmul_precision("highest"):
        got_out, got = jax.jit(lambda *a: (scan(*a), jax.grad(
            lambda *b: jnp.sum(scan(*b) * weight),
            argnums=range(5))(*a)))(*args)
    _close(got_out, out, 1e-4, "outputs")
    for name, a, b in zip("q k v g beta".split(), got, want):
        _close(a, b, 1e-3, f"d{name}")


@pytest.mark.parametrize("impl", IMPLS)
@pytest.mark.parametrize("seq,batch", SHAPES)
def test_a_chunked_scan_equals_the_recurrence(seq, batch, impl):
    _held_to_the_recurrence(impl, 8, seq, batch)


@pytest.mark.parametrize("heads", [1, 3, 5, 6])
def test_a_head_block_of_every_shape_equals_the_recurrence(heads):
    """``_Shape`` takes the most heads up to four that divide the layer's:
    one head; three (a pair's solve and an odd head's); five blocks of one;
    two blocks of three.  Two rows, three chunks: the residuals' layout is
    held wherever a head can lie in it."""
    _held_to_the_recurrence("pallas", 8, 24, 2, 1.0, 0, heads)


def _inverses_by_head(inverse, heads, chunk):
    """``kda_solve``'s result, (batch, head blocks, seq, hb * chunk), as
    (batch, chunks, heads, chunk, chunk)."""
    batch, blocks, seq, width = inverse.shape
    return inverse.reshape(batch, blocks, seq // chunk, chunk, width // chunk,
                           chunk).transpose(0, 2, 1, 4, 3, 5).reshape(
        batch, seq // chunk, heads, chunk, chunk)


@pytest.mark.parametrize("heads,chunk,seq", [
    (1, 8, 24), (2, 8, 24), (3, 8, 24), (5, 8, 16), (6, 32, 64)])
def test_a_residual_is_the_inverse_of_the_chunks_system(heads, chunk, seq):
    """``kda_solve`` alone, from ``k``, ``g`` and ``beta``: ``(I + L) @
    inverse`` is the identity for every head and chunk, with ``L_rj = b_r
    sum_d k_rd k_jd e^(G_rd - G_jd)``, ``j < r``, made here.  One head, a
    pair as one system, a pair and an odd head, blocks of one; a chunk and
    two chunks a grid step."""
    q, k, v, g, beta = operands(seq, 2, heads)
    with jax.default_matmul_precision("highest"):
        inverse = kda._solve(k, g, beta, chunk)

        def chunks(t):      # (B, S, H * d) -> (B, chunks, H, C, d)
            return t.reshape(2, seq // chunk, chunk, heads, -1).transpose(
                0, 1, 3, 2, 4)

        K, G = chunks(k), jnp.cumsum(chunks(g), axis=-2)
        below = jnp.arange(chunk)[:, None] > jnp.arange(chunk)[None, :]
        decay = jnp.exp(jnp.where(
            below[:, :, None], G[..., :, None, :] - G[..., None, :, :],
            -jnp.inf))
        L = chunks(beta) * jnp.sum(
            K[..., :, None, :] * K[..., None, :, :] * decay, axis=-1)
        eye = jnp.eye(chunk)
        got = (eye + L) @ _inverses_by_head(inverse, heads, chunk)
    np.testing.assert_allclose(
        np.asarray(got), np.broadcast_to(eye, got.shape), rtol=0, atol=1e-5)


@pytest.mark.parametrize("heads", [2, 3])
def test_a_backward_reads_the_inverse_it_is_given(heads):
    """``_backward`` on ``kda_solve``'s result, and on an inverse made again
    outside a kernel from the same operands by the backward's own ``_Inside``
    (its ``Akk``: the k.k pairs under the q.k pairs, where the solve makes
    two heads' in one product) and ``_unit_lower_inverses``: the same five
    gradients, bit for bit."""
    chunk, (q, k, v, g, beta) = 8, operands(24, 2, heads)
    d, tree = q.shape[-1] // heads, kda._tree(chunk)
    inverse = kda._solve(k, g, beta, chunk)
    o, before = kda._forward(q, k, v, g, beta, inverse, chunk)

    def block(b, at):   # the heads' inverses of one chunk, side by side
        inside = kda._Inside(
            *([t[b, at:at + chunk, h * d:(h + 1) * d] for h in range(heads)]
              for t in (q, k, g)), *tree, q.dtype)
        Ls = [beta[b, at:at + chunk, h:h + 1] * Akk
              for h, Akk in enumerate(inside.Akks)]
        # two heads' systems down the diagonal of one matrix
        Xs = kda._unit_lower_inverses(
            [jax.scipy.linalg.block_diag(*Ls[i:i + 2])
             for i in range(0, heads, 2)], chunk)
        return kda._beside([X[i:i + chunk, i:i + chunk] for X in Xs
                            for i in range(0, len(X), chunk)])

    again = jnp.stack([
        jnp.concatenate([block(b, at) for at in range(0, 24, chunk)])
        for b in range(2)])[:, None]
    assert again.shape == inverse.shape     # heads <= 4: one head block
    do = jax.random.normal(jax.random.PRNGKey(7), o.shape)
    back = functools.partial(kda._backward, q, k, v, g, beta, before)
    for a, b in zip(back(inverse, do, chunk), back(again, do, chunk)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    # and makes none of its own: dv = b * inverse^T dU
    assert not np.any(back(jnp.zeros_like(inverse), do, chunk)[2])


@pytest.mark.parametrize("heads", [2, 3])
def test_a_forward_reads_the_inverse_it_is_given(heads):
    """``_forward`` makes no solve of its own: ``U = inverse @ rhs``, so a
    zero inverse writes nothing to the state and reads nothing out of it,
    and the identity's outputs are not the solve's."""
    chunk, (q, k, v, g, beta) = 8, operands(24, 2, heads)
    inverse = kda._solve(k, g, beta, chunk)
    forward = functools.partial(kda._forward, q, k, v, g, beta)
    o, before = forward(inverse, chunk)
    np.testing.assert_array_equal(
        np.asarray(o), np.asarray(kda.kda_scan(q, k, v, g, beta, chunk=chunk)))
    for t in forward(jnp.zeros_like(inverse), chunk):
        assert not np.any(t)
    eyes = jnp.tile(jnp.eye(chunk), (24 // chunk, heads))
    wrong, _ = forward(jnp.broadcast_to(eyes, inverse.shape), chunk)
    np.testing.assert_array_equal(      # a chunk's first position solves
        np.asarray(wrong[:, 0]), np.asarray(o[:, 0]))   # nothing
    assert float(jnp.max(jnp.abs(wrong - o))) > 1e-2 * float(
        jnp.max(jnp.abs(o)))


def _matmuls_between(f, *args):
    """The body of the one Mosaic call in ``f(*args)``, as its jaxpr: (its
    ``dot_general``s, for every one that reads another's result — through
    anything but a third — how many stand between the two)."""
    def calls(jaxpr):
        for eqn in jaxpr.eqns:
            if eqn.primitive.name == "pallas_call":
                yield eqn
            for sub in jax.core.jaxprs_in_params(eqn.params):
                yield from calls(sub)

    (call,) = calls(jax.make_jaxpr(f)(*args).jaxpr)
    made_by, count, between = {}, 0, []     # a value -> the matmuls behind it
    for eqn in call.params["jaxpr"].eqns:
        read = set().union(*(made_by.get(v, ()) for v in eqn.invars
                             if not isinstance(v, jax.extend.core.Literal)))
        if eqn.primitive.name == "dot_general":
            between += [count - at - 1 for at in read]
            read, count = {count}, count + 1
        made_by.update(dict.fromkeys(eqn.outvars, read))
    return count, between


@pytest.mark.parametrize("kernel", ["kda_solve", "kda_fwd", "kda_bwd"])
def test_a_kernels_matmuls_come_a_stage_of_every_head_together(kernel):
    """Mosaic issues a body's matmuls in the order of its text, so what the
    kernels are timed at (``ops/kda.py``, above ``_kda_solve_kernel``) is the
    order itself: a matmul that waits for another's result has the other
    heads' matmuls of that stage before it — three of them at a head block of
    four, as at the solve's two chunks of two pairs of heads.  A body that
    walks a head at a time reads 0 here."""
    heads, d, chunk, seq = 4, 16, 32, 64
    q, k, v, g, beta = operands(seq, 1, heads, d)
    inverse = jnp.zeros((1, 1, seq, heads * chunk))
    before = jnp.zeros((1, seq // chunk, heads * d, d))
    f, args = {
        "kda_solve": (kda._solve, (k, g, beta)),
        "kda_fwd": (kda._forward, (q, k, v, g, beta, inverse)),
        "kda_bwd": (kda._backward, (q, k, v, g, beta, before, inverse, v)),
    }[kernel]
    count, between = _matmuls_between(lambda *a: f(*a, chunk), *args)
    assert count >= 50 and len(between) >= count    # the chains are seen
    assert min(between) >= heads - 1


# sha256 of ``kda_scan``'s outputs and five gradients, float32 bytes one
# after the other, on ``operands(seq, 2, heads)`` at chunks of 8 under the
# Pallas interpreter, taken from the tree before ``kda_solve`` (commit
# ee0fc4d, the fused ``kda_fwd`` that solved for itself): (heads, bf16 q, k
# and v, seq) -> digest.  The same matmuls on the same operands in the same
# order of sums: the split, the paired k.k product and the order the kernels
# are written in change no bit.
_BEFORE_THE_SPLIT = {
    (1, False, 24):
        "6989583a265cbed582f064dfabe5f0c01e42e6b3ef8920be492cbf1868e57a7e",
    (2, False, 32):
        "23d7745f81d92000dac647bea2652e0f1f3512c52ccf64dc252dcfbde58ec9f3",
    (3, False, 24):
        "a249500fa31a86b3bb5ef7aa4a160e56b26fb34f2b21540206bc2fa733981848",
    (5, False, 32):
        "8c621f8c19d59f3aa71c6cb84c5b935074dcedeafb794e948e6d4a0b17228887",
    (4, True, 32):
        "98fda6d1931ae1dc0b700c9b634a3fc85af24f961547f128b81f98b3db2b338b",
    (6, True, 24):
        "8f2d2e2dcb500b1e40bcc0899a5cf8e30165e5e098c0ddd0f8e63d138e632bf3",
}


@pytest.mark.parametrize("heads,low,seq", sorted(_BEFORE_THE_SPLIT))
def test_a_outputs_and_gradients_are_the_fused_forwards_bit_for_bit(
        heads, low, seq):
    q, k, v, g, beta = operands(seq, 2, heads)
    if low:
        q, k, v = (t.astype(jnp.bfloat16) for t in (q, k, v))
    weight = jax.random.normal(jax.random.PRNGKey(7), v.shape)
    scan = functools.partial(kda.kda_scan, chunk=8)
    out, grads = jax.jit(lambda *a: (scan(*a), jax.grad(
        lambda *b: jnp.sum(scan(*b).astype(jnp.float32) * weight),
        argnums=range(5))(*a)))(q, k, v, g, beta)
    digest = hashlib.sha256()
    for t in (out, *grads):
        digest.update(np.asarray(t).tobytes())
    assert digest.hexdigest() == _BEFORE_THE_SPLIT[heads, low, seq]


@pytest.mark.parametrize("impl", IMPLS)
@pytest.mark.parametrize("chunk,seq", [(8, 24), (32, 64)])
def test_a_decays_that_a_split_exponent_cannot_hold(chunk, seq, impl):
    """Log-decays near -30 a step: over a chunk of 8 the running sum passes
    -200 and ``e^(-G_j)`` is past float32 (``e^88``); over the sixteen
    positions of a sub-block of a chunk of 32 it passes -400.  Outputs and
    gradients stay finite and equal the recurrence's."""
    args = _by_the_recurrence(seq, 1, 40.0, 1)[0]
    assert float(jnp.min(jnp.cumsum(args[3][:, :8], axis=1))) < -150
    _held_to_the_recurrence(impl, chunk, seq, 1, 40.0, 1)


@pytest.mark.parametrize("impl", IMPLS)
def test_a_position_reads_no_later_input(impl):
    """Every operand changed from position 13 on: outputs before it are the
    same bits."""
    args = operands(29)
    other = operands(29, seed=3)
    mixed = tuple(jnp.concatenate([a[:, :13], b[:, 13:]], axis=1)
                  for a, b in zip(args, other))
    scan = functools.partial(FORMS[impl], chunk=8)
    np.testing.assert_array_equal(np.asarray(scan(*args))[:, :13],
                                  np.asarray(scan(*mixed))[:, :13])


@pytest.mark.parametrize("impl", IMPLS)
def test_a_low_precision_operands_keep_a_float32_state(impl):
    """bf16 q, k, v: the result is bf16, within bf16's rounding of the
    float32 recurrence on the same rounded operands, and the log-decays are
    never rounded (a bf16 running sum over a chunk is another model)."""
    q, k, v, g, beta = operands(32)
    low = tuple(t.astype(jnp.bfloat16) for t in (q, k, v))
    got = FORMS[impl](*low, g, beta, chunk=8)
    assert got.dtype == jnp.bfloat16
    want = recurrence(*(t.astype(jnp.float32) for t in low), g, beta)
    _close(got.astype(jnp.float32), want, 3e-2, "bf16 outputs")

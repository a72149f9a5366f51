"""``ops/gdn.py``'s chunked scan against Gated DeltaNet's recurrence one
position at a time, in float32 on the CPU: outputs and the gradient of every
operand, for the ``jax.numpy`` form and for the Mosaic kernels under the
Pallas interpreter, at the smallest shapes that cross each edge — two value
heads a key head (and one, and three), a length that is padded, a state that
crosses chunk ends, two rows, several head blocks, a decay near 0 (``e^g`` so
small that ``e^(G_r) * e^(-G_j)`` would overflow inside a chunk) and near 1;
that no position reads a later one; ``gdn_scan`` equal to ``kda_scan`` on the
broadcast operands; ``gdn_solve``'s ``(I + L)^-1`` by its definition; and that
``g`` and the key heads' ``q`` and ``k`` reach the kernels as they are, never
broadcast.

Tolerances as ``tests/test_kda_scan.py``'s: float32 against float32 at matmul
precision 'highest' differ by summation order and by the solve's: 1e-4 of the
largest value for outputs, 1e-3 of a gradient's largest value.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_tpu.ops import gdn, kda

FORMS = {"xla": gdn.gdn_scan_xla, "pallas": gdn.gdn_scan}
IMPLS = tuple(FORMS)


def recurrence(q, k, v, g, beta):
    """``S_t = (I - b_t k_t k_t^T) e^(g_t) S_{t-1} + b_t k_t v_t^T``, ``o_t =
    S_t^T q_t``, one position at a time from a zero state, value head ``h``
    reading key head ``h // r``.  Operands as ``gdn_scan`` takes them."""
    batch, seq, heads = beta.shape
    d = v.shape[-1] // heads
    r = heads * d // k.shape[-1]
    q, k = (jnp.repeat(t.reshape(batch, seq, heads // r, d), r, axis=2)
            for t in (q, k))
    v = v.reshape(batch, seq, heads, d)

    def step(S, at):
        q_t, k_t, v_t, g_t, b_t = at
        S = jnp.exp(g_t)[..., None, None] * S
        S = S + (b_t[..., None] * k_t)[..., None] * (
            v_t - jnp.einsum("bhd,bhde->bhe", k_t, S))[..., None, :]
        return S, jnp.einsum("bhde,bhd->bhe", S, q_t)

    _, o = jax.lax.scan(
        step, jnp.zeros((batch, heads, d, d)),
        tuple(jnp.moveaxis(t, 1, 0) for t in (q, k, v, g, beta)))
    return jnp.moveaxis(o, 0, 1).reshape(batch, seq, -1)


def operands(seq, batch=1, keys=2, r=2, d=16, decay=1.0, seed=0):
    """q and k of unit length a key head (q scaled as the layer scales it);
    ``decay`` multiplies the log-decays, one a value head."""
    ks = jax.random.split(jax.random.PRNGKey(seed * 1000 + seq), 5)
    heads = keys * r

    def unit(key):
        t = jax.random.normal(key, (batch, seq, keys, d))
        return (t / jnp.linalg.norm(t, axis=-1, keepdims=True)).reshape(
            batch, seq, keys * d)

    g = -decay * jax.nn.softplus(jax.random.normal(ks[3],
                                                   (batch, seq, heads)))
    return (unit(ks[0]) * d ** -0.5, unit(ks[1]),
            jax.random.normal(ks[2], (batch, seq, heads * d)), g,
            jax.nn.sigmoid(jax.random.normal(ks[4], (batch, seq, heads))))


def _close(got, want, rel, what):
    scale = float(jnp.max(jnp.abs(want)))
    assert np.isfinite(np.asarray(got)).all(), what
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=0,
                               atol=rel * max(scale, 1e-30), err_msg=what)


def _against_the_recurrence(ops, impl, chunk=8):
    """Outputs and all five gradients of ``impl`` against the recurrence's,
    each side jitted once."""
    weight = jax.random.normal(jax.random.PRNGKey(9), ops[2].shape)

    def both(f):
        return jax.jit(lambda *a: (f(*a), jax.grad(
            lambda *a: jnp.sum(f(*a) * weight), argnums=(0, 1, 2, 3, 4))(*a)))

    with jax.default_matmul_precision("highest"):
        got, got_grads = both(
            lambda *a: FORMS[impl](*a, chunk=chunk))(*ops)
        want, want_grads = both(recurrence)(*ops)
    _close(got, want, 1e-4, "o")
    for name, a, b in zip(("dq", "dk", "dv", "dg", "dbeta"), got_grads,
                          want_grads):
        _close(a, b, 1e-3, name)


# (seq, batch, key heads, value heads a key head, x the log-decays): several
# chunks, the state crossing their ends, and a partial last one; two rows and
# three key heads (three head blocks of one key head); three value heads a
# key head; a decay near 0 — and for the kernels alone: shorter than a
# chunk; one value head a key head, its decay near 1; four key heads (two
# head blocks of two)
BOTH = [(37, 1, 2, 2, 1.0), (24, 2, 3, 2, 1.0), (20, 1, 2, 3, 1.0),
        (24, 1, 2, 2, 30.0)]
KERNELS = [(5, 1, 2, 2, 1.0), (32, 1, 1, 1, 0.01), (32, 1, 4, 2, 1.0)]


@pytest.mark.parametrize("seq,batch,keys,r,decay,impl", [
    (*shape, impl) for impl in IMPLS for shape in BOTH] + [
    (*shape, "pallas") for shape in KERNELS])
def test_a_chunked_scan_equals_the_recurrence(seq, batch, keys, r, decay,
                                              impl):
    _against_the_recurrence(operands(seq, batch, keys, r, decay=decay), impl)


@pytest.mark.parametrize("impl", IMPLS)
def test_a_decay_that_a_split_exponent_cannot_hold(impl):
    """At ``e^g`` near ``e^-60`` a position, ``e^(G_r)`` and ``e^(-G_j)``
    apart leave float32 within a chunk of 32; the differences do not."""
    ops = operands(64, decay=60.0)
    assert float(jnp.sum(ops[3][0, :32, 0])) < -700
    _against_the_recurrence(ops, impl, chunk=32)


def test_a_position_reads_no_later_input(impl="pallas"):
    q, k, v, g, beta = operands(24)
    with jax.default_matmul_precision("highest"):
        whole = FORMS[impl](q, k, v, g, beta, chunk=8)
        cut = FORMS[impl](*(t[:, :13] for t in (q, k, v, g, beta)), chunk=8)
    _close(whole[:, :13], cut, 1e-5, "prefix")


def test_the_kernels_equal_kda_scan_on_the_broadcast_operands():
    """``kda_scan`` fed ``g`` broadcast to a head's channels and ``q``, ``k``
    repeated to the value heads computes this function: the two families of
    kernels agree, outputs and the gradients summed back."""
    q, k, v, g, beta = operands(40, batch=2, keys=2, r=2)
    d, r = 16, 2
    weight = jax.random.normal(jax.random.PRNGKey(3), v.shape)

    def broadcast(q, k, v, g, beta):
        def wide(t):
            return jnp.repeat(t.reshape(2, 40, 2, d), r, axis=2).reshape(
                2, 40, -1)
        return kda.kda_scan(wide(q), wide(k), v, jnp.repeat(g, d, axis=-1),
                            beta, chunk=8)

    def both(f):
        return jax.jit(lambda *a: (f(*a), jax.grad(
            lambda *a: jnp.sum(f(*a) * weight), argnums=(0, 1, 2, 3, 4))(*a)))

    with jax.default_matmul_precision("highest"):
        got, got_grads = both(
            lambda *a: gdn.gdn_scan(*a, chunk=8))(q, k, v, g, beta)
        want, want_grads = both(broadcast)(q, k, v, g, beta)
    _close(got, want, 1e-5, "o")
    for name, a, b in zip(("dq", "dk", "dv", "dg", "dbeta"), got_grads,
                          want_grads):
        _close(a, b, 1e-4, name)


@pytest.mark.parametrize("keys,r,seq", [(2, 2, 32), (1, 3, 16)])
def test_a_residual_is_the_inverse_of_the_chunks_system(keys, r, seq):
    """``gdn_solve``'s result, a value head's (C, C) a chunk, times ``I +
    L`` with ``L_rj = beta_r (k_r . k_j) e^(G_r - G_j)`` below the diagonal is
    the identity."""
    chunk, d = 8, 16
    q, k, v, g, beta = operands(seq, keys=keys, r=r)
    heads = keys * r
    with jax.default_matmul_precision("highest"):
        G = gdn._running(g, chunk)
        inverse = gdn._solve(k, G, beta, chunk, d)
    s = gdn._Grouped(k, beta, chunk, d)
    assert inverse.shape == (1, s.blocks, seq, s.hb * chunk)
    kh = np.asarray(k).reshape(seq, keys, d)
    for h in range(heads):
        block, within = divmod(h, s.hb)
        for c in range(seq // chunk):
            rows = slice(c * chunk, (c + 1) * chunk)
            kc, Gc = kh[rows, h // r], np.asarray(G)[0, rows, h]
            L = np.tril(np.asarray(beta)[0, rows, h, None] * (kc @ kc.T)
                        * np.exp(Gc[:, None] - Gc[None, :]), -1)
            X = np.asarray(inverse)[0, block, rows,
                                    within * chunk:(within + 1) * chunk]
            np.testing.assert_allclose(X @ (np.eye(chunk) + L),
                                       np.eye(chunk), atol=1e-5)


def test_the_kernels_take_one_decay_a_head_and_the_key_heads_as_they_are():
    """No operand of a Mosaic call is as wide as ``g`` broadcast to a head's
    channels would be in float32, or ``q`` / ``k`` repeated to the value
    heads: the calls read ``q`` and ``k`` at the key heads' width and the
    running sums at one number a value head; the gradient holds one
    ``gdn_solve``, ``gdn_fwd`` and ``gdn_bwd``."""
    q, k, v, g, beta = operands(32, keys=2, r=2)
    jaxpr = jax.make_jaxpr(jax.grad(
        lambda *a: jnp.sum(gdn.gdn_scan(*a, chunk=8)),
        argnums=(0, 1, 2, 3, 4)))(q, k, v, g, beta)
    calls = {}

    def walk(j):
        for eqn in j.eqns:
            if eqn.primitive.name == "pallas_call":
                calls.setdefault(eqn.params["name"], []).append(
                    [tuple(x.aval.shape) for x in eqn.invars])
            for sub in jax.core.jaxprs_in_params(eqn.params):
                walk(sub)

    walk(jaxpr.jaxpr)
    assert {n: len(c) for n, c in calls.items()} == {
        "gdn_solve": 1, "gdn_fwd": 1, "gdn_bwd": 1}
    key_wide, value_wide = (1, 32, 2 * 16), (1, 32, 4 * 16)
    for name, (shapes,) in calls.items():
        # q and k (the solve: k) at the key heads; of the arrays as wide as
        # the value heads only v, and in the backward the cotangent
        assert shapes.count(key_wide) == (1 if name == "gdn_solve" else 2)
        assert shapes.count(value_wide) == {"gdn_solve": 0, "gdn_fwd": 1,
                                            "gdn_bwd": 2}[name]
        # the running sums and beta: a column a value head
        assert shapes.count((1, 1, 32, 4)) == 2


def test_low_precision_operands_keep_a_float32_state(impl="pallas"):
    """bf16 q, k, v: the result is bf16, ``g`` and ``beta`` stay float32, and
    the result lies within bf16's rounding of the float32 scan's."""
    q, k, v, g, beta = operands(48)
    low = [t.astype(jnp.bfloat16) for t in (q, k, v)]
    got = FORMS[impl](*low, g, beta, chunk=8)
    assert got.dtype == jnp.bfloat16
    want = recurrence(*(t.astype(jnp.float32) for t in low), g, beta)
    _close(got.astype(jnp.float32), want, 3e-2, "o in bf16")


def test_a_chunk_that_is_no_power_of_two_is_refused():
    with pytest.raises(ValueError, match="power of two"):
        jax.eval_shape(lambda *a: gdn.gdn_scan(*a, chunk=12), *operands(24))


def test_value_heads_that_no_key_head_count_divides_are_refused():
    q, k, v, g, beta = operands(16, keys=2, r=2)
    with pytest.raises(ValueError, match="value heads"):
        jax.eval_shape(lambda *a: gdn.gdn_scan(*a, chunk=8),
                       q, k, v[..., :48], g[..., :3], beta[..., :3])

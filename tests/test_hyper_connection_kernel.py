"""``ops/hyper_connection.py::hyper_connection`` on the CPU, its kernels under
the interpreter: a sub-layer as the block runs it — the call on the stream
alone, a branch of the mix, the write-back — and every gradient (the stream,
the branch's output, ``phi``, ``scale``, ``bias``, ``alpha``) against reverse
mode through ``hyper_connection_xla``, the lines ``HyperConnection`` had, at
four streams of 128 and 256 lanes, in float32 and bfloat16, over rows that
are a block, several and a part of one; the statistic, the coefficients and
the Sinkhorn are float32 inside a bfloat16 call (a Sinkhorn in bfloat16,
coefficients in bfloat16 and one iteration for twenty each outside the
tolerance, at weights moved off ``alpha`` 0.01); lowered for the TPU the
backward is two Mosaic calls with no float32 array of a stream's size and no
sum of stream-sized cotangents beside them; under ``dp x fsdp`` on the
virtual mesh the sharded calls give one device's numbers; and the selector
takes the cell's shape and leaves the toy's 64-wide streams to the plain
form."""

import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_tpu.models.llama import _hc_bias_init
from ray_tpu.ops import hyper_connection as hc
from ray_tpu.ops.hyper_connection import (Spec, hyper_connection,
                                          hyper_connection_xla, write_back,
                                          write_back_xla)
from ray_tpu.parallel.mesh import MeshConfig, build_mesh

N = 4


def spec_of(dtype, iters=20):
    return Spec(N, iters, 1e-6, 30.0, 1e-5, dtype)


def operands(batch, seq, c, dtype, seed=0):
    """The stream, the parameters moved off their start (``alpha`` near 1:
    the coefficients depend on the stream, the Sinkhorn works), what the
    branch adds to the mix and scales it by, and the result's cotangent."""
    keys = jax.random.split(jax.random.PRNGKey(seed), 8)
    k = 2 * N + N * N

    def normal(key, shape, dtype=jnp.float32):
        return jax.random.normal(key, shape, jnp.float32).astype(dtype)

    x = normal(keys[0], (batch, seq, N * c), dtype)
    scale = 1.0 + 0.3 * normal(keys[1], (N * c,))
    phi = normal(keys[2], (N * c, k)) / np.sqrt(N * c)
    bias = jnp.asarray(_hc_bias_init(N)) + 0.3 * normal(keys[3], (k,))
    alpha = jnp.array([0.7, 1.3, 0.9], jnp.float32)
    f = normal(keys[4], (batch, seq, c), dtype)
    w = 1.0 + 0.1 * normal(keys[5], (c,))
    return (x, scale, phi, bias, alpha, f, w), normal(keys[6], x.shape, dtype)


KERNELS = (hyper_connection, write_back)
PLAIN = (hyper_connection_xla, write_back_xla)


def sublayer(calls, spec):
    """``X <- H_res X + H_post^T (f + w H_pre X)`` through the two calls, as
    ``LlamaBlock`` makes them."""
    first, second = calls

    def run(x, scale, phi, bias, alpha, f, w):
        mixed, coefficients, _ = first(x, scale, phi, bias, alpha, spec)
        branch = (f.astype(jnp.float32) + mixed.astype(jnp.float32) * w
                  ).astype(f.dtype)
        return second(x, branch, coefficients, spec)
    return run


def both(fn, given, dout):
    out, pull = jax.vjp(fn, *given)
    return (out, *pull(dout))


def kernel_calls(fn, given) -> int:
    return str(jax.make_jaxpr(lambda *a: both(fn, a, a[0]))(*given)
               ).count("pallas_call")


# name -> (lanes a stream, batch, positions, dtype, kernels run)
CASES = {
    "128_lanes_one_block": (128, 1, 128, jnp.float32, True),
    "128_lanes_bfloat16_two_blocks": (128, 2, 128, jnp.bfloat16, True),
    "256_lanes_rows_padded": (256, 2, 100, jnp.float32, True),
    "256_lanes_bfloat16_a_part": (256, 1, 200, jnp.bfloat16, True),
    "the_toys_64_lanes": (64, 2, 32, jnp.bfloat16, False),
    "192_lanes": (192, 1, 32, jnp.float32, False),
}


@pytest.mark.parametrize("name", sorted(CASES))
def test_a_sublayer_equals_reverse_mode_through_the_plain_form(name):
    c, batch, seq, dtype, kernels = CASES[name]
    spec = spec_of(dtype)
    given, dout = operands(batch, seq, c, dtype)
    fn = sublayer(KERNELS, spec)
    # the stream's pass, the write-back's backward, the stream's pass' own
    assert kernel_calls(fn, given) == (3 if kernels else 0)
    got = jax.jit(lambda *a: both(fn, a[:-1], a[-1]))(*given, dout)
    want = jax.jit(lambda *a: both(
        sublayer(PLAIN, spec), a[:-1], a[-1]))(*given, dout)
    # bfloat16: both round the mix, the branch and the streams once each, so
    # they differ by an ulp where a sum's order moved a value across a
    # rounding edge, and the parameters' gradients by what those flips sum to
    tol = 2e-5 if dtype == jnp.float32 else 1e-2
    for g, w in zip(got, want, strict=True):
        assert g.shape == w.shape and g.dtype == w.dtype
        g, w = (np.asarray(t, np.float32) for t in (g, w))
        np.testing.assert_allclose(g, w, rtol=tol, atol=tol * np.abs(w).max())


def test_the_statistics_the_module_sows_are_the_plain_forms():
    spec = spec_of(jnp.bfloat16)
    (x, *params, _, _), _ = operands(2, 100, 128, jnp.bfloat16)
    *_, got = hyper_connection(x, *params, spec)
    *_, want = hyper_connection_xla(x, *params, spec)
    np.testing.assert_allclose(got, want, rtol=1e-4)
    assert float(want[0]) > 1e-4        # twenty iterations, off the start


def _first_call(fn, spec):
    """The call on the stream alone with its coefficients among the results:
    (the mix, ``H_post``, ``H_res``)."""
    def run(x, scale, phi, bias, alpha):
        mixed, coefficients, _ = fn(x, scale, phi, bias, alpha, spec)
        return mixed, coefficients[0], coefficients[1]
    return run


def _rounded_form(wrong):
    """``hyper_connection_xla``'s first call with one thing in bfloat16:
    ``sinkhorn`` (every division's result) or ``coefficients`` (the
    projection's operands and result)."""
    def b(t):
        return t.astype(jnp.bfloat16).astype(jnp.float32)

    def run(x, scale, phi, bias, alpha, spec):
        n = spec.n
        xf = x.astype(jnp.float32)
        w = scale[:, None] * phi
        if wrong == "coefficients":
            z = b(jnp.einsum("bsc,ck->kbs", b(xf * hc._rstd(xf, spec.rms_eps)),
                             b(w), precision=jax.lax.Precision.HIGHEST))
        else:
            z = jnp.einsum("bsc,ck->kbs", xf, w,
                           precision=jax.lax.Precision.HIGHEST) \
                * hc._rstd(xf, spec.rms_eps)[..., 0]
        z = z * jnp.repeat(alpha, np.array([n, n, n * n]),
                           total_repeat_length=spec.k)[:, None, None] \
            + bias[:, None, None]
        pre, post = jax.nn.sigmoid(z[:n]), 2.0 * jax.nn.sigmoid(z[n:2 * n])
        m = z[2 * n:].reshape(n, n, *z.shape[1:])
        if wrong == "sinkhorn":
            m = b(jnp.exp(jnp.clip(b(m), -spec.clamp, spec.clamp)))
            for _ in range(spec.iters):
                m = b(m / b(jnp.sum(m, axis=1, keepdims=True) + spec.eps))
                m = b(m / b(jnp.sum(m, axis=0, keepdims=True) + spec.eps))
        else:
            m = hc.sinkhorn(m, spec.iters, spec.eps, spec.clamp)
        mixed = sum(p[..., None] * part.astype(jnp.float32)
                    for p, part in zip(pre, hc.streams(x, n)))
        return mixed.astype(spec.dtype), (post, m), None
    return run


def test_the_coefficients_are_float32_inside_a_bfloat16_call():
    """Against the float32 lines on the same bfloat16 stream, the kernels'
    coefficients and the parameters' gradients err by float32's rounding and
    the mix and the stream's gradient by the one rounding at the store; a
    Sinkhorn in bfloat16, coefficients in bfloat16 and one iteration for
    twenty are each a hundred times further off in ``H_res`` (when this was
    written, of the largest value: ``H_post`` 2.4e-7, ``H_res`` 1.9e-7,
    ``dphi`` 2.5e-7, ``dbias`` 1e-7 for the kernels; ``H_res`` 2e-3, 4e-3 and
    0.3 for the three)."""
    spec = spec_of(jnp.bfloat16)
    (x, *params, _, _), _ = operands(2, 100, 256, jnp.bfloat16, seed=3)
    keys = jax.random.split(jax.random.PRNGKey(11), 3)

    def errors(fn, spec=spec):
        exact = _first_call(hyper_connection_xla,
                            spec_of(jnp.float32, spec_of(None).iters))
        want_out, pull = jax.vjp(exact, x.astype(jnp.float32), *params)
        # (the mix's cotangent a bfloat16 value on both sides)
        cotangents = [jax.random.normal(k, t.shape).astype(
            jnp.bfloat16).astype(jnp.float32) for k, t in zip(keys, want_out)]
        want = (*want_out, *pull(tuple(cotangents)))
        got_out, pull = jax.vjp(_first_call(fn, spec), x, *params)
        got = (*got_out, *pull(tuple(
            c.astype(t.dtype) for c, t in zip(cotangents, got_out))))
        return [float(np.abs(np.asarray(g, np.float32) - np.asarray(w)).max()
                      / np.abs(np.asarray(w)).max())
                for g, w in zip(got, want, strict=True)]

    # (the mix, H_post, H_res, dx, dscale, dphi, dbias, dalpha)
    kernels = errors(hyper_connection)
    assert max(kernels[1:3] + kernels[4:]) < 2e-6, kernels
    assert max(kernels[0], kernels[3]) < 4e-3, kernels  # half an ulp, bf16
    for wrong, fn, spec_ in (
            ("sinkhorn", _rounded_form("sinkhorn"), spec),
            ("coefficients", _rounded_form("coefficients"), spec),
            ("one iteration", hyper_connection_xla, spec_of(jnp.bfloat16, 1))):
        off = errors(fn, spec_)
        assert off[2] > 100 * kernels[2] and off[2] > 1e-4, (wrong, off)
        assert max(off[5:]) > 100 * max(kernels[5:]), (wrong, off)


def test_lowered_for_the_tpu_the_backward_holds_no_float32_stream(monkeypatch):
    """The backward alone, as Mosaic lowers it (the forward has run, under
    the interpreter): the write-back's and the stream's pass' custom calls,
    nothing in float32 of the stream's size, and no addition of arrays of the
    stream's size: the write-back's cotangent of the stream is an operand of the
    second call."""
    c, rows = 128, 1024
    spec = spec_of(jnp.bfloat16)
    given, dout = operands(1, rows, c, jnp.bfloat16)
    _, pull = jax.vjp(sublayer(KERNELS, spec), *given)
    monkeypatch.delenv("RAY_TPU_PALLAS_INTERPRET")
    text = jax.jit(lambda pull, d: pull(d)).trace(pull, dout).lower(
        lowering_platforms=("tpu",)).as_text()
    assert text.count("tpu_custom_call") == 2
    float32 = {tuple(int(n) for n in dims.split("x") if n)
               for dims in re.findall(r"tensor<((?:\d+x)*)f32>", text)}
    # (the widest: the branch of this test, in float32 a position's C values)
    assert float32 and max(np.prod(d) for d in float32) == rows * c, float32
    adds = re.findall(r"stablehlo\.add.*tensor<((?:\d+x)*)\w+>", text)
    assert adds and all(
        np.prod([int(n) for n in dims.split("x") if n]) <= rows * c
        for dims in adds), adds


def test_a_sharded_mesh_gives_the_single_device_numbers():
    """Rows over ``dp`` and ``fsdp``, the lanes whole: both calls inside
    their ``shard_map`` on a CPU virtual mesh, forward and every gradient —
    the parameters' summed over the shards —, equal one device's.  No chip
    has run this."""
    spec = spec_of(jnp.float32)
    given, dout = operands(4, 40, 128, jnp.float32)
    fn = sublayer(KERNELS, spec)
    one = both(fn, given, dout)
    mesh = build_mesh(MeshConfig(dp=2, fsdp=2), devices=jax.devices()[:4])
    with jax.set_mesh(mesh):
        jaxpr = str(jax.make_jaxpr(fn)(*given))
        assert jaxpr.count("shard_map") == 2 and "pallas_call" in jaxpr
        many = jax.jit(lambda *a: both(fn, a[:-1], a[-1]))(*given, dout)
    for g, w in zip(many, one, strict=True):
        np.testing.assert_allclose(np.asarray(g), np.asarray(w), rtol=2e-5,
                                   atol=2e-5 * np.abs(w).max())


@pytest.mark.parametrize("lanes,dtype,n,taken", [
    (4 * 3584, jnp.bfloat16, 4, True),      # xing4-s8k-1chip
    (4 * 3584, jnp.float32, 4, True),
    (4 * 64, jnp.bfloat16, 4, False),       # toy-xing4
    (4 * 3584, jnp.float16, 4, False),
    (2 * 1024, jnp.bfloat16, 2, True),
    (6 * 128, jnp.bfloat16, 6, False),      # 48 coefficients: over 32 planes
])
def test_the_selector_goes_by_shape(lanes, dtype, n, taken):
    x = jax.ShapeDtypeStruct((1, 8192, lanes), dtype)
    assert hc._kernels_apply(x, spec_of(dtype)._replace(n=n)) == taken

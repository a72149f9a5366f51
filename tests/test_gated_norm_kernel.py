"""``ops/gated_norm.py::gated_rms_norm`` on the CPU, its kernels under the
interpreter: the output and every gradient (``y``, ``z``, ``scale``) against
reverse mode through ``gated_rms_norm_xla``, the lines ``Mamba2Mixer`` had —
at Nemotron-3-Nano's eight groups of 512, at Granite's one group of 4,096 and
at toy widths, over rows that are several tiles, a part of one and a batch of
two, in float32 and bfloat16; the statistic is float32 inside a bfloat16 call
(one in bfloat16 is seen at a stated margin); a group that is no whole lanes
runs the reference and no kernel; lowered for the TPU, no float32 array of an
operand's size is left beside the two Mosaic calls; and under ``dp x tp`` on
the virtual mesh the sharded call gives one device's numbers, with one group
under ``tp`` on the reference's lines."""

import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_tpu.ops import gated_norm
from ray_tpu.ops.gated_norm import gated_rms_norm, gated_rms_norm_xla
from ray_tpu.parallel.mesh import MeshConfig, build_mesh

EPS = 1e-5


def operands(batch, seq, channels, dtype, seed=0):
    keys = jax.random.split(jax.random.PRNGKey(seed), 4)
    y, z, dout = (jax.random.normal(k, (batch, seq, channels), dtype)
                  for k in keys[:3])
    scale = 1.0 + 0.3 * jax.random.normal(keys[3], (channels,), jnp.float32)
    return (y, z, scale), dout


def both(fn, given, dout):
    out, pull = jax.vjp(fn, *given)
    return (out, *pull(dout))


def kernel_calls(fn, given) -> int:
    return str(jax.make_jaxpr(lambda *a: both(fn, a, a[0]))(*given)
               ).count("pallas_call")


# name -> (groups, channels, batch, positions, dtype, kernels run); a tile of
# 4,096 channels is 64 rows in float32 and 128 in bfloat16
CASES = {
    "eight_groups_of_512_three_tiles": (8, 4096, 2, 96, jnp.float32, True),
    "eight_groups_of_512_bfloat16": (8, 4096, 2, 192, jnp.bfloat16, True),
    "one_group_of_4096_two_tiles": (1, 4096, 2, 64, jnp.float32, True),
    "one_group_of_4096_bfloat16_a_part": (1, 4096, 1, 200, jnp.bfloat16, True),
    "two_groups_of_128_rows_padded": (2, 256, 2, 21, jnp.float32, True),
    "one_group_of_384": (1, 384, 1, 48, jnp.float32, True),
    "three_groups_of_256_bfloat16": (3, 768, 2, 40, jnp.bfloat16, True),
    "a_group_of_64": (4, 256, 2, 16, jnp.float32, False),
    "a_group_of_192": (2, 384, 1, 16, jnp.bfloat16, False),
}


@pytest.mark.parametrize("name", sorted(CASES))
def test_gated_rms_norm_equals_reverse_mode_through_the_plain_form(name):
    groups, channels, batch, seq, dtype, kernels = CASES[name]
    given, dout = operands(batch, seq, channels, dtype)

    def fn(*a):
        return gated_rms_norm(*a, groups, EPS)

    assert kernel_calls(fn, given) == (2 if kernels else 0)
    got = jax.jit(lambda *a: both(fn, a[:-1], a[-1]))(*given, dout)
    want = jax.jit(lambda *a: both(
        lambda *b: gated_rms_norm_xla(*b, groups, EPS), a[:-1], a[-1]))(
            *given, dout)
    # bfloat16: both round once at the output, so they differ by an ulp
    # where a sum's order moved a value across a rounding edge
    tol = 1e-5 if dtype == jnp.float32 else 1e-2
    for g, w in zip(got, want, strict=True):
        assert g.shape == w.shape and g.dtype == w.dtype
        g, w = (np.asarray(t, np.float32) for t in (g, w))
        np.testing.assert_allclose(g, w, rtol=tol, atol=tol * np.abs(w).max())


def test_the_statistic_is_float32_inside_a_bfloat16_call():
    """Against the float32 lines on the same bfloat16 operands, the kernels'
    output and gradients err by the one rounding at the store; the same
    lines with the sum of squares in bfloat16 err three times that in the
    output and ten times in ``dy`` and ``dz`` (when this was written, of the
    largest value: 1.9e-3, 2.1e-4 and 3.7e-4 against 7.3e-3, 6.8e-3 and
    6.0e-3; ``dscale`` 1.8e-7 against 2.9e-3)."""
    groups, channels = 8, 4096
    given, dout = operands(1, 128, channels, jnp.bfloat16, seed=3)

    def exact(y, z, scale):     # float32 from the operands to the end
        return gated_rms_norm_xla(y.astype(jnp.float32),
                                  z.astype(jnp.float32), scale, groups, EPS)

    def statistic_in_bfloat16(y, z, scale):
        g = (y.astype(jnp.float32) * jax.nn.silu(z.astype(jnp.float32))
             ).reshape(*y.shape[:-1], groups, -1)
        half = g.astype(jnp.bfloat16)
        mean = jnp.mean(half * half, axis=-1, keepdims=True,
                        dtype=jnp.bfloat16)
        g = g * jax.lax.rsqrt(mean.astype(jnp.float32) + EPS)
        return (g.reshape(y.shape) * scale).astype(y.dtype)

    def errors(fn):
        got = both(fn, given, dout)
        want = both(exact, given, dout.astype(jnp.float32))
        return [float(np.abs(np.asarray(g, np.float32) - np.asarray(w)).max()
                      / np.abs(np.asarray(w)).max())
                for g, w in zip(got, want, strict=True)]

    kernels = errors(lambda *a: gated_rms_norm(*a, groups, EPS))
    wrong = errors(statistic_in_bfloat16)
    assert max(kernels[:3]) < 4e-3, kernels     # half an ulp of bfloat16
    assert wrong[0] > 3 * kernels[0], (kernels, wrong)
    assert all(w > 10 * k for w, k in zip(wrong[1:], kernels[1:])), (
        kernels, wrong)


def test_lowered_for_the_tpu_no_float32_array_of_an_operands_size(monkeypatch):
    """The whole of forward and backward, as Mosaic lowers it: two custom
    calls, and of float32 nothing but ``scale`` and ``dscale``'s eight
    rows."""
    monkeypatch.delenv("RAY_TPU_PALLAS_INTERPRET")
    rows, channels = 512, 1024
    given, dout = operands(1, rows, channels, jnp.bfloat16)

    def fn(*a):
        return both(lambda *b: gated_rms_norm(*b, 2, EPS), a[:-1], a[-1])

    text = jax.jit(fn).trace(*given, dout).lower(
        lowering_platforms=("tpu",)).as_text()
    assert text.count("tpu_custom_call") == 2
    float32 = {tuple(int(n) for n in dims.split("x") if n)
               for dims in re.findall(r"tensor<((?:\d+x)*)f32>", text)}
    assert float32 and max(np.prod(d) for d in float32) <= \
        gated_norm._SUB * channels, float32


def test_a_sharded_mesh_gives_the_single_device_numbers():
    """Rows over ``dp``, whole groups over ``tp``: the call inside its
    ``shard_map`` on a CPU virtual mesh, forward and every gradient —
    ``scale``'s summed over ``dp`` —, equals one device's; one group under
    ``tp`` would be cut, and runs the reference's lines.  No chip has run
    this."""
    given, dout = operands(2, 40, 512, jnp.float32)
    mesh = build_mesh(MeshConfig(dp=2, tp=2), devices=jax.devices()[:4])
    for groups, sharded in ((2, True), (4, True), (1, False)):
        def fn(*a):
            return gated_rms_norm(*a, groups, EPS)

        one = both(fn, given, dout)
        with jax.set_mesh(mesh):
            jaxpr = str(jax.make_jaxpr(fn)(*given))
            assert ("shard_map" in jaxpr) == ("pallas_call" in jaxpr) \
                == sharded, groups
            many = jax.jit(lambda *a: both(fn, a[:-1], a[-1]))(*given, dout)
        for g, w in zip(many, one, strict=True):
            np.testing.assert_allclose(np.asarray(g), np.asarray(w), rtol=1e-5,
                                       atol=1e-5 * np.abs(w).max())

"""``ops/conv.py::conv_silu`` on the CPU, its kernels under the interpreter:
the output and every gradient (``x``, ``kernel``, ``bias``) against reverse
mode through ``silu(models/mamba.py::causal_conv(..))`` and a head's unit
norm written out here — at three, four and six taps, with a bias and without, with
the unit norm and without, over two batch rows (the second row's first
positions read zeros, not the first row's last), at one tile, at several and
at a sequence that is no whole tiles, in float32 and bfloat16; where the
channels are no whole lanes, a head is not 128 columns or the kernel is too
wide, the ``jax.numpy`` form runs and no kernel; and under ``dp x tp`` on the
virtual mesh the sharded call gives one device's numbers."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_tpu.models.mamba import causal_conv
from ray_tpu.ops import conv
from ray_tpu.ops.conv import conv_silu
from ray_tpu.parallel.mesh import MeshConfig, build_mesh


def plain(x, kernel, bias=None, unit_heads=None, scale=1.0):
    y = jax.nn.silu(causal_conv(x, kernel, bias))
    if unit_heads is None:
        return y
    t = y.astype(jnp.float32).reshape(*y.shape[:-1], unit_heads, -1)
    length = jnp.sqrt(jnp.sum(t * t, axis=-1, keepdims=True) + conv.L2_EPS)
    return (scale * t / length).reshape(y.shape).astype(y.dtype)


def operands(width, bias, seq, channels, dtype, batch=2, seed=0):
    keys = jax.random.split(jax.random.PRNGKey(seed), 4)
    x = jax.random.normal(keys[0], (batch, seq, channels), dtype)
    kernel = jax.random.uniform(keys[1], (width, channels), dtype, -0.6, 0.6)
    given = (x, kernel) + ((jax.random.uniform(
        keys[2], (channels,), dtype, -0.5, 0.5),) if bias else ())
    return given, jax.random.normal(keys[3], x.shape, dtype)


def both(fn, given, dy):
    out, pull = jax.vjp(fn, *given)
    return (out, *pull(dy))


def kernel_calls(fn, given) -> int:
    return str(jax.make_jaxpr(lambda *a: both(fn, a, a[0]))(*given)
               ).count("pallas_call")


# name -> (taps, bias, unit heads, positions, channels, dtype, kernels run)
CASES = {
    "four_taps_bias": (4, True, None, 48, 256, jnp.float32, True),
    "three_taps_unit": (3, False, 2, 48, 256, jnp.float32, True),
    "four_taps_unit_scaled_bias": (4, True, 3, 64, 384, jnp.float32, True),
    "two_whole_tiles": (4, True, None, 2 * conv._SEQ_TILE, 128, jnp.float32,
                        True),
    "tiles_and_a_part_unit": (4, False, 2, 2 * conv._SEQ_TILE + 76, 256,
                              jnp.float32, True),
    "tiles_and_a_part_bias": (3, True, None, conv._SEQ_TILE + 5, 128,
                              jnp.float32, True),
    "bfloat16_unit": (4, False, 2, conv._SEQ_TILE + 40, 256, jnp.bfloat16,
                      True),
    "bfloat16_bias": (4, True, None, 80, 128, jnp.bfloat16, True),
    "six_taps": (6, True, None, 32, 128, jnp.float32, True),
    "channels_no_whole_lanes": (4, True, None, 40, 192, jnp.float32, False),
    "a_head_of_64": (4, False, 4, 40, 256, jnp.float32, False),
    "seven_taps": (7, True, None, 40, 128, jnp.float32, False),
}


@pytest.mark.parametrize("name", sorted(CASES))
def test_conv_silu_equals_reverse_mode_through_the_plain_form(name):
    width, bias, heads, seq, channels, dtype, kernels = CASES[name]
    given, dy = operands(width, bias, seq, channels, dtype)
    scale = 0.25 if heads else 1.0

    def fn(*a):
        return conv_silu(*a, unit_heads=heads, scale=scale)

    assert kernel_calls(fn, given) == (2 if kernels else 0)
    got = both(fn, given, dy)
    want = both(lambda *a: plain(*a, unit_heads=heads, scale=scale), given,
                dy)
    # bfloat16: the plain form rounds every tap's product and the silu before
    # the norm, the kernels once at the output
    tol = 1e-5 if dtype == jnp.float32 else 3e-2
    for g, w in zip(got, want, strict=True):
        assert g.shape == w.shape and g.dtype == w.dtype
        g, w = (np.asarray(t, np.float32) for t in (g, w))
        np.testing.assert_allclose(g, w, rtol=tol, atol=tol * np.abs(w).max())


def test_no_position_reads_a_later_one_or_another_row():
    """Across a tile's edge too: a change at one position of one row moves
    that row from there on, nothing before it and nothing of the other."""
    seq, at = conv._SEQ_TILE + 24, conv._SEQ_TILE - 2
    (x, kernel), _ = operands(4, False, seq, 128, jnp.float32)
    moved = np.asarray(conv_silu(x.at[0, at].add(1.0), kernel)
                       - conv_silu(x, kernel))
    assert np.all(moved[1] == 0) and np.all(moved[0, :at] == 0)
    assert np.all(np.abs(moved[0, at:at + 4]).max(axis=-1) > 0)
    assert np.all(moved[0, at + 4:] == 0)


def test_a_sharded_mesh_gives_the_single_device_numbers():
    """Rows over ``dp``, channels (whole heads) over ``tp``: the call inside
    its ``shard_map`` on a CPU virtual mesh, forward and every gradient —
    the parameters' summed over ``dp`` —, equals one device's.  No chip has
    run this."""
    heads = 2
    given, dy = operands(4, True, 72, heads * 128, jnp.float32)

    def fn(*a):
        return conv_silu(*a, unit_heads=heads, scale=0.5)

    one = both(fn, given, dy)
    mesh = build_mesh(MeshConfig(dp=2, tp=2), devices=jax.devices()[:4])
    with jax.set_mesh(mesh):
        assert "shard_map" in str(jax.make_jaxpr(fn)(*given))
        many = jax.jit(lambda *a: both(fn, a[:-1], a[-1]))(*given, dy)
    for g, w in zip(many, one, strict=True):
        np.testing.assert_allclose(np.asarray(g), np.asarray(w), rtol=1e-5,
                                   atol=1e-6)

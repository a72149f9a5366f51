"""``models/llama.py::apply_rope`` — the rotation (and SDAR's per-head norm
before it) as one lane-dense pass, the half-swap a matmul by a signed
permutation — against the plain split / concatenate rotation, which lives
here only: values and gradients, the whole head, a part of it (Laguna's full
layers), a 64-wide head (Kimi-VL's rotary lanes and its shared key), tables
with an attention factor; the folded norm against ``nn.RMSNorm`` then the
plain rotation; and the parameter tree the fold must not move."""

import dataclasses

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_tpu.models.llama import (LlamaAttention, LlamaConfig, RopeTable,
                                  apply_rope, rope_table)

SEQ = 24
# name -> (head width, its table)
SHAPES = {
    "whole_128": (128, RopeTable(theta=1e4)),
    "half_table_128": (128, RopeTable(theta=5e5, rotary_fraction=0.5)),
    "quarter_table_128": (128, RopeTable(theta=5e5, rotary_fraction=0.25)),
    "head_64": (64, RopeTable(theta=5e4)),
    "attention_factor": (128, RopeTable(
        theta=5e5, factor=32.0, original_positions=16, rotary_fraction=0.5,
        attention_factor=1.3466)),
}


def plain_rope(x, cos, sin):
    """The rotation written out: halves split off, turned, joined."""
    rot = 2 * cos.shape[-1]
    x1, x2 = jnp.split(x[..., :rot].astype(jnp.float32), 2, axis=-1)
    turned = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin],
                             axis=-1).astype(x.dtype)
    return jnp.concatenate([turned, x[..., rot:]], axis=-1)


def _case(shape, dtype, heads=3):
    width, table = SHAPES[shape]
    cos, sin = rope_table(width, jnp.arange(SEQ) + 3, table)
    x, g = (jax.random.normal(jax.random.PRNGKey(seed),
                              (2, heads, SEQ, width), jnp.float32).astype(dtype)
            for seed in (0, 1))
    return x, g, cos, sin


@pytest.mark.parametrize("dtype", ["bfloat16", "float32"])
@pytest.mark.parametrize("shape", list(SHAPES))
def test_the_rotation_equals_the_plain_split_and_concatenate_form(shape,
                                                                  dtype):
    """Each product of the matmul is by 0 or +-1 and each output one term, so
    nothing is rounded that the plain form does not round: float32 to 1e-6,
    bfloat16 bit for bit."""
    x, _, cos, sin = _case(shape, dtype)
    got = apply_rope(x, cos, sin)
    want = plain_rope(x, cos, sin)
    assert got.dtype == x.dtype and got.shape == x.shape
    if dtype == "bfloat16":
        assert bool(jnp.all(got == want))
    else:
        np.testing.assert_allclose(got, want, atol=1e-6, rtol=0)
    # lanes past the tables pass as they are
    rot = 2 * cos.shape[-1]
    assert bool(jnp.all(got[..., rot:] == x[..., rot:]))
    if shape == "attention_factor":     # the tables are not a rotation's
        assert float(jnp.max(cos)) > 1.3


@pytest.mark.parametrize("dtype", ["bfloat16", "float32"])
@pytest.mark.parametrize("shape", list(SHAPES))
def test_the_rotations_gradient_equals_the_plain_forms(shape, dtype):
    """The backward is the opposite rotation of the cotangent, through the
    same pass: what autodiff makes of the plain form."""
    x, g, cos, sin = _case(shape, dtype)
    got, = jax.vjp(lambda x: apply_rope(x, cos, sin), x)[1](g)
    want, = jax.vjp(lambda x: plain_rope(x, cos, sin), x)[1](g)
    assert got.dtype == x.dtype
    if dtype == "bfloat16":
        assert bool(jnp.all(got == want))
    else:
        np.testing.assert_allclose(got, want, atol=1e-6, rtol=0)


def test_the_rotation_under_jit_with_shared_keys_shape():
    """Kimi-VL's one rotary key a position, (B, 1, S, 64), jitted."""
    x, _, cos, sin = _case("head_64", "bfloat16", heads=1)
    got = jax.jit(apply_rope)(x, cos, sin)
    assert bool(jnp.all(got == plain_rope(x, cos, sin)))


def _normed_then_turned(eps, cos, sin):
    norm = nn.RMSNorm(epsilon=eps, dtype=jnp.float32)
    return lambda x, scale: plain_rope(
        norm.apply({"params": {"scale": scale}}, x), cos, sin)


@pytest.mark.parametrize("shape", ["whole_128", "half_table_128", "head_64"])
def test_the_folded_head_norm_equals_rmsnorm_then_rope_in_float32(shape):
    """Values, and the gradients of ``x`` and of the scale."""
    x, g, cos, sin = _case(shape, "float32")
    scale = 1.0 + 0.3 * jax.random.normal(jax.random.PRNGKey(2),
                                          (x.shape[-1],))
    eps = 1e-5
    got, got_vjp = jax.vjp(
        lambda x, scale: apply_rope(x, cos, sin, scale, eps), x, scale)
    want, want_vjp = jax.vjp(_normed_then_turned(eps, cos, sin), x, scale)
    np.testing.assert_allclose(got, want, atol=2e-6, rtol=0)
    (dx, dscale), (want_dx, want_dscale) = got_vjp(g), want_vjp(g)
    np.testing.assert_allclose(dx, want_dx, atol=5e-6, rtol=0)
    np.testing.assert_allclose(dscale, want_dscale, rtol=1e-5, atol=1e-4)


def test_the_folded_head_norm_in_bfloat16_rounds_once():
    """bfloat16 in and out: the fold's result is the float32 computation's,
    rounded once — at least as near to it as norm, round, rotate, round."""
    x, _, cos, sin = _case("whole_128", "bfloat16")
    scale = 1.0 + 0.3 * jax.random.normal(jax.random.PRNGKey(2), (128,))
    exact = _normed_then_turned(1e-6, cos, sin)(x.astype(jnp.float32), scale)
    got = apply_rope(x, cos, sin, scale, 1e-6)
    assert got.dtype == jnp.bfloat16
    twice = plain_rope(nn.RMSNorm(epsilon=1e-6, dtype=jnp.bfloat16).apply(
        {"params": {"scale": scale}}, x), cos, sin)

    def off(y):
        return float(jnp.mean(jnp.abs(y.astype(jnp.float32) - exact)))

    assert off(got) <= off(twice)
    # one rounding: half a unit in the last place of bfloat16 at most
    np.testing.assert_allclose(got.astype(jnp.float32), exact, rtol=2 ** -8,
                               atol=1e-30)


@pytest.mark.parametrize("rope", [True, False])
def test_the_head_norms_parameters_keep_their_paths_and_shapes(rope):
    """``q_norm/scale`` and ``k_norm/scale``, ``head_dim`` wide, float32 ones
    at the start, as ``nn.RMSNorm`` made them: checkpoints and the reference
    check's weight mapping load as before.  Without RoPE the norm alone is
    applied (tables of no width)."""
    cfg = dataclasses.replace(
        LlamaConfig.tiny(), head_dim=32, qk_norm="head", rope=rope,
        dtype=jnp.float32, attention_impl="reference")
    layer = LlamaAttention(cfg)
    x = jax.random.normal(jax.random.PRNGKey(0), (2, SEQ, 64), jnp.float32)
    p = jax.jit(layer.init)(jax.random.PRNGKey(1), x, jnp.arange(SEQ))[
        "params"]
    assert sorted(p) == ["k_norm", "q_norm", "wk", "wo", "wq", "wv"]
    for name in ("q_norm", "k_norm"):
        assert list(p[name]) == ["scale"]
        assert p[name]["scale"].shape == (32,)
        assert p[name]["scale"].dtype == jnp.float32
        assert bool(jnp.all(p[name]["scale"] == 1.0))
    # the scales are used: the output moves with them, and they get gradients
    grads = jax.jit(jax.grad(lambda p: jnp.sum(
        layer.apply({"params": p}, x, jnp.arange(SEQ)) ** 2)))(p)
    for name in ("q_norm", "k_norm"):
        assert float(jnp.max(jnp.abs(grads[name]["scale"]))) > 0


def test_no_split_or_concatenate_rotation_is_left_in_the_program():
    """One implementation: the jaxpr of a rotation holds the matmul and no
    ``split`` or ``concatenate`` of the head (the tables are widened by
    ``pad``)."""
    x, _, cos, sin = _case("half_table_128", "bfloat16")
    text = str(jax.make_jaxpr(lambda x: apply_rope(x, cos, sin))(x))
    assert "dot_general" in text
    assert "split" not in text and "concatenate" not in text

"""``models/llama.py::apply_rope`` — the rotation (and SDAR's per-head norm
before it) as one lane-dense pass, the half-swap a matmul by a signed
permutation — against the plain split / concatenate rotation, which lives
here only: values and gradients, the whole head, a part of it (Laguna's full
layers), a 64-wide head (Kimi-VL's rotary lanes and its shared key), tables
with an attention factor; the folded norm against ``nn.RMSNorm`` then the
plain rotation; and the parameter tree the fold must not move.  Every case
also runs as ``form="kernels"``: ``ops/rope.py::rope_qk`` — the same pass as
one Pallas call a direction over q and k where ``wq`` / ``wk`` wrote them —
under the interpreter, the case's heads cut into q's (two thirds) and k's;
and the kernels' trace is held to its budget: one trace of a body a distinct
shape, a body of a few dozen equations whatever the heads and the rows."""

import dataclasses

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_tpu.models.llama import (LlamaAttention, LlamaConfig, RopeTable,
                                  apply_rope, rope_table)
from ray_tpu.ops import rope as rope_kernels
from ray_tpu.parallel.mesh import MeshConfig, build_mesh

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


FORMS = ["pass", "kernels"]


def _tokens(a):
    """(B, H, S, D) -> (B, S, H * D), as a projection writes its heads."""
    return a.transpose(0, 2, 1, 3).reshape(a.shape[0], a.shape[2], -1)


def _heads(a, width):
    return a.reshape(*a.shape[:2], -1, width).transpose(0, 2, 1, 3)


def _turned(form, x, cos, sin, scale=None, eps=1e-6, k_scale=None):
    """``apply_rope`` of x (B, H, S, D) — or, ``form="kernels"``, ``rope_qk``
    of its first two thirds of heads as q and the rest as k, H != KV, each
    (B, S, heads * D), under the interpreter (24 positions: no whole block)."""
    if form == "pass":
        return apply_rope(x, cos, sin, scale, eps)
    width, n_q = x.shape[-1], x.shape[1] * 2 // 3
    q, k = _tokens(x[:, :n_q]), _tokens(x[:, n_q:])
    assert rope_kernels.takes(q.shape, k.shape, width, 2 * cos.shape[-1])
    q, k = rope_kernels.rope_qk(q, k, cos, sin, scale,
                        scale if k_scale is None else k_scale, eps,
                        head_dim=width)
    return jnp.concatenate([_heads(q, width), _heads(k, width)], axis=1)


def _same(got, want, bit_for_bit: bool):
    """Equal: bit for bit, or to 1e-6 — a float32's rounding, and under the
    interpreter a bfloat16 result's too where two terms cancel to a value
    that small (the CPU contracts one form's multiply-add and not the
    other's: one element of 18,432 in ``head_64``)."""
    if bit_for_bit:
        assert bool(jnp.all(got == want))
    else:
        np.testing.assert_allclose(got.astype(jnp.float32),
                                   want.astype(jnp.float32), atol=1e-6, rtol=0)


def _case(shape, dtype, heads=3, form="pass"):
    width, table = SHAPES[shape]
    if form == "kernels":   # k's heads fill a block of 128 lanes, q's two
        heads = 3 * (128 // width)
    cos, sin = rope_table(width, jnp.arange(SEQ) + 3, table)
    x, g = (jax.random.normal(jax.random.PRNGKey(seed),
                              (2, heads, SEQ, width), jnp.float32).astype(dtype)
            for seed in (0, 1))
    return x, g, cos, sin


@pytest.mark.parametrize("form", FORMS)
@pytest.mark.parametrize("dtype", ["bfloat16", "float32"])
@pytest.mark.parametrize("shape", list(SHAPES))
def test_the_rotation_equals_the_plain_split_and_concatenate_form(shape,
                                                                  dtype, form):
    """Each product of the matmul is by 0 or +-1 and each output one term, so
    nothing is rounded that the plain form does not round: float32 to 1e-6,
    bfloat16 bit for bit."""
    x, _, cos, sin = _case(shape, dtype, form=form)
    got = _turned(form, x, cos, sin)
    want = plain_rope(x, cos, sin)
    assert got.dtype == x.dtype and got.shape == x.shape
    _same(got, want, bit_for_bit=dtype == "bfloat16" and form == "pass")
    # lanes past the tables pass as they are
    rot = 2 * cos.shape[-1]
    assert bool(jnp.all(got[..., rot:] == x[..., rot:]))
    if shape == "attention_factor":     # the tables are not a rotation's
        assert float(jnp.max(cos)) > 1.3


@pytest.mark.parametrize("form", FORMS)
@pytest.mark.parametrize("dtype", ["bfloat16", "float32"])
@pytest.mark.parametrize("shape", list(SHAPES))
def test_the_rotations_gradient_equals_the_plain_forms(shape, dtype, form):
    """The backward is the opposite rotation of the cotangent, through the
    same pass (the kernels': the forward's own call at the negated sine): what
    autodiff makes of the plain form."""
    x, g, cos, sin = _case(shape, dtype, form=form)
    got, = jax.vjp(lambda x: _turned(form, x, cos, sin), x)[1](g)
    want, = jax.vjp(lambda x: plain_rope(x, cos, sin), x)[1](g)
    assert got.dtype == x.dtype
    _same(got, want, bit_for_bit=dtype == "bfloat16" and form == "pass")


def test_the_rotation_under_jit_with_shared_keys_shape():
    """Kimi-VL's one rotary key a position, (B, 1, S, 64), jitted."""
    x, _, cos, sin = _case("head_64", "bfloat16", heads=1)
    got = jax.jit(apply_rope)(x, cos, sin)
    assert bool(jnp.all(got == plain_rope(x, cos, sin)))


def _normed_then_turned(eps, cos, sin):
    norm = nn.RMSNorm(epsilon=eps, dtype=jnp.float32)
    return lambda x, scale: plain_rope(
        norm.apply({"params": {"scale": scale}}, x), cos, sin)


@pytest.mark.parametrize("form", FORMS)
@pytest.mark.parametrize("shape", ["whole_128", "half_table_128", "head_64"])
def test_the_folded_head_norm_equals_rmsnorm_then_rope_in_float32(shape,
                                                                  form):
    """Values, and the gradients of ``x`` and of the scale."""
    x, g, cos, sin = _case(shape, "float32", form=form)
    scale = 1.0 + 0.3 * jax.random.normal(jax.random.PRNGKey(2),
                                          (x.shape[-1],))
    eps = 1e-5
    got, got_vjp = jax.vjp(
        lambda x, scale: _turned(form, x, cos, sin, scale, eps), x, scale)
    want, want_vjp = jax.vjp(_normed_then_turned(eps, cos, sin), x, scale)
    np.testing.assert_allclose(got, want, atol=2e-6, rtol=0)
    (dx, dscale), (want_dx, want_dscale) = got_vjp(g), want_vjp(g)
    np.testing.assert_allclose(dx, want_dx, atol=5e-6, rtol=0)
    np.testing.assert_allclose(dscale, want_dscale, rtol=1e-5, atol=1e-4)


@pytest.mark.parametrize("form", FORMS)
def test_the_folded_head_norm_in_bfloat16_rounds_once(form):
    """bfloat16 in and out: the fold's result is the float32 computation's,
    rounded once — at least as near to it as norm, round, rotate, round."""
    x, _, cos, sin = _case("whole_128", "bfloat16", form=form)
    scale = 1.0 + 0.3 * jax.random.normal(jax.random.PRNGKey(2), (128,))
    exact = _normed_then_turned(1e-6, cos, sin)(x.astype(jnp.float32), scale)
    got = _turned(form, x, cos, sin, scale, 1e-6)
    assert got.dtype == jnp.bfloat16
    twice = plain_rope(nn.RMSNorm(epsilon=1e-6, dtype=jnp.bfloat16).apply(
        {"params": {"scale": scale}}, x), cos, sin)

    def off(y):
        return float(jnp.mean(jnp.abs(y.astype(jnp.float32) - exact)))

    assert off(got) <= off(twice)
    # one rounding: half a unit in the last place of bfloat16 at most
    np.testing.assert_allclose(got.astype(jnp.float32), exact, rtol=2 ** -8,
                               atol=1e-30)


def _kernel_calls(jaxpr):
    """The ``pallas_call`` equations of a jaxpr, those of its sub-jaxprs
    too."""
    found = []
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "pallas_call":
            found.append(eqn)
        for sub in jax.core.jaxprs_in_params(eqn.params):
            found += _kernel_calls(sub)
    return found


@pytest.mark.parametrize("head_dim", [32, 64])
@pytest.mark.parametrize("rope", [True, False])
def test_the_head_norms_parameters_keep_their_paths_and_shapes(rope,
                                                               head_dim):
    """``q_norm/scale`` and ``k_norm/scale``, ``head_dim`` wide, float32 ones
    at the start, as ``nn.RMSNorm`` made them: checkpoints and the reference
    check's weight mapping load as before.  Without RoPE the norm alone is
    applied (tables of no width).  Four heads of 64 over two are whole blocks
    of 128 lanes and take the kernels' one call a direction; heads of 32
    leave k half a block, ``takes`` says no, and the XLA pass runs."""
    cfg = dataclasses.replace(
        LlamaConfig.tiny(), head_dim=head_dim, qk_norm="head", rope=rope,
        dtype=jnp.float32, attention_impl="reference")
    layer = LlamaAttention(cfg)
    x = jax.random.normal(jax.random.PRNGKey(0), (2, SEQ, 64), jnp.float32)
    p = jax.jit(layer.init)(jax.random.PRNGKey(1), x, jnp.arange(SEQ))[
        "params"]
    assert sorted(p) == ["k_norm", "q_norm", "wk", "wo", "wq", "wv"]
    for name in ("q_norm", "k_norm"):
        assert list(p[name]) == ["scale"]
        assert p[name]["scale"].shape == (head_dim,)
        assert p[name]["scale"].dtype == jnp.float32
        assert bool(jnp.all(p[name]["scale"] == 1.0))

    def loss(p):
        return jnp.sum(layer.apply({"params": p}, x, jnp.arange(SEQ)) ** 2)

    # which pass runs is the shapes' to say, and the program shows it
    whole = head_dim == 64
    assert rope_kernels.takes((2, SEQ, 4 * head_dim), (2, SEQ, 2 * head_dim),
                              head_dim, head_dim if rope else 0) == whole
    calls = _kernel_calls(jax.make_jaxpr(jax.grad(loss))(p).jaxpr)
    assert [c.params["name"] for c in calls] == (
        ["rope_fwd", "rope_bwd"] if whole else [])
    # the scales are used: the output moves with them, and they get gradients
    grads = jax.jit(jax.grad(loss))(p)
    for name in ("q_norm", "k_norm"):
        assert float(jnp.max(jnp.abs(grads[name]["scale"]))) > 0


@pytest.mark.parametrize("dtype", ["bfloat16", "float32"])
@pytest.mark.parametrize("shape", ["whole_128", "half_table_128", "head_64"])
def test_the_kernels_norm_q_and_k_each_under_its_own_scale(shape, dtype):
    """One call, two scales: q's heads under ``q_norm``'s and k's under
    ``k_norm``'s, against ``apply_rope`` of each (``_norm_rotate``) — values,
    and the gradients of ``x`` and of both scales."""
    x, g, cos, sin = _case(shape, dtype, form="kernels")
    n_q = x.shape[1] * 2 // 3
    scales = [1.0 + 0.3 * jax.random.normal(jax.random.PRNGKey(seed),
                                            (x.shape[-1],)) for seed in (2, 3)]

    def kernels(x, q_scale, k_scale):
        return _turned("kernels", x, cos, sin, q_scale, 1e-5, k_scale)

    def passes(x, q_scale, k_scale):
        return jnp.concatenate([
            apply_rope(x[:, :n_q], cos, sin, q_scale, 1e-5),
            apply_rope(x[:, n_q:], cos, sin, k_scale, 1e-5)], axis=1)

    got, got_vjp = jax.vjp(kernels, x, *scales)
    want, want_vjp = jax.vjp(passes, x, *scales)
    assert got.dtype == x.dtype
    # float32: the sums are taken in another order; bfloat16: one rounding
    # of values that differ so
    tol = dict(atol=5e-6, rtol=0) if dtype == "float32" else \
        dict(atol=1e-30, rtol=2 ** -7)
    np.testing.assert_allclose(got.astype(jnp.float32),
                               want.astype(jnp.float32), **tol)
    for a, b in zip(got_vjp(g), want_vjp(g)):
        assert a.dtype == b.dtype and a.shape == b.shape
        if a.ndim == 1:     # a scale's: float32 sums over every row and head
            np.testing.assert_allclose(a, b, rtol=2e-3 if dtype == "bfloat16"
                                       else 1e-5, atol=1e-2 if dtype ==
                                       "bfloat16" else 1e-4)
        else:
            np.testing.assert_allclose(
                a.astype(jnp.float32), b.astype(jnp.float32),
                **(dict(atol=1e-5, rtol=0) if dtype == "float32"
                   else dict(atol=2 ** -6, rtol=2 ** -6)))


@pytest.mark.parametrize("normed", [True, False])
def test_the_kernels_on_a_sharded_mesh_give_one_devices_numbers(normed):
    """Rows over dp and fsdp, whole blocks of heads over tp: the calls inside
    their ``shard_map`` on a CPU virtual mesh, forward and every gradient —
    the scales' summed over the mesh —, equal one device's; a ``tp`` that
    would cut a block of 128 lanes, a sequence cut over ``sp`` and a batch
    that the rows' axes do not divide are refused (``takes``), and the caller
    runs the XLA pass.  The chip's own four run this under ``fsdp=4``
    (``mistral-fsdp4-s4k``)."""
    x, g, cos, sin = _case("head_64", "float32", form="kernels")
    # four rows; eight heads of 64 for q and four for k: two blocks a device
    x, g = (jnp.concatenate([a, a[::-1]]) for a in (x, g))
    x, g = (jnp.concatenate([a, 0.5 * a], axis=1) for a in (x, g))
    scale = 1.0 + 0.3 * jax.random.normal(jax.random.PRNGKey(2), (64,))

    def both(x, g, scale):
        out, vjp = jax.vjp(lambda x, scale: _turned(
            "kernels", x, cos, sin, scale if normed else None), x, scale)
        return out, vjp(g)

    one = both(x, g, scale)
    mesh = build_mesh(MeshConfig(dp=2, fsdp=2, tp=2))
    with jax.set_mesh(mesh):
        jaxpr = str(jax.make_jaxpr(both)(x, g, scale))
        assert "shard_map" in jaxpr and "pallas_call" in jaxpr
        many = jax.jit(both)(x, g, scale)
        # q's four heads of 64 are two blocks, k's two one: tp=2 cuts it
        shapes = ((4, SEQ, 256), (4, SEQ, 128))
        assert rope_kernels.takes((4, SEQ, 512), (4, SEQ, 256), 64, 64)
        assert not rope_kernels.takes(*shapes, 64, 64)
        assert not rope_kernels.takes((3, SEQ, 512), (3, SEQ, 256), 64, 64)
    with jax.set_mesh(build_mesh(MeshConfig(dp=4, sp=2))):
        assert not rope_kernels.takes((4, SEQ, 512), (4, SEQ, 256), 64, 64)
    assert rope_kernels.takes(*shapes, 64, 64)      # one device: whole
    for got, want in zip(jax.tree.leaves(many), jax.tree.leaves(one),
                         strict=True):
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=1e-5, atol=1e-5 * np.abs(want).max())


def _equations(jaxpr) -> int:
    """The equations of a jaxpr and of every jaxpr inside it."""
    return sum(1 + sum(_equations(sub) for sub in
                       jax.core.jaxprs_in_params(eqn.params))
               for eqn in jaxpr.eqns)


def _body_equations(fn, *operands) -> dict:
    """name -> equations of the kernel body of each Mosaic call ``fn``
    makes."""
    return {c.params["name"]: _equations(c.params["jaxpr"]) for c in
            _kernel_calls(jax.make_jaxpr(fn)(*operands).jaxpr)}


# the most equations a kernel's body may have, q's and k's copies together
# (under the norm the forward reads 144 and the backward 240, the rotation
# alone 65 for both directions; PR 69's normed backward read 704 an operand at
# 2,048 rows, a block's 256 registers of ``d_scale`` written out, and grew
# with the rows)
BODY_EQUATIONS = 300


@pytest.mark.parametrize("normed", [True, False])
def test_the_trace_budget_one_trace_a_shape_and_a_short_body(normed,
                                                             monkeypatch):
    """What a start pays for the kernels, without a clock (``PERF.md``, PR
    70: PR 69's pair cost six seconds of ``setup_trace_s``): ``value_and_grad``
    of four attention layers, each under remat, enters each kernel's body
    once — not once a layer, a recomputation or an operand; the rotation alone
    has one body for both directions (twice the forward's in all: see below)
    —, and a body is under
    ``BODY_EQUATIONS`` equations, the same number at four times the heads and
    sixteen times the rows: nothing is written out a head, a column or a
    row."""
    cfg = dataclasses.replace(
        LlamaConfig.tiny(), d_model=128, n_head=4, n_kv_head=2, head_dim=64,
        qk_norm="head" if normed else False, attention_impl="reference")

    class Stack(nn.Module):
        @nn.compact
        def __call__(self, x, positions):
            for i in range(4):
                x = x + nn.remat(LlamaAttention)(cfg, name=f"h_{i}")(
                    x, positions)
            return x

    entered = {"_fwd_kernel": 0, "_bwd_kernel": 0}
    for name in entered:
        def counted(*refs, _body=getattr(rope_kernels, name), _name=name,
                    **static):
            entered[_name] += 1
            return _body(*refs, **static)
        monkeypatch.setattr(rope_kernels, name, counted)
    x = jnp.ones((3, 40, 128), jnp.bfloat16)
    positions = jnp.arange(40)
    params = jax.eval_shape(Stack().init, jax.random.PRNGKey(0), x, positions)
    # (the entries' own cache: ``init`` has traced these shapes, and an
    # earlier test may have)
    rope_kernels._forward.clear_cache()
    rope_kernels._backward.clear_cache()
    entered.update(_fwd_kernel=0, _bwd_kernel=0)
    traced = jax.make_jaxpr(jax.value_and_grad(lambda p: jnp.sum(
        Stack().apply(p, x, positions).astype(jnp.float32))))(params)
    calls = [c.params["name"] for c in _kernel_calls(traced.jaxpr)]
    # a layer: forward, again under remat, backward
    assert sorted(calls) == sorted(
        ["rope_fwd"] * 8 + ["rope_bwd" if normed else "rope_fwd"] * 4), calls
    # twice: remat's own trace of a block and that trace's differentiation
    # differ in JAX's trace context (no mesh / an empty one), the cache's key
    assert entered == {"_fwd_kernel": 2, "_bwd_kernel": int(normed)}, entered
    rope_kernels._forward.clear_cache()
    rope_kernels._backward.clear_cache()

    def both_ways(heads, rows):
        q = jnp.ones((1, rows, 2 * heads * 64), jnp.bfloat16)
        k = jnp.ones((1, rows, heads * 64), jnp.bfloat16)
        cos, sin = rope_table(64, jnp.arange(rows), RopeTable())
        scale = jnp.ones((64,)) if normed else None

        def pair(q, k):
            out, vjp = jax.vjp(lambda q, k: rope_kernels.rope_qk(
                q, k, cos, sin, scale, scale, head_dim=64), q, k)
            return vjp(out)
        return _body_equations(pair, q, k)

    small, large = both_ways(2, 32), both_ways(8, 512)
    assert small == large, (small, large)
    assert set(small) == ({"rope_fwd", "rope_bwd"} if normed
                          else {"rope_fwd"})
    assert max(small.values()) < BODY_EQUATIONS, small


def test_no_split_or_concatenate_rotation_is_left_in_the_program():
    """One implementation: the jaxpr of a rotation holds the matmul and no
    ``split`` or ``concatenate`` of the head (the tables are widened by
    ``pad``)."""
    x, _, cos, sin = _case("half_table_128", "bfloat16")
    text = str(jax.make_jaxpr(lambda x: apply_rope(x, cos, sin))(x))
    assert "dot_general" in text
    assert "split" not in text and "concatenate" not in text

"""A sigmoid gate a head a query on the attention output, taken inside the
flash kernels (``ops/attention.py``: ``_Tiles.gated``; ``attention``'s and
``flash_attention``'s ``gate``, the logits as (B, S, H)): the forward's last
step multiplies where it divided, the backward kernel meets the gates in the
logsumexp and ``delta`` it is handed, and the logits' gradient is read off
``delta``.  Every case runs the call (Pallas
interpreter) against ``mha_reference`` times ``sigmoid(gate)`` under
``jax.vjp``: the output, dq, dk, dv and the gate's gradient.  Then the entry:
``attention`` under each implementation says the same thing, and under a
``dp x tp`` mesh the gate's heads are cut as the operands' are.  That a call
without a gate is the program it was is ``test_pinned_steps.py``'s to say."""

import functools

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import Mesh

from ray_tpu.ops.attention import (attention, block_diffusion_mask,
                                   flash_attention, mha_reference)

SEQ = 256
_TILES = dict(block_q=128, block_k=128)

# name -> (query heads, key/value heads, head width, length, the call's
#          keywords, the reference's, which of q, k, v are rank 3, dtype)
CASES = {
    "causal": (2, 2, 128, SEQ, {}, {}, "", jnp.float32),
    "causal-bf16": (2, 2, 128, SEQ, {}, {}, "qkv", jnp.bfloat16),
    "causal-many-tiles": (2, 2, 128, 384, _TILES, {}, "qkv", jnp.float32),
    # a length that is no multiple of the tile: the gates' rows are padded
    "causal-padded": (2, 2, 128, 264, {}, {}, "v", jnp.float32),
    "window": (2, 2, 128, 384, dict(window=100, **_TILES), dict(window=100),
               "", jnp.float32),
    "window-padded-bf16": (2, 2, 128, 264, dict(window=100), dict(window=100),
                           "qkv", jnp.bfloat16),
    # Laguna's calls: q and k rotated, as heads; v as projected; eight (six)
    # query heads a key/value head, the group's dQ in VMEM
    "causal-rep4": (8, 2, 128, 384, _TILES, {}, "v", jnp.float32),
    "window-rep8": (8, 1, 128, 264, dict(window=100), dict(window=100), "v",
                    jnp.float32),
    "window-rep4-rank-3-bf16": (4, 1, 128, 264, dict(window=100),
                                dict(window=100), "qkv", jnp.bfloat16),
    # two 64-wide heads a grid step: two rows of gates a block
    "causal-d64-pair": (4, 4, 64, SEQ, {}, {}, "qkv", jnp.float32),
    # any width as (B, H, S, D)
    "causal-d32": (2, 2, 32, SEQ, {}, {}, "qkv", jnp.float32),
    # the block mask lays a statistic a query out as two copies
    "block-mask": (2, 2, 128, 2 * 192,
                   dict(causal=False, diffusion_block=4, **_TILES),
                   dict(causal=False, mask=block_diffusion_mask(192, 4)),
                   "v", jnp.float32),
}


def _tokens(x):
    b, h, s, d = x.shape
    return x.transpose(0, 2, 1, 3).reshape(b, s, h * d)


def _operands(h, n_kv, d, s, dtype, b=2):
    """q, k, v, the output's cotangent (B, S, H * D) and the gate's logits,
    of which two are far out: a gate that underflows and one that is 1."""
    keys = jax.random.split(jax.random.PRNGKey(62), 5)
    q, k, v, g = (jax.random.normal(key, (b, heads, s, d), dtype)
                  for key, heads in zip(keys, (h, n_kv, n_kv, h)))
    gate = 2.0 * jax.random.normal(keys[4], (b, s, h), dtype)
    gate = gate.at[0, 3, 1].set(-100.0).at[1, s - 1, 0].set(100.0)
    return q, k, v, _tokens(g), gate


def _gated_reference(q, k, v, gate, **kw):
    rep = q.shape[1] // k.shape[1]
    k, v = (jnp.repeat(x, rep, axis=1) for x in (k, v))
    out = mha_reference(q, k, v, **kw).astype(jnp.float32)
    return _tokens(out * jax.nn.sigmoid(gate.astype(jnp.float32)).transpose(
        0, 2, 1)[..., None]).astype(q.dtype)


def _close(got, want, dtype, what):
    got, want = (np.asarray(x, np.float32) for x in (got, want))
    assert np.isfinite(got).all(), what
    if dtype == jnp.bfloat16:   # the last place of a bf16 value
        np.testing.assert_allclose(got, want, rtol=2 ** -6, atol=4e-2,
                                   err_msg=what)
    else:
        np.testing.assert_allclose(got, want, rtol=1e-5, atol=3e-5,
                                   err_msg=what)


@pytest.mark.parametrize("case", list(CASES))
def test_gated_kernels_against_the_reference_times_the_gate(case):
    h, n_kv, d, s, kw, ref_kw, rank3, dtype = CASES[case]
    q, k, v, g, gate = _operands(h, n_kv, d, s, dtype)

    def kernels(q, k, v, gate):
        q, k, v = (_tokens(x) if n in rank3 else x
                   for n, x in zip("qkv", (q, k, v)))
        return flash_attention(q, k, v, head_dim=d, tokens_out=True,
                               gate=gate, **kw)

    def both(f):
        out, vjp = jax.vjp(f, q, k, v, gate)
        return out, vjp(g)

    (got, got_grads), (want, want_grads) = (
        jax.jit(functools.partial(both, f))() for f in (
            kernels, functools.partial(_gated_reference, **ref_kw)))
    assert got.shape == (2, s, h * d) and got.dtype == dtype
    _close(got, want, dtype, "out")
    for name, a, b in zip(("dq", "dk", "dv", "dgate"), got_grads, want_grads):
        assert a.shape == b.shape and a.dtype == b.dtype, name
        _close(a, b, dtype, name)
    # a gate that underflows: its head's output is 0 and its logit's
    # gradient 0, not a NaN from a division by the gate
    width = got.shape[-1] // h
    assert not np.asarray(got[0, 3, width:2 * width], np.float32).any()
    assert abs(float(got_grads[3][0, 3, 1])) < 1e-30


def test_the_gates_rows_reach_the_kernels_and_nothing_as_wide_as_the_output():
    """What the gate adds to a call: one (b, h, 1, s) float32 operand of the
    forward kernel, none of the backward's, and outside them nothing of the
    output's size that the ungated call has not."""
    h, d, s = 4, 128, SEQ
    q, k, v, g, gate = _operands(h, h, d, s, jnp.float32)

    def program(gate):
        def both(q, k, v):
            f = lambda q, k, v: flash_attention(    # noqa: E731
                _tokens(q), _tokens(k), _tokens(v), head_dim=d, gate=gate)
            out, vjp = jax.vjp(f, q, k, v)
            return out, vjp(g)
        return jax.make_jaxpr(both)(q, k, v).jaxpr

    def walk(jaxpr, kernels, wide):
        for eqn in jaxpr.eqns:
            if eqn.primitive.name == "pallas_call":
                kernels.setdefault(eqn.params["name"], []).append(
                    [x.aval.shape for x in eqn.invars])
                continue
            subs = jax.core.jaxprs_in_params(eqn.params)
            for sub in subs:
                walk(sub, kernels, wide)
            if not subs:
                wide.append(sum(x.aval.size >= 2 * s * h * d
                                for x in eqn.outvars))
        return kernels, sum(wide)

    (with_gate, wide_with), (without, wide_without) = (
        walk(program(x), {}, []) for x in (gate, None))
    for name, rows in (("flash_fwd", [(2, h, 1, s)]), ("flash_bwd", [])):
        (gated,), (plain,) = with_gate[name], without[name]
        extra = list(gated)
        for shape in plain:
            extra.remove(shape)
        assert extra == rows, (name, extra)
    assert wide_with == wide_without, (wide_with, wide_without)


@pytest.mark.parametrize("kind", ["full", "window", "grouped-window"])
def test_every_implementation_of_the_entry_says_the_same(kind):
    """``attention(..., gate=...)``: the kernels against "reference", whose
    multiply after the call is the statement of what a gate is."""
    h, n_kv, window = {"full": (2, 2, 0), "window": (2, 2, 100),
                       "grouped-window": (4, 1, 100)}[kind]
    d, s = 128, SEQ
    q, k, v, g, gate = _operands(h, n_kv, d, s, jnp.float32)

    def both(impl):
        f = functools.partial(attention, impl=impl, window=window)
        out, vjp = jax.vjp(lambda q, k, v, gate: f(
            q, k, _tokens(v), gate=gate), q, k, v, gate)
        return out, vjp(g)

    (got, got_grads), (want, want_grads) = (
        jax.jit(functools.partial(both, impl))()
        for impl in ("flash", "reference"))
    _close(got, want, jnp.float32, "out")
    for name, a, b in zip(("dq", "dk", "dv", "dgate"), got_grads, want_grads):
        _close(a, b, jnp.float32, name)


def test_under_a_tp_mesh_the_gates_heads_are_cut_as_the_operands_are():
    """dp=2 x tp=2 on four CPU devices: each device's call sees its half of
    the heads and that half's gates."""
    h, d, s = 4, 128, SEQ
    q, k, v, g, gate = _operands(h, h, d, s, jnp.float32)

    def both(q, k, v, gate):
        out, vjp = jax.vjp(lambda *a: flash_attention(
            *map(_tokens, a[:3]), head_dim=d, gate=a[3]), q, k, v, gate)
        return out, vjp(g)

    want, want_grads = jax.jit(both)(q, k, v, gate)
    mesh = Mesh(np.asarray(jax.devices()[:4]).reshape(2, 2), ("dp", "tp"))
    with jax.set_mesh(mesh):
        got, got_grads = jax.jit(both)(q, k, v, gate)
    np.testing.assert_allclose(got, want, rtol=1e-6, atol=1e-6)
    for name, a, b in zip(("dq", "dk", "dv", "dgate"), got_grads, want_grads):
        np.testing.assert_allclose(a, b, rtol=1e-5, atol=2e-5, err_msg=name)

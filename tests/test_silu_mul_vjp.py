"""``models/moe.py::silu_mul``'s differentiation rule against ``jax.grad`` of
the plain expression, alone and through the two dense SwiGLU modules that call
it (``models/llama.py::SwiGLU``, ``models/moe.py::SharedSwiGLU``), and the
tree those modules initialise: names, shapes, dtypes and values as before, so
checkpoints load."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_tpu.models.llama import LlamaConfig, SwiGLU
from ray_tpu.models.moe import SharedSwiGLU, silu_mul


def _close(got, want, dtype):
    """float32: round-off.  bf16: an ulp of the largest element — the rule's
    float32 inside rounds once where autodiff rounds every product."""
    tol = 2e-5 if dtype == jnp.float32 else 2 ** -7
    for a, b in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
        a, b = np.asarray(a, np.float32), np.asarray(b, np.float32)
        assert a.shape == b.shape
        assert np.abs(a - b).max() <= tol * np.abs(b).max()


def _both_ways(f, operands, g, checkpointed):
    if checkpointed:
        f = jax.checkpoint(f)
    out, vjp = jax.vjp(f, *operands)
    return out, vjp(g)


@pytest.mark.parametrize("shape", [(2, 16, 256), (1, 7, 384)],
                         ids=["2x16x256", "ragged_1x7x384"])
@pytest.mark.parametrize("checkpointed", [False, True],
                         ids=["plain", "checkpoint"])
@pytest.mark.parametrize("dtype", [jnp.bfloat16, jnp.float32],
                         ids=["bf16", "f32"])
def test_rule_matches_autodiff_of_the_plain_expression(dtype, checkpointed,
                                                       shape):
    keys = jax.random.split(jax.random.PRNGKey(sum(shape)), 3)
    gate, up, g = ((2.0 * jax.random.normal(k, shape)).astype(dtype)
                   for k in keys)
    plain = lambda a, b: jax.nn.silu(a) * b  # noqa: E731
    out, grads = jax.jit(lambda: _both_ways(silu_mul, (gate, up), g,
                                            checkpointed))()
    assert [a.dtype for a in (out, *grads)] == [dtype] * 3
    tol = 2e-5 if dtype == jnp.float32 else 2 ** -8

    def close(a, b):
        a, b = np.asarray(a, np.float32), np.asarray(b, np.float32)
        assert np.abs(a - b).max() <= tol * np.abs(b).max()

    # the forward is the expression itself (to how XLA fuses it on the CPU)
    close(out, plain(gate, up))
    # the gradients: the float32 derivative at the same operands, rounded
    # once (autodiff in bf16 rounds every product: 1.2e-2 of the largest
    # element off it here, the rule 1.9e-3)
    exact = jax.jit(lambda: _both_ways(
        plain, (gate.astype(jnp.float32), up.astype(jnp.float32)),
        g.astype(jnp.float32), checkpointed))()[1]
    for a, b in zip(grads, exact):
        close(a, b)


def _dense_swiglu(kind, d_model, d_ff, dtype):
    """(module, plain): a dense SwiGLU of the model code and the same three
    matmuls with ``silu * up`` left to autodiff, over the module's tree."""
    module = SwiGLU(LlamaConfig(d_model=d_model, d_ff=d_ff, dtype=dtype)) \
        if kind == "SwiGLU" else SharedSwiGLU(d_model, d_ff, dtype)

    def plain(variables, x):
        p = jax.tree.map(lambda a: a.astype(dtype), variables["params"])
        x = x.astype(dtype)
        return (jax.nn.silu(x @ p["gate_proj"]["kernel"])
                * (x @ p["up_proj"]["kernel"])) @ p["down_proj"]["kernel"]

    return module, plain


@pytest.mark.parametrize("checkpointed", [False, True],
                         ids=["plain", "checkpoint"])
@pytest.mark.parametrize("dtype", [jnp.bfloat16, jnp.float32],
                         ids=["bf16", "f32"])
@pytest.mark.parametrize("kind", ["SwiGLU", "SharedSwiGLU"])
def test_the_dense_swiglus_keep_their_tree_and_their_gradients(
        kind, dtype, checkpointed):
    module, plain = _dense_swiglu(kind, 128, 384, dtype)
    kx, kp, kg = jax.random.split(jax.random.PRNGKey(3), 3)
    x = jax.random.normal(kx, (2, 7, 128)).astype(dtype)
    g = jax.random.normal(kg, (2, 7, 128)).astype(dtype)
    variables = module.init(kp, x)
    # three bias-free Dense children, float32 kernels (in, out): what the
    # checkpoints hold
    assert {name: (tuple(child), child["kernel"].shape, child["kernel"].dtype)
            for name, child in variables["params"].items()} == {
        "gate_proj": (("kernel",), (128, 384), jnp.float32),
        "up_proj": (("kernel",), (128, 384), jnp.float32),
        "down_proj": (("kernel",), (384, 128), jnp.float32)}
    with jax.default_matmul_precision("highest"):
        got = jax.jit(lambda: _both_ways(module.apply, (variables, x), g,
                                         checkpointed))()
        want = jax.jit(lambda: _both_ways(plain, (variables, x), g,
                                          checkpointed))()
    _close(got, want, dtype)

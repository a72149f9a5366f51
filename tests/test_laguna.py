"""Laguna-XS.2's block through the shared Llama block, at toy widths on the
CPU, with seeded weights moved off their initial values: the program
(``models/llama.py`` with a kind, a head count and a rotary table a layer, the
gate on the attention's output, ``mlp_types``; ``models/moe.py`` with sigmoid
scores, the routed scale, the shared expert and a part of the experts held;
``ops/attention.py``'s window) against the plain reference of
``perfbench/harness/families/laguna.py`` — dense masks from indices, the
rotary tables written out, every held expert on every token.  The toy
(``perfbench/tests/toy/toy-laguna.json``): 64 wide, heads of 16, 12 / 16 query
heads over 4 key/value heads, layers full + dense, sliding + sparse twice,
full + sparse, a window of 8 under sequences of 64, 16 experts of 32 of which
4 are held (chip 1 of 4), top-4.  On the chip the same reference runs at
published widths against the bf16 program (``perfbench/harness/agreement.py``).
"""

import dataclasses
import functools
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from perfbench.harness import reference
from perfbench.harness.families import laguna
from perfbench.harness.tokens import ZipfStream
from ray_tpu.models.moe import RoutedConfig, RoutedSwiGLU
from ray_tpu.models.pretrain import init_params, loss_fn

_TOYS = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "perfbench", "tests", "toy")


def _toy(name="toy-laguna"):
    with open(os.path.join(_TOYS, name + ".json")) as f:
        return json.load(f)


TOY = _toy()
# the same layers on a chip that holds all sixteen experts
WHOLE = dict(TOY, num_experts=16, deployment={"chips_sharing_a_layer": 1,
                                              "this_chip": 0})


def _program(config=TOY, impl="reference", positions=64):
    """The program in float32, so that what is left to differ from the
    reference is the mathematics; ``impl`` "flash" is the Pallas kernels
    interpreted, with their own backward rule."""
    cfg = dataclasses.replace(laguna.model_config(config, 1),
                              dtype=jnp.float32, attention_impl=impl)
    model, params = init_params(cfg)
    keys = iter(jax.random.split(jax.random.PRNGKey(1), 1000))
    params = jax.tree_util.tree_map(
        lambda a: a + 0.1 * jax.random.normal(next(keys), a.shape, a.dtype),
        params)
    rows = ZipfStream(cfg.vocab_size, seed=5).rows(2, positions)
    return model, params, {k: jnp.asarray(v) for k, v in rows.items()}


def _both(model, params, batch, config=TOY, wrong=None):
    """(logits, loss, gradient norm) of program and reference."""
    def program(params, batch):
        logits = model.apply({"params": params}, batch["input_ids"])
        loss, grads = jax.value_and_grad(
            lambda p: loss_fn(model, p, batch))(params)
        return (logits[..., :model.config.vocab_size], loss,
                reference.global_norm(grads))

    def plain(params, batch):
        return laguna.logits_loss_gradnorm(
            params, batch["input_ids"], batch["targets"], config, wrong=wrong)

    with jax.default_matmul_precision("highest"):
        return jax.jit(program)(params, batch), jax.jit(plain)(params, batch)


@pytest.mark.parametrize("config,impl,positions", [
    (TOY, "reference", 64), (TOY, "flash", 64), (TOY, "flash", 52),
    (WHOLE, "reference", 64), (WHOLE, "flash", 64)],
    ids=["part-reference", "part-flash", "part-flash-52", "all-reference",
         "all-flash"])
def test_a_program_equals_the_reference_in_float32(config, impl, positions):
    """Logits, loss and the gradient norm to float32 rounding, a part of the
    experts held and all of them; 52 positions are not whole tiles."""
    got, want = _both(*_program(config, impl, positions), config=config)
    assert got[0].shape == (2, positions, 512)
    np.testing.assert_allclose(got[0], want[0], rtol=2e-4, atol=2e-4)
    assert float(got[1]) == pytest.approx(float(want[1]), rel=1e-5)
    assert float(got[2]) == pytest.approx(float(want[2]), rel=1e-4)
    assert float(want[3]) > 0


def test_a_every_gradient_equals_the_references():
    """Leaf by leaf, not only the norm: the gate, the shared expert, the
    router under sigmoid scores, the held experts."""
    model, params, batch = _program(impl="flash")
    def loss(p):
        logp = jax.nn.log_softmax(
            laguna.logits(p, batch["input_ids"], TOY), axis=-1)
        return -jnp.take_along_axis(
            logp, batch["targets"][..., None], axis=-1).mean()

    with jax.default_matmul_precision("highest"):
        got = jax.jit(jax.grad(lambda p: loss_fn(model, p, batch)))(params)
        want = jax.jit(jax.grad(loss))(params)
    for (path, g), w in zip(jax.tree_util.tree_flatten_with_path(got)[0],
                            jax.tree_util.tree_leaves(want)):
        np.testing.assert_allclose(g, w, rtol=2e-3, atol=2e-5,
                                   err_msg=jax.tree_util.keystr(path))


# -------------------------------------------------- (d) the wrong models
@functools.lru_cache(maxsize=None)
def _program_logits():
    model, params, batch = _program()
    with jax.default_matmul_precision("highest"):
        return params, batch, jax.jit(lambda p, b: model.apply(
            {"params": p}, b["input_ids"]))(params, batch)


@pytest.mark.parametrize("wrong", laguna.WRONG + (laguna.PRECISION_BELOW,))
def test_d_the_tolerance_sees_each_wrong_model(wrong):
    """Each wrong model of the on-chip controls moves the toy's logits by far
    more than (a)'s tolerance — the window left off, a window of 513 (here:
    of 9), the rotary tables swapped, the gate left out, softmax scores, the
    routed scale 1, the shared expert left out, top-(k-1) — and so does the
    reference itself with float8 activations."""
    params, batch, got = _program_logits()
    with jax.default_matmul_precision("highest"):
        want = jax.jit(lambda p, b: laguna._forward(
            p, b["input_ids"], TOY, wrong)[0])(params, batch)
    assert float(jnp.max(jnp.abs(got - want))) > 100 * 2e-4


def test_d_statistics_for_scores_that_do_not_sum_to_one():
    """``load_balance`` is ``top_k`` at balance whatever the scores sum to:
    under sigmoid scores each token's are divided by their sum first."""
    layer = RoutedSwiGLU(RoutedConfig(
        n_experts=8, top_k=2, d_model=16, d_ff=8, dtype=jnp.float32,
        scoring="sigmoid"))
    x = jax.random.normal(jax.random.PRNGKey(0), (1, 64, 16), jnp.float32)
    params = layer.init(jax.random.PRNGKey(1), x)["params"]
    # a router that scores every expert alike: any top-2 is balanced in P_e
    params = dict(params, router={"kernel": jnp.zeros((16, 8))})
    _, sown = layer.apply({"params": params}, x, mutable=["intermediates"])
    stats = {k: float(v[0]) for k, v in sown["intermediates"].items()}
    # f_e sums to top_k and every P_e is 1/8: E * sum_e f_e P_e = top_k
    assert stats["moe_load_balance"] == pytest.approx(2.0, rel=1e-5)


def test_g_the_step_reports_the_routing_statistics():
    """A stack whose routed layers are named by ``mlp_types`` (``moe_every``
    0) still gives the step's statistics, the held experts' counters among
    them, and the loss falls."""
    from ray_tpu.models.pretrain import make_optimizer, train_step

    model, params, batch = _program()
    tx = make_optimizer(lr=3e-3, warmup=1)
    step = jax.jit(lambda s, b: train_step(model, tx, s, b))
    state = (params, tx.init(params))
    first = None
    for _ in range(12):
        state, loss, stats = step(state, batch)
        first = float(loss) if first is None else first
    assert set(stats) == {"load_balance", "z", "max_load", "moe_rows_held",
                          "moe_buffer_rows"}
    # two rows of 64 tokens take 4 of 16 experts each, 4 of them held here
    assert 0 < float(stats["moe_rows_held"]) <= 2 * 64 * 4
    assert float(stats["moe_rows_held"]) <= float(stats["moe_buffer_rows"])
    assert float(loss) < first - 0.5

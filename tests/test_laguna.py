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

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import toys
from perfbench.harness.families import laguna
from ray_tpu.models.moe import RoutedConfig, RoutedSwiGLU

TOY = toys.toy("toy-laguna")
# the same layers on a chip that holds all sixteen experts
WHOLE = dict(TOY, num_experts=16, deployment={"chips_sharing_a_layer": 1,
                                              "this_chip": 0})
# The program runs in float32, so that what is left to differ from the
# reference is the mathematics; ``attention_impl`` "flash" is the Pallas
# kernels interpreted, with their own backward rule.


@pytest.mark.parametrize("config,impl,positions", [
    (TOY, "reference", 64), (TOY, "flash", 64), (TOY, "flash", 52),
    (WHOLE, "reference", 64), (WHOLE, "flash", 64)],
    ids=["part-reference", "part-flash", "part-flash-52", "all-reference",
         "all-flash"])
def test_a_program_equals_the_reference_in_float32(config, impl, positions):
    """Logits, loss and the gradient norm to float32 rounding, a part of the
    experts held and all of them; 52 positions are not whole tiles."""
    got = toys.program(config, positions, attention_impl=impl)
    want = toys.reference(config, positions, attention_impl=impl)
    assert got.logits.shape == (2, positions, 512)
    np.testing.assert_allclose(got.logits, want.logits, rtol=2e-4, atol=2e-4)
    assert float(got.loss) == pytest.approx(float(want.loss), rel=1e-5)
    assert float(got.gradnorm) == pytest.approx(float(want.gradnorm),
                                                rel=1e-4)
    assert float(want.held) > 0


def test_a_every_gradient_equals_the_references():
    """Leaf by leaf, not only the norm: the gate, the shared expert, the
    router under sigmoid scores, the held experts.  The program's leaves are
    (a)'s ``part-flash`` case's."""
    got = toys.program(TOY, 64, attention_impl="flash").grads
    want = toys.reference(TOY, 64, leaves=True, attention_impl="flash").grads
    for (path, g), w in zip(jax.tree_util.tree_flatten_with_path(got)[0],
                            jax.tree_util.tree_leaves(want)):
        np.testing.assert_allclose(g, w, rtol=2e-3, atol=2e-5,
                                   err_msg=jax.tree_util.keystr(path))


# -------------------------------------------------- (d) the wrong models
@pytest.mark.parametrize("wrong", laguna.WRONG + (laguna.PRECISION_BELOW,))
def test_d_the_tolerance_sees_each_wrong_model(wrong):
    """Each wrong model of the on-chip controls moves the toy's logits by far
    more than (a)'s tolerance — the window left off, a window of 513 (here:
    of 9), the rotary tables swapped, the gate left out, softmax scores, the
    routed scale 1, the shared expert left out, top-(k-1) — and so does the
    reference itself with float8 activations.  The program's logits are (a)'s
    ``part-reference`` case's."""
    got = toys.program(TOY, 64, attention_impl="reference").logits
    want = toys.reference(TOY, 64, backward=False, wrong=wrong,
                          attention_impl="reference").logits
    assert float(jnp.max(jnp.abs(got - want))) > 100 * 2e-4


def test_d_statistics_for_scores_that_do_not_sum_to_one():
    """``load_balance`` is ``top_k`` at balance whatever the scores sum to:
    under sigmoid scores each token's are divided by their sum first."""
    layer = RoutedSwiGLU(RoutedConfig(
        n_experts=8, top_k=2, d_model=16, d_ff=8, dtype=jnp.float32,
        scoring="sigmoid"))
    x = jax.random.normal(jax.random.PRNGKey(0), (1, 64, 16), jnp.float32)
    params = jax.jit(layer.init)(jax.random.PRNGKey(1), x)["params"]
    # a router that scores every expert alike: any top-2 is balanced in P_e
    params = dict(params, router={"kernel": jnp.zeros((16, 8))})
    _, sown = jax.jit(lambda p, x: layer.apply(
        {"params": p}, x, mutable=["intermediates"]))(params, x)
    stats = {k: float(v[0]) for k, v in sown["intermediates"].items()}
    # f_e sums to top_k and every P_e is 1/8: E * sum_e f_e P_e = top_k
    assert stats["moe_load_balance"] == pytest.approx(2.0, rel=1e-5)


def test_g_the_step_reports_the_routing_statistics():
    """A stack whose routed layers are named by ``mlp_types`` (``moe_every``
    0) still gives the step's statistics, the held experts' counters among
    them, and the loss falls."""
    from ray_tpu.models.pretrain import make_optimizer, train_step

    model, params = toys.weights(TOY, attention_impl="reference")
    batch = toys.rows(TOY, 2, 64)
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

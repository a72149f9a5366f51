"""SDAR's block-diffusion training through the shared Llama block, at toy
widths on the CPU, with seeded weights moved off their initial values: the
program (``models/llama.py`` with its own ``head_dim``, the per-head q/k
norm, a part of each layer's experts held, ``objective="block_diffusion"``;
``models/pretrain.py``'s noising and loss; ``ops/attention.py``'s block mask)
against the plain reference of ``perfbench/harness/families/sdar_moe.py`` —
a dense (2L, 2L) mask, every held expert on every token, the objective
written out.  On the chip the same reference runs at published widths against
the bf16 program (``perfbench/harness/bd_agreement.py``)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import toys
from perfbench.harness.families import sdar_moe
from ray_tpu.models.pretrain import (loss_fn, make_optimizer, objective_fn,
                                     train_step)

# 64 wide, 4 / 2 heads of 32 (not 64 / 4), 8 experts of 32 of which 4 are
# held, top-2 renormalised, 512 of 2048 vocabulary rows, blocks of 4.  The
# program runs in float32, so that what is left to differ from the reference
# is the mathematics; ``attention_impl`` "flash" is the Pallas kernels
# interpreted, with their own backward rule.  A batch is ``toys.rows``'s:
# the rows under the noise of ``PRNGKey(3)``.
TOY = toys.toy("toy-sdar")


@pytest.mark.parametrize("impl,positions", [("reference", 48), ("flash", 48),
                                            ("flash", 44)])
def test_a_program_equals_the_reference_in_float32(impl, positions):
    """Logits of the noised half, the masked-token loss, the gradient norm to
    float32 rounding, and the held experts' assignments exactly; 44
    positions make 2 x 88 x 2 = 352 buffer rows, which the grouped matmul has
    to pad."""
    got = toys.program("toy-sdar", positions, attention_impl=impl)
    want = toys.reference("toy-sdar", positions, attention_impl=impl)
    assert got.logits.shape == (2, positions, 512)
    np.testing.assert_allclose(got.logits, want.logits, rtol=2e-4, atol=2e-4)
    assert float(got.loss) == pytest.approx(float(want.loss), rel=1e-5)
    assert float(got.gradnorm) == pytest.approx(float(want.gradnorm),
                                                rel=1e-4)
    assert float(got.held) == float(want.held) > 0


@pytest.mark.parametrize("wrong", sdar_moe.WRONG
                         + (sdar_moe.PRECISION_BELOW,))
def test_b_the_tolerance_sees_each_wrong_model(wrong):
    """Each wrong model of the on-chip controls lands far outside (a)'s
    tolerance on the loss, or (the one that leaves the forward as it is) on
    the loss alone: the noised block also seeing its own clean block, the
    noised block made causal inside, weights renormalised over the held
    chosen experts only, top-(k-1), and 1/t left out; and so does the
    reference itself with float8 activations, the nearest precision below the
    program's bf16.  The program's side is (a)'s first case, made once."""
    got = toys.program("toy-sdar", 48, attention_impl="reference")
    want = toys.reference("toy-sdar", 48, backward=False, wrong=wrong,
                          attention_impl="reference")
    assert abs(float(got.loss) / float(want.loss) - 1) > 100 * 1e-5
    if wrong != "no_inverse_t":
        assert float(jnp.max(jnp.abs(got.logits - want.logits))) \
            > 100 * 2e-4


def test_g_train_step_draws_another_noise_every_step_and_learns():
    """The batch is what ``train_loop`` hands over (``input_ids`` and
    ``targets``; the latter is not read).  Each step's loss is the objective
    under the noise of ``fold_in(PRNGKey(0), the optimizer's step count)``,
    so the same batch gives another loss the next step; and at a fixed noise
    the loss falls."""
    model, params = toys.weights("toy-sdar", attention_impl="reference")
    fixed = toys.rows("toy-sdar", 2, 48)
    batch = {"input_ids": fixed["input_ids"],
             "targets": jnp.roll(fixed["input_ids"], -1, axis=1)}
    tx = make_optimizer(lr=3e-3, warmup=1)
    step = jax.jit(lambda s, b: train_step(model, tx, s, b))
    under = jax.jit(lambda p, b, key: objective_fn(model, p, b, key)[1][0])
    at_fixed = jax.jit(lambda p: loss_fn(model, p, fixed))
    state = (params, tx.init(params))
    seen = []
    for n in range(3):
        want = under(state[0], batch, jax.random.fold_in(
            jax.random.PRNGKey(0), n))
        state, loss, stats = step(state, batch)
        assert float(loss) == pytest.approx(float(want), rel=1e-5)
        assert set(stats) == {"load_balance", "z", "max_load",
                              "moe_rows_held", "moe_buffer_rows"}
        assert stats["moe_rows_held"] <= stats["moe_buffer_rows"] <= 2 * 96 * 2
        seen.append(float(loss))
    assert len(set(seen)) == 3
    first = float(at_fixed(params))
    for _ in range(20):
        state, _, _ = step(state, batch)
    assert float(at_fixed(state[0])) < first - 0.5


@pytest.mark.parametrize("mesh", [{"dp": 2, "fsdp": 2}, {"dp": 2, "tp": 2}])
def test_h_a_sharded_mesh_gives_the_single_device_loss(mesh):
    """On a virtual CPU mesh the step — the noise drawn under the layout, the
    block mask inside ``shard_map``, each device routing its own rows through
    the held experts in a buffer of its own capacity — gives the losses and
    the statistics of one device (run once for both meshes)."""
    from ray_tpu.models.pretrain import ShardedPretrainer
    from ray_tpu.parallel.mesh import MeshConfig

    one = toys.one_device("toy-sdar", 4, 64, 2, want=False)
    many = ShardedPretrainer(toys.config("toy-sdar"), MeshConfig(**mesh),
                             devices=jax.devices()[:4])
    for want in one.losses:
        assert float(many.step(one.rows)) == pytest.approx(want, rel=1e-5)
    for name, value in one.stats.items():
        if name == "moe_buffer_rows":   # each device's own rung, together
            continue
        assert float(many.moe_stats[name]) == pytest.approx(float(value),
                                                            rel=1e-4), name
    # every device picks a capacity for its own rows, with no collective:
    # the four buffers together hold the rows that came and are four rungs
    # of the quarter-size ladder, not the one device's rung
    from ray_tpu.models.moe import capacity_ladder

    rows, buffers = (float(many.moe_stats[name]) for name in (
        "moe_rows_held", "moe_buffer_rows"))
    ladder = capacity_ladder(2 * 64 * 2, 4, 8)      # a device: one row
    assert rows <= buffers <= 4 * ladder[-1]
    assert 4 * ladder[0] <= buffers

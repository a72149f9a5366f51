"""SDAR's block-diffusion training through the shared Llama block, at toy
widths on the CPU, with seeded weights moved off their initial values: the
program (``models/llama.py`` with its own ``head_dim``, the per-head q/k
norm, a part of each layer's experts held, ``objective="block_diffusion"``;
``models/pretrain.py``'s noising and loss; ``ops/attention.py``'s block mask)
against the plain reference of ``perfbench/harness/families/sdar_moe.py`` —
a dense (2L, 2L) mask, every held expert on every token, the objective
written out.  On the chip the same reference runs at published widths against
the bf16 program (``perfbench/harness/bd_agreement.py``)."""

import dataclasses
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from perfbench.harness import reference
from perfbench.harness.families import sdar_moe
from perfbench.harness.tokens import ZipfStream
from ray_tpu.models.pretrain import (init_params, loss_fn, make_optimizer,
                                     noise_blocks, objective_fn, train_step)

_TOYS = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "perfbench", "tests", "toy")
with open(os.path.join(_TOYS, "toy-sdar.json")) as f:
    # 64 wide, 4 / 2 heads of 32 (not 64 / 4), 8 experts of 32 of which 4 are
    # held, top-2 renormalised, 512 of 2048 vocabulary rows, blocks of 4
    TOY = json.load(f)


def _program(impl="reference", positions=48, config=TOY):
    """The program in float32, so that what is left to differ from the
    reference is the mathematics; ``impl`` "flash" is the Pallas kernels
    interpreted, with their own backward rule."""
    cfg = dataclasses.replace(sdar_moe.model_config(config, 1),
                              dtype=jnp.float32, attention_impl=impl)
    model, params = init_params(cfg)
    keys = iter(jax.random.split(jax.random.PRNGKey(1), 1000))
    params = jax.tree_util.tree_map(
        lambda a: a + 0.1 * jax.random.normal(next(keys), a.shape, a.dtype),
        params)
    ids = jnp.asarray(ZipfStream(cfg.vocab_size, seed=5).rows(
        2, positions)["input_ids"])
    x_t, _, weights = noise_blocks(jax.random.PRNGKey(3), ids,
                                   cfg.diffusion_block, cfg.mask_token_id,
                                   cfg.diffusion_t_min)
    return model, params, {"input_ids": ids, "x_t": x_t, "weights": weights}


def _both(model, params, batch, wrong=None, config=TOY):
    """(logits, loss, gradient norm, held rows) of program and reference."""
    ids = batch["input_ids"]
    with jax.default_matmul_precision("highest"):
        logits = model.apply({"params": params}, jnp.concatenate(
            [batch["x_t"], ids], axis=1))
        (_, (loss, stats)), grads = jax.value_and_grad(
            lambda p: objective_fn(model, p, batch), has_aux=True)(params)
    got = (logits[..., :model.config.vocab_size], loss,
           reference.global_norm(grads), stats["moe_rows_held"])
    return got, sdar_moe.logits_loss_gradnorm(
        params, ids, batch["x_t"], batch["weights"], config, ids.size,
        wrong=wrong)


@pytest.mark.parametrize("impl,positions", [("reference", 48), ("flash", 48),
                                            ("flash", 44)])
def test_a_program_equals_the_reference_in_float32(impl, positions):
    """Logits of the noised half, the masked-token loss, the gradient norm to
    float32 rounding, and the held experts' assignments exactly; 44
    positions make 2 x 88 x 2 = 352 buffer rows, which the grouped matmul has
    to pad."""
    got, want = _both(*_program(impl, positions))
    assert got[0].shape == (2, positions, 512)
    np.testing.assert_allclose(got[0], want[0], rtol=2e-4, atol=2e-4)
    assert float(got[1]) == pytest.approx(float(want[1]), rel=1e-5)
    assert float(got[2]) == pytest.approx(float(want[2]), rel=1e-4)
    assert float(got[3]) == float(want[3]) > 0


@pytest.mark.parametrize("wrong", sdar_moe.WRONG
                         + (sdar_moe.PRECISION_BELOW,))
def test_b_the_tolerance_sees_each_wrong_model(wrong):
    """Each wrong model of the on-chip controls lands far outside (a)'s
    tolerance on the loss, or (the one that leaves the forward as it is) on
    the loss alone: the noised block also seeing its own clean block, the
    noised block made causal inside, weights renormalised over the held
    chosen experts only, top-(k-1), and 1/t left out; and so does the
    reference itself with float8 activations, the nearest precision below the
    program's bf16."""
    got, want = _both(*_program(), wrong=wrong)
    assert abs(float(got[1]) / float(want[1]) - 1) > 100 * 1e-5
    if wrong != "no_inverse_t":
        assert float(jnp.max(jnp.abs(got[0] - want[0]))) > 100 * 2e-4


def test_g_train_step_draws_another_noise_every_step_and_learns():
    """The batch is what ``train_loop`` hands over (``input_ids`` and
    ``targets``; the latter is not read).  Each step's loss is the objective
    under the noise of ``fold_in(PRNGKey(0), the optimizer's step count)``,
    so the same batch gives another loss the next step; and at a fixed noise
    the loss falls."""
    model, params, batch = _program()
    batch = {"input_ids": batch["input_ids"],
             "targets": jnp.roll(batch["input_ids"], -1, axis=1)}
    tx = make_optimizer(lr=3e-3, warmup=1)
    step = jax.jit(lambda s, b: train_step(model, tx, s, b))
    state = (params, tx.init(params))
    seen = []
    for n in range(3):
        want = objective_fn(model, state[0], batch, jax.random.fold_in(
            jax.random.PRNGKey(0), n))[1][0]
        state, loss, stats = step(state, batch)
        assert float(loss) == pytest.approx(float(want), rel=1e-5)
        assert set(stats) == {"load_balance", "z", "max_load",
                              "moe_rows_held", "moe_buffer_rows"}
        assert stats["moe_rows_held"] <= stats["moe_buffer_rows"] <= 2 * 96 * 2
        seen.append(float(loss))
    assert len(set(seen)) == 3
    fixed = _program()[2]
    first = float(loss_fn(model, params, fixed))
    for _ in range(20):
        state, _, _ = step(state, batch)
    assert float(loss_fn(model, state[0], fixed)) < first - 0.5




@pytest.mark.parametrize("mesh", [{"dp": 2, "fsdp": 2}, {"dp": 2, "tp": 2}])
def test_h_a_sharded_mesh_gives_the_single_device_loss(mesh):
    """On a virtual CPU mesh the step — the noise drawn under the layout, the
    block mask inside ``shard_map``, each device routing its own rows through
    the held experts in a buffer of its own capacity — gives the losses and
    the statistics of one device."""
    from ray_tpu.models.pretrain import ShardedPretrainer
    from ray_tpu.parallel.mesh import MeshConfig

    cfg = dataclasses.replace(sdar_moe.model_config(TOY, 1),
                              dtype=jnp.float32)
    rows = ZipfStream(cfg.vocab_size, seed=5).rows(4, 64)
    one = ShardedPretrainer(cfg, MeshConfig(), devices=jax.devices()[:1])
    many = ShardedPretrainer(cfg, MeshConfig(**mesh),
                             devices=jax.devices()[:4])
    for _ in range(2):
        assert float(many.step(rows)) == pytest.approx(float(one.step(rows)),
                                                       rel=1e-5)
    for name, value in one.moe_stats.items():
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

"""The plain reference against the program at toy widths on the CPU, program
in float32 with XLA attention: the two must agree to float32 rounding, which
pins the reference's mathematics (norms, biases, RoPE convention, grouped
heads, masks).  On the chip the same functions run at published widths against
the bf16 program (``agreement.py``)."""

import dataclasses
import json
import os

import numpy as np
import pytest

TOY = os.path.join(os.path.dirname(os.path.abspath(__file__)), "toy")


@pytest.mark.parametrize("name,chips", [("toy-gpt2", 1), ("toy-llama", 4)])
def test_reference_equals_program_in_float32(name, chips):
    import jax
    import jax.numpy as jnp

    from perfbench.harness import families, reference
    from perfbench.harness.tokens import ZipfStream
    from ray_tpu.models.pretrain import init_params, loss_fn

    with open(os.path.join(TOY, name + ".json")) as f:
        config = json.load(f)
    cfg = dataclasses.replace(
        families.of(config).model_config(config, chips), dtype=jnp.float32,
        attention_impl="reference")
    model, params = init_params(cfg)
    # biases and norm scales are initialised to 0 and 1: move them, or a
    # reference that dropped one would still pass
    keys = iter(jax.random.split(jax.random.PRNGKey(1), 1000))
    params = jax.tree_util.tree_map(
        lambda a: a + 0.1 * jax.random.normal(next(keys), a.shape, a.dtype),
        params)
    rows = ZipfStream(config["vocab_size"], seed=5).rows(2, 48)
    batch = {k: jnp.asarray(v) for k, v in rows.items()}

    with jax.default_matmul_precision("highest"):
        want_logits = model.apply({"params": params}, batch["input_ids"])
        want_loss, grads = jax.value_and_grad(
            lambda p: loss_fn(model, p, batch))(params)
    got_logits, got_loss, got_norm = reference.logits_loss_gradnorm(
        params, batch["input_ids"], batch["targets"], config)

    vocab = config["vocab_size"]
    np.testing.assert_allclose(got_logits, want_logits[..., :vocab],
                               rtol=2e-4, atol=2e-4)
    assert float(got_loss) == pytest.approx(float(want_loss), rel=1e-5)
    assert float(got_norm) == pytest.approx(
        float(reference.global_norm(grads)), rel=1e-4)


def test_prefix_of_a_longer_sequence_is_the_same_problem():
    """Causality is what lets the chip check compare 512 positions of an 8192
    sequence: logits on a prefix do not depend on what follows."""
    import jax.numpy as jnp

    from perfbench.harness.families import llama
    from perfbench.harness.tokens import ZipfStream
    from ray_tpu.models.pretrain import init_params

    with open(os.path.join(TOY, "toy-llama.json")) as f:
        config = json.load(f)
    _, params = init_params(llama.model_config(config, 1))
    ids = jnp.asarray(ZipfStream(512, seed=2).rows(1, 64)["input_ids"])
    whole = llama.logits(params, ids, config)
    prefix = llama.logits(params, ids[:, :16], config)
    np.testing.assert_allclose(whole[:, :16], prefix, rtol=1e-4, atol=1e-5)

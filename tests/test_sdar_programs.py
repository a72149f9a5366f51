"""What SDAR's block-diffusion training left as it was (split from
``tests/test_sdar_parts.py``, which holds the kernels' block mask and the
routed layer that holds a part of its experts): (h) ``head_dim`` and the
per-head q/k norm against a plain attention; (i) every model the benchmark had
before is the program it was, to the bits of its loss; (m) the SDAR toy has
the loss it had with the whole buffer.
"""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import toys
from perfbench.harness import reference
from perfbench.harness.tokens import ZipfStream
from ray_tpu.models.llama import LlamaConfig
from ray_tpu.models.pretrain import (loss_fn, make_optimizer, objective_fn,
                                     train_step)
from test_sdar_parts import _walk

TOY = toys.toy("toy-sdar")


def test_h_head_dim_and_the_per_head_norm_against_a_plain_attention():
    """A causal model whose ``head_dim`` is not ``d_model / n_head`` (32 at
    64 / 4) with the per-head q/k norm: the projections are ``n_head *
    head_dim`` wide, the norms' scales ``head_dim`` wide, and the layer equals
    plain attention with the norm applied per head after the split; the
    whole-projection norm (``qk_norm=True``) keeps its projection-wide
    scale."""
    from ray_tpu.models.llama import LlamaAttention

    cfg = dataclasses.replace(
        LlamaConfig.tiny(), head_dim=32, qk_norm="head", dtype=jnp.float32,
        attention_impl="reference")
    layer = LlamaAttention(cfg)
    x = jax.random.normal(jax.random.PRNGKey(0), (2, 24, 64), jnp.float32)
    p = jax.jit(layer.init)(jax.random.PRNGKey(1), x, jnp.arange(24))[
        "params"]
    assert p["wq"]["kernel"].shape == (64, 4 * 32)
    assert p["wk"]["kernel"].shape == (64, 2 * 32)
    assert p["wo"]["kernel"].shape == (4 * 32, 64)
    assert p["q_norm"]["scale"].shape == p["k_norm"]["scale"].shape == (32,)
    for name in ("q_norm", "k_norm"):
        p[name]["scale"] = 1.0 + 0.3 * jax.random.normal(
            jax.random.PRNGKey(5), (32,))
    with jax.default_matmul_precision("highest"):
        got = layer.apply({"params": p}, x, jnp.arange(24))
        q = reference.rope(reference.rms_norm(reference.heads(
            x @ p["wq"]["kernel"], 4), p["q_norm"], cfg.rms_eps),
            cfg.rope_theta)
        k = reference.rope(reference.rms_norm(reference.heads(
            x @ p["wk"]["kernel"], 2), p["k_norm"], cfg.rms_eps),
            cfg.rope_theta)
        v = reference.heads(x @ p["wv"]["kernel"], 2)
        want = reference.merge(reference.causal_attention(
            q.reshape(2, 2, 2, 24, 32), k, v)) @ p["wo"]["kernel"]
    np.testing.assert_allclose(got, want, atol=2e-5)
    whole = jax.eval_shape(
        LlamaAttention(dataclasses.replace(cfg, qk_norm=True)).init,
        jax.random.PRNGKey(1), x, jnp.arange(24))["params"]
    assert whole["q_norm"]["scale"].shape == (128,)
    assert whole["k_norm"]["scale"].shape == (64,)


# loss of loss_fn on ZipfStream(vocab, seed=5).rows(2, 48) at PRNGKey(0)
# weights, as float.hex(), and the parameter count, at the parent commit
# (223ded3): (XLA attention, interpreted flash kernels)
_AS_IT_WAS = {
    "toy-gpt2": (173824, "0x1.a497d00000000p+2", "0x1.a49a560000000p+2"),
    "toy-llama": (108736, "0x1.b2d00c0000000p+2", "0x1.b2c9600000000p+2"),
    "toy-olmoe": (198208, "0x1.a3552e0000000p+2", "0x1.a35efe0000000p+2"),
    "toy-granite": (175408, "0x1.8dc7500000000p+2", "0x1.8dc6cc0000000p+2"),
}


@pytest.mark.parametrize("name", sorted(_AS_IT_WAS))
def test_i_every_model_the_benchmark_has_is_the_program_it_was(name):
    """``head_dim``, the per-head norm, ``experts_held``, the objective and
    the block mask come from the configuration: the toy of every family the
    benchmark had before has the parameters it had and, bit for bit, the loss
    it had at the parent commit, under XLA attention and under the
    (interpreted) flash kernels; nothing of the new objective is in its step.
    (The jaxpr text of all eight train steps equals the parent's character
    for character: checked by hand in PR 31.)"""
    config = toys.toy(name)
    chips = 1 if "1" in config.get("cut_by_chips", {"1": 0}) else 4
    n_params, *losses = _AS_IT_WAS[name]
    batch = {k: jnp.asarray(v) for k, v in ZipfStream(
        config["vocab_size"], seed=5).rows(2, 48).items()}
    for impl, want in zip(("reference", "flash"), losses):
        # the weights as ``init_params`` leaves them (made under jit, once
        # for both: the eager ones bit for bit), the loss op by op as it was
        # taken
        model, params = toys.weights(name, chips, by=0, dtype=None,
                                     attention_impl=impl)
        assert sum(a.size for a in jax.tree_util.tree_leaves(params)) \
            == n_params
        assert float(loss_fn(model, params, batch)).hex() == want, impl
    tx = make_optimizer()
    step = jax.make_jaxpr(lambda s, b: train_step(model, tx, s, b))(
        (params, tx.init(params)), batch)
    text = str(step)
    for absent in ("noise", "random_bits", "threefry", "bd_diagonal"):
        assert absent not in text, absent
    # a layer that holds all its experts has no capacity to choose: no
    # branch outside the kernels (the toy OLMoE step's jaxpr equals the
    # parent's character for character: checked by hand in PR 32)
    found = {"wide": [], "switches": [], "loops": []}
    _walk(step.jaxpr, found)
    assert found["switches"] == [] and found["loops"] == [], name


@pytest.mark.parametrize("impl,dtype,want", [
    ("reference", None, "0x1.5c19680000000p+2"),
    ("flash", None, "0x1.5c18e20000000p+2"),
    ("reference", jnp.float32, "0x1.5c68860000000p+2"),
    ("flash", jnp.float32, "0x1.5c68860000000p+2")])
def test_m_the_sdar_toy_has_the_loss_it_had_with_the_whole_buffer(
        impl, dtype, want):
    """The toy's objective at ``PRNGKey(0)`` weights under the noise of
    ``PRNGKey(0)``, on ``ZipfStream(held vocabulary, seed=5).rows(2, 48)``,
    against an earlier commit's (6530a06: every layer passing over all
    ``T * k`` rows): the order of a token's sum may change, the number may
    not.  The flash row in the toy's own bf16 is PR 34's: a noised row's
    output is rounded to bf16 once, from one softmax over all its keys, where
    it was the kernel's bf16 result merged with the own squares' term in
    float32 and rounded again (0x1.5c2ed6p+2 then, further from the XLA
    row).  Both bf16 rows are PR 39's: the per-head norm of q and k is
    applied in the rotation's float32 pass and no longer rounded to bf16
    between the two (0x1.5bfd30p+2 and 0x1.5c0c4ep+2 before: each moved
    towards the float32 rows).  In float32 nothing rounds: the flash kernels
    give the XLA row's number, as they did at the parent (0x1.5c6888p+2) to
    the last bit but one."""
    model, params = toys.weights(TOY, by=0, dtype=dtype, attention_impl=impl)
    batch = {k: jnp.asarray(v) for k, v in ZipfStream(
        model.config.vocab_size, seed=5).rows(2, 48).items()}
    # (the bf16 rows op by op, as they were taken: compiled as one program
    # the roundings fall elsewhere, 2e-5 away)
    run = jax.jit if dtype is not None else (lambda f: f)
    loss, stats = run(lambda p, b, key: objective_fn(model, p, b, key))(
        params, batch, jax.random.PRNGKey(0))[1]
    assert float(loss) == pytest.approx(float.fromhex(want), rel=1e-6)
    assert stats["moe_rows_held"] <= stats["moe_buffer_rows"] <= 2 * 96 * 2

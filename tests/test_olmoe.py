"""OLMoE through the shared Llama block, at toy widths on the CPU: the routed
layer (``models/moe.py::RoutedSwiGLU``) against the plain reference of
``perfbench/harness/families/olmoe.py`` — every expert on every token, masked
by the top-k set — with seeded weights whose norm scales and router are moved
off their initial values.  On the chip the same reference runs at published
widths against the bf16 program (``perfbench/harness/agreement.py``)."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import toys
from perfbench.harness import reference
from perfbench.harness.families import olmoe
from perfbench.harness.tokens import ZipfStream
from ray_tpu.models.llama import LlamaConfig
from ray_tpu.models.pretrain import (ShardedPretrainer, init_params, loss_fn,
                                     objective_fn)
from ray_tpu.parallel.mesh import MeshConfig

TOY = toys.toy("toy-olmoe")     # 64 wide, 4 heads, 8 experts of 32, top-2, 2 layers
# The program in float32 with XLA attention, so that what is left to differ
# from the reference is the mathematics (the grouped matmul is the interpreted
# Pallas one with its own backward rule).  48 positions make 2 x 48 x 2 = 192
# (token, expert) rows, a whole row tile; 41 make 164, which the grouped
# matmul has to pad.
_F32 = dict(attention_impl="reference")


def _assert_equal(got, want):
    np.testing.assert_allclose(got.logits, want.logits, rtol=2e-4, atol=2e-4)
    assert float(got.loss) == pytest.approx(float(want.loss), rel=1e-5)
    assert float(got.gradnorm) == pytest.approx(float(want.gradnorm),
                                                rel=1e-4)


@pytest.mark.parametrize("norm_topk_prob", [False, True])
def test_a_program_equals_the_reference_in_float32(norm_topk_prob):
    """Logits, loss and gradient norm to float32 rounding: as published (the
    chosen probabilities as the softmax over all experts gave them) and with
    the configuration's other value (renormalised over the chosen)."""
    config = dict(TOY, norm_topk_prob=norm_topk_prob)
    _assert_equal(toys.program(config, 48, **_F32),
                  toys.reference(config, 48, **_F32))


@pytest.mark.parametrize("positions", [48, 41])
def test_b_auxiliary_losses_and_max_load_equal_the_reference(positions):
    model, params = toys.weights(TOY, **_F32)
    batch = toys.rows(TOY, 2, positions)
    with jax.default_matmul_precision("highest"):
        objective, (loss, stats) = jax.jit(
            lambda p, b: objective_fn(model, p, b))(params, batch)
        want = jax.jit(lambda p, b: olmoe.aux_losses(
            p, b["input_ids"], TOY))(params, batch)
        plain_loss = jax.jit(lambda p, b: loss_fn(model, p, b))(params, batch)
    assert set(stats) == set(want) == {"load_balance", "z", "max_load"}
    for name in want:
        assert float(stats[name]) == pytest.approx(float(want[name]),
                                                   rel=1e-5), name
    # what is differentiated is the cross entropy plus the weighted terms
    assert float(loss) == pytest.approx(float(plain_loss))
    assert float(objective) == pytest.approx(float(
        loss + 0.01 * want["load_balance"] + 0.001 * want["z"]), rel=1e-6)
    # top-2 of 8: 1.0 is balance, 4.0 every token on the same two experts
    assert 1.0 <= float(stats["max_load"]) <= 4.0


@pytest.mark.parametrize("positions", [48, 41])
def test_c_no_token_is_dropped_under_the_worst_imbalance(positions):
    """A router of zeros gives every expert the same probability and top-k
    breaks the tie by index: every token of every layer takes experts 0 and
    1, whose groups hold all the rows while six groups are empty.  A layer
    with a capacity would drop most tokens; this one equals the reference."""
    model, params = toys.weights(TOY, **_F32)
    batch = toys.rows(TOY, 2, positions)
    params = dict(params, **{name: dict(params[name], moe=dict(
        params[name]["moe"], router={"kernel": jnp.zeros_like(
            params[name]["moe"]["router"]["kernel"])}))
        for name in ("h_0", "h_1")})

    def program(params, batch):
        logits = model.apply({"params": params}, batch["input_ids"])
        loss, grads = jax.value_and_grad(
            lambda p: loss_fn(model, p, batch))(params)
        return toys.Run(logits[..., :TOY["vocab_size"]], loss,
                        reference.global_norm(grads))

    def plain(params, batch):
        return toys.Run(*reference.logits_loss_gradnorm(
            params, batch["input_ids"], batch["targets"], TOY))

    with jax.default_matmul_precision("highest"):
        _assert_equal(jax.jit(program)(params, batch),
                      jax.jit(plain)(params, batch))
    # (op by op: under jit the mean over 164 rows is a product with 1 / 164,
    # and 3.9999998)
    assert float(objective_fn(model, params, batch)[1][1]["max_load"]) == 4.0


@pytest.mark.parametrize("wrong", [{"norm_topk_prob": True},
                                   {"num_experts_per_tok": 1}])
def test_d_the_tolerance_sees_a_wrong_model(wrong):
    """The reference with renormalised top-k weights, or with top-(k-1),
    lands far outside (a)'s tolerance (2e-4 on the logits, 1e-5 on the
    loss): a dropped or rescaled term cannot hide in it.  The program's side
    is (a)'s published case, made once."""
    got = toys.program(TOY, 48, **_F32)
    want = toys.reference(TOY, 48, backward=False, wrong=wrong, **_F32)
    assert float(jnp.max(jnp.abs(got.logits - want.logits))) > 100 * 2e-4
    assert abs(float(got.loss) / float(want.loss) - 1) > 100 * 1e-5


def test_e_train_step_lowers_the_loss_and_reports_the_cross_entropy():
    cfg = dataclasses.replace(olmoe.model_config(TOY, 1),
                              attention_impl="reference", dtype=jnp.float32)
    trainer = ShardedPretrainer(cfg, MeshConfig(), lr=3e-3,
                                devices=jax.devices()[:1], total_steps=40)
    assert trainer.moe_stats == {}
    batches = ZipfStream(TOY["vocab_size"], seed=3).batches(4, 32)
    first = {k: jnp.asarray(v) for k, v in next(batches).items()}
    want = jax.jit(lambda p: loss_fn(trainer.model, p, first))(
        trainer.state[0])
    losses = [trainer.step(first)] + [trainer.step(next(batches))
                                      for _ in range(19)]
    # the loss handed back is the cross entropy, not the objective
    assert float(losses[0]) == pytest.approx(float(want), rel=1e-5)
    assert float(np.mean([float(x) for x in losses[-3:]])) \
        < float(losses[0]) - 0.15
    # the statistics stay on the trainer as device scalars
    assert set(trainer.moe_stats) == {"load_balance", "z", "max_load"}
    assert all(isinstance(v, jax.Array) and v.shape == ()
               for v in trainer.moe_stats.values())
    assert 1.0 <= float(trainer.moe_stats["max_load"]) <= 4.0


@pytest.mark.parametrize("mesh", [{"dp": 1, "fsdp": 2}, {"dp": 2, "tp": 2},
                                  {"dp": 1, "ep": 2}])
def test_f_a_sharded_mesh_gives_the_single_device_loss(mesh):
    """fsdp (and dp x tp) under GSPMD with the routed layer in a shard_map
    that splits the tokens over every axis, tp among them (a tp group that
    kept the same rows would do the same experts' work tp times); ep > 1 has
    no dropless path yet and says so."""
    from ray_tpu.models.moe import token_spec

    cfg = toys.config(TOY)
    n = int(np.prod(list(mesh.values())))
    if "ep" in mesh:
        batch = next(ZipfStream(TOY["vocab_size"], seed=4).batches(4, 32))
        with pytest.raises(NotImplementedError, match="ep > 1"):
            ShardedPretrainer(cfg, MeshConfig(**mesh),
                              devices=jax.devices()[:n]).step(batch)
        return
    one = toys.one_device(TOY, 4, 32, 2, seed=4, want=False)   # for both
    many = ShardedPretrainer(cfg, MeshConfig(**mesh),
                             devices=jax.devices()[:n])
    assert token_spec(many.mesh) == jax.sharding.PartitionSpec(
        ("dp", "fsdp"), ("sp", "tp"), None)
    spec = many.param_specs["h_0"]["moe"]
    assert spec["gate_proj"] == jax.sharding.PartitionSpec("ep", "fsdp", "tp")
    assert spec["down_proj"] == jax.sharding.PartitionSpec("ep", "tp", "fsdp")
    assert spec["router"]["kernel"] == jax.sharding.PartitionSpec()
    for want in one.losses:     # the second step has been through an update
        assert float(many.step(one.rows)) == pytest.approx(want, rel=2e-5)
    for name, value in one.stats.items():
        assert float(many.moe_stats[name]) == pytest.approx(float(value),
                                                            rel=1e-4), name


@pytest.mark.parametrize("family", ["llama", "gpt2"])
def test_g_a_dense_model_is_the_program_it_was(family):
    """The routed layer and the q/k norm come from the configuration: a dense
    configuration has the parameter tree it had and nothing of either in its
    step (the lowered text of both toy steps equals the parent commit's byte
    for byte: checked by hand in PR 25)."""
    from ray_tpu.models.gpt2 import GPT2Config, GPT2LMModel
    from ray_tpu.models.llama import LlamaLMModel
    from ray_tpu.models.pretrain import make_optimizer, train_step

    if family == "llama":
        cfg = dataclasses.replace(LlamaConfig.tiny(),
                                  attention_impl="reference")
        block = {"attn": {"wq", "wk", "wv", "wo"}, "attn_norm": {"scale"},
                 "mlp": {"gate_proj", "up_proj", "down_proj"},
                 "mlp_norm": {"scale"}}
        top = {"wte", "h_0", "h_1", "norm_f", "lm_head"}
    else:
        cfg = GPT2Config(vocab_size=512, n_positions=64, n_embd=64, n_layer=2,
                         n_head=4, attention_impl="reference")
        block = {"attn": {"qkv_proj", "out_proj"}, "ln_1": {"scale", "bias"},
                 "mlp": {"fc_in", "fc_out"}, "ln_2": {"scale", "bias"}}
        top = {"wte", "wpe", "h_0", "h_1", "ln_f", "lm_head"}
    model = (LlamaLMModel if family == "llama" else GPT2LMModel)(cfg)
    # (the tree's shapes: nothing here reads a parameter's value)
    params = jax.eval_shape(lambda: init_params(cfg)[1])
    assert set(params) == top
    assert {k: set(v) for k, v in params["h_0"].items()} == block
    tx = make_optimizer()
    batch = {k: jnp.zeros((2, 16), jnp.int32)
             for k in ("input_ids", "targets")}
    state = (params, jax.eval_shape(tx.init, params))
    _, loss, stats = jax.eval_shape(
        lambda s, b: train_step(model, tx, s, b), state, batch)
    assert stats == {} and loss.shape == ()
    text = str(jax.make_jaxpr(lambda s, b: train_step(model, tx, s, b))(
        state, batch))
    for absent in ("moe", "router", "argsort", "top_k",
                   "pallas_call", "q_norm"):
        assert absent not in text, absent


def test_h_the_router_is_float32_inside_a_bf16_layer():
    """The guarantee the chip's agreement check cannot hold (a bf16 router
    softmax lands inside its limits): in a bf16 layer the router's matmul,
    softmax and statistics are float32, so on the same bf16 inputs they equal
    a float32 computation to float32 rounding; bf16 anywhere on the way would
    show at 1e-3."""
    from ray_tpu.models.moe import RoutedConfig, RoutedSwiGLU

    layer = RoutedSwiGLU(RoutedConfig(n_experts=8, top_k=2, d_model=64,
                                      d_ff=32))
    x = jax.random.normal(jax.random.PRNGKey(2), (2, 48, 64), jnp.bfloat16)
    params = layer.init(jax.random.PRNGKey(0), x)["params"]
    params["router"]["kernel"] = 2.0 * jax.random.normal(
        jax.random.PRNGKey(3), (64, 8))
    out, sown = layer.apply({"params": params}, x, mutable=["intermediates"])
    assert out.dtype == jnp.bfloat16
    logits = jnp.einsum("bsd,de->bse", x.astype(jnp.float32),
                        params["router"]["kernel"], precision="highest")
    probs = jax.nn.softmax(logits, axis=-1)
    _, idx = jax.lax.top_k(probs, 2)
    share = jnp.mean(jax.nn.one_hot(idx, 8), axis=(0, 1, 2))
    want = {"moe_load_balance": 8 * 2 * jnp.sum(
                share * jnp.mean(probs, axis=(0, 1))),
            "moe_z": jnp.mean(jax.nn.logsumexp(logits, axis=-1) ** 2),
            "moe_max_load": jnp.max(share) * 8}
    for name, value in want.items():
        got = sown["intermediates"][name][0]
        assert got.dtype == jnp.float32
        assert float(got) == pytest.approx(float(value), rel=1e-6), name

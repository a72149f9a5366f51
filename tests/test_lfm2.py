"""LFM2-24B-A2B through the shared Llama block, at toy widths on the CPU, with
seeded weights moved off their initial values: (a) the gated short convolution
(``models/mamba.py::gated_short_conv`` with its written-out backward, and the
mixer around it) against the plain form; (b) the program (``models/llama.py``'s
``ShortConvMixer`` and ``LlamaAttention`` with the per-head norm under
``layer_types`` and ``mlp_types``, ``models/moe.py`` with sigmoid scores, the
selection bias, the epsilon and a part of the experts held) against the plain
reference of ``perfbench/harness/families/lfm2.py`` — the convolution as a sum
over three shifted copies, a dense causal mask, every held expert on every
token — and every wrong model of the on-chip controls outside the float32
limits; (c) the chip's share of a sparse layer tied to the uncut layer; (d)
the selection bias: in the choice and not in the weights, no gradient, no
update; (e) the new parameters' partition rules on a virtual mesh, with no
collective inside ``conv/mix`` under ``tp``; (f) the step of the one older toy
that no earlier hash pins, as the parent lowered it.  The toy
(``perfbench/tests/toy/toy-lfm2.json``): 64 wide, a conv + dense layer, an
attention + sparse layer (4 / 2 heads of 16) and two conv + sparse ones, 16
experts of 32 of which 2 are held (chip 1 of 8), top-4.  On the chip the same
reference runs at published widths against the bf16 program
(``perfbench/harness/agreement.py``).
"""

import dataclasses
import functools
import hashlib
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from perfbench.harness import families, reference
from perfbench.harness.families import lfm2
from perfbench.harness.tokens import ZipfStream
from ray_tpu.models.mamba import causal_conv, gated_short_conv
from ray_tpu.models.moe import RoutedConfig, RoutedSwiGLU
from ray_tpu.models.pretrain import init_params, loss_fn

_TOYS = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "perfbench", "tests", "toy")


def _toy(name="toy-lfm2"):
    with open(os.path.join(_TOYS, name + ".json")) as f:
        return json.load(f)


TOY = _toy()
# the same layers on a chip that holds all sixteen experts
WHOLE = dict(TOY, num_experts=16,
             deployment={"chips_sharing_a_layer": 1, "this_chip": 0})


# ------------------------------------------------------ (a) the mixer's pass
def _plain_pass(b, c, u, kernel):
    """``c_t * sum_j kernel[j] * (b * u)_{t - 2 + j}``, position by
    position."""
    x, width = b * u, kernel.shape[0]
    rows = []
    for t in range(x.shape[1]):
        rows.append(sum(kernel[j] * x[:, t - (width - 1) + j]
                        for j in range(width) if t - (width - 1) + j >= 0))
    return c * jnp.stack(rows, axis=1)


@pytest.mark.parametrize("batch,seq,channels", [
    (1, 2, 8),          # a sequence shorter than the convolution
    (2, 13, 16),        # one that is not a multiple of 8
    (1, 64, 128),
    (2, 24, 64),
])
def test_a_the_pass_equals_the_plain_form(batch, seq, channels):
    """Forward and every gradient (``B``, ``C``, ``u`` and the kernel) of
    ``gated_short_conv``, whose backward is written out, against the sum
    taken position by position; and against reverse mode through
    ``causal_conv``, the form it replaces."""
    keys = jax.random.split(jax.random.PRNGKey(seq * 100 + channels), 5)
    b, c, u, g = (jax.random.normal(k, (batch, seq, channels), jnp.float32)
                  for k in keys[:4])
    kernel = jax.random.normal(keys[4], (3, channels), jnp.float32)
    want = _plain_pass(b, c, u, kernel)
    np.testing.assert_allclose(gated_short_conv(b, c, u, kernel), want,
                               atol=1e-5)

    def grads(f):
        return jax.grad(lambda *a: jnp.sum(f(*a) * g), (0, 1, 2, 3))(
            b, c, u, kernel)

    got = grads(gated_short_conv)
    for other in (_plain_pass,
                  lambda b, c, u, k: c * causal_conv(b * u, k)):
        for name, a, w in zip(("B", "C", "u", "kernel"), got, grads(other)):
            assert a.shape == w.shape
            np.testing.assert_allclose(a, w, atol=2e-4, err_msg="d" + name)


def test_a_no_position_sees_a_later_one():
    """The mixer's output at position ``t`` does not move when any later
    input does, and does when the input two positions back does."""
    from ray_tpu.models.llama import ShortConvMixer

    cfg = dataclasses.replace(lfm2.model_config(TOY, 1), dtype=jnp.float32)
    mixer = ShortConvMixer(cfg)
    x = jax.random.normal(jax.random.PRNGKey(0), (2, 20, 64), jnp.float32)
    params = mixer.init(jax.random.PRNGKey(1), x)["params"]
    assert params["in_proj"]["kernel"].shape == (64, 3, 64)
    assert params["conv_kernel"].shape == (3, 64)
    assert set(params) == {"in_proj", "conv_kernel", "out_proj"}
    out = mixer.apply({"params": params}, x)
    later = mixer.apply({"params": params}, x.at[:, 11:].add(1.0))
    np.testing.assert_array_equal(out[:, :11], later[:, :11])
    assert float(jnp.min(jnp.max(jnp.abs(out - later)[:, 11:14],
                                 axis=(0, 2)))) > 1e-3
    earlier = mixer.apply({"params": params}, x.at[:, 8].add(1.0))
    assert float(jnp.max(jnp.abs(out - earlier)[:, 10])) > 1e-3
    np.testing.assert_array_equal(out[:, 11:], earlier[:, 11:])


def test_a_an_unknown_layer_type_is_named_with_the_known_ones():
    cfg = dataclasses.replace(lfm2.model_config(TOY, 1),
                              layer_types=("conv", "convolution") * 2)
    with pytest.raises(ValueError, match="'convolution'.*'mamba', 'conv' or "
                       "one of .*full_attention"):
        init_params(cfg)


# ------------------------------------------ (b) the stack and its reference
def _program(config=TOY, impl="reference", positions=64):
    """The program in float32, so that what is left to differ from the
    reference is the mathematics; ``impl`` "flash" is the Pallas kernels
    interpreted, with their own backward rule.  Every leaf is moved off its
    initial value: the selection bias is not zero."""
    cfg = dataclasses.replace(lfm2.model_config(config, 1),
                              dtype=jnp.float32, attention_impl=impl)
    model, params = init_params(cfg)
    keys = iter(jax.random.split(jax.random.PRNGKey(1), 1000))
    params = jax.tree_util.tree_map(
        lambda a: a + 0.1 * jax.random.normal(next(keys), a.shape, a.dtype),
        params)
    rows = ZipfStream(cfg.vocab_size, seed=5).rows(2, positions)
    return model, params, {k: jnp.asarray(v) for k, v in rows.items()}


def _both(model, params, batch, config=TOY):
    """(logits, loss, gradient norm) of program and reference."""
    def program(params, batch):
        logits = model.apply({"params": params}, batch["input_ids"])
        loss, grads = jax.value_and_grad(
            lambda p: loss_fn(model, p, batch))(params)
        return (logits[..., :model.config.vocab_size], loss,
                reference.global_norm(grads))

    def plain(params, batch):
        return lfm2.logits_loss_gradnorm(
            params, batch["input_ids"], batch["targets"], config)

    with jax.default_matmul_precision("highest"):
        return jax.jit(program)(params, batch), jax.jit(plain)(params, batch)


@pytest.mark.parametrize("config,impl,positions", [
    (TOY, "reference", 64), (TOY, "flash", 64), (TOY, "flash", 52),
    (WHOLE, "reference", 64), (WHOLE, "flash", 64)],
    ids=["part-reference", "part-flash", "part-flash-52", "all-reference",
         "all-flash"])
def test_b_program_equals_the_reference_in_float32(config, impl, positions):
    """Logits, loss and the gradient norm to float32 rounding, a part of the
    experts held and all of them; 52 positions are not whole tiles."""
    got, want = _both(*_program(config, impl, positions), config=config)
    assert got[0].shape == (2, positions, 512)
    np.testing.assert_allclose(got[0], want[0], rtol=2e-4, atol=2e-4)
    assert float(got[1]) == pytest.approx(float(want[1]), rel=1e-5)
    assert float(got[2]) == pytest.approx(float(want[2]), rel=1e-4)
    assert float(want[3]) > 0


def test_b_every_gradient_equals_the_references():
    """Leaf by leaf, not only the norm: the mixer's two projections and its
    kernel, attention's four with the two norms' scales, the router, the held
    experts; the selection bias's is zero on both sides."""
    model, params, batch = _program(impl="flash")

    def loss(p):
        logp = jax.nn.log_softmax(
            lfm2.logits(p, batch["input_ids"], TOY), axis=-1)
        return -jnp.take_along_axis(
            logp, batch["targets"][..., None], axis=-1).mean()

    with jax.default_matmul_precision("highest"):
        got = jax.jit(jax.grad(lambda p: loss_fn(model, p, batch)))(params)
        want = jax.jit(jax.grad(loss))(params)
    assert set(got["h_0"]["conv"]) == {"in_proj", "conv_kernel", "out_proj"}
    assert set(got["h_1"]["attn"]) == {"wq", "wk", "wv", "wo", "q_norm",
                                       "k_norm"}
    assert set(got["h_2"]["moe"]) == {"router", "selection_bias",
                                      "gate_proj", "up_proj", "down_proj"}
    assert "lm_head" not in got         # the head is the embedding table
    for (path, g), w in zip(jax.tree_util.tree_flatten_with_path(got)[0],
                            jax.tree_util.tree_leaves(want)):
        np.testing.assert_allclose(g, w, rtol=2e-3, atol=2e-5,
                                   err_msg=jax.tree_util.keystr(path))
    for layer in ("h_1", "h_2", "h_3"):
        assert not np.any(got[layer]["moe"]["selection_bias"])


def test_b_the_trainers_step_takes_the_references_loss_down():
    """Through ``ShardedPretrainer``, the path the benchmark times: the first
    step's loss is the reference's on the same batch and weights, the steps
    report the held experts' counters, and the loss falls."""
    from ray_tpu.models.pretrain import ShardedPretrainer
    from ray_tpu.parallel.mesh import MeshConfig

    cfg = dataclasses.replace(lfm2.model_config(TOY, 1), dtype=jnp.float32)
    # (the schedule warms up over 100 steps: 0.1 is 0.011 by the twelfth)
    trainer = ShardedPretrainer(cfg, MeshConfig(), devices=jax.devices()[:1],
                                lr=0.1)
    rows = ZipfStream(cfg.vocab_size, seed=5).rows(2, 64)
    with jax.default_matmul_precision("highest"):
        want = lfm2.logits_loss_gradnorm(
            trainer.state[0], jnp.asarray(rows["input_ids"]),
            jnp.asarray(rows["targets"]), TOY)[1]
    losses = [float(trainer.step(rows)) for _ in range(12)]
    assert losses[0] == pytest.approx(float(want), rel=1e-4)
    assert losses[-1] < losses[0] - 0.5
    stats = trainer.moe_stats
    assert set(stats) == {"load_balance", "z", "max_load", "moe_rows_held",
                          "moe_buffer_rows"}
    # two rows of 64 tokens take 4 of 16 experts each, 2 of them held here
    assert 0 < float(stats["moe_rows_held"]) <= 2 * 64 * 2


@functools.lru_cache(maxsize=None)
def _program_logits():
    model, params, batch = _program()
    with jax.default_matmul_precision("highest"):
        return params, batch, jax.jit(lambda p, b: model.apply(
            {"params": p}, b["input_ids"]))(params, batch)


@pytest.mark.parametrize("wrong", lfm2.WRONG + (lfm2.PRECISION_BELOW,))
def test_b_the_tolerance_sees_each_wrong_model(wrong):
    """Each wrong model of the on-chip controls moves the toy's logits by far
    more than (b)'s tolerance — a gate left out (``B``, then ``C``), a
    convolution 4 wide, one that sees a position ahead, no q / k norm, RoPE
    left off, top-3, softmax scores, no renormalisation, the (non-zero) bias
    added to the weights — and so does the reference itself with float8
    activations."""
    params, batch, got = _program_logits()
    with jax.default_matmul_precision("highest"):
        want = jax.jit(lambda p, b: lfm2._forward(
            p, b["input_ids"], TOY, wrong)[0])(params, batch)
    assert float(jnp.max(jnp.abs(got - want))) > 100 * 2e-4


# ------------------------------------------- (c) the share tied to the model
def _sparse_layer(key, d=64, f=32, e=16):
    keys = jax.random.split(key, 5)

    def normal(key, *shape):
        return 0.3 * jax.random.normal(key, shape, jnp.float32)

    return {"router": {"kernel": normal(keys[0], d, e)},
            "selection_bias": normal(keys[4], e),
            "gate_proj": normal(keys[1], e, d, f),
            "up_proj": normal(keys[2], e, d, f),
            "down_proj": normal(keys[3], e, f, d)}


def _layer(held, dtype=jnp.float32, **more):
    return RoutedSwiGLU(RoutedConfig(
        n_experts=16, top_k=4, d_model=64, d_ff=32, norm_topk_prob=True,
        dtype=dtype, experts_held=held, scoring="sigmoid",
        selection_bias=True, norm_topk_eps=1e-6, **more))


def test_c_the_eight_shares_add_up_to_the_uncut_layer():
    """The routed parts that the eight chips of the toy's deployment compute,
    each from its own two experts under the whole router and the whole
    (non-zero) selection bias, are the uncut reference's sparse layer; and
    with the residual, counted once, the uncut layer's output."""
    keys = jax.random.split(jax.random.PRNGKey(7), 3)
    whole = _sparse_layer(keys[0])
    y = jax.random.normal(keys[1], (2, 24, 64), jnp.float32)
    residual = jax.random.normal(keys[2], (2, 24, 64), jnp.float32)
    with jax.default_matmul_precision("highest"):
        routed, chosen = lfm2.sparse_parts(y, whole, WHOLE, 0)
        assert float(jnp.sum(chosen)) == 2 * 24 * 4
        total = 0.0
        for chip in range(8):
            lo = 2 * chip
            mine = dict(whole, **{name: whole[name][lo:lo + 2] for name in
                                  ("gate_proj", "up_proj", "down_proj")})
            part = _layer((lo, 2)).apply({"params": mine}, y)
            total = total + part
            # the reference given the same share gives the same part
            np.testing.assert_allclose(
                part, lfm2.sparse_parts(y, mine, TOY, lo)[0], atol=2e-5)
    np.testing.assert_allclose(total, routed, atol=5e-5)
    np.testing.assert_allclose(residual + total, residual + routed,
                               atol=5e-5)


# ------------------------------------------------- (d) the selection bias
def test_d_the_bias_chooses_and_does_not_weigh():
    """A bias large enough to carry an expert into every token's four changes
    which experts are chosen; each chosen expert's weight is still its own
    score over the chosen scores' sum (+ 1e-6), the bias nowhere in it; and
    no gradient reaches the bias."""
    keys = jax.random.split(jax.random.PRNGKey(3), 2)
    params = _sparse_layer(keys[0])
    y = jax.random.normal(keys[1], (2, 24, 64), jnp.float32)
    pushed = dict(params, selection_bias=jnp.zeros(16).at[5].set(10.0))
    plain = dict(params, selection_bias=jnp.zeros(16))
    layer = _layer(None)
    with jax.default_matmul_precision("highest"):
        score = jax.nn.sigmoid(y @ params["router"]["kernel"])
        for p, always in ((pushed, True), (plain, False)):
            _, chosen = lfm2.sparse_parts(y, p, WHOLE, 0)
            assert bool(jnp.all(chosen[..., 5] == 1)) == always
            # the layer's output, rebuilt from unbiased weights over the set
            # the biased scores chose
            weight = score * chosen
            weight = weight / (jnp.sum(weight, -1, keepdims=True) + 1e-6)
            hidden = jax.nn.silu(jnp.einsum("bsd,edf->bsef", y,
                                            p["gate_proj"])) \
                * jnp.einsum("bsd,edf->bsef", y, p["up_proj"])
            want = jnp.einsum("bsef,efd,bse->bsd", hidden, p["down_proj"],
                              weight)
            np.testing.assert_allclose(layer.apply({"params": p}, y), want,
                                       atol=3e-5)
        grads = jax.grad(lambda p: jnp.sum(
            layer.apply({"params": p}, y) ** 2))(pushed)
    assert not np.any(grads["selection_bias"])
    assert np.any(grads["router"]["kernel"])


def test_d_the_default_has_no_bias_and_no_epsilon():
    layer = RoutedSwiGLU(RoutedConfig(n_experts=16, top_k=4, d_model=64,
                                      d_ff=32, dtype=jnp.float32))
    params = layer.init(jax.random.PRNGKey(0), jnp.zeros((1, 8, 64)))[
        "params"]
    assert set(params) == {"router", "gate_proj", "up_proj", "down_proj"}


def test_d_an_optimizer_step_leaves_the_bias_bit_for_bit():
    """Under AdamW with weight decay the bias is what it was, bit for bit,
    after a step (and after a second, whose moments are no longer zero), and
    its moments stay zero; the router beside it moves."""
    from ray_tpu.models.pretrain import ShardedPretrainer
    from ray_tpu.parallel.mesh import MeshConfig

    cfg = dataclasses.replace(lfm2.model_config(TOY, 1), dtype=jnp.float32)
    trainer = ShardedPretrainer(cfg, MeshConfig(), devices=jax.devices()[:1],
                                lr=0.1)
    keys = iter(jax.random.split(jax.random.PRNGKey(2), 8))
    params = jax.tree_util.tree_map_with_path(
        lambda path, a: 0.3 * jax.random.normal(next(keys), a.shape, a.dtype)
        if path[-1].key == "selection_bias" else a, trainer.state[0])
    trainer.state = (params, trainer.state[1])
    before = {k: np.array(params[k]["moe"]["selection_bias"])
              for k in ("h_1", "h_2", "h_3")}
    router = np.array(params["h_1"]["moe"]["router"]["kernel"])
    assert all(np.any(b) for b in before.values())
    rows = ZipfStream(cfg.vocab_size, seed=5).rows(2, 64)
    for _ in range(2):
        trainer.step(rows)
        after = trainer.state[0]
        for k, b in before.items():
            assert np.array(after[k]["moe"]["selection_bias"]).tobytes() \
                == b.tobytes()
    assert np.any(np.array(after["h_1"]["moe"]["router"]["kernel"])
                  != router)
    moments = [np.array(leaf) for path, leaf in
               jax.tree_util.tree_flatten_with_path(trainer.state[1])[0]
               if "selection_bias" in jax.tree_util.keystr(path)]
    assert len(moments) == 6 and not any(np.any(m) for m in moments)


# ------------------------------------------------- (e) on a virtual mesh
@pytest.mark.parametrize("mesh", [{"dp": 1, "fsdp": 4}, {"dp": 2, "tp": 2}])
def test_e_a_sharded_mesh_gives_the_single_device_loss(mesh):
    """``conv/in_proj`` shards each of ``B``, ``C``, ``u`` by channel, the
    depthwise kernel with them and ``conv/out_proj`` by rows, the selection
    bias is whole everywhere; the step under them gives one device's losses,
    and under ``tp`` the compiled step has no collective inside
    ``conv/mix``."""
    import re

    from jax.sharding import PartitionSpec as P

    from ray_tpu.models.pretrain import ShardedPretrainer
    from ray_tpu.parallel.mesh import MeshConfig
    from ray_tpu.parallel.sharding import (llama_partition_rules,
                                           match_partition_rules)

    cfg = dataclasses.replace(lfm2.model_config(TOY, 1), dtype=jnp.float32)
    specs = match_partition_rules(llama_partition_rules(),
                                  init_params(cfg)[1])
    conv = specs["h_0"]["conv"]
    assert conv["in_proj"]["kernel"] == P("fsdp", None, "tp")
    assert conv["out_proj"]["kernel"] == P("tp", "fsdp")
    assert conv["conv_kernel"] == P(None, "tp")
    assert specs["h_1"]["moe"]["selection_bias"] == P()
    assert specs["h_1"]["attn"]["q_norm"]["scale"] == P()

    rows = ZipfStream(cfg.vocab_size, seed=5).rows(4, 64)
    one = ShardedPretrainer(cfg, MeshConfig(), devices=jax.devices()[:1])
    many = ShardedPretrainer(cfg, MeshConfig(**mesh),
                             devices=jax.devices()[:4])
    if "tp" in mesh:
        text = many.lower(rows).compile().as_text()
        collectives = [line for line in text.splitlines() if re.search(
            r"= \S+ (all-reduce|all-gather|all-to-all|collective-permute|"
            r"reduce-scatter)", line)]
        assert collectives     # the step has them: out_proj's sum, for one
        assert not [line for line in collectives if "/conv/mix/" in line]
    for _ in range(2):      # the second step sees the first's gradients
        assert float(many.step(rows)) == pytest.approx(float(one.step(rows)),
                                                       rel=1e-5)


# ------------------------------------- (f) the other models' steps, untouched
def test_f_kimi_vls_step_is_the_parents(flash_names_off):
    """With the new fields at their defaults the traced calls are the
    parent's: ``tests/test_laguna_parts.py`` (e), ``tests/test_sdar_parts.py``
    (i), (n) and ``tests/test_kimi_vl.py`` (d) pin the dense, routed, hybrid,
    block-diffusion and window toys, unedited; this is the latent-attention
    toy, which none of them pins: sha256 of its lowered train step on the
    parent commit (PR 40), kernel bodies included.  Its routed layers run
    ``RoutedSwiGLU`` with sigmoid scores and ``norm_topk_prob``, so the
    selection's and the renormalisation's new branches are what it holds
    still.  (Since PR 42 the hash is that PR's: the kernels read each head's
    key part and values where ``wukv`` wrote them and write (B, S, H * Dv).
    Since PR 49 that PR's: the dense layer's and the shared expert's ``silu *
    up`` go through ``models/moe.py::silu_mul``.)"""
    from ray_tpu.models.pretrain import make_optimizer, sharded_train_step
    from ray_tpu.parallel.mesh import MeshConfig, build_mesh

    config = _toy("toy-kimi-vl")
    cfg = families.of(config).model_config(config, 1)
    mesh = build_mesh(MeshConfig(), devices=jax.devices()[:1])
    s = sharded_train_step(cfg, mesh, make_optimizer())
    batch = {k: jax.ShapeDtypeStruct((2, 64), jnp.int32, sharding=sh)
             for k, sh in s.batch_sharding.items()}
    with jax.set_mesh(mesh):
        text = s.step.trace(s.state, batch).lower().as_text()
    assert hashlib.sha256(text.encode()).hexdigest() == \
        "5a017906206e0d13c1f718b10c38f6a80c6893aafaa08d252665c2a7a745d148"

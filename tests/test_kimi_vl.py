"""Kimi-VL-A3B's decoder through the shared Llama block, at toy widths on the
CPU, with seeded weights moved off their initial values: (a) the flash kernels
with scores wider than values and the key's shared part, interpreted, against
``mha_reference``; (b) the program (``models/llama.py``'s ``LatentAttention``
under ``mlp_types``, ``models/moe.py`` with sigmoid scores, the routed scale,
the shared expert and a part of the experts held) against the plain reference
of ``perfbench/harness/families/kimi_vl.py`` — a dense causal mask, the shared
rotary key given to the heads by indexing, every held expert on every token —
and every wrong model of the on-chip controls outside the float32 limits; (c)
the chip's share of a sparse layer tied to the uncut layer; (d) the step of
the one toy that PR 35's hashes do not pin, as the parent lowered it; (e) the
new parameters' partition rules on a virtual mesh.  The toy
(``perfbench/tests/toy/toy-kimi-vl.json``): 64 wide, 4 heads whose scores are
16 + 8 wide over values 16 wide, a latent of 32, a dense layer and two sparse
ones, 16 experts of 32 of which 2 are held (chip 1 of 8), top-3, a shared
expert of 64.  On the chip the same reference runs at published widths
against the bf16 program (``perfbench/harness/agreement.py``).
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
from perfbench.harness.families import kimi_vl
from perfbench.harness.tokens import ZipfStream
from ray_tpu.models.moe import RoutedConfig, RoutedSwiGLU
from ray_tpu.models.pretrain import init_params, loss_fn
from ray_tpu.ops.attention import (flash_attention, mha_reference,
                                   ring_attention)

_TOYS = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "perfbench", "tests", "toy")


def _toy(name="toy-kimi-vl"):
    with open(os.path.join(_TOYS, name + ".json")) as f:
        return json.load(f)


TOY = _toy()
# the same layers on a chip that holds all sixteen experts
WHOLE = dict(TOY, n_routed_experts=16,
             deployment={"chips_sharing_a_layer": 1, "this_chip": 0})


# ------------------------------------------------------ (a) the kernels
@pytest.mark.parametrize("seq,d_qk,d_shared,d_v,block,window", [
    (64, 24, 8, 16, None, 0),       # the toy's: one tile
    (256, 24, 8, 16, 128, 0),       # whole tiles, the diagonal in chunks
    (300, 24, 8, 16, 128, 0),       # a length that is not whole tiles
    (384, 24, 8, 40, 128, 0),       # values wider than the scores
    (256, 192, 64, 128, 128, 0),    # the published widths exactly
    (256, 24, 0, 16, 128, 0),       # two widths, every key part a head's own
    (300, 24, 8, 16, 128, 100),     # ... and under a window
])
def test_a_two_width_kernels_equal_the_reference(seq, d_qk, d_shared, d_v,
                                                 block, window):
    """Forward and every gradient of the interpreted flash kernels, float32,
    against ``mha_reference``: scores ``d_qk`` wide over values ``d_v`` wide,
    the last ``d_shared`` dimensions of the key one vector a position for all
    heads (its gradient summed over them)."""
    keys = jax.random.split(jax.random.PRNGKey(seq * 1000 + d_qk), 5)

    def normal(key, heads, d):
        return jax.random.normal(key, (2, heads, seq, d), jnp.float32)

    operands = [normal(keys[0], 3, d_qk), normal(keys[1], 3, d_qk - d_shared),
                normal(keys[2], 3, d_v)]
    if d_shared:
        operands.append(normal(keys[3], 1, d_shared))
    g = normal(keys[4], 3, d_v)

    def flash(q, k, v, k_shared=None):
        return flash_attention(q, k, v, k_shared=k_shared, window=window,
                               block_q=block, block_k=block)

    def plain(q, k, v, k_shared=None):
        return mha_reference(q, k, v, k_shared=k_shared, window=window)

    out = flash(*operands)
    assert out.shape == (2, 3, seq, d_v)
    np.testing.assert_allclose(out, plain(*operands), atol=2e-5)
    argnums = tuple(range(len(operands)))
    got = jax.grad(lambda *a: jnp.sum(flash(*a) * g), argnums)(*operands)
    want = jax.grad(lambda *a: jnp.sum(plain(*a) * g), argnums)(*operands)
    for name, a, b in zip(("q", "k", "v", "k_shared"), got, want):
        assert a.shape == b.shape
        np.testing.assert_allclose(a, b, atol=1e-4, err_msg="d" + name)


def test_a_two_widths_are_refused_where_no_kernel_takes_them():
    q, k, v = (jnp.zeros((1, 2, 128, d), jnp.float32) for d in (24, 24, 16))
    with pytest.raises(NotImplementedError, match="one width"):
        flash_attention(q, k, v, causal=False, diffusion_block=4)
    with pytest.raises(NotImplementedError, match="one width"):
        ring_attention(q, k, v)
    with pytest.raises(ValueError, match="24 wide against keys 16 \\+ 4"):
        flash_attention(q, k[..., :16], v, k_shared=k[:, :1, :, :4])
    cfg = dataclasses.replace(kimi_vl.model_config(TOY, 1),
                              attention_impl="ring")
    model, params = init_params(cfg)    # (initialised without the ring)
    with pytest.raises(NotImplementedError, match="latent attention"):
        model.apply({"params": params}, jnp.zeros((1, 16), jnp.int32))


# ------------------------------------------ (b) the stack and its reference
def _program(config=TOY, impl="reference", positions=64):
    """The program in float32, so that what is left to differ from the
    reference is the mathematics; ``impl`` "flash" is the Pallas kernels
    interpreted, with their own backward rule."""
    cfg = dataclasses.replace(kimi_vl.model_config(config, 1),
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
        return kimi_vl.logits_loss_gradnorm(
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
    """Leaf by leaf, not only the norm: the four projections of the latent
    attention, its norm, the shared expert, the router, the held experts."""
    model, params, batch = _program(impl="flash")

    def loss(p):
        logp = jax.nn.log_softmax(
            kimi_vl.logits(p, batch["input_ids"], TOY), axis=-1)
        return -jnp.take_along_axis(
            logp, batch["targets"][..., None], axis=-1).mean()

    with jax.default_matmul_precision("highest"):
        got = jax.jit(jax.grad(lambda p: loss_fn(model, p, batch)))(params)
        want = jax.jit(jax.grad(loss))(params)
    assert set(got["h_1"]["attn"]) == {"wq", "wdkv", "kv_norm", "wukv", "wo"}
    for (path, g), w in zip(jax.tree_util.tree_flatten_with_path(got)[0],
                            jax.tree_util.tree_leaves(want)):
        np.testing.assert_allclose(g, w, rtol=2e-3, atol=2e-5,
                                   err_msg=jax.tree_util.keystr(path))


def test_b_the_trainers_step_takes_the_references_loss_down():
    """Through ``ShardedPretrainer``, the path the benchmark times: the first
    step's loss is the reference's on the same batch and weights, the steps
    report the held experts' counters, and the loss falls."""
    from ray_tpu.models.pretrain import ShardedPretrainer
    from ray_tpu.parallel.mesh import MeshConfig

    cfg = dataclasses.replace(kimi_vl.model_config(TOY, 1), dtype=jnp.float32)
    # (the schedule warms up over 100 steps: 0.1 is 0.011 by the twelfth)
    trainer = ShardedPretrainer(cfg, MeshConfig(), devices=jax.devices()[:1],
                                lr=0.1)
    rows = ZipfStream(cfg.vocab_size, seed=5).rows(2, 64)
    with jax.default_matmul_precision("highest"):
        want = kimi_vl.logits_loss_gradnorm(
            trainer.state[0], jnp.asarray(rows["input_ids"]),
            jnp.asarray(rows["targets"]), TOY)[1]
    losses = [float(trainer.step(rows)) for _ in range(12)]
    assert losses[0] == pytest.approx(float(want), rel=1e-4)
    assert losses[-1] < losses[0] - 0.5
    stats = trainer.moe_stats
    assert set(stats) == {"load_balance", "z", "max_load", "moe_rows_held",
                          "moe_buffer_rows"}
    # two rows of 64 tokens take 3 of 16 experts each, 2 of them held here
    assert 0 < float(stats["moe_rows_held"]) <= 2 * 64 * 2


@functools.lru_cache(maxsize=None)
def _program_logits():
    model, params, batch = _program()
    with jax.default_matmul_precision("highest"):
        return params, batch, jax.jit(lambda p, b: model.apply(
            {"params": p}, b["input_ids"]))(params, batch)


@pytest.mark.parametrize("wrong", kimi_vl.WRONG + (kimi_vl.PRECISION_BELOW,))
def test_b_the_tolerance_sees_each_wrong_model(wrong):
    """Each wrong model of the on-chip controls moves the toy's logits by far
    more than (b)'s tolerance — the latent's norm left out, RoPE left off the
    shared key, the scores scaled by the unrotated width alone, the values
    taken from the key half of the up-projection, one shared expert for two,
    the routed scale 1, top-(k-1), softmax scores — and so does the reference
    itself with float8 activations."""
    params, batch, got = _program_logits()
    with jax.default_matmul_precision("highest"):
        want = jax.jit(lambda p, b: kimi_vl._forward(
            p, b["input_ids"], TOY, wrong)[0])(params, batch)
    assert float(jnp.max(jnp.abs(got - want))) > 100 * 2e-4


# ------------------------------------------- (c) the share tied to the model
def test_c_the_eight_shares_add_up_to_the_uncut_layer():
    """The routed parts that the eight chips of the toy's deployment compute,
    each from its own two experts, plus the shared expert counted once, are
    the uncut reference's sparse layer; and with the residual, also counted
    once, the uncut layer's output."""
    d, f, e, chips = 64, 32, 16, 8
    keys = jax.random.split(jax.random.PRNGKey(7), 9)

    def normal(key, *shape):
        return 0.3 * jax.random.normal(key, shape, jnp.float32)

    whole = {"router": {"kernel": normal(keys[0], d, e)},
             "gate_proj": normal(keys[1], e, d, f),
             "up_proj": normal(keys[2], e, d, f),
             "down_proj": normal(keys[3], e, f, d),
             "shared": {name: {"kernel": normal(key, *shape)}
                        for name, key, shape in (
                            ("gate_proj", keys[4], (d, 2 * f)),
                            ("up_proj", keys[5], (d, 2 * f)),
                            ("down_proj", keys[6], (2 * f, d)))}}
    y = jax.random.normal(keys[7], (2, 24, d), jnp.float32)
    residual = jax.random.normal(keys[8], (2, 24, d), jnp.float32)
    held = e // chips
    with jax.default_matmul_precision("highest"):
        routed, shared, chosen = kimi_vl.sparse_parts(y, whole, WHOLE, 0)
        assert float(jnp.sum(chosen)) == 2 * 24 * 3
        total = 0.0
        for chip in range(chips):
            lo = held * chip
            layer = RoutedSwiGLU(RoutedConfig(
                n_experts=e, top_k=3, d_model=d, d_ff=f, norm_topk_prob=True,
                dtype=jnp.float32, experts_held=(lo, held),
                scoring="sigmoid", routed_scale=2.446, d_shared=2 * f))
            mine = dict(whole, **{name: whole[name][lo:lo + held] for name in
                                  ("gate_proj", "up_proj", "down_proj")})
            # what every chip computes alike is taken off each share ...
            part = layer.apply({"params": mine}, y) - shared
            total = total + part
            # ... and the reference given the same share gives the same part
            np.testing.assert_allclose(
                part, kimi_vl.sparse_parts(y, mine, TOY, lo)[0], atol=2e-5)
    np.testing.assert_allclose(total, routed, atol=5e-5)
    # ... and counted once: the uncut layer's output
    np.testing.assert_allclose(residual + shared + total,
                               residual + shared + routed, atol=5e-5)


# ------------------------------------- (d) the other models' steps, untouched
def test_d_lagunas_step_is_the_parents(flash_names_off):
    """With one width and no shared key part the traced calls are the
    parent's: ``tests/test_laguna_parts.py`` (e) and ``tests/test_sdar_parts.py``
    (n) pin the dense, routed, hybrid and block-diffusion toys, unedited; this
    is the window kernels' toy, which neither pins: sha256 of its lowered
    train step on the parent commit (PR 36), kernel bodies included (and,
    since PR 38, ``flash_names_off``; since PR 39 the hash is that PR's: the
    rotation of q and k is ``apply_rope``'s one pass, the kernels' calls as
    they were; since PR 42 that PR's: the kernels' grid is (batch, heads,
    tiles, tiles) and their output (B, S, H * D), the gate widened along
    the lanes; since PR 49 that PR's: the dense layer's and the shared
    expert's ``silu * up`` go through ``models/moe.py::silu_mul``; since
    PR 52 that PR's: k and v reach the kernels with their own heads; since
    PR 55 that PR's: the toy's grouped heads are narrower than a lane tile,
    and the backward kernel indexes such a group's dQ in the accumulator
    itself, interpreted as compiled)."""
    from ray_tpu.models.pretrain import make_optimizer, sharded_train_step
    from ray_tpu.parallel.mesh import MeshConfig, build_mesh

    config = _toy("toy-laguna")
    cfg = families.of(config).model_config(config, 1)
    mesh = build_mesh(MeshConfig(), devices=jax.devices()[:1])
    s = sharded_train_step(cfg, mesh, make_optimizer())
    batch = {k: jax.ShapeDtypeStruct((2, 64), jnp.int32, sharding=sh)
             for k, sh in s.batch_sharding.items()}
    with jax.set_mesh(mesh):
        text = s.step.trace(s.state, batch).lower().as_text()
    assert hashlib.sha256(text.encode()).hexdigest() == \
        "4d7f074a49c3ae1f68e4b5e66cd1af93e583972f0ae498461edcd62e2babffc0"


# ------------------------------------------------- (e) on a virtual mesh
@pytest.mark.parametrize("mesh", [{"dp": 1, "fsdp": 4}, {"dp": 2, "tp": 2}])
def test_e_a_sharded_mesh_gives_the_single_device_loss(mesh):
    """``attn/wdkv`` and ``attn/wukv`` shard by the Llama rules — the latent
    and the shared rotary key belong to no head, the up-projection's columns
    to the heads — and the step under them (the kernels inside ``shard_map``,
    the shared key whole on every device of a ``tp`` group and its gradient
    summed over the group) gives one device's losses."""
    from jax.sharding import PartitionSpec as P

    from ray_tpu.models.pretrain import ShardedPretrainer
    from ray_tpu.parallel.mesh import MeshConfig
    from ray_tpu.parallel.sharding import (llama_partition_rules,
                                           match_partition_rules)

    cfg = dataclasses.replace(kimi_vl.model_config(TOY, 1), dtype=jnp.float32)
    attn = match_partition_rules(llama_partition_rules(),
                                 init_params(cfg)[1])["h_1"]["attn"]
    assert attn["wq"]["kernel"] == attn["wukv"]["kernel"] == P("fsdp", "tp")
    assert attn["wdkv"]["kernel"] == P("fsdp", None)
    assert attn["wo"]["kernel"] == P("tp", "fsdp")
    assert attn["kv_norm"]["scale"] == P()

    rows = ZipfStream(cfg.vocab_size, seed=5).rows(4, 64)
    one = ShardedPretrainer(cfg, MeshConfig(), devices=jax.devices()[:1])
    many = ShardedPretrainer(cfg, MeshConfig(**mesh),
                             devices=jax.devices()[:4])
    for _ in range(2):      # the second step sees the first's gradients
        assert float(many.step(rows)) == pytest.approx(float(one.step(rows)),
                                                       rel=1e-5)

"""The parts Laguna-XS.2 brought, each against its own ground truth on the
CPU (the whole model against its reference: ``tests/test_laguna.py``): (b) the
flash kernels under a window, interpreted, against ``mha_reference``; (c) the
chip's share of a sparse layer tied to the uncut layer; (e) every new field of
``LlamaConfig`` at its default leaves the other models' steps as they were:
``tests/test_pinned_steps.py``; (f) the partition rules of the gate and the shared expert on a virtual mesh.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import toys
from perfbench.harness.families import laguna
from ray_tpu.models.moe import RoutedConfig, RoutedSwiGLU
from ray_tpu.ops.attention import flash_attention, mha_reference

TOY = toys.toy("toy-laguna")
# the same layers on a chip that holds all sixteen experts
WHOLE = dict(TOY, num_experts=16, deployment={"chips_sharing_a_layer": 1,
                                              "this_chip": 0})


# ------------------------------------------------------ (b) the kernels
@pytest.mark.parametrize("seq,window,block", [
    (64, 8, None),          # the toy's: one tile, the band inside it
    (256, 8, 128),          # whole tiles, a window smaller than a tile
    (512, 128, 128),        # ... equal to a tile
    (512, 129, 128),        # ... one more than a tile
    (512, 300, 128),        # ... larger than a tile, not whole lanes
    (300, 100, 128),        # a sequence that is not whole tiles
    (700, 512, 256),        # ... under a window of two tiles
    (640, 256, None),       # the tile edge the window picks itself
    (384, 1000, 128),       # a window longer than the sequence
])
def test_b_window_kernels_equal_the_masked_reference(seq, window, block):
    """Forward and all three gradients of the interpreted flash kernels under
    a window against ``mha_reference(window=...)``."""
    keys = jax.random.split(jax.random.PRNGKey(seq * 1000 + window), 4)
    q, k, v, g = (jax.random.normal(key, (1, 2, seq, 32), jnp.float32)
                  for key in keys)

    def flash(q, k, v):
        return flash_attention(q, k, v, window=window, block_q=block,
                               block_k=block)

    def plain(q, k, v):
        return mha_reference(q, k, v, window=window)

    np.testing.assert_allclose(flash(q, k, v), plain(q, k, v), atol=2e-5)
    got = jax.grad(lambda *a: jnp.sum(flash(*a) * g), (0, 1, 2))(q, k, v)
    want = jax.grad(lambda *a: jnp.sum(plain(*a) * g), (0, 1, 2))(q, k, v)
    for name, a, b in zip("qkv", got, want):
        np.testing.assert_allclose(a, b, atol=5e-5, err_msg="d" + name)


def test_b_a_window_of_the_whole_sequence_is_the_causal_kernel_bit_for_bit():
    q, k, v = (jax.random.normal(key, (1, 2, 256, 32), jnp.float32)
               for key in jax.random.split(jax.random.PRNGKey(0), 3))
    kw = dict(block_q=128, block_k=128)

    def both(f):
        return (f(q, k, v),) + jax.grad(
            lambda *a: jnp.sum(f(*a) ** 2), (0, 1, 2))(q, k, v)

    for a, b in zip(both(lambda *a: flash_attention(*a, **kw)),
                    both(lambda *a: flash_attention(*a, window=256, **kw))):
        assert bool(jnp.all(a == b))


def test_b_the_grid_walks_the_band_alone():
    """At the cell's shape no tile outside the band is a step of either grid:
    two key tiles a query tile (its own and the one before), two query tiles a
    key tile, where the causal kernels walk all sixteen."""
    from ray_tpu.ops.attention import _Tiles

    t = _Tiles.of(8192, 8192, 128, jnp.bfloat16, True, 0, 256, window=512)
    assert (t.block_q, t.block_k, t.nq, t.nk) == (512, 512, 16, 16)
    assert (t.steps(False), t.steps(True)) == (2, 2)
    causal = _Tiles.of(8192, 8192, 128, jnp.bfloat16, True, 0, 256)
    assert (causal.steps(False), causal.steps(True)) == (causal.nk, causal.nq)
    # a window of two and a half tiles meets four tiles a row
    assert _Tiles.of(4096, 4096, 128, jnp.bfloat16, True, 0, 256,
                     window=1280, block_q=512, block_k=512).steps(False) == 4


def test_b_a_window_under_ring_attention_is_refused():
    # (the weights are initialised without the ring)
    model, params = toys.weights(
        dict(TOY, layer_types=["sliding_attention"] * 4), dtype=None, by=0,
        attention_impl="ring")
    with pytest.raises(NotImplementedError, match="window"):
        # (refused while it is traced: no layer before it runs)
        jax.eval_shape(lambda: model.apply(
            {"params": params}, jnp.zeros((1, 16), jnp.int32)))


# ------------------------------------------- (c) the share tied to the model
def test_c_the_shares_add_up_to_the_uncut_layer():
    """The routed parts that the four chips of the toy's deployment compute,
    each from its own four experts, plus the shared expert counted once, are
    the uncut reference's sparse layer: the program's layer on every share
    against the reference holding all sixteen."""
    d, f, e = 64, 32, 16
    keys = jax.random.split(jax.random.PRNGKey(7), 8)

    def normal(key, *shape):
        return 0.3 * jax.random.normal(key, shape, jnp.float32)

    whole = {"router": {"kernel": normal(keys[0], d, e)},
             "gate_proj": normal(keys[1], e, d, f),
             "up_proj": normal(keys[2], e, d, f),
             "down_proj": normal(keys[3], e, f, d),
             "shared": {name: {"kernel": normal(key, *shape)}
                        for name, key, shape in (
                            ("gate_proj", keys[4], (d, f)),
                            ("up_proj", keys[5], (d, f)),
                            ("down_proj", keys[6], (f, d)))}}
    y = jax.random.normal(keys[7], (2, 24, d), jnp.float32)
    with jax.default_matmul_precision("highest"):
        routed, shared, chosen = laguna.sparse_parts(y, whole, WHOLE, 0)
        assert float(jnp.sum(chosen)) == 2 * 24 * 4
        total = 0.0
        for chip in range(4):
            lo = 4 * chip
            layer = RoutedSwiGLU(RoutedConfig(
                n_experts=e, top_k=4, d_model=d, d_ff=f, norm_topk_prob=True,
                dtype=jnp.float32, experts_held=(lo, 4), scoring="sigmoid",
                routed_scale=2.5, d_shared=f))
            mine = dict(whole, **{name: whole[name][lo:lo + 4] for name in
                                  ("gate_proj", "up_proj", "down_proj")})
            total = total + layer.apply({"params": mine}, y) - shared
            # ... and the reference given the same share gives the same part
            part = laguna.sparse_parts(y, mine, TOY, lo)[0]
            np.testing.assert_allclose(
                layer.apply({"params": mine}, y) - shared, part, atol=2e-5)
    np.testing.assert_allclose(total, routed, atol=5e-5)


# ------------------------------------------------- (f) on a virtual mesh
@pytest.mark.parametrize("mesh", [{"dp": 1, "fsdp": 4}, {"dp": 2, "tp": 2}])
def test_f_a_sharded_mesh_gives_the_single_device_loss(mesh):
    """``attn/wg`` and ``moe/shared/*`` shard by the Llama rules, and the
    step under them — the window kernels inside ``shard_map``, the gate's
    heads over ``tp``, each device routing its own rows — gives one device's
    losses."""
    from jax.sharding import PartitionSpec as P

    from ray_tpu.models.pretrain import ShardedPretrainer
    from ray_tpu.parallel.mesh import MeshConfig
    from ray_tpu.parallel.sharding import (llama_partition_rules,
                                           match_partition_rules)

    specs = match_partition_rules(llama_partition_rules(),
                                  toys.weights(TOY)[1])
    assert specs["h_1"]["attn"]["wg"]["kernel"] == P("fsdp", "tp")
    shared = specs["h_1"]["moe"]["shared"]
    assert shared["gate_proj"]["kernel"] == shared["up_proj"]["kernel"] \
        == P("fsdp", "tp")
    assert shared["down_proj"]["kernel"] == P("tp", "fsdp")
    assert specs["h_1"]["moe"]["gate_proj"] == P("ep", "fsdp", "tp")

    one = toys.one_device(TOY, 4, 64, 2, want=False)    # for both meshes
    many = ShardedPretrainer(toys.config(TOY), MeshConfig(**mesh),
                             devices=jax.devices()[:4])
    for want in one.losses[:2 if "fsdp" in mesh else 1]:
        assert float(many.step(one.rows)) == pytest.approx(want, rel=1e-5)

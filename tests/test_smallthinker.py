"""SmallThinker-21BA3B's block through the shared Llama block, at toy widths
on the CPU, with seeded weights moved off their initial values: the program
(``models/llama.py`` with a kind a layer, a rotary table for the window kind
and none for the whole-row kind, ``router_before_attention``,
``expert_activation="relu"``; ``models/moe.py`` with ``router_input``, the
ReLU gate and a part of the experts held; ``ops/attention.py``'s window and
its grouped form at seven query heads a key/value head) against the plain
reference of ``perfbench/harness/families/smallthinker.py`` — the router from
the first norm's output, top-k of the logits then a softmax over the chosen,
``relu(gate) * up``, dense masks from indices, rotate-half written out,
key/value head ``h // 7`` by indexing, a loop over the held experts.  The toy
(``perfbench/tests/toy/toy-smallthinker.json``): 64 wide, heads of 16, 14
query heads over 2 key/value heads, four layers (whole-row without rotation,
then three under a window of 8 with RoPE) under sequences of 64, 8 experts of
32 of which 2 are held (chip 1 of 4), top-3.

(a) the stack: logits, loss, every gradient leaf (``attn_norm``'s scale takes
the router's term), remat on and off, each wrong model; (b) the routed layer
alone: the published order of top-k and softmax against the program's, the
ReLU gate through both of its paths and their backward, the new fields unset;
(c) a kind without a table (the kernels at a group of seven are
``tests/test_flash_layout.py``'s ``rep7`` cases); (d) the four quarter-shares
of a layer add up to the uncut layer; (e) the trainer's step on one device
and on the virtual meshes.  On the chip the same reference runs at published
widths against the bf16 program (``perfbench/harness/agreement.py``).
"""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import toys
from perfbench.harness.families import smallthinker
from ray_tpu.models.llama import LlamaBlock, LlamaConfig
from ray_tpu.models.moe import RoutedConfig, RoutedSwiGLU

TOY = toys.toy("toy-smallthinker")
# the same layers on a chip that holds all eight experts
WHOLE = dict(TOY, moe_num_primary_experts=8,
             deployment={"chips_sharing_a_layer": 1, "this_chip": 0})


def _same(got, want, rtol, atol):
    for (path, g), w in zip(jax.tree_util.tree_flatten_with_path(got)[0],
                            jax.tree_util.tree_leaves(want)):
        np.testing.assert_allclose(g, w, rtol=rtol, atol=atol,
                                   err_msg=jax.tree_util.keystr(path))


# ------------------------------------------------------------ (a) the stack
@pytest.mark.parametrize("config,impl,positions", [
    (TOY, "reference", 64), (TOY, "flash", 64), (TOY, "flash", 52),
    (WHOLE, "flash", 64)],
    ids=["part-reference", "part-flash", "part-flash-52", "all-flash"])
def test_a_program_equals_the_reference_in_float32(config, impl, positions):
    """Logits, loss and the gradient norm to float32 rounding, a part of the
    experts held and all of them (the held loop's hand-written backward, and
    autodiff through the grouped matmuls); 52 positions are not whole
    tiles."""
    got = toys.program(config, positions, attention_impl=impl)
    want = toys.reference(config, positions, attention_impl=impl)
    assert got.logits.shape == (2, positions, 512)
    np.testing.assert_allclose(got.logits, want.logits, rtol=2e-4, atol=2e-4)
    assert float(got.loss) == pytest.approx(float(want.loss), rel=1e-5)
    assert float(got.gradnorm) == pytest.approx(float(want.gradnorm),
                                                rel=1e-4)
    assert float(want.held) > 0


def test_a_every_gradient_equals_the_references():
    """Leaf by leaf, not only the norm.  ``attn_norm``'s scale receives the
    router's term beside the attention's three projections': the reference
    that routes from the second norm gives that leaf another gradient, in
    the last layer by far more than the tolerance."""
    got = toys.program(TOY, 64, attention_impl="flash").grads
    want = toys.reference(TOY, 64, leaves=True, attention_impl="flash").grads
    _same(got, want, rtol=2e-3, atol=2e-5)
    late = toys.reference(TOY, 64, leaves=True, wrong="router_from_n2").grads
    ours, theirs = (np.asarray(g["h_3"]["attn_norm"]["scale"])
                    for g in (got, late))
    assert np.max(np.abs(ours - theirs)) > 100 * 2e-5


def test_a_remat_on_and_off_give_the_same_gradients():
    """Under ``remat_block`` the routing is made again from the block's
    input; with and without it every leaf's gradient is the same."""
    on = toys.program(TOY, 64, attention_impl="flash").grads
    off = toys.program(TOY, 64, attention_impl="flash", remat=False).grads
    _same(on, off, rtol=1e-4, atol=1e-6)


@pytest.mark.parametrize(
    "wrong", smallthinker.WRONG + (smallthinker.PRECISION_BELOW,))
def test_a_the_tolerance_sees_each_wrong_model(wrong):
    """Each wrong model of the on-chip controls moves the toy's logits by far
    more than (a)'s tolerance — the router from the second norm or from the
    un-normed input, silu or gelu for the ReLU, the scores not renormalised
    or sigmoids, top-(k - 1), the window left off or one key wider, every
    layer turned or none, theta 1e4, the layouts shifted by a layer,
    key/value head ``h % 2``, half the vocabulary held — and so does the
    reference itself with float8 activations.  The program's logits are (a)'s
    ``part-reference`` case's."""
    got = toys.program(TOY, 64, attention_impl="reference").logits
    want = toys.reference(TOY, 64, backward=False, wrong=wrong,
                          attention_impl="reference").logits
    assert float(jnp.max(jnp.abs(got - want))) > 100 * 2e-4


def test_a_the_references_blocks_of_queries_are_the_whole_mask(monkeypatch):
    """The reference takes its scores ``Q_BLOCK`` queries at a time; four
    blocks of 16 give what one block of 64 gives."""
    _, params = toys.weights(TOY)
    ids = toys.rows(TOY, 2, 64)["input_ids"]
    with jax.default_matmul_precision("highest"):
        whole = jax.jit(lambda p: smallthinker.logits(p, ids, TOY))(params)
        monkeypatch.setattr(smallthinker, "Q_BLOCK", 16)
        blocks = jax.jit(lambda p: smallthinker.logits(p, ids, TOY))(params)
    np.testing.assert_allclose(blocks, whole, rtol=1e-5, atol=1e-5)


# ----------------------------------------------- (b) the routed layer alone
def _layer(held, activation="relu", **more):
    return RoutedSwiGLU(RoutedConfig(
        n_experts=8, top_k=3, d_model=16, d_ff=8, norm_topk_prob=True,
        dtype=jnp.float32, experts_held=held, activation=activation, **more))


def _plain_layer(params, x, reads, first):
    """The family's own lines for one layer's feed-forward."""
    config = {"moe_num_active_primary_experts": 3,
              "published_counts": {"moe_num_primary_experts": 8}}
    weight, _ = smallthinker.routing(reads @ params["router"]["kernel"],
                                     config)
    return smallthinker.held_experts(x, weight, params, first)[0]


def test_b_topk_then_softmax_is_softmax_then_topk_renormalised():
    """The published order against the program's: the same experts, the same
    weights, on logits with no ties."""
    r = 3.0 * jax.random.normal(jax.random.PRNGKey(0), (2, 64, 8))
    config = {"moe_num_active_primary_experts": 3,
              "published_counts": {"moe_num_primary_experts": 8}}
    published, chosen = smallthinker.routing(r, config)
    weights, idx = jax.lax.top_k(jax.nn.softmax(r, axis=-1), 3)
    weights = weights / jnp.sum(weights, axis=-1, keepdims=True)
    ours = jnp.einsum("...k,...ke->...e", weights, jax.nn.one_hot(idx, 8))
    np.testing.assert_array_equal(chosen, jnp.sum(jax.nn.one_hot(idx, 8), -2))
    np.testing.assert_allclose(ours, published, rtol=1e-6, atol=1e-7)


@pytest.mark.parametrize("held", [(2, 2), None], ids=["held-loop", "all"])
def test_b_relu_and_the_routers_own_input_through_both_paths(held):
    """``relu(gate) * up`` through ``routed_experts`` (all eight held:
    autodiff through the grouped matmuls) and through the held loop's
    hand-written backward (two of eight), the router reading another tensor
    than the experts: the output and every operand's gradient — the tokens,
    what the router reads, the router, the three matrices — against autodiff
    of the family's plain lines."""
    layer = _layer(held)
    keys = jax.random.split(jax.random.PRNGKey(3), 4)
    x, reads, g = (jax.random.normal(k, (2, 64, 16)) for k in keys[:3])
    params = toys.moved(jax.jit(layer.init)(keys[3], x)["params"])
    first = held[0] if held else 0

    def ours(params, x, reads):
        return jnp.sum(layer.apply({"params": params}, x, reads) * g)

    def plain(params, x, reads):
        return jnp.sum(_plain_layer(params, x, reads, first) * g)

    with jax.default_matmul_precision("highest"):
        got, want = (jax.jit(jax.value_and_grad(f, argnums=(0, 1, 2)))(
            params, x, reads) for f in (ours, plain))
    assert float(got[0]) == pytest.approx(float(want[0]), rel=1e-5)
    assert float(jnp.max(jnp.abs(want[1][2]))) > 0     # the router's own input
    _same(got[1], want[1], rtol=1e-4, atol=1e-5)


def test_b_with_the_new_fields_unset_the_layers_are_the_parents():
    """``router_input`` left out is the router reading the experts' tensor,
    to the bit, and the defaults name the parent's layer: silu, the router
    behind attention, every kind turned by ``rope_theta``.  (That the older
    programs lower to the text they were is ``test_pinned_steps.py``'s.)"""
    assert RoutedConfig(8, 3, 16, 8).activation == "silu"
    assert not LlamaConfig().router_before_attention
    assert LlamaConfig().expert_activation == "silu"
    layer = _layer((2, 2), "silu")
    x = jax.random.normal(jax.random.PRNGKey(5), (2, 64, 16))
    params = jax.jit(layer.init)(jax.random.PRNGKey(6), x)["params"]
    alone, given = (jax.jit(lambda p, x, *r: layer.apply({"params": p}, x, *r)
                            )(params, x, *reads) for reads in ((), (x,)))
    np.testing.assert_array_equal(alone, given)
    with pytest.raises(ValueError, match="unknown activation"):
        jax.eval_shape(lambda p, x: _layer((2, 2), "gelu").apply(
            {"params": p}, x), params, x)


# ------------------------------------------- (c) a kind without a table
def test_c_a_kind_with_no_table_turns_nothing_while_another_turns():
    """``rope_tables`` with ``None`` for a kind: that kind's layer gives the
    same output whatever the positions, the other kind's does not; a kind
    that is not named is still turned by ``rope_theta``."""
    cfg = toys.config(TOY, attention_impl="reference", remat=False)
    x = jax.random.normal(jax.random.PRNGKey(7), (2, 32, 64))
    # (twice as far apart: a rotation sees distances, not a common shift)
    here, later = jnp.arange(32), 2 * jnp.arange(32)

    def moved_by_positions(cfg, kind):
        block = LlamaBlock(cfg, "sparse", kind)
        params = jax.jit(block.init)(jax.random.PRNGKey(8), x, here)
        a, b = (jax.jit(block.apply)(params, x, p) for p in (here, later))
        return float(jnp.max(jnp.abs(a - b)))

    assert moved_by_positions(cfg, "full_attention") == 0.0
    assert moved_by_positions(cfg, "sliding_attention") > 1e-3
    unnamed = dataclasses.replace(cfg, rope_tables=cfg.rope_tables[1:])
    assert moved_by_positions(unnamed, "full_attention") > 1e-3


# -------------------------------------- (d) the share tied to the model
def test_d_the_four_quarter_shares_add_up_to_the_uncut_layer():
    """One layer of the toy as each of four chips holds it (experts 0-1, 2-3,
    4-5, 6-7; attention and the residual whole on each): the four routed
    parts add up to the uncut reference's, so attention and the residual
    counted once plus the four parts is the uncut layer; and the held rows'
    logits are the uncut head's on those rows."""
    whole_cfg = toys.config(WHOLE, attention_impl="reference", remat=False)
    block = LlamaBlock(whole_cfg, "sparse", "sliding_attention")
    x = jax.random.normal(jax.random.PRNGKey(9), (2, 64, 64))
    positions = jnp.arange(64)
    params = toys.moved(jax.jit(block.init)(jax.random.PRNGKey(10), x,
                                            positions)["params"])

    def share(chip):
        cfg = dataclasses.replace(whole_cfg, experts_held=(2 * chip, 2))
        moe = {k: v[2 * chip:2 * chip + 2] if k.endswith("_proj") else v
               for k, v in params["moe"].items()}
        return LlamaBlock(cfg, "sparse", "sliding_attention").apply(
            {"params": dict(params, moe=moe)}, x, positions)

    with jax.default_matmul_precision("highest"):
        whole = jax.jit(lambda: block.apply({"params": params}, x,
                                            positions))()
        parts = jax.jit(lambda: [share(chip) for chip in range(4)])()
    # attention and the residual are in every share: counted once
    without_experts = (sum(parts) - whole) / 3
    np.testing.assert_allclose(
        without_experts + sum(p - without_experts for p in parts), whole,
        rtol=1e-5, atol=1e-5)
    assert float(jnp.max(jnp.abs(whole - without_experts))) > 1e-2
    for a, b in zip(parts, parts[1:]):  # no two chips add the same part
        assert float(jnp.max(jnp.abs(a - b))) > 1e-3

    # the head's rows: a quarter of the uncut head's columns, as they are
    uncut = dict(TOY, vocab_size=2048)
    model, big = toys.weights(uncut, by=0.0, attention_impl="reference")
    ids = toys.rows(TOY, 2, 64)["input_ids"]
    small = jax.tree_util.tree_map(lambda a: a, big)
    small = dict(small, wte={"embedding": big["wte"]["embedding"][:512]},
                 lm_head={"kernel": big["lm_head"]["kernel"][:, :512]})
    with jax.default_matmul_precision("highest"):
        all_rows = jax.jit(lambda p: smallthinker.logits(p, ids, uncut))(big)
        held_rows = jax.jit(lambda p: smallthinker.logits(p, ids, TOY))(small)
    np.testing.assert_allclose(held_rows, all_rows[..., :512], rtol=1e-5,
                               atol=1e-5)


# --------------------------------------------------- (e) the trainer's step
def _one_device():
    return toys.one_device(TOY, 4, 64, 6, lr=0.1)


def test_e_the_trainers_step_takes_the_references_loss_down():
    """Through ``ShardedPretrainer``, the path the benchmark times: the first
    step's loss is the reference's on the same batch and weights, the steps
    report the pre-attention router's counters, and the loss falls."""
    want, losses, stats, *_ = _one_device()
    assert losses[0] == pytest.approx(want, rel=1e-4)
    assert losses[-1] < losses[0] - 0.3
    assert set(stats) == {"load_balance", "z", "max_load", "moe_rows_held",
                          "moe_buffer_rows"}
    # four rows of 64 tokens take 3 of 8 experts each, 2 of them held here
    assert 0 < float(stats["moe_rows_held"]) <= 4 * 64 * 2
    assert float(stats["moe_rows_held"]) <= float(stats["moe_buffer_rows"])


@pytest.mark.parametrize("mesh", [{"dp": 1, "fsdp": 4}, {"dp": 2, "tp": 2}])
def test_e_a_sharded_mesh_gives_the_single_device_loss(mesh):
    """``wq`` (64 x 224) and the held experts under the rules that serve the
    older models; at ``tp`` 2 a device holds one key/value head and its seven
    query heads, whole heads, and the grouped form stays.  The steps give one
    device's losses."""
    from jax.sharding import PartitionSpec as P

    from ray_tpu.models.pretrain import ShardedPretrainer
    from ray_tpu.parallel.mesh import MeshConfig
    from ray_tpu.parallel.sharding import (llama_partition_rules,
                                           match_partition_rules)

    specs = match_partition_rules(llama_partition_rules(),
                                  toys.weights(TOY)[1])
    assert specs["h_0"]["attn"]["wq"]["kernel"] == P("fsdp", "tp")
    assert specs["h_0"]["moe"]["router"]["kernel"] == P()
    one = _one_device()
    many = ShardedPretrainer(toys.config(TOY), MeshConfig(**mesh),
                             devices=jax.devices()[:4], lr=0.1)
    for want in one.losses[:3]:
        assert float(many.step(one.rows)) == pytest.approx(want, rel=2e-5)

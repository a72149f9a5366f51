"""NVIDIA-Nemotron-3-Nano-30B-A3B's stack (``nemotron_h``) through the shared
Llama block, at toy widths on the CPU, with seeded weights moved off their
initial values: the program (``models/llama.py`` with layers that are one
branch behind one norm — ``layer_types`` / ``mlp_types`` entries ``"none"`` —;
``models/mamba.py``'s gated norm a group over ``ops/ssd.py`` in groups;
``models/moe.py`` under ``activation="relu2"``, experts of two matrices and no
gate, in the routed layer, the held loop and the shared expert) against the
plain reference of ``perfbench/harness/families/nemotron_h.py`` — the
recurrence position by position, the convolution as shifted multiply-adds, a
full masked softmax with each key/value head copied to its query heads, every
held expert on every token, the router in the published order.  The toy
(``perfbench/tests/toy/toy-nemotron-h.json``): 64 wide, the published
pattern's first nine letters ``MEMEM*EME``, 8 Mamba heads of 8 in 4 groups
over a state of 16 at chunks of 16, 8 query heads over 2 key/value heads of
16, 8 experts of 24 of which 2 are held (chip 1 of 4), top-3, a shared expert
of 48, sequences of 64.

(a) the stack: logits, loss, gradient norm, every gradient leaf, remat on and
off; (b) the sixteen-chip deployment in small: the four shares of two experts,
the shared expert counted once, give the uncut layer's output; (c) each wrong
model against the tolerance; (d) the held loop's written-out squared-ReLU
derivative against reverse mode; (e) ``ssd_scan`` at eight groups of eight
heads and chunk 128 in the interpreter against the recurrence, forward and
gradients; (f) one group through the grouped norm is the norm it was, bit for
bit; (g) what the stack refuses; (h) the trainer's step on one device and on
the virtual meshes.  On the chip the same reference runs at published widths
against the bf16 program (``perfbench/harness/agreement.py``).
"""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import toys
from perfbench.harness.families import nemotron_h
from ray_tpu.models import moe
from ray_tpu.models.llama import LlamaBlock, LlamaConfig
from ray_tpu.models.mamba import Mamba2Mixer
from ray_tpu.models.moe import RoutedConfig, RoutedSwiGLU, SharedSwiGLU
from ray_tpu.ops import ssd

TOY = toys.toy("toy-nemotron-h")
# the same layers on a chip that holds all eight experts
WHOLE = dict(TOY, n_routed_experts=8,
             deployment={"chips_sharing_a_layer": 1, "this_chip": 0})


def _same(got, want, rtol, atol):
    for (path, g), w in zip(jax.tree_util.tree_flatten_with_path(got)[0],
                            jax.tree_util.tree_leaves(want)):
        np.testing.assert_allclose(g, w, rtol=rtol, atol=atol,
                                   err_msg=jax.tree_util.keystr(path))


# ------------------------------------------------------------ (a) the stack
def test_a_a_layer_is_one_branch_behind_one_norm():
    """The parameter tree: an ``M`` or ``*`` layer has ``attn_norm`` and its
    mixer and nothing else, an ``E`` layer ``mlp_norm`` and ``moe``; no
    ``gate_proj`` exists anywhere, in the held experts or in the shared one;
    and the count is the family's, the published parameters held."""
    _, params = toys.weights(TOY, by=0.0)
    kinds = {"M": {"attn_norm", "mamba"}, "*": {"attn_norm", "attn"},
             "E": {"mlp_norm", "moe"}}
    for i, letter in enumerate("MEMEM*EME"):
        assert set(params[f"h_{i}"]) == kinds[letter], (i, letter)
    assert set(params["h_1"]["moe"]) == {
        "router", "selection_bias", "up_proj", "down_proj", "shared"}
    assert set(params["h_1"]["moe"]["shared"]) == {"up_proj", "down_proj"}
    assert params["h_1"]["moe"]["up_proj"].shape == (2, 64, 24)
    assert sum(leaf.size for leaf in jax.tree_util.tree_leaves(params)) \
        == nemotron_h.n_params(TOY)


@pytest.mark.parametrize("config,impl,positions", [
    (TOY, "reference", 64), (TOY, "flash", 64), (TOY, "flash", 52),
    (WHOLE, "flash", 64)],
    ids=["part-reference", "part-flash", "part-flash-52", "all-flash"])
def test_a_program_equals_the_reference_in_float32(config, impl, positions):
    """Logits, loss and the gradient norm to float32 rounding, a part of the
    experts held and all of them (the held loop's hand-written backward, and
    autodiff through the grouped matmuls); 52 positions are not whole chunks
    of the scan nor whole tiles of the flash kernels."""
    got = toys.program(config, positions, attention_impl=impl)
    want = toys.reference(config, positions, attention_impl=impl)
    assert got.logits.shape == (2, positions, 512)
    np.testing.assert_allclose(got.logits, want.logits, rtol=2e-4, atol=2e-4)
    assert float(got.loss) == pytest.approx(float(want.loss), rel=1e-5)
    assert float(got.gradnorm) == pytest.approx(float(want.gradnorm),
                                                rel=1e-4)
    assert float(want.held) > 0


def test_a_every_gradient_equals_the_references():
    """Leaf by leaf, not only the norm: the two matrices of the held experts
    through the held loop's written-out backward, the shared expert's, the
    grouped norm's scale, the scan's ``A_log``, ``dt_bias`` and ``D``."""
    got = toys.program(TOY, 64, attention_impl="flash").grads
    want = toys.reference(TOY, 64, leaves=True, attention_impl="flash").grads
    _same(got, want, rtol=2e-3, atol=2e-5)
    assert float(jnp.max(jnp.abs(got["h_1"]["moe"]["up_proj"]))) > 1e-4
    assert not np.any(np.asarray(got["h_1"]["moe"]["selection_bias"]))


def test_a_remat_on_and_off_give_the_same_gradients():
    """``remat_block`` wraps one branch; with and without it every leaf's
    gradient is the same."""
    on = toys.program(TOY, 64, attention_impl="flash").grads
    off = toys.program(TOY, 64, attention_impl="flash", remat=False).grads
    _same(on, off, rtol=1e-4, atol=1e-6)


# -------------------------------------- (b) the share tied to the model
def test_b_the_four_shares_add_up_to_the_uncut_layer():
    """One ``E`` layer of the toy as each of four chips holds it (experts
    0-1, 2-3, 4-5, 6-7; the router, the norm, the shared expert and the
    residual whole on each): the four routed parts, with what every chip
    computes alike counted once, add up to the uncut layer's output, which
    is the uncut reference's."""
    whole_cfg = toys.config(WHOLE, remat=False)
    block = LlamaBlock(whole_cfg, "sparse", "none")
    x = jax.random.normal(jax.random.PRNGKey(9), (2, 64, 64))
    positions = jnp.arange(64)
    params = toys.moved(jax.jit(block.init)(jax.random.PRNGKey(10), x,
                                            positions)["params"])

    def share(chip, d_shared=whole_cfg.d_shared_expert):
        cfg = dataclasses.replace(whole_cfg, experts_held=(2 * chip, 2),
                                  d_shared_expert=d_shared)
        mine = {k: v[2 * chip:2 * chip + 2] if k.endswith("_proj") else v
                for k, v in params["moe"].items()
                if d_shared or k != "shared"}
        return LlamaBlock(cfg, "sparse", "none").apply(
            {"params": dict(params, moe=mine)}, x, positions)

    with jax.default_matmul_precision("highest"):
        whole = jax.jit(lambda: block.apply({"params": params}, x,
                                            positions))()
        parts = jax.jit(lambda: [share(chip) for chip in range(4)])()
        # a share without the shared expert: the residual and its routed part
        bare = jax.jit(lambda: [share(chip, 0) for chip in range(4)])()
        n = nemotron_h._rms(x, WHOLE["norm_eps"]) * params["mlp_norm"]["scale"]
        want = x + jax.jit(lambda p, n: nemotron_h.experts(p, n, WHOLE)[0])(
            params["moe"], n)
    # the residual and the shared expert are in every share: counted once
    alike = parts[0] - (bare[0] - x)
    np.testing.assert_allclose(
        alike + sum(b - x for b in bare), whole, rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(whole, want, rtol=1e-5, atol=1e-5)
    for a, b in zip(bare, bare[1:]):    # no two chips add the same part
        assert float(jnp.max(jnp.abs(a - b))) > 1e-3
    assert float(jnp.max(jnp.abs(alike - x))) > 1e-2    # the shared expert


# ------------------------------------------------------ (c) wrong models
@pytest.mark.parametrize(
    "wrong", nemotron_h.WRONG + (nemotron_h.PRECISION_BELOW,))
def test_c_the_tolerance_sees_each_wrong_model(wrong):
    """Each wrong model moves the toy's logits by far more than (a)'s
    tolerance — the gated norm over all channels or after the norm's gate,
    one group of B and C for all heads, a plain ReLU, a gated expert, a mixer
    and the feed-forward after it as the two halves of one parallel block,
    the routed scale left out, the choice by the scores without the (moved,
    non-zero) bias, softmax scores, top-(k - 1), RoPE switched on, key/value
    head ``h % 2``, the router or the scan's running sums in bf16 — and so
    does the reference itself with float8 activations.  The program's logits
    are (a)'s ``part-reference`` case's."""
    got = toys.program(TOY, 64, attention_impl="reference").logits
    want = toys.reference(TOY, 64, backward=False, wrong=wrong,
                          attention_impl="reference").logits
    assert float(jnp.max(jnp.abs(got - want))) > 100 * 2e-4


def test_c_the_references_blocks_are_the_whole_row(monkeypatch):
    """The reference walks its recurrence ``SCAN_BLOCK`` positions under one
    checkpoint and its scores ``Q_BLOCK`` queries at a time; blocks of 16
    give what one block of 64 gives."""
    _, params = toys.weights(TOY)
    ids = toys.rows(TOY, 2, 64)["input_ids"]
    with jax.default_matmul_precision("highest"):
        whole = jax.jit(lambda p: nemotron_h.logits(p, ids, TOY))(params)
        monkeypatch.setattr(nemotron_h, "Q_BLOCK", 16)
        monkeypatch.setattr(nemotron_h, "SCAN_BLOCK", 16)
        blocks = jax.jit(lambda p: nemotron_h.logits(p, ids, TOY))(params)
    np.testing.assert_allclose(blocks, whole, rtol=1e-5, atol=1e-5)


# ----------------------------------- (d) the squared ReLU's own derivative
def _layer(held, **more):
    return RoutedSwiGLU(RoutedConfig(
        n_experts=8, top_k=3, d_model=16, d_ff=8, norm_topk_prob=True,
        dtype=jnp.float32, experts_held=held, activation="relu2",
        scoring="sigmoid", routed_scale=2.5, selection_bias=True,
        norm_topk_eps=1e-20, **more))


@pytest.mark.parametrize("held", [(2, 2), None], ids=["held-loop", "all"])
def test_d_the_squared_relu_through_both_paths(held):
    """``relu(up) ** 2`` through ``routed_experts`` (all eight held: autodiff
    through the two grouped matmuls) and through the held loop's hand-written
    backward (two of eight: ``2 relu(a)``, four grouped matmuls), with a
    shared expert of the same form: the output and every operand's gradient
    against reverse mode through the family's plain lines."""
    layer = _layer(held, d_shared=12)
    keys = jax.random.split(jax.random.PRNGKey(3), 3)
    x, g = (jax.random.normal(k, (2, 64, 16)) for k in keys[:2])
    params = toys.moved(jax.jit(layer.init)(keys[2], x)["params"])
    assert "gate_proj" not in params and "gate_proj" not in params["shared"]
    first = held[0] if held else 0
    config = {"num_experts_per_tok": 3, "norm_topk_prob": True,
              "routed_scaling_factor": 2.5, "n_routed_experts": held[1]
              if held else 8, "published_counts": {"n_routed_experts": 8},
              "deployment": {"this_chip": first // 2 if held else 0}}

    def ours(params, x):
        return jnp.sum(layer.apply({"params": params}, x) * g)

    def plain(params, x):
        return jnp.sum(nemotron_h.experts(params, x, config)[0] * g)

    with jax.default_matmul_precision("highest"):
        got, want = (jax.jit(jax.value_and_grad(f, argnums=(0, 1)))(params, x)
                     for f in (ours, plain))
    assert float(got[0]) == pytest.approx(float(want[0]), rel=1e-5)
    _same(got[1], want[1], rtol=1e-4, atol=1e-5)
    assert float(jnp.max(jnp.abs(got[1][0]["up_proj"]))) > 1e-3


def test_d_the_written_out_derivative_is_reverse_modes():
    """``_hidden_bwd`` under ``relu2`` against ``jax.vjp`` of ``_hidden``,
    zeros and negatives among the pre-activations; and the gated forms are
    the functions they were."""
    a = jnp.asarray([[-1.5, 0.0, 0.25, 2.0], [3.0, -0.0, 1e-3, -7.0]])
    d_h = jax.random.normal(jax.random.PRNGKey(0), a.shape)
    want = jax.vjp(lambda a: moe._hidden((a,), "relu2"), a)[1](d_h)
    np.testing.assert_array_equal(moe._hidden_bwd((a,), d_h, "relu2")[0],
                                  want[0])
    np.testing.assert_array_equal(moe._hidden((a,), "relu2"),
                                  jnp.where(a > 0, a * a, 0.0))
    b = jax.random.normal(jax.random.PRNGKey(1), a.shape)
    for act in ("silu", "relu"):
        want = jax.vjp(lambda a, b: moe._gated(a, act) * b, a, b)[1](d_h)
        for got, w in zip(moe._hidden_bwd((a, b), d_h, act), want):
            np.testing.assert_allclose(got, w, rtol=1e-6, atol=1e-7)
    with pytest.raises(ValueError, match="unknown activation"):
        moe._hidden((a, b), "gelu")


def test_d_the_shared_expert_under_relu2_has_two_matrices():
    x = jax.random.normal(jax.random.PRNGKey(2), (3, 16))
    shared = SharedSwiGLU(16, 12, jnp.float32, "relu2")
    params = jax.jit(shared.init)(jax.random.PRNGKey(4), x)["params"]
    assert set(params) == {"up_proj", "down_proj"}
    with jax.default_matmul_precision("highest"):
        want = jnp.square(jax.nn.relu(x @ params["up_proj"]["kernel"])) \
            @ params["down_proj"]["kernel"]
        np.testing.assert_allclose(shared.apply({"params": params}, x), want,
                                   rtol=1e-5, atol=1e-6)


# --------------------------- (e) the scan at eight groups of eight heads
def test_e_the_scan_at_eight_groups_of_eight_heads_and_chunk_128():
    """The cell's grid step in the interpreter: 64 heads of 64 in 8 groups
    over a state of 128, chunks of 128 — a whole chunk, and a ragged second —
    in float32 against the recurrence position by position, values and the
    five gradients; and a head reads its own group's B and C: with one
    group's B moved, only that group's eight heads' outputs move."""
    seq, heads, p, groups, n = 160, 64, 64, 8, 128
    k = jax.random.split(jax.random.PRNGKey(63), 5)
    args = (jax.random.normal(k[0], (1, seq, heads, p)),
            jnp.exp(jax.random.uniform(k[1], (1, seq, heads), jnp.float32,
                                       np.log(1e-3), np.log(0.1))),
            -jax.random.uniform(k[2], (heads,), jnp.float32, 1.0, 16.0),
            jax.random.normal(k[3], (1, seq, groups, n)) / np.sqrt(n),
            jax.random.normal(k[4], (1, seq, groups, n)))

    def plain(x, dt, rate, b, c):
        b, c = (jnp.repeat(t, heads // groups, axis=2) for t in (b, c))
        return nemotron_h.recurrence(x, dt, dt * rate, b, c)

    def scan(*a):
        return ssd.ssd_scan(*a, chunk=128)

    def grads(fn):
        return jax.jit(jax.grad(lambda *a: jnp.sum(jnp.sin(fn(*a))),
                                argnums=(0, 1, 2, 3, 4)))(*args)

    with jax.default_matmul_precision("highest"):
        got, want = jax.jit(scan)(*args), jax.jit(plain)(*args)
        np.testing.assert_allclose(got, want, rtol=0,
                                   atol=1e-4 * float(jnp.max(jnp.abs(want))))
        for name, g, w in zip("x dt A B C".split(), grads(scan),
                              grads(plain)):
            np.testing.assert_allclose(
                g, w, rtol=0, atol=1e-3 * float(jnp.max(jnp.abs(w))),
                err_msg=name)
        x, dt, rate, b, c = args
        moved = jax.jit(scan)(x, dt, rate, b.at[:, :, 3].multiply(2.0), c)
    changed = np.asarray(jnp.max(jnp.abs(moved - got), axis=(0, 1, 3)) > 0)
    assert changed.tolist() == [h // 8 == 3 for h in range(heads)]


# ------------------------------------------------- (f) the norm by group
def _mixer(groups):
    cfg = LlamaConfig(d_model=32, mamba_n_heads=4, mamba_d_head=8,
                      mamba_d_state=16, mamba_n_groups=groups, mamba_chunk=16,
                      dtype=jnp.float32)
    return Mamba2Mixer(cfg)


def _normed(groups, x, seed=6):
    """What reaches ``out_proj`` of a mixer in ``groups`` groups, its norm's
    scale divided out: the mixer with an identity there."""
    mixer = _mixer(groups)
    params = toys.moved(jax.jit(mixer.init)(jax.random.PRNGKey(seed),
                                            x)["params"])
    eye = dict(params, out_proj={"kernel": jnp.eye(32)})
    with jax.default_matmul_precision("highest"):
        out = jax.jit(lambda p: mixer.apply({"params": p}, x))(eye)
    return np.asarray(out) / np.asarray(params["norm_scale"])


def test_f_one_group_through_the_grouped_norm_is_the_norm_it_was():
    """``mamba_n_groups`` 1: the gated norm's lines trace to the operations
    they were — no reshape into groups, no reshape back (the lowered step of
    Granite's toy is pinned whole in ``test_pinned_steps.py``: bit for bit) —
    and every position's 32 channels have a mean square of one.  Two groups:
    each group's 16 channels have, and all 32 together have not."""
    x = jax.random.normal(jax.random.PRNGKey(5), (2, 32, 32))

    def norm_lines(groups):
        mixer = _mixer(groups)
        params = jax.jit(mixer.init)(jax.random.PRNGKey(6), x)["params"]
        text = str(jax.make_jaxpr(
            lambda p: mixer.apply({"params": p}, x))(params))
        # from the gate's logistic to the cast before out_proj
        return text[text.index("logistic", text.index("ssd")):]

    assert "reshape" not in norm_lines(1).split("dot_general")[0]
    assert "reshape" in norm_lines(2).split("dot_general")[0]
    one, two = _normed(1, x), _normed(2, x)
    np.testing.assert_allclose(np.mean(one ** 2, -1), 1.0, rtol=1e-2)
    np.testing.assert_allclose(
        np.mean(two.reshape(2, 32, 2, 16) ** 2, -1), 1.0, rtol=1e-2)
    assert np.max(np.abs(
        np.mean(one.reshape(2, 32, 2, 16) ** 2, -1) - 1.0)) > 0.1


# ------------------------------------------------ (g) what is refused
def test_g_what_the_stack_refuses():
    """A layer with neither branch, a feed-forward kind that is none of the
    three, a router that is to read an attention input the layer has not:
    refused by name, under ``eval_shape``."""
    cfg = toys.config(TOY, remat=False)
    x, positions = jnp.zeros((1, 16, 64)), jnp.arange(16)

    def init(cfg, mlp, mixer):
        block = LlamaBlock(cfg, mlp, mixer)
        return jax.eval_shape(block.init, jax.random.PRNGKey(0), x, positions)

    with pytest.raises(ValueError, match="the feed-forward 'none'"):
        init(cfg, "none", "none")
    with pytest.raises(ValueError, match="the feed-forward 'routed'"):
        init(cfg, "routed", "mamba")
    with pytest.raises(ValueError, match="no attention input"):
        init(dataclasses.replace(cfg, router_before_attention=True),
             "sparse", "none")
    assert set(init(cfg, "none", "mamba")["params"]) == {"attn_norm", "mamba"}
    assert set(init(cfg, "dense", "none")["params"]) == {"mlp_norm", "mlp"}


# --------------------------------------------------- (h) the trainer's step
def _one_device():
    return toys.one_device(TOY, 4, 64, 6, lr=0.03)


def test_h_the_trainers_step_takes_the_references_loss_down():
    """Through ``ShardedPretrainer``, the path the benchmark times: the first
    step's loss is the reference's on the same batch and weights, the steps
    report the routed layers' counters, and the loss falls."""
    want, losses, stats, *_ = _one_device()
    assert losses[0] == pytest.approx(want, rel=1e-4)
    assert losses[-1] < losses[0] - 0.3
    assert set(stats) == {"load_balance", "z", "max_load", "moe_rows_held",
                          "moe_buffer_rows"}
    # four rows of 64 tokens take 3 of 8 experts each, 2 of them held here
    assert 0 < float(stats["moe_rows_held"]) <= 4 * 64 * 2
    assert float(stats["moe_rows_held"]) <= float(stats["moe_buffer_rows"])


@pytest.mark.parametrize("mesh", [{"dp": 1, "fsdp": 4}, {"dp": 2, "tp": 2}])
def test_h_a_sharded_mesh_gives_the_single_device_loss(mesh):
    """The one-branch layers under the rules that serve the older models: the
    Mamba projections and the attention's as theirs, the held experts' two
    matrices as a gated expert's ``up_proj`` and ``down_proj``.  The steps
    give one device's losses."""
    from jax.sharding import PartitionSpec as P

    from ray_tpu.models.pretrain import ShardedPretrainer
    from ray_tpu.parallel.mesh import MeshConfig
    from ray_tpu.parallel.sharding import (llama_partition_rules,
                                           match_partition_rules)

    specs = match_partition_rules(llama_partition_rules(),
                                  toys.weights(TOY)[1])
    assert specs["h_0"]["mamba"]["in_proj"]["kernel"] == P("fsdp", "tp")
    assert specs["h_1"]["moe"]["up_proj"] == P("ep", "fsdp", "tp")
    assert specs["h_1"]["moe"]["shared"]["down_proj"]["kernel"] == P(
        "tp", "fsdp")
    assert specs["h_5"]["attn"]["wq"]["kernel"] == P("fsdp", "tp")
    one = _one_device()
    many = ShardedPretrainer(toys.config(TOY), MeshConfig(**mesh),
                             devices=jax.devices()[:4], lr=0.03)
    for want in one.losses[:3]:
        assert float(many.step(one.rows)) == pytest.approx(want, rel=2e-5)

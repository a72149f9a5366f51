"""Phi-4-mini-flash-reasoning (SambaY) through the shared Llama block, at toy
widths on the CPU in float32, with seeded weights moved off their initial
values: (b) the mixers — ``Mamba1Mixer``, ``GatedMemoryUnit`` — against the
plain forms of ``perfbench/harness/families/phi4_flash.py``, differential
attention against two ``mha_reference`` calls, under the window and causal,
and a block with every new field unset the block it was; (c) the whole stack
— tensors that cross blocks through ``remat_block`` — against the plain
reference: logits, loss, every gradient leaf (on a stack with two gated memory
units and two cross layers, so that each producer's gradient is a sum over
two readers), a ``ShardedPretrainer`` step, remat on and off, and every wrong
model of the on-chip controls outside the float32 tolerance; (d) the cut tied
to the model: the six kinds of layer at the seam give an uncut 12-layer
reference's output of its layer 9 from its input of layer 4 (the published
order at a third of the depth: 32 toy layers cost tier-1 half a minute), and
the held rows' logits are the uncut head's; (e) the new parameters' partition rules on a virtual mesh, a
sharded sequence refused, a reader before its producer and a producer without
a reader refused; (f) every toy's lowered step is held by
``tests/test_pinned_steps.py``, the older programs' losses and kernels by
``tests/test_sdar_programs.py`` (i) and ``tests/test_sdar_parts.py`` (n).  The
scan alone is ``tests/test_selective_scan.py``'s.  The toy
(``perfbench/tests/toy/toy-phi4-flash.json``): 64 wide, the six kinds of layer
(published 14 to 19 of 32), 4 / 2 heads of 16, 128 channels of 4 states (the
interpreted kernels write a position's states out one by one: 16 of them,
which ``tests/test_selective_scan.py`` has, are three times the program to
trace), window 8, ``scan_block`` 8.
"""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import toys
from perfbench.harness import reference
from perfbench.harness.families import phi4_flash
from ray_tpu.models.llama import (DifferentialAttention, LlamaBlock,
                                  LlamaConfig, LlamaLMModel, carried_plan)
from ray_tpu.models.mamba import GatedMemoryUnit, Mamba1Mixer
from ray_tpu.models.pretrain import init_params
from ray_tpu.ops.attention import mha_reference

TOY = toys.toy("toy-phi4-flash")
# one more pair of the cross-decoder, one layer less of the self-decoder:
# every kind of layer, and m and the keys and values each with two readers in
# blocks of their own
SEVEN = dict(TOY, num_hidden_layers=7, layers_kept=list(range(15, 22)))
# the same order at 12 layers (Mamba-1 at 0, 2, 4, 6, the last handing on;
# window attention at 1, 3, 5; whole-row attention at 7; memory units at 8,
# 10; cross layers at 9, 11), and its layers 4 to 9: the six kinds at the seam
UNCUT = dict(TOY, num_hidden_layers=12, layers_kept=None,
             published_counts=dict(TOY["published_counts"],
                                   num_hidden_layers=12))
SEAM = dict(UNCUT, num_hidden_layers=6, layers_kept=list(range(4, 10)))
STACKS = {"six": TOY, "seven": SEVEN}
# the seam's four layers (Mamba-1 handing on, whole-row attention handing on,
# a memory unit, a cross layer), for the steps that are compiled under a mesh
FOUR = dict(TOY, num_hidden_layers=4, layers_kept=list(range(16, 20)))


def _same(got, want, rtol=2e-3, atol=2e-5):
    for (path, g), w in zip(jax.tree_util.tree_flatten_with_path(got)[0],
                            jax.tree_util.tree_leaves(want)):
        np.testing.assert_allclose(g, w, rtol=rtol, atol=atol,
                                   err_msg=jax.tree_util.keystr(path))


# ------------------------------------------------------------ (b) the mixers
@pytest.mark.parametrize("seq", [21])
def test_b_the_mamba1_mixer_equals_the_plain_form(seq):
    """``Mamba1Mixer`` alone — ``in_proj``'s two parts, the convolution and
    silu, ``x_proj``, the step sizes through ``dt_proj`` and softplus, the
    scan's kernels, the skip, the gate, ``out_proj`` — against the
    reference's layer: the output, the scan output it hands on, and every
    parameter's gradient; 21 positions are no whole blocks."""
    cfg = toys.config(TOY)
    mixer = Mamba1Mixer(cfg)
    x = jax.random.normal(jax.random.PRNGKey(0), (2, seq, cfg.d_model))
    params = toys.moved(jax.jit(mixer.init)(jax.random.PRNGKey(1), x)["params"])
    assert set(params) == {"in_proj", "x_proj", "dt_proj", "out_proj",
                           "conv_kernel", "conv_bias", "dt_bias", "A_log",
                           "D"}
    assert params["in_proj"]["kernel"].shape == (64, 2, 128)
    assert params["A_log"].shape == (128, 4)
    weights = [jax.random.normal(jax.random.PRNGKey(k), (2, seq, width))
               for k, width in ((2, 64), (3, 128))]

    def program(p, x):
        return mixer.apply({"params": p}, x)

    def plain(p, x):
        return phi4_flash.mamba1(x, p, TOY)

    def with_grads(f):
        return jax.jit(lambda p, x: (f(p, x), jax.grad(
            lambda p, x: sum(jnp.sum(o * w) for o, w in zip(f(p, x), weights)),
            argnums=(0, 1))(p, x)))

    with jax.default_matmul_precision("highest"):
        (out, got), (plain_out, want) = (
            with_grads(f)(params, x) for f in (program, plain))
    for o, w in zip(out, plain_out):
        np.testing.assert_allclose(o, w, atol=5e-5)
    _same(got, want, atol=1e-4)


def test_b_the_gated_memory_unit_equals_the_plain_form():
    cfg = toys.config(TOY)
    unit = GatedMemoryUnit(cfg)
    x = jax.random.normal(jax.random.PRNGKey(0), (2, 24, cfg.d_model))
    m = jax.random.normal(jax.random.PRNGKey(1), (2, 24, 128))
    params = toys.moved(unit.init(jax.random.PRNGKey(2), x, m)["params"])
    assert set(params) == {"in_proj", "out_proj"}
    with jax.default_matmul_precision("highest"):
        np.testing.assert_allclose(unit.apply({"params": params}, x, m),
                                   phi4_flash.gmu(x, params, m), atol=2e-5)


@pytest.mark.parametrize("impl", ["reference", "flash"])
@pytest.mark.parametrize("kind", ["sliding_attention", "full_attention",
                                  "cross_attention"])
def test_b_differential_attention_is_two_softmaxes_subtracted(kind, impl):
    """``DifferentialAttention`` against ``mha_reference`` called twice — the
    even query heads against the even key heads and the odd against the odd,
    a key head to two query heads, both over the value heads in pairs side by
    side — under the window of 8 and causal, with keys and values of its own
    and (a cross layer) another layer's; then lam, the subtraction, the
    sub-layer norm over a pair's 32 and ``1 - lam0`` at depth 17."""
    cfg = toys.config(TOY, attention_impl=impl)
    layer = DifferentialAttention(cfg, kind, depth=17)
    x = jax.random.normal(jax.random.PRNGKey(0), (2, 40, cfg.d_model))
    given = jax.random.normal(jax.random.PRNGKey(3), (2, 40, 64)) \
        if kind == "cross_attention" else None
    p = toys.moved(jax.jit(layer.init)(jax.random.PRNGKey(1), x, given)["params"])
    assert set(p) == {"wq" if given is not None else "wqkv", "wo",
                      "lambda_q1", "lambda_k1", "lambda_q2", "lambda_k2",
                      "sub_norm"}
    assert p["sub_norm"]["scale"].shape == (32,) and "bias" in p["wo"]
    with jax.default_matmul_precision("highest"):
        got, kv = jax.jit(layer.apply)({"params": p}, x, given)
        if given is None:
            qkv = reference.dense(x, p["wqkv"])
            q, given = qkv[..., :64], qkv[..., 64:]
            np.testing.assert_array_equal(kv, given)
        else:
            q = reference.dense(x, p["wq"])
        q = q.reshape(2, 40, 4, 16).transpose(0, 2, 1, 3)
        k = given[..., :32].reshape(2, 40, 2, 16).transpose(0, 2, 1, 3)
        v = given[..., 32:].reshape(2, 40, 1, 32).transpose(0, 2, 1, 3)
        a1, a2 = (mha_reference(
            q[:, i::2], jnp.repeat(k[:, i::2], 2, axis=1),
            jnp.repeat(v, 2, axis=1),
            window=8 if kind == "sliding_attention" else 0) for i in (0, 1))
        lam0 = 0.8 - 0.6 * np.exp(-0.3 * 17)
        lam = jnp.exp(jnp.sum(p["lambda_q1"] * p["lambda_k1"])) \
            - jnp.exp(jnp.sum(p["lambda_q2"] * p["lambda_k2"])) + lam0
        o = reference.rms_norm(a1 - lam * a2, p["sub_norm"], cfg.rms_eps) \
            * (1 - lam0)
        want = reference.dense(
            o.transpose(0, 2, 1, 3).reshape(2, 40, 64), p["wo"])
    np.testing.assert_allclose(got, want, atol=3e-5)
    assert float(jnp.max(jnp.abs(a1 - a2))) > 1e-2


def test_b_a_block_with_every_new_field_unset_is_the_block_it_was():
    """RMSNorm without a bias, ``wq``, ``wk``, ``wv``, ``wo`` without biases,
    one array out, no third argument; and ``carried_plan`` reads nothing."""
    cfg = dataclasses.replace(LlamaConfig.tiny(), dtype=jnp.float32,
                              attention_impl="reference")
    assert (cfg.norm, cfg.attn_bias, cfg.diff_attn, cfg.producers,
            cfg.layer_depths) == ("rms", False, False, (), ())
    assert carried_plan(cfg) == (None,) * cfg.n_layer
    block = LlamaBlock(cfg)
    x = jax.random.normal(jax.random.PRNGKey(0), (1, 16, cfg.d_model))
    params = block.init(jax.random.PRNGKey(1), x, jnp.arange(16))["params"]
    assert set(params) == {"attn_norm", "attn", "mlp_norm", "mlp"}
    assert set(params["attn_norm"]) == {"scale"}
    assert set(params["attn"]) == {"wq", "wk", "wv", "wo"}
    assert all(set(leaf) == {"kernel"} for leaf in params["attn"].values())
    out = block.apply({"params": params}, x, jnp.arange(16))
    assert isinstance(out, jax.Array) and out.shape == x.shape


# ------------------------------------------ (c) the stack and its reference
# weights moved off their start by 0.05 a leaf, each stack's made once
_BY = dict(by=0.05)


def _ours(stack: str, positions: int, backward: bool = False,
             remat: bool = True):
    """(logits, loss, gradients) of the program, compiled once for the tests
    that read it.  With the backward the attention is ``mha_reference`` (the
    flash kernels' backward at these widths is the trainer's step's, below,
    and ``tests/test_flash_layout.py``'s): a third of the graph to compile,
    the scan's kernels and the tensors that cross blocks as they are."""
    if not backward:
        return toys.program(STACKS[stack], positions, backward=False, **_BY)
    return toys.program(STACKS[stack], positions, remat=remat,
                        attention_impl="reference", **_BY)


def _theirs(stack: str, positions: int, backward: bool = False):
    """The same of the reference."""
    return toys.reference(STACKS[stack], positions, backward=False,
                          leaves=backward, **_BY)


def test_c_the_toy_is_the_six_kinds_at_the_seam():
    cfg = toys.config(TOY)
    assert cfg.layer_types == ("mamba1", "sliding_attention", "mamba1",
                               "full_attention", "gmu", "cross_attention")
    assert cfg.producers == (2, 3) and carried_plan(cfg) == (
        None, None, None, None, 2, 3)
    assert carried_plan(toys.config(SEVEN)) == (None,) * 3 + (1, 2, 1, 2)
    assert cfg.layer_depths == (14, 15, 16, 17, 18, 19)
    assert (cfg.norm, cfg.rope, cfg.tie_embeddings, cfg.diff_attn,
            cfg.attn_bias) == ("layer", False, True, True, True)
    assert [phi4_flash.layer_kind(i, 32) for i in (0, 1, 16, 17, 30, 31)] == [
        "mamba1", "sliding_attention", "mamba1", "full_attention", "gmu",
        "cross_attention"]
    kinds = [phi4_flash.layer_kind(i, 32) for i in range(32)]
    assert [kinds.count(k) for k in (
        "mamba1", "sliding_attention", "full_attention", "gmu",
        "cross_attention")] == [9, 8, 1, 7, 7]


def test_c_program_equals_the_reference_in_float32():
    """Logits and loss to float32 rounding on the six layers, at 43 positions
    (no whole blocks of the scan, five windows)."""
    got, want = _ours("six", 43), _theirs("six", 43)
    assert got.logits.shape == (2, 43, 512)
    np.testing.assert_allclose(got.logits, want.logits, rtol=2e-4, atol=2e-4)
    assert float(got.loss) == pytest.approx(float(want.loss), rel=1e-5)


def test_c_every_gradient_equals_the_references():
    """Leaf by leaf on seven layers, one of each kind and a second pair of
    the cross-decoder: the scan output of ``h_1`` is read by two gated memory
    units and the keys and values of ``h_2`` by two cross layers (and by
    ``h_2`` itself), each reader in a block of its own under remat, and the
    producers' leaves carry the sum of their cotangents, as the plain
    reference's do by reverse mode alone."""
    ours, theirs = (f("seven", 32, True) for f in (_ours, _theirs))
    got, want = ours.grads, theirs.grads
    assert float(ours.loss) == pytest.approx(float(theirs.loss), rel=1e-5)
    assert set(got["h_0"]["attn"]) == set(got["h_2"]["attn"]) >= {
        "wqkv", "wo", "sub_norm", "lambda_q1"}
    assert set(got["h_4"]["attn"]) >= {"wq", "wo"} \
        and "wqkv" not in got["h_6"]["attn"]
    assert "mamba1" in got["h_1"] and "gmu" in got["h_3"] \
        and "gmu" in got["h_5"]
    assert set(got["h_0"]["attn_norm"]) == {"scale", "bias"}
    _same(got, want, rtol=3e-3, atol=3e-5)


def test_c_remat_on_and_off_give_the_same_gradients():
    """The tensors that cross blocks are inputs of the recomputation: with
    and without it every leaf's gradient is the same, the producers' sums
    over two readers among them."""
    on = _ours("seven", 32, True).grads
    off = _ours("seven", 32, True, remat=False).grads
    _same(on, off, rtol=1e-4, atol=1e-6)


def _one_device():
    """Six steps of ``ShardedPretrainer`` on one device, four rows of 32, of
    the seam's four layers (``FOUR``: both producers and both kinds of
    reader, the flash kernels and the scan's with their backward), once for
    (c) and for both meshes of (e)."""
    return toys.one_device(FOUR, 4, 32, 6, lr=0.1)


def test_c_the_trainers_step_takes_the_references_loss_down():
    want, losses, *_ = _one_device()
    assert losses[0] == pytest.approx(want, rel=1e-4)
    assert losses[-1] < losses[0] - 0.2


@pytest.mark.parametrize("wrong", phi4_flash.WRONG
                         + (phi4_flash.PRECISION_BELOW,))
def test_c_the_tolerance_sees_each_wrong_model(wrong):
    """Each wrong model of the on-chip controls moves the toy's logits by far
    more than (c)'s tolerance, and so does the reference itself with float8
    activations."""
    got = _ours("six", 43).logits
    want = toys.reference(TOY, 43, backward=False, wrong=wrong, **_BY).logits
    assert not float(jnp.max(jnp.abs(got - want))) <= 100 * 2e-4


# ---------------------------------------------- (d) the cut tied to the model
def test_d_the_six_layers_are_the_seam_of_the_uncut_model():
    """A 12-layer toy in the published order (the plain reference alone runs
    it: four Mamba-1 layers and three under the window, the whole-row layer,
    two memory units and two cross layers): fed its activations at layer 4's
    input, the program's six layers — layers 4 to 9, the toy's six kinds with
    ``lam0`` by those indices — give its output of layer 9, through the
    table, whose first rows are set to those activations so that ids 0 ..
    S-1 embed to them and the tied head reads the result out; and with the
    table's held rows unchanged the program's logits are the uncut head's on
    those rows."""
    _, params = toys.weights(TOY, **_BY)
    model = LlamaLMModel(toys.config(SEAM))
    assert model.config.layer_depths == (4, 5, 6, 7, 8, 9)
    kinds = [phi4_flash.layer_kind(i, 12) for i in range(12)]
    assert tuple(kinds[4:10]) == model.config.layer_types
    kept_of = dict(zip(model.config.layer_types, range(6)))  # a layer a kind
    uncut = {f"h_{i}": params[f"h_{i - 4}"] if 4 <= i <= 9 else toys.moved(
        params[f"h_{kept_of[kind]}"], seed=100 + i, by=0.02)
        for i, kind in enumerate(kinds)}
    seq, eps = 40, TOY["layer_norm_eps"]
    table = 0.5 * jax.random.normal(jax.random.PRNGKey(9), (4096, 64))
    ids = jax.random.randint(jax.random.PRNGKey(8), (1, seq), 0, 512)

    def through(x):
        taps = {}
        return phi4_flash.layers(uncut, x, UNCUT, taps=taps), taps

    @jax.jit
    def program(rows, ids):     # the six layers over a table of 512 rows
        return model.apply(
            {"params": dict(params, wte={"embedding": rows})}, ids)

    with jax.default_matmul_precision("highest"):
        last, taps = jax.jit(through)(table[ids])
        assert sorted(taps) == list(range(12))
        assert float(jnp.max(jnp.abs(last - taps[10]))) > 1e-2
        # ids 0 .. S-1 embed to layer 4's input
        fed = table[:512].at[:seq].set(taps[4][0])
        got = program(fed, jnp.arange(seq)[None])
        want = phi4_flash.layer_norm(taps[10], params["norm_f"], eps) @ fed.T
        np.testing.assert_allclose(got, want, rtol=2e-4, atol=2e-4)
        # the held rows' logits are the uncut head's on those rows
        held = program(table[:512], ids)
        whole = jax.jit(lambda ids: phi4_flash._forward(
            dict(params, wte={"embedding": table}), ids, SEAM))(ids)
        assert whole.shape[-1] == 4096
        np.testing.assert_allclose(held, whole[..., :512], rtol=2e-4,
                                   atol=2e-4)


# ------------------------------------------------- (e) on a virtual mesh
@pytest.mark.parametrize("mesh", [{"dp": 1, "fsdp": 4}, {"dp": 2, "tp": 2}])
def test_e_a_sharded_mesh_gives_the_single_device_loss(mesh):
    """The new parameters shard by the Llama rules — a Mamba-1 layer's by
    channel, ``x_proj`` row-parallel, the gated memory unit's as a
    feed-forward's — and the step under them (the scan's kernels inside
    ``shard_map``, the tensors that cross blocks pinned to the batch's
    layout) gives one device's losses."""
    from jax.sharding import PartitionSpec as P

    from ray_tpu.models.pretrain import ShardedPretrainer
    from ray_tpu.parallel.mesh import MeshConfig
    from ray_tpu.parallel.sharding import (llama_partition_rules,
                                           match_partition_rules)

    cfg = toys.config(FOUR)
    specs = match_partition_rules(llama_partition_rules(), jax.eval_shape(
        lambda: init_params(cfg)[1]))
    mamba1, gmu = specs["h_0"]["mamba1"], specs["h_2"]["gmu"]
    assert mamba1["in_proj"]["kernel"] == P("fsdp", None, "tp")
    assert mamba1["x_proj"]["kernel"] == P("tp", None)
    assert mamba1["dt_proj"]["kernel"] == mamba1["conv_kernel"] \
        == P(None, "tp")
    assert mamba1["conv_bias"] == mamba1["dt_bias"] == mamba1["D"] == P("tp")
    assert mamba1["A_log"] == P("tp", None)
    assert mamba1["out_proj"]["kernel"] == gmu["out_proj"]["kernel"] \
        == P("tp", "fsdp")
    assert gmu["in_proj"]["kernel"] == P("fsdp", "tp")
    assert specs["h_1"]["attn"]["wqkv"]["kernel"] == P("fsdp", None)
    assert specs["h_3"]["attn"]["wq"]["kernel"] == P("fsdp", "tp")
    assert specs["h_3"]["attn"]["lambda_q1"] == P()

    one = _one_device()
    many = ShardedPretrainer(cfg, MeshConfig(**mesh),
                             devices=jax.devices()[:4], lr=0.1)
    for want in one.losses[:2]:     # the second sees the first's gradients
        assert float(many.step(one.rows)) == pytest.approx(want, rel=1e-5)


def test_e_a_sharded_sequence_is_refused():
    """A ``mamba1`` layer carries its state across every position: under an
    ``sp`` axis it raises, in the words ``ops.attention`` refuses with."""
    from ray_tpu.parallel.mesh import MeshConfig, build_mesh

    model = LlamaLMModel(toys.config(TOY))
    mesh = build_mesh(MeshConfig(dp=1, sp=2), devices=jax.devices()[:2])
    with jax.set_mesh(mesh), pytest.raises(
            NotImplementedError,
            match="sharded on 'sp' has no 'mamba1' layer"):
        jax.eval_shape(model.init, jax.random.PRNGKey(0),
                       jnp.zeros((1, 16), jnp.int32))


@pytest.mark.parametrize("changes,words", [
    (dict(layer_types=("gmu", "mamba1") + ("full_attention",) * 4,
          producers=(1,)),
     r"layer 0 \('gmu'\) reads the scan output of an earlier layer"),
    (dict(producers=(3,)),
     r"layer 4 \('gmu'\) reads the scan output of an earlier layer"),
    (dict(layer_types=("mamba1",) * 3 + ("full_attention", "mamba1",
                                         "cross_attention"),
          producers=(2, 3)),
     r"layer 2 \('mamba1'\) hands on its scan output and no later layer"),
    (dict(producers=(2, 3, 4)), "layer 4 is named a producer and has nothing")],
    ids=["reader-first", "no-producer", "no-reader", "no-such-producer"])
def test_e_a_reader_without_its_producer_is_refused(changes, words):
    """At construction, in words that name the layer."""
    model = LlamaLMModel(toys.config(TOY, **changes))
    with pytest.raises(ValueError, match=words):
        jax.eval_shape(model.init, jax.random.PRNGKey(0),
                       jnp.zeros((1, 16), jnp.int32))

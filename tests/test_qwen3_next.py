"""Qwen3-Next-80B-A3B through the shared Llama block, at toy widths on the
CPU, with seeded weights moved off their initial values: (b) the Gated
DeltaNet mixer (``models/gdn.py``: the two projections a key head at a time,
the convolution, unit norms, one decay a value head, the scan, the norm
before the silu gate) against the plain form of
``perfbench/harness/families/qwen3_next.py``, and the gated attention layer
(``wq`` twice as wide, the per-head norm under the unit offset, a quarter of
each head turned, a gate a channel) against it; (c) the whole stack (three
``gdn`` layers and one of gated attention, every feed-forward sparse with a
part of the experts held beside the gated shared expert) against the plain
reference — the recurrence position by position, a dense causal mask, every
held expert on every token — logits, loss, gradient norm and every gradient
leaf, and every wrong model of the on-chip controls outside the float32
limits; (d) the chip's share of a sparse layer tied to the uncut layer; and
what the configuration refuses.  The ``ShardedPretrainer`` step and the
partition rules on a virtual mesh are ``tests/test_qwen3_next_mesh.py``'s;
the toy's lowered step is held by ``tests/test_pinned_steps.py``; the scan
alone is ``tests/test_gdn_scan.py``'s.  The toy
(``perfbench/tests/toy/toy-qwen3-next.json``): 64 wide, four layers, 2 key
and 4 value heads of 16 at chunks of 8, 4 query heads of 32 over 2 with 8
lanes turned, 32 experts of 32 of which 8 are held (chip 1 of 4), top-10, a
shared expert of 32 under its gate.
"""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import toys
from perfbench.harness.families import qwen3_next
from ray_tpu.models.gdn import GDNMixer
from ray_tpu.models.llama import LlamaAttention, LlamaLMModel
from ray_tpu.models.moe import RoutedConfig, RoutedSwiGLU

TOY = toys.toy("toy-qwen3-next")
# the same layers on a chip that holds all 32 experts
WHOLE = dict(TOY, num_experts=32,
             deployment={"chips_sharing_a_layer": 1, "this_chip": 0})


def _with_grads(f, weight):
    return jax.jit(lambda p, x: (f(p, x), jax.grad(
        lambda p, x: jnp.sum(f(p, x) * weight), argnums=(0, 1))(p, x)))


def _kernel_stacks(jaxpr, prefix=""):
    """The name stack of every Pallas call under ``jaxpr``."""
    out = []
    for eqn in jaxpr.eqns:
        name = f"{prefix}/{eqn.source_info.name_stack}"
        if eqn.primitive.name == "pallas_call":
            out.append(f"{name}/{eqn.params['name']}")
        for sub in jax.core.jaxprs_in_params(eqn.params):
            out += _kernel_stacks(sub, name)
    return out


def _same(got, want):
    (out, grads), (plain_out, plain_grads) = got, want
    np.testing.assert_allclose(out, plain_out, atol=2e-5)
    for (path, g), w in zip(jax.tree_util.tree_flatten_with_path(grads)[0],
                            jax.tree_util.tree_leaves(plain_grads)):
        np.testing.assert_allclose(g, w, rtol=2e-3, atol=2e-5,
                                   err_msg=jax.tree_util.keystr(path))


# ------------------------------------------------------ (b) the two mixers
def test_b_the_mixer_equals_the_plain_form(seq=21):
    """``GDNMixer`` alone against the reference's layer, output and every
    parameter's gradient; 21 positions are no whole chunks."""
    cfg = toys.config(TOY)
    mixer = GDNMixer(cfg)
    x = jax.random.normal(jax.random.PRNGKey(0), (2, seq, cfg.d_model))
    params = toys.moved(
        jax.jit(mixer.init)(jax.random.PRNGKey(1), x)["params"])
    assert set(params) == {"in_proj_qkvz", "in_proj_ba", "conv_kernel",
                           "A_log", "dt_bias", "o_norm", "out_proj"}
    assert params["in_proj_qkvz"]["kernel"].shape == (64, 2, 6 * 16)
    assert params["in_proj_ba"]["kernel"].shape == (64, 2, 4)
    assert params["conv_kernel"].shape == (4, 2, 4 * 16)
    assert params["A_log"].shape == params["dt_bias"].shape == (4,)
    weight = jax.random.normal(jax.random.PRNGKey(2), x.shape)
    with jax.default_matmul_precision("highest"):
        _same(*(_with_grads(f, weight)(params, x) for f in (
            lambda p, x: mixer.apply({"params": p}, x),
            lambda p, x: qwen3_next.gdn(x, p, TOY))))


@pytest.mark.parametrize("impl", ["reference", "flash"])
def test_b_the_gated_attention_equals_the_plain_form(impl):
    """``LlamaAttention`` under ``attn_gate="channel"``: a head's query and
    its gate's logits from one ``wq``, ``q_norm`` and ``k_norm`` times ``1 +
    w``, 8 of 32 lanes turned, the gate a channel on the kernels' result —
    output and every gradient; and its kernels' calls stand under the scope
    ``gated``, the gate's pass under ``gate``."""
    cfg = toys.config(TOY, attention_impl=impl)
    layer = LlamaAttention(cfg, "full_attention")
    x = jax.random.normal(jax.random.PRNGKey(0), (2, 40, cfg.d_model))
    positions = jnp.arange(40)
    p = toys.moved(jax.jit(layer.init)(jax.random.PRNGKey(1), x, positions)[
        "params"])
    assert set(p) == {"wq", "wk", "wv", "wo", "q_norm", "k_norm"}
    assert p["wq"]["kernel"].shape == (64, 2 * 4 * 32)
    weight = jax.random.normal(jax.random.PRNGKey(2), x.shape)
    with jax.default_matmul_precision("highest"):
        _same(*(_with_grads(f, weight)(p, x) for f in (
            lambda p, x: layer.apply({"params": p}, x, positions),
            lambda p, x: qwen3_next.gated_attention(x, p, TOY))))
    if impl == "flash":
        stacks = _kernel_stacks(jax.make_jaxpr(
            lambda x: layer.apply({"params": p}, x, positions))(x).jaxpr)
        assert [s for s in stacks if "flash_fwd" in s], stacks
        assert all("gated" in s for s in stacks), stacks
    assert "wg" not in p


def test_b_a_scale_from_zero_is_the_unit_offsets():
    """Under ``norm_unit_offset`` the per-head norm's scales start from zero
    and multiply as ``1 + w``; without it they start from one, as before."""
    cfg = toys.config(TOY, attention_impl="reference")
    x = jnp.ones((1, 8, cfg.d_model))
    for offset, start in ((True, 0.0), (False, 1.0)):
        layer = LlamaAttention(dataclasses.replace(
            cfg, norm_unit_offset=offset), "full_attention")
        p = jax.jit(layer.init)(jax.random.PRNGKey(1), x, jnp.arange(8))[
            "params"]
        assert float(jnp.max(jnp.abs(p["q_norm"]["scale"] - start))) == 0.0
        assert float(jnp.max(jnp.abs(p["k_norm"]["scale"] - start))) == 0.0


# ------------------------------------------ (c) the stack and its reference
STACKS = {"part": TOY, "all": WHOLE}


@pytest.mark.parametrize("stack,positions,backward", [
    ("part", 43, True), ("all", 48, False)], ids=["part-43-backward", "all"])
def test_c_program_equals_the_reference_in_float32(stack, positions,
                                                   backward):
    """Logits and loss to float32 rounding: the four layers with a part of
    the experts held (43 positions, which are no whole chunks), with the
    gradient norm, and with all 32."""
    got = toys.program(STACKS[stack], positions, backward=backward)
    want = toys.reference(STACKS[stack], positions, backward=False,
                          leaves=backward)
    assert got.logits.shape == (2, positions, 512)
    np.testing.assert_allclose(got.logits, want.logits, rtol=2e-4, atol=2e-4)
    assert float(got.loss) == pytest.approx(float(want.loss), rel=1e-5)
    if backward:
        assert float(got.gradnorm) == pytest.approx(float(want.gradnorm),
                                                    rel=1e-4)


def test_c_every_gradient_equals_the_references():
    """Leaf by leaf, not only the norm: the mixer's seven, the gated
    attention's six, the router, the held experts, the shared expert with its
    gate, the unit-offset norms, the embedding and the head."""
    got = toys.program(TOY, 43).grads
    want = toys.reference(TOY, 43, backward=False, leaves=True).grads
    assert set(got["h_3"]["attn"]) == {"wq", "wk", "wv", "wo", "q_norm",
                                       "k_norm"}
    assert len(got["h_0"]["gdn"]) == 7 and "gdn" in got["h_2"]
    assert set(got["h_1"]["moe"]["shared"]) == {"gate_proj", "up_proj",
                                                "down_proj", "gate"}
    for (path, g), w in zip(jax.tree_util.tree_flatten_with_path(got)[0],
                            jax.tree_util.tree_leaves(want)):
        np.testing.assert_allclose(g, w, rtol=2e-3, atol=2e-5,
                                   err_msg=jax.tree_util.keystr(path))


@pytest.mark.parametrize("wrong", qwen3_next.WRONG
                         + (qwen3_next.PRECISION_BELOW,))
def test_c_the_tolerance_sees_each_wrong_model(wrong):
    """Each wrong model of the on-chip controls moves the toy's logits by far
    more than (c)'s tolerance, and so does the reference itself with float8
    activations."""
    got = toys.program(TOY, 43).logits
    want = toys.reference(TOY, 43, backward=False, wrong=wrong).logits
    assert float(jnp.max(jnp.abs(got - want))) > 100 * 2e-4


# ------------------------------------------- (d) the share tied to the model
def test_d_the_four_shares_add_up_to_the_uncut_layer():
    """The routed parts that the four chips of the toy's deployment compute,
    each from its own eight experts, plus the gated shared expert counted
    once, are the uncut reference's sparse layer; and with the residual, also
    counted once, the uncut layer's output."""
    d, f, e, chips = 64, 32, 32, 4
    keys = jax.random.split(jax.random.PRNGKey(7), 10)

    def normal(key, *shape):
        return 0.3 * jax.random.normal(key, shape, jnp.float32)

    whole = {"router": {"kernel": 0.2 * normal(keys[0], d, e)},
             "gate_proj": normal(keys[1], e, d, f),
             "up_proj": normal(keys[2], e, d, f),
             "down_proj": normal(keys[3], e, f, d),
             "shared": {name: {"kernel": normal(key, *shape)}
                        for name, key, shape in (
                            ("gate_proj", keys[4], (d, f)),
                            ("up_proj", keys[5], (d, f)),
                            ("down_proj", keys[6], (f, d)),
                            ("gate", keys[9], (d, 1)))}}
    y = jax.random.normal(keys[7], (2, 24, d), jnp.float32)
    residual = jax.random.normal(keys[8], (2, 24, d), jnp.float32)
    held = e // chips
    with jax.default_matmul_precision("highest"):
        routed, shared, chosen = qwen3_next.sparse_parts(y, whole, WHOLE, 0)
        assert float(jnp.sum(chosen)) == 2 * 24 * 10
        total = 0.0
        for chip in range(chips):
            lo = held * chip
            layer = RoutedSwiGLU(RoutedConfig(
                n_experts=e, top_k=10, d_model=d, d_ff=f, norm_topk_prob=True,
                dtype=jnp.float32, experts_held=(lo, held), d_shared=f,
                shared_gate=True))
            mine = dict(whole, **{name: whole[name][lo:lo + held] for name in
                                  ("gate_proj", "up_proj", "down_proj")})
            # what every chip computes alike is taken off each share ...
            part = layer.apply({"params": mine}, y) - shared
            total = total + part
            # ... and the reference given the same share gives the same part
            np.testing.assert_allclose(
                part, qwen3_next.sparse_parts(y, mine, TOY, lo)[0],
                atol=2e-5)
    np.testing.assert_allclose(total, routed, atol=5e-5)
    np.testing.assert_allclose(residual + shared + total,
                               residual + shared + routed, atol=5e-5)


# ------------------------------------------------ what the stack refuses
def test_value_heads_that_the_key_heads_do_not_divide_are_refused():
    model = LlamaLMModel(toys.config(TOY, gdn_key_heads=3))
    with pytest.raises(ValueError, match="4 value heads over 3 key heads"):
        jax.eval_shape(model.init, jax.random.PRNGKey(0),
                       jnp.zeros((1, 16), jnp.int32))


def test_an_unknown_gate_is_refused():
    model = LlamaLMModel(toys.config(TOY, attn_gate="token"))
    with pytest.raises(ValueError, match="unknown attn_gate 'token'"):
        jax.eval_shape(model.init, jax.random.PRNGKey(0),
                       jnp.zeros((1, 16), jnp.int32))

"""Kimi-Linear-48B-A3B through the shared Llama block, at toy widths on the
CPU, with seeded weights moved off their initial values: (b) the KDA mixer
(``models/kda.py``: convolutions, unit norms, the low-rank decay and gate, the
per-head norm) against the plain form of
``perfbench/harness/families/kimi_linear.py``, and latent attention without
rotation against ``mha_reference``; (c) the whole stack (``kda`` and
``full_attention`` layers mixed, a dense layer and sparse ones with a part of
the experts held) against the plain reference — the recurrence position by
position, a dense causal mask, every held expert on every token — logits
and loss on the five layers, every gradient leaf on one layer of each kind,
and every wrong model of the on-chip controls outside the float32 limits; (d)
the chip's share of a sparse layer tied to the uncut layer.  The
``ShardedPretrainer`` step and (e), the partition rules on a virtual mesh, are
``tests/test_kimi_linear_mesh.py``'s; the toys' lowered steps are held by
``tests/test_pinned_steps.py``.  The scan alone is ``tests/test_kda_scan.py``'s.  The toy
(``perfbench/tests/toy/toy-kimi-linear.json``): 64 wide, five layers (KDA +
dense; KDA, KDA, MLA, KDA sparse), 4 KDA heads of 16 at chunks of 8, 4 MLA
heads whose scores are 16 + 8 wide over values 16 wide, 8 experts of 32 of
which 2 are held (chip 1 of 4), top-3, a shared expert of 32.  On the chip
the same reference runs at published widths against the bf16 program
(``perfbench/harness/agreement.py``).
"""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import toys
from perfbench.harness import reference
from perfbench.harness.families import kimi_linear
from ray_tpu.models.kda import KDAMixer
from ray_tpu.models.llama import LatentAttention
from ray_tpu.models.moe import RoutedConfig, RoutedSwiGLU
from ray_tpu.ops.attention import mha_reference

TOY = toys.toy("toy-kimi-linear")
# the same layers on a chip that holds all eight experts
WHOLE = dict(TOY, num_experts=8,
             deployment={"chips_sharing_a_layer": 1, "this_chip": 0})


# three of the five layers, one of each kind (KDA + dense, KDA + sparse, MLA
# + sparse), for the steps that are compiled under a mesh
SHORT = dict(TOY, num_hidden_layers=3, linear_attn_config=dict(
    TOY["linear_attn_config"], kda_layers=[1, 2], full_attn_layers=[3]))


# ------------------------------------------------------ (b) the two mixers
@pytest.mark.parametrize("seq", [24, 21])
def test_b_the_mixer_equals_the_plain_form(seq):
    """``KDAMixer`` alone — three convolutions, silu, the unit norms and q's
    scale, softplus under ``A_log``, the write strengths, the scan, the
    per-head norm under the low-rank gate — against the reference's layer,
    output and every parameter's gradient; 21 positions are no whole
    chunks."""
    cfg = toys.config(TOY)
    mixer = KDAMixer(cfg)
    x = jax.random.normal(jax.random.PRNGKey(0), (2, seq, cfg.d_model))
    params = toys.moved(jax.jit(mixer.init)(jax.random.PRNGKey(1), x)["params"])
    assert set(params) == {
        "q_proj", "k_proj", "v_proj", "f_a", "f_b", "g_a", "g_b", "b_proj",
        "o_proj", "o_norm", "q_conv", "k_conv", "v_conv", "A_log", "dt_bias"}
    weight = jax.random.normal(jax.random.PRNGKey(2), x.shape)

    def program(p, x):
        return mixer.apply({"params": p}, x)

    def plain(p, x):
        return kimi_linear.kda(x, p, TOY)

    def with_grads(f):
        return jax.jit(lambda p, x: (f(p, x), jax.grad(
            lambda p, x: jnp.sum(f(p, x) * weight), argnums=(0, 1))(p, x)))

    with jax.default_matmul_precision("highest"):
        (out, got), (plain_out, want) = (
            with_grads(f)(params, x) for f in (program, plain))
    np.testing.assert_allclose(out, plain_out, atol=2e-5)
    for (path, g), w in zip(jax.tree_util.tree_flatten_with_path(got)[0],
                            jax.tree_util.tree_leaves(want)):
        np.testing.assert_allclose(g, w, rtol=2e-3, atol=2e-5,
                                   err_msg=jax.tree_util.keystr(path))


@pytest.mark.parametrize("impl", ["reference", "flash"])
def test_b_latent_attention_without_rotation(impl):
    """With ``rope`` off ``LatentAttention`` turns nothing: its output is
    ``mha_reference`` on q as projected and the key ``[kn ; kr]`` with the
    one un-rotated ``kr`` for all heads — and differs from the rotated
    layer's, which with the field unset is the call it was."""
    cfg = toys.config(TOY, attention_impl=impl)
    layer = LatentAttention(cfg, "full_attention")
    x = jax.random.normal(jax.random.PRNGKey(0), (2, 40, cfg.d_model))
    positions = jnp.arange(40)
    p = toys.moved(jax.jit(layer.init)(jax.random.PRNGKey(1), x, positions)[
        "params"])
    h, dn, dv, rank = (cfg.n_head, cfg.qk_nope_head_dim, cfg.v_head_dim,
                       cfg.kv_lora_rank)
    with jax.default_matmul_precision("highest"):
        got = layer.apply({"params": p}, x, positions)
        heads = lambda a: a.reshape(2, 40, h, -1).transpose(0, 2, 1, 3)
        q = heads(x @ p["wq"]["kernel"])
        down = x @ p["wdkv"]["kernel"]
        c = reference.rms_norm(down[..., :rank], p["kv_norm"], cfg.rms_eps)
        kv = heads(c @ p["wukv"]["kernel"])
        out = mha_reference(q, kv[..., :dn], kv[..., dn:],
                            k_shared=down[:, None, :, rank:])
        want = out.transpose(0, 2, 1, 3).reshape(2, 40, h * dv) \
            @ p["wo"]["kernel"]
        rotated = LatentAttention(dataclasses.replace(cfg, rope=True),
                                  "full_attention").apply(
                                      {"params": p}, x, positions)
    np.testing.assert_allclose(got, want, atol=2e-5)
    assert float(jnp.max(jnp.abs(got - rotated))) > 1e-2
    assert "rope" not in str(jax.make_jaxpr(
        lambda x: layer.apply({"params": p}, x, positions))(x))


# ------------------------------------------ (c) the stack and its reference
# the five layers with 2 of 8 experts held, with all eight, and one layer of
# each kind for what is compiled with its backward
STACKS = {"part": TOY, "all": WHOLE, "short": SHORT}


# The program runs in float32 (the scan's kernels interpreted, with their own
# backward rule), so that what is left to differ from the reference is the
# mathematics; each stack's weights, and each (stack, positions) of program
# and reference, are made once (``tests/toys.py``).
@pytest.mark.parametrize("stack,positions,backward", [
    ("part", 43, False), ("all", 48, False), ("short", 48, True)],
    ids=["part-43", "all", "short-backward"])
def test_c_program_equals_the_reference_in_float32(stack, positions,
                                                   backward):
    """Logits and loss to float32 rounding: the five layers with a part of
    the experts held (43 positions, which are no whole chunks) and with all
    of them; and with the gradient norm, one layer of each kind (KDA + dense,
    KDA + sparse, MLA + sparse)."""
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
    """Leaf by leaf, not only the norm: the mixer's fifteen, latent
    attention's five, the dense feed-forward, the shared expert, the router,
    the held experts, the embedding and the head."""
    got = toys.program(SHORT, 48).grads
    want = toys.reference(SHORT, 48, backward=False, leaves=True).grads
    assert set(got["h_2"]["attn"]) == {"wq", "wdkv", "kv_norm", "wukv", "wo"}
    assert len(got["h_1"]["kda"]) == 15
    assert "kda" in got["h_0"] and "mlp" in got["h_0"] and "moe" in got["h_1"]
    for (path, g), w in zip(jax.tree_util.tree_flatten_with_path(got)[0],
                            jax.tree_util.tree_leaves(want)):
        np.testing.assert_allclose(g, w, rtol=2e-3, atol=2e-5,
                                   err_msg=jax.tree_util.keystr(path))


@pytest.mark.parametrize("wrong", kimi_linear.WRONG
                         + (kimi_linear.PRECISION_BELOW,))
def test_c_the_tolerance_sees_each_wrong_model(wrong):
    """Each wrong model of the on-chip controls moves the toy's logits by far
    more than (c)'s tolerance — no decay, one decay a head, ``b`` = 1, the
    delta correction dropped, the decay after the correction, no unit norms,
    ``q`` unscaled, no convolution, a silu output gate, no output norm, a
    rotation in MLA, ``kr`` a head's own, softmax scores, top-6 (of the toy's
    8, for its 3), the routed scale 1, no renormalisation — and so does the
    reference itself with float8 activations."""
    got = toys.program(TOY, 43, backward=False).logits
    want = toys.reference(TOY, 43, backward=False, wrong=wrong).logits
    assert float(jnp.max(jnp.abs(got - want))) > 100 * 2e-4


# ------------------------------------------- (d) the share tied to the model
def test_d_the_four_shares_add_up_to_the_uncut_layer():
    """The routed parts that the four chips of the toy's deployment compute,
    each from its own two experts, plus the shared expert counted once, are
    the uncut reference's sparse layer; and with the residual, also counted
    once, the uncut layer's output."""
    d, f, e, chips = 64, 32, 8, 4
    keys = jax.random.split(jax.random.PRNGKey(7), 9)

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
    residual = jax.random.normal(keys[8], (2, 24, d), jnp.float32)
    held = e // chips
    with jax.default_matmul_precision("highest"):
        routed, shared, chosen = kimi_linear.sparse_parts(y, whole, WHOLE, 0)
        assert float(jnp.sum(chosen)) == 2 * 24 * 3
        total = 0.0
        for chip in range(chips):
            lo = held * chip
            layer = RoutedSwiGLU(RoutedConfig(
                n_experts=e, top_k=3, d_model=d, d_ff=f, norm_topk_prob=True,
                dtype=jnp.float32, experts_held=(lo, held),
                scoring="sigmoid", routed_scale=2.446, d_shared=f))
            mine = dict(whole, **{name: whole[name][lo:lo + held] for name in
                                  ("gate_proj", "up_proj", "down_proj")})
            # what every chip computes alike is taken off each share ...
            part = layer.apply({"params": mine}, y) - shared
            total = total + part
            # ... and the reference given the same share gives the same part
            np.testing.assert_allclose(
                part, kimi_linear.sparse_parts(y, mine, TOY, lo)[0],
                atol=2e-5)
    np.testing.assert_allclose(total, routed, atol=5e-5)
    np.testing.assert_allclose(residual + shared + total,
                               residual + shared + routed, atol=5e-5)

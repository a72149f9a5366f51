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
the selection bias: in the choice and not in the weights, no gradient.  The
``ShardedPretrainer`` steps — the reference's loss taken down, no update of
the bias, and (e) the partition rules on a virtual mesh — are
``tests/test_lfm2_mesh.py``'s; the toys' lowered steps are held by
``tests/test_pinned_steps.py``.  The toy
(``perfbench/tests/toy/toy-lfm2.json``): 64 wide, a conv + dense layer, an
attention + sparse layer (4 / 2 heads of 16) and two conv + sparse ones, 16
experts of 32 of which 2 are held (chip 1 of 8), top-4.  On the chip the same
reference runs at published widths against the bf16 program
(``perfbench/harness/agreement.py``).
"""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import toys
from perfbench.harness.families import lfm2
from ray_tpu.models.mamba import causal_conv, gated_short_conv
from ray_tpu.models.moe import RoutedConfig, RoutedSwiGLU
from ray_tpu.models.pretrain import init_params

TOY = toys.toy("toy-lfm2")
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
        jax.eval_shape(lambda: init_params(cfg)[1])     # (while it is traced)


# ------------------------------------------ (b) the stack and its reference
# The program runs in float32, so that what is left to differ from the
# reference is the mathematics; ``attention_impl`` "flash" is the Pallas
# kernels interpreted, with their own backward rule.  Every leaf is moved off
# its initial value.
@pytest.mark.parametrize("config,impl,positions", [
    (TOY, "reference", 64), (TOY, "flash", 64), (TOY, "flash", 52),
    (WHOLE, "reference", 64), (WHOLE, "flash", 64)],
    ids=["part-reference", "part-flash", "part-flash-52", "all-reference",
         "all-flash"])
def test_b_program_equals_the_reference_in_float32(config, impl, positions):
    """Logits, loss and the gradient norm to float32 rounding, a part of the
    experts held and all of them; 52 positions are not whole tiles."""
    got = toys.program(config, positions, attention_impl=impl)
    want = toys.reference(config, positions, attention_impl=impl)
    assert got.logits.shape == (2, positions, 512)
    np.testing.assert_allclose(got.logits, want.logits, rtol=2e-4, atol=2e-4)
    assert float(got.loss) == pytest.approx(float(want.loss), rel=1e-5)
    assert float(got.gradnorm) == pytest.approx(float(want.gradnorm),
                                                rel=1e-4)
    assert float(want.held) > 0


def test_b_every_gradient_equals_the_references():
    """Leaf by leaf, not only the norm: the mixer's two projections and its
    kernel, attention's four with the two norms' scales, the router, the held
    experts; the selection bias's is zero on both sides."""
    got = toys.program(TOY, 64, attention_impl="flash").grads
    want = toys.reference(TOY, 64, leaves=True, attention_impl="flash").grads
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


@pytest.mark.parametrize("wrong", lfm2.WRONG + (lfm2.PRECISION_BELOW,))
def test_b_the_tolerance_sees_each_wrong_model(wrong):
    """Each wrong model of the on-chip controls moves the toy's logits by far
    more than (b)'s tolerance — a gate left out (``B``, then ``C``), a
    convolution 4 wide, one that sees a position ahead, no q / k norm, RoPE
    left off, top-3, softmax scores, no renormalisation, the (non-zero) bias
    added to the weights — and so does the reference itself with float8
    activations."""
    got = toys.program(TOY, 64, attention_impl="reference").logits
    want = toys.reference(TOY, 64, backward=False, wrong=wrong,
                          attention_impl="reference").logits
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
            np.testing.assert_allclose(jax.jit(
                lambda p, y: layer.apply({"params": p}, y))(p, y), want,
                atol=3e-5)
        grads = jax.jit(jax.grad(lambda p: jnp.sum(
            layer.apply({"params": p}, y) ** 2)))(pushed)
    assert not np.any(grads["selection_bias"])
    assert np.any(grads["router"]["kernel"])


def test_d_the_default_has_no_bias_and_no_epsilon():
    layer = RoutedSwiGLU(RoutedConfig(n_experts=16, top_k=4, d_model=64,
                                      d_ff=32, dtype=jnp.float32))
    params = jax.eval_shape(layer.init, jax.random.PRNGKey(0),
                            jnp.zeros((1, 8, 64)))["params"]
    assert set(params) == {"router", "gate_proj", "up_proj", "down_proj"}

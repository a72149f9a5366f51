"""Kimi-VL-A3B's decoder through the shared Llama block, at toy widths on the
CPU, with seeded weights moved off their initial values: (a) the flash kernels
with scores wider than values and the key's shared part, interpreted, against
``mha_reference``; (b) the program (``models/llama.py``'s ``LatentAttention``
under ``mlp_types``, ``models/moe.py`` with sigmoid scores, the routed scale,
the shared expert and a part of the experts held) against the plain reference
of ``perfbench/harness/families/kimi_vl.py`` — a dense causal mask, the shared
rotary key given to the heads by indexing, every held expert on every token —
and every wrong model of the on-chip controls outside the float32 limits; (c)
the chip's share of a sparse layer tied to the uncut layer.  The
``ShardedPretrainer`` step and (e), the partition rules on a virtual mesh, are
``tests/test_kimi_vl_mesh.py``'s; the toys' lowered steps are held by
``tests/test_pinned_steps.py``.  The toy
(``perfbench/tests/toy/toy-kimi-vl.json``): 64 wide, 4 heads whose scores are
16 + 8 wide over values 16 wide, a latent of 32, a dense layer and two sparse
ones, 16 experts of 32 of which 2 are held (chip 1 of 8), top-3, a shared
expert of 64.  On the chip the same reference runs at published widths
against the bf16 program (``perfbench/harness/agreement.py``).
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import toys
from perfbench.harness.families import kimi_vl
from ray_tpu.models.moe import RoutedConfig, RoutedSwiGLU
from ray_tpu.ops.attention import (flash_attention, mha_reference,
                                   ring_attention)

TOY = toys.toy("toy-kimi-vl")
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
    # (the weights are the stack's: initialised without the ring)
    model, params = toys.weights(TOY, attention_impl="ring")
    with pytest.raises(NotImplementedError, match="latent attention"):
        # (refused while it is traced: no layer before it runs)
        jax.eval_shape(lambda: model.apply(
            {"params": params}, jnp.zeros((1, 16), jnp.int32)))


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
    """Leaf by leaf, not only the norm: the four projections of the latent
    attention, its norm, the shared expert, the router, the held experts."""
    got = toys.program(TOY, 64, attention_impl="flash").grads
    want = toys.reference(TOY, 64, leaves=True, attention_impl="flash").grads
    assert set(got["h_1"]["attn"]) == {"wq", "wdkv", "kv_norm", "wukv", "wo"}
    for (path, g), w in zip(jax.tree_util.tree_flatten_with_path(got)[0],
                            jax.tree_util.tree_leaves(want)):
        np.testing.assert_allclose(g, w, rtol=2e-3, atol=2e-5,
                                   err_msg=jax.tree_util.keystr(path))


@pytest.mark.parametrize("wrong", kimi_vl.WRONG + (kimi_vl.PRECISION_BELOW,))
def test_b_the_tolerance_sees_each_wrong_model(wrong):
    """Each wrong model of the on-chip controls moves the toy's logits by far
    more than (b)'s tolerance — the latent's norm left out, RoPE left off the
    shared key, the scores scaled by the unrotated width alone, the values
    taken from the key half of the up-projection, one shared expert for two,
    the routed scale 1, top-(k-1), softmax scores — and so does the reference
    itself with float8 activations."""
    got = toys.program(TOY, 64, attention_impl="reference").logits
    want = toys.reference(TOY, 64, backward=False, wrong=wrong,
                          attention_impl="reference").logits
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

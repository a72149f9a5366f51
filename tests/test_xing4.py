"""Xing4.0-29B-A4B through the shared Llama block, at toy widths on the CPU,
with seeded weights moved off their initial values: (a) the program — the
residual path four streams wide (``models/llama.py``'s ``HyperConnection``
around both branches of every layer), ``LatentAttention`` with a query latent
under a YaRN table, sigmoid-routed experts chosen through a selection bias of
which a part is held, and the prediction module behind the trunk with
``models/pretrain.py``'s ``L_main + MTP_WEIGHT L_mtp`` — against the plain
reference of ``perfbench/harness/families/xing4.py`` (the stream an explicit
(B, S, 4, C) array, the Sinkhorn a loop over (B, S, 4, 4), YaRN from the
published formula); (b) the chip's share of a sparse layer tied to the uncut
layer; (c) every wrong model outside the float32 tolerance; (d) what 20
Sinkhorn iterations do and do not reach; (e) one stream, and no query latent,
are the program they were; (f) the latent path's rotary table.  The toy
(``perfbench/tests/toy/toy-xing4.json``): 64 wide, 4 streams, 4 heads whose
scores are 16 + 8 wide over values 16 wide, a query latent of 24 and a
key/value latent of 32, YaRN factor 64 over 16 positions, a dense layer and two
sparse ones, 16 experts of 32 of which 2 are held (chip 1 of 8), top-4, a
shared expert of 32; the module is off in the file, as in the cell, and on in
``MTP``.  The toys' lowered steps are held by ``tests/test_pinned_steps.py``.
"""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import toys
from perfbench.harness.families import kimi_vl, xing4
from ray_tpu.models import llama
from ray_tpu.models.moe import RoutedConfig, RoutedSwiGLU
from ray_tpu.models.pretrain import init_params, objective_fn

TOY = toys.toy("toy-xing4")
MTP = dict(TOY, num_nextn_predict_layers=1)
# the same sparse layer on a chip that holds all sixteen experts
WHOLE = dict(TOY, n_routed_experts=16,
             deployment={"chips_sharing_a_layer": 1, "this_chip": 0})


# ------------------------------------------ (a) the stack and its reference
@pytest.mark.parametrize("config,impl,positions", [
    (MTP, "reference", 64), (TOY, "flash", 64)],
    ids=["mtp-reference", "part-flash"])
def test_a_program_equals_the_reference_in_float32(config, impl, positions):
    """Logits, the objective (both terms where the module is on) and the
    gradient norm to float32 rounding, the module on over XLA attention and
    off over the kernels (a case is two compiles of 20 to 30 s: the kernels'
    own edges are ``tests/test_kimi_vl.py`` (a)'s)."""
    got = toys.program(config, positions, attention_impl=impl)
    want = toys.reference(config, positions, attention_impl=impl)
    assert got.logits.shape == (2, positions, 512)
    np.testing.assert_allclose(got.logits, want.logits, rtol=2e-4, atol=2e-4)
    assert float(got.loss) == pytest.approx(float(want.loss), rel=1e-5)
    assert float(got.gradnorm) == pytest.approx(float(want.gradnorm),
                                                rel=1e-4)
    assert float(want.held) > 0


def test_a_both_terms_of_the_objective_are_the_references():
    """``objective_fn`` returns ``L_main + 0.3 L_mtp``, reports ``L_main`` and
    has both terms, the hyper-connections' two statistics and the routers'
    among what it brings back."""
    model, params = toys.weights(MTP, attention_impl="reference")
    data = toys.rows(MTP, 2, 64)
    with jax.default_matmul_precision("highest"):
        total, (reported, stats) = jax.jit(
            lambda p: objective_fn(model, p, data))(params)
        out, _, ahead = jax.jit(lambda p: xing4._forward(
            p, data["input_ids"], MTP))(params)
    want, main, mtp = xing4.losses(out[..., :512], ahead, data["targets"],
                                   MTP)
    assert float(stats["loss_main"]) == pytest.approx(float(main), rel=1e-5)
    assert float(stats["loss_mtp"]) == pytest.approx(float(mtp), rel=1e-5)
    assert float(reported) == float(stats["loss_main"])
    assert float(total) == pytest.approx(float(want), rel=1e-5)
    assert float(total) == pytest.approx(float(main) + 0.3 * float(mtp),
                                         rel=1e-6)
    assert {"hc_res_row_err", "hc_pre_max", "moe_rows_held",
            "max_load"} <= set(stats)
    assert 0 < float(stats["hc_pre_max"]) < 1
    assert 0 <= float(stats["hc_res_row_err"]) < 0.1


@pytest.mark.parametrize("masked", [False, True])
def test_a_the_ahead_loss_counts_the_positions_that_have_a_token(masked):
    """``ahead_loss(logits, targets, mask, k)`` is the cross entropy of
    ``logits[t]`` against ``targets[t + k]`` over ``t < S - k`` — and, under
    a mask, where ``mask[t + k]`` counts — written out in numpy."""
    from ray_tpu.models.gpt2 import ahead_loss

    keys = jax.random.split(jax.random.PRNGKey(11), 3)
    logits = jax.random.normal(keys[0], (2, 9, 7))
    targets = jax.random.randint(keys[1], (2, 9), 0, 7)
    mask = (jax.random.uniform(keys[2], (2, 9)) < 0.6).astype(jnp.float32) \
        if masked else None
    logp = np.asarray(jax.nn.log_softmax(logits, axis=-1))
    for k in (1, 2):
        terms = [(-logp[b, t, int(targets[b, t + k])],
                  1.0 if mask is None else float(mask[b, t + k]))
                 for b in range(2) for t in range(9 - k)]
        want = sum(v * w for v, w in terms) / sum(w for _, w in terms)
        assert float(ahead_loss(logits, targets, mask, k)) == pytest.approx(
            want, rel=1e-5)


def test_a_every_gradient_equals_the_references():
    """Leaf by leaf with the module off (``toys.reference(leaves=True)``
    differentiates the main term): the hyper-connections' four leaves a
    sub-layer, the query latent's three, the router and the held experts."""
    got = toys.program(TOY, 64, attention_impl="flash").grads
    want = toys.reference(TOY, 64, leaves=True, attention_impl="flash").grads
    assert set(got["h_1"]) == {"attn", "attn_norm", "hc_attn", "hc_mlp",
                               "mlp_norm", "moe"}
    assert set(got["h_1"]["hc_attn"]) == {"alpha", "bias", "phi", "scale"}
    assert set(got["h_1"]["attn"]) == {"wq_a", "q_norm", "wq_b", "wdkv",
                                       "kv_norm", "wukv", "wo"}
    for (path, g), w in zip(jax.tree_util.tree_flatten_with_path(got)[0],
                            jax.tree_util.tree_leaves(want)):
        np.testing.assert_allclose(g, w, rtol=2e-3, atol=2e-5,
                                   err_msg=jax.tree_util.keystr(path))


# --------------------------------------------------- (c) the wrong models
@pytest.mark.parametrize("wrong", xing4.WRONG + xing4.BF16_WHERE_FLOAT32
                         + (xing4.PRECISION_BELOW,))
def test_c_the_tolerance_sees_each_wrong_model(wrong):
    """Each wrong model of the on-chip controls moves the toy's logits by far
    more than (a)'s tolerance — the Sinkhorn at 1 iteration, ``H_post``
    without its 2, ``H_res`` the identity, the stream's norm left out,
    ``q_norm`` left out, plain RoPE for YaRN, the scores without ``m^2``,
    top-3, the routed scale left out — and so do a Sinkhorn and coefficients
    in bf16 (5 times the tolerance: their rounding is 2^-9 of a gate), and
    the reference itself with float8 activations."""
    got = toys.program(MTP, 64, attention_impl="reference").logits
    want = toys.reference(MTP, 64, backward=False, wrong=wrong,
                          attention_impl="reference").logits
    margin = 5 if wrong in xing4.BF16_WHERE_FLOAT32 else 100
    assert float(jnp.max(jnp.abs(got - want))) > margin * 2e-4


@pytest.mark.parametrize("wrong", xing4.WRONG_OBJECTIVE)
def test_c_the_tolerance_sees_each_wrong_objective(wrong):
    """The module scoring ``t_{i+1}`` for ``t_{i+2}``, and ``lambda`` 1 for
    0.3: the logits are the right model's, the objective is not."""
    got = toys.program(MTP, 64, attention_impl="reference")
    want = toys.reference(MTP, 64, wrong=wrong, attention_impl="reference")
    np.testing.assert_allclose(got.logits, want.logits, rtol=2e-4, atol=2e-4)
    assert abs(float(got.loss) - float(want.loss)) \
        > 100 * 1e-5 * float(want.loss)


# ------------------------------------------- (b) the share tied to the model
def test_b_the_eight_shares_add_up_to_the_uncut_layer():
    """The routed parts that the eight chips of the toy's deployment compute,
    each from its own two experts under the one selection bias, plus the
    shared expert counted once, are the uncut reference's sparse layer."""
    d, f, e, chips = 64, 32, 16, 8
    keys = jax.random.split(jax.random.PRNGKey(7), 9)

    def normal(key, *shape):
        return 0.3 * jax.random.normal(key, shape, jnp.float32)

    whole = {"router": {"kernel": normal(keys[0], d, e)},
             "selection_bias": normal(keys[8], e),
             "gate_proj": normal(keys[1], e, d, f),
             "up_proj": normal(keys[2], e, d, f),
             "down_proj": normal(keys[3], e, f, d),
             "shared": {name: {"kernel": normal(key, *shape)}
                        for name, key, shape in (
                            ("gate_proj", keys[4], (d, f)),
                            ("up_proj", keys[5], (d, f)),
                            ("down_proj", keys[6], (f, d)))}}
    y = jax.random.normal(keys[7], (2, 24, d), jnp.float32)
    held = e // chips
    with jax.default_matmul_precision("highest"):
        routed, shared, chosen = xing4.sparse_parts(y, whole, WHOLE, 0)
        assert float(jnp.sum(chosen)) == 2 * 24 * 4
        # the bias moved the choice: without it other experts are chosen
        unbiased = xing4.sparse_parts(
            y, {k: v for k, v in whole.items() if k != "selection_bias"},
            WHOLE, 0)[2]
        assert float(jnp.sum(jnp.abs(chosen - unbiased))) > 0
        total = 0.0
        for chip in range(chips):
            lo = held * chip
            layer = RoutedSwiGLU(RoutedConfig(
                n_experts=e, top_k=4, d_model=d, d_ff=f, norm_topk_prob=True,
                dtype=jnp.float32, experts_held=(lo, held),
                scoring="sigmoid", routed_scale=2.0, d_shared=f,
                selection_bias=True))
            mine = dict(whole, **{name: whole[name][lo:lo + held] for name in
                                  ("gate_proj", "up_proj", "down_proj")})
            part = layer.apply({"params": mine}, y) - shared
            total = total + part
            np.testing.assert_allclose(
                part, xing4.sparse_parts(y, mine, TOY, lo)[0], atol=2e-5)
    np.testing.assert_allclose(total, routed, atol=5e-5)


# ------------------------------------------------------- (d) the Sinkhorn
def test_d_twenty_iterations_and_what_they_reach():
    """Columns sum to 1 after the last division whatever the logits; rows
    within 1e-5 for logits as wide as the model's at its start (alpha 0.01:
    the bias and a hundredth of a unit normal), and no longer for wider ones
    (1e-2 at half a unit: 1e-5 "across the clamp's range" is not what 20
    iterations give, and ISSUE 65's satellite (d) is held to what they do) —
    20 iterations are the published count, not a tolerance, and
    ``hc_res_row_err`` is what says so in a run; the clamp keeps every entry
    finite at any logit; the program's and the reference's loops agree."""
    n = 4
    start = jnp.asarray(llama._hc_bias_init(n)[2 * n:]).reshape(n, n, 1)
    key = jax.random.PRNGKey(3)
    row_err = {}
    for sigma in (0.01, 0.5, 3.0, 30.0, 1e3):
        logits = start + sigma * jax.random.normal(key, (n, n, 4096))
        m = llama.sinkhorn(logits, 20, 1e-6, 30.0)
        assert bool(jnp.all(jnp.isfinite(m))) and float(m.min()) >= 0
        np.testing.assert_allclose(jnp.sum(m, axis=0), 1.0, atol=1e-5)
        row_err[sigma] = float(jnp.max(jnp.abs(jnp.sum(m, axis=1) - 1.0)))
        plain = xing4.sinkhorn(jnp.moveaxis(logits, -1, 0), TOY, 20)
        np.testing.assert_allclose(jnp.moveaxis(m, -1, 0), plain, atol=1e-6)
    assert row_err[0.01] < 1e-5
    # (near the identity, where the model starts, the iteration is slowest:
    # half a unit of noise on the logits leaves 1e-2 after twenty)
    assert 1e-4 < row_err[0.5] < 0.1
    assert row_err[3.0] > 1e-3 and row_err[30.0] > 0.5
    # one iteration is not twenty
    one = llama.sinkhorn(start + 0.5 * jax.random.normal(key, (n, n, 4096)),
                         1, 1e-6, 30.0)
    assert float(jnp.max(jnp.abs(jnp.sum(one, axis=1) - 1.0))) > 1e-3


def test_d_reverse_mode_goes_through_every_iteration():
    """The gradient of the unrolled loop against finite differences in
    float64-free form: a directional derivative at a step of 1e-3."""
    logits = jax.random.normal(jax.random.PRNGKey(5), (4, 4, 8))
    direction = jax.random.normal(jax.random.PRNGKey(6), (4, 4, 8))
    weigh = jax.random.normal(jax.random.PRNGKey(7), (4, 4, 8))

    def f(l):
        return jnp.sum(llama.sinkhorn(l, 20, 1e-6, 30.0) * weigh)

    analytic = float(jnp.sum(jax.grad(f)(logits) * direction))
    numeric = float(f(logits + 5e-3 * direction)
                    - f(logits - 5e-3 * direction)) / 1e-2
    assert analytic == pytest.approx(numeric, rel=2e-2, abs=1e-3)


# ------------------------------------- (e) the older programs are unchanged
def test_e_one_stream_is_the_block_it_was():
    """``hc_mult`` 1 makes no module and no parameter, and the model is the
    plain pre-norm stack bit for bit: the same tree and logits as Kimi-VL's
    toy gives through the fields it always had."""
    old = toys.config("toy-kimi-vl")
    assert (old.hc_mult, old.q_lora_rank, old.n_mtp_modules) == (1, 0, 0)
    model, params = toys.weights("toy-kimi-vl", attention_impl="reference")
    assert set(params["h_1"]) == {"attn", "attn_norm", "mlp_norm", "moe"}
    assert set(params["h_1"]["attn"]) == {"wq", "wdkv", "kv_norm", "wukv",
                                          "wo"}
    assert not [k for k in params if k.startswith("mtp")]
    ids = toys.rows("toy-kimi-vl", 2, 64)["input_ids"]
    with jax.default_matmul_precision("highest"):
        got = model.apply({"params": params}, ids)
        want = kimi_vl.logits(params, ids, toys.toy("toy-kimi-vl"))
    np.testing.assert_allclose(got[..., :512], want, rtol=2e-4, atol=2e-4)
    # four equal streams under gates that sum to 1 and n and an H_res whose
    # rows sum to 1 are the one stream: the path's algebra, in float32
    cfg = dataclasses.replace(toys.config(TOY), n_layer=1,
                              mlp_types=("dense",), attention_impl="reference")
    model, params = init_params(cfg)
    for name in ("hc_attn", "hc_mlp"):
        hc = dict(params["h_0"][name])
        hc["alpha"] = jnp.zeros(3)
        hc["bias"] = jnp.concatenate([
            jnp.full(4, -jnp.log(3.0)), jnp.zeros(4),
            hc["bias"][8:]])
        params["h_0"][name] = hc
    one = dataclasses.replace(cfg, hc_mult=1)
    plain = {k: v for k, v in params.items()}
    plain["h_0"] = {k: v for k, v in params["h_0"].items()
                    if not k.startswith("hc_")}
    ids = toys.rows(TOY, 2, 32)["input_ids"]
    with jax.default_matmul_precision("highest"):
        got = model.apply({"params": params}, ids)
        want = llama.LlamaLMModel(one).apply({"params": plain}, ids)
    np.testing.assert_allclose(got, want, rtol=2e-4, atol=2e-4)


# ----------------------------------------------------- (f) the rotary table
def test_f_the_latent_paths_table_is_the_published_yarn():
    """``rope_table`` under the configuration's ``RopeTable`` against the
    reference's frequencies (``find_correction_range``, the linear ramp) at
    8,192 positions and the published sizes; below dimension 10 it is plain
    RoPE, above 23 the frequencies are a 64th; the score scale is ``192^-0.5
    x 2.0047``."""
    published = dict(
        TOY, qk_rope_head_dim=64, qk_nope_head_dim=128,
        rope_scaling=dict(TOY["rope_scaling"],
                          original_max_position_embeddings=4096))
    cfg = xing4.model_config(published, 1)
    table = dict(cfg.rope_tables)["attention"]
    assert (table.factor, table.original_positions, table.attention_factor
            ) == (64.0, 4096, 1.0)
    assert cfg.attn_scale == pytest.approx(192 ** -0.5 * 2.0047, rel=1e-4)
    positions = jnp.arange(8192)
    cos, sin = llama.rope_table(64, positions, table)
    inv = xing4.yarn_inverse_frequencies(published)
    assert xing4.find_correction_range(32, 1, 64, 10000.0, 4096) == (10, 23)
    angle = np.arange(8192, dtype=np.float32)[:, None] * inv[None, :]
    np.testing.assert_allclose(cos, np.cos(angle), atol=2e-3)
    np.testing.assert_allclose(sin, np.sin(angle), atol=2e-3)
    plain_cos, _ = llama.rope_frequencies(64, positions, 10000.0)
    np.testing.assert_array_equal(cos[:, :11], plain_cos[:, :11])
    assert float(jnp.max(jnp.abs(cos[:, 11:] - plain_cos[:, 11:]))) > 0.5
    plain_inv = xing4.yarn_inverse_frequencies(published, plain=True)
    np.testing.assert_allclose(inv[23:], plain_inv[23:] / 64, rtol=1e-6)
    np.testing.assert_allclose(inv[:11], plain_inv[:11], rtol=1e-6)

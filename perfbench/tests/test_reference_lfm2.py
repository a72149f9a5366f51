"""The ``lfm2`` family's plain reference, piece by piece against values
written out by hand — a conv mixer's output at one position, a head's scores
under the per-head norm and RoPE with a key/value head shared by four query
heads, the sigmoid router with its selection bias and its epsilon, the chip's
share — and the program's own configuration against the file.  The whole
model, program against reference: ``tests/test_lfm2.py``."""

import json
import math
import os

import numpy as np
import pytest

from perfbench.harness import manifest
from perfbench.harness.families import lfm2

CONFIG = json.load(open(os.path.join(
    manifest.BENCH_DIR, "configs", "lfm2-24b-a2b.json")))
TOY = json.load(open(os.path.join(os.path.dirname(
    os.path.abspath(__file__)), "toy", "toy-lfm2.json")))


def _conv(seed=0, d=64):
    import jax

    keys = jax.random.split(jax.random.PRNGKey(seed), 3)
    return {"in_proj": {"kernel": 0.3 * jax.random.normal(keys[0], (d, 3, d))},
            "conv_kernel": jax.random.normal(keys[1], (3, d)),
            "out_proj": {"kernel": 0.3 * jax.random.normal(keys[2], (d, d))}}


def test_one_position_of_the_mixer_by_hand():
    """Position 4: ``C_4 * (k_0 (B u)_2 + k_1 (B u)_3 + k_2 (B u)_4)`` through
    ``Wout``; position 0 has its own tap alone, position 1 two."""
    import jax

    c = _conv()
    y = np.asarray(jax.random.normal(jax.random.PRNGKey(9), (1, 7, 64)),
                   np.float64)
    got = np.asarray(lfm2.short_conv(
        jax.numpy.asarray(y, jax.numpy.float32), c, TOY))
    win = np.asarray(c["in_proj"]["kernel"], np.float64)
    taps = np.asarray(c["conv_kernel"], np.float64)
    wout = np.asarray(c["out_proj"]["kernel"], np.float64)
    b, gate, u = (y[0] @ win[:, i] for i in range(3))
    bu = b * u
    for t, want in ((4, taps[0] * bu[2] + taps[1] * bu[3] + taps[2] * bu[4]),
                    (1, taps[1] * bu[0] + taps[2] * bu[1]),
                    (0, taps[2] * bu[0])):
        np.testing.assert_allclose(got[0, t], (gate[t] * want) @ wout,
                                   rtol=2e-4, atol=2e-5)


def _attn(seed=0, d=64, h=4, kv=2, hd=16):
    import jax

    keys = jax.random.split(jax.random.PRNGKey(seed), 6)
    a = {name: {"kernel": 0.3 * jax.random.normal(key, shape)}
         for key, name, shape in zip(
             keys, ("wq", "wk", "wv", "wo"),
             ((d, h * hd), (d, kv * hd), (d, kv * hd), (h * hd, d)))}
    for key, name in zip(keys[4:], ("q_norm", "k_norm")):
        a[name] = {"scale": 1.0 + 0.5 * jax.random.normal(key, (hd,))}
    return a


def test_one_head_by_hand():
    """Query 5 of head 3 at the toy's widths: its 16 dimensions normed by the
    one query scale and turned, against each earlier position's key of
    key/value head 1 (which serves query heads 2 and 3), scaled by ``16 **
    -0.5``, a softmax over positions 0..5, the weighted sum of key/value head
    1's values; then through ``Wo`` with the other heads'."""
    import jax

    a = _attn()
    y = np.asarray(jax.random.normal(jax.random.PRNGKey(9), (1, 7, 64)),
                   np.float64)
    w = {k: np.asarray(v.get("kernel", v.get("scale")), np.float64)
         for k, v in a.items()}
    out = np.asarray(lfm2.attention(
        jax.numpy.asarray(y, jax.numpy.float32),
        dict(a, wo={"kernel": jax.numpy.eye(64)}), TOY))

    def normed_turned(x, scale, position):
        x = x / math.sqrt((x ** 2).mean() + 1e-5) * scale
        inv = 1e6 ** (-np.arange(0, 16, 2) / 16)
        c, s = np.cos(position * inv), np.sin(position * inv)
        return np.concatenate([x[:8] * c - x[8:] * s, x[8:] * c + x[:8] * s])

    head, group, t = 3, 1, 5
    q = normed_turned((y[0, t] @ w["wq"])[head * 16:(head + 1) * 16],
                      w["q_norm"], t)
    scores, values = [], []
    for j in range(t + 1):
        k = normed_turned((y[0, j] @ w["wk"])[group * 16:(group + 1) * 16],
                          w["k_norm"], j)
        scores.append(q @ k / 4.0)
        values.append((y[0, j] @ w["wv"])[group * 16:(group + 1) * 16])
    p = np.exp(scores - np.max(scores))
    want = (p / p.sum()) @ np.asarray(values)
    np.testing.assert_allclose(out[0, t, head * 16:(head + 1) * 16], want,
                               rtol=2e-4, atol=2e-5)


@pytest.mark.parametrize("wrong", ["no_gate_b", "no_gate_c", "conv_4_wide",
                                   "conv_sees_ahead", "no_qk_norm",
                                   "no_rope"])
def test_each_wrong_mixer_is_another_function(wrong):
    import jax

    y = jax.random.normal(jax.random.PRNGKey(3), (2, 9, 64))
    if wrong.startswith(("no_gate", "conv")):
        c = _conv(1)
        right, other = (lfm2.short_conv(y, c, TOY, w) for w in (None, wrong))
        if wrong == "conv_4_wide":
            # positions 0 to 2 have nothing three back
            np.testing.assert_allclose(right[:, :3], other[:, :3], atol=1e-6)
    else:
        a = _attn(1)
        right, other = (lfm2.attention(y, a, TOY, w) for w in (None, wrong))
        if wrong == "no_rope":
            # position 0 sees itself alone: no rotation can show there
            np.testing.assert_allclose(right[:, 0], other[:, 0], atol=1e-6)
    assert not np.allclose(right, other, atol=1e-3), wrong


def test_the_program_is_given_the_files_sizes():
    cfg = lfm2.model_config(CONFIG, 1)
    assert (cfg.d_model, cfg.n_head, cfg.n_kv_head, cfg.n_layer) == (
        2048, 32, 8, 5)
    assert (cfg.head_dim, cfg.qk_norm, cfg.rope, cfg.rope_theta) == (
        64, "head", True, 1e6)
    assert cfg.layer_types == ("conv", "full_attention", "conv", "conv",
                               "conv")
    assert cfg.conv_width == 3
    assert cfg.mlp_types == ("dense",) + ("sparse",) * 4
    assert (cfg.d_ff, cfg.d_expert, cfg.d_shared_expert) == (11776, 1536, 0)
    assert (cfg.n_experts, cfg.moe_top_k, cfg.experts_held) == (64, 4, (0, 8))
    assert (cfg.router_scoring, cfg.routed_scale, cfg.norm_topk_prob,
            cfg.norm_topk_eps, cfg.router_selection_bias) == (
        "sigmoid", 1.0, True, 1e-6, True)
    assert (cfg.rms_eps, cfg.vocab_size, cfg.tie_embeddings) == (
        1e-5, 8192, True)
    assert cfg.attn_scale is None       # 64 ** -0.5, the kernels' own
    assert (cfg.router_aux_weight, cfg.router_z_weight) == (0.0, 0.0)
    assert cfg.remat and cfg.remat_policy == "full"
    # the published 40 layers: two dense, 38 sparse; attention at 2, 6, .. 38
    whole = dict(CONFIG, num_dense_layers=2)
    assert [lfm2.is_dense(whole, i) for i in range(40)] \
        == [True] * 2 + [False] * 38
    published = CONFIG["published_counts"]["layer_types"]
    assert [i for i, kind in enumerate(published)
            if kind == "full_attention"] == list(range(2, 40, 4))


def test_the_router_scores_chooses_by_the_bias_and_shares():
    import jax
    import jax.numpy as jnp

    keys = jax.random.split(jax.random.PRNGKey(2), 7)
    d, f, e = 64, 32, 16
    m = {"router": {"kernel": jax.random.normal(keys[0], (d, e))},
         "selection_bias": 0.3 * jax.random.normal(keys[5], (e,)),
         "gate_proj": jax.random.normal(keys[1], (e, d, f)) * 0.1,
         "up_proj": jax.random.normal(keys[2], (e, d, f)) * 0.1,
         "down_proj": jax.random.normal(keys[3], (e, f, d)) * 0.1}
    y = jax.random.normal(keys[4], (1, 5, d))
    whole = dict(TOY, num_experts=16)
    routed, chosen = lfm2.sparse_parts(y, m, whole, 0)
    assert np.asarray(chosen).sum(-1).tolist() == [[4.0] * 5]
    # by hand for one token: sigmoid scores, the four largest of score + bias,
    # each weighted by its score over the four scores' sum + 1e-6
    t = np.asarray(y[0, 2], np.float64)
    score = 1 / (1 + np.exp(-t @ np.asarray(m["router"]["kernel"],
                                            np.float64)))
    bias = np.asarray(m["selection_bias"], np.float64)
    top = np.argsort(score + bias)[-4:]
    assert set(top) == set(np.flatnonzero(np.asarray(chosen[0, 2])))
    # (the bias does change some token's choice here)
    unbiased = lfm2.sparse_parts(y, dict(m, selection_bias=jnp.zeros(e)),
                                 whole, 0)[1]
    assert not np.array_equal(unbiased, chosen)
    want = np.zeros(d)
    for i in top:
        g, u, dn = (np.asarray(m[n][i], np.float64)
                    for n in ("gate_proj", "up_proj", "down_proj"))
        a = t @ g
        want += score[i] / (score[top].sum() + 1e-6) \
            * ((a / (1 + np.exp(-a))) * (t @ u)) @ dn
    np.testing.assert_allclose(routed[0, 2], want, rtol=2e-3, atol=1e-4)
    # a share's part has the held experts' terms alone, under the same
    # weights
    mine = dict(m, **{n: m[n][2:4] for n in ("gate_proj", "up_proj",
                                             "down_proj")})
    part, chosen_again = lfm2.sparse_parts(y, mine, TOY, 2)
    np.testing.assert_array_equal(chosen_again, chosen)
    assert float(jnp.max(jnp.abs(part))) < float(jnp.max(jnp.abs(routed)))
    for wrong in ("softmax_scores", "top_3", "no_renorm", "bias_in_weights"):
        other = lfm2.sparse_parts(y, m, whole, 0, wrong)
        assert not np.allclose(other[0], routed, atol=1e-3), wrong

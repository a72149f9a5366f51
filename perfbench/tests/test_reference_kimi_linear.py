"""The ``kimi_linear`` family's plain reference, piece by piece against
values written out by hand in float64 — one head's delta-rule recurrence with
its decay a channel, the convolution's taps, the unit norms, the low-rank
gates; a head's scores over the key's two un-rotated parts; the sigmoid
router with its renormalisation and scale — and the program's own
configuration against the file.  The whole model, program against reference:
``tests/test_kimi_linear.py``."""

import json
import math
import os

import numpy as np
import pytest

from perfbench.harness import manifest
from perfbench.harness.families import kimi_linear

CONFIG = json.load(open(os.path.join(
    manifest.BENCH_DIR, "configs", "kimi-linear-48b-a3b-instruct.json")))
TOY = json.load(open(os.path.join(os.path.dirname(
    os.path.abspath(__file__)), "toy", "toy-kimi-linear.json")))
KDA_WRONG = ("no_decay", "head_decay", "beta_1", "no_delta",
             "decay_after_correction", "no_l2norm", "q_unscaled", "no_conv",
             "silu_out_gate", "no_out_norm")


def _kda(seed=0, d=64, heads=4, w=16):
    import jax

    keys = iter(jax.random.split(jax.random.PRNGKey(seed), 20))
    inner = heads * w

    def dense(rows, cols, bias=False):
        p = {"kernel": 0.3 * jax.random.normal(next(keys), (rows, cols))}
        if bias:
            p["bias"] = 0.3 * jax.random.normal(next(keys), (cols,))
        return p

    p = {name: dense(d, inner) for name in ("q_proj", "k_proj", "v_proj")}
    p.update(f_a=dense(d, w), f_b=dense(w, inner), g_a=dense(d, w),
             g_b=dense(w, inner, bias=True), b_proj=dense(d, heads),
             o_proj=dense(inner, d))
    for name in ("q_conv", "k_conv", "v_conv"):
        p[name] = 0.5 * jax.random.normal(next(keys), (4, inner))
    p["A_log"] = jax.random.normal(next(keys), (heads,))
    p["dt_bias"] = jax.random.normal(next(keys), (inner,))
    p["o_norm"] = {"scale": 1.0 + 0.3 * jax.random.normal(next(keys), (w,))}
    return p


def test_one_head_by_hand():
    """Head 2 of the toy's mixer over seven positions, every step written
    out: the convolution's four taps, silu, the unit norms and q's scale, the
    decay a channel, the write strength, ``S_t = (I - b k k^T) Diag(a) S +
    b k v^T``, the read-out, the per-head norm under the gate."""
    import jax

    p = _kda()
    y = np.asarray(jax.random.normal(jax.random.PRNGKey(9), (1, 7, 64)),
                   np.float64)
    got = np.asarray(kimi_linear.kda(
        jax.numpy.asarray(y, jax.numpy.float32), p, TOY))
    f = lambda a: np.asarray(a, np.float64)  # noqa: E731
    head, w = 2, 16
    at = slice(head * w, (head + 1) * w)
    silu = lambda x: x / (1 + np.exp(-x))  # noqa: E731
    sigmoid = lambda x: 1 / (1 + np.exp(-x))  # noqa: E731

    def mixed(name):        # the projection, its causal convolution, silu
        x = y[0] @ f(p[name + "_proj"]["kernel"])
        kernel = f(p[name + "_conv"])
        out = np.zeros_like(x)
        for t in range(7):
            for tap in range(4):        # tap 3 reads position t itself
                if t - 3 + tap >= 0:
                    out[t] += kernel[tap] * x[t - 3 + tap]
        return silu(out)[:, at]

    unit = lambda x: x / np.sqrt((x * x).sum(-1, keepdims=True) + 1e-6)  # noqa: E731
    q, k, v = unit(mixed("q")) / math.sqrt(w), unit(mixed("k")), mixed("v")
    low = (y[0] @ f(p["f_a"]["kernel"])) @ f(p["f_b"]["kernel"])
    g = -np.exp(f(p["A_log"])[head]) * np.log1p(np.exp(
        low[:, at] + f(p["dt_bias"])[at]))
    b = sigmoid(y[0] @ f(p["b_proj"]["kernel"]))[:, head]
    S, outs = np.zeros((w, w)), []
    for t in range(7):
        S = np.exp(g[t])[:, None] * S
        S = S + b[t] * np.outer(k[t], v[t] - k[t] @ S)
        outs.append(S.T @ q[t])
    o = np.asarray(outs)
    o = o / np.sqrt((o * o).mean(-1, keepdims=True) + 1e-5) \
        * f(p["o_norm"]["scale"])
    gate = (y[0] @ f(p["g_a"]["kernel"])) @ f(p["g_b"]["kernel"]) \
        + f(p["g_b"]["bias"])
    mine = o * sigmoid(gate[:, at])
    # the other heads' part of Wo's product, by the reference itself with
    # this head's rows of Wo zeroed
    wo = f(p["o_proj"]["kernel"])
    rest = dict(p, o_proj={"kernel": jax.numpy.asarray(
        np.where(np.arange(64)[:, None] // w == head, 0.0, wo),
        jax.numpy.float32)})
    others = np.asarray(kimi_linear.kda(
        jax.numpy.asarray(y, jax.numpy.float32), rest, TOY))
    np.testing.assert_allclose(got[0] - others[0], mine @ wo[at],
                               rtol=2e-3, atol=2e-4)


@pytest.mark.parametrize("wrong", KDA_WRONG)
def test_each_wrong_mixer_is_another_function(wrong):
    import jax

    p = _kda(1)
    y = jax.random.normal(jax.random.PRNGKey(3), (2, 9, 64))
    right = kimi_linear.kda(y, p, TOY)
    other = kimi_linear.kda(y, p, TOY, wrong)
    assert not np.allclose(right, other, atol=1e-3), wrong
    # position 0 starts from a zero state: neither a decay nor the
    # correction nor their order can show there
    if wrong in ("no_decay", "head_decay", "no_delta",
                 "decay_after_correction"):
        np.testing.assert_allclose(right[:, 0], other[:, 0], atol=1e-6)


def _attn(seed=0, d=64, h=4, dn=16, dr=8, dv=16, rank=32):
    import jax

    keys = jax.random.split(jax.random.PRNGKey(seed), 5)
    shapes = {"wq": (d, h * (dn + dr)), "wdkv": (d, rank + dr),
              "wukv": (rank, h * (dn + dv)), "wo": (h * dv, d)}
    a = {name: {"kernel": 0.3 * jax.random.normal(key, shape)}
         for key, (name, shape) in zip(keys, shapes.items())}
    a["kv_norm"] = {"scale": 1.0 + 0.5 * jax.random.normal(keys[4], (rank,))}
    return a


def test_one_attention_head_by_hand():
    """Query 5 of head 2: its 16 + 8 dimensions against each earlier
    position's ``[kn_2 ; kr]``, nothing turned, scaled by ``24 ** -0.5``."""
    import jax

    a = _attn()
    eye = dict(a, wo={"kernel": jax.numpy.eye(64)})
    y = np.asarray(jax.random.normal(jax.random.PRNGKey(9), (1, 7, 64)),
                   np.float64)
    got = np.asarray(kimi_linear.latent_attention(
        jax.numpy.asarray(y, jax.numpy.float32), eye, TOY))
    w = {k: np.asarray(v.get("kernel", v.get("scale")), np.float64)
         for k, v in a.items()}
    head, t = 2, 5
    q = (y[0, t] @ w["wq"])[head * 24:(head + 1) * 24]
    scores, values = [], []
    for j in range(t + 1):
        down = y[0, j] @ w["wdkv"]
        c = down[:32] / math.sqrt((down[:32] ** 2).mean() + 1e-5) \
            * w["kv_norm"]
        kv = (c @ w["wukv"])[head * 32:(head + 1) * 32]
        scores.append(q @ np.concatenate([kv[:16], down[32:]])
                      / math.sqrt(24))
        values.append(kv[16:])
    p = np.exp(scores - np.max(scores))
    want = (p / p.sum()) @ np.asarray(values)
    np.testing.assert_allclose(got[0, t, head * 16:(head + 1) * 16], want,
                               rtol=2e-4, atol=2e-5)
    for wrong in ("mla_rope", "own_kr"):
        other = np.asarray(kimi_linear.latent_attention(
            jax.numpy.asarray(y, jax.numpy.float32), eye, TOY, wrong))
        assert not np.allclose(got, other, atol=1e-3), wrong


def test_the_program_is_given_the_files_sizes():
    cfg = kimi_linear.model_config(CONFIG, 1)
    assert (cfg.d_model, cfg.n_head, cfg.n_layer) == (2304, 32, 5)
    assert cfg.layer_types == ("kda",) * 3 + ("full_attention", "kda")
    assert (cfg.kda_n_heads, cfg.kda_head_dim, cfg.kda_d_conv,
            cfg.kda_chunk) == (32, 128, 4, 64)
    assert (cfg.kv_lora_rank, cfg.qk_nope_head_dim, cfg.qk_rope_head_dim,
            cfg.v_head_dim) == (512, 128, 64, 128)
    assert not cfg.rope and cfg.attn_scale is None
    assert cfg.mlp_types == ("dense",) + ("sparse",) * 4
    assert (cfg.d_ff, cfg.d_expert, cfg.d_shared_expert) == (9216, 1024, 1024)
    assert (cfg.n_experts, cfg.moe_top_k, cfg.experts_held) == (256, 8,
                                                                (0, 8))
    assert (cfg.router_scoring, cfg.routed_scale, cfg.norm_topk_prob) == (
        "sigmoid", 2.446, True)
    assert (cfg.rms_eps, cfg.vocab_size) == (1e-5, 20480)
    assert (cfg.router_aux_weight, cfg.router_z_weight) == (0.0, 0.0)
    assert cfg.remat and cfg.remat_policy == "full"
    # the published 27 layers: three KDA then one MLA, the last two KDA then
    # MLA; dense first, sparse after
    whole = dict(CONFIG, num_hidden_layers=27,
                 linear_attn_config=CONFIG["published_counts"][
                     "linear_attn_config"])
    kinds = kimi_linear.layer_kinds(whole)
    assert kinds.count("kda") == 20 and kinds.count("full_attention") == 7
    assert kinds[:8] == ("kda", "kda", "kda", "full_attention") * 2
    assert kinds[-3:] == ("kda", "kda", "full_attention")
    assert [kimi_linear.is_dense(whole, i) for i in range(27)] \
        == [True] + [False] * 26


def test_the_router_scores_scales_and_shares():
    import jax
    import jax.numpy as jnp

    keys = jax.random.split(jax.random.PRNGKey(2), 8)
    d, f, e = 64, 32, 8
    m = {"router": {"kernel": jax.random.normal(keys[0], (d, e))},
         "gate_proj": jax.random.normal(keys[1], (e, d, f)) * 0.1,
         "up_proj": jax.random.normal(keys[2], (e, d, f)) * 0.1,
         "down_proj": jax.random.normal(keys[3], (e, f, d)) * 0.1,
         "shared": {n: {"kernel": jax.random.normal(k, s) * 0.1}
                    for n, k, s in (("gate_proj", keys[4], (d, f)),
                                    ("up_proj", keys[5], (d, f)),
                                    ("down_proj", keys[6], (f, d)))}}
    y = jax.random.normal(keys[7], (1, 5, d))
    whole = dict(TOY, num_experts=8)
    routed, shared, chosen = kimi_linear.sparse_parts(y, m, whole, 0)
    assert np.asarray(chosen).sum(-1).tolist() == [[3.0] * 5]
    # by hand for one token: sigmoid scores, the top three divided by their
    # sum, times 2.446, each on its expert's SwiGLU
    t = np.asarray(y[0, 2], np.float64)
    score = 1 / (1 + np.exp(-t @ np.asarray(m["router"]["kernel"], np.float64)))
    top = np.argsort(score)[-3:]
    assert set(top) == set(np.flatnonzero(np.asarray(chosen[0, 2])))
    want = np.zeros(d)
    for i in top:
        g, u, dn = (np.asarray(m[n][i], np.float64)
                    for n in ("gate_proj", "up_proj", "down_proj"))
        a = t @ g
        want += 2.446 * score[i] / score[top].sum() \
            * ((a / (1 + np.exp(-a))) * (t @ u)) @ dn
    np.testing.assert_allclose(routed[0, 2], want, rtol=2e-3, atol=1e-4)
    # a share's part has the held experts' terms alone, under the same
    # weights; the shared expert does not depend on the share
    mine = dict(m, **{n: m[n][2:4] for n in ("gate_proj", "up_proj",
                                             "down_proj")})
    part, shared_again, _ = kimi_linear.sparse_parts(y, mine, TOY, 2)
    np.testing.assert_allclose(shared_again, shared, rtol=1e-6)
    assert float(jnp.max(jnp.abs(part))) < float(jnp.max(jnp.abs(routed)))
    for wrong in ("softmax_scores", "routed_scale_1", "top_6", "no_renorm"):
        other = kimi_linear.sparse_parts(y, m, whole, 0, wrong)
        assert not np.allclose(other[0] + other[1], routed + shared,
                               atol=1e-3), wrong

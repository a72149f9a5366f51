"""The ``kimi_vl`` family's plain reference, piece by piece against values
written out by hand — a head's scores over the key's two parts, the one
rotary key of a position, the latent's norm, the sigmoid router with its
scale, the shared expert's width, the chip's share — and the program's own
configuration against the file.  The whole model, program against reference:
``tests/test_kimi_vl.py``."""

import json
import math
import os

import numpy as np
import pytest

from perfbench.harness import manifest
from perfbench.harness.families import kimi_vl

CONFIG = json.load(open(os.path.join(
    manifest.BENCH_DIR, "configs", "kimi-vl-a3b-instruct.json")))
TOY = json.load(open(os.path.join(os.path.dirname(
    os.path.abspath(__file__)), "toy", "toy-kimi-vl.json")))


def _attn(seed=0, d=64, h=4, dn=16, dr=8, dv=16, rank=32):
    import jax

    keys = jax.random.split(jax.random.PRNGKey(seed), 5)
    shapes = {"wq": (d, h * (dn + dr)), "wdkv": (d, rank + dr),
              "wukv": (rank, h * (dn + dv)), "wo": (h * dv, d)}
    a = {name: {"kernel": 0.3 * jax.random.normal(key, shape)}
         for key, (name, shape) in zip(keys, shapes.items())}
    a["kv_norm"] = {"scale": 1.0 + 0.5 * jax.random.normal(keys[4], (rank,))}
    return a


def test_one_head_by_hand():
    """Query 5 of head 2 at the toy's widths: its 16 + 8 dimensions against
    each earlier position's ``[kn_2 ; R(kr)]``, scaled by ``24 ** -0.5``, a
    softmax over positions 0..5, the weighted sum of head 2's values."""
    import jax

    a = _attn()
    y = np.asarray(jax.random.normal(jax.random.PRNGKey(9), (1, 7, 64)),
                   np.float64)
    got = np.asarray(kimi_vl.latent_attention(
        jax.numpy.asarray(y, jax.numpy.float32), a, TOY))
    assert got.shape == (1, 7, 4 * 16)
    w = {k: np.asarray(v.get("kernel", v.get("scale")), np.float64)
         for k, v in a.items()}

    def turned(x, position):    # rotate-half over the 8 rotary dimensions
        inv = 800000.0 ** (-np.arange(0, 8, 2) / 8)
        c, s = np.cos(position * inv), np.sin(position * inv)
        return np.concatenate([x[:4] * c - x[4:] * s, x[4:] * c + x[:4] * s])

    head, t = 2, 5
    q = (y[0, t] @ w["wq"])[head * 24:(head + 1) * 24]
    q = np.concatenate([q[:16], turned(q[16:], t)])
    scores, values = [], []
    for j in range(t + 1):
        down = y[0, j] @ w["wdkv"]
        c = down[:32] / math.sqrt((down[:32] ** 2).mean() + 1e-5) \
            * w["kv_norm"]
        kv = (c @ w["wukv"])[head * 32:(head + 1) * 32]
        k = np.concatenate([kv[:16], turned(down[32:], j)])
        scores.append(q @ k / math.sqrt(24))
        values.append(kv[16:])
    p = np.exp(scores - np.max(scores))
    want = (p / p.sum()) @ np.asarray(values)
    np.testing.assert_allclose(got[0, t, head * 16:(head + 1) * 16], want,
                               rtol=2e-4, atol=2e-5)


@pytest.mark.parametrize("wrong", ["no_latent_norm", "no_rope_on_shared_key",
                                   "scale_128", "values_from_key_half"])
def test_each_wrong_attention_is_another_function(wrong):
    import jax

    a = _attn(1)
    y = jax.random.normal(jax.random.PRNGKey(3), (2, 9, 64))
    right = kimi_vl.latent_attention(y, a, TOY)
    other = kimi_vl.latent_attention(y, a, TOY, wrong)
    assert not np.allclose(right, other, atol=1e-3), wrong
    # position 0 sees itself alone: no scale and no rotation can show there
    if wrong in ("scale_128", "no_rope_on_shared_key"):
        np.testing.assert_allclose(right[:, 0], other[:, 0], atol=1e-6)


def test_the_program_is_given_the_files_sizes():
    cfg = kimi_vl.model_config(CONFIG, 1)
    assert (cfg.d_model, cfg.n_head, cfg.n_layer) == (2048, 16, 6)
    assert (cfg.kv_lora_rank, cfg.qk_nope_head_dim, cfg.qk_rope_head_dim,
            cfg.v_head_dim) == (512, 128, 64, 128)
    assert cfg.mlp_types == ("dense",) + ("sparse",) * 5
    assert (cfg.d_ff, cfg.d_expert, cfg.d_shared_expert) == (11264, 1408,
                                                             2816)
    assert (cfg.n_experts, cfg.moe_top_k, cfg.experts_held) == (64, 6, (0, 8))
    assert (cfg.router_scoring, cfg.routed_scale, cfg.norm_topk_prob) == (
        "sigmoid", 2.446, True)
    assert (cfg.rope_theta, cfg.rms_eps, cfg.vocab_size) == (8e5, 1e-5, 20480)
    assert cfg.attn_scale is None       # (128 + 64) ** -0.5, the kernels' own
    assert (cfg.router_aux_weight, cfg.router_z_weight) == (0.0, 0.0)
    assert cfg.remat and cfg.remat_policy == "full"
    # the published 27 layers: dense first, sparse after
    whole = dict(CONFIG, num_hidden_layers=27)
    assert [kimi_vl.is_dense(whole, i) for i in range(27)] \
        == [True] + [False] * 26


def test_the_router_scores_scales_and_shares():
    import jax
    import jax.numpy as jnp

    keys = jax.random.split(jax.random.PRNGKey(2), 8)
    d, f, e = 64, 32, 16
    m = {"router": {"kernel": jax.random.normal(keys[0], (d, e))},
         "gate_proj": jax.random.normal(keys[1], (e, d, f)) * 0.1,
         "up_proj": jax.random.normal(keys[2], (e, d, f)) * 0.1,
         "down_proj": jax.random.normal(keys[3], (e, f, d)) * 0.1,
         "shared": {n: {"kernel": jax.random.normal(k, s) * 0.1}
                    for n, k, s in (("gate_proj", keys[4], (d, 2 * f)),
                                    ("up_proj", keys[5], (d, 2 * f)),
                                    ("down_proj", keys[6], (2 * f, d)))}}
    y = jax.random.normal(keys[7], (1, 5, d))
    whole = dict(TOY, n_routed_experts=16)
    routed, shared, chosen = kimi_vl.sparse_parts(y, m, whole, 0)
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
    # the shared expert is one SwiGLU of both halves' width: the sum of two
    # of the experts' width over the halves of its hidden units
    halves = sum(
        kimi_vl.swiglu(y, {n: {"kernel": (
            w["kernel"][lo:lo + f] if n == "down_proj"
            else w["kernel"][:, lo:lo + f])} for n, w in m["shared"].items()})
        for lo in (0, f))
    np.testing.assert_allclose(shared, halves, rtol=1e-4, atol=1e-5)
    # a share's part has the held experts' terms alone, under the same
    # weights; the shared expert does not depend on the share
    mine = dict(m, **{n: m[n][2:4] for n in ("gate_proj", "up_proj",
                                             "down_proj")})
    part, shared_again, _ = kimi_vl.sparse_parts(y, mine, TOY, 2)
    np.testing.assert_allclose(shared_again, shared, rtol=1e-6)
    assert float(jnp.max(jnp.abs(part))) < float(jnp.max(jnp.abs(routed)))
    for wrong in ("softmax_scores", "routed_scale_1", "top_5",
                  "one_shared_expert"):
        other = kimi_vl.sparse_parts(y, m, whole, 0, wrong)
        assert not np.allclose(other[0] + other[1], routed + shared,
                               atol=1e-3), wrong

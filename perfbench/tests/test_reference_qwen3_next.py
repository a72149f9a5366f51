"""The ``qwen3_next`` family's plain reference, piece by piece against values
written out by hand in float64 — one value head's delta-rule recurrence with
its one decay a head, reading its key head's q and k, the convolution's taps,
the unit norms, the norm before the silu gate; a query head's scores with a
quarter of its lanes turned, under the unit-offset norm and a gate a channel;
the softmax router with its renormalisation and the gated shared expert — and
the program's own configuration against the file.  The whole model, program
against reference: ``tests/test_qwen3_next.py``."""

import json
import math
import os

import numpy as np
import pytest

from perfbench.harness import manifest
from perfbench.harness.families import qwen3_next

CONFIG = json.load(open(os.path.join(
    manifest.BENCH_DIR, "configs", "qwen3-next-80b-a3b-instruct.json")))
TOY = json.load(open(os.path.join(os.path.dirname(
    os.path.abspath(__file__)), "toy", "toy-qwen3-next.json")))
GDN_WRONG = ("no_decay", "beta_1", "no_delta", "decay_after_correction",
             "no_l2norm", "q_unscaled", "no_conv", "gate_before_norm",
             "sigmoid_out_gate", "key_head_mod")
ATTN_WRONG = ("whole_head_rope", "no_attn_gate", "head_attn_gate",
              "no_unit_offset")
MOE_WRONG = ("sigmoid_scores", "top_8", "no_renorm", "shared_ungated")


def _gdn(seed=0, e=64, keys_=2, r=2, w=16):
    import jax

    keys = iter(jax.random.split(jax.random.PRNGKey(seed), 20))
    heads = keys_ * r
    return {
        "in_proj_qkvz": {"kernel": 0.3 * jax.random.normal(
            next(keys), (e, keys_, (2 + 2 * r) * w))},
        "in_proj_ba": {"kernel": 0.3 * jax.random.normal(
            next(keys), (e, keys_, 2 * r))},
        "conv_kernel": 0.5 * jax.random.normal(
            next(keys), (4, keys_, (2 + r) * w)),
        "A_log": jax.random.normal(next(keys), (heads,)),
        "dt_bias": jax.random.normal(next(keys), (heads,)),
        "o_norm": {"scale": 1.0 + 0.3 * jax.random.normal(next(keys), (w,))},
        "out_proj": {"kernel": 0.3 * jax.random.normal(
            next(keys), (heads * w, e))}}


def test_wrong_names_every_wrong_model_once():
    assert set(GDN_WRONG + ATTN_WRONG + MOE_WRONG) == set(qwen3_next.WRONG)
    assert len(qwen3_next.WRONG) == len(set(qwen3_next.WRONG)) == 18
    assert set(qwen3_next.UNSEEN_IN_BF16) <= set(qwen3_next.WRONG)


def test_one_value_head_by_hand():
    """Value head 3 of the toy's mixer, which reads key head 1, over seven
    positions, every step written out: the convolution's four taps, silu,
    the unit norms and q's scale, ONE decay a head, the write strength,
    ``S_t = (I - b k k^T) a S + b k v^T``, the read-out, the per-head norm
    and then the silu gate."""
    import jax

    p = _gdn()
    y = np.asarray(jax.random.normal(jax.random.PRNGKey(9), (1, 7, 64)),
                   np.float64)
    got = np.asarray(qwen3_next.gdn(
        jax.numpy.asarray(y, jax.numpy.float32), p, TOY))
    f = lambda a: np.asarray(a, np.float64)  # noqa: E731
    head, w, r = 3, 16, 2
    key, within = head // r, head % r
    silu = lambda x: x / (1 + np.exp(-x))  # noqa: E731
    sigmoid = lambda x: 1 / (1 + np.exp(-x))  # noqa: E731
    qkvz = y[0] @ f(p["in_proj_qkvz"]["kernel"])[:, key]      # (7, 96)
    ba = y[0] @ f(p["in_proj_ba"]["kernel"])[:, key]          # (7, 4)
    kernel = f(p["conv_kernel"])[:, key]                      # (4, 64)
    x = qkvz[:, :(2 + r) * w]
    mixed = np.zeros_like(x)
    for t in range(7):
        for tap in range(4):            # tap 3 reads position t itself
            if t - 3 + tap >= 0:
                mixed[t] += kernel[tap] * x[t - 3 + tap]
    mixed = silu(mixed)
    unit = lambda x: x / np.sqrt((x * x).sum(-1, keepdims=True) + 1e-6)  # noqa: E731
    q, k = unit(mixed[:, :w]) / math.sqrt(w), unit(mixed[:, w:2 * w])
    v = mixed[:, (2 + within) * w:(3 + within) * w]
    z = qkvz[:, (2 + r + within) * w:(3 + r + within) * w]
    b, a = sigmoid(ba[:, within]), ba[:, r + within]
    g = -np.exp(f(p["A_log"])[head]) * np.log1p(np.exp(
        a + f(p["dt_bias"])[head]))
    S, outs = np.zeros((w, w)), []
    for t in range(7):
        S = np.exp(g[t]) * S
        S = S + b[t] * np.outer(k[t], v[t] - k[t] @ S)
        outs.append(S.T @ q[t])
    o = np.asarray(outs)
    o = o / np.sqrt((o * o).mean(-1, keepdims=True) + 1e-6) \
        * f(p["o_norm"]["scale"])
    mine = o * silu(z)
    at = slice(head * w, (head + 1) * w)
    wo = f(p["out_proj"]["kernel"])
    rest = dict(p, out_proj={"kernel": jax.numpy.asarray(
        np.where(np.arange(64)[:, None] // w == head, 0.0, wo),
        jax.numpy.float32)})
    others = np.asarray(qwen3_next.gdn(
        jax.numpy.asarray(y, jax.numpy.float32), rest, TOY))
    np.testing.assert_allclose(got[0] - others[0], mine @ wo[at],
                               rtol=2e-3, atol=2e-4)


@pytest.mark.parametrize("wrong", GDN_WRONG)
def test_each_wrong_mixer_is_another_function(wrong):
    import jax

    p = _gdn(1)
    y = jax.random.normal(jax.random.PRNGKey(3), (2, 9, 64))
    right = qwen3_next.gdn(y, p, TOY)
    other = qwen3_next.gdn(y, p, TOY, wrong)
    assert not np.allclose(right, other, atol=1e-3), wrong
    # position 0 starts from a zero state: neither a decay nor the
    # correction can show there (the wrong order decays its own write)
    if wrong in ("no_decay", "no_delta"):
        np.testing.assert_allclose(right[:, 0], other[:, 0], atol=1e-6)


def _attn(seed=0, e=64, h=4, kv=2, d=32):
    import jax

    keys = jax.random.split(jax.random.PRNGKey(seed), 6)
    shapes = {"wq": (e, 2 * h * d), "wk": (e, kv * d), "wv": (e, kv * d),
              "wo": (h * d, e)}
    a = {name: {"kernel": 0.3 * jax.random.normal(key, shape)}
         for key, (name, shape) in zip(keys, shapes.items())}
    for name, key in (("q_norm", keys[4]), ("k_norm", keys[5])):
        a[name] = {"scale": 0.5 * jax.random.normal(key, (d,))}
    return a


def test_one_attention_head_by_hand():
    """Query 5 of head 3 (key/value head 1): the norm x (1 + w), the first 8
    of its 32 lanes turned in pairs (i, i + 4) at theta 1e7, scores at ``32
    ** -0.5``, and the sigmoid of its own 32 gate logits on the result."""
    import jax

    a = _attn()
    eye = dict(a, wo={"kernel": jax.numpy.eye(128)})
    y = np.asarray(jax.random.normal(jax.random.PRNGKey(9), (1, 7, 64)),
                   np.float64)
    got = np.asarray(qwen3_next.gated_attention(
        jax.numpy.asarray(y, jax.numpy.float32), eye, TOY))
    w = {k: np.asarray(v.get("kernel", v.get("scale")), np.float64)
         for k, v in a.items()}
    head, t, d, rot = 3, 5, 32, 8

    def normed(x, scale):
        return x / math.sqrt((x * x).mean() + 1e-6) * (1 + scale)

    def turned(x, pos):
        out = x.copy()
        for i in range(rot // 2):
            angle = pos * 1e7 ** (-2 * i / rot)
            out[i] = x[i] * math.cos(angle) - x[i + rot // 2] * math.sin(angle)
            out[i + rot // 2] = x[i + rot // 2] * math.cos(angle) \
                + x[i] * math.sin(angle)
        return out

    both = (y[0, t] @ w["wq"])[head * 2 * d:(head + 1) * 2 * d]
    q = turned(normed(both[:d], w["q_norm"]), t)
    scores, values = [], []
    for j in range(t + 1):
        k = (y[0, j] @ w["wk"])[d:2 * d]
        scores.append(q @ turned(normed(k, w["k_norm"]), j) / math.sqrt(d))
        values.append((y[0, j] @ w["wv"])[d:2 * d])
    p = np.exp(scores - np.max(scores))
    want = (p / p.sum()) @ np.asarray(values) / (1 + np.exp(-both[d:]))
    np.testing.assert_allclose(got[0, t, head * d:(head + 1) * d], want,
                               rtol=2e-4, atol=2e-5)
    for wrong in ATTN_WRONG:
        other = np.asarray(qwen3_next.gated_attention(
            jax.numpy.asarray(y, jax.numpy.float32), eye, TOY, wrong))
        assert not np.allclose(got, other, atol=1e-3), wrong


def test_the_program_is_given_the_files_sizes():
    cfg = qwen3_next.model_config(CONFIG, 1)
    assert (cfg.d_model, cfg.n_head, cfg.n_kv_head, cfg.head_dim,
            cfg.n_layer) == (2048, 16, 2, 256, 4)
    assert cfg.layer_types == ("gdn",) * 3 + ("full_attention",)
    assert (cfg.kda_n_heads, cfg.gdn_key_heads, cfg.kda_head_dim,
            cfg.kda_d_conv, cfg.kda_chunk) == (32, 16, 128, 4, 64)
    table = dict(cfg.rope_tables)["full_attention"]
    assert (table.theta, table.rotary_fraction) == (1e7, 0.25)
    assert (cfg.qk_norm, cfg.norm_unit_offset, cfg.attn_gate) == (
        "head", True, "channel")
    assert cfg.rope and cfg.attn_scale is None
    assert cfg.mlp_types == ("sparse",) * 4
    assert (cfg.d_expert, cfg.d_shared_expert, cfg.shared_expert_gate) == (
        512, 512, True)
    assert (cfg.n_experts, cfg.moe_top_k, cfg.experts_held) == (512, 10,
                                                                (0, 32))
    assert (cfg.router_scoring, cfg.routed_scale, cfg.norm_topk_prob) == (
        "softmax", 1.0, True)
    assert (cfg.rms_eps, cfg.vocab_size) == (1e-6, 18992)
    assert (cfg.router_aux_weight, cfg.router_z_weight) == (0.0, 0.0)
    assert cfg.remat and cfg.remat_policy == "full"
    # the published 48 layers: three gdn then one attention, twelve times
    kinds = qwen3_next.layer_kinds(dict(CONFIG, num_hidden_layers=48))
    assert kinds.count("gdn") == 36 and kinds.count("full_attention") == 12
    assert kinds == ("gdn", "gdn", "gdn", "full_attention") * 12


def test_the_router_scores_and_the_gated_shared_expert():
    import jax
    import jax.numpy as jnp

    keys = jax.random.split(jax.random.PRNGKey(2), 9)
    d, f, e = 64, 32, 32
    m = {"router": {"kernel": 0.05 * jax.random.normal(keys[0], (d, e))},
         "gate_proj": jax.random.normal(keys[1], (e, d, f)) * 0.1,
         "up_proj": jax.random.normal(keys[2], (e, d, f)) * 0.1,
         "down_proj": jax.random.normal(keys[3], (e, f, d)) * 0.1,
         "shared": {n: {"kernel": jax.random.normal(k, s) * 0.1}
                    for n, k, s in (("gate_proj", keys[4], (d, f)),
                                    ("up_proj", keys[5], (d, f)),
                                    ("down_proj", keys[6], (f, d)),
                                    ("gate", keys[8], (d, 1)))}}
    y = jax.random.normal(keys[7], (1, 5, d))
    whole = dict(TOY, num_experts=32,
                 deployment={"chips_sharing_a_layer": 1, "this_chip": 0})
    routed, shared, chosen = qwen3_next.sparse_parts(y, m, whole, 0)
    assert np.asarray(chosen).sum(-1).tolist() == [[10.0] * 5]
    # by hand for one token: softmax over all 32, the top ten divided by
    # their sum, each on its expert's SwiGLU
    t = np.asarray(y[0, 2], np.float64)
    logit = t @ np.asarray(m["router"]["kernel"], np.float64)
    score = np.exp(logit - logit.max())
    score /= score.sum()
    top = np.argsort(score)[-10:]
    assert set(top) == set(np.flatnonzero(np.asarray(chosen[0, 2])))
    want = np.zeros(d)
    for i in top:
        g, u, dn = (np.asarray(m[n][i], np.float64)
                    for n in ("gate_proj", "up_proj", "down_proj"))
        a = t @ g
        want += score[i] / score[top].sum() \
            * ((a / (1 + np.exp(-a))) * (t @ u)) @ dn
    np.testing.assert_allclose(routed[0, 2], want, rtol=2e-3, atol=1e-4)
    # the shared expert under its one gate a token
    s = {n: np.asarray(v["kernel"], np.float64)
         for n, v in m["shared"].items()}
    a = t @ s["gate_proj"]
    plain = ((a / (1 + np.exp(-a))) * (t @ s["up_proj"])) @ s["down_proj"]
    np.testing.assert_allclose(
        shared[0, 2], plain / (1 + np.exp(-(t @ s["gate"])[0])),
        rtol=2e-3, atol=1e-5)
    # a share's part has the held experts' terms alone, under the same
    # weights; the shared expert does not depend on the share
    mine = dict(m, **{n: m[n][8:16] for n in ("gate_proj", "up_proj",
                                              "down_proj")})
    part, shared_again, _ = qwen3_next.sparse_parts(y, mine, TOY, 8)
    np.testing.assert_allclose(shared_again, shared, rtol=1e-6)
    assert float(jnp.max(jnp.abs(part))) < float(jnp.max(jnp.abs(routed)))
    for wrong in MOE_WRONG:
        other = qwen3_next.sparse_parts(y, m, whole, 0, wrong)
        assert not np.allclose(other[0] + other[1], routed + shared,
                               atol=1e-3), wrong

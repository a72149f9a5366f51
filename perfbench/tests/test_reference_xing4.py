"""The ``xing4`` family's plain reference, piece by piece against values
written out by hand — the hyper-connection's coefficients and the stream's
update position by position, the Sinkhorn's row and column divisions, YaRN's
correction range and ramp, the score scale, the router's published order and
the chip's share, the prediction module's targets.  The whole model, program
against reference: ``tests/test_xing4.py``."""

import json
import math
import os

import numpy as np
import pytest

from perfbench.harness import manifest
from perfbench.harness.families import xing4

CONFIG = json.load(open(os.path.join(
    manifest.BENCH_DIR, "configs", "xing4.0-29b-a4b.json")))


def test_the_cut_and_the_share():
    assert xing4.n_experts(CONFIG) == 64 and xing4.held(CONFIG) == (0, 8)
    assert xing4.held(dict(CONFIG, deployment={"this_chip": 3})) == (24, 8)
    assert [xing4.is_dense(CONFIG, i) for i in range(5)] == [
        True, False, False, False, False]
    assert xing4.shared_width(CONFIG) == 1024
    assert xing4.score_width(CONFIG) == 192
    assert xing4.attention_params(CONFIG) == 28_411_136 - 768 - 512 \
        == 28_409_856


def test_yarn_is_the_published_formula():
    assert xing4.find_correction_range(32, 1, 64, 10000.0, 4096) == (10, 23)
    inv = xing4.yarn_inverse_frequencies(CONFIG)
    plain = xing4.yarn_inverse_frequencies(CONFIG, plain=True)
    assert inv.shape == (32,)
    np.testing.assert_allclose(plain, 10000.0 ** (-np.arange(32) / 32),
                               rtol=1e-6)
    np.testing.assert_allclose(inv[:11], plain[:11], rtol=1e-6)
    np.testing.assert_allclose(inv[23:], plain[23:] / 64, rtol=1e-6)
    # the ramp between: dimension 10 + j is j / 13 of the way
    for j in (1, 6, 12):
        ramp = j / 13
        assert inv[10 + j] == pytest.approx(
            plain[10 + j] * ((1 - ramp) + ramp / 64), rel=1e-5)
    m = 0.1 * math.log(64) + 1
    assert xing4.yarn_m(1, 64) == pytest.approx(m) and m == pytest.approx(
        1.41589, rel=1e-5)
    assert xing4.table_factor(CONFIG) == 1.0
    assert xing4.score_scale(CONFIG) == pytest.approx(
        192 ** -0.5 * 2.0047, rel=1e-4)


def test_the_rotation_turns_pairs_a_half_apart():
    import jax.numpy as jnp

    x = jnp.zeros((1, 1, 3, 4)).at[..., 0].set(1.0)
    out = xing4.rotate(x, np.asarray([0.5, 0.25]))
    for t in range(3):      # lane 0 pairs with lane 2, at 0.5 a position
        np.testing.assert_allclose(
            out[0, 0, t], [math.cos(0.5 * t), 0, math.sin(0.5 * t), 0],
            atol=1e-6)


def test_the_sinkhorn_is_rows_then_columns():
    import jax.numpy as jnp

    config = dict(CONFIG, hc_eps=0.0)
    logits = jnp.log(jnp.asarray([[[1.0, 3.0], [2.0, 2.0]]]))
    once = np.asarray(xing4.sinkhorn(logits, config, 1))[0]
    rows = np.asarray([[0.25, 0.75], [0.5, 0.5]])
    np.testing.assert_allclose(once, rows / rows.sum(0), rtol=1e-6)
    many = np.asarray(xing4.sinkhorn(logits, config, 20))[0]
    np.testing.assert_allclose(many.sum(0), 1.0, atol=1e-6)
    np.testing.assert_allclose(many.sum(1), 1.0, atol=1e-5)
    # the clamp: a logit of 1e4 is exp(30), not inf
    big = np.asarray(xing4.sinkhorn(logits * 1e4, config, 20))
    assert np.all(np.isfinite(big))


def _hc(n=2, c=3, seed=0):
    import jax

    k = jax.random.split(jax.random.PRNGKey(seed), 4)
    width = 2 * n + n * n
    return {"scale": 1.0 + 0.1 * jax.random.normal(k[0], (n * c,)),
            "phi": jax.random.normal(k[1], (n * c, width)),
            "bias": jax.random.normal(k[2], (width,)),
            "alpha": jax.numpy.asarray([0.5, -0.3, 0.7])}


def test_a_sublayer_is_the_update_written_out():
    """``X <- H_res X + H_post^T F(H_pre X)`` at n = 2, C = 3, one position
    at a time in numpy."""
    import jax

    n, c = 2, 3
    config = dict(CONFIG, hc_mult=n, hidden_size=c)
    p = _hc(n, c)
    X = jax.random.normal(jax.random.PRNGKey(9), (1, 4, n, c))
    got = np.asarray(xing4.sublayer(X, p, lambda u: 2.0 * u + 1.0, config))
    sig = lambda a: 1.0 / (1.0 + np.exp(-a))  # noqa: E731
    for t in range(4):
        x = np.asarray(X[0, t], np.float64)
        flat = x.reshape(-1)
        flat = flat / np.sqrt(np.mean(flat ** 2) + config["rms_norm_eps"]) \
            * np.asarray(p["scale"])
        z = flat @ np.asarray(p["phi"])
        bias, alpha = np.asarray(p["bias"]), np.asarray(p["alpha"])
        pre = sig(alpha[0] * z[:n] + bias[:n])
        post = 2 * sig(alpha[1] * z[n:2 * n] + bias[n:2 * n])
        m = np.exp((alpha[2] * z[2 * n:] + bias[2 * n:]).reshape(n, n))
        for _ in range(20):
            m = m / (m.sum(1, keepdims=True) + 1e-6)
            m = m / (m.sum(0, keepdims=True) + 1e-6)
        f = 2.0 * (pre @ x) + 1.0
        np.testing.assert_allclose(got[0, t], m @ x + post[:, None] * f,
                                   rtol=2e-4, atol=2e-5)
    pre, post, res = xing4.coefficients(X, p, config)
    assert pre.shape == (1, 4, n) and res.shape == (1, 4, n, n)
    assert float(post.max()) <= 2.0 and float(pre.max()) <= 1.0
    half = xing4.coefficients(X, p, config, "post_without_2")[1]
    np.testing.assert_allclose(2 * half, post, rtol=1e-6)
    eye = xing4.coefficients(X, p, config, "res_identity")[2]
    np.testing.assert_allclose(eye[0, 0], np.eye(n))


def test_the_router_in_the_published_order_and_the_share():
    import jax
    import jax.numpy as jnp

    config = {"num_experts_per_tok": 2, "norm_topk_prob": True,
              "routed_scaling_factor": 2, "n_routed_experts": 2,
              "published_counts": {"n_routed_experts": 4}}
    k = jax.random.split(jax.random.PRNGKey(3), 8)
    m = {"router": {"kernel": jnp.eye(4)},
         "selection_bias": jnp.asarray([0.0, 0.0, 0.0, 0.6]),
         "gate_proj": jax.random.normal(k[1], (2, 4, 3)),
         "up_proj": jax.random.normal(k[2], (2, 4, 3)),
         "down_proj": jax.random.normal(k[3], (2, 3, 4)),
         "shared": {name: {"kernel": jax.random.normal(key, shape)}
                    for name, key, shape in (
                        ("gate_proj", k[4], (4, 3)), ("up_proj", k[5], (4, 3)),
                        ("down_proj", k[6], (3, 4)))}}
    y = jnp.asarray([[[2.0, 0.0, 1.0, 0.5]]])
    s = 1.0 / (1.0 + np.exp(-np.asarray([2.0, 0.0, 1.0, 0.5])))
    routed, shared, chosen = xing4.sparse_parts(y, m, config, 2)
    # by s + bias expert 3 (0.62 + 0.6) passes expert 2 (0.73); its weight is
    # its score, over the chosen scores' sum, times 2
    assert chosen.tolist() == [[[1.0, 0.0, 0.0, 1.0]]]
    w3 = 2 * s[3] / (s[0] + s[3])
    silu = lambda a: a / (1.0 + np.exp(-a))  # noqa: E731
    yv = np.asarray(y[0, 0])
    want = w3 * ((silu(yv @ np.asarray(m["gate_proj"][1]))
                  * (yv @ np.asarray(m["up_proj"][1])))
                 @ np.asarray(m["down_proj"][1]))
    np.testing.assert_allclose(routed[0, 0], want, rtol=1e-5, atol=1e-6)
    # (expert 2, held and not chosen, adds nothing; expert 0, chosen and
    # absent, is left out)
    np.testing.assert_allclose(shared[0, 0], xing4.swiglu(y, m["shared"])[0, 0])
    top1 = xing4.sparse_parts(y, m, config, 2, "top_3")[2]
    assert float(top1.sum()) == 1.0
    unscaled = xing4.sparse_parts(y, m, config, 2, "routed_scale_1")[0]
    np.testing.assert_allclose(2 * unscaled, routed, rtol=1e-6)


def test_the_module_scores_the_token_two_ahead():
    import jax.numpy as jnp

    vocab = 5
    config = dict(CONFIG, vocab_size=vocab, mtp_lambda=0.3)
    targets = jnp.asarray([[1, 2, 3, 4]])       # ids 0 1 2 3 rolled left
    hot = lambda ids: 20.0 * jnp.eye(vocab)[jnp.asarray(ids)][None]  # noqa
    out = hot([1, 2, 3, 4])                     # main: exactly right
    ahead = hot([2, 3, 4, 0])                   # module: the token after
    total, main, mtp = xing4.losses(out, ahead, targets, config)
    assert float(main) < 1e-6 and float(mtp) < 1e-6
    # the same module scored against the next token is wrong everywhere
    _, _, off = xing4.losses(out, ahead, targets, config, "mtp_scores_next")
    assert float(off) > 10
    total, main, mtp = xing4.losses(out, hot([0, 0, 0, 0]), targets, config)
    assert float(total) == pytest.approx(float(main) + 0.3 * float(mtp))
    whole, _, _ = xing4.losses(out, hot([0, 0, 0, 0]), targets, config,
                               "lambda_1")
    assert float(whole) == pytest.approx(float(main) + float(mtp))
    assert float(xing4.losses(out, None, targets, config)[2]) == 0.0

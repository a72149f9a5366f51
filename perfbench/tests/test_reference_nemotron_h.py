"""The ``nemotron_h`` family's plain reference, piece by piece against values
written out by hand — the recurrence position by position and in blocks, the
convolution's taps, the norm a group, key/value head ``h // 16``, the router's
published order, the squared ReLU without a gate and the chip's share.  The
whole model, program against reference: ``tests/test_nemotron_h.py``."""

import json
import os

import numpy as np
import pytest

from perfbench.harness import manifest
from perfbench.harness.families import nemotron_h

CONFIG = json.load(open(os.path.join(
    manifest.BENCH_DIR, "configs", "nemotron-3-nano-30b-a3b.json")))


def test_the_pattern_and_the_share():
    assert nemotron_h.pattern(CONFIG) == "MEMEM*EME"
    assert nemotron_h.n_experts(CONFIG) == 128
    assert nemotron_h.held(CONFIG) == (0, 8)
    assert nemotron_h.held(dict(CONFIG, deployment={"this_chip": 3})) \
        == (24, 8)
    whole = CONFIG["published_counts"]["hybrid_override_pattern"]
    assert (len(whole), whole.count("M"), whole.count("E"),
            whole.count("*")) == (52, 23, 23, 6)
    assert [i for i, c in enumerate(whole) if c == "*"] == [
        5, 12, 19, 26, 33, 42]
    # the model whole: 23 M, 6 *, 23 E layers of 128 experts, the whole head
    uncut = dict(CONFIG, num_hidden_layers=52, hybrid_override_pattern=whole,
                 n_routed_experts=128, vocab_size=131072)
    assert nemotron_h.n_params(uncut) == pytest.approx(31.58e9, rel=1e-3)


def test_the_recurrence_is_the_sum_written_out():
    """``y_t = sum_{s <= t} (prod_{s < r <= t} a_r) dt_s (c_t . b_s) x_s``,
    one head of width 2 over a state of 3, and whole blocks give what one
    block gives."""
    import jax
    import jax.numpy as jnp

    k = jax.random.split(jax.random.PRNGKey(0), 5)
    x = jax.random.normal(k[0], (1, 6, 1, 2))
    dt = jax.nn.softplus(jax.random.normal(k[1], (1, 6, 1)))
    log_a = -dt * 0.7
    b, c = (jax.random.normal(key, (1, 6, 1, 3)) for key in k[2:4])
    got = np.asarray(nemotron_h.recurrence(x, dt, log_a, b, c))
    want = np.zeros((6, 2))
    for t in range(6):
        for s in range(t + 1):
            decay = np.exp(float(jnp.sum(log_a[0, s + 1:t + 1, 0])))
            want[t] += decay * float(dt[0, s, 0]) * float(
                jnp.dot(c[0, t, 0], b[0, s, 0])) * np.asarray(x[0, s, 0])
    np.testing.assert_allclose(got[0, :, 0], want, rtol=1e-5, atol=1e-6)
    try:
        nemotron_h.SCAN_BLOCK = 2
        blocks = np.asarray(nemotron_h.recurrence(x, dt, log_a, b, c))
    finally:
        nemotron_h.SCAN_BLOCK = 64
    np.testing.assert_allclose(blocks, got, rtol=1e-6, atol=1e-7)


def _toy_mamba(groups=2):
    return {"mamba_num_heads": 4, "mamba_head_dim": 2, "n_groups": groups,
            "ssm_state_size": 3, "conv_kernel": 4, "chunk_size": 4,
            "norm_eps": 1e-12}


def _mamba_params(config, d=5, seed=1):
    import jax

    heads, p, groups, n = nemotron_h.mamba_sizes(config)
    inner, xbc = heads * p, heads * p + 2 * groups * n
    k = jax.random.split(jax.random.PRNGKey(seed), 8)
    return {"in_proj": {"kernel": jax.random.normal(k[0], (d, inner + xbc
                                                           + heads))},
            "conv_kernel": jax.random.normal(k[1], (4, xbc)),
            "conv_bias": jax.random.normal(k[2], (xbc,)),
            "dt_bias": jax.random.normal(k[3], (heads,)),
            "A_log": jax.random.normal(k[4], (heads,)),
            "D": jax.random.normal(k[5], (heads,)),
            "norm_scale": 1.0 + 0.1 * jax.random.normal(k[6], (inner,)),
            "out_proj": {"kernel": jax.numpy.eye(inner)}}


def test_the_mixer_reads_no_later_position_and_norms_each_group_apart():
    import jax
    import jax.numpy as jnp

    config = _toy_mamba()
    p = _mamba_params(config)
    n = jax.random.normal(jax.random.PRNGKey(2), (1, 7, 5))
    out = nemotron_h.mamba(p, n, config)
    # causal: the first four outputs do not see positions 4 to 6
    again = nemotron_h.mamba(p, n.at[:, 4:].set(0.0), config)
    np.testing.assert_allclose(out[:, :4], again[:, :4], rtol=1e-6)
    assert float(jnp.max(jnp.abs(out[:, 4:] - again[:, 4:]))) > 1e-3
    # with an identity for out_proj: each group's four channels, their
    # scale divided out, have a mean square of one, and all eight have not
    unit = np.asarray(out / p["norm_scale"]).reshape(1, 7, 2, 4)
    np.testing.assert_allclose(np.mean(unit ** 2, -1), 1.0, rtol=1e-3)
    over_all = np.asarray(nemotron_h.mamba(p, n, config, "norm_over_all")
                          / p["norm_scale"])
    np.testing.assert_allclose(np.mean(over_all ** 2, -1), 1.0, rtol=1e-3)
    assert np.max(np.abs(over_all.reshape(1, 7, 2, 4) - unit)) > 1e-2
    # head j reads group j // 2: one group for all heads is another model
    assert float(jnp.max(jnp.abs(
        nemotron_h.mamba(p, n, config, "one_bc_group") - out))) > 1e-3
    one = _toy_mamba(groups=1)      # (and with one group it is the model)
    p1 = _mamba_params(one)
    assert float(jnp.max(jnp.abs(nemotron_h.mamba(p1, n, one, "one_bc_group")
                                 - nemotron_h.mamba(p1, n, one)))) == 0.0


def test_the_convolution_is_four_shifted_multiply_adds():
    import jax.numpy as jnp

    x = jnp.arange(1.0, 6.0)[None, :, None]
    assert nemotron_h._delayed(x, 2)[0, :, 0].tolist() == [0, 0, 1, 2, 3]
    assert nemotron_h._delayed(x, 0) is x


def test_a_query_head_reads_key_value_head_h_over_16():
    """Values that name their key/value head: with one key a query its
    output is its head's value, whatever the query."""
    import jax.numpy as jnp

    config = {"num_attention_heads": 32, "num_key_value_heads": 2,
              "head_dim": 4, "rope_theta": 10000}
    p = {"wq": {"kernel": jnp.zeros((4, 32 * 4))},
         "wk": {"kernel": jnp.zeros((4, 2 * 4))},
         # key/value head g's values are g + 1 in every lane
         "wv": {"kernel": jnp.concatenate(
             [jnp.full((4, 4), 0.25 * (g + 1)) for g in range(2)], axis=1)},
         "wo": {"kernel": jnp.eye(32 * 4)}}
    out = nemotron_h.attention(p, jnp.ones((1, 1, 4)), config)
    assert out.reshape(32, 4)[:, 0].tolist() == [
        float(h // 16 + 1) for h in range(32)]
    wrong = nemotron_h.attention(p, jnp.ones((1, 1, 4)), config,
                                 "kv_head_mod")
    assert wrong.reshape(32, 4)[:, 0].tolist() == [
        float(h % 2 + 1) for h in range(32)]


def test_the_router_in_the_published_order():
    import jax.numpy as jnp

    config = {"num_experts_per_tok": 2, "norm_topk_prob": True,
              "routed_scaling_factor": 2.5,
              "published_counts": {"n_routed_experts": 4}}
    r = jnp.asarray([[2.0, 0.0, 1.0, -1.0]])
    s = 1.0 / (1.0 + np.exp(-np.asarray([2.0, 0.0, 1.0, -1.0])))
    weight, chosen = nemotron_h.routing(r, jnp.zeros(4), config)
    np.testing.assert_allclose(
        weight, [[2.5 * s[0] / (s[0] + s[2]), 0.0,
                  2.5 * s[2] / (s[0] + s[2]), 0.0]], rtol=1e-6)
    assert chosen.tolist() == [[1.0, 0.0, 1.0, 0.0]]
    # the bias moves the choice and not the weights: expert 3 for expert 2
    weight, chosen = nemotron_h.routing(
        r, jnp.asarray([0.0, 0.0, 0.0, 0.6]), config)
    assert chosen.tolist() == [[1.0, 0.0, 0.0, 1.0]]
    np.testing.assert_allclose(
        weight, [[2.5 * s[0] / (s[0] + s[3]), 0.0, 0.0,
                  2.5 * s[3] / (s[0] + s[3])]], rtol=1e-6)
    by_scores, _ = nemotron_h.routing(r, jnp.asarray([0.0, 0.0, 0.0, 0.6]),
                                      config, "choice_without_bias")
    assert float(by_scores[0, 2]) > 0 and float(by_scores[0, 3]) == 0
    unscaled, _ = nemotron_h.routing(r, jnp.zeros(4), config,
                                     "no_routed_scale")
    np.testing.assert_allclose(float(jnp.sum(unscaled)), 1.0, rtol=1e-6)


def test_the_held_experts_have_no_gate_and_the_absent_are_left_out():
    import jax
    import jax.numpy as jnp

    config = {"num_experts_per_tok": 2, "norm_topk_prob": True,
              "routed_scaling_factor": 2.5, "n_routed_experts": 2,
              "published_counts": {"n_routed_experts": 4},
              "deployment": {"this_chip": 1}}
    k = jax.random.split(jax.random.PRNGKey(3), 6)
    p = {"router": {"kernel": jax.random.normal(k[0], (5, 4))},
         "selection_bias": jnp.zeros(4),
         "up_proj": jax.random.normal(k[1], (2, 5, 3)),
         "down_proj": jax.random.normal(k[2], (2, 3, 5)),
         "shared": {"up_proj": {"kernel": jax.random.normal(k[3], (5, 6))},
                    "down_proj": {"kernel": jax.random.normal(k[4], (6, 5))}}}
    assert "gate_proj" not in p
    n = jax.random.normal(k[5], (1, 8, 5))
    out, rows = nemotron_h.experts(p, n, config)
    weight, chosen = nemotron_h.routing(n @ p["router"]["kernel"],
                                        p["selection_bias"], config)
    want = np.zeros((8, 5))
    for t in range(8):
        for e in (2, 3):    # chip 1 of 2 holds experts 2 and 3
            a = np.maximum(np.asarray(n[0, t] @ p["up_proj"][e - 2]), 0.0)
            want[t] += float(weight[0, t, e]) * np.asarray(
                (a * a) @ p["down_proj"][e - 2])
        a = np.maximum(np.asarray(n[0, t] @ p["shared"]["up_proj"]["kernel"]),
                       0.0)
        want[t] += np.asarray((a * a) @ p["shared"]["down_proj"]["kernel"])
    np.testing.assert_allclose(out[0], want, rtol=1e-4, atol=1e-5)
    assert float(rows) == float(jnp.sum(chosen[..., 2:]))
    assert 0 < float(rows) < 16

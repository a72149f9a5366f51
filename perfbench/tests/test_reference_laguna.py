"""The ``laguna`` family's plain reference, piece by piece against values
written out by hand — the two masks, the two rotary tables, the sigmoid
router with its scale, the chip's share — and the program's own rotary table
against it.  The whole model, program against reference:
``tests/test_laguna.py``."""

import json
import math
import os

import numpy as np
import pytest

from perfbench.harness import manifest
from perfbench.harness.families import laguna

CONFIG = json.load(open(os.path.join(
    manifest.BENCH_DIR, "configs", "laguna-xs.2.json")))
FULL = CONFIG["rope_parameters"]["full_attention"]
SLIDING = CONFIG["rope_parameters"]["sliding_attention"]


def test_the_two_masks():
    full, band = np.asarray(laguna.seen(6, None)), \
        np.asarray(laguna.seen(6, 3))
    assert full.sum() == 21 and np.array_equal(full, np.tril(np.ones((6, 6))))
    # a query sees itself and the two positions before it
    assert band.tolist() == [[1, 0, 0, 0, 0, 0], [1, 1, 0, 0, 0, 0],
                             [1, 1, 1, 0, 0, 0], [0, 1, 1, 1, 0, 0],
                             [0, 0, 1, 1, 1, 0], [0, 0, 0, 1, 1, 1]]
    assert int(np.asarray(laguna.seen(64, 8)).sum()) \
        == laguna.band_pairs(64, 8) == 8 * 9 // 2 + 56 * 8


def test_the_sliding_table_is_plain_rope():
    inv = np.asarray(laguna.inverse_frequencies(SLIDING, 128))
    assert inv.shape == (64,)
    np.testing.assert_allclose(inv, 1e4 ** (-np.arange(64) / 64), rtol=1e-6)


def test_the_full_table_is_yarn_over_half_a_head():
    inv = np.asarray(laguna.inverse_frequencies(FULL, 64))
    plain = 5e5 ** (-np.arange(32) / 32)
    # the dimension that turns 64 times in 4,096 positions is 5.66, the one
    # that turns once 15.8: 0 to 5 kept, 16 to 31 divided by 64, a ramp of
    # elevenths between
    assert 64 * math.log(4096 / (64 * 2 * math.pi)) / (2 * math.log(5e5)) \
        == pytest.approx(5.66, abs=0.01)
    assert 64 * math.log(4096 / (2 * math.pi)) / (2 * math.log(5e5)) \
        == pytest.approx(15.80, abs=0.01)
    np.testing.assert_allclose(inv[:6], plain[:6], rtol=1e-6)
    np.testing.assert_allclose(inv[16:], plain[16:] / 64, rtol=1e-6)
    np.testing.assert_allclose(
        inv[10], plain[10] * (6 / 11) + plain[10] / 64 * (5 / 11), rtol=1e-5)
    assert np.all(np.diff(inv) < 0)


def test_rotary_turns_a_part_of_the_head_and_scales_it():
    import jax
    import jax.numpy as jnp

    x = jax.random.normal(jax.random.PRNGKey(0), (1, 2, 9, 128), jnp.float32)
    y = np.asarray(laguna.rotary(x, FULL))
    x = np.asarray(x)
    # the second half of a head passes through; the first keeps its length,
    # times attention_factor; position 0 is not turned
    np.testing.assert_array_equal(y[..., 64:], x[..., 64:])
    factor = FULL["attention_factor"]
    np.testing.assert_allclose(np.linalg.norm(y[..., :64], axis=-1),
                               factor * np.linalg.norm(x[..., :64], axis=-1),
                               rtol=1e-5)
    np.testing.assert_allclose(y[..., 0, :64], factor * x[..., 0, :64],
                               rtol=1e-6)
    # the pair (i, i + 32) turns by position * inv[i]
    inv = np.asarray(laguna.inverse_frequencies(FULL, 64))
    angle = 7 * inv[3]
    np.testing.assert_allclose(
        y[0, 0, 7, 3], factor * (x[0, 0, 7, 3] * math.cos(angle)
                                 - x[0, 0, 7, 35] * math.sin(angle)),
        rtol=1e-4, atol=1e-5)
    z = np.asarray(laguna.rotary(jnp.asarray(x), SLIDING))
    assert not np.allclose(z[..., 1:, 64:], x[..., 1:, 64:])
    np.testing.assert_allclose(np.linalg.norm(z, axis=-1),
                               np.linalg.norm(x, axis=-1), rtol=1e-5)


def test_the_program_builds_the_same_tables():
    import jax.numpy as jnp

    from ray_tpu.models.llama import rope_table

    cfg = laguna.model_config(CONFIG, 1)
    assert cfg.n_head_per_layer == (48, 64, 64, 64, 48)
    assert cfg.mlp_types == ("dense",) + ("sparse",) * 4
    positions = jnp.arange(8192)
    for kind, table in cfg.rope_tables:
        p = CONFIG["rope_parameters"][kind]
        rot = int(128 * p["partial_rotary_factor"])
        cos, sin = rope_table(128, positions, table)
        angle = np.arange(8192)[:, None] * np.asarray(
            laguna.inverse_frequencies(p, rot), np.float64)[None, :]
        scale = p.get("attention_factor", 1.0)
        assert cos.shape == (8192, rot // 2)
        # float32 angles of up to 8,192 radians: 1e-3 is their rounding
        np.testing.assert_allclose(cos, scale * np.cos(angle), atol=2e-3)
        np.testing.assert_allclose(sin, scale * np.sin(angle), atol=2e-3)


def test_the_router_scores_scales_and_shares():
    import jax
    import jax.numpy as jnp

    toy = json.load(open(os.path.join(os.path.dirname(
        os.path.abspath(__file__)), "toy", "toy-laguna.json")))
    keys = jax.random.split(jax.random.PRNGKey(2), 8)
    d, f, e = 64, 32, 16
    m = {"router": {"kernel": jax.random.normal(keys[0], (d, e))},
         "gate_proj": jax.random.normal(keys[1], (e, d, f)) * 0.1,
         "up_proj": jax.random.normal(keys[2], (e, d, f)) * 0.1,
         "down_proj": jax.random.normal(keys[3], (e, f, d)) * 0.1,
         "shared": {n: {"kernel": jax.random.normal(k, s) * 0.1}
                    for n, k, s in (("gate_proj", keys[4], (d, f)),
                                    ("up_proj", keys[5], (d, f)),
                                    ("down_proj", keys[6], (f, d)))}}
    y = jax.random.normal(keys[7], (1, 5, d))
    whole = dict(toy, num_experts=16)
    routed, shared, chosen = laguna.sparse_parts(y, m, whole, 0)
    assert np.asarray(chosen).sum(-1).tolist() == [[4.0] * 5]
    # by hand for one token: sigmoid scores, the top four divided by their
    # sum, times 2.5, each on its expert's SwiGLU
    t = np.asarray(y[0, 2], np.float64)
    score = 1 / (1 + np.exp(-t @ np.asarray(m["router"]["kernel"], np.float64)))
    top = np.argsort(score)[-4:]
    assert set(top) == set(np.flatnonzero(np.asarray(chosen[0, 2])))
    want = np.zeros(d)
    for i in top:
        g, u, dn = (np.asarray(m[n][i], np.float64)
                    for n in ("gate_proj", "up_proj", "down_proj"))
        a = t @ g
        want += 2.5 * score[i] / score[top].sum() \
            * ((a / (1 + np.exp(-a))) * (t @ u)) @ dn
    np.testing.assert_allclose(routed[0, 2], want, rtol=2e-3, atol=1e-4)
    # a share's part has the held experts' terms alone, under the same
    # weights; the shared expert does not depend on the share
    mine = dict(m, **{n: m[n][4:8] for n in ("gate_proj", "up_proj",
                                             "down_proj")})
    part, shared_again, _ = laguna.sparse_parts(y, mine, toy, 4)
    np.testing.assert_allclose(shared_again, shared, rtol=1e-6)
    assert float(jnp.max(jnp.abs(part))) < float(jnp.max(jnp.abs(routed)))
    for wrong in ("softmax_scores", "routed_scale_1", "top_7",
                  "no_shared_expert"):
        other = laguna.sparse_parts(y, m, whole, 0, wrong)
        assert not np.allclose(other[0] + other[1], routed + shared,
                               atol=1e-3), wrong

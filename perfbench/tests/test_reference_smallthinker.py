"""The ``smallthinker`` family's plain reference, piece by piece against
values written out by hand — the two masks in blocks of queries, rotate-half
at theta 1.5e6, key/value head ``h // 7``, the router's published order, the
ReLU gate and the chip's share — and the program's own rotary table against
it.  The whole model, program against reference:
``tests/test_smallthinker.py``."""

import json
import os

import numpy as np
import pytest

from perfbench.harness import manifest
from perfbench.harness.families import smallthinker

CONFIG = json.load(open(os.path.join(
    manifest.BENCH_DIR, "configs", "smallthinker-21b-a3b-instruct.json")))


def test_the_two_masks_block_by_block():
    full = np.concatenate([np.asarray(smallthinker.seen(at, 3, 6, None))
                           for at in (0, 3)])
    band = np.concatenate([np.asarray(smallthinker.seen(at, 2, 6, 3))
                           for at in (0, 2, 4)])
    assert np.array_equal(full, np.tril(np.ones((6, 6))))
    # a query sees itself and the two positions before it
    assert band.tolist() == [[1, 0, 0, 0, 0, 0], [1, 1, 0, 0, 0, 0],
                             [1, 1, 1, 0, 0, 0], [0, 1, 1, 1, 0, 0],
                             [0, 0, 1, 1, 1, 0], [0, 0, 0, 1, 1, 1]]
    assert int(np.asarray(smallthinker.seen(0, 64, 64, 8)).sum()) \
        == smallthinker.band_pairs(64, 8) == 8 * 9 // 2 + 56 * 8


def test_rotate_half_is_the_programs_table():
    import jax
    import jax.numpy as jnp

    from perfbench.harness.reference import rope
    from ray_tpu.models.llama import RopeTable, apply_rope, rope_table

    x = jax.random.normal(jax.random.PRNGKey(0), (1, 2, 40, 128))
    got = rope(x, 1.5e6)
    # position 0 is not turned; a pair keeps its length
    np.testing.assert_allclose(got[:, :, 0], x[:, :, 0], rtol=1e-6)
    np.testing.assert_allclose(got[..., :64] ** 2 + got[..., 64:] ** 2,
                               x[..., :64] ** 2 + x[..., 64:] ** 2,
                               rtol=1e-4, atol=1e-5)
    # dimension 0 turns by one radian a position, the last pair hardly
    np.testing.assert_allclose(
        got[0, 0, 1, 0], x[0, 0, 1, 0] * np.cos(1) - x[0, 0, 1, 64]
        * np.sin(1), rtol=1e-5)
    cos, sin = rope_table(128, jnp.arange(40), RopeTable(theta=1.5e6))
    np.testing.assert_allclose(apply_rope(x, cos, sin), got, rtol=1e-5,
                               atol=1e-5)


def test_a_query_head_reads_key_value_head_h_over_7():
    """Values that name their key/value head: with one key a query its
    output is its head's value."""
    import jax.numpy as jnp

    q = jnp.zeros((1, 28, 1, 128))
    k = jnp.zeros((1, 4, 1, 128))
    v = jnp.arange(4.0)[None, :, None, None] * jnp.ones((1, 4, 1, 128))
    out = smallthinker.attend(q, k, v, None, lambda h: h // 7)
    assert out[0, :, 0, 0].tolist() == [float(h // 7) for h in range(28)]


def test_the_router_takes_top_k_then_a_softmax_over_the_chosen():
    import jax.numpy as jnp

    config = {"moe_num_active_primary_experts": 2,
              "published_counts": {"moe_num_primary_experts": 4}}
    r = jnp.asarray([[2.0, 0.0, 1.0, -1.0]])
    weight, chosen = smallthinker.routing(r, config)
    e2, e1 = np.exp(2.0), np.exp(1.0)
    np.testing.assert_allclose(
        weight, [[e2 / (e2 + e1), 0.0, e1 / (e2 + e1), 0.0]], rtol=1e-6)
    assert chosen.tolist() == [[1.0, 0.0, 1.0, 0.0]]
    # the weights add up to one over the chosen, held here or not
    assert float(weight.sum()) == pytest.approx(1.0)


def test_the_held_experts_are_relu_gated_and_the_absent_left_out():
    import jax.numpy as jnp

    y = jnp.asarray([[[1.0, -1.0]]])
    m = {"gate_proj": jnp.asarray([[[1.0], [0.0]], [[0.0], [1.0]]]),
         "up_proj": jnp.asarray([[[2.0], [0.0]], [[2.0], [0.0]]]),
         "down_proj": jnp.asarray([[[1.0, 0.0]], [[0.0, 1.0]]])}
    # the chip holds experts 2 and 3 of four; the token chose 2 and 3 alike
    weight = jnp.asarray([[[0.0, 0.0, 0.5, 0.5]]])
    out, zero = smallthinker.held_experts(y, weight, m, 2)
    # expert 2: relu(1) * 2 = 2 -> (2, 0) x 0.5; expert 3: relu(-1) = 0
    assert out.tolist() == [[[1.0, 0.0]]]
    assert float(zero) == 0.5       # one of the two gate units is exactly 0
    # a token that chose neither held expert gets nothing from this chip
    out, _ = smallthinker.held_experts(
        y, jnp.asarray([[[0.5, 0.5, 0.0, 0.0]]]), m, 2)
    assert out.tolist() == [[[0.0, 0.0]]]


def test_the_program_is_filled_from_the_published_keys():
    cfg = smallthinker.model_config(CONFIG, 1)
    assert (cfg.d_model, cfg.n_layer, cfg.n_head, cfg.n_kv_head,
            cfg.head_dim, cfg.vocab_size) == (2560, 4, 28, 4, 128, 38016)
    assert (cfg.n_experts, cfg.moe_top_k, cfg.d_expert, cfg.experts_held) \
        == (64, 6, 768, (0, 16))
    assert cfg.router_before_attention and cfg.expert_activation == "relu"
    assert cfg.norm_topk_prob and cfg.router_scoring == "softmax"
    assert cfg.sliding_window == 4096 and cfg.rms_eps == 1e-6
    assert (cfg.router_aux_weight, cfg.router_z_weight) == (0.0, 0.0)
    tables = dict(cfg.rope_tables)
    assert tables["full_attention"] is None
    assert tables["sliding_attention"].theta == 1.5e6
    with pytest.raises(NotImplementedError):
        smallthinker.model_config(dict(CONFIG, rope_layout=[1, 1, 1, 1]), 1)

"""The ``evabyte`` family's plain reference, piece by piece against values
written out by hand — a chunk's summary, the mask's two index rules, one
query's output past its first window, the unit-offset norm, the eight-head
loss — and the program's own configuration against the file.  The whole
model, program against reference: ``tests/test_evabyte.py``."""

import json
import os

import numpy as np
import pytest

from perfbench.harness import manifest
from perfbench.harness.families import evabyte

CONFIG = json.load(open(os.path.join(
    manifest.BENCH_DIR, "configs", "evabyte.json")))
TOY = json.load(open(os.path.join(os.path.dirname(
    os.path.abspath(__file__)), "toy", "toy-evabyte.json")))


def _softmax(x):
    e = np.exp(x - x.max())
    return e / e.sum()


def _head(seed=0, s=48, d=8):
    import jax

    keys = jax.random.split(jax.random.PRNGKey(seed), 5)
    return [np.asarray(jax.random.normal(k, shape), np.float64)
            for k, shape in zip(keys, [(s, d)] * 3 + [(d,)] * 2)]


def test_one_chunks_summary_by_hand():
    """Chunk 2 of 4 positions: weights from ``s * phi . k`` over its own four
    keys, ``mu`` on the key alone."""
    import jax.numpy as jnp

    q, k, v, phi, mu = _head()
    kt, vt = evabyte.pool(*(jnp.asarray(a, jnp.float32)
                            for a in (k, v, phi, mu)), 4, 0.5)
    assert kt.shape == vt.shape == (12, 8)
    a = _softmax(0.5 * k[8:12] @ phi)
    np.testing.assert_allclose(kt[2], a @ k[8:12] + mu, rtol=1e-4, atol=1e-5)
    np.testing.assert_allclose(vt[2], a @ v[8:12], rtol=1e-4, atol=1e-5)
    wrong = {w: evabyte.pool(*(jnp.asarray(x, jnp.float32)
                               for x in (k, v, phi, mu)), 4, 0.5, w)
             for w in ("no_mu", "mean_pooling", "no_pool_scale")}
    np.testing.assert_allclose(wrong["no_mu"][0][2], a @ k[8:12], rtol=1e-4,
                               atol=1e-5)
    np.testing.assert_allclose(wrong["mean_pooling"][1][2],
                               v[8:12].mean(0), rtol=1e-4, atol=1e-5)
    np.testing.assert_allclose(wrong["no_pool_scale"][1][2],
                               _softmax(k[8:12] @ phi) @ v[8:12], rtol=1e-4,
                               atol=1e-5)


def test_the_masks_two_rules():
    """Windows of 8, chunks of 2, 20 positions: query 13 (window 1) sees the
    4 summaries of window 0 and positions 8 to 13; the wrong models' rules
    beside it."""
    m = np.asarray(evabyte.mask(20, 8, 2))
    assert m.shape == (20, 10 + 20)
    assert list(np.flatnonzero(m[13])) == [0, 1, 2, 3] + [10 + p for p in
                                                         range(8, 14)]
    assert list(np.flatnonzero(m[5])) == [10 + p for p in range(6)]
    assert list(np.flatnonzero(m[16, :10])) == list(range(8))
    own = np.asarray(evabyte.mask(20, 8, 2, "own_window_summaries"))
    assert list(np.flatnonzero(own[13, :10])) == list(range(8))
    by_chunk = np.asarray(evabyte.mask(20, 8, 2, "chunkwise_summaries"))
    assert list(np.flatnonzero(by_chunk[13, :10])) == list(range(6))
    assert (own[:, 10:] == m[:, 10:]).all()
    assert (by_chunk[:, 10:] == m[:, 10:]).all()


def test_one_query_past_its_first_window_by_hand():
    """Query 21 of a row of 48, windows of 16 and chunks of 4: one softmax
    over 4 summaries and positions 16 to 21."""
    import jax.numpy as jnp

    q, k, v, phi, mu = _head(1)
    toy = dict(TOY, window_size=16, chunk_size=4)
    got = np.asarray(evabyte.eva_head(
        *(jnp.asarray(a, jnp.float32) for a in (q, k, v, phi, mu)), toy))
    s = 8 ** -0.5
    kt, vt = [], []
    for j in range(4):
        a = _softmax(s * k[4 * j:4 * j + 4] @ phi)
        kt.append(a @ k[4 * j:4 * j + 4] + mu)
        vt.append(a @ v[4 * j:4 * j + 4])
    keys = np.concatenate([np.stack(kt), k[16:22]])
    values = np.concatenate([np.stack(vt), v[16:22]])
    np.testing.assert_allclose(got[21], _softmax(s * keys @ q[21]) @ values,
                               rtol=1e-4, atol=1e-5)
    # a query of the first window: plain causal attention
    np.testing.assert_allclose(got[5], _softmax(s * k[:6] @ q[5]) @ v[:6],
                               rtol=1e-4, atol=1e-5)


def test_the_norm_has_a_unit_offset():
    import jax.numpy as jnp

    x = np.linspace(-1.0, 2.0, 8)
    g = np.linspace(-0.5, 0.5, 8)
    want = x / np.sqrt((x * x).mean() + 1e-5) * (1 + g)
    got = evabyte.norm(jnp.asarray(x, jnp.float32),
                       {"scale": jnp.asarray(g, jnp.float32)}, TOY)
    np.testing.assert_allclose(got, want, rtol=1e-5)
    without = evabyte.norm(jnp.asarray(x, jnp.float32),
                           {"scale": jnp.asarray(g, jnp.float32)}, TOY,
                           "no_unit_offset")
    np.testing.assert_allclose(without, want / (1 + g) * g, rtol=1e-5,
                               atol=1e-7)


def test_the_eight_head_loss_by_hand():
    import jax
    import jax.numpy as jnp

    toy = dict(TOY, num_pred_heads=3, vocab_size=5)
    out = np.asarray(jax.random.normal(jax.random.PRNGKey(2), (1, 6, 15)),
                     np.float64)
    ids = np.array([[1, 4, 0, 2, 3, 1]])
    targets = np.roll(ids, -1, axis=1)
    terms = []
    for r in range(3):
        for t in range(6 - r):
            z = out[0, t, 5 * r:5 * r + 5]
            terms.append(np.log(np.exp(z).sum()) - z[targets[0, t + r]])
    assert len(terms) == 6 + 5 + 4
    got = evabyte.heads_loss(jnp.asarray(out, jnp.float32),
                             jnp.asarray(targets), toy)
    assert float(got) == pytest.approx(np.mean(terms), rel=1e-5)
    assert float(evabyte.heads_loss(
        jnp.asarray(out, jnp.float32), jnp.asarray(targets), toy,
        "heads_next_byte")) != pytest.approx(np.mean(terms), rel=1e-3)


def test_the_programs_configuration_is_the_files():
    import jax.numpy as jnp

    cfg = evabyte.model_config(CONFIG, 1)
    assert (cfg.d_model, cfg.n_layer, cfg.n_head, cfg.n_kv_head, cfg.d_ff) \
        == (4096, 4, 32, 32, 11008)
    assert (cfg.vocab_size, cfg.n_pred_heads) == (320, 8)
    assert (cfg.eva_window, cfg.eva_chunk) == (2048, 16)
    assert cfg.rope_theta == 100000.0 and cfg.rms_eps == 1e-5
    assert cfg.norm_unit_offset and not cfg.tie_embeddings
    assert cfg.residual_dtype == cfg.logits_dtype == jnp.float32
    assert cfg.dtype == jnp.bfloat16 and cfg.attention_impl == "flash"
    assert cfg.remat and cfg.remat_policy == "full"
    assert evabyte.PRECISION_BELOW not in evabyte.WRONG
    assert set(evabyte.UNSEEN_IN_BF16) <= set(evabyte.WRONG)

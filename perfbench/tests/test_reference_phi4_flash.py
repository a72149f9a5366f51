"""The ``phi4_flash`` family's plain reference, piece by piece against values
written out by hand in float64 — one channel's selective-scan recurrence with
its decay a state and its own step size, the convolution's taps, the gate;
one query head pair's two softmaxes under the window, lam, the subtraction and
the sub-layer norm; the gated memory unit — and the program's own
configuration against the file.  The whole model, program against reference:
``tests/test_phi4_flash.py``."""

import json
import math
import os

import numpy as np
import pytest

from perfbench.harness import manifest
from perfbench.harness.families import phi4_flash

CONFIG = json.load(open(os.path.join(
    manifest.BENCH_DIR, "configs", "phi-4-mini-flash-reasoning.json")))
TOY = json.load(open(os.path.join(os.path.dirname(
    os.path.abspath(__file__)), "toy", "toy-phi4-flash.json")))
MAMBA_WRONG = ("scalar_decay", "head_dt", "no_softplus", "no_skip", "no_conv",
               "gate_before_scan", "m_after_gate")
ATTN_WRONG = ("lam_zero", "lam_lam0", "no_sub_norm", "no_one_minus_lam0",
              "v_one_head", "rope")


def _normal(key, *shape, scale=0.3):
    import jax

    return scale * jax.random.normal(key, shape)


def _mamba(seed=0, e=64, d=128, n=4, rank=4):
    import jax

    k = iter(jax.random.split(jax.random.PRNGKey(seed), 12))
    return {"in_proj": {"kernel": _normal(next(k), e, 2, d)},
            "x_proj": {"kernel": _normal(next(k), d, rank + 2 * n)},
            "dt_proj": {"kernel": _normal(next(k), rank, d)},
            "out_proj": {"kernel": _normal(next(k), d, e)},
            "conv_kernel": _normal(next(k), 4, d, scale=0.5),
            "conv_bias": _normal(next(k), d),
            "dt_bias": _normal(next(k), d),
            "A_log": _normal(next(k), d, n),
            "D": 1.0 + _normal(next(k), d)}


def _attn(seed=0, e=64, h=4, kv=2, d=16, cross=False):
    import jax

    k = iter(jax.random.split(jax.random.PRNGKey(seed), 12))
    p = {"wo": {"kernel": _normal(next(k), h * d, e),
                "bias": _normal(next(k), e)},
         "sub_norm": {"scale": 1.0 + _normal(next(k), 2 * d)}}
    for name in ("lambda_q1", "lambda_k1", "lambda_q2", "lambda_k2"):
        p[name] = _normal(next(k), d)
    name, width = ("wq", h * d) if cross else ("wqkv", (h + 2 * kv) * d)
    p[name] = {"kernel": _normal(next(k), e, width),
               "bias": _normal(next(k), width)}
    return p


def _silu(x):
    return x / (1.0 + np.exp(-x))


def test_one_channel_by_hand():
    """Channel 37 of the toy's mixer over seven positions, every step written
    out: the convolution's four taps and its bias, silu, ``B``, ``C`` and the
    step size from ``x_proj`` and ``dt_proj`` (which need every channel's
    ``u``), ``h_t[j] = exp(D_t A[j]) h_{t-1}[j] + D_t B_t[j] u_t``, the
    read-out, the skip, the gate."""
    import jax

    p = _mamba()
    y = np.asarray(jax.random.normal(jax.random.PRNGKey(9), (1, 7, 64)),
                   np.float64)
    q = jax.tree_util.tree_map(lambda a: np.asarray(a, np.float64), p)
    c, n, rank = 37, 4, 4
    u_all = y[0] @ q["in_proj"]["kernel"][:, 0]             # (7, 128)
    z = y[0] @ q["in_proj"]["kernel"][:, 1]
    conv = np.zeros_like(u_all)
    for t in range(7):
        for k in range(4):              # tap k reads position t - 3 + k
            if t - 3 + k >= 0:
                conv[t] += q["conv_kernel"][k] * u_all[t - 3 + k]
    u = _silu(conv + q["conv_bias"])
    proj = u @ q["x_proj"]["kernel"]
    r, b, cc = proj[:, :rank], proj[:, rank:rank + n], proj[:, rank + n:]
    delta = np.log1p(np.exp(r @ q["dt_proj"]["kernel"] + q["dt_bias"]))
    a = -np.exp(q["A_log"][c])
    h, scanned = np.zeros(n), []
    for t in range(7):
        h = np.exp(delta[t, c] * a) * h + delta[t, c] * b[t] * u[t, c]
        scanned.append(h @ cc[t] + q["D"][c] * u[t, c])
    with jax.default_matmul_precision("highest"):
        out, m = phi4_flash.mamba1(np.asarray(y, np.float32), p, TOY)
    np.testing.assert_allclose(m[0, :, c], scanned, rtol=2e-4, atol=2e-5)
    # and the whole output, from every channel's m through the gate
    gated = np.asarray(m[0], np.float64) * _silu(z)
    np.testing.assert_allclose(out[0], gated @ q["out_proj"]["kernel"],
                               rtol=2e-3, atol=2e-4)


@pytest.mark.parametrize("window", [0, 3])
def test_one_head_pair_by_hand(window):
    """Query heads 2 and 3 (pair 1 of the toy's two): head 2 against key head
    0 and head 3 against key head 1 of key/value group... the toy has 4
    query and 2 key heads, so both pairs read the one pair of key heads and
    the one pair of value heads, 32 wide; each softmax, lam, the subtraction,
    the norm over 32 and ``1 - lam0`` written out over six positions."""
    import jax

    p = _attn()
    y = np.asarray(jax.random.normal(jax.random.PRNGKey(4), (1, 6, 64)),
                   np.float64)
    q = jax.tree_util.tree_map(lambda a: np.asarray(a, np.float64), p)
    qkv = y[0] @ q["wqkv"]["kernel"] + q["wqkv"]["bias"]    # (6, 128)
    heads = qkv[:, :64].reshape(6, 4, 16)
    keys = qkv[:, 64:96].reshape(6, 2, 16)
    values = qkv[:, 96:]                                    # one pair, 32
    depth = 17
    lam0 = 0.8 - 0.6 * math.exp(-0.3 * depth)
    lam = math.exp(q["lambda_q1"] @ q["lambda_k1"]) \
        - math.exp(q["lambda_q2"] @ q["lambda_k2"]) + lam0

    def softmaxed(query_head, key_head):
        out = np.zeros((6, 32))
        for t in range(6):
            seen = [s for s in range(t + 1) if not window or t - s < window]
            scores = np.array([heads[t, query_head] @ keys[s, key_head] / 4.0
                               for s in seen])
            w = np.exp(scores - scores.max())
            out[t] = (w / w.sum()) @ values[seen]
        return out

    pairs = []
    for pair in range(2):       # q1 = heads 0, 2; q2 = heads 1, 3
        o = softmaxed(2 * pair, 0) - lam * softmaxed(2 * pair + 1, 1)
        o = o / np.sqrt((o * o).mean(-1, keepdims=True) + 1e-5) \
            * q["sub_norm"]["scale"] * (1 - lam0)
        pairs.append(o)
    want = np.concatenate(pairs, -1) @ q["wo"]["kernel"] + q["wo"]["bias"]
    with jax.default_matmul_precision("highest"):
        got, kv = phi4_flash.diff_attention(
            np.asarray(y, np.float32), p, TOY, depth, window)
    np.testing.assert_allclose(got[0], want, rtol=2e-3, atol=2e-4)
    np.testing.assert_allclose(kv[0], qkv[:, 64:], rtol=1e-4, atol=1e-5)
    # a cross layer: a query projection alone over those keys and values
    cross = _attn(seed=3, cross=True)
    with jax.default_matmul_precision("highest"):
        other, same = phi4_flash.diff_attention(
            np.asarray(y, np.float32), cross, TOY, depth, window, kv)
    assert same is kv and other.shape == got.shape


def test_the_gated_memory_unit_by_hand():
    import jax

    k = jax.random.split(jax.random.PRNGKey(2), 4)
    p = {"in_proj": {"kernel": _normal(k[0], 64, 128)},
         "out_proj": {"kernel": _normal(k[1], 128, 64)}}
    y, m = (np.asarray(jax.random.normal(key, shape), np.float64)
            for key, shape in ((k[2], (1, 5, 64)), (k[3], (1, 5, 128))))
    q = jax.tree_util.tree_map(lambda a: np.asarray(a, np.float64), p)
    want = (_silu(y @ q["in_proj"]["kernel"]) * m) @ q["out_proj"]["kernel"]
    with jax.default_matmul_precision("highest"):
        got = phi4_flash.gmu(np.float32(y), p, np.float32(m))
    np.testing.assert_allclose(got, want, rtol=2e-3, atol=2e-4)


@pytest.mark.parametrize("wrong", MAMBA_WRONG + ATTN_WRONG)
def test_each_wrong_layer_is_another_function(wrong):
    import jax

    y = jax.random.normal(jax.random.PRNGKey(9), (1, 12, 64))
    with jax.default_matmul_precision("highest"):
        if wrong in MAMBA_WRONG:
            p = _mamba()
            right, other = (phi4_flash.mamba1(y, p, TOY, w)
                            for w in (None, wrong))
            moved = max(float(abs(a - b).max())
                        for a, b in zip(right, other))
        else:
            p = _attn()
            right, other = (phi4_flash.diff_attention(
                y, p, TOY, 17, 0, wrong=w)[0] for w in (None, wrong))
            moved = float(abs(right - other).max())
    assert not moved <= 1e-3


def test_the_programs_configuration_is_the_files():
    import jax.numpy as jnp

    cfg = phi4_flash.model_config(CONFIG, 1)
    assert (cfg.d_model, cfg.n_head, cfg.n_kv_head, cfg.d_ff, cfg.vocab_size
            ) == (2560, 40, 20, 10240, 25088)
    assert cfg.layer_types == (
        "mamba1", "sliding_attention", "mamba1", "full_attention", "gmu",
        "cross_attention")
    assert cfg.layer_depths == (14, 15, 16, 17, 18, 19)
    assert cfg.producers == (2, 3) and cfg.sliding_window == 512
    assert (cfg.norm, cfg.rope, cfg.tie_embeddings, cfg.diff_attn,
            cfg.attn_bias) == ("layer", False, True, True, True)
    assert (cfg.mamba_d_state, cfg.mamba_d_conv, cfg.mamba_chunk
            ) == (16, 4, 256)
    assert cfg.dtype == jnp.bfloat16 and cfg.remat \
        and cfg.remat_policy == "full" and cfg.rms_eps == 1e-5
    assert phi4_flash.sizes(CONFIG) == {
        "e": 2560, "d": 5120, "n": 16, "rank": 160, "taps": 4, "h": 40,
        "kv": 20, "hd": 64}
    assert CONFIG["assumed"]["mamba"]["dt_rank"] == 160
    assert set(phi4_flash.UNSEEN_IN_BF16) <= set(phi4_flash.WRONG)
    # lam0 by the published index, which the cut keeps
    assert 0.8 - 0.6 * math.exp(-0.3 * 14) == pytest.approx(0.7910, abs=1e-4)
    assert 0.8 - 0.6 * math.exp(-0.3 * 19) == pytest.approx(0.7980, abs=1e-4)

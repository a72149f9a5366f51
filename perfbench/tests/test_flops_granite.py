"""``families/granite_hybrid.py::shape`` and ``ssd_work.py`` against counts
made by hand from the published sizes (PR 29).  ``flops.py``'s formula
charges causal attention to ``n_layer`` layers; the family hands it the
number of ``attention`` layers and all layers' matmul parameters over that
number, and this file holds the product to the sum written out."""

import json
import os

import pytest

from perfbench.harness import flops, manifest, ssd_work
from perfbench.harness.families import granite_hybrid

with open(os.path.join(manifest.BENCH_DIR, "configs",
                       "granite-4.0-h-micro.json")) as f:
    GRANITE = json.load(f)

SEQ = 8192
# one Mamba layer's recurrence, forward, one token, chunks of 256: C B^T once
# (one group), then per head the masked product with X (64 wide) and the
# chunk's state and its read-out (64 x 128 each)
SCAN = 2 * 256 * 128 + 64 * (2 * 256 * 64 + 2 * 128 * 64 + 2 * 128 * 64)
# in_proj 2048 x (4096 + 4096 + 128 + 128 + 64), the conv's 4 taps on 4352
# channels, the scan as equivalent parameters, out_proj 4096 x 2048
MIXER = 2048 * 8512 + 4 * 4352 + SCAN // 2 + 4096 * 2048
ATTENTION = 2 * 2048 * 2048 + 2 * 2048 * 512      # wq, wo; wk, wv at 8 heads
SWIGLU = 3 * 2048 * 8192


def test_the_scan_is_4_26_mflop_a_token_a_layer():
    assert SCAN == 4_259_840
    assert granite_hybrid.scan_flops_per_token(GRANITE) == SCAN


def test_six_layers_are_4_05_gflop_a_token():
    assert (MIXER, ATTENTION, SWIGLU) == (27_968_512, 10_485_760, 50_331_648)
    layers = 5 * (MIXER + SWIGLU) + 1 * (ATTENTION + SWIGLU)
    assert layers == 452_318_208
    s = flops.shape(GRANITE, 1)
    # one attention layer in the cut: the formula's attention term is charged
    # once, and that one "layer" holds all six layers' matmul parameters
    assert s["n_layer"] == 1 and s["layer_mm_params"] == layers
    assert (s["n_head"], s["n_kv_head"], s["head_dim"]) == (32, 8, 64)
    n_mm = layers + 2048 * 100352       # the tied table, as the head's matmul
    assert flops.matmul_params(GRANITE, 1) == n_mm == 657_839_104
    want = 6 * n_mm + 6 * 1 * SEQ * 2048
    assert flops.train_flops_per_token(GRANITE, 1, SEQ) == want
    assert want == 4_047_697_920
    # lm_head is 30% of the required FLOPs at this depth, the scans 1.6%
    assert 6 * 2048 * 100352 / want == pytest.approx(0.305, abs=2e-3)
    assert 3 * 5 * SCAN / want == pytest.approx(0.016, abs=1e-3)


def test_the_program_runs_six_layers_whatever_shape_says():
    cfg = granite_hybrid.model_config(GRANITE, 1)
    assert cfg.n_layer == 6
    assert cfg.layer_types == ("mamba",) * 5 + ("attention",)
    assert cfg.layer_types == tuple(GRANITE["layer_types"][:6])
    assert (cfg.d_model, cfg.d_ff, cfg.n_head, cfg.n_kv_head) \
        == (2048, 8192, 32, 8)
    assert (cfg.mamba_n_heads, cfg.mamba_d_head, cfg.mamba_d_state,
            cfg.mamba_n_groups, cfg.mamba_d_conv, cfg.mamba_chunk) \
        == (64, 64, 128, 1, 4, 256)
    assert not cfg.rope and cfg.attn_scale == 1 / 64 and cfg.tie_embeddings
    assert (cfg.embedding_multiplier, cfg.residual_multiplier,
            cfg.logits_scaling) == (12.0, 0.22, 8.0)


def test_scan_step_and_its_roofline():
    step = ssd_work.scan_step(GRANITE, 1, rows=1, seq=SEQ)
    # five layers, forward and backward at twice the forward
    assert step["flops"] == 3 * 5 * SEQ * SCAN
    # a layer forward: X and y 4096 wide, B and C 128, dt 64, bf16; the
    # float32 state of 64 heads x 64 x 128 at each of 32 chunk ends, written
    # and read
    forward = 2 * SEQ * (4096 + 4096 + 128 + 128 + 64) \
        + 2 * 4 * 32 * 64 * 64 * 128
    assert step["bytes"] == 3 * 5 * forward
    least, bound = flops.roofline_seconds(
        step, manifest.peaks()["TPU v5 lite"])
    assert bound == "memory"
    assert least == pytest.approx(5.01e-3, rel=5e-3)

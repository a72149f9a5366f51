"""``families/sdar_moe.py::train_flops_per_token`` and ``bd_work.py`` against
counts made by hand from the published sizes and the cut of
``sdar-bd-s4k-1chip``; the new readers on a synthetic trace."""

import json
import os

import pytest

from perfbench.harness import bd_work, flops, manifest
from perfbench.harness.families import sdar_moe
from perfbench.harness.readers import (held_experts_roofline, kernel_roofline,
                                       measured)
from perfbench.harness.readers.context import Context
from perfbench.harness.trace_reduce import Op, Trace

CELL = manifest.cell("sdar-bd-s4k-1chip")
CONFIG = CELL.config
PEAK = manifest.peaks()["TPU v5 lite"]


def test_sdar_is_3_16_gflop_a_data_token_at_six_layers():
    # wq, wo 2048 x 4096 (32 heads of 128); wk, wv 2048 x 512 (4 heads)
    attn = 2 * 2048 * 4096 + 2 * 2048 * 512
    router, expert = 2048 * 128, 3 * 2048 * 768
    assert (attn, router, expert) == (18_874_368, 262_144, 4_718_592)
    s = sdar_moe.shape(CONFIG, 1)
    assert (s["n_layer"], s["n_held"], s["vocab"], s["block"]) == \
        (6, 16, 18_992, 4)
    assert sdar_moe.held(CONFIG, 1) == (0, 16)
    # both copies pass the layers (attention, router, and top_k * 16 / 128 =
    # one held expert each at balance); the head sees one copy
    matmuls = 6 * (2 * (attn + router) + 2 * 1 * expert) + 2048 * 18_992
    # scores: (L^2 + L B) / L live pairs a token, QK^T and PV, 32 x 128,
    # forward + backward = 3 x 2 x 2 = 12
    scores = 12 * 6 * 32 * 128 * (4096 + 4)
    want = 6 * matmuls + scores
    assert sdar_moe.train_flops_per_token(CONFIG, 1, 4096) == want
    assert want == pytest.approx(3.16e9, rel=2e-3)
    # the shares cut_why states: attention 81% (projections 43, scores 38),
    # the held experts 11, the head 7
    assert 6 * 6 * 2 * attn / want == pytest.approx(0.43, abs=0.005)
    assert scores / want == pytest.approx(0.38, abs=0.005)
    assert 6 * 6 * 2 * expert / want == pytest.approx(0.11, abs=0.005)
    assert 6 * 2048 * 18_992 / want == pytest.approx(0.07, abs=0.005)


def test_state_is_10_3_gb_of_the_chip():
    layer = 18_874_368 + 262_144 + 16 * 4_718_592 + 2 * 2048 + 2 * 128
    assert layer == pytest.approx(94.6e6, rel=1e-3)
    total = 6 * layer + 2 * 19_072 * 2048 + 2048
    assert total == pytest.approx(645.9e6, rel=1e-3)
    assert 16 * total == pytest.approx(10.33e9, rel=2e-3)


def test_block_mask_kernel_work():
    fwd = bd_work.flash_fwd_call(CONFIG, 1, rows=2, seq=4096)
    pairs = 2 * 32 * (4096 * 4096 + 4096 * 4)
    assert fwd["flops"] == 2 * 2 * pairs * 128
    # Q, O at 32 heads and K, V at 4, both copies (8192 positions), bf16
    assert fwd["bytes"] == 2 * 2 * 8192 * 128 * (32 + 32 + 4 + 4)
    assert flops.roofline_seconds(fwd, PEAK)[1] == "compute"
    bwd = bd_work.flash_bwd_call(CONFIG, 1, rows=2, seq=4096)
    assert bwd["flops"] == 5 * 2 * pairs * 128
    # a quarter of the 2L x 2L square and a little: what a causal kernel over
    # 2L positions would count is twice this
    causal_2l = 2 * 2 * 2 * 32 * 8192 * 8192 * 128 / 2
    assert fwd["flops"] / causal_2l == pytest.approx(0.5, abs=1e-3)


def _ctx(measured_values):
    """Two steps on one device: per step one forward attention call of 6 ms,
    its recomputation, one backward call of 9 ms, and three grouped-matmul
    calls of 0.5 ms."""
    ops, t = [], 0.0
    stack = "jit(pretrain_step)/jvp(LlamaLMModel)/"
    back = "jit(pretrain_step)/transpose(jvp(LlamaLMModel))/"
    for step in range(2):
        for i, (path, secs) in enumerate([
                (stack + "h_0/attn/flash_fwd/pallas_call", 6e-3),
                (back + "rematted_computation/h_0/attn/flash_fwd/pallas_call",
                 6e-3),
                (back + "h_0/attn/flash_bwd/flash_bwd/pallas_call", 9e-3),
                (stack + "h_0/moe/experts/jit(gmm)/pallas_call", 5e-4),
                (stack + "h_0/moe/experts/jit(gmm)/pallas_call", 5e-4),
                (back + "h_0/moe/experts/jit(tgmm)/pallas_call", 5e-4)]):
            ops.append(Op(f"call.{step}{i}", "custom-call:tpu_custom_call",
                          path, t, t + secs))
            t += secs
    trace = Trace(ops={0: ops}, spans=[("window", 0.0, t)])
    return Context(CELL, PEAK, dict(measured_values), trace, traced_steps=2)


def _metric(name):
    with open(os.path.join(manifest.BENCH_DIR, "layer_metrics",
                           name + ".json")) as f:
        return json.load(f)


def test_the_new_readers_on_a_synthetic_trace():
    ctx = _ctx({"moe_rows_held": 8192.0})
    fwd = _metric("bd_attn_fwd_roofline")
    assert fwd["reader"] == "kernel_roofline"
    least = bd_work.flash_fwd_call(CONFIG, 1, 2, 4096)["flops"] / 197e12
    # the forward and its recomputation, not the backward
    assert kernel_roofline.read(ctx, **fwd["args"]) == pytest.approx(
        100 * least / 6e-3)
    bwd = _metric("bd_attn_bwd_roofline")
    least = bd_work.flash_bwd_call(CONFIG, 1, 2, 4096)["flops"] / 197e12
    assert kernel_roofline.read(ctx, **bwd["args"]) == pytest.approx(
        100 * least / 9e-3)
    rows = _metric("moe_rows_held_per_step")
    assert measured.read(ctx, **rows["args"]) == 8192.0
    held = _metric("moe_held_experts_roofline")
    # 8192 rows x 2048 x 768 x 2 FLOPs = 25.8 GFLOP = 0.131 ms at the peak;
    # rows in, 16 matrices, result out = 96.5 MB = 0.118 ms at 819 GB/s:
    # compute-bound
    assert held_experts_roofline.read(ctx, **held["args"]) == pytest.approx(
        100 * (2 * 8192 * 2048 * 768 / 197e12) / 5e-4)
    # an eighth of the rows: the 16 matrices' 50 MB dominate, memory-bound
    call_bytes = 2 * (1024 * 2048 + 16 * 2048 * 768 + 1024 * 768)
    assert held_experts_roofline.read(
        _ctx({"moe_rows_held": 1024.0}), **held["args"]) == pytest.approx(
        100 * (call_bytes / PEAK["hbm_bytes_per_s"]) / 5e-4)


def test_a_run_without_the_counter_reports_nothing():
    """The parent's program has no ``moe_rows_held``: the readers give None
    and do not raise, and the line leaves the metrics out."""
    ctx = _ctx({})
    assert measured.read(ctx, key="moe_rows_held") is None
    assert held_experts_roofline.read(
        ctx, **_metric("moe_held_experts_roofline")["args"]) is None
    empty = Context(CELL, PEAK, {"moe_rows_held": 1.0},
                    Trace(ops={0: []}, spans=[("window", 0.0, 1.0)]), 1)
    assert held_experts_roofline.read(
        empty, **_metric("moe_held_experts_roofline")["args"]) is None


def test_every_line_of_the_manifest_is_printable_and_within_200():
    """``test_manifest.py`` holds the cells' ``why`` to one line of 200; the
    driver holds a configuration's ``why``, every ``layer`` and ``source`` and
    each word of ``command`` to the same, and to printable ASCII."""
    bench = manifest.benchmark()
    lines = list(bench["command"])
    for c in bench["configs"]:
        lines += [c["why"], c["source"], c["file"]]
        assert len(c["reduced"]) <= 16
    lines += [w["why"] for w in bench["workloads"]]
    lines += [m["layer"] for m in bench["per_layer"]]
    for text in lines:
        assert 1 <= len(text) <= 200, text
        assert text.isascii() and text.isprintable(), text

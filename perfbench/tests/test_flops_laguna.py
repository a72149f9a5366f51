"""``families/laguna.py::shape`` (what ``flops.train_flops_per_token`` counts
``laguna-s8k-1chip`` from) and ``flash_work.py`` at its sizes against sums written out by
hand from the published sizes and the cut; the five new metrics on a
synthetic trace whose name paths are as the chip's trace prints them."""

import json
import os

import pytest

from perfbench.harness import flash_work, flops, manifest
from perfbench.harness.families import laguna
from perfbench.tests.manifest_lists import on_at_least
from perfbench.harness.readers import kernel_roofline, trace_ops
from perfbench.harness.readers.context import Context
from perfbench.harness.trace_reduce import Op, Trace

CELL = manifest.cell("laguna-s8k-1chip")
CONFIG = CELL.config
PEAK = manifest.peaks()["TPU v5 lite"]
# a row's live pairs under the window: the first 512 queries see 1 .. 512
# keys, the other 7,680 see 512
PAIRS = 512 * 513 // 2 + (8192 - 512) * 512


def test_laguna_is_2_41_gflop_a_token_at_the_cut():
    assert laguna.band_pairs(8192, 512) == PAIRS == 4_063_488
    d = 2048
    # wq, wo at the layer's query heads; wk, wv at 8 heads of 128; the gate
    full = 2 * d * 48 * 128 + 2 * d * 8 * 128 + d * 48
    sliding = 2 * d * 64 * 128 + 2 * d * 8 * 128 + d * 64
    assert (full, sliding) == (29_458_432, 37_879_808)
    dense, expert, router = 3 * d * 8192, 3 * d * 512, d * 256
    # the router, the shared expert, top_k * 32 / 256 = one held expert
    sparse = router + expert + 1 * expert
    # the band of a sliding layer as parameters: 6 FLOPs a parameter a token
    # = QK^T and PV (2 x 2 FLOPs a pair a head dimension), forward + backward
    # (x 3), over the row's 8,192 tokens
    band = 2 * PAIRS * 64 * 128 // 8192
    assert band == 8_126_976
    layers = (full + dense) + 3 * (sliding + band + sparse) + (full + sparse)
    head = d * 12_544
    # the two full layers' causal scores: 12 x seq/2 x 6,144 a token each
    causal = 2 * 12 * 4096 * 6144
    want = 6 * (layers + head) + causal
    s = laguna.shape(CONFIG, 1)
    assert (s["n_layer"], s["d_model"], s["vocab"]) == (6, 2048, 12_544)
    assert 6 * s["n_layer"] * 8192 * s["d_model"] == causal
    got = flops.train_flops_per_token(CONFIG, 1, 8192)
    # layer_mm_params is a floor over the 6: 4 parameters are lost
    assert layers % 6 == 4 and want - got == 6 * 4
    assert want == pytest.approx(2.405e9, rel=1e-3)
    # the shares cut_why states, of the forward's 0.80 GFLOP a token
    assert 6 * 3 * sliding / want == pytest.approx(0.28, abs=0.005)
    assert 6 * 3 * band / want == pytest.approx(0.06, abs=0.005)
    assert 6 * 2 * full / want == pytest.approx(0.15, abs=0.005)
    assert causal / want == pytest.approx(0.25, abs=0.005)
    assert 6 * dense / want == pytest.approx(0.13, abs=0.005)
    assert 6 * head / want == pytest.approx(0.06, abs=0.005)
    assert 6 * 4 * sparse / want == pytest.approx(0.07, abs=0.005)
    # charged the causal triangle, the sliding layers would read 1.4x high
    wrong = want + 6 * 3 * (2 * (8192 * 8193 // 2) * 64 * 128 // 8192 - band)
    assert wrong / want == pytest.approx(1.4, abs=0.05)


def test_state_is_11_1_gb_of_the_chip():
    d, expert = 2048, 3 * 2048 * 512
    attn_full = 2 * d * 6144 + 2 * d * 1024 + d * 48
    attn_sliding = 2 * d * 8192 + 2 * d * 1024 + d * 64
    sparse = d * 256 + expert + 32 * expert
    assert 32 * expert == pytest.approx(100.7e6, rel=1e-3)
    assert 256 * expert * 16 == pytest.approx(12.9e9, rel=2e-3)
    total = (attn_full + 3 * d * 8192) + 3 * (attn_sliding + sparse) \
        + (attn_full + sparse) + 2 * 12_544 * d + 11 * d
    assert total == pytest.approx(691.6e6, rel=1e-4)
    assert 16 * total == pytest.approx(11.07e9, rel=1e-3)


WINDOW = "jit(pretrain_step)/jvp(LlamaLMModel)/h_1/attn/window/flash_fwd/" \
    "flash_fwd/pallas_call"


def test_window_kernel_work():
    fwd = flash_work.fwd_call(CONFIG, 1, rows=2, seq=8192, path=WINDOW)
    assert fwd["flops"] == 2 * 2 * (2 * 64 * PAIRS) * 128
    # Q, O at 64 heads and K, V at 8, bf16
    assert fwd["bytes"] == 2 * 2 * 8192 * 128 * (64 + 64 + 8 + 8)
    assert flops.roofline_seconds(fwd, PEAK)[1] == "compute"
    bwd = flash_work.bwd_call(CONFIG, 1, rows=2, seq=8192, path=WINDOW)
    assert bwd["flops"] == 5 * 2 * (2 * 64 * PAIRS) * 128
    assert bwd["bytes"] == 2 * 2 * 8192 * 128 * (3 * 64 + 4 * 8)
    # an eighth of the causal triangle, less the band's own corner
    causal = 2 * 2 * 2 * 64 * 8192 * 8192 * 128 / 2
    assert fwd["flops"] / causal == pytest.approx(1 / 8, rel=0.04)


def _ctx():
    """Two steps on one device: a sliding and a full layer, each a forward
    call, its recomputation and a backward call, and the new scopes."""
    ops, t = [], 0.0
    stack = "jit(pretrain_step)/jvp(LlamaLMModel)/"
    back = "jit(pretrain_step)/transpose(jvp(LlamaLMModel))/" \
        "jvp(LlamaLMModel)/checkpoint/"
    call, fusion = "custom-call:tpu_custom_call", "fusion"
    for step in range(2):
        for i, (kind, path, secs) in enumerate([
                (call, stack + "h_1/attn/window/flash_fwd/flash_fwd/pallas_call", 4e-3),
                (call, back + "rematted_computation/h_1/attn/window/flash_fwd/flash_fwd/pallas_call", 4e-3),
                (call, back + "h_1/attn/window/flash_bwd/flash_bwd/pallas_call", 8e-3),
                (fusion, back + "h_1/attn/window/flash_bwd/reduce_sum", 1e-3),
                (call, stack + "h_4/attn/flash_fwd/flash_fwd/pallas_call", 14e-3),
                (call, back + "h_4/attn/flash_bwd/flash_bwd/pallas_call", 26e-3),
                (fusion, stack + "h_1/attn/rope/mul", 2e-3),
                (fusion, back + "rematted_computation/h_1/attn/gate/mul", 1e-3),
                (fusion, back + "h_1/attn/wg/dot_general", 5e-4),
                (fusion, stack + "h_1/moe/shared/up_proj/dot_general", 3e-4),
                (fusion, back + "h_1/moe/shared/down_proj/dot_general", 7e-4)]):
            ops.append(Op(f"op.{step}.{i}", kind, path, t, t + secs))
            t += secs
    trace = Trace(ops={0: ops}, spans=[("window", 0.0, t)])
    return Context(CELL, PEAK, {}, trace, traced_steps=2)


def _metric(name):
    with open(os.path.join(manifest.BENCH_DIR, "layer_metrics",
                           name + ".json")) as f:
        return json.load(f)


def test_the_new_metrics_on_a_synthetic_trace():
    ctx = _ctx()
    fwd = _metric("window_attn_fwd_roofline")
    least = flash_work.fwd_call(CONFIG, 1, 2, 8192, WINDOW)["flops"] / 197e12
    # the sliding layer's forward and its recomputation: not its backward,
    # not the full layer's calls
    assert kernel_roofline.read(ctx, **fwd["args"]) == pytest.approx(
        100 * least / 4e-3)
    bwd = _metric("window_attn_bwd_roofline")
    least = flash_work.bwd_call(CONFIG, 1, 2, 8192, WINDOW)["flops"] / 197e12
    assert kernel_roofline.read(ctx, **bwd["args"]) == pytest.approx(
        100 * least / 8e-3)
    ms = {name: trace_ops.read(ctx, **_metric(name)["args"]) for name in (
        "window_attn_ms_per_step", "attn_rope_norm_ms_per_step",
        "moe_shared_ms_per_step", "flash_fwd_ms_per_step",
        "flash_bwd_ms_per_step", "attn_outside_kernels_ms_per_step")}
    # the three kernel calls, not the XLA work around the backward kernel
    assert ms["window_attn_ms_per_step"] == pytest.approx(16.0)
    # attn_gate_rope_ms_per_step's rotation half (that entry went with PR
    # 67; the gate's half, attn_gate_ms_per_step, with PR 71: since PR 62 the
    # multiply is inside the flash kernels and the scope held 0.013 ms a
    # step); what still runs under gate is in the XLA work under attn
    assert ms["attn_rope_norm_ms_per_step"] == pytest.approx(2.0)
    # (the backward's reduce_sum, the rotation, the gate, wg's matmul)
    assert ms["attn_outside_kernels_ms_per_step"] == pytest.approx(
        1.0 + 2.0 + 1.0 + 0.5)
    assert ms["moe_shared_ms_per_step"] == pytest.approx(1.0)
    # the list-less metrics read both kinds of layer as they stand
    assert ms["flash_fwd_ms_per_step"] == pytest.approx(4 + 4 + 14)
    assert ms["flash_bwd_ms_per_step"] == pytest.approx(8 + 1 + 26)


def test_a_program_without_the_scopes_reports_nothing():
    """The parent's program has no window, gate or shared expert: on its
    trace the readers give None and do not raise."""
    path = "jit(pretrain_step)/jvp(LlamaLMModel)/h_0/attn/flash_fwd/" \
        "flash_fwd/pallas_call"
    trace = Trace(ops={0: [Op("op", "custom-call:tpu_custom_call", path, 0.0,
                              1e-3)]}, spans=[("window", 0.0, 1e-3)])
    ctx = Context(CELL, PEAK, {}, trace, traced_steps=1)
    for name in ("window_attn_fwd_roofline", "window_attn_bwd_roofline"):
        assert kernel_roofline.read(ctx, **_metric(name)["args"]) is None
    for name in ("window_attn_ms_per_step", "moe_shared_ms_per_step"):
        assert trace_ops.read(ctx, **_metric(name)["args"]) is None


def test_the_cell_as_the_manifest_has_it():
    bench = manifest.benchmark()
    entry = next(c for c in bench["configs"] if c["name"] == "laguna-xs.2")
    assert entry["reduced"] == CONFIG["reduced"]
    assert CELL.chips == 1 and CELL.traffic["kind"] == "train_loop"
    # PR 35's five by name (attn_gate_rope_ms_per_step is since PR 67 its
    # rotation's half; the gate's went with PR 71); later PRs list the cell
    # under more
    on_at_least(bench, "laguna-s8k-1chip", [
        "window_attn_fwd_roofline", "window_attn_bwd_roofline",
        "window_attn_ms_per_step", "attn_rope_norm_ms_per_step",
        "moe_shared_ms_per_step"])
    # every catalog number stands in the file; the six cut keys beside their
    # published counts
    assert (CONFIG["hidden_size"], CONFIG["head_dim"],
            CONFIG["intermediate_size"], CONFIG["moe_intermediate_size"],
            CONFIG["shared_expert_intermediate_size"],
            CONFIG["num_experts_per_tok"], CONFIG["sliding_window"]) == \
        (2048, 128, 8192, 512, 512, 8, 512)
    assert CONFIG["published_counts"]["num_experts"] == 256
    assert len(CONFIG["num_attention_heads_per_layer"]) == 40
    assert len(CONFIG["layer_types"]) == CONFIG["num_hidden_layers"] == 5
    assert laguna.held(CONFIG) == (0, 32)
    assert CONFIG["reference"]["prefix"] >= 1024

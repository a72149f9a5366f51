"""``families/kimi_vl.py::shape`` (what ``flops.train_flops_per_token`` counts
``kimi-vl-s16k-1chip`` from) and ``mla_work.py`` against sums written out by
hand from the published sizes, the equations of latent attention and the cut;
the four new metrics on a synthetic trace whose name paths are as the chip's
trace prints them."""

import json
import os

import pytest

from perfbench.harness import flops, manifest, mla_work
from perfbench.harness.families import kimi_vl
from perfbench.tests.manifest_lists import on_at_least
from perfbench.harness.readers import kernel_roofline, trace_ops
from perfbench.harness.readers.context import Context
from perfbench.harness.trace_reduce import Op, Trace

CELL = manifest.cell("kimi-vl-s16k-1chip")
CONFIG = CELL.config
PEAK = manifest.peaks()["TPU v5 lite"]
SEQ = 16384
NEW = ["mla_attn_fwd_roofline", "mla_attn_bwd_roofline",
       "mla_latent_ms_per_step", "mla_assemble_ms_per_step"]


def test_kimi_vl_is_3_39_gflop_a_token_at_the_cut():
    d = 2048
    # Wq: 16 heads of 128 + 64; Wdkv: the latent and the shared rotary key;
    # Wukv: 16 heads of 128 + 128 from the latent; Wo from 16 x 128
    attention = d * 16 * 192 + d * (512 + 64) + 512 * 16 * 256 + 16 * 128 * d
    assert attention == 13_762_560
    dense, expert, router = 3 * d * 11_264, 3 * d * 1408, d * 64
    shared = 3 * d * 2816
    # top_k * 8 / 64 = 0.75 held experts a token, at balance
    sparse = router + shared + 6 * 8 * expert // 64
    layers = 6 * attention + dense + 5 * sparse
    head = d * 20_480
    # a layer's causal scores, forward + backward: q.k over 16 x 192 and p.v
    # over 16 x 128, 2 FLOPs a pair a dimension, half the square, x 3
    scores = 6 * 3 * 2 * 16 * (192 + 128) * SEQ // 2
    assert scores == 6 * SEQ * 6 * 2560
    want = 6 * (layers + head) + scores
    s = kimi_vl.shape(CONFIG, 1)
    assert (s["n_layer"], s["d_model"], s["vocab"]) == (7, 2048, 20_480)
    # the formula's second term charges 7 x 2,048 of the 15,360; the other
    # 1,024 x seq ride in layer_mm_params
    assert 6 * s["n_layer"] * SEQ * s["d_model"] + 6 * 1024 * SEQ == scores
    got = flops.train_flops_per_token(CONFIG, 1, SEQ)
    # layer_mm_params is a floor over the 7
    lost = (layers + 1024 * SEQ) % 7
    assert want - got == 6 * lost and lost < 7
    assert want == pytest.approx(3.39e9, rel=2e-3)
    # the shares cut_why states
    assert scores / want == pytest.approx(0.45, abs=0.005)
    assert 6 * 6 * attention / want == pytest.approx(0.15, abs=0.005)
    assert 6 * dense / want == pytest.approx(0.12, abs=0.005)
    assert 6 * 5 * sparse / want == pytest.approx(0.21, abs=0.005)
    assert 6 * head / want == pytest.approx(0.07, abs=0.005)
    # at 8,192 positions the scores would be 29% and attention 47%
    at_8k = want - scores / 2
    assert scores / 2 / at_8k == pytest.approx(0.29, abs=0.005)
    assert (scores / 2 + 6 * 6 * attention) / at_8k == pytest.approx(
        0.47, abs=0.005)
    assert (scores + 6 * 6 * attention) / want == pytest.approx(0.59,
                                                                abs=0.005)
    # padded to 256 everywhere the scores alone would be charged 1.6 x
    assert (256 + 256) / (192 + 128) == 1.6


def test_state_is_10_7_gb_of_the_chip():
    d, expert = 2048, 3 * 2048 * 1408
    attention = d * 3072 + d * 576 + 512 * 4096 + 2048 * d + 512  # + kv_norm
    sparse = attention + d * 64 + 3 * d * 2816 + 8 * expert
    assert attention + 3 * d * 11_264 == pytest.approx(83.0e6, rel=1e-3)
    assert sparse == pytest.approx(100.4e6, rel=1e-3)
    assert 64 * expert * 16 == pytest.approx(8.86e9, rel=1e-3)
    total = (attention + 3 * d * 11_264) + 5 * sparse + 2 * 20_480 * d \
        + 13 * d
    assert total == pytest.approx(668.9e6, rel=1e-4)
    assert 16 * total == pytest.approx(10.70e9, rel=1e-3)
    # four sparse layers, had five not fitted
    assert total - sparse == pytest.approx(568.5e6, rel=1e-4)


def test_mla_kernel_work():
    fwd = mla_work.flash_fwd_call(CONFIG, 1, rows=1, seq=SEQ)
    assert fwd["flops"] == 2 * 1 * 16 * (192 + 128) * SEQ * SEQ / 2
    # bf16: q 16 x 192 in, the output 16 x 128 out, kn and v 16 x 128 in,
    # kr 64 once a position
    assert fwd["bytes"] == 2 * SEQ * (16 * (192 + 128 + 128 + 128) + 64)
    assert flops.roofline_seconds(fwd, PEAK)[1] == "compute"
    bwd = mla_work.flash_bwd_call(CONFIG, 1, rows=1, seq=SEQ)
    # S, dK, dQ at 192; dP, dV at 128
    assert bwd["flops"] == 2 * 16 * (3 * 192 + 2 * 128) * SEQ * SEQ / 2
    # q, dQ at 192; dO, the output, kn, v, dkn, dv at 128; kr and dkr once
    assert bwd["bytes"] == 2 * SEQ * (16 * (2 * 192 + 6 * 128) + 2 * 64)
    assert flops.roofline_seconds(bwd, PEAK)[1] == "compute"
    # the shared key handed to the kernel a head would be 16 x 64 a position
    # where it is 64: not counted
    assert fwd["bytes"] < 2 * SEQ * 16 * (192 + 128 + 192 + 128)
    # a forward call is 1.37 TFLOP: 7.0 ms at the peak
    assert fwd["flops"] / 197e12 == pytest.approx(6.98e-3, rel=1e-2)


def _ctx(mla="mla/"):
    """Two steps on one device: a layer's forward call, its recomputation
    and its backward call with the sum beside it, and the new scopes."""
    ops, t = [], 0.0
    stack = "jit(pretrain_step)/jvp(LlamaLMModel)/"
    back = "jit(pretrain_step)/transpose(jvp(LlamaLMModel))/" \
        "jvp(LlamaLMModel)/checkpoint/"
    call, fusion = "custom-call:tpu_custom_call", "fusion"
    for step in range(2):
        for i, (kind, path, secs) in enumerate([
                (call, stack + f"h_1/attn/{mla}flash_fwd/flash_fwd/pallas_call", 14e-3),
                (call, back + f"rematted_computation/h_1/attn/{mla}flash_fwd/flash_fwd/pallas_call", 14e-3),
                (call, back + f"h_1/attn/{mla}flash_bwd/flash_bwd/pallas_call", 28e-3),
                (fusion, back + f"h_1/attn/{mla}flash_bwd/reduce_sum", 1e-3),
                (fusion, stack + "h_1/attn/rope/mul", 2e-3),
                (fusion, back + "rematted_computation/h_1/attn/rope/mul", 1e-3),
                (fusion, stack + "h_1/attn/wdkv/dot_general", 4e-4),
                (fusion, stack + "h_1/attn/kv_norm/mul", 1e-4),
                (fusion, back + "h_1/attn/wukv/dot_general", 5e-4),
                (fusion, stack + "h_1/attn/wq/dot_general", 9e-4),
                (call, stack + "h_1/moe/experts/gmm/pallas_call", 7e-4)]):
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
    least = mla_work.flash_fwd_call(CONFIG, 1, 1, SEQ)["flops"] / 197e12
    # the forward and its recomputation: not the backward kernel
    assert kernel_roofline.read(
        ctx, **_metric("mla_attn_fwd_roofline")["args"]) == pytest.approx(
            100 * least / 14e-3)
    least = mla_work.flash_bwd_call(CONFIG, 1, 1, SEQ)["flops"] / 197e12
    # the kernel alone: not the sum of the shared key's gradient beside it
    assert kernel_roofline.read(
        ctx, **_metric("mla_attn_bwd_roofline")["args"]) == pytest.approx(
            100 * least / 28e-3)
    ms = {name: trace_ops.read(ctx, **_metric(name)["args"]) for name in (
        "mla_latent_ms_per_step", "mla_assemble_ms_per_step",
        "flash_fwd_ms_per_step", "flash_bwd_ms_per_step",
        "flash_fwd_calls_per_step", "flash_bwd_calls_per_step")}
    assert ms["mla_latent_ms_per_step"] == pytest.approx(1.0)
    assert ms["mla_assemble_ms_per_step"] == pytest.approx(3.0)
    # the list-less metrics read the new cell by their own selectors: the
    # flash kernels are the only Mosaic calls under h_<n>/attn/
    assert ms["flash_fwd_ms_per_step"] == pytest.approx(28.0)
    assert ms["flash_bwd_ms_per_step"] == pytest.approx(29.0)
    assert ms["flash_fwd_calls_per_step"] == pytest.approx(2.0)
    assert ms["flash_bwd_calls_per_step"] == pytest.approx(1.0)


def test_a_program_without_the_scopes_reports_nothing():
    """A program that has no ``mla`` scope and none of the latent's modules
    (the parent's, on any cell it can run): the readers give None and do not
    raise."""
    ctx = _ctx(mla="")
    ctx.trace.ops[0] = [o for o in ctx.trace.ops[0]
                        if "/attn/flash" in o.path or "/attn/wq/" in o.path]
    for name in ("mla_attn_fwd_roofline", "mla_attn_bwd_roofline"):
        assert kernel_roofline.read(ctx, **_metric(name)["args"]) is None
    for name in ("mla_latent_ms_per_step", "mla_assemble_ms_per_step"):
        assert trace_ops.read(ctx, **_metric(name)["args"]) is None


CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"


@pytest.mark.skipif(not os.path.exists(CATALOG),
                    reason="the catalog is beside the builder's guides only")
def test_every_catalog_key_stands_in_the_file():
    """Every key of the catalog row's ``config`` under the same key, verbatim
    but the three of ``reduced``, which stand beside their published
    counts."""
    with open(CATALOG) as f:
        row = next(r for r in map(json.loads, f)
                   if r["name"] == "Kimi-VL-A3B-Instruct")
    assert row["source_url"] == CONFIG["source"]
    for key, value in row["config"].items():
        if key in CONFIG["reduced"]:
            assert CONFIG["published_counts"][key] == value
        else:
            assert CONFIG[key] == value, key


def test_the_cell_as_the_manifest_has_it():
    bench = manifest.benchmark()
    entry = next(c for c in bench["configs"]
                 if c["name"] == "kimi-vl-a3b-instruct")
    assert entry["reduced"] == CONFIG["reduced"] == [
        "num_hidden_layers", "n_routed_experts", "vocab_size"]
    assert CELL.chips == 1 and CELL.traffic["kind"] == "train_loop"
    assert (CELL.traffic["seq"], CELL.traffic["rows_per_step"]) == (SEQ, 1)
    assert CONFIG["flops_counted_at_seq"] == SEQ
    # the cell has its four metrics (a later PR may list it under more);
    # since PR 67 the four list every cell with latent attention, whose heads
    # and widths mla_work.py reads from the cell's own configuration
    on_at_least(bench, "kimi-vl-s16k-1chip", NEW)
    assert set(NEW) <= {m["name"] for m in CELL.per_layer}
    assert CONFIG["published_counts"] == {
        "num_hidden_layers": 27, "n_routed_experts": 64, "vocab_size": 163840}
    assert kimi_vl.held(CONFIG) == (0, 8) and kimi_vl.n_experts(CONFIG) == 64
    assert kimi_vl.shared_width(CONFIG) == 2816
    assert CONFIG["deployment"]["chips_sharing_a_layer"] == 8
    assert CONFIG["reference"]["prefix"] >= 1024
    for key in ("cut_why", "assumed", "program_departures", "dtypes"):
        assert CONFIG[key]
    assert "vision_tower" in CONFIG["program_departures"]

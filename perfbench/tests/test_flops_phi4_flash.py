"""``families/phi4_flash.py::shape`` (what ``flops.train_flops_per_token``
counts ``phi4-flash-s16k-1chip`` from), ``scan_ops_per_token``,
``selective_scan_work.py`` and ``diff_attn_work.py`` against sums written out
by hand from the published sizes, the layers' equations and the cut, a
brute-force count of the live (query, key) pairs, and the parameter tree's own
matmul leaves; the nine new metrics on a synthetic trace whose name paths are
as the chip's trace prints them."""

import json
import os

import pytest

from perfbench.harness import (diff_attn_work, flops, manifest,
                               selective_scan_work)
from perfbench.harness.families import phi4_flash
from perfbench.tests.manifest_lists import on_at_least
from perfbench.harness.readers import kernel_roofline, scope_roofline, trace_ops
from perfbench.harness.readers.context import Context
from perfbench.harness.trace_reduce import Op, Trace

NAME = "phi4-flash-s16k-1chip"
CELL = manifest.cell(NAME)
CONFIG = CELL.config
PEAK = manifest.peaks()["TPU v5 lite"]
SEQ = 16384
NEW = ["mamba1_scope_share_pct", "selective_scan_ms_per_step",
       "selective_scan_roofline", "mamba1_proj_ms_per_step",
       "gmu_ms_per_step", "diff_attn_ms_per_step", "diff_attn_fwd_roofline",
       "diff_attn_bwd_roofline", "diff_attn_combine_ms_per_step"]
E, D, FF, VOCAB = 2560, 5120, 10240, 25088


@pytest.mark.parametrize("seq,window", [(1, 0), (7, 0), (40, 8), (8, 8),
                                        (5, 8), (2048, 512)])
def test_live_pairs_are_a_brute_force_count(seq, window):
    want = sum(1 for q in range(seq) for k in range(seq)
               if k <= q and (not window or q - k < window))
    assert phi4_flash.live_pairs(seq, window) == want


def test_the_scan_is_115_operations_a_channel():
    # 16 state cells x (delta A, exp, x h, B x written, +, C x h, + into y)
    # and delta u, D u, + a channel
    assert phi4_flash.scan_ops_per_token(CONFIG) == D * (16 * 7 + 3) \
        == 588_800


def test_phi4_flash_is_4_96_gflop_a_token_at_the_cut():
    # in_proj, the convolution, x_proj, dt_proj, out_proj
    mamba = E * 2 * D + 4 * D + D * (160 + 32) + 160 * D + D * E
    assert mamba == 41_144_320
    attn = E * (40 + 20 + 20) * 64 + 40 * 64 * E
    assert attn == 19_660_800
    cross, gmu, mlp = 2 * E * E, 2 * E * D, 3 * E * FF
    assert (cross, gmu, mlp) == (13_107_200, 26_214_400, 78_643_200)
    scan = phi4_flash.scan_ops_per_token(CONFIG) // 2
    layers = 2 * (mamba + scan) + 2 * attn + gmu + cross + 6 * mlp
    head = E * VOCAB
    # the full and the cross layer's scores, forward + backward: two
    # softmaxes of 20 heads, q.k at 64 and p.v at 128, 2 FLOPs a pair a
    # dimension, half the square; the window layer's over its band
    whole = 3 * 2 * 2 * 20 * (64 + 128) * SEQ // 2
    assert whole == 6 * SEQ * 3840 == int(6 * SEQ * 1.5 * E)
    band = 3 * 2 * 2 * 20 * 192 * phi4_flash.live_pairs(SEQ, 512) // SEQ
    want = 6 * (layers + head) + 2 * whole + band
    s = phi4_flash.shape(CONFIG, 1)
    assert (s["n_layer"], s["d_model"], s["vocab"]) == (3, E, VOCAB)
    # the formula's second term charges 3 x 2,560 a token; the band's
    # 0.09 x 2,560 x seq ride in layer_mm_params
    assert 6 * s["n_layer"] * SEQ * E == 2 * whole
    got = flops.train_flops_per_token(CONFIG, 1, SEQ)
    assert abs(want - got) < 6 * 4
    assert want == pytest.approx(4.965e9, rel=2e-3)
    # the shares cut_why states
    assert 6 * 6 * mlp / want == pytest.approx(0.570, abs=0.003)
    assert 2 * whole / want == pytest.approx(0.152, abs=0.003)
    assert band / want == pytest.approx(0.005, abs=0.001)
    assert 6 * 2 * mamba / want == pytest.approx(0.099, abs=0.003)
    assert 6 * head / want == pytest.approx(0.078, abs=0.003)
    assert 6 * (2 * attn + cross) / want == pytest.approx(0.063, abs=0.003)
    assert 6 * gmu / want == pytest.approx(0.032, abs=0.003)
    assert 6 * 2 * scan / want == pytest.approx(0.0007, abs=0.0002)


def test_state_is_11_16_gb_of_the_chip():
    mamba = E * 2 * D + (4 + 1) * D + D * 192 + (160 + 1) * D + D * 16 + D \
        + D * E
    attn = E * 5120 + 5120 + E * E + E + 4 * 64 + 128
    cross = 2 * (E * E + E) + 4 * 64 + 128
    gmu, mlp, norms = 2 * E * D, 3 * E * FF, 2 * 2 * E
    assert mamba == pytest.approx(41.24e6, rel=1e-3)
    assert attn == pytest.approx(19.67e6, rel=1e-3)
    assert cross == pytest.approx(13.11e6, rel=1e-3)
    six = 2 * (mamba + mlp + norms) + 2 * (attn + mlp + norms) \
        + (gmu + mlp + norms) + (cross + mlp + norms)
    assert six == pytest.approx(633.08e6, rel=1e-4)
    total = six + VOCAB * E + 2 * E
    assert total == pytest.approx(697.3e6, rel=1e-3)
    assert 16 * total == pytest.approx(11.16e9, rel=1e-3)
    # the whole model: 9 Mamba-1, 9 attention, 7 GMU, 7 cross, the table
    whole = 9 * (mamba + mlp + norms) + 9 * (attn + mlp + norms) \
        + 7 * (gmu + mlp + norms) + 7 * (cross + mlp + norms) \
        + 200_064 * E + 2 * E
    assert whole == pytest.approx(3.8527e9, rel=1e-3)
    # a second GMU + cross pair, or the whole table, had they fitted
    assert 16 * (total + gmu + cross + 2 * (mlp + norms)) == pytest.approx(
        14.3e9, rel=1e-2)
    assert 16 * 200_064 * E == pytest.approx(8.2e9, rel=1e-2)
    assert -(-200_064 // 8) == 25_008 and VOCAB == 196 * 128 >= 25_008


def test_shape_counts_the_parameter_trees_matmul_leaves():
    """At the toy's widths: every kernel of the program's parameter tree that
    is a matmul operand (the tied table once, as the head, at the unpadded
    vocabulary), the convolutions' taps, the scans and the scores' remainder
    are what ``shape`` hands the formula."""
    import jax

    from ray_tpu.models.pretrain import init_params

    with open(os.path.join(os.path.dirname(os.path.abspath(__file__)), "toy",
                           "toy-phi4-flash.json")) as f:
        toy = json.load(f)
    cfg = phi4_flash.model_config(toy, 1)
    params = jax.eval_shape(lambda: init_params(cfg)[1])
    total = 0
    for path, leaf in jax.tree_util.tree_flatten_with_path(params)[0]:
        name = jax.tree_util.keystr(path)
        size = 1
        for n in leaf.shape:
            size *= n
        if "wte" not in name and ("['kernel']" in name
                                  or name.endswith("['conv_kernel']")):
            total += size
    kinds = phi4_flash.layer_kinds(toy)
    scans = kinds.count("mamba1") * (phi4_flash.scan_ops_per_token(toy) // 2)
    s = phi4_flash.shape(toy, 1)
    widths = phi4_flash.score_widths(toy)
    scores = sum(widths.get(kind, 0.0) for kind in kinds)
    assert widths["full_attention"] == 1.5 and s["n_layer"] == int(scores)
    remainder = int((scores - s["n_layer"]) * 64 * 64)
    assert total + scans + remainder - s["n_layer"] * s["layer_mm_params"] \
        in range(s["n_layer"])
    assert flops.matmul_params(toy, 1) == s["n_layer"] * s["layer_mm_params"] \
        + 64 * 512


@pytest.mark.parametrize("rows,seq,block", [(1, SEQ, 256), (2, 40, 8),
                                            (1, 29, 8)])
def test_selective_scan_work(rows, seq, block):
    config = dict(CONFIG, scan_block=block)
    work = selective_scan_work.scan_step(config, 1, rows=rows, seq=seq)
    blocks = rows * -(-seq // block)
    # two Mamba-1 layers, forward + twice that backward
    assert work["flops"] == 3 * 2 * rows * seq * D * 115
    # bf16 u and y, float32 step sizes, bf16 B and C; the float32 state a
    # block, written and read
    assert work["bytes"] == 3 * 2 * (
        rows * seq * (D * (2 + 2 + 4) + 2 * 2 * 16) + 2 * 4 * blocks * D * 16)


def test_the_scans_roofline_is_its_bytes():
    work = selective_scan_work.scan_step(CONFIG, 1, rows=1, seq=SEQ)
    least, bound = flops.roofline_seconds(work, PEAK)
    assert bound == "memory"
    # 4.28 GB a step, 5.2 ms; the elementwise operations at the matmul peak
    # would be 0.3 ms, which no vector unit reaches
    assert work["bytes"] == pytest.approx(4.28e9, rel=1e-2)
    assert least == pytest.approx(5.23e-3, rel=1e-2)
    assert work["flops"] / PEAK["bf16_flops_per_s"] == pytest.approx(
        0.294e-3, rel=1e-2)


def test_diff_attn_work():
    fwd = diff_attn_work.flash_fwd_call(CONFIG, 1, rows=1, seq=SEQ)
    bwd = diff_attn_work.flash_bwd_call(CONFIG, 1, rows=1, seq=SEQ)
    pairs = SEQ * (SEQ + 1) // 2
    assert fwd["flops"] == 2 * 20 * (64 + 128) * pairs
    assert bwd["flops"] == 2 * 20 * (3 * 64 + 2 * 128) * pairs
    assert fwd["bytes"] == 2 * SEQ * (20 * 64 + 20 * 128 + 10 * 64 + 10 * 128)
    assert bwd["bytes"] == 2 * SEQ * (20 * (2 * 64 + 2 * 128)
                                      + 10 * (2 * 64 + 2 * 128))
    assert flops.roofline_seconds(fwd, PEAK)[1] == "compute"
    assert flops.roofline_seconds(fwd, PEAK)[0] == pytest.approx(5.23e-3,
                                                                 rel=1e-2)
    # a layer's two forward calls are the formula's 1.5 d_model, forward
    assert 2 * fwd["flops"] == pytest.approx(
        2 * SEQ * SEQ * 1.5 * E, rel=1e-3)


def _ctx():
    """Two steps on one device: a Mamba-1 layer's projections, convolution
    and scan, forward, recomputed and backward; the full, the sliding and
    the cross layer's flash calls and what follows them; a gated memory
    unit; other work."""
    ops, t = [], 0.0
    stack = "jit(pretrain_step)/jvp(LlamaLMModel)/"
    back = "jit(pretrain_step)/transpose(jvp(LlamaLMModel))/" \
        "jvp(LlamaLMModel)/checkpoint/"
    call, fusion = "custom-call:tpu_custom_call", "fusion"
    scan = "mamba1/scan/"
    for step in range(2):
        for i, (kind, path, secs) in enumerate([
                (call, stack + f"h_0/{scan}selective_scan_fwd/pallas_call", 10e-3),
                (call, back + f"rematted_computation/h_0/{scan}selective_scan_fwd/pallas_call", 10e-3),
                (call, back + f"h_0/{scan}selective_scan_bwd/pallas_call", 30e-3),
                (fusion, back + f"h_0/{scan}reduce_sum", 1e-3),
                (fusion, stack + "h_0/mamba1/in_proj/dot_general", 4e-3),
                (fusion, back + "h_0/mamba1/dt_proj/dot_general", 1e-3),
                (fusion, back + "h_0/mamba1/x_proj/dot_general", 1e-3),
                (fusion, stack + "h_0/mamba1/conv/mul", 2e-3),
                (fusion, stack + "h_0/mamba1/gate/mul", 1e-3),
                (call, stack + "h_3/attn/diff/flash_fwd/flash_fwd/pallas_call", 8e-3),
                (call, stack + "h_3/attn/diff/flash_fwd/flash_fwd/pallas_call", 8e-3),
                (call, stack + "h_1/attn/diff/window/flash_fwd/flash_fwd/pallas_call", 1e-3),
                (call, back + "h_5/attn/diff/flash_bwd/flash_bwd/pallas_call", 20e-3),
                (call, back + "h_1/attn/diff/window/flash_bwd/flash_bwd/pallas_call", 2e-3),
                (fusion, stack + "h_3/attn/diff/transpose", 1e-3),
                (fusion, stack + "h_3/attn/combine/sub_norm/mul", 3e-3),
                (fusion, stack + "h_3/attn/wqkv/dot_general", 2e-3),
                (fusion, stack + "h_4/gmu/in_proj/dot_general", 2e-3),
                (fusion, back + "h_4/gmu/gate/mul", 1e-3),
                (fusion, stack + "h_4/mlp/gate_proj/dot_general", 5e-3)]):
            ops.append(Op(f"op.{step}.{i}", kind, path, t, t + secs))
            t += secs
    trace = Trace(ops={0: ops}, spans=[("window", 0.0, t)])
    return Context(CELL, PEAK, {}, trace, traced_steps=2)


def _metric(name):
    with open(os.path.join(manifest.BENCH_DIR, "layer_metrics",
                           name + ".json")) as f:
        return json.load(f)


def _read(ctx, name):
    metric = _metric(name)
    reader = {"trace_ops": trace_ops, "scope_roofline": scope_roofline,
              "kernel_roofline": kernel_roofline}[metric["reader"]]
    return reader.read(ctx, **metric["args"])


def test_the_new_metrics_on_a_synthetic_trace():
    ctx = _ctx()
    assert _read(ctx, "selective_scan_ms_per_step") == pytest.approx(51.0)
    assert _read(ctx, "mamba1_proj_ms_per_step") == pytest.approx(6.0)
    assert _read(ctx, "mamba1_scope_share_pct") == pytest.approx(
        100 * 60 / 113)
    assert _read(ctx, "gmu_ms_per_step") == pytest.approx(3.0)
    assert _read(ctx, "diff_attn_ms_per_step") == pytest.approx(40.0)
    assert _read(ctx, "diff_attn_combine_ms_per_step") == pytest.approx(3.0)
    least = flops.roofline_seconds(
        selective_scan_work.scan_step(CONFIG, 1, 1, SEQ), PEAK)[0]
    # over everything under the scope, the recomputation's time included
    assert _read(ctx, "selective_scan_roofline") == pytest.approx(
        100 * least / 51e-3)
    fwd = flops.roofline_seconds(
        diff_attn_work.flash_fwd_call(CONFIG, 1, 1, SEQ), PEAK)[0]
    bwd = flops.roofline_seconds(
        diff_attn_work.flash_bwd_call(CONFIG, 1, 1, SEQ), PEAK)[0]
    # the whole-row calls alone: the window's are left out
    assert _read(ctx, "diff_attn_fwd_roofline") == pytest.approx(
        100 * fwd / 8e-3)
    assert _read(ctx, "diff_attn_bwd_roofline") == pytest.approx(
        100 * bwd / 20e-3)
    # the list-less metrics read the new cell by their own selectors: the
    # flash kernels are the only Mosaic calls under h_<n>/attn/
    assert trace_ops.read(ctx, **_metric("flash_fwd_ms_per_step")["args"]) \
        == pytest.approx(17.0)
    assert trace_ops.read(ctx, **_metric("flash_bwd_ms_per_step")["args"]) \
        == pytest.approx(22.0)


def test_a_program_without_the_scopes_reports_nothing():
    """A program that has no ``mamba1``, ``gmu`` or ``attn/diff`` (the
    parent's, on any cell it can run): the readers give None and do not
    raise."""
    ctx = _ctx()
    ctx.trace.ops[0] = [o for o in ctx.trace.ops[0]
                        if not any(s in o.path for s in (
                            "/mamba1/", "/gmu/", "/diff/", "/combine/"))]
    for name in NEW:
        assert _read(ctx, name) is None


CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"


@pytest.mark.skipif(not os.path.exists(CATALOG),
                    reason="the catalog is beside the builder's guides only")
def test_every_catalog_key_stands_in_the_file():
    """Every key of the catalog row's ``config`` under the same key, verbatim
    but those of ``reduced``, which stand beside their published counts."""
    with open(CATALOG) as f:
        row = next(r for r in map(json.loads, f)
                   if r["name"] == "Phi-4-mini-flash-reasoning")
    assert row["source_url"] == CONFIG["source"]
    for key, value in row["config"].items():
        if key in CONFIG["reduced"]:
            assert CONFIG["published_counts"][key] == value
        else:
            assert CONFIG[key] == value, key


def test_the_cell_as_the_manifest_has_it():
    bench = manifest.benchmark()
    entry = next(c for c in bench["configs"]
                 if c["name"] == "phi-4-mini-flash-reasoning")
    assert entry["reduced"] == CONFIG["reduced"] == [
        "num_hidden_layers", "vocab_size"]
    assert CELL.chips == 1 and CELL.traffic["kind"] == "train_loop"
    assert (CELL.traffic["seq"], CELL.traffic["rows_per_step"]) == (SEQ, 1)
    assert CONFIG["flops_counted_at_seq"] == SEQ
    # the very traffic file of the two Kimi cells
    assert next(w for w in bench["workloads"] if w["name"] == NAME)[
        "traffic"] == next(w for w in bench["workloads"]
                           if w["name"] == "kimi-vl-s16k-1chip")["traffic"]
    # by name, and at least these: later PRs append and list the cell under
    # more
    on_at_least(bench, NAME, NEW)
    assert set(NEW) <= {m["name"] for m in CELL.per_layer}
    assert CONFIG["published_counts"] == {"num_hidden_layers": 32,
                                          "vocab_size": 200064}
    assert CONFIG["layers_kept"] == [14, 15, 16, 17, 18, 19]
    assert phi4_flash.layer_kinds(CONFIG) == (
        "mamba1", "sliding_attention", "mamba1", "full_attention", "gmu",
        "cross_attention")
    assert phi4_flash.producers(CONFIG) == (2, 3)
    assert CONFIG["deployment"]["chips_sharing_the_vocabulary"] == 8
    assert CONFIG["reference"]["prefix"] >= 1024
    assert list(CONFIG["assumed"])[1] == "differential_attention"
    for key in ("cut_why", "assumed", "program_departures", "dtypes"):
        assert CONFIG[key]

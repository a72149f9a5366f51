"""``families/smallthinker.py::shape`` (what ``flops.train_flops_per_token``
counts ``smallthinker-s16k-1chip`` from) against the parameter tree's matmul
leaves and a brute-force count of live pairs, ``flash_work.py`` at its sizes
against sums written out by hand, and the cell's metrics on a synthetic trace
whose name paths are as the chip's trace prints them."""

import json
import os

import numpy as np
import pytest

from perfbench.harness import flash_work, flops, manifest
from perfbench.harness.families import smallthinker
from perfbench.tests.manifest_lists import on_at_least
from perfbench.harness.readers import kernel_roofline, trace_ops
from perfbench.harness.readers.context import Context
from perfbench.harness.trace_reduce import Op, Trace

NAME = "smallthinker-s16k-1chip"
CELL = manifest.cell(NAME)
CONFIG = CELL.config
PEAK = manifest.peaks()["TPU v5 lite"]
SEQ = 16384
# PR 59's seven; since PR 67 the three that were copies of an older entry's
# selection are that entry (band4k_attn_ms_per_step -> window_attn_ms_per_step,
# pre_router_ms_per_step -> moe_router_ms_per_step, reglu_experts_ms_per_step
# -> moe_experts_ms_per_step), which lists this cell too; since PR 71 its four
# rooflines are the entries of one work function for every configuration
# (band4k_attn_*_roofline -> window_attn_*_roofline, gqa7_full_attn_*_roofline
# -> flash_*_roofline)
NEW = ["window_attn_ms_per_step", "window_attn_fwd_roofline",
       "window_attn_bwd_roofline", "flash_fwd_roofline",
       "flash_bwd_roofline", "moe_router_ms_per_step",
       "moe_experts_ms_per_step"]
STACK = "jit(pretrain_step)/jvp(LlamaLMModel)/"
WHOLE = STACK + "h_0/attn/flash_fwd/flash_fwd/pallas_call"
WINDOW = STACK + "h_1/attn/window/flash_fwd/flash_fwd/pallas_call"
# a row's live pairs under the window: the first 4,096 queries see 1 .. 4,096
# keys, the other 12,288 see 4,096
BAND = 4096 * 4097 // 2 + (SEQ - 4096) * 4096
TRIANGLE = SEQ * (SEQ + 1) // 2    # what mfu_pct charges this family: shape()
HALF_SQUARE = SEQ * SEQ // 2     # what flash_work.py charges a whole-row call


def test_smallthinker_is_2_12_gflop_a_token_at_the_cut():
    assert smallthinker.band_pairs(SEQ, 4096) == BAND
    d = 2560
    attn = 2 * d * 28 * 128 + 2 * d * 4 * 128
    expert, router = 3 * d * 768, d * 64
    assert (attn, expert, router) == (20_971_520, 5_898_240, 163_840)
    # top_k * 16 / 64 = one and a half held experts a token
    layer = attn + router + 6 * 16 * expert // 64
    head = d * 38_016
    # a layer's scores as FLOPs a token: QK^T and PV (2 x 2 a pair a head
    # dimension), forward + backward (x 3), over the row's tokens
    window, full = (12 * pairs * 28 * 128 / SEQ for pairs in (BAND, TRIANGLE))
    want = 6 * (4 * layer + head) + full + 3 * window
    got = flops.train_flops_per_token(CONFIG, 1, SEQ)
    assert got == pytest.approx(want, rel=1e-6)
    assert want == pytest.approx(2.118e9, rel=1e-3)
    # the shares cut_why states
    assert 3 * window / want == pytest.approx(0.22, abs=0.005)
    assert full / want == pytest.approx(0.17, abs=0.005)
    assert 6 * 4 * attn / want == pytest.approx(0.24, abs=0.005)
    assert 6 * head / want == pytest.approx(0.28, abs=0.005)
    assert 6 * 4 * 1.5 * expert / want == pytest.approx(0.10, abs=0.005)
    assert 6 * 4 * router / want == pytest.approx(0.002, abs=0.0005)
    # charged the causal triangle, the window layers would read 1.29x high
    assert (want + 3 * (full - window)) / want == pytest.approx(1.28, abs=0.02)


def test_the_scores_count_is_a_brute_force_count_of_live_pairs():
    for seq, window in ((64, 8), (64, 64), (64, 100), (37, 5)):
        i, j = np.arange(seq)[:, None], np.arange(seq)[None, :]
        assert smallthinker.band_pairs(seq, window) \
            == int(((j <= i) & (i - j < window)).sum())
        small = dict(CONFIG, sliding_window_size=window)
        assert smallthinker.layer_pairs(small, 1, seq) \
            == int(np.asarray(smallthinker.seen(0, seq, seq, window)).sum())
        assert smallthinker.layer_pairs(small, 0, seq) \
            == int(np.asarray(smallthinker.seen(0, seq, seq, None)).sum()) \
            == seq * (seq + 1) // 2


def test_shape_counts_the_parameter_trees_matmul_leaves():
    """Every matmul leaf of the program's own tree at the cut (shapes only:
    nothing is initialised), the held experts at 1.5 of 16 a token."""
    import jax

    from ray_tpu.models.llama import LlamaLMModel

    cfg = smallthinker.model_config(CONFIG, 1)
    tree = jax.eval_shape(
        lambda: LlamaLMModel(cfg).init(jax.random.PRNGKey(0),
                                       jax.numpy.zeros((1, 8), "int32")))
    sizes = {jax.tree_util.keystr(path): int(np.prod(leaf.shape)) for
             path, leaf in jax.tree_util.tree_flatten_with_path(
                 tree["params"])[0]}
    total = sum(sizes.values())
    assert total == pytest.approx(656.7e6, rel=1e-4)
    assert 16 * total == pytest.approx(10.51e9, rel=1e-3)
    experts = sum(n for k, n in sizes.items() if "_proj" in k)
    assert experts == 4 * 16 * 3 * 2560 * 768
    matmuls = sum(n for k, n in sizes.items()
                  if "kernel" in k and "_proj" not in k) \
        + experts * 6 // 64        # 1.5 of the 16 held a token
    s = smallthinker.shape(CONFIG, 1)
    scores = sum(2 * smallthinker.layer_pairs(CONFIG, i, SEQ) * 28 * 128
                 // SEQ for i in range(4))
    assert s["n_layer"] * s["layer_mm_params"] + s["d_model"] * s["vocab"] \
        == matmuls + scores - SEQ * 2560
    assert (s["n_head"], s["n_kv_head"], s["head_dim"]) == (28, 4, 128)


def test_kernel_work():
    fwd = flash_work.fwd_call(CONFIG, 1, rows=1, seq=SEQ, path=WINDOW)
    assert fwd["flops"] == 2 * 2 * 28 * BAND * 128
    # Q, O at 28 heads and K, V at 4, bf16
    assert fwd["bytes"] == 2 * SEQ * 128 * (28 + 28 + 4 + 4)
    assert flops.roofline_seconds(fwd, PEAK)[1] == "compute"
    bwd = flash_work.bwd_call(CONFIG, 1, rows=1, seq=SEQ, path=WINDOW)
    assert bwd["flops"] == 5 * 2 * 28 * BAND * 128
    assert bwd["bytes"] == 2 * SEQ * 128 * (3 * 28 + 4 * 4)
    full = flash_work.fwd_call(CONFIG, 1, rows=1, seq=SEQ, path=WHOLE)
    assert full["flops"] == 2 * 2 * 28 * HALF_SQUARE * 128
    assert full["bytes"] == fwd["bytes"]
    assert flash_work.bwd_call(CONFIG, 1, 1, SEQ, path=WHOLE)["flops"] \
        == 5 * 2 * 28 * HALF_SQUARE * 128
    # the band is 7/16 of the triangle at 16,384 under 4,096
    assert fwd["flops"] / full["flops"] == pytest.approx(7 / 16, rel=1e-3)


def _ctx():
    """Two steps on one device: a whole-row and a window layer, each a
    forward call and a backward call, and the routed layer's scopes."""
    ops, t = [], 0.0
    stack = STACK
    back = "jit(pretrain_step)/transpose(jvp(LlamaLMModel))/" \
        "jvp(LlamaLMModel)/checkpoint/"
    call, fusion = "custom-call:tpu_custom_call", "fusion"
    for step in range(2):
        for i, (kind, path, secs) in enumerate([
                (call, stack + "h_0/attn/flash_fwd/flash_fwd/pallas_call", 20e-3),
                (call, back + "h_0/attn/flash_bwd/flash_bwd/pallas_call", 50e-3),
                (call, stack + "h_1/attn/window/flash_fwd/flash_fwd/pallas_call", 10e-3),
                (call, back + "h_1/attn/window/flash_bwd/flash_bwd/pallas_call", 25e-3),
                (fusion, back + "h_1/attn/window/flash_bwd/reduce_sum", 1e-3),
                (fusion, stack + "h_1/attn/rope/mul", 2e-3),
                (fusion, stack + "h_1/moe/router/router/dot_general", 4e-4),
                (fusion, back + "rematted_computation/h_1/moe/router/top_k", 6e-4),
                (call, stack + "h_1/moe/while/body/h_1/moe/experts/pallas_call", 3e-3),
                (fusion, back + "h_1/moe/while/body/h_1/moe/experts/mul", 1e-3)]):
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
    got = {name: (kernel_roofline if name.endswith("roofline")
                  else trace_ops).read(ctx, **_metric(name)["args"])
           for name in NEW}
    least = {(fn, path): getattr(flash_work, fn)(
        CONFIG, 1, 1, SEQ, path=path)["flops"] / 197e12
        for fn in ("fwd_call", "bwd_call") for path in (WHOLE, WINDOW)}
    # each kind's calls alone, each against its own least time: the window's
    # not the whole-row layer's
    assert got["window_attn_fwd_roofline"] == pytest.approx(
        100 * least["fwd_call", WINDOW] / 10e-3)
    assert got["window_attn_bwd_roofline"] == pytest.approx(
        100 * least["bwd_call", WINDOW] / 25e-3)
    assert got["flash_fwd_roofline"] == pytest.approx(
        100 * least["fwd_call", WHOLE] / 20e-3)
    assert got["flash_bwd_roofline"] == pytest.approx(
        100 * least["bwd_call", WHOLE] / 50e-3)
    # the two kernel calls, not the XLA work around the backward kernel
    assert got["window_attn_ms_per_step"] == pytest.approx(35.0)
    assert got["moe_router_ms_per_step"] == pytest.approx(1.0)
    assert got["moe_experts_ms_per_step"] == pytest.approx(4.0)
    # no roofline over 100% at these times, which are about the chip's
    assert all(v <= 100 for k, v in got.items() if k.endswith("roofline"))


def test_a_program_without_the_scopes_reports_nothing():
    """A program with no window and no routed layer: the readers give None
    and do not raise."""
    path = "jit(pretrain_step)/jvp(GPT2LMModel)/h_0/mlp/dot_general"
    trace = Trace(ops={0: [Op("op", "fusion", path, 0.0, 1e-3)]},
                  spans=[("window", 0.0, 1e-3)])
    ctx = Context(CELL, PEAK, {}, trace, traced_steps=1)
    for name in NEW:
        reader = kernel_roofline if name.endswith("roofline") else trace_ops
        assert reader.read(ctx, **_metric(name)["args"]) is None


def test_the_cell_as_the_manifest_has_it():
    bench = manifest.benchmark()
    entry = next(c for c in bench["configs"]
                 if c["name"] == "smallthinker-21b-a3b-instruct")
    assert entry["reduced"] == CONFIG["reduced"] == [
        "num_hidden_layers", "moe_num_primary_experts", "vocab_size",
        "rope_layout", "sliding_window_layout"]
    assert CELL.chips == 1 and CELL.traffic["kind"] == "train_loop"
    assert (CELL.traffic["seq"], CELL.traffic["rows_per_step"]) == (SEQ, 1)
    assert CONFIG["flops_counted_at_seq"] == SEQ
    # the very traffic file of the two Kimi cells and Phi-4-flash's
    assert next(w for w in bench["workloads"] if w["name"] == NAME)[
        "traffic"] == next(w for w in bench["workloads"]
                           if w["name"] == "kimi-vl-s16k-1chip")["traffic"]
    # by name, and at least these: later PRs list the cell under more
    on_at_least(bench, NAME, NEW)
    assert set(NEW) <= {m["name"] for m in CELL.per_layer}
    assert CONFIG["published_counts"]["moe_num_primary_experts"] == 64
    assert smallthinker.held(CONFIG) == (0, 16)
    assert smallthinker.layer_kinds(CONFIG) == (
        "full_attention",) + ("sliding_attention",) * 3
    assert CONFIG["deployment"]["chips_sharing_a_layer"] == 4
    assert CONFIG["reference"]["prefix"] == 8192
    assert list(CONFIG["assumed"])[1] == "router_input"
    for key in ("cut_why", "assumed", "program_departures", "dtypes"):
        assert CONFIG[key]


def test_every_catalog_number_stands_in_the_file():
    path = "/opt/skills/guides/model-configs/architectures.jsonl"
    if not os.path.exists(path):
        pytest.skip("the catalog is not on this machine")
    with open(path) as f:
        row = next(r for r in map(json.loads, f)
                   if r["name"] == "SmallThinker-21BA3B-Instruct")
    assert row["source_url"] == CONFIG["source"]
    for key, value in row["config"].items():
        if key not in CONFIG["reduced"]:
            assert CONFIG[key] == value, key
        elif isinstance(value, list):
            assert CONFIG[key] == value[:len(CONFIG[key])]
            assert str(len(value)) in CONFIG["published_counts"][key]
        else:
            assert CONFIG["published_counts"][key] == value

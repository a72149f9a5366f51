"""``families/evabyte.py::train_flops_per_token`` (what ``mtp_train_loop``
counts ``evabyte-eva-1chip`` from) and ``eva_work.py`` against sums written
out by hand from the published sizes, the layer equations and the cut — the
live-pair formula against a brute-force count of the mask — and the five new
metrics on a synthetic trace whose name paths are as the chip's trace prints
them."""

import json
import os

import numpy as np
import pytest

from perfbench.harness import eva_work, flops, manifest
from perfbench.harness.families import evabyte
from perfbench.tests.manifest_lists import on_at_least
from perfbench.harness.readers import kernel_roofline, scope_roofline, trace_ops
from perfbench.harness.readers.context import Context
from perfbench.harness.trace_reduce import Op, Trace

NAME = "evabyte-eva-1chip"
CELL = manifest.cell(NAME)
CONFIG = CELL.config
PEAK = manifest.peaks()["TPU v5 lite"]
SEQ = CELL.traffic["seq"]
NEW = ["eva_attn_fwd_roofline", "eva_attn_bwd_roofline",
       "eva_attn_ms_per_step", "eva_pool_ms_per_step", "eva_pool_roofline"]


def _brute(seq, window, chunk):
    """Live entries of the mask over [summaries ; positions], counted."""
    i = np.arange(seq)[:, None]
    first = i // window * window
    own = (np.arange(seq)[None, :] >= first) & (np.arange(seq)[None, :] <= i)
    summaries = np.arange(seq // chunk)[None, :] * chunk < first
    return int(own.sum() + summaries.sum())


@pytest.mark.parametrize("seq,window,chunk", [
    (10, 4, 2), (384, 128, 16), (300, 128, 8), (96, 128, 16),
    (1000, 256, 32), (4096, 2048, 16), (16384, 2048, 16)])
def test_live_pairs_against_the_mask_counted(seq, window, chunk):
    assert evabyte.live_pairs(seq, window, chunk) == _brute(seq, window, chunk)


def test_a_query_meets_1472_5_keys_at_16384():
    pairs = evabyte.live_pairs(16384, 2048, 16)
    # S (W + 1) / 2 of its own window, and W x w W / c summaries a window
    assert pairs == 16384 * 2049 // 2 + sum(2048 * w * 128 for w in range(8))
    assert pairs / 16384 == 1024.5 + 448 == 1472.5
    assert pairs == pytest.approx(24.1e6, rel=2e-3)
    assert 16384 * 16385 // 2 == pytest.approx(134e6, rel=2e-3)
    # 30% of the pairs are summaries; 16% at 8,192
    assert 448 / 1472.5 == pytest.approx(0.30, abs=0.005)
    at_8k = evabyte.live_pairs(8192, 2048, 16) / 8192
    assert (at_8k - 1024.5) / at_8k == pytest.approx(0.16, abs=0.005)


def test_evabyte_is_5_21_gflop_a_token_at_the_cut():
    d, ff = 4096, 11008
    layer = 4 * d * d + 3 * d * ff
    head = d * 8 * 320
    assert (layer, head) == (202_375_168, 10_485_760)
    assert 4 * layer + head == pytest.approx(820.0e6, rel=1e-4)
    want = 6 * (4 * layer + head) + 12 * 4 * d * 1472.5
    s = evabyte.shape(CONFIG, 1)
    assert (s["n_layer"], s["d_model"], s["vocab"]) == (4, 4096, 2560)
    assert (s["n_head"], s["n_kv_head"], s["head_dim"]) == (32, 32, 128)
    assert evabyte.train_flops_per_token(CONFIG, 1, SEQ) == want
    assert want == pytest.approx(5.21e9, rel=1e-3)
    # the shares cut_why states
    assert 12 * 4 * d * 1472.5 / want == pytest.approx(0.056, abs=0.001)
    assert 6 * 4 * 4 * d * d / want == pytest.approx(0.31, abs=0.005)
    assert 6 * 4 * 3 * d * ff / want == pytest.approx(0.62, abs=0.005)
    assert 6 * head / want == pytest.approx(0.012, abs=0.001)
    # were the square causal the pairs would be a quarter of the step
    causal = 12 * 4 * d * 8192.5
    assert causal / (want - 12 * 4 * d * 1472.5 + causal) \
        == pytest.approx(0.25, abs=0.005)
    # at the published 32,768: 7%
    longer = evabyte.train_flops_per_token(CONFIG, 1, 32768)
    assert (longer - 6 * (4 * layer + head)) / longer == pytest.approx(
        0.07, abs=0.005)
    # one step of 16,384 tokens at 55% of the peak: 0.79 s
    assert SEQ * want / (0.55 * 197e12) == pytest.approx(0.79, abs=0.01)


def test_state_is_13_14_gb_of_the_chip():
    d, ff = 4096, 11008
    layer = 4 * d * d + 3 * d * ff + 2 * d + 2 * 32 * 128
    assert layer == pytest.approx(202.4e6, rel=1e-3)
    # the program's table has 384 rows: 320 rounded up to whole lanes
    total = 4 * layer + 384 * d + d * 2560 + d
    assert total == pytest.approx(821.6e6, rel=1e-3)
    assert 16 * total == pytest.approx(13.15e9, rel=1e-3)
    assert 16 * total / 16e9 == pytest.approx(0.82, abs=0.005)
    import jax

    from ray_tpu.models.pretrain import init_params

    shapes = jax.eval_shape(
        lambda: init_params(evabyte.model_config(CONFIG, 1))[1])
    assert sum(int(a.size) for a in jax.tree_util.tree_leaves(shapes)) \
        == total


def test_kernel_and_pool_work():
    pairs = 32 * evabyte.live_pairs(SEQ, 2048, 16)
    sized, pooled = SEQ * 4096, 7 * 128 * 4096
    fwd = eva_work.flash_fwd_call(CONFIG, 1, rows=1, seq=SEQ)
    assert fwd["flops"] == 2 * 2 * pairs * 128
    assert fwd["bytes"] == 2 * (4 * sized + 2 * pooled)
    bwd = eva_work.flash_bwd_call(CONFIG, 1, rows=1, seq=SEQ)
    assert bwd["flops"] == 5 * 2 * pairs * 128
    assert bwd["bytes"] == 2 * (7 * sized + 4 * pooled)
    for work, ms in ((fwd, 2.0), (bwd, 5.0)):
        least, bound = flops.roofline_seconds(work, PEAK)
        assert bound == "compute"
        assert least == pytest.approx(ms * 1e-3, rel=0.05)
    pool = eva_work.pool_step(CONFIG, 1, rows=1, seq=SEQ)
    # what the scope times: the whole row's k and v in and its summaries
    # out, twice (forward and recomputation); those, the summaries'
    # cotangents in and k's and v's out
    summaries = sized // 16
    assert pool["bytes"] == 4 * 2 * (
        2 * (2 * sized + 2 * summaries) + 4 * sized + 2 * summaries)
    least, bound = flops.roofline_seconds(pool, PEAK)
    assert bound == "memory"
    assert least == pytest.approx(5.5e-3, rel=0.05)


def _ctx(scope="eva/"):
    """Two steps on one device: an EVA layer's pooling, kernels and the rest
    of its attention module as XLA names them, forward, recomputation and
    backward, beside another layer kind's window calls."""
    ops, t = [], 0.0
    stack = "jit(pretrain_step)/jvp(LlamaLMModel)/"
    back = "jit(pretrain_step)/transpose(jvp(LlamaLMModel))/" \
        "jvp(LlamaLMModel)/checkpoint/"
    call, fusion = "custom-call:tpu_custom_call", "fusion"
    pool = "pool/" if scope else "rope/"
    for step in range(2):
        for i, (kind, path, secs) in enumerate([
                (fusion, stack + "h_2/attn/wq/dot_general", 12e-3),
                (fusion, stack + "h_2/attn/rope/mul", 1e-3),
                (fusion, stack + f"h_2/attn/{pool}reduce_sum", 1e-3),
                (call, stack + f"h_2/attn/{scope}flash_fwd/flash_fwd/pallas_call", 4e-3),
                (fusion, back + f"rematted_computation/h_2/attn/{pool}reduce_sum", 1e-3),
                (fusion, back + f"h_2/attn/{pool}mul", 3e-3),
                (call, back + f"h_2/attn/{scope}flash_bwd/flash_bwd/pallas_call", 10e-3),
                (fusion, back + f"h_2/attn/{scope}flash_bwd/mul", 2e-3),
                (call, stack + "h_1/attn/window/flash_fwd/flash_fwd/pallas_call", 14e-3),
                (fusion, stack + "h_2/mlp/gate_proj/dot_general", 30e-3)]):
            ops.append(Op(f"op.{step}.{i}", kind, path, t, t + secs))
            t += secs
    trace = Trace(ops={0: ops}, spans=[("window", 0.0, t)])
    return Context(CELL, PEAK, {}, trace, traced_steps=2)


def _metric(name):
    with open(os.path.join(manifest.BENCH_DIR, "layer_metrics",
                           name + ".json")) as f:
        return json.load(f)


def _read(ctx, name):
    m = _metric(name)
    reader = {"trace_ops": trace_ops, "kernel_roofline": kernel_roofline,
              "scope_roofline": scope_roofline}[m["reader"]]
    return reader.read(ctx, **m["args"])


def test_the_new_metrics_on_a_synthetic_trace():
    ctx = _ctx()
    least = {f.__name__: flops.roofline_seconds(f(CONFIG, 1, 1, SEQ), PEAK)[0]
             for f in (eva_work.flash_fwd_call, eva_work.flash_bwd_call,
                       eva_work.pool_step)}
    # the kernels under eva/ alone, and not the window layer's
    assert _read(ctx, "eva_attn_ms_per_step") == pytest.approx(14.0)
    assert _read(ctx, "eva_attn_fwd_roofline") == pytest.approx(
        100 * least["flash_fwd_call"] / 4e-3)
    assert _read(ctx, "eva_attn_bwd_roofline") == pytest.approx(
        100 * least["flash_bwd_call"] / 10e-3)
    # forward, recomputation and backward of the pooling
    assert _read(ctx, "eva_pool_ms_per_step") == pytest.approx(5.0)
    assert _read(ctx, "eva_pool_roofline") == pytest.approx(
        100 * least["pool_step"] / 5e-3)
    # the accepted selections read the same calls, as calls of an attention
    # module under flash_bwd (with the passes beside the kernel there)
    assert _read(ctx, "flash_bwd_ms_per_step") == pytest.approx(12.0)
    assert _read(ctx, "flash_bwd_calls_per_step") == pytest.approx(1.0)
    assert _read(ctx, "flash_fwd_calls_per_step") == pytest.approx(2.0)


def test_a_program_without_the_scopes_reports_nothing():
    """A program that has neither scope (the parent's, on any cell it can
    run): the readers give None and do not raise."""
    ctx = _ctx(scope="")
    for name in NEW:
        assert _read(ctx, name) is None


CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"


@pytest.mark.skipif(not os.path.exists(CATALOG),
                    reason="the catalog is beside the builder's guides only")
def test_every_catalog_key_stands_in_the_file():
    """Every key of the catalog row's ``config`` under the same key, verbatim
    but the depth, which stands beside its published count."""
    with open(CATALOG) as f:
        row = next(r for r in map(json.loads, f) if r["name"] == "EvaByte")
    assert row["source_url"] == CONFIG["source"]
    assert CONFIG["reduced"] == ["num_hidden_layers"]
    for key, value in row["config"].items():
        if key in CONFIG["reduced"]:
            assert CONFIG["published_counts"][key] == value == 32
        else:
            assert key in CONFIG and CONFIG[key] == value, key
    assert CONFIG["num_hidden_layers"] == 4


def test_the_cell_as_the_manifest_has_it():
    bench = manifest.benchmark()
    entry = next(c for c in bench["configs"] if c["name"] == "evabyte")
    assert entry["reduced"] == CONFIG["reduced"]
    assert entry["file"] == "perfbench/configs/evabyte.json"
    assert CELL.chips == 1 and CELL.traffic["kind"] == "mtp_train_loop"
    assert CELL.traffic["rows_per_step"] == 1
    assert SEQ % CONFIG["window_size"] == 0 and SEQ >= 8192
    # by name, and at least these: later PRs list the cell under more
    on_at_least(bench, NAME, NEW)
    assert set(NEW) <= {m["name"] for m in CELL.per_layer}
    # the agreement's prefix: whole windows, and enough of them that the
    # last meets more than one tile of 128 summaries (the kernels' walk over
    # several, the clamp on the last visible one)
    prefix = CONFIG["reference"]["prefix"]
    assert prefix >= 3 * CONFIG["window_size"] \
        and prefix % CONFIG["window_size"] == 0
    # ISSUE 47's cell: the loss has to fall by 1.0, on a stream that can
    assert CELL.traffic["loss_fall_min"] == 1.0
    assert 0.0 < CELL.traffic["hold"] < 1.0
    for key in ("cut_why", "assumed", "program_departures", "dtypes",
                "cut_by_chips"):
        assert CONFIG[key], key
    for key in ("pooling", "summaries_visible", "pooling_init", "head_layout",
                "head_weights"):
        assert "alternative" in CONFIG["assumed"][key], key

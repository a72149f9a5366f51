"""``gated_norm_kernel_calls_per_step`` and ``gated_norm_kernel_ms_per_step``
(PR 64) on a synthetic trace whose name paths are as JAX gives them to the
calls of ``ops/gated_norm.py`` under ``Mamba2Mixer``'s scope: the Mosaic calls
under ``mamba/gated_norm`` — forward, the forward again under remat, the
backward — and nothing else the scope holds; a program whose norm runs the
reference's lines (the parent) reports nothing, while
``grouped_gated_norm_ms_per_step`` reads the scope on both.  A file of its own
because a PR that claims a gain edits no file the benchmark has."""

import json
import os

import pytest

from perfbench.harness import manifest
from perfbench.harness.readers import trace_ops
from perfbench.harness.readers.context import Context
from perfbench.harness.trace_reduce import Op, Trace

CELLS = ["granite-h-s8k-1chip", "nemotron3-nano-s16k-1chip"]
NEW = ["gated_norm_kernel_calls_per_step", "gated_norm_kernel_ms_per_step"]
FWD = "jit(pretrain_step)/jvp(LlamaLMModel)/"
REMAT = "jit(pretrain_step)/transpose(jvp(LlamaLMModel))/" \
    "rematted_computation/"
BWD = "jit(pretrain_step)/transpose(jvp(LlamaLMModel))/"
CALL, FUSION = "custom-call:tpu_custom_call", "fusion"


def _read(cell, ops, name, steps=2):
    with open(os.path.join(manifest.BENCH_DIR, "layer_metrics",
                           name + ".json")) as f:
        metric = json.load(f)
    assert metric["reader"] == "trace_ops"
    end = max(o.end for o in ops)
    ctx = Context(manifest.cell(cell), manifest.peaks()["TPU v5 lite"], {},
                  Trace(ops={0: ops}, spans=[("window", 0.0, end)]),
                  traced_steps=steps)
    return trace_ops.read(ctx, **metric["args"])


def _steps(layer):
    ops, t = [], 0.0
    for step in range(2):
        for i, (kind, path, secs) in enumerate(layer):
            ops.append(Op(f"op.{step}.{i}", kind, path, t, t + secs))
            t += secs
    return ops


@pytest.mark.parametrize("cell", CELLS)
def test_the_kernels_calls_and_nothing_else_of_the_scope(cell):
    norm = "h_2/mamba/gated_norm/"
    ops = _steps([
        (CALL, FWD + "h_2/mamba/ssd/ssd_fwd/pallas_call", 7e-3),
        (FUSION, FWD + "h_2/mamba/ssd/mul", 4e-4),
        (CALL, FWD + norm + "gated_norm_fwd/pallas_call", 65e-5),
        (FUSION, FWD + "h_2/mamba/out_proj/dot_general", 4e-3),
        (CALL, REMAT + norm + "gated_norm_fwd/pallas_call", 66e-5),
        (CALL, BWD + norm + "gated_norm_bwd/pallas_call", 103e-5),
        (FUSION, BWD + norm + "reduce_sum", 1e-5),
        (CALL, BWD + "h_2/mamba/conv/conv_silu_bwd/pallas_call", 2e-3)])
    assert _read(cell, ops, NEW[0]) == pytest.approx(3.0)
    assert _read(cell, ops, NEW[1]) == pytest.approx(0.65 + 0.66 + 1.03)
    # the scope's own metric holds the sum of dscale's rows too
    assert _read(cell, ops, "grouped_gated_norm_ms_per_step") == \
        pytest.approx(0.65 + 0.66 + 1.03 + 0.01)


@pytest.mark.parametrize("cell", CELLS)
def test_a_program_on_the_references_lines_reports_nothing(cell):
    """The parent of PR 64, and a group that is no whole lanes: fusions under
    the scope and no Mosaic call.  The readers give None and do not raise."""
    ops = _steps([
        (FUSION, FWD + "h_0/mamba/gated_norm/mul", 1e-3),
        (FUSION, BWD + "h_0/mamba/gated_norm/reduce_sum", 5e-3),
        (CALL, FWD + "h_0/mamba/conv/conv_silu_fwd/pallas_call", 1e-3)])
    for name in NEW:
        assert _read(cell, ops, name) is None, name
    assert _read(cell, ops, "grouped_gated_norm_ms_per_step") == \
        pytest.approx(6.0)


def test_the_manifest_lists_them_for_the_two_mamba_2_cells():
    per_layer = {m["name"]: m for m in manifest.benchmark()["per_layer"]}
    for name, unit in zip(NEW, ("calls", "ms")):
        assert per_layer[name] == {
            "name": name, "unit": unit, "better": "lower",
            "source": "device_trace", "layer": "kernels",
            "moves": "tokens_per_s_per_chip", "workloads": CELLS}

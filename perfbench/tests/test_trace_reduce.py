"""The interval arithmetic of ``trace_reduce.py`` on traces small enough to
check by hand, and on a recorded one (``fixtures/``)."""

import json
import os

import pytest

from perfbench.harness import trace_reduce as tr
from perfbench.harness.trace_reduce import Op, Trace
from perfbench.tests.recorded import mistral_step

FIXTURES = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "fixtures")


def _ops(*rows):
    """Rows of (instruction name, name path, start, end); the opcode is the
    name without its number."""
    return sorted((Op(n, n.split(".")[0], p, a, b) for n, p, a, b in rows),
                  key=lambda o: (o.start, -o.end))


def test_busy_union_counts_overlap_once():
    ops = _ops(("a", "", 0.0, 2.0), ("b", "", 1.0, 3.0), ("c", "", 5.0, 6.0))
    assert tr.busy_seconds(ops) == pytest.approx(4.0)
    assert tr.gaps(ops, (0.0, 7.0)) == [(3.0, 5.0), (6.0, 7.0)]


def test_self_time_under_nesting():
    # a while of 10 s whose body ran 3 + 4 s: 3 s are its own
    ops = _ops(("while.1", "", 0.0, 10.0), ("fusion.1", "x", 1.0, 4.0),
               ("fusion.2", "y", 5.0, 9.0), ("copy.1", "", 11.0, 12.0))
    got = {o.name: s for o, s in tr.self_seconds(ops)}
    assert got == pytest.approx({"while.1": 3.0, "fusion.1": 3.0,
                                 "fusion.2": 4.0, "copy.1": 1.0})
    assert [o.name for o in tr.leaves(ops)] == ["fusion.1", "fusion.2",
                                                "copy.1"]
    assert sum(got.values()) == pytest.approx(tr.busy_seconds(ops))


def test_gaps_are_named_by_the_innermost_host_span():
    idle = [(1.0, 4.0), (6.0, 7.0)]
    spans = [("window", 0.0, 10.0), ("step", 0.0, 5.0), ("sync", 2.0, 3.5),
             ("report", 6.2, 6.6)]
    got = tr.label_gaps(idle, spans)
    assert got == pytest.approx({"sync": 1.5, "step": 1.5, "report": 0.4,
                                 "none": 0.6})
    assert sum(got.values()) == pytest.approx(tr.total(idle))


def test_exposed_collective_arithmetic():
    ops = _ops(("all-gather-start.1", "", 0.0, 0.1),
               ("fusion.1", "", 0.1, 2.0),          # hides 1.9 s of it
               ("all-gather-done.1", "", 2.0, 3.0),  # 1 s nothing else runs
               ("reduce-scatter.7", "", 4.0, 5.0),   # synchronous: all exposed
               ("fusion.2", "", 5.0, 6.0))
    these = tr.async_intervals(ops, r"^(all-gather|reduce-scatter)")
    assert tr.async_intervals(ops, r"^fusion ") == [(0.1, 2.0), (5.0, 6.0)]
    assert sorted(these) == [(0.0, 3.0), (4.0, 5.0)]
    others = [(o.start, o.end) for o in ops if o.name.startswith("fusion")]
    assert tr.exposed_seconds(these, others) == pytest.approx(0.1 + 1.0 + 1.0)


def test_clip_and_json_round_trip():
    trace = Trace({0: _ops(("a", "p", 0.0, 2.0), ("b", "", 3.0, 5.0))},
                  [("window", 1.0, 4.0), ("step", 0.5, 1.5)])
    cut = trace.clipped(trace.window())
    assert [(o.start, o.end) for o in cut.ops[0]] == [(1.0, 2.0), (3.0, 4.0)]
    assert cut.spans == [("window", 1.0, 4.0), ("step", 1.0, 1.5)]
    again = Trace.from_json(cut.to_json())
    assert again.ops == cut.ops and again.spans == cut.spans


def test_instruction_names_and_opcodes():
    text = ('%attn.4 = (bf16[32,8192,128]{2,1,0:T(8,128)(2,1)}, f32[32,8192,'
            '128]{2,1,0:T(8,128)}) custom-call(bf16[32,8192,128]{2,1,0:T(8,128'
            ')(2,1)} %pad_maximum_fusion), custom_call_target="tpu_custom_call"')
    assert tr.parse_instruction(text) == ("attn.4",
                                          "custom-call:tpu_custom_call")
    assert tr.parse_instruction(
        "%fusion.348 = bf16[8192,14336]{1,0:T(8,128)(2,1)} fusion(f32[4096,"
        "14336]{1,0:T(8,128)} %p), kind=kOutput") == ("fusion.348", "fusion")
    assert tr.parse_instruction(
        "%while.2 = (s32[]{:T(128)}, f32[1,32]{1,0}) while((s32[]{:T(128)}, "
        "f32[1,32]{1,0}) %tuple.162), condition=%c, body=%b") == ("while.2",
                                                                  "while")


def _varint(n):
    out = bytearray()
    while True:
        out.append((n & 0x7F) | (0x80 if n > 0x7F else 0))
        n >>= 7
        if not n:
            return bytes(out)


def _field(number, value):
    if isinstance(value, int):
        return _varint(number << 3) + _varint(value)
    return _varint(number << 3 | 2) + _varint(len(value)) + value


def test_name_paths_from_the_metadata_of_an_xspace():
    """An XSpace encoded by hand, field numbers as in TSL's xplane.proto: one
    device plane whose event metadata carries a ``tf_op`` stat."""
    stat_meta = _field(1, 7) + _field(2, b"tf_op")
    other_meta = _field(1, 8) + _field(2, b"flops")
    path = b"jit(pretrain_step)/jvp(M)/h_0/attn/wq/dot_general:"
    event_meta = (_field(1, 3) + _field(2, b"%fusion.1 = f32[] fusion()")
                  + _field(5, _field(1, 8) + _field(3, 12345))
                  + _field(5, _field(1, 7) + _field(5, path)))
    line = _field(2, b"XLA Ops") + _field(4, _field(1, 3) + _field(2, 10))
    plane = (_field(1, 0) + _field(2, b"/device:TPU:0") + _field(3, line)
             + _field(4, _field(1, 3) + _field(2, event_meta))
             + _field(5, _field(1, 7) + _field(2, stat_meta))
             + _field(5, _field(1, 8) + _field(2, other_meta)))
    host = _field(2, b"/host:CPU") + _field(
        4, _field(1, 1) + _field(2, _field(2, b"bench/step")))
    got = tr._name_paths(_field(1, plane) + _field(1, host))
    assert got == {"/device:TPU:0": {
        "%fusion.1 = f32[] fusion()":
            "jit(pretrain_step)/jvp(M)/h_0/attn/wq/dot_general"}}


def _flash_forward(metric):
    """The selection of a flash-forward metric, from its own data file."""
    with open(os.path.join(os.path.dirname(FIXTURES), "layer_metrics",
                           metric + ".json")) as f:
        args = json.load(f)["args"]
    return {k: args[k] for k in ("op", "path", "not_path") if k in args}


def test_flash_forward_metrics_leave_every_other_kernel_out():
    """The three forward metrics select the same calls: the Mosaic calls
    under the scope ``flash_fwd``, with remat and without.  Not the backward
    kernel, and (PR 67) no other kernel under ``attn``: EvaByte's pooling
    kernels stood in ``flash_fwd_calls_per_step`` as eight of twelve, and a
    Pallas prelude beside the flash kernels would have read a roofline over
    100%."""
    from perfbench.harness.readers import trace_ops

    ops = mistral_step().ops[0]
    end = ops[-1].end
    bwd = [Op(f"attn.{90 + i}", "custom-call:tpu_custom_call", path,
              end + i, end + i + 0.5) for i, path in enumerate([
        # the backward of a rematted block, and of a plain one
        "jit(pretrain_step)/transpose(jvp(LlamaLMModel))/jvp(LlamaLMModel)/"
        "checkpoint/h_1/attn/flash_bwd/flash_bwd/pallas_call",
        "jit(pretrain_step)/transpose(jvp(GPT2LMModel))/h_3/attn/flash_bwd/"
        "flash_bwd/pallas_call",
        # other kernels of an attention layer: EVA's pooling, forward and
        # again inside the backward pass, and a prelude of its own
        "jit(pretrain_step)/jvp(LlamaLMModel)/h_1/attn/pool/pool_fwd/"
        "pallas_call",
        "jit(pretrain_step)/transpose(jvp(LlamaLMModel))/jvp(LlamaLMModel)/"
        "checkpoint/rematted_computation/h_1/attn/pool/pool_fwd/pallas_call",
        "jit(pretrain_step)/jvp(LlamaLMModel)/h_1/attn/rope/rope_fwd/"
        "pallas_call",
    ])]
    fwd = Op("attn.99", "custom-call:tpu_custom_call",
             "jit(pretrain_step)/jvp(GPT2LMModel)/h_3/attn/flash_fwd/"
             "flash_fwd/pallas_call", end + 5, end + 5.5)
    selections = [_flash_forward(m) for m in (
        "flash_fwd_ms_per_step", "flash_fwd_calls_per_step",
        "flash_fwd_roofline")]
    # the roofline's are the whole-row calls of these (PR 71: a call under
    # a window scope is window_attn_fwd_roofline's, against its own count)
    assert selections[0] == selections[1] \
        == {k: v for k, v in selections[2].items() if k != "not_path"}
    assert selections[2]["not_path"] == "/window/"
    assert "not_path" not in selections[0]
    every_call = trace_ops.selected(ops + bwd + [fwd],
                                    op=selections[0]["op"])
    assert len(every_call) == 4 + 6
    found = trace_ops.selected(ops + bwd + [fwd], **selections[0])
    assert [o.name for o, _ in found] == [
        "attn.4", "attn.5", "attn.6", "attn.7", "attn.99"]


def test_recorded_step_of_mistral_on_the_chip():
    """One step of ``mistral-s8k-1chip`` as the v5e's profiler recorded it
    (PR 22; 2 layers, seq 8192, remat): the readers' arithmetic on real
    nesting, real name paths and the real Mosaic calls."""
    from perfbench.harness.readers import trace_ops

    trace = mistral_step()  # its forward calls under today's scope
    ops, window = trace.ops[0], trace.window()
    busy = tr.busy_seconds(ops)
    assert busy == pytest.approx(0.57226, abs=1e-4)
    assert window[1] - window[0] == pytest.approx(0.57520, abs=1e-4)
    # self times partition the busy time: nothing counted twice under a while
    assert sum(s for _, s in tr.self_seconds(ops)) == pytest.approx(busy,
                                                                    rel=1e-6)
    # idle time is all named: here the device waits while the host feeds it
    idle = tr.gaps(ops, window)
    named = tr.label_gaps(idle, trace.spans)
    assert sum(named.values()) == pytest.approx(window[1] - window[0] - busy,
                                                rel=1e-6)
    # remat runs the flash forward twice a layer: 4 Mosaic calls of ~34 ms,
    # two of them recomputation inside the backward pass
    flash = trace_ops.selected(ops, **_flash_forward("flash_fwd_ms_per_step"))
    assert len(flash) == 4
    assert sum("/rematted_computation/" in o.path for o, _ in flash) == 2
    assert sum(s for _, s in flash) == pytest.approx(4 * 0.03397, rel=1e-3)
    # the XLA-scan backward of attention is two whiles whose bodies carry the
    # attn scope; projections are kept out of the attention share
    assert sum(1 for o in ops if o.kind == "while") == 2
    attn = trace_ops.selected(
        ops, path=r"/h_\d+/attn/",
        not_path=r"/attn/(wq|wk|wv|wo|qkv_proj|out_proj)/")
    share = sum(s for _, s in attn) / busy
    assert 0.50 < share < 0.60
    projections = trace_ops.selected(ops, path=r"/attn/(wq|wk|wv|wo)/")
    assert projections and not {o.name for o, _ in projections} \
        & {o.name for o, _ in attn}


# the one-chip per-layer metrics of the first benchmark (PR 22); a metric a
# later PR adds brings a test of its own
# (less the two host-span medians, retired at PR 71 with their reader)
FIRST_METRICS = {
    "step_build_s", "step_device_ms", "peak_hbm_gb", "attn_scope_share_pct", "flash_fwd_ms_per_step",
    "flash_fwd_calls_per_step", "flash_fwd_roofline", "device_idle_pct"}


def test_every_metric_file_reads_the_recorded_step():
    """Each per-layer metric of ``mistral-s8k-1chip`` through its own data
    file and reader, as the driver reads it, on the recorded step."""
    from perfbench.harness import driver, manifest
    from perfbench.harness.readers.context import Context

    cell = manifest.cell("mistral-s8k-1chip")
    trace = mistral_step()
    measured = {
        "memory": [{"peak_bytes_in_use": 8e9, "peak_bytes_reserved": 4e9}],
        "build_events": [
            ["/jax/core/compile/jaxpr_trace_duration", "pretrain_step", 0.75],
            ["/jax/core/compile/backend_compile_duration", "pretrain_step",
             0.5],
            ["/jax/core/compile/backend_compile_duration", "other", 9.0]]}
    ctx = Context(cell, manifest.peaks()["TPU v5 lite"], measured, trace,
                  traced_steps=1)
    got = {m["name"]: driver._read_metric(ctx, m["file"])
           for m in cell.per_layer if m["name"] in FIRST_METRICS}
    assert set(got) == FIRST_METRICS
    assert got["flash_fwd_calls_per_step"] == 4
    assert got["flash_fwd_ms_per_step"] == pytest.approx(4 * 33.97, rel=1e-3)
    # 2 * 2 * 32 heads * 8192^2 * 128 / 2 FLOPs a call at 197e12 a second
    assert got["flash_fwd_roofline"] == pytest.approx(
        100 * (2 * 2 * 32 * 8192 ** 2 * 128 / 2 / 197e12) / 0.03397, rel=1e-3)
    assert got["step_device_ms"] == pytest.approx(572.26, abs=0.1)
    assert got["device_idle_pct"] == pytest.approx(
        100 * (1 - 0.57226 / 0.57520), abs=0.02)
    assert 50 < got["attn_scope_share_pct"] < 60
    assert got["peak_hbm_gb"] == 12.0 and got["step_build_s"] == 1.25
    breakdown = driver._breakdown(ctx)
    assert len(breakdown["device_ops"]) == 10 and breakdown["idle_gaps"]

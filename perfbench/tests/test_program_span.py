"""The metrics that read what the program names itself (ISSUE 23): the
``program_span`` reader on the profiler's file a traced CPU rehearsal leaves
and on a hand-made trace, and the five ``trace_ops`` data files on a copy of
the recorded step with the new scopes written into its name paths.

No time read here is a device's: what is checked is arithmetic and names.
"""

import dataclasses
import functools
import json
import os
import re
import time

import pytest

from perfbench.harness import driver, manifest
from perfbench.harness import trace_reduce as tr
from perfbench.harness.readers import program_span
from perfbench.harness.readers.context import Context
from perfbench.harness.trace_reduce import Op, Trace
from perfbench.tests.recorded import mistral_step

NEW_SPAN_METRICS = {
    "report_handoff_ms_per_step", "report_self_ms_per_step",
    "data_pull_ms_per_step", "data_rebatch_ms_per_step",
    "data_device_put_ms_per_step", "shard_batch_ms_per_step",
    "dispatch_ms_per_step", "idle_in_trainer_pct", "idle_in_data_pct",
    "idle_in_step_host_pct", "idle_in_user_loop_pct"}
NEW_OPS_METRICS = {
    "flash_bwd_ms_per_step", "loss_ms_per_step", "optimizer_ms_per_step",
    "kv_repeat_ms_per_step", "unscoped_device_share_pct"}
IDLE_IN = ("idle_in_trainer_pct", "idle_in_data_pct", "idle_in_step_host_pct",
           "idle_in_user_loop_pct")


def _metric_file(name):
    with open(os.path.join(manifest.BENCH_DIR, "layer_metrics",
                           name + ".json")) as f:
        return json.load(f)


def _read(ctx, name):
    return driver._read_metric(ctx, _metric_file(name))


def test_the_new_metrics_are_in_the_benchmark_with_their_readers():
    entries = {m["name"]: m for m in manifest.benchmark()["per_layer"]}
    for name in NEW_SPAN_METRICS | NEW_OPS_METRICS:
        reader = "program_span" if name in NEW_SPAN_METRICS else "trace_ops"
        assert _metric_file(name)["reader"] == reader
        assert entries[name]["source"] == (
            "program_span" if name in NEW_SPAN_METRICS else "device_trace")
    loop_only = {n for n, m in entries.items()
                 if m.get("workloads") == ["gpt2s-loop-b8-s1k"]}
    assert loop_only == {n for n in NEW_SPAN_METRICS
                         if n.startswith("data_") or n == "idle_in_data_pct"}
    # a generator cell reports three idle_in_*, the loop cell all four
    reported = lambda cell: {m["name"] for m in  # noqa: E731
                             manifest.cell(cell).per_layer}
    assert reported("gpt2s-loop-b8-s1k") >= set(IDLE_IN)
    assert reported("gpt2s-b24-s1k") & set(IDLE_IN) == set(IDLE_IN) - {
        "idle_in_data_pct"}
    # the copy of K and V is since PR 52 the fallback of heads 64 wide: no
    # Mistral cell enters the scope, and the list says so since PR 67
    assert "kv_repeat_ms_per_step" not in reported("mistral-s8k-1chip")
    assert "kv_repeat_ms_per_step" not in reported("gpt2s-b24-s1k")
    assert "kv_repeat_ms_per_step" in reported("lfm2-s16k-1chip")


# ------------------------------------------- on a hand-made trace
WINDOW = (0.0, 10.0)
LOOP = [   # the loop thread: two steps, a report, a block pull
    ("ray_tpu/step", 0.5, 3.5), ("ray_tpu/step/shard_batch", 0.6, 1.0),
    ("ray_tpu/step/dispatch", 2.0, 3.2),
    ("ray_tpu/train/report", 4.2, 5.5),
    ("ray_tpu/train/report/heartbeat", 4.3, 4.4),
    ("ray_tpu/train/report/handoff_wait", 4.5, 5.4),
    ("ray_tpu/data/pull_block", 5.6, 5.9),
    ("ray_tpu/step", 9.5, 12.0),            # runs past the window
]
OTHER = [("ray_tpu/data/device_put", 1.0, 3.0)]     # a prefetch thread
BUSY = {0: [(0.0, 1.0), (3.0, 4.0), (6.0, 10.0)],   # idle 1-3 and 4-6
        1: [(0.0, 10.0)]}                           # never idle


@pytest.fixture
def hand_made(monkeypatch):
    """A context whose device operations and program spans are the tables
    above; the profiler's file is never opened."""
    monkeypatch.setattr(program_span, "xplane_of", lambda ctx: "hand-made")
    monkeypatch.setattr(program_span, "load",
                        lambda path: (tuple(LOOP), tuple(OTHER)))
    ops = {d: [Op(f"fusion.{i}", "fusion", "", a, b)
               for i, (a, b) in enumerate(rows)] for d, rows in BUSY.items()}
    trace = Trace(ops, [("window", *WINDOW)])
    return Context(manifest.cell("gpt2s-loop-b8-s1k"), {}, {}, trace,
                   traced_steps=2)


def test_idle_is_named_by_the_innermost_loop_thread_span():
    ops = {0: [Op("f", "fusion", "", a, b) for a, b in BUSY[0]]}
    got = program_span.idle_seconds(
        ops, WINDOW, Trace(spans=LOOP).clipped(WINDOW).spans)[0]
    assert got == pytest.approx({       # shard_batch, 0.6-1.0, was all busy
        "ray_tpu/step/dispatch": 1.0,               # 2-3
        "ray_tpu/step": 1.0,                        # 1-2
        "ray_tpu/train/report/handoff_wait": 0.9,
        "ray_tpu/train/report/heartbeat": 0.1,
        "ray_tpu/train/report": 0.3,                # 4.2-4.3, 4.4-4.5, 5.4-5.5
        "ray_tpu/data/pull_block": 0.3,
        "none": 0.4})                               # 4-4.2, 5.5-5.6, 5.9-6
    assert sum(got.values()) == pytest.approx(4.0)


def test_idle_in_each_layer_sums_to_the_device_idle(hand_made):
    got = {name: _read(hand_made, name) for name in IDLE_IN}
    # device 0 is idle 4 s of 10, device 1 never: shares are means over both
    assert got == pytest.approx({
        "idle_in_trainer_pct": 100 * 1.3 / 10 / 2,
        "idle_in_data_pct": 100 * 0.3 / 10 / 2,
        "idle_in_step_host_pct": 100 * 2.0 / 10 / 2,
        "idle_in_user_loop_pct": 100 * 0.4 / 10 / 2})
    assert sum(got.values()) == pytest.approx(_read(hand_made,
                                                    "device_idle_pct"))
    # the other thread's span covers the same idle seconds and names none
    assert program_span.read(hand_made, "idle_pct",
                             span="ray_tpu/data/device_put") is None


def test_every_quantity_of_a_span(hand_made):
    read = functools.partial(program_span.read, hand_made)
    # a sum over the window, per traced step; the last step is cut at 10.0
    assert read("ms_per_step", span="ray_tpu/step") == pytest.approx(
        (3.0 + 0.5) / 2 * 1e3)
    assert read("self_ms_per_step", span="ray_tpu/step") == pytest.approx(
        (3.0 - 0.4 - 1.2 + 0.5) / 2 * 1e3)
    assert read("self_ms_per_step",
                span="ray_tpu/train/report") == pytest.approx(
        (1.3 - 0.1 - 0.9) / 2 * 1e3)
    assert _read(hand_made, "report_self_ms_per_step") == pytest.approx(150.0)
    assert _read(hand_made, "report_handoff_ms_per_step") == pytest.approx(
        450.0)
    # whichever thread ran it
    assert _read(hand_made, "data_device_put_ms_per_step") == pytest.approx(
        1000.0)
    assert read("ms_per_step", prefix="ray_tpu/data/") == pytest.approx(
        (0.3 + 2.0) / 2 * 1e3)
    with pytest.raises(ValueError):
        read("p99_ms", span="ray_tpu/step")
    with pytest.raises(ValueError):
        read("ms_per_step")


def test_nothing_to_read_is_none(hand_made, monkeypatch):
    read = functools.partial(program_span.read, hand_made)
    assert read("ms_per_step", span="ray_tpu/train/report/persist") is None
    assert read("self_ms_per_step", prefix="ray_tpu/serve/") is None
    assert read("idle_pct", prefix="ray_tpu/serve/") is None
    # a program without the spans (the parent commit): every new metric None
    monkeypatch.setattr(program_span, "load", lambda path: ((),))
    assert {_read(hand_made, n) for n in NEW_SPAN_METRICS} == {None}
    # an untraced run, and a trace with no profiler file beside it
    monkeypatch.undo()
    bare = Context(hand_made.cell, {}, {}, hand_made.trace, traced_steps=2)
    assert program_span.read(bare, "ms_per_step", span="ray_tpu/step") is None
    bare.measured["trace"] = {"file": "/nonexistent/reduced.json", "steps": 2}
    assert program_span.read(bare, "idle_pct") is None


# ------------------------- on the profiler's file of a CPU rehearsal
@pytest.fixture(scope="module")
def rehearsal(tmp_path_factory):
    """The dataset toy cell, traced, on one virtual CPU device: the worker
    leaves the profiler's file beside the reduced trace, and this process —
    ``run.py``'s, which must initialise no backend — reads it."""
    import ray_tpu.train
    from ray_tpu.train.jax_config import JaxConfig
    from test_rehearsal import _toy_cell

    with pytest.MonkeyPatch.context() as patch:
        scaling = ray_tpu.train.ScalingConfig
        patch.setattr(ray_tpu.train, "ScalingConfig",
                      lambda num_workers, tpus_per_worker: scaling(
                          num_workers=num_workers))
        patch.setattr(ray_tpu.train, "JaxTrainer", functools.partial(
            ray_tpu.train.JaxTrainer,
            jax_config=JaxConfig(platform="cpu", cpu_devices_per_worker=1)))
        patch.setenv("RAY_TPU_TMPDIR",
                     str(tmp_path_factory.mktemp("ray_tpu")))
        # a name, and so a trace directory, of its own (driver.run_cell)
        cell = dataclasses.replace(_toy_cell("toy-gpt2", "toy-data", 1),
                                   name="toy-spans")
        m = driver.run_cell(cell, seed=5, seconds=2.0, trace=True,
                            t_start=time.time())
    with open(m["trace"]["file"]) as f:
        trace = Trace.from_json(f.read())
    return Context(cell, {}, m, trace, m["trace"]["steps"])


def test_the_rehearsal_leaves_every_span_and_no_backend(rehearsal):
    # reading the profiler's file initialises no backend; an earlier JAX
    # test of this process may have, which is not this test's to judge
    was = driver.backend_initialized()
    program_span.load.cache_clear()
    threads = program_span.load(program_span.xplane_of(rehearsal))
    assert driver.backend_initialized() == was
    names = {n for spans in threads for n, _, _ in spans}
    assert names == {
        "ray_tpu/train/report", "ray_tpu/train/report/heartbeat",
        "ray_tpu/train/report/handoff_wait", "ray_tpu/data/pull_block",
        "ray_tpu/data/rebatch", "ray_tpu/data/device_put", "ray_tpu/step",
        "ray_tpu/step/shard_batch", "ray_tpu/step/dispatch"}
    # the loop thread is first and holds them all; the traced window holds
    # the steps the traffic file says
    assert {n for n, _, _ in threads[0]} == names
    inside = Trace(spans=list(threads[0])).clipped(
        rehearsal.trace.window()).spans
    assert sum(1 for n, _, _ in inside if n == "ray_tpu/step") \
        == rehearsal.traced_steps


def test_span_metrics_of_the_rehearsal_add_up(rehearsal):
    got = {n: _read(rehearsal, n) for n in NEW_SPAN_METRICS
           if not n.startswith("idle_in_")}
    assert all(v is not None and v >= 0 for v in got.values()), got
    assert got["dispatch_ms_per_step"] > 0
    assert got["report_handoff_ms_per_step"] > 0
    read = functools.partial(program_span.read, rehearsal)
    report = read("ms_per_step", span="ray_tpu/train/report")
    heartbeat = read("ms_per_step", span="ray_tpu/train/report/heartbeat")
    assert got["report_self_ms_per_step"] == pytest.approx(
        report - heartbeat - got["report_handoff_ms_per_step"])
    step = read("ms_per_step", span="ray_tpu/step")
    assert read("self_ms_per_step", span="ray_tpu/step") == pytest.approx(
        step - got["shard_batch_ms_per_step"] - got["dispatch_ms_per_step"])
    # the benchmark's own spans cover the program's: bench/step the step,
    # bench/input the iterator, bench/report the report
    bench = {name: tr.total(tr.union(
        (a, b) for n, a, b in rehearsal.trace.spans if n == name))
        / rehearsal.traced_steps * 1e3 for name in ("step", "input", "report")}
    assert step <= bench["step"] and report <= bench["report"]
    assert read("ms_per_step", prefix="ray_tpu/data/") <= bench["input"]
    # the CPU backend has no device plane: nothing to call idle
    assert rehearsal.devices == []
    assert {_read(rehearsal, n) for n in IDLE_IN} == {None}


def test_a_device_idle_all_window_is_covered_span_by_span(rehearsal):
    """The rehearsal's spans under a made-up device that never runs anything:
    every second of the window is idle, so each layer's idle share is its
    spans' share of the window, and the four sum to 100."""
    window = rehearsal.trace.window()
    idle = Context(rehearsal.cell, {}, rehearsal.measured,
                   Trace({0: []}, [("window", *window)]),
                   rehearsal.traced_steps)
    got = {n: _read(idle, n) for n in IDLE_IN}
    assert sum(got.values()) == pytest.approx(100.0)
    assert all(v > 0 for v in got.values()), got
    per_step = idle.window_s / idle.traced_steps * 1e3
    for name, prefix in (("idle_in_trainer_pct", "ray_tpu/train/"),
                         ("idle_in_data_pct", "ray_tpu/data/"),
                         ("idle_in_step_host_pct", "ray_tpu/step")):
        assert got[name] == pytest.approx(100 * program_span.read(
            idle, "ms_per_step", prefix=prefix) / per_step)


# --------------------- the scopes, written into the recorded step
def _scoped(path):
    """A name path of the recorded step (PR 22, before the scopes) as the
    program of this PR names the same operation."""
    if re.fullmatch(r"jit\(pretrain_step\)/[a-z_]+", path):
        return path.replace("/", "/optimizer/", 1)
    path = re.sub(r"jvp\((jit\((log_softmax|take_along_axis)\))\)",
                  r"jvp(lm_loss)/\1", path)
    path = path.replace("jvp()/reduce_sum", "jvp(lm_loss)/reduce_sum")
    path = path.replace("/attn/pallas_call", "/attn/flash_fwd/pallas_call")
    path = path.replace("/attn/broadcast_in_dim",
                        "/attn/kv_repeat/broadcast_in_dim")
    if "transpose(" in path and "/rematted_computation/" not in path:
        path = re.sub(r"/attn/(while|gt|iota)\b", r"/attn/flash_bwd/\1", path)
    return re.sub(r"/attn/(cos|sin|pow)$", r"/attn/rope/\1", path)


@pytest.fixture(scope="module")
def recorded():
    before = mistral_step()     # its forward calls under today's scope
    after = Trace({0: [Op(o.name, o.kind, _scoped(o.path), o.start, o.end)
                       for o in before.ops[0]]}, before.spans)
    ctx = lambda t: Context(manifest.cell("mistral-s8k-1chip"),  # noqa: E731
                            manifest.peaks()["TPU v5 lite"], {}, t, 1)
    return ctx(before), ctx(after)


def _self_ms(ctx, chosen):
    return 1e3 * sum(s for o, s in tr.self_seconds(ctx.trace.ops[0])
                     if chosen(o.path))


def test_scope_metrics_on_the_recorded_step(recorded):
    before, after = recorded
    got = {n: _read(after, n) for n in NEW_OPS_METRICS}
    # each against the same operations chosen by their old, anonymous paths
    assert got["flash_bwd_ms_per_step"] == pytest.approx(_self_ms(
        before, lambda p: "transpose(" in p and "/rematted_computation/"
        not in p and re.search(r"/attn/(while|gt|iota)\b", p)))
    assert got["flash_bwd_ms_per_step"] > 170     # the three einsums alone
    assert got["loss_ms_per_step"] == pytest.approx(_self_ms(
        before, lambda p: "log_softmax" in p or "take_along_axis" in p
        or "jvp()/" in p))
    assert got["optimizer_ms_per_step"] == pytest.approx(_self_ms(
        before, lambda p: re.fullmatch(r"jit\(pretrain_step\)/[a-z_]+", p)))
    assert got["optimizer_ms_per_step"] > 26      # the ledger's "add"
    assert got["kv_repeat_ms_per_step"] == pytest.approx(_self_ms(
        before, lambda p: p.endswith("/attn/broadcast_in_dim")))
    busy = tr.busy_seconds(after.trace.ops[0])
    assert got["unscoped_device_share_pct"] == pytest.approx(
        100 * _self_ms(after, lambda p: not p or p.endswith(
            "jvp(LlamaLMModel)/iota")) / 1e3 / busy)
    # what had no name before the scopes: the optimizer and the loss too
    assert _read(before, "unscoped_device_share_pct") == pytest.approx(
        got["unscoped_device_share_pct"] + 100 * (
            got["optimizer_ms_per_step"] + got["loss_ms_per_step"])
        / 1e3 / busy)
    assert got["unscoped_device_share_pct"] < 2.0
    # a program without the scopes (the parent commit) reports none of them
    assert {_read(before, n) for n in NEW_OPS_METRICS
            - {"unscoped_device_share_pct"}} == {None}


def test_the_scopes_move_no_metric_the_benchmark_had(recorded):
    before, after = recorded
    for name in ("flash_fwd_ms_per_step", "flash_fwd_calls_per_step",
                 "flash_fwd_roofline", "attn_scope_share_pct",
                 "step_device_ms", "device_idle_pct"):
        assert _read(after, name) == _read(before, name), name
    groups = dict(driver._breakdown(after, top=40)["device_ops"])
    assert "optimizer" in groups
    assert "h_*/attn/flash_bwd/bhqd,bhkd->bhqk bwd" in groups
    assert not any(g == "add" or g.startswith("jvp(jit(") for g in groups)

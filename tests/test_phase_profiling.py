"""Phase-resolved task profiling + dashboard time series (observability
tentpole): phase histograms reach the Prometheus scrape, PHASES annotations
reach the state API / CLI / timeline / OTLP export, the dashboard serves a
multi-interval history ring buffer, and the satellite fixes (cancel-marker
eviction, recursive-cancel warning) hold."""

import json
import time
import urllib.request
import warnings

import pytest

import ray_tpu
from ray_tpu._private.taskfold import PHASE_ORDER


@pytest.fixture
def cluster():
    from conftest import ensure_shared_runtime

    yield ensure_shared_runtime()


def _wait_for_phases(name, task_id=None, timeout=30):
    """Poll the state API until a completed task row carries its phase
    breakdown (the PHASES annotation rides the periodic event flush)."""
    from ray_tpu.util import state

    deadline = time.monotonic() + timeout
    while time.monotonic() < deadline:
        for row in state.list_tasks(limit=100_000, name=name):
            if task_id is not None and row["task_id"] != task_id:
                continue
            if row.get("phases"):
                return row
        time.sleep(0.5)
    raise AssertionError(f"no PHASES annotation for {name!r} within {timeout}s")


def test_phase_breakdown_sums_to_roundtrip(cluster):
    """A sync round-trip's six phases are contiguous: they sum to ~the
    observed end-to-end latency (the acceptance bar for 'where does a sync
    call spend its time')."""

    @ray_tpu.remote
    def phased(x):
        return x + 1

    # warm: lease grant + worker boot must not ride the measured call
    assert ray_tpu.get(phased.remote(1), timeout=60) == 2

    t0 = time.perf_counter()
    ref = phased.remote(10)
    assert ray_tpu.get(ref, timeout=60) == 11
    e2e = time.perf_counter() - t0

    row = _wait_for_phases(phased._call_name, task_id=ref.oid.task_id().hex())
    phases = row["phases"]
    assert set(PHASE_ORDER) <= set(phases), phases
    total = sum(phases[p] for p in PHASE_ORDER)
    assert total > 0
    # generous bounds for loaded CI hosts; the phases cover submit -> the
    # completion landing on the driver IO loop (get()'s wake adds a hair)
    assert total <= e2e * 1.5 + 0.05, (total, e2e, phases)
    assert total >= e2e * 0.2, (total, e2e, phases)


def test_phase_summary_and_cli_profile(cluster, capsys):
    from ray_tpu.util import state

    @ray_tpu.remote
    def profiled():
        return 1

    refs = [profiled.remote() for _ in range(5)]
    assert ray_tpu.get(refs, timeout=60) == [1] * 5
    task_name = profiled._call_name
    _wait_for_phases(task_name)

    summary = state.summarize_task_phases(name=task_name)
    for p in PHASE_ORDER:
        assert p in summary, (p, summary)
        st = summary[p]
        assert st["count"] >= 1
        assert st["p50"] <= st["p95"] <= st["p99"]
        assert st["total"] >= st["p50"]

    from ray_tpu.scripts.cli import main as cli_main

    core = ray_tpu._private.worker.require_core()
    addr = f"{core._gcs_addr[0]}:{core._gcs_addr[1]}"
    assert cli_main(["profile", "--address", addr, "--name", task_name]) == 0
    out = capsys.readouterr().out
    assert "p50" in out and "p95" in out and "p99" in out
    for p in PHASE_ORDER:
        assert p in out
    assert cli_main(["summary", "tasks", "--address", addr]) == 0
    out = capsys.readouterr().out
    assert "profiled" in out
    assert "exec" in out  # phase table rides the summary too


def test_phase_histograms_in_metrics_scrape(cluster):
    """ray_tpu_task_phase_seconds reaches the nodelet's merged Prometheus
    scrape: driver-pushed submit/exec/wake phases AND the nodelet's own
    lease phases."""

    @ray_tpu.remote
    def tick():
        return 1

    assert ray_tpu.get(tick.remote(), timeout=60) == 1
    core = ray_tpu._private.worker.require_core()
    needed = ('ray_tpu_task_phase_seconds_bucket', 'phase="exec"',
              'phase="driver_stage"', 'phase="result_wake"',
              'phase="lease_queue"')
    deadline = time.monotonic() + 45  # driver pushes every ~5 s
    text = ""
    while time.monotonic() < deadline:
        text = core.io.run(core.nodelet_conn.call("get_metrics_text", None))
        if all(n in text for n in needed):
            break
        time.sleep(0.5)
    for n in needed:
        assert n in text, f"{n} missing from the scrape"
    assert "ray_tpu_task_phase_seconds_count" in text
    assert "ray_tpu_task_phase_seconds_sum" in text


def test_timeline_phase_subslices(cluster, tmp_path):
    from ray_tpu.util import state

    @ray_tpu.remote
    def sliced():
        return 1

    ref = sliced.remote()
    assert ray_tpu.get(ref, timeout=60) == 1
    task_name = sliced._call_name
    _wait_for_phases(task_name, task_id=ref.oid.task_id().hex())

    trace = state.timeline()
    phase_ev = [e for e in trace if e.get("cat") == "task_phase"
                and e["name"].startswith(f"{task_name}:")]
    assert phase_ev, "no phase sub-slices in timeline()"
    names = {e["name"] for e in phase_ev}
    assert f"{task_name}:exec" in names
    for e in phase_ev:
        assert e["ph"] == "X" and e["dur"] > 0
    # the sub-slices lie inside a plausible window around the task slice
    task_ev = [e for e in trace if e.get("cat") == "task"
               and e["name"] == task_name]
    assert task_ev
    # round-trips through the file writer as valid JSON
    path = tmp_path / "tl.json"
    state.timeline(str(path))
    json.loads(path.read_text())


def test_otlp_export_carries_phase_events(cluster, tmp_path):
    from ray_tpu.util import tracing

    @ray_tpu.remote
    def traced():
        return 1

    ref = traced.remote()
    assert ray_tpu.get(ref, timeout=60) == 1
    _wait_for_phases(traced._call_name, task_id=ref.oid.task_id().hex())

    path = tmp_path / "otlp.json"
    n = tracing.export_otlp(str(path))
    assert n > 0
    doc = json.loads(path.read_text())
    spans = doc["resourceSpans"][0]["scopeSpans"][0]["spans"]
    ev_names = {e["name"] for s in spans for e in s.get("events", ())}
    assert "phase.exec" in ev_names, sorted(ev_names)[:20]


def test_dashboard_history_ring_buffer(cluster):
    """/api/history serves >=2 samples after two scrape intervals, each with
    node utilization + task-state counts, and the page ships the sparkline
    renderer that draws them (a past stall stays visible after it ends)."""
    import asyncio
    import threading

    from ray_tpu.dashboard import Dashboard

    core = ray_tpu._private.worker.require_core()
    dash = Dashboard(tuple(core._gcs_addr), history_interval_s=0.3)

    port_holder = {}
    started = threading.Event()

    def run_loop():
        loop = asyncio.new_event_loop()
        asyncio.set_event_loop(loop)

        async def main():
            port_holder["port"] = await dash.serve(port=0)
            started.set()
            await asyncio.Event().wait()

        try:
            loop.run_until_complete(main())
        except RuntimeError:
            pass

    t = threading.Thread(target=run_loop, daemon=True)
    t.start()
    assert started.wait(30)
    port = port_holder["port"]

    @ray_tpu.remote
    def busy():
        return 1

    assert ray_tpu.get(busy.remote(), timeout=60) == 1

    def get(path):
        with urllib.request.urlopen(
                f"http://127.0.0.1:{port}{path}", timeout=30) as r:
            return json.loads(r.read())

    deadline = time.monotonic() + 30
    data = {"samples": []}
    while time.monotonic() < deadline:
        data = get("/api/history")
        if len(data["samples"]) >= 2:
            break
        time.sleep(0.3)
    assert len(data["samples"]) >= 2, "ring buffer never reached 2 samples"
    assert data["interval_s"] == pytest.approx(0.3)
    last = data["samples"][-1]
    assert last["ts"] > 0
    assert last["nodes"], "no per-node utilization in the sample"
    for util in last["nodes"].values():
        assert set(util) == {"cpu_frac", "mem_frac", "store_frac"}
    assert isinstance(last["tasks"], dict)
    # samples accumulate monotonically in time
    ts = [s["ts"] for s in data["samples"]]
    assert ts == sorted(ts)

    with urllib.request.urlopen(f"http://127.0.0.1:{port}/", timeout=30) as r:
        page = r.read().decode()
    assert "function spark" in page and "/api/history" in page


def test_cancel_marker_oldest_first_eviction(cluster):
    """VERDICT #9: the cancelled-before-start marker bound must evict
    OLDEST first — a still-pending recent cancel survives a flood of >4096
    markers; with the old arbitrary set.pop() it could be forgotten."""
    core = ray_tpu._private.worker.require_core()
    saved_set = set(core._cancelled_exec)
    try:
        core._cancelled_exec.clear()
        core._cancelled_exec_order.clear()

        pending = b"P" * 24
        # through the real RPC handler: the marker wiring, not just the helper
        core.io.run(core.rpc_cancel_task(None, {"task_id": pending}))
        assert pending in core._cancelled_exec

        # flood within the window: the pending cancel must hold
        for i in range(4000):
            core._mark_cancelled_exec(b"%024d" % i)
        assert pending in core._cancelled_exec

        # flood past the bound: the OLDEST markers (ours included) age out,
        # the newest 4096 survive, and the set stays bounded
        for i in range(4000, 8200):
            core._mark_cancelled_exec(b"%024d" % i)
        assert pending not in core._cancelled_exec
        assert (b"%024d" % 8199) in core._cancelled_exec
        assert (b"%024d" % 4200) in core._cancelled_exec  # 4096th-newest
        assert len(core._cancelled_exec) <= 4096
        # consumed markers (discarded at task start) don't pin deque growth
        for i in range(4000, 8200):
            core._cancelled_exec.discard(b"%024d" % i)
        for i in range(20_000):
            core._mark_cancelled_exec(b"%024x" % i)
        assert len(core._cancelled_exec_order) <= 4 * 4096 + 4096
    finally:
        core._cancelled_exec.clear()
        core._cancelled_exec_order.clear()
        core._cancelled_exec.update(saved_set)


def test_recursive_cancel_warns_once(cluster):
    """ADVICE low: cancel(recursive=True) warns exactly once per process
    that child propagation is unimplemented."""

    @ray_tpu.remote
    def quick():
        return 1

    ref = quick.remote()
    assert ray_tpu.get(ref, timeout=60) == 1  # finished: cancel is a no-op

    ray_tpu._warned_recursive_cancel = False
    with warnings.catch_warnings(record=True) as caught:
        warnings.simplefilter("always")
        ray_tpu.cancel(ref)  # default recursive=True
        ray_tpu.cancel(ref)  # second call must stay silent
        ray_tpu.cancel(quick.remote(), recursive=False)  # never warns
    msgs = [w for w in caught if "recursive=True" in str(w.message)]
    assert len(msgs) == 1, [str(w.message) for w in caught]

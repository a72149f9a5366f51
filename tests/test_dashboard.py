"""Dashboard REST endpoints against a live cluster (reference:
python/ray/dashboard head + api modules)."""

import json
import threading
import urllib.request

import pytest

import ray_tpu


@pytest.fixture
def cluster():
    from conftest import ensure_shared_runtime

    yield ensure_shared_runtime()


def _start_dashboard():
    """Run a Dashboard on a daemon thread; returns (dash, port)."""
    import asyncio

    from ray_tpu.dashboard import Dashboard

    core = ray_tpu._private.worker.require_core()
    dash = Dashboard(tuple(core._gcs_addr))

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
    return dash, port_holder["port"]


def test_dashboard_endpoints(cluster):
    dash, port = _start_dashboard()

    @ray_tpu.remote
    class Marker:
        def ping(self):
            return 1

    m = Marker.options(name="dash-marker").remote()
    assert ray_tpu.get(m.ping.remote(), timeout=30) == 1

    def get(path):
        with urllib.request.urlopen(
                f"http://127.0.0.1:{port}{path}", timeout=30) as r:
            return json.loads(r.read())

    nodes = get("/api/nodes")
    assert nodes and any(n["alive"] for n in nodes)
    actors = get("/api/actors")
    assert any(a.get("name") == "dash-marker" for a in actors)
    status = get("/api/cluster_status")
    assert "pending_demand" in status
    jobs = get("/api/jobs")
    assert isinstance(jobs, list)

    # task table: the marker's ping must appear with a full lifecycle
    import time as _t

    deadline = _t.time() + 30
    while _t.time() < deadline:
        tasks = get("/api/tasks?limit=1000")
        if any(t["name"] == "ping" and t["state"] == "FINISHED"
               for t in tasks):
            break
        _t.sleep(0.5)
    else:
        raise AssertionError(f"Marker.ping never FINISHED in /api/tasks: "
                             f"{[t['name'] for t in tasks][:20]}")
    summary = get("/api/task_summary")
    assert "ping" in summary

    # per-node utilization parsed from the nodelet metric registries
    metrics = get("/api/node_metrics")
    alive = [n for n in nodes if n["alive"]]
    assert any(n["node_id"] in metrics for n in alive)
    some = next(m for m in metrics.values())
    assert some["mem_frac"] is None or 0 <= some["mem_frac"] <= 1

    # log browser: list + tail through the dashboard
    node_id = alive[0]["node_id"]
    files = get(f"/api/logs?node_id={node_id}")
    assert isinstance(files, list) and files, "no log files listed"
    tail = get(f"/api/log?node_id={node_id}&name={files[0]['name']}")
    assert "text" in tail

    with urllib.request.urlopen(f"http://127.0.0.1:{port}/", timeout=30) as r:
        assert b"ray_tpu" in r.read()
    ray_tpu.kill(m)


def test_history_endpoint_shapes(cluster):
    """/api/history must serve well-formed series for an EMPTY ring buffer
    (fresh dashboard) and a PARTIALLY-FILLED one (samples predating the
    library series carry no serve/data/train keys)."""
    import time as _t

    dash, port = _start_dashboard()

    def get(path):
        with urllib.request.urlopen(
                f"http://127.0.0.1:{port}{path}", timeout=30) as r:
            return json.loads(r.read())

    # empty ring: the loop may not have ticked yet — force emptiness
    dash._history.clear()
    out = get("/api/history")
    assert isinstance(out["interval_s"], (int, float))
    assert out["samples"] == []

    # partially filled: an old-format sample (no library keys) next to a
    # full one must both serialize and keep their fields
    dash._history.clear()
    dash._history.append({"ts": _t.time(), "nodes": {}, "tasks": {}})
    dash._history.append({
        "ts": _t.time(), "nodes": {"n1": {"cpu_frac": 0.5}},
        "tasks": {"RUNNING": 2},
        "serve": {"a/D": {"requests": 3, "queue": 1, "replicas": 1}},
        "data": {}, "train": {},
    })
    out = get("/api/history")
    assert len(out["samples"]) == 2
    assert "serve" not in out["samples"][0]
    assert out["samples"][1]["serve"]["a/D"]["requests"] == 3
    assert out["samples"][1]["nodes"]["n1"]["cpu_frac"] == 0.5

    # library view endpoints: well-formed shells on an idle cluster
    assert isinstance(get("/api/serve"), dict)
    data_view = get("/api/data")
    assert set(data_view) == {"operators", "pipelines", "iterator"}
    assert isinstance(get("/api/train"), dict)
    assert isinstance(get("/api/llm"), dict)


def test_state_log_api(cluster):
    """Driver-side `ray logs` equivalent (reference: util/state get_log)."""
    from ray_tpu.util import state

    files = state.list_logs()
    assert isinstance(files, list)
    if files:
        text = state.get_log(files[0]["name"], tail=1024)
        assert isinstance(text, str)


def test_critical_path_and_flamegraph_endpoints(cluster):
    """/api/critical_path renders a real trace's chain; /api/flamegraph and
    /flamegraph.svg serve the profiler aggregate (well-formed even when
    profiling is off and the aggregate is empty — ISSUE 18)."""
    import time as _t

    from ray_tpu.util.tracing import trace_span

    dash, port = _start_dashboard()

    @ray_tpu.remote
    def dash_cpath_child(x):
        return x * 3

    with trace_span("dash-cpath") as span:
        tid = span.trace_id
        assert ray_tpu.get(dash_cpath_child.remote(2), timeout=30) == 6

    def get(path):
        with urllib.request.urlopen(
                f"http://127.0.0.1:{port}{path}", timeout=30) as r:
            return json.loads(r.read())

    deadline = _t.time() + 30
    out = None
    while _t.time() < deadline:
        try:
            out = get(f"/api/critical_path?trace_id={tid}")
        except urllib.error.HTTPError:
            out = None  # 500 until the trace's spans all land
        if out and {"dash-cpath", "dash_cpath_child"} <= {
                n["name"].rsplit(".", 1)[-1] for n in out["nodes"]}:
            break
        _t.sleep(0.5)
    assert out is not None, "critical_path endpoint never served the trace"
    assert abs(sum(out["buckets"].values()) - out["path_s"]) < 5e-6
    assert out["on_path_span_ids"]

    flame = get("/api/flamegraph")
    assert isinstance(flame["collapsed"], list)
    from ray_tpu._private.profiler import parse_collapsed

    parse_collapsed(flame["collapsed"])  # valid collapsed format (or empty)

    with urllib.request.urlopen(
            f"http://127.0.0.1:{port}/flamegraph.svg", timeout=30) as r:
        assert r.headers["Content-Type"].startswith("image/svg+xml")
        body = r.read()
    assert body.startswith(b"<svg")


def test_hangs_and_stacks_endpoints(cluster):
    """/api/hangs is well-formed when nothing hangs; /api/stacks serves the
    GCS-proxied per-node thread dumps (ISSUE 3 live-introspection layer)."""
    dash, port = _start_dashboard()

    def get(path):
        with urllib.request.urlopen(
                f"http://127.0.0.1:{port}{path}", timeout=30) as r:
            return json.loads(r.read())

    hangs = get("/api/hangs")
    assert isinstance(hangs, list)
    for h in hangs:  # flagged rows (if an earlier suite left one) are shaped
        assert {"task_id", "elapsed_s", "stack"} <= set(h)
    stacks = get("/api/stacks")
    assert isinstance(stacks, list) and stacks
    for node in stacks:
        assert "node_id" in node and "workers" in node
        for w in node["workers"]:
            assert isinstance(w["threads"], list)

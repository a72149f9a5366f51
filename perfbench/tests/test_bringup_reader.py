"""The ``bringup`` reader on rings written through the program's own
``flight_recorder`` into a temporary session directory: every argument
combination the eleven set-up metrics use (thirteen until PR 71 retired
``tpu_client_s`` and ``bringup_gap_s``), nothing where a record is
absent, and the train worker told from another worker by ``train_fn_enter``.

The times are made up; what is checked is which records each metric reads.
"""

import json
import os

import pytest

from perfbench.harness import manifest
from perfbench.harness.readers import bringup
from ray_tpu._private import flight_recorder as fr

# (kind, seconds or None for a point, detail, the stamp = the end)
DRIVER = [
    ("bringup.init.gcs_spawn", 1.0, "", 101.1),
    ("bringup.init.nodelet_spawn", 1.5, "", 102.7),
    ("bringup.init.driver_connect", 0.2, "", 102.9),
    ("bringup.init", 3.0, "", 103.0),
    ("bringup.gang.placement_group", 0.1, "", 104.1),
    ("bringup.gang.actors", 5.0, "", 109.1),
    ("bringup.gang.backend", 4.8, "", 113.9),
    ("bringup.gang", 10.0, "", 114.0),
    ("bringup.session", 0.5, "", 114.5),
    ("task.start", None, "not a mark", 114.6),
]
NODELET = [
    ("bringup.worker_spawn", 1.0, "w-pooled", 100.9),
    ("bringup.worker_spawn", 2.0, "w-train", 106.2),
    ("lease.grant", None, "not a mark", 106.3),
]
TRAIN_WORKER = [
    ("bringup.worker.imports", 1.2, "", 105.9),
    ("bringup.worker.connect", 0.1, "", 106.1),
    ("bringup.worker.actor", 3.0, "TrainWorker", 109.0),
    ("bringup.worker.jax_import", 0.0, "", 109.2),
    ("bringup.worker.distributed_init", 0.5, "", 109.8),
    ("bringup.worker.tpu_client", 4.0, "", 113.8),
    ("bringup.worker.train_fn_enter", 0.0, "", 114.4),
    ("compile", 0.4, "jaxpr_trace_duration|init_state", 115.0),
    ("compile.cache", None, "miss", 116.0),
    ("compile", 1.5, "backend_compile_duration|jit(init_state)", 116.0),
    ("bringup.state_init", 2.0, "", 116.5),
    ("compile", 0.6, "jaxpr_trace_duration|pretrain_step", 117.5),
    # traced inside pretrain_step's trace: covered once, not added
    ("compile", 0.2, "jaxpr_trace_duration|_flash_backward", 117.3),
    ("compile", 0.3, "jaxpr_to_mlir_module_duration|jit(pretrain_step)", 118.0),
    ("compile.cache", None, "hit", 122.0),
    ("compile", 3.0, "cache_retrieval_time_sec|", 122.0),
    ("compile", 3.5, "backend_compile_duration|jit(pretrain_step)", 122.0),
    ("compile.cache", None, "miss", 123.0),
]
POOLED_WORKER = [       # registered first, never trained: read by no metric
    ("bringup.worker.imports", 0.5, "", 100.8),
    ("compile", 7.0, "backend_compile_duration|jit(other)", 112.0),
    ("compile.cache", None, "miss", 112.0),
]
EXPECTED = {
    "runtime_start_s": 3.0, "nodelet_spawn_s": 1.5, "worker_spawn_s": 2.0,
    "worker_imports_s": 1.2, "gang_start_s": 10.0, "session_start_s": 0.5,
    "jax_import_s": 0.0, "state_init_s": 2.0,
    "setup_trace_s": 1.3, "setup_compile_s": 2.0, "setup_cache_misses": 2.0,
}


def _ring(session_dir, name, rows):
    assert fr.init_process(session_dir, name)
    for kind, seconds, detail, end in rows:
        if seconds is not None:
            detail = f"{seconds:.6f}|{detail}" if detail else f"{seconds:.6f}"
        fr.record(kind, detail, ts=end)
    fr.shutdown()


@pytest.fixture
def session(tmp_path):
    bringup.timeline.cache_clear()
    fr.shutdown()
    _ring(str(tmp_path), "driver", DRIVER)
    _ring(str(tmp_path), "nodelet-n", NODELET)
    _ring(str(tmp_path), "w-pooled", POOLED_WORKER)
    _ring(str(tmp_path), "w-train", TRAIN_WORKER)
    yield str(tmp_path)
    bringup.timeline.cache_clear()


def _args(name):
    with open(os.path.join(manifest.BENCH_DIR, "layer_metrics",
                           name + ".json")) as f:
        metric = json.load(f)
    assert metric["reader"] == "bringup" and set(metric) <= {
        "reader", "args", "note"}
    return metric["args"]


def test_the_manifest_has_the_eleven():
    entries = {m["name"]: m for m in manifest.benchmark()["per_layer"]
               if m["name"] in EXPECTED}
    assert set(entries) == set(EXPECTED)
    for m in entries.values():
        assert m["moves"] == "setup_s" and m["better"] == "lower"
        assert "workloads" not in m     # every cell starts the same way
    counted = {"setup_trace_s", "setup_compile_s", "setup_cache_misses"}
    assert {n for n, m in entries.items()
            if m["source"] == "program_counter"} == counted
    assert all(m["source"] == "program_span" for n, m in entries.items()
               if n not in counted)


@pytest.mark.parametrize("name", sorted(EXPECTED))
def test_each_metric_reads_its_records(session, name):
    value = bringup.read(None, session_dir=session, **_args(name))
    assert value == pytest.approx(EXPECTED[name])


def test_the_session_is_the_newest_under_the_runtimes_tmpdir(
        session, tmp_path, monkeypatch):
    root = tmp_path / "root"
    for i, name in enumerate(("session_1_1", "session_2_2")):
        (root / name).mkdir(parents=True)
        os.utime(root / name, (1000 + i, 1000 + i))
    monkeypatch.setenv("RAY_TPU_TMPDIR", str(root))
    assert bringup.newest_session() == str(root / "session_2_2")
    assert bringup.read(None, **_args("runtime_start_s")) is None  # no ring
    monkeypatch.setenv("RAY_TPU_TMPDIR", str(root / "nothing"))
    assert bringup.newest_session() is None
    assert bringup.read(None, **_args("gang_start_s")) is None


@pytest.mark.parametrize("name", sorted(EXPECTED))
def test_nothing_where_the_record_is_absent(tmp_path, name):
    """A session in which only the driver's ring exists and no train
    function was entered: the driver's own marks read, the rest is None —
    a count too, which is 0 only where the train worker was found."""
    bringup.timeline.cache_clear()
    fr.shutdown()
    _ring(str(tmp_path), "driver", DRIVER[:4])
    value = bringup.read(None, session_dir=str(tmp_path), **_args(name))
    of_the_driver = {"runtime_start_s": 3.0, "nodelet_spawn_s": 1.5}
    assert value == of_the_driver.get(name)
    bringup.timeline.cache_clear()


def test_a_warm_run_counts_no_miss(tmp_path):
    bringup.timeline.cache_clear()
    fr.shutdown()
    _ring(str(tmp_path), "w-train",
          [r for r in TRAIN_WORKER if r[2] != "miss"])
    assert bringup.read(None, session_dir=str(tmp_path),
                        **_args("setup_cache_misses")) == 0.0
    bringup.timeline.cache_clear()


def test_a_program_without_the_timeline_gives_nothing(session, monkeypatch):
    """The parent commit's recorder has no ``bringup_timeline``: every metric
    is left out of the line, and nothing raises."""
    monkeypatch.delattr(fr, "bringup_timeline")
    bringup.timeline.cache_clear()
    for name in EXPECTED:
        assert bringup.read(None, session_dir=session, **_args(name)) is None


def test_an_unknown_reduction_is_an_error(session):
    with pytest.raises(ValueError):
        bringup.read(None, session_dir=session, mark="bringup.init",
                     as_="median")


def test_a_list_of_marks_is_their_union(session):
    """``mark`` as a list: the seconds the kinds' records cover together
    (what ``tpu_client_s`` read, until PR 71, of the client's two marks)."""
    assert bringup.read(None, session_dir=session, mark=[
        "bringup.worker.tpu_client", "bringup.worker.distributed_init"]) \
        == pytest.approx(4.5)

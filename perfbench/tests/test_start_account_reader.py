"""The ``start_account`` reader on rings written through the program's own
``flight_recorder`` into a temporary session directory: each ``key`` the three
metrics of PR 68 use, nothing where the path ends nowhere, where no train
worker has reported or where the program has no ``start_account``; the two
metrics of PR 68 that go through reader ``bringup``; and the manifest with
them in it.

The times are made up; what is checked is which records each metric reads.
"""

import collections
import json
import os

import pytest

from perfbench.harness import manifest
from perfbench.harness.readers import bringup, start_account
from ray_tpu._private import flight_recorder as fr

COST = "cpu=0.500000 majflt=2 inblock=64"
# (kind, seconds or None for a point, detail, the stamp = the end)
DRIVER = [
    ("bringup.init", 3.0, COST, 103.0),
    ("bringup.gang", 10.0, COST, 114.0),            # 103..104 under no mark
    ("bringup.session", 0.5, COST, 114.5),
]
TRAIN_WORKER = [
    ("bringup.worker.tpu_client", 4.0, COST, 113.0),
    ("bringup.worker.train_fn_enter", 0.0, "", 114.4),
    ("bringup.trainer_build", 1.25, COST, 116.0),   # 114.4..114.75 under none
    ("bringup.state_init", 2.0, COST, 118.0),
    ("compile", 0.6, "jaxpr_trace_duration|pretrain_step", 119.0),
    ("compile", 3.0, "cache_retrieval_time_sec|", 122.0),
    ("compile", 3.5, "backend_compile_duration|jit(pretrain_step)", 122.0),
    ("compile", 0.5, "cache_retrieval_time_sec|", 122.75),
    ("bringup.first_run", 5.0, "pretrain_step|" + COST, 123.0),
    ("bringup.first_report", None, "", 124.0),      # 123..124 under none
    ("bringup.state_init", 9.0, COST, 140.0),       # after the start's end
]
EXPECTED = {
    "time_to_first_report_s": 24.0,
    "start_unnamed_s": 1.0 + 0.35 + 1.0,
    "tpu_client_off_cpu_s": 3.5,
    "setup_program_load_s": 3.5,
    "trainer_build_s": 1.25,
}
READERS = {"start_account": start_account, "bringup": bringup}


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
    start_account.account.cache_clear()
    fr.shutdown()
    _ring(str(tmp_path), "driver", DRIVER)
    _ring(str(tmp_path), "w-train", TRAIN_WORKER)
    yield str(tmp_path)
    bringup.timeline.cache_clear()
    start_account.account.cache_clear()


def _metric(name):
    with open(os.path.join(manifest.BENCH_DIR, "layer_metrics",
                           name + ".json")) as f:
        metric = json.load(f)
    assert set(metric) == {"reader", "args", "note"}
    return metric


@pytest.mark.parametrize("name", sorted(EXPECTED))
def test_each_metric_reads_its_records(session, name):
    metric = _metric(name)
    value = READERS[metric["reader"]].read(None, session_dir=session,
                                           **metric["args"])
    assert value == pytest.approx(EXPECTED[name])


@pytest.mark.parametrize("key, value", [
    ("total", 24.0), ("named/bringup.trainer_build", 1.25),
    ("named/compile|cache_retrieval_time_sec", 3.5),
    ("named/bringup.first_run", 0.9),       # what its compile records leave
    ("marks/bringup.first_run/cpu", 0.5),
    ("marks/bringup.worker.tpu_client/inblock", 64.0),
    ("marks/bringup.worker.tpu_client/seconds", 4.0),
    ("marks/bringup.worker.nothing/off_cpu", None), ("marks", None),
    ("named/bringup.worker.train_fn_enter", None), ("nothing", None)])
def test_a_key_is_a_path_into_the_account(session, key, value):
    assert start_account.read(None, key, session_dir=session) == (
        value if value is None else pytest.approx(value))


def test_nothing_before_a_first_report(tmp_path):
    start_account.account.cache_clear()
    fr.shutdown()
    _ring(str(tmp_path), "w-train", TRAIN_WORKER[:4])
    for key in ("total", "unnamed"):
        assert start_account.read(None, key, session_dir=str(tmp_path)) is None
    assert start_account.read(None, "total",
                              session_dir=str(tmp_path / "none")) is None
    start_account.account.cache_clear()


def test_nothing_where_the_program_has_no_account(session, monkeypatch):
    """The parent of PR 68: ``flight_recorder`` without ``start_account``."""
    monkeypatch.delattr(fr, "start_account")
    start_account.account.cache_clear()
    for name in ("time_to_first_report_s", "start_unnamed_s",
                 "tpu_client_off_cpu_s"):
        assert start_account.read(None, session_dir=session,
                                  **_metric(name)["args"]) is None


def test_the_manifest_has_the_five():
    bench = manifest.benchmark()
    entries = {m["name"]: m for m in bench["per_layer"]
               if m["name"] in EXPECTED}
    assert set(entries) == set(EXPECTED)
    # by name and in their order among themselves, wherever a later PR's
    # entries stand (PR 70 appended one behind them, PR 71 retired others)
    assert [m["name"] for m in bench["per_layer"] if m["name"] in EXPECTED] \
        == ["time_to_first_report_s", "start_unnamed_s",
            "tpu_client_off_cpu_s", "setup_program_load_s", "trainer_build_s"]
    for m in entries.values():
        assert (m["moves"], m["better"], m["unit"]) == ("setup_s", "lower",
                                                        "s")
        assert "workloads" not in m     # every cell starts the same way
    assert {n: m["layer"] for n, m in entries.items()} == {
        "time_to_first_report_s": "trainer", "start_unnamed_s": "runtime",
        "tpu_client_off_cpu_s": "worker bring-up",
        "setup_program_load_s": "worker bring-up",
        "trainer_build_s": "step definition"}
    assert {n for n in EXPECTED
            if _metric(n)["reader"] == "start_account"} == {
        "time_to_first_report_s", "start_unnamed_s", "tpu_client_off_cpu_s"}


def test_the_manifest_under_the_cap():
    """The contract's cap is 128 (127 entries at PR 68); no two entries are
    one selection."""
    per_layer = manifest.benchmark()["per_layer"]
    assert len(per_layer) <= 128
    by_selection = collections.defaultdict(list)
    for m in per_layer:
        f = _metric(m["name"])
        by_selection[json.dumps([f["reader"], f.get("args", {})],
                                sort_keys=True)].append(m["name"])
    assert not [names for names in by_selection.values() if len(names) > 1]

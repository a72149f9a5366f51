"""The ``rounds`` reader on rings written through the program's own
``flight_recorder`` into a temporary session directory: the window chosen by
the records' step counts, a record that straddles the warm-up's end, the
final report's round left out, the driver's records by the share of their
time inside the window, and nothing where the program has no
``round_timeline``.

The times are made up; what is checked is which records each metric reads.
"""

import json
import os
from types import SimpleNamespace

import pytest

from perfbench.harness import manifest
from perfbench.harness.readers import rounds
from ray_tpu._private import flight_recorder as fr

SIX = ("round_worst_excess_s", "rounds_stalled",
       "report_driver_off_poll_ms_per_step", "report_skew_probe_ms_per_step",
       "gc_pause_ms_per_step", "host_loop_whole_window_ms_per_step")
WINDOW_STEPS = 100


def _round(kind, end, seconds, counts, by):
    return (kind, seconds, fr.round_detail(counts, by), end)


# The loop: two warm-up rounds of one step (the first compiles), then a
# window of 100 steps reported every step, then the final report.  The
# window opens at 1012.0 inside the record that ends at 1012.5.
WORKER = [
    ("bringup.worker.train_fn_enter", 0.0, "", 1000.0),
    _round(fr.ROUNDS, 1012.0, 12.0, {"rounds": 1, "steps": 1, "longest": 12.0},
           {"step": 11.0, "compile": 10.0, "train/report": 0.5, "gc": 0.5}),
    # straddles: one warm-up round (1 step) and nine of the window's
    _round(fr.ROUNDS, 1012.5, 0.5, {"rounds": 10, "steps": 10,
                                    "longest": 0.05},
           {"step": 0.3, "train/report": 0.01, "data/next": 0.09,
            "gc": 0.02}),
    _round(fr.ROUNDS, 1016.5, 4.0, {"rounds": 80, "steps": 80,
                                    "longest": 0.07},
           {"step": 2.4, "train/report": 0.08, "data/next": 0.72,
            "gc": 0.16}),
    # a stalled round of the window: alone, and a stall record beside it
    _round(fr.ROUNDS, 1017.5, 1.0, {"rounds": 1, "steps": 1, "longest": 1.0},
           {"step": 0.03, "train/report": 0.9,
            "train/report/handoff_wait": 0.9, "data/next": 0.01, "gc": 0.0}),
    _round(fr.STALL, 1017.5, 1.0, {"at": 93, "steps": 1, "expected": 0.05},
           {"train/report/handoff_wait": 0.9}),
    _round(fr.ROUNDS, 1018.0, 0.5, {"rounds": 10, "steps": 10,
                                    "longest": 0.05},
           {"step": 0.3, "train/report": 0.01, "data/next": 0.09,
            "gc": 0.02}),
    # the final report: no step, long (the run's measurements are gathered);
    # the program writes such a round alone and judges it by nothing
    _round(fr.ROUNDS, 1021.0, 3.0, {"rounds": 1, "longest": 3.0},
           {"train/report": 0.001, "gc": 1.0}),
]
# opened: 1012.5 - 0.9 * 0.5 = 1012.05; closed: 1018.0
DRIVER = [
    # ends before the window opens: none of it
    _round(fr.DRIVER_ROUNDS, 1012.0, 12.0, {"rounds": 1, "timeouts": 11,
                                            "longest": 12.0},
           {"skew_probe": 5.0, "poll": 6.0, "turnaround": 1.0}),
    # 1012.0 to 1017.0: 4.95 of its 5 s are inside
    _round(fr.DRIVER_ROUNDS, 1017.0, 5.0, {"rounds": 90, "timeouts": 0,
                                           "longest": 0.07},
           {"skew_probe": 1.0, "poll": 3.8, "turnaround": 0.2}),
    _round(fr.DRIVER_ROUNDS, 1018.0, 1.0, {"rounds": 11, "timeouts": 0,
                                           "longest": 0.9},
           {"skew_probe": 0.91, "poll": 0.08, "turnaround": 0.01}),
    # the final report's: after the window closed
    _round(fr.DRIVER_ROUNDS, 1021.0, 3.0, {"rounds": 1, "timeouts": 2,
                                           "longest": 3.0},
           {"skew_probe": 0.5, "poll": 2.4, "turnaround": 0.1}),
]
EXPECTED = {
    # each(): 9 x 0.05 (the straddler, at its mean), 0.07 + 79 x 3.93 / 79,
    # 1.0, 10 x 0.05: the median is 3.93 / 79
    "round_worst_excess_s": 1.0 - 3.93 / 79,
    "rounds_stalled": 1.0,
    "report_driver_off_poll_ms_per_step":
        (1.2 * 4.95 / 5 + 0.92) / WINDOW_STEPS * 1e3,
    "report_skew_probe_ms_per_step":
        (1.0 * 4.95 / 5 + 0.91) / WINDOW_STEPS * 1e3,
    "gc_pause_ms_per_step": (0.9 * 0.02 + 0.16 + 0.0 + 0.02)
        / WINDOW_STEPS * 1e3,
    "host_loop_whole_window_ms_per_step":
        (0.9 * 0.4 + 3.2 + 0.94 + 0.4) / WINDOW_STEPS * 1e3,
}


def _ring(session_dir, name, rows):
    assert fr.init_process(session_dir, name)
    for kind, seconds, detail, end in rows:
        fr.record(kind, f"{seconds:.6f}|{detail}" if detail
                  else f"{seconds:.6f}", ts=end)
    fr.shutdown()


def _ctx(steps=WINDOW_STEPS, profiler_calls=None):
    measured = {"steps": steps}
    if profiler_calls is not None:      # a traced run's
        measured["trace"] = {"steps": 8, "file": "reduced.json",
                             "profiler_calls": profiler_calls}
    return SimpleNamespace(measured=measured)


@pytest.fixture
def session(tmp_path):
    rounds.timeline.cache_clear()
    fr.shutdown()
    _ring(str(tmp_path), "driver", DRIVER)
    _ring(str(tmp_path), "w-train", WORKER)
    yield str(tmp_path)
    rounds.timeline.cache_clear()


def _args(name):
    with open(os.path.join(manifest.BENCH_DIR, "layer_metrics",
                           name + ".json")) as f:
        metric = json.load(f)
    assert metric["reader"] == "rounds" and set(metric) <= {
        "reader", "args", "note"}
    return metric["args"]


def test_the_manifest_has_the_six():
    entries = {m["name"]: m for m in manifest.benchmark()["per_layer"]
               if m["name"] in SIX}
    assert set(entries) == set(SIX)     # by name: later PRs append
    for m in entries.values():
        assert m["moves"] == "tokens_per_s_per_chip"
        assert m["better"] == "lower" and "workloads" not in m
    assert {n for n, m in entries.items()
            if m["source"] == "program_counter"} == {"rounds_stalled"}
    assert all(m["source"] == "program_span" for n, m in entries.items()
               if n != "rounds_stalled")
    for cell in manifest.benchmark()["workloads"]:
        reported = {m["name"] for m in manifest.cell(cell["name"]).per_layer}
        assert set(SIX) <= reported, cell["name"]


@pytest.mark.parametrize("name", SIX)
def test_each_metric_reads_the_whole_window(session, name):
    value = rounds.read(_ctx(), session_dir=session, **_args(name))
    assert value == pytest.approx(EXPECTED[name])


def test_the_window_is_counted_back_by_the_records_steps(session):
    mine = [r for r in rounds.timeline(session) if r.kind == rounds.ROUNDS]
    held = rounds.window(mine, WINDOW_STEPS)
    assert [(r.counts.get("steps"), share) for r, share in held] == [
        (10, pytest.approx(0.9)), (80, 1.0), (1, 1.0), (10, 1.0)]
    # a window of the last ten steps: one record, whole; of eleven: the
    # stalled round too
    assert [(r.counts["steps"], s) for r, s in rounds.window(mine, 10)] \
        == [(10, 1.0)]
    assert [(r.counts["steps"], s) for r, s in rounds.window(mine, 11)] \
        == [(1, 1.0), (10, 1.0)]
    assert rounds.read(_ctx(10), session_dir=session,
                       **_args("rounds_stalled")) == 0.0
    assert rounds.read(_ctx(11), session_dir=session,
                       **_args("rounds_stalled")) == 1.0
    # longer than the run: everything that has a step, the compile round too
    assert [s for _, s in rounds.window(mine, 500)] == [1.0] * 5


@pytest.mark.parametrize("calls,stalled,excess,left_out", [
    # an untraced run, and a traced one whose calls fell outside the window
    (None, 1.0, 1.0 - 3.93 / 79, 0.0),
    ([[1011.0, 1011.5], [1018.5, 1019.0]], 1.0, 1.0 - 3.93 / 79, 0.0),
    # start_trace inside the stalled round (1016.5 to 1017.5): the round and
    # its stall record are the benchmark's; what is left is 9 x 0.05, 0.07,
    # 79 x 3.93 / 79 and 10 x 0.05
    ([[1016.6, 1017.4], [1018.2, 1018.3]], 0.0, 0.07 - 3.93 / 79, 1.0),
    # stop_trace in a round that did not stall: the ten rounds summed with
    # it go too, and the stall stays
    ([[1011.0, 1011.5], [1017.7, 1017.8]], 1.0, 1.0 - 3.93 / 79, 10.0),
])
def test_the_profilers_own_rounds_are_left_out(session, calls, stalled,
                                               excess, left_out):
    """A round in which the benchmark called ``start_trace`` or
    ``stop_trace`` is no round of the program's: neither its length nor its
    stall record is read, and ``left_out`` says how many went.  The seconds
    by name are left whole."""
    read = lambda **args: rounds.read(  # noqa: E731
        _ctx(profiler_calls=calls), session_dir=session, **args)
    assert read(**_args("rounds_stalled")) == stalled
    assert read(**_args("round_worst_excess_s")) == pytest.approx(excess)
    assert read(as_="left_out") == left_out
    for name in SIX[2:]:
        assert read(**_args(name)) == pytest.approx(EXPECTED[name])


def test_a_real_stall_beside_the_profilers_calls_is_still_read(session):
    """The calls take the rounds they overlap and no other: a stall elsewhere
    in the window is counted as before."""
    calls = [[1013.0, 1013.2], [1015.0, 1015.1]]    # inside the 80 rounds
    read = lambda **args: rounds.read(  # noqa: E731
        _ctx(profiler_calls=calls), session_dir=session, **args)
    assert read(**_args("rounds_stalled")) == 1.0
    assert read(as_="left_out") == 80.0
    # 9 x 0.05, 1.0, 10 x 0.05: the median is 0.05
    assert read(**_args("round_worst_excess_s")) == pytest.approx(0.95)


def test_a_straddling_record_counts_by_its_share(session):
    """Of the record that holds the warm-up's last round and the window's
    first nine, nine tenths are the window's — and its longest round may be
    the warm-up's, so it stands in the median at its mean."""
    read = lambda steps, name: rounds.read(  # noqa: E731
        _ctx(steps), session_dir=session, **_args(name))
    whole = read(101, "host_loop_whole_window_ms_per_step") * 101
    part = read(100, "host_loop_whole_window_ms_per_step") * 100
    assert whole - part == pytest.approx(0.1 * 0.4 * 1e3)
    assert read(96, "gc_pause_ms_per_step") == pytest.approx(
        (0.5 * 0.02 + 0.16 + 0.02) / 96 * 1e3)


def test_the_session_is_the_newest_under_the_runtimes_tmpdir(
        session, tmp_path, monkeypatch):
    root = tmp_path / "root"
    (root / "session_1_1").mkdir(parents=True)
    monkeypatch.setenv("RAY_TPU_TMPDIR", str(root))
    assert rounds.read(_ctx(), **_args("rounds_stalled")) is None  # no ring
    monkeypatch.setenv("RAY_TPU_TMPDIR", str(root / "nothing"))
    assert rounds.read(_ctx(), **_args("rounds_stalled")) is None


@pytest.mark.parametrize("name", SIX)
def test_nothing_where_no_round_was_recorded(tmp_path, name):
    """A session whose worker wrote its start and no round (a recorder that
    was on, a loop that never reported): every metric is None, the count
    too."""
    rounds.timeline.cache_clear()
    fr.shutdown()
    _ring(str(tmp_path), "w-train", WORKER[:1])
    _ring(str(tmp_path), "driver", DRIVER)
    assert rounds.read(_ctx(), session_dir=str(tmp_path),
                       **_args(name)) is None
    rounds.timeline.cache_clear()


def test_without_the_drivers_records_only_its_metrics_are_missing(tmp_path):
    rounds.timeline.cache_clear()
    fr.shutdown()
    _ring(str(tmp_path), "w-train", WORKER)
    read = lambda name: rounds.read(  # noqa: E731
        _ctx(), session_dir=str(tmp_path), **_args(name))
    assert read("report_skew_probe_ms_per_step") is None
    assert read("report_driver_off_poll_ms_per_step") is None
    assert read("gc_pause_ms_per_step") == pytest.approx(
        EXPECTED["gc_pause_ms_per_step"])
    rounds.timeline.cache_clear()


@pytest.mark.parametrize("name", SIX)
def test_a_program_without_the_timeline_gives_nothing(session, monkeypatch,
                                                      name):
    """The parent commit's recorder has no ``round_timeline``: every metric
    is left out of its line, and nothing raises."""
    monkeypatch.delattr(fr, "round_timeline")
    rounds.timeline.cache_clear()
    assert rounds.read(_ctx(), session_dir=session, **_args(name)) is None


def test_an_unknown_quantity_is_an_error(session):
    with pytest.raises(ValueError, match="as_ must be"):
        rounds.read(_ctx(), as_="p99", session_dir=session)
    with pytest.raises(ValueError, match="side must be"):
        rounds.read(_ctx(), as_="ms_per_step", side="gcs", of=["gc"],
                    session_dir=session)

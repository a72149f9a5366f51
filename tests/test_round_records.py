"""The steady state in the flight recorder (ISSUE 50): ``profiler_span``'s
second sink, a train worker's report rounds as ``train.rounds`` records, a
stalled round as ``train.stall`` and one WARNING, the driver's side as
``train.driver_rounds``, and ``flight_recorder.round_timeline`` reading them
back on the one clock.

CPU only.  No verdict hangs on a time beyond a 0.6 s sleep against a
threshold of half its length; everything else is a count, a name, or seconds
handed to ``RoundLog.close`` by the test itself.
"""

import logging
import threading
import time

import pytest

from ray_tpu._private import flight_recorder as fr
from test_blackbox import own_ring  # noqa: F401  (a fixture)

SLEEP_S = 0.6
HALF = SLEEP_S / 2


class _Keep(logging.Handler):
    def __init__(self, level):
        super().__init__(level)
        self.lines = []

    def emit(self, record):
        self.lines.append(record.getMessage())


@pytest.fixture
def ring(own_ring):
    """This process's recorder on a ring of the default size under the
    test's directory, and the thread's span table empty."""
    from ray_tpu._private.config import RayConfig

    _, sdir = own_ring
    RayConfig.set("flight_recorder_bytes", 256 * 1024)
    assert fr.init_process(sdir, "unit")
    fr.take_spans()
    return sdir


def _run_session(loop, **context):
    """``loop(session)`` on a ``_TrainSession``'s thread, every result taken
    as the actor thread takes it; the session after its loop returned."""
    from ray_tpu.train._session import TrainContext, _TrainSession

    holder = []
    session = _TrainSession(lambda: loop(holder[0]), {},
                            TrainContext(**context))
    holder.append(session)
    session.start()
    while True:
        result = session.get_next(timeout=120)
        assert result is not None, "the loop stalled"
        if result.final:
            assert result.error is None, result.error
            return session


def _kinds(sdir, kind):
    return [r for r in fr.round_timeline(sdir) if r.kind == kind]


# ------------------------------------------------ RoundLog: sums and verdicts
def test_rounds_are_summed_until_half_a_second_has_passed(ring):
    log = fr.RoundLog(fr.ROUNDS)
    for _ in range(17):             # 17 x 0.059 = 1.003: two records' worth
        assert log.close(0.059, {"step": 0.03, "train/report": 0.001},
                         steps=1) is None
    records = _kinds(ring, fr.ROUNDS)
    assert [r.counts["rounds"] for r in records] == [9, 8] or \
        [r.counts["rounds"] for r in records] == [9]
    log.flush()
    records = _kinds(ring, fr.ROUNDS)
    assert sum(r.counts["rounds"] for r in records) == 17
    assert sum(r.counts["steps"] for r in records) == 17
    first = records[0]
    assert first.end - first.start == pytest.approx(9 * 0.059)
    assert first.counts["longest"] == pytest.approx(0.059)
    assert first.seconds["step"] == pytest.approx(9 * 0.03)
    assert len(first.each()) == 9
    assert sum(first.each()) == pytest.approx(9 * 0.059)


@pytest.mark.parametrize("steps, seconds, stalled", [
    (8, 2.14, False), (8, 3.0, False), (8, 3.3, True), (8, 5.44, True),
    (1, 0.41, True),                # a round of one step is judged as one
    (1, 0.30, False)])
def test_a_round_is_judged_by_the_step(ring, steps, seconds, stalled):
    """Mistral's round: 8 steps, 2.14 s as a rule (0.2675 s a step); stalled
    from half of ``n * m`` over it."""
    log = fr.RoundLog(fr.ROUNDS)
    for _ in range(3):              # warm-up reports every step
        assert log.close(0.27, {}, steps=1) is None
    for _ in range(10):
        assert log.close(2.14, {}, steps=8) is None
    expected = log.close(seconds, {"train/report/handoff_wait": 1.0},
                         steps=steps)
    if not stalled:
        assert expected is None
        return
    assert expected == pytest.approx(steps * 0.2675)
    # written at once, and alone (the test's rounds all end "now": the
    # timeline's order is not the order they were written in)
    (alone,) = [r for r in _kinds(ring, fr.ROUNDS)
                if r.end - r.start == pytest.approx(seconds)]
    assert alone.counts == {"rounds": 1, "steps": steps, "longest": seconds}
    assert sum(r.counts["rounds"] for r in _kinds(ring, fr.ROUNDS)) == 14


def test_no_round_is_judged_before_eight_are_in(ring):
    log = fr.RoundLog(fr.ROUNDS)
    for _ in range(7):
        assert log.close(0.05, {}, steps=1) is None
    assert log.close(30.0, {}, steps=1) is None     # the eighth: compiles
    assert log.close(30.0, {}, steps=1) is not None


def test_a_short_round_is_never_stalled(ring):
    log = fr.RoundLog(fr.ROUNDS)
    for _ in range(20):
        log.close(0.001, {}, steps=1)
    assert log.close(0.2, {}, steps=1) is None      # 200 x, under 0.25 s
    assert log.close(0.26, {}, steps=1) is not None


def test_a_round_without_a_step_in_a_stepping_loop_is_alone_and_unjudged(ring):
    """The last report of a loop that made steps (the run's measurements,
    an evaluation) has no ``m`` to be held to, and its seconds are not the
    steps' before it."""
    log = fr.RoundLog(fr.ROUNDS)
    for _ in range(12):
        log.close(0.06, {"gc": 0.001}, steps=1)
    assert log.close(3.0, {"gc": 0.5}) is None              # 50 x, no step
    assert log.close(0.06, {"gc": 0.001}, steps=1) is None  # m is what it was
    assert log.close(0.2, {}, steps=1) is None
    assert log.close(0.3, {}, steps=1) == pytest.approx(0.06)
    records = _kinds(ring, fr.ROUNDS)
    (alone,) = [r for r in records if "steps" not in r.counts]
    assert alone.counts == {"rounds": 1, "longest": 3.0}
    assert alone.seconds == {"gc": 0.5}
    assert sum(r.counts["rounds"] for r in records) == 16


def test_a_record_stays_under_the_payload_limit(ring):
    by = {f"user/span_with_a_long_name_{i:03d}": 1.0 + i for i in range(60)}
    log = fr.RoundLog(fr.ROUNDS)
    log.close(0.7, by, steps=1)
    (record,) = _kinds(ring, fr.ROUNDS)
    rows = [r for r in fr.harvest(fr._path) if r["kind"] == fr.ROUNDS]
    assert len(f"{rows[0]['kind']}|{rows[0]['detail']}".encode()) \
        <= fr.MAX_PAYLOAD
    # the largest first; what did not fit is left out, whole
    assert 3 <= len(record.seconds) < 60
    assert min(record.seconds.values()) > 60 - len(record.seconds)
    assert record.counts == {"rounds": 1, "steps": 1, "longest": 0.7}


# --------------------------------- (a) a stalled round names itself, once
@pytest.fixture
def slept(ring):
    """Twenty rounds, the fifteenth asleep for 0.6 s in the user's loop;
    then another stall, inside the warning's rate limit."""
    keep = _Keep(logging.WARNING)
    logger = logging.getLogger("ray_tpu.train._session")
    logger.addHandler(keep)

    def loop(session):
        for i in range(22):
            if i in (14, 20):
                time.sleep(SLEEP_S)
            session.report({"i": i})

    try:
        _run_session(loop, experiment_name="rounds-a")
    finally:
        logger.removeHandler(keep)
    return ring, keep.lines


def test_a_slept_round_is_one_stall_in_the_users_loop(slept):
    from ray_tpu.train._session import user_loop_seconds

    sdir, _ = slept
    stalls = _kinds(sdir, fr.STALL)
    assert [s.counts["at"] for s in stalls] == [15, 21]
    stall = stalls[0]
    took = stall.end - stall.start
    assert took >= HALF
    assert stall.counts["expected"] < HALF
    assert user_loop_seconds(took, stall.seconds) >= HALF
    assert stall.seconds["cpu"] < HALF          # blocked, not busy
    assert stall.seconds.get("train/report/handoff_wait", 0.0) < HALF
    # the round is also a rounds record of its own, on the same interval
    alone = [r for r in _kinds(sdir, fr.ROUNDS) if r.counts["rounds"] == 1
             and abs(r.end - stall.end) < 0.05]
    assert len(alone) == 1
    assert sum(r.counts["rounds"] for r in _kinds(sdir, fr.ROUNDS)) == 22


def test_the_warning_is_logged_once_and_says_where(slept):
    _, lines = slept
    (line,) = [ln for ln in lines if ln.startswith("train round")]
    assert line.startswith("train round 15 (0 steps) took 0.6")
    assert "expected: user loop 0.6" in line
    assert "(cpu 0.0" in line and "gc 0.0" in line and "compile 0.0" in line


# ---------------------------- (c) the ring is a budget: 2,000 empty rounds
def test_two_thousand_rounds_leave_the_start_harvestable(ring):
    for i in range(60):
        fr.mark(f"bringup.phase_{i:02d}", 0.01 * i, "a worker's start")
    fr.mark("compile", 1.5, "backend_compile_duration|pretrain_step")
    cursor, t0 = fr._cursor, time.perf_counter()

    def loop(session):
        for i in range(2000):
            session.report({"i": i})

    _run_session(loop, experiment_name="rounds-c")
    minutes = (time.perf_counter() - t0) / 60
    marks, _ = fr.bringup_timeline(ring)
    assert sum(m[1].startswith("bringup.phase_") for m in marks) == 60
    assert sum(m[1] == "compile" for m in marks) == 1
    records = _kinds(ring, fr.ROUNDS)
    assert sum(r.counts["rounds"] for r in records) == 2000
    # CHANGES.md: at most two records a second and one at the end, none over
    # a frame's 536 bytes; so a minute writes 64,320 bytes at the very most
    assert len(records) <= 120 * minutes + 1
    assert fr._cursor - cursor <= (120 * minutes + 1) * (
        fr.REC_HEAD.size + fr.MAX_PAYLOAD)


# ------------------------------------------------------- (d) recorder off
def test_with_the_recorder_off_a_span_is_what_it_was(own_ring):
    import jax.profiler

    from ray_tpu.util.tracing import profiler_span

    assert not fr.RECORDING
    fr.take_spans()
    span = profiler_span("train/report")
    assert type(span) is jax.profiler.TraceAnnotation
    assert type(profiler_span("step", step_num=3)) \
        is jax.profiler.StepTraceAnnotation
    with span:
        pass
    assert fr.take_spans() == {}
    seen = []
    with profiler_span("train/report/persist", observe=seen.append):
        pass                        # the histograms' seconds need no recorder
    assert len(seen) == 1 and fr.take_spans() == {}


def test_with_the_recorder_off_a_session_writes_no_record(own_ring):
    _, sdir = own_ring

    def loop(session):
        for i in range(12):
            session.report({"i": i})

    session = _run_session(loop, experiment_name="rounds-d")
    assert session._rounds is None
    assert fr.round_timeline(sdir) == []
    import gc

    assert session._gc not in gc.callbacks


def test_the_table_is_the_threads_own(ring):
    from ray_tpu.util.tracing import profiler_span

    def other():
        with profiler_span("data/pull_block"):
            pass

    thread = threading.Thread(target=other)
    thread.start()
    thread.join(10)
    with profiler_span("data/rebatch"):
        with profiler_span("data/rebatch"):
            pass
    table = fr.take_spans()
    assert list(table) == ["data/rebatch"] and table["data/rebatch"][1] == 2
    assert fr.take_spans() == {}


# ----------- (b) the driver's side, and the two joined on the one clock
def _paced(config):
    from ray_tpu import train

    for i in range(40):
        time.sleep(0.02)
        train.report({"i": i})


@pytest.fixture(scope="module")
def probed(tmp_path_factory):
    """One isolated runtime, one fit of ``_paced`` whose driver sleeps 0.6 s
    in one ``_observe_gang_skew``, well after the gang is warm."""
    import ray_tpu
    from ray_tpu._private.worker import global_worker_core
    from ray_tpu.train import JaxTrainer, RunConfig, ScalingConfig
    from ray_tpu.train._backend_executor import BackendExecutor
    from ray_tpu.train.jax_config import JaxConfig

    keep = _Keep(logging.INFO)
    logger = logging.getLogger("ray_tpu.train.base_trainer")
    level = logger.level
    logger.addHandler(keep)
    logger.setLevel(logging.INFO)
    calls = []
    observe = BackendExecutor._observe_gang_skew

    def slow_once(self):
        calls.append(None)
        if len(calls) == 25:
            time.sleep(SLEEP_S)
        return observe(self)

    tmp = tmp_path_factory.mktemp("fit")
    ray_tpu.shutdown()
    fr.shutdown()
    ray_tpu.init(num_cpus=4, object_store_memory=128 * 1024**2)
    with pytest.MonkeyPatch.context() as patch:
        patch.setattr(BackendExecutor, "_observe_gang_skew", slow_once)
        try:
            session_dir = global_worker_core().session_dir
            JaxTrainer(
                _paced, jax_config=JaxConfig(platform="cpu"),
                scaling_config=ScalingConfig(num_workers=1),
                run_config=RunConfig(name="rounds-b",
                                     storage_path=str(tmp)),
            ).fit()
        finally:
            ray_tpu.shutdown()
            logger.removeHandler(keep)
            logger.setLevel(level)
    return {"timeline": fr.round_timeline(session_dir),
            "lines": [ln for ln in keep.lines
                      if ln.startswith("train loop made")]}


def test_the_workers_stall_reads_handoff_wait(probed):
    stalls = [r for r in probed["timeline"] if r.kind == fr.STALL]
    assert stalls, probed["timeline"]
    stall = max(stalls, key=lambda r: r.end - r.start)
    assert stall.end - stall.start >= HALF
    assert stall.seconds["train/report/handoff_wait"] >= HALF
    assert stall.seconds["cpu"] < HALF


def test_the_drivers_record_beside_it_reads_skew_probe(probed):
    stall = max((r for r in probed["timeline"] if r.kind == fr.STALL),
                key=lambda r: r.end - r.start)
    beside = stall.driver
    assert beside is not None and beside.kind == fr.DRIVER_ROUNDS
    assert beside.process != stall.process      # the driver's own ring
    assert beside.counts["rounds"] == 1         # long: written alone
    assert beside.seconds["skew_probe"] >= HALF
    assert beside.seconds["turnaround"] < HALF
    assert min(beside.end, stall.end) - max(beside.start, stall.start) \
        >= HALF


def test_both_sides_count_every_round(probed):
    rounds = lambda kind: sum(  # noqa: E731
        r.counts["rounds"] for r in probed["timeline"] if r.kind == kind)
    assert rounds(fr.ROUNDS) == 40
    # the driver's last round ends with the loop's return, not a report
    assert rounds(fr.DRIVER_ROUNDS) == 41
    drivers = [r for r in probed["timeline"] if r.kind == fr.DRIVER_ROUNDS]
    assert all(set(r.seconds) == {"skew_probe", "poll", "turnaround"}
               for r in drivers)
    assert all(r.counts["timeouts"] == 0 for r in drivers)


def test_fit_logs_the_loops_line_when_it_ends(probed):
    (line,) = probed["lines"]
    assert line.startswith("train loop made 40 rounds (0 steps), median 0.0")
    assert "handoff_wait 0." in line and "driver beside it: skew_probe 0." \
        in line
    assert " | stalled 1 | gc 0." in line


# --------------------------------- (f) the line, from a fixture timeline
def test_the_loops_line_from_a_fixture_timeline():
    from ray_tpu.train.base_trainer import rounds_line

    t = 1_000_000.0
    usual = {"step": 0.16, "step/dispatch": 0.15, "train/report": 0.004,
             "train/report/handoff_wait": 0.003, "cpu": 0.2, "gc": 0.01}
    timeline = [
        fr.Round("w", fr.ROUNDS, t, t + 21.4,
                 {"rounds": 10, "steps": 80, "longest": 2.2},
                 {k: 10 * v for k, v in usual.items()}),
        fr.Round("d", fr.DRIVER_ROUNDS, t, t + 21.4,
                 {"rounds": 10, "timeouts": 20, "longest": 2.2},
                 {"skew_probe": 0.03, "poll": 21.3, "turnaround": 0.01}),
        fr.Round("w", fr.ROUNDS, t + 21.4, t + 26.84,
                 {"rounds": 1, "steps": 8, "longest": 5.44},
                 dict(usual, **{"train/report": 3.281, "cpu": 0.01,
                                "train/report/handoff_wait": 3.28})),
        fr.Round("w", fr.STALL, t + 21.4, t + 26.84,
                 {"at": 57, "steps": 8, "expected": 2.14}, {}),
        fr.Round("d", fr.DRIVER_ROUNDS, t + 21.4, t + 26.84,
                 {"rounds": 1, "timeouts": 5, "longest": 5.44},
                 {"skew_probe": 3.3, "poll": 2.13, "turnaround": 0.01}),
    ]
    assert rounds_line(timeline) == (
        "train loop made 11 rounds (88 steps), median 2.133 s; longest "
        "5.440 s, in 1 round(s) of 5.44 s: handoff_wait 3.28, user loop "
        "2.00 (cpu 0.01), step 0.16 | driver beside it: skew_probe 3.30, "
        "poll 2.13, turnaround 0.01 | stalled 1 | gc 0.11 s")
    assert rounds_line([]) is None
    assert rounds_line(timeline[1:2]) is None   # no worker's record


def test_the_stalls_line_is_the_issues():
    from ray_tpu.train._session import stall_line

    assert stall_line(57, 8, 5.44, 2.14, {
        "train/report": 3.281, "train/report/handoff_wait": 3.28,
        "step": 0.02, "cpu": 0.01, "gc": 0.0}) == (
        "train round 57 (8 steps) took 5.44 s, 2.14 expected: handoff_wait "
        "3.28, user loop 2.14 (cpu 0.01), step 0.02, gc 0.00, compile 0.00")


# --------- (e) one span site, two sinks, one answer: under a profiler session
STEPS = 3


@pytest.fixture(scope="module")
def both_sinks(tmp_path_factory):
    """``test_profiler_spans``'s toy loop — a ``from_numpy`` shard's
    ``iter_jax_batches``, ``ShardedPretrainer.step``, ``float(loss)``,
    ``report`` with one checkpoint — for three steps inside a profiler
    session with the recorder on: the spans the profiler's file holds, and
    the seconds the rounds' records hold for the same rounds."""
    import jax
    import jax.numpy as jnp
    from conftest import ensure_shared_runtime
    from test_profiler_spans import _program_spans, _toy_ids, _toy_trainer

    ray_tpu = ensure_shared_runtime()
    import ray_tpu.data
    from ray_tpu._private.worker import global_worker_core
    from ray_tpu.train._checkpoint import Checkpoint

    assert fr.RECORDING         # the shared runtime's driver has its ring
    session_dir = global_worker_core().session_dir
    tmp = tmp_path_factory.mktemp("sinks")
    (tmp / "ckpt").mkdir()
    (tmp / "ckpt" / "state.txt").write_text("x")
    trainer = _toy_trainer("gpt2")
    (shard,) = ray_tpu.data.from_numpy(
        list(_toy_ids(64).reshape(8, 8, 64)),
        column="input_ids").streaming_split(1)
    batches = shard.iter_jax_batches(batch_size=4)
    traced_from = []

    def three_steps(session):
        for i in range(STEPS):
            ids = next(batches)["input_ids"]
            loss = trainer.step({"input_ids": ids,
                                 "targets": jnp.roll(ids, -1, axis=1)})
            session.report(
                {"loss": float(loss)},
                Checkpoint.from_directory(str(tmp / "ckpt"))
                if i == 1 else None)

    def loop(session):
        three_steps(session)            # warm: compiles
        session._rounds.flush()         # the traced rounds: records of theirs
        traced_from.append(time.time())
        jax.profiler.start_trace(str(tmp / "on"))
        three_steps(session)
        jax.profiler.stop_trace()

    try:
        _run_session(loop, experiment_name="rounds-e",
                     trial_dir=str(tmp / "trial"))
    finally:
        ray_tpu.kill(shard._coord)
    records = [r for r in fr.round_timeline(session_dir)
               if r.kind == fr.ROUNDS and r.start >= traced_from[0] - 0.001]
    table = {}
    for r in records:
        for name, secs in r.seconds.items():
            table[name] = table.get(name, 0.0) + secs
    (spans,) = _program_spans(str(tmp / "on"))
    profiled = {}
    for name, start, end in spans:
        profiled[name] = profiled.get(name, 0.0) + (end - start) * 1e-9
    return {"records": records, "table": table, "profiled": profiled}


def test_the_table_holds_the_profilers_names(both_sinks):
    from test_profiler_spans import HOST_SPANS

    assert set(both_sinks["profiled"]) == HOST_SPANS
    assert {"ray_tpu/" + n for n in both_sinks["table"]} == HOST_SPANS | {
        "ray_tpu/data/next", "ray_tpu/cpu", "ray_tpu/gc"}
    assert sum(r.counts["rounds"] for r in both_sinks["records"]) == STEPS
    assert sum(r.counts["steps"] for r in both_sinks["records"]) == STEPS


@pytest.mark.parametrize("name", [
    "step", "step/shard_batch", "step/dispatch", "train/report",
    "train/report/heartbeat", "train/report/persist",
    "train/report/handoff_wait", "data/pull_block", "data/rebatch",
    "data/device_put"])
def test_the_tables_seconds_are_the_profilers(both_sinks, name):
    """The same region, timed by the table's ``perf_counter`` pair inside the
    profiler's annotation of it: equal within 5% or a millisecond."""
    mine = both_sinks["table"][name]
    theirs = both_sinks["profiled"]["ray_tpu/" + name]
    assert abs(mine - theirs) <= max(0.05 * theirs, 0.001), (mine, theirs)

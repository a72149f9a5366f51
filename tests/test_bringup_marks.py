"""A job's start on one clock: the ``bringup.*`` marks of the driver, the
nodelet and the train worker, and the worker's own compile records, in the
flight recorder — read back through ``flight_recorder.bringup_timeline``.

One real local cluster and one one-worker ``JaxTrainer`` on the cpu platform
serve cases (a), (b), (e), (f) and the trainer's log line; (g) runs the same
loop with the recorder off.  No wall-clock threshold decides a verdict: the
orderings hold by construction, and the recompile warning is driven through
JAX's own monitoring channel with a stated duration.

Since PR 68 the same fit (its worker granted a nominal TPU, so that the
nodelet watches it leave) also serves the start's account: where a start
ends (``bringup.first_report``), ``flight_recorder.start_account``, what a
mark cost, and how the TPU worker ended; the chip's state on arrival, the
client's seams and the compile cache's record are driven alone.
"""

import logging
import os
import re
import subprocess
import sys
import time

import pytest

import ray_tpu
from ray_tpu._private import flight_recorder as fr
from test_blackbox import own_ring  # noqa: F401  (a fixture)

BUILT = "/jax/core/compile/backend_compile_duration"
STEPS_AFTER_THE_FIRST = 10


def _loop(config):
    """The train worker: a toy ``ShardedPretrainer`` loop that counts what its
    own ring holds after the first step, after ten more of the same shape and
    after a second shape, and keeps the platform module's warnings."""
    import jax
    import numpy as np

    from ray_tpu import train
    from ray_tpu.models.gpt2 import GPT2Config
    from ray_tpu.models.pretrain import ShardedPretrainer

    warnings = []

    class Keep(logging.Handler):
        def emit(self, record):
            warnings.append(record.getMessage())

    logging.getLogger("ray_tpu._private.platform").addHandler(
        Keep(level=logging.WARNING))

    def ours():
        rows = fr.harvest(fr._path) if fr._path else []
        return [(r["kind"], r["detail"]) for r in rows
                if r["kind"].startswith(("bringup.", "compile"))]

    trainer = ShardedPretrainer(GPT2Config(
        vocab_size=128, n_layer=2, n_head=2, n_embd=32, n_positions=32))
    rng = np.random.default_rng(0)

    def step(seq):
        ids = rng.integers(0, 128, (2, seq)).astype("int32")
        return float(trainer.step({"input_ids": ids, "targets": ids}))

    # a long build before the first report is set-up: no warning
    jax.monitoring.record_event_duration_secs(
        BUILT, 1.5, fun_name="before_first_report")
    before = ours()
    step(16)
    after_first = ours()
    train.report({"step": 1})
    for _ in range(STEPS_AFTER_THE_FIRST):
        step(16)
    after_ten_more = ours()
    train.report({"step": 1 + STEPS_AFTER_THE_FIRST})
    step(32)                        # a second shape: the step is built again
    after_second_shape = ours()
    jax.monitoring.record_event_duration_secs(
        BUILT, 1.25, fun_name="second_shape")
    train.report({"before": before, "after_first": after_first,
                  "after_ten_more": after_ten_more,
                  "after_second_shape": after_second_shape,
                  "warnings": warnings})


def _fit(tmp, name):
    """One isolated runtime, one fit of ``_loop``; the session's timeline is
    read after the gang is gone, as the benchmark's reader reads it."""
    from ray_tpu._private.worker import global_worker_core
    from ray_tpu.train import JaxTrainer, RunConfig, ScalingConfig
    from ray_tpu.train.jax_config import JaxConfig

    lines = []

    class Keep(logging.Handler):
        def emit(self, record):
            lines.append(record.getMessage())

    keep = Keep(level=logging.INFO)
    logger = logging.getLogger("ray_tpu.train.base_trainer")
    level = logger.level
    logger.addHandler(keep)
    logger.setLevel(logging.INFO)
    ray_tpu.shutdown()
    # marks that an earlier test file of this process left waiting for a
    # ring (a ``ShardedPretrainer`` built outside any runtime) are not this
    # session's: without this the verdict hangs on which files xdist gave
    # this worker before
    fr.shutdown()
    ray_tpu.init(num_cpus=4, num_tpus=1, object_store_memory=128 * 1024**2)
    try:
        core = global_worker_core()
        out = {"session_dir": core.session_dir,
               "driver": core.worker_id.hex()}
        out["metrics"] = JaxTrainer(
            _loop, jax_config=JaxConfig(platform="cpu"),
            scaling_config=ScalingConfig(num_workers=1, tpus_per_worker=1),
            run_config=RunConfig(name=name, storage_path=str(tmp / name)),
        ).fit().metrics
    finally:
        ray_tpu.shutdown()
        logger.removeHandler(keep)
        logger.setLevel(level)
    out["lines"] = [ln for ln in lines if ln.startswith("train gang up")]
    out["start_lines"] = [ln for ln in lines if ln.startswith("train start")]
    out["marks"], out["gap"] = fr.bringup_timeline(out["session_dir"])
    out["account"] = fr.start_account(out["session_dir"])
    return out


@pytest.fixture(scope="module")
def jax_cache_env(tmp_path_factory):
    """The workers' JAX keeps a persistent cache in a fresh directory and
    writes every program to it, so the first build of each is a miss that
    JAX reports (its own variables; the workers inherit them)."""
    env = {"JAX_COMPILATION_CACHE_DIR": str(tmp_path_factory.mktemp("jaxc")),
           "JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS": "0",
           "JAX_PERSISTENT_CACHE_MIN_ENTRY_SIZE_BYTES": "-1"}
    saved = {k: os.environ.get(k) for k in env}
    os.environ.update(env)
    yield
    for k, v in saved.items():
        os.environ.pop(k, None) if v is None else os.environ.__setitem__(k, v)


@pytest.fixture(scope="module")
def started(tmp_path_factory, jax_cache_env):
    return _fit(tmp_path_factory.mktemp("fit"), "bringup-marks")


def _one(marks, kind):
    found = [m for m in marks if m[1] == kind]
    assert len(found) == 1, (kind, found)
    return found[0]


DRIVER = ("init", "init.gcs_spawn", "init.nodelet_spawn",
          "init.driver_connect", "gang", "gang.placement_group",
          "gang.actors", "gang.backend", "session")
WORKER = ("worker.imports", "worker.connect", "worker.actor",
          "worker.jax_import", "worker.tpu_client",
          "worker.tpu_client.device_query", "worker.train_fn_enter",
          "trainer_build", "state_init", "first_run", "first_report")


def test_every_mark_in_its_process(started):
    marks = started["marks"]
    worker = _one(marks, fr.ENTERED)[0]
    for kind in DRIVER:
        assert _one(marks, f"bringup.{kind}")[0] == started["driver"], kind
    for kind in WORKER:
        assert _one(marks, f"bringup.{kind}")[0] == worker, kind
    spawned = [m for m in marks if m[1] == "bringup.worker_spawn"
               and m[4] == worker]
    assert len(spawned) == 1 and spawned[0][0].startswith("nodelet-")
    # only on the chip, and only in a gang of several processes
    kinds = {m[1] for m in marks}
    assert "bringup.worker.compile_cache" not in kinds
    assert "bringup.worker.distributed_init" not in kinds
    # the repair: the driver's ring is the session's, not a fixed path's
    assert os.path.exists(fr.ring_path(started["session_dir"],
                                       started["driver"]))


def test_marks_nest_on_the_one_clock(started):
    marks = started["marks"]
    at = {m[1][len("bringup."):]: (m[2], m[3]) for m in marks
          if m[1].startswith("bringup.") and m[1] != "bringup.worker_spawn"}
    worker = _one(marks, fr.ENTERED)[0]
    at["worker_spawn"] = next((m[2], m[3]) for m in marks if m[4] == worker
                              and m[1] == "bringup.worker_spawn")

    def inside(child, parent):
        (a, b), (lo, hi) = at[child], at[parent]
        assert lo <= a <= b <= hi, (child, at[child], parent, at[parent])

    assert at["init"][1] <= at["gang"][0]
    for child in ("init.gcs_spawn", "init.nodelet_spawn",
                  "init.driver_connect"):
        inside(child, "init")
    for child in ("gang.placement_group", "gang.actors", "gang.backend"):
        inside(child, "gang")
    inside("worker.imports", "worker_spawn")
    # the registration is answered inside `connect`: its start is inside
    assert at["worker_spawn"][0] <= at["worker.connect"][0] \
        <= at["worker_spawn"][1]
    inside("worker.actor", "gang.actors")
    # the class load is where a train worker first imports jax (PR 68)
    inside("worker.jax_import", "worker.actor")
    inside("worker.tpu_client", "gang.backend")
    inside("worker.tpu_client.device_query", "worker.tpu_client")
    inside("worker.train_fn_enter", "session")
    assert at["gang"][1] <= at["session"][0]
    assert at["worker.train_fn_enter"][1] <= at["trainer_build"][0] \
        <= at["trainer_build"][1] <= at["state_init"][0]
    assert at["state_init"][1] <= at["first_run"][0] <= at["first_run"][1] \
        <= at["first_report"][0]
    for parent in ("init", "gang"):
        children = sum(b - a for k, (a, b) in at.items()
                       if k.startswith(parent + "."))
        assert children <= at[parent][1] - at[parent][0]
    assert started["gap"] is not None and 0.0 <= started["gap"] \
        <= at["worker.train_fn_enter"][1] - at["init"][0]


def test_marks_before_the_ring_keep_their_stamps(own_ring):
    recorder, session_dir = own_ring
    recorder.shutdown()
    with recorder.timed("bringup.first"):
        pass
    recorder.mark("bringup.second", 0.25, "why")
    assert recorder.init_process(session_dir, "early")
    recorder.record("later")
    rows = recorder.harvest_for(session_dir, "early")
    assert [r["kind"] for r in rows] == [
        "bringup.first", "bringup.second", "recorder.init", "later"]
    assert rows[0]["ts"] <= rows[1]["ts"] <= rows[2]["ts"]
    assert rows[1]["detail"] == "0.250000|why"
    marks, gap = recorder.bringup_timeline(session_dir)
    # ordered by start: the second began a quarter second before its stamp
    assert [(m[0], m[1], m[4]) for m in marks] == [
        ("early", "bringup.second", "why"), ("early", "bringup.first", "")]
    assert marks[0][3] - marks[0][2] == pytest.approx(0.25)
    assert gap is None          # no train function was entered


def test_gap_is_the_uncovered_time():
    marks = [("d", "bringup.init", 10.0, 13.0, ""),
             ("d", "bringup.init.gcs_spawn", 10.5, 11.0, ""),
             ("d", "bringup.gang", 14.0, 20.0, ""),        # 13..14 open
             ("w", "bringup.worker.imports", 15.0, 16.0, ""),
             ("w", "compile", 12.0, 30.0, "trace|f"),      # covers nothing
             ("d", "bringup.session", 20.5, 21.5, ""),     # 20..20.5 open
             ("w", fr.ENTERED, 22.0, 22.0, ""),            # 21.5..22 open
             ("w", "bringup.state_init", 23.0, 25.0, "")]  # after the end
    assert fr.bringup_gap(marks) == pytest.approx(2.0)
    assert fr.bringup_gap(marks[:6]) is None
    # a mark that runs past train_fn_enter covers only up to it
    marks[2] = ("d", "bringup.gang", 14.0, 40.0, "")
    assert fr.bringup_gap(marks) == pytest.approx(1.0)


def test_steps_after_the_first_write_nothing(started):
    m = started["metrics"]
    first = m["after_first"][len(m["before"]):]
    stages = {d.split("|")[1] for k, d in first if k == "compile"}
    assert {"jaxpr_trace_duration", "backend_compile_duration"} <= stages
    assert ["compile.cache", "miss"] in [list(r) for r in first]
    assert any(k == "bringup.state_init" for k, _ in m["before"])
    assert [d.split("|")[1] for k, d in first
            if k == "bringup.first_run"] == ["pretrain_step"]
    # ten further steps of the same shape: not one record of either family
    # but the first report's own, which the steps before it did not write
    assert m["after_ten_more"] == m["after_first"] + [
        ("bringup.first_report", "")]
    again = m["after_second_shape"][len(m["after_ten_more"]):]
    assert any(k == "compile" and "pretrain_step" in d for k, d in again)
    assert not [k for k, _ in again if k.startswith("bringup.")]


def test_a_build_after_the_first_report_warns_once(started):
    warnings = started["metrics"]["warnings"]
    assert not [w for w in warnings if "before_first_report" in w]
    named = [w for w in warnings if "second_shape" in w]
    assert len(named) == 1 and "train.report step 2" in named[0]


def test_the_trainer_logs_one_line(started):
    assert len(started["lines"]) == 1
    for label in ("init", "gcs", "nodelet", "connect", "gang", "placement",
                  "actors", "backend", "worker spawn", "imports", "actor",
                  "jax import", "tpu client", "session", "uncovered"):
        assert f"{label} " in started["lines"][0], label


def test_recorder_off_runs_and_records_nothing(tmp_path, jax_cache_env):
    from ray_tpu._private.config import RayConfig

    saved = RayConfig.flight_recorder_bytes
    RayConfig.set("flight_recorder_bytes", 0)
    try:
        off = _fit(tmp_path, "bringup-off")
    finally:
        RayConfig.set("flight_recorder_bytes", saved)
    assert off["metrics"]["after_first"] == []
    assert off["marks"] == [] and off["gap"] is None and off["lines"] == []
    assert not os.path.exists(os.path.join(off["session_dir"], "blackbox"))
    # the warning needs no ring: it is the listener's, not the recorder's
    assert len([w for w in off["metrics"]["warnings"]
                if "second_shape" in w]) == 1


# --------------------------------------------- PR 68: the start's account
def test_first_report_is_written_once_a_session(started):
    """Three reports in ``_loop``; the mark is the first's, on the worker."""
    marks = started["marks"]
    point = _one(marks, fr.FIRST_REPORT)
    assert point[0] == _one(marks, fr.ENTERED)[0] and point[2] == point[3]
    assert point[3] >= _one(marks, "bringup.first_run")[3]


def _ring(session_dir, name, rows):
    """A ring of made-up marks: (kind, seconds or None, detail, end)."""
    assert fr.init_process(session_dir, name)
    for kind, seconds, detail, end in rows:
        if seconds is not None:
            detail = f"{seconds:.6f}|{detail}" if detail else f"{seconds:.6f}"
        fr.record(kind, detail, ts=end)
    fr.shutdown()


COST = "cpu=0.250000 majflt=3 inblock=16"


@pytest.fixture
def recorded(own_ring, tmp_path):
    """A start of 30 s, 100 to 130, with nested and overlapping marks, a
    trace inside a trace and a pooled worker that is not of the gang."""
    from ray_tpu._private.config import RayConfig

    RayConfig.set("flight_recorder_bytes", 1 << 16)     # own_ring restores it
    _ring(str(tmp_path), "driver", [
        ("bringup.init", 3.0, COST, 103.0),
        ("bringup.init.nodelet_spawn", 1.0, COST, 102.5),
        ("bringup.gang", 10.0, COST, 114.0),                # 103..104 bare
        ("bringup.gang.backend", 5.0, COST, 113.5),
        ("bringup.session", 1.0, COST, 115.5)])             # 114..114.5 bare
    _ring(str(tmp_path), "nodelet-n", [
        ("bringup.worker_spawn", 9.0, "w-pooled", 109.0),   # not of the gang
        ("bringup.worker_spawn", 2.0, "w-train", 106.5)])   # overlaps the next
    _ring(str(tmp_path), "w-pooled", [
        ("bringup.worker.imports", 8.0, COST, 112.0)])
    _ring(str(tmp_path), "w-train", [
        ("bringup.worker.imports", 1.0, COST, 106.0),       # 105..106
        ("bringup.worker.connect", 1.0, COST, 107.0),       # past the spawn's
        ("bringup.worker.tpu_client", 4.0, COST, 113.0),
        ("bringup.worker.tpu_client", 0.5, "cpu=0.4 majflt=0 inblock=0",
         109.5),                                            # a shorter one
        ("bringup.worker.tpu_client.client", 3.0, COST, 112.5),
        ("bringup.worker.chip_on_arrival", None, "free", 109.0),
        ("bringup.worker.train_fn_enter", 0.0, "", 115.0),
        ("bringup.trainer_build", 1.0, COST, 117.0),        # 115..116 bare
        ("compile", 4.0, "jaxpr_trace_duration|pretrain_step", 124.0),
        ("compile", 1.0, "jaxpr_trace_duration|_flash_backward", 123.0),
        ("compile", 2.0, "cache_retrieval_time_sec|", 127.0),
        ("compile", 2.5, "backend_compile_duration|jit(pretrain_step)",
         127.0),
        ("bringup.first_run", 9.0, "pretrain_step|" + COST, 129.0),
        ("compile.cache_dir", None, "start|bytes=10 entries=1 max=-1 "
         "written=0 evicted=0", 108.0),
        ("compile.cache_dir", None, "first_report|bytes=30 entries=2 max=-1 "
         "written=1 evicted=0", 130.0),
        ("bringup.first_report", None, "", 130.0),
        ("bringup.state_init", 5.0, COST, 140.0)])          # after the end
    return str(tmp_path)


def test_the_account_sums_to_the_total(recorded):
    account = fr.start_account(recorded)
    assert account["total"] == pytest.approx(30.0)
    named = account["named"]
    assert sum(named.values()) + account["unnamed"] == pytest.approx(30.0)
    # 103..104, 114..114.5, 115..116, 117..120 and 129..130
    assert account["unnamed"] == pytest.approx(6.5)
    assert account["unnamed_by"] == pytest.approx(
        {"before_loop": 1.5, "in_loop": 5.0})
    expected = {
        "bringup.init": 2.0, "bringup.init.nodelet_spawn": 1.0,
        # the gang's own: 104..104.5, 107..108.5 and 113.5..114; its
        # backend's: 108.5..109 and 113..113.5
        "bringup.gang": 2.5, "bringup.gang.backend": 1.0,
        "bringup.worker_spawn": 0.5,            # before the worker's main
        "bringup.worker.imports": 1.0,
        "bringup.worker.connect": 1.0,          # began last: the spawn's end is its
        "bringup.worker.tpu_client": 1.0, "bringup.worker.tpu_client.client": 3.0,
        "bringup.session": 0.5,                 # up to train_fn_enter
        "bringup.trainer_build": 1.0,
        # a trace inside a trace is the inner one's, once
        "compile|jaxpr_trace_duration": 4.0,
        "compile|cache_retrieval_time_sec": 2.0,
        "compile|backend_compile_duration": 0.5,
        "bringup.first_run": 2.5}               # 124..124.5 and 127..129
    assert named == pytest.approx(expected)
    # the longest mark of a kind, whole, with what it cost
    assert account["marks"]["bringup.worker.tpu_client"] == pytest.approx(
        {"seconds": 4.0, "cpu": 0.25, "off_cpu": 3.75, "majflt": 3.0,
         "inblock": 16.0})
    assert account["marks"]["bringup.first_run"]["cpu"] == 0.25
    assert "bringup.worker_spawn" not in account["marks"]   # another's time
    assert account["points"] == {
        "bringup.worker.chip_on_arrival": ["free"],
        "compile.cache_dir": [
            "start|bytes=10 entries=1 max=-1 written=0 evicted=0",
            "first_report|bytes=30 entries=2 max=-1 written=1 evicted=0"]}
    # the timeline's marks are what they were: the cost is off the detail
    marks, gap = fr.bringup_timeline(recorded)
    assert {m[4] for m in marks if m[1] == "bringup.first_run"} == {
        "pretrain_step"}
    assert {m[4] for m in marks if m[1] == "bringup.init"} == {""}
    assert gap == pytest.approx(0.5)     # the pooled worker's spawn covers 103..104


def test_no_account_before_the_first_report(own_ring, tmp_path):
    _ring(str(tmp_path), "w", [("bringup.worker.imports", 1.0, "", 101.0),
                               (fr.ENTERED, 0.0, "", 102.0)])
    assert fr.start_account(str(tmp_path)) is None


def test_the_real_start_is_accounted(started):
    account = started["account"]
    assert sum(account["named"].values()) + account["unnamed"] \
        == pytest.approx(account["total"])
    marks = started["marks"]
    assert account["total"] == pytest.approx(
        _one(marks, fr.FIRST_REPORT)[3] - min(
            m[2] for m in marks if m[1].startswith("bringup.")))
    assert 0.0 <= account["unnamed"] < account["total"]
    for kind in ("bringup.trainer_build", "bringup.first_run",
                 "bringup.worker.jax_import", "compile|jaxpr_trace_duration"):
        assert account["named"][kind] > 0.0, kind
    # what each mark cost, in all three processes' rings: parsed, not negative
    for kind in [f"bringup.{k}" for k in DRIVER + WORKER
                 if k not in ("worker.train_fn_enter", "first_report")]:
        cost = account["marks"][kind]
        assert set(cost) == {"seconds", "cpu", "off_cpu", "majflt",
                             "inblock"}, kind
        assert all(v >= 0.0 for v in cost.values()), (kind, cost)
        assert cost["off_cpu"] <= cost["seconds"]
    # the import is what the class load's seconds were computed in
    assert account["marks"]["bringup.worker.jax_import"]["cpu"] > 0.0


def test_details_a_reader_matches_are_letter_for_letter(started):
    """``bringup.worker_spawn``'s is the worker's id and ``compile``'s
    ``<stage>|<fun_name>``, behind the seconds and with no cost after."""
    worker = _one(started["marks"], fr.ENTERED)[0]
    nodelet = next(m[0] for m in started["marks"]
                   if m[1] == "bringup.worker_spawn")
    spawned = [r["detail"] for r in fr.harvest_for(
        started["session_dir"], nodelet)
        if r["kind"] == "bringup.worker_spawn"]
    assert any(re.fullmatch(r"\d+\.\d{6}\|" + worker, d) for d in spawned)
    compiles = [d for k, d in started["metrics"]["after_first"]
                if k == "compile"]
    assert compiles and all(re.fullmatch(
        r"\d+\.\d{6}\|[a-z_]+\|[^|]*", d) for d in compiles), compiles
    assert any(d.endswith("|jaxpr_trace_duration|pretrain_step")
               for d in compiles)


def test_the_tpu_worker_s_end_is_the_nodelet_s_record(started):
    """The gang's one worker held a (nominal) TPU and the trainer killed it:
    one ``shutdown.worker|<how>|<seconds>|<worker id>``."""
    worker = _one(started["marks"], fr.ENTERED)[0]
    nodelet = next(m[0] for m in started["marks"]
                   if m[1] == "bringup.worker_spawn")
    deadline = time.monotonic() + 10.0      # the watcher's thread writes it
    while True:
        ended = [r["detail"].split("|") for r in fr.harvest_for(
            started["session_dir"], nodelet) if r["kind"] == "shutdown.worker"]
        if ended or time.monotonic() > deadline:
            break
        time.sleep(0.1)
    assert len(ended) == 1, ended
    how, seconds, wid = ended[0][:3]
    assert how == "SIGKILL" and float(seconds) >= 0.0 and wid == worker
    assert ended[0][3:] in ([], ["alive"])


def test_the_trainer_logs_the_start_once(started):
    assert len(started["start_lines"]) == 1
    line = started["start_lines"][0]
    for label in ("to the first report", " more ", "unnamed ", "(in the loop ",
                  "tpu client ", "(cpu ", "chip ?"):   # no arrival on the cpu
        assert label in line, (label, line)
    from ray_tpu.train.base_trainer import start_line

    whole = start_line(started["account"], top=100)
    for name in started["account"]["named"]:
        assert name.replace("bringup.", "") + " " in whole, name


def _holder(path):
    """A process that holds ``path`` open until it is killed."""
    proc = subprocess.Popen(
        [sys.executable, "-c",
         f"import time; f = open({path!r}); print('open', flush=True); "
         "time.sleep(60)"], stdout=subprocess.PIPE)
    assert proc.stdout.readline().strip() == b"open"
    return proc


def _arrivals(session_dir):
    return [r["detail"] for r in fr.harvest_for(session_dir, "test")
            if r["kind"] == "bringup.worker.chip_on_arrival"]


def test_chip_on_arrival(own_ring, tmp_path, monkeypatch):
    from ray_tpu._private import platform

    _, session_dir = own_ring
    assert fr.init_process(session_dir, "test")
    # no device file (this machine has none): free, and no walk of /proc
    monkeypatch.setattr(platform, "_CHIP_FILES", (str(tmp_path / "chip*"),))
    assert platform.chip_holders() == []
    with platform.chip_on_arrival():
        pass
    assert _arrivals(session_dir) == ["free"]
    # a device file that nobody holds
    chip = tmp_path / "chip0"
    chip.write_text("")
    with platform.chip_on_arrival():
        pass
    assert _arrivals(session_dir) == ["free", "free"]
    # held to the block's end, and let go inside it
    first, second = _holder(str(chip)), None
    try:
        (pid, state, link), = platform.chip_holders()
        assert pid == first.pid and state in "RSD" and os.readlink(
            link) == str(chip)
        t0 = time.perf_counter()
        with platform.chip_on_arrival():
            time.sleep(0.2)
        whole = time.perf_counter() - t0
        first.kill()
        first.wait(10.0)
        second = _holder(str(chip))
        with platform.chip_on_arrival():
            second.kill()
            second.wait(10.0)
            time.sleep(0.5)
    finally:
        for proc in (first, second):
            if proc is not None and proc.poll() is None:
                proc.kill()
                proc.wait(10.0)
    held = [d.split("|") for d in _arrivals(session_dir)[2:]]
    assert [h[:2] for h in held] == [["held", str(first.pid)],
                                     ["held", str(second.pid)]]
    assert 0.2 <= float(held[0][3]) <= whole
    assert float(held[1][3]) < 0.5          # gone before the block was


def test_the_client_s_seams_are_marks(own_ring):
    """``client_seams`` on stand-ins of the installed JAX's two functions:
    the load inside the client's making, each from its call to its return."""
    from ray_tpu._private import platform

    _, session_dir = own_ring
    assert fr.init_process(session_dir, "test")

    def load_pjrt_plugin_dynamically():
        time.sleep(0.02)

    def make_tpu_client():
        load_pjrt_plugin_dynamically()
        time.sleep(0.02)

    def other():
        pass

    with platform.client_seams():
        other()
        make_tpu_client()
    make_tpu_client()               # outside: nothing
    assert sys.getprofile() is None
    marks = {m[1]: m for m in fr.bringup_timeline(session_dir)[0]}
    assert set(marks) == {"bringup.worker.tpu_client.plugin_load",
                          "bringup.worker.tpu_client.client"}
    load = marks["bringup.worker.tpu_client.plugin_load"]
    client = marks["bringup.worker.tpu_client.client"]
    # a start is the stamp less the seconds, off two clocks: a millisecond
    assert client[2] - 1e-3 <= load[2] <= load[3] <= client[3]
    assert load[3] - load[2] >= 0.02 and client[3] - client[2] >= 0.04


def test_the_compile_cache_s_fill_is_recorded(own_ring, tmp_path,
                                              monkeypatch):
    from ray_tpu._private import platform

    _, session_dir = own_ring
    assert fr.init_process(session_dir, "test")
    cache = tmp_path / "jaxc"
    cache.mkdir()
    (cache / "a-cache").write_bytes(b"x" * 100)
    (cache / "a-atime").write_bytes(b"x" * 8)
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(cache))
    monkeypatch.setattr(platform, "_cache_entries", None)
    platform.record_compile_cache("too early")      # no cache: nothing
    assert platform.enable_compile_cache() == str(cache)
    (cache / "b-cache").write_bytes(b"x" * 50)
    (cache / "a-cache").unlink()
    platform.record_compile_cache("first_report")
    found = [r["detail"] for r in fr.harvest_for(session_dir, "test")
             if r["kind"] == "compile.cache_dir"]
    limit = "max=-1"    # JAX's default: JAX_COMPILATION_CACHE_MAX_SIZE unset
    assert found == [
        f"start|bytes=108 entries=1 {limit} written=0 evicted=0",
        f"first_report|bytes=58 entries=1 {limit} written=1 evicted=1"]

"""A job's start on one clock: the ``bringup.*`` marks of the driver, the
nodelet and the train worker, and the worker's own compile records, in the
flight recorder — read back through ``flight_recorder.bringup_timeline``.

One real local cluster and one one-worker ``JaxTrainer`` on the cpu platform
serve cases (a), (b), (e), (f) and the trainer's log line; (g) runs the same
loop with the recorder off.  No wall-clock threshold decides a verdict: the
orderings hold by construction, and the recompile warning is driven through
JAX's own monitoring channel with a stated duration.
"""

import logging
import os

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
    ray_tpu.init(num_cpus=4, object_store_memory=128 * 1024**2)
    try:
        core = global_worker_core()
        out = {"session_dir": core.session_dir,
               "driver": core.worker_id.hex()}
        out["metrics"] = JaxTrainer(
            _loop, jax_config=JaxConfig(platform="cpu"),
            scaling_config=ScalingConfig(num_workers=1),
            run_config=RunConfig(name=name, storage_path=str(tmp / name)),
        ).fit().metrics
    finally:
        ray_tpu.shutdown()
        logger.removeHandler(keep)
        logger.setLevel(level)
    out["lines"] = [ln for ln in lines if ln.startswith("train gang up")]
    out["marks"], out["gap"] = fr.bringup_timeline(out["session_dir"])
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
          "worker.jax_import", "worker.tpu_client", "worker.train_fn_enter",
          "state_init")


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
    inside("worker.jax_import", "gang.backend")
    inside("worker.tpu_client", "gang.backend")
    inside("worker.train_fn_enter", "session")
    assert at["gang"][1] <= at["session"][0]
    assert at["worker.train_fn_enter"][1] <= at["state_init"][0]
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
    # ten further steps of the same shape: not one record of either family
    assert m["after_ten_more"] == m["after_first"]
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

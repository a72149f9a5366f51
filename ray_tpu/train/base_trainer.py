"""BaseTrainer + DataParallelTrainer.

Counterpart of the reference's trainer stack (reference:
python/ray/train/base_trainer.py:111 BaseTrainer, fit :567;
train/data_parallel_trainer.py:25 DataParallelTrainer, _run_training :362).
The reference routes every ``fit()`` through a single-trial Tuner
(base_trainer.py:577-623); here ``fit()`` runs through
``ray_tpu.tune.run_single_trial`` — the same controller Tune uses — so
failure retries, experiment snapshots, and checkpoint bookkeeping are one
code path whether the trainer is used standalone or under a Tuner.
"""

from __future__ import annotations

import json
import logging
import os
import time
import uuid
from typing import Any, Callable, Dict, List, Optional

import cloudpickle

from ray_tpu._private import flight_recorder
from ray_tpu.air.config import RunConfig, ScalingConfig
from ray_tpu.air.result import Result
from ray_tpu.train._backend_executor import BackendExecutor, TrainingFailedError
from ray_tpu.train import storage
from ray_tpu.train._checkpoint import Checkpoint
from ray_tpu.train.jax_config import BackendConfig

_TRAINER_PKL = "trainer.pkl"
_PROGRESS_JSON = "progress.json"

logger = logging.getLogger(__name__)


class BaseTrainer:
    """Reference: train/base_trainer.py:111."""

    def __init__(self, *, scaling_config: Optional[ScalingConfig] = None,
                 run_config: Optional[RunConfig] = None,
                 resume_from_checkpoint: Optional[Checkpoint] = None):
        import copy

        self.scaling_config = scaling_config or ScalingConfig()
        # private copy: auto-generating a name must not mutate a RunConfig
        # the caller may share between trainers
        self.run_config = copy.deepcopy(run_config) if run_config else RunConfig()
        self.resume_from_checkpoint = resume_from_checkpoint
        if self.run_config.name is None:
            self.run_config.name = (
                f"{type(self).__name__}_{time.strftime('%Y-%m-%d_%H-%M-%S')}"
                f"_{uuid.uuid4().hex[:6]}")

    # ------------------------------------------------------------------ fit
    def fit(self) -> Result:
        """Run to completion, with FailureConfig-driven retries restoring
        from the latest durable checkpoint (reference: fit routes through
        Tuner, base_trainer.py:577-623)."""
        from ray_tpu.tune._single_trial import run_trainer_as_single_trial

        return run_trainer_as_single_trial(self)

    # --------------------------------------------------------- restoration
    @classmethod
    def can_restore(cls, path: str) -> bool:
        return storage.exists(
            storage.join(storage.expand(path), _TRAINER_PKL))

    @classmethod
    def restore(cls, path: str, **overrides) -> "BaseTrainer":
        """Rebuild a trainer from a trial dir written by a previous fit();
        training resumes from the latest complete checkpoint (reference:
        base_trainer.py restore/can_restore)."""
        path = storage.expand(path)
        state = cloudpickle.loads(
            storage.read_bytes(storage.join(path, _TRAINER_PKL)))
        trainer: BaseTrainer = state["trainer"]
        for k, v in overrides.items():
            if v is not None:
                setattr(trainer, k, v)
        latest = latest_checkpoint(path)
        if latest:
            trainer.resume_from_checkpoint = Checkpoint(latest)
        # keep writing into the same trial dir
        trainer.run_config.name = state["name"]
        trainer.run_config.storage_path = state["storage_path"]
        return trainer

    # ------------------------------------------------------------- plumbing
    @property
    def trial_dir(self) -> str:
        return storage.join(storage.expand(self.run_config.storage_path),
                            self.run_config.name)

    def _save_trainer_state(self) -> None:
        storage.makedirs(self.trial_dir)
        storage.write_bytes(
            storage.join(self.trial_dir, _TRAINER_PKL),
            cloudpickle.dumps({
                "trainer": self,
                "name": self.run_config.name,
                "storage_path": self.run_config.storage_path,
            }))

    def training_loop(self) -> Result:
        """One attempt; subclasses implement.  Retries are the caller's job
        (single-trial controller)."""
        raise NotImplementedError


def _next_checkpoint_seq(trial_dir: str) -> int:
    """First unused checkpoint number: a restarted attempt must not merge
    fresh state into a stale same-numbered dir."""
    seqs = []
    try:
        for d in storage.listdir(trial_dir):
            if d.startswith("checkpoint_"):
                try:
                    seqs.append(int(d.split("_", 1)[1]))
                except ValueError:
                    pass
    except OSError:
        pass
    return max(seqs) + 1 if seqs else 0


def latest_checkpoint(trial_dir: str) -> Optional[str]:
    """The newest checkpoint recorded COMPLETE in progress.json (written by
    the driver only after every rank's report round-tripped) — scanning the
    filesystem would trust half-written dirs."""
    progress = storage.join(trial_dir, _PROGRESS_JSON)
    try:
        data = json.loads(storage.read_bytes(progress))
    except (OSError, json.JSONDecodeError):
        return None
    path = data.get("latest_checkpoint")
    return path if path and storage.exists(path) else None


# the gang's start, phase by phase, as the one line an operator reads:
# (label, mark, [(label, mark) shown in brackets after it])
_GANG_UP_PHASES = (
    ("init", "bringup.init", (("gcs", "bringup.init.gcs_spawn"),
                              ("nodelet", "bringup.init.nodelet_spawn"),
                              ("connect", "bringup.init.driver_connect"))),
    ("gang", "bringup.gang", (("placement", "bringup.gang.placement_group"),
                              ("actors", "bringup.gang.actors"),
                              ("backend", "bringup.gang.backend"))),
    ("worker spawn", "bringup.worker_spawn",
     (("imports", "bringup.worker.imports"),
      ("connect", "bringup.worker.connect"))),
    ("actor", "bringup.worker.actor",
     (("jax import", "bringup.worker.jax_import"),)),
    ("compile cache", "bringup.worker.compile_cache", ()),
    ("distributed init", "bringup.worker.distributed_init", ()),
    ("tpu client", "bringup.worker.tpu_client",
     (("plugin", "bringup.worker.tpu_client.plugin_load"),
      ("client", "bringup.worker.tpu_client.client"),
      ("query", "bringup.worker.tpu_client.device_query"))),
    ("session", "bringup.session", ()),
)


def gang_up_line(marks: List[flight_recorder.Mark]) -> Optional[str]:
    """``flight_recorder.bringup_timeline``'s marks as one line: every phase
    that has a mark (the slowest where a gang's workers each wrote one) and
    the seconds no mark covers.  ``None`` until a train function was
    entered."""
    gap = flight_recorder.bringup_gap(marks)
    if gap is None:
        return None
    trained = {m[0] for m in marks if m[1] == flight_recorder.ENTERED}
    seconds: Dict[str, float] = {}
    for name, kind, start, end, detail in marks:
        if kind == "bringup.worker_spawn" and detail not in trained:
            continue    # a pooled worker that is not of this gang
        seconds[kind] = max(seconds.get(kind, 0.0), end - start)

    def shown(label, kind, children=()):
        if kind not in seconds:
            return None
        inner = ", ".join(filter(None, (shown(*c) for c in children)))
        return f"{label} {seconds[kind]:.1f}" + (f" ({inner})" if inner else "")

    total = max(m[3] for m in marks if m[1] == flight_recorder.ENTERED) \
        - min(m[2] for m in marks if m[1].startswith("bringup."))
    phases = filter(None, (shown(*p) for p in _GANG_UP_PHASES))
    return (f"train gang up in {total:.1f} s: " + " | ".join(phases)
            + f" | uncovered {gap:.1f}")


def start_line(account: Dict[str, Any], top: int = 12) -> str:
    """``flight_recorder.start_account``'s account as one line: the start's
    seconds to the first ``train.report``, the ``top`` names that hold most
    of them (each what none of its children holds), the seconds no mark
    holds, what the TPU client's seconds were (computing, the disk, or
    neither), the chip's state on arrival and the compile cache's fill."""
    named = sorted(account["named"].items(), key=lambda kv: -kv[1])
    parts = [f"{name.removeprefix('bringup.')} {secs:.1f}"
             for name, secs in named[:top]]
    if named[top:]:
        parts.append(f"{len(named) - top} more "
                     f"{sum(secs for _, secs in named[top:]):.1f}")
    parts.append(f"unnamed {account['unnamed']:.1f} (in the loop "
                 f"{account['unnamed_by']['in_loop']:.1f})")
    client = account["marks"].get("bringup.worker.tpu_client")
    if client:
        parts.append(
            f"tpu client {client['seconds']:.1f} (cpu {client['cpu']:.1f}, "
            f"major faults {client['majflt']:.0f}, blocks read "
            f"{client['inblock']:.0f}; chip " + ", ".join(
                account["points"].get("bringup.worker.chip_on_arrival", "?"))
            + ")")
    for cache in account["points"].get("compile.cache_dir", ())[-1:]:
        parts.append("compile cache " + cache.partition("|")[2])
    return (f"train start took {account['total']:.1f} s to the first report: "
            + " | ".join(parts))


def rounds_line(timeline: List[flight_recorder.Round]) -> Optional[str]:
    """``flight_recorder.round_timeline``'s records as one line: the rounds
    and steps the workers' loops made, the median round, the record that
    holds the longest with where its seconds went on the worker's side and
    in the driver's records beside it, the rounds stalled and the seconds of
    garbage collection.  ``None`` where no round was recorded."""
    import statistics

    from ray_tpu.train._session import where_it_went

    records = [r for r in timeline if r.kind == flight_recorder.ROUNDS]
    if not records:
        return None
    total = lambda key: sum(r.counts.get(key, 0) for r in records)  # noqa: E731
    worst = max(records, key=lambda r: r.counts.get("longest", 0.0))
    took = worst.end - worst.start
    beside = dict.fromkeys(("skew_probe", "poll", "turnaround"), 0.0)
    for d in timeline:
        shared = min(d.end, worst.end) - max(d.start, worst.start)
        if d.kind == flight_recorder.DRIVER_ROUNDS and shared > 0:
            for key in beside:      # its share, by time
                beside[key] += d.seconds.get(key, 0.0) * shared / (
                    d.end - d.start)
    median = statistics.median(s for r in records for s in r.each())
    return (
        f"train loop made {total('rounds'):.0f} rounds ({total('steps'):.0f} "
        f"steps), median {median:.3f} s; longest "
        f"{worst.counts.get('longest', took):.3f} s, in "
        f"{worst.counts.get('rounds', 1):.0f} round(s) of {took:.2f} s: "
        f"{where_it_went(took, worst.seconds)} | driver beside it: "
        + ", ".join(f"{key} {secs:.2f}" for key, secs in sorted(
            beside.items(), key=lambda kv: -kv[1]))
        + f" | stalled {sum(r.kind == flight_recorder.STALL for r in timeline)}"
        f" | gc {sum(r.seconds.get('gc', 0.0) for r in records):.2f} s")


class DataParallelTrainer(BaseTrainer):
    """SPMD function-trainer: same ``train_loop_per_worker`` on every worker
    of the gang (reference: train/data_parallel_trainer.py:25)."""

    _default_backend_config: BackendConfig = BackendConfig()

    def __init__(self, train_loop_per_worker: Callable,
                 *, train_loop_config: Optional[Dict[str, Any]] = None,
                 backend_config: Optional[BackendConfig] = None,
                 scaling_config: Optional[ScalingConfig] = None,
                 run_config: Optional[RunConfig] = None,
                 datasets: Optional[Dict[str, Any]] = None,
                 resume_from_checkpoint: Optional[Checkpoint] = None):
        super().__init__(scaling_config=scaling_config, run_config=run_config,
                         resume_from_checkpoint=resume_from_checkpoint)
        if not callable(train_loop_per_worker):
            raise ValueError("train_loop_per_worker must be callable "
                             "(taking 0 or 1 argument: the config dict)")
        self.train_loop_per_worker = train_loop_per_worker
        self.train_loop_config = train_loop_config or {}
        self.backend_config = backend_config or self._default_backend_config
        # name -> ray_tpu.data.Dataset; split per-worker at fit time and
        # consumed in the loop via train.get_dataset_shard (reference:
        # data_parallel_trainer.py datasets= + session dataset_shard)
        self.datasets = datasets or {}

    # ------------------------------------------------------- one attempt
    def training_loop(self) -> Result:
        """Reference: data_parallel_trainer.py:362 _run_training — but the
        executor lives on the driver side of the trial."""
        from ray_tpu.train._metrics import GANG_STATES, train_metrics

        t_loop = time.time()
        trial_dir = self.trial_dir
        storage.makedirs(trial_dir)
        self._save_trainer_state()

        metrics = train_metrics()
        mlabels = {"experiment": self.run_config.name or ""}
        metrics["gang_state"].set(GANG_STATES["STARTING"], mlabels)
        executor = BackendExecutor(self.backend_config, self.scaling_config)
        executor.start()
        t_session = flight_recorder.usage()
        metrics_history = []
        latest_ckpt: Optional[str] = (
            self.resume_from_checkpoint.path
            if self.resume_from_checkpoint else None)
        last_metrics: Dict[str, Any] = {}
        # Each named dataset splits into one coordinated streaming iterator
        # per worker; equal=True keeps lockstep SPMD loops in sync.
        n_workers = self.scaling_config.num_workers
        dataset_shards: Optional[list] = None
        if self.datasets:
            per_name = {name: ds.streaming_split(n_workers, equal=True)
                        for name, ds in self.datasets.items()}
            dataset_shards = [
                {name: its[rank] for name, its in per_name.items()}
                for rank in range(n_workers)
            ]
        try:
            executor.start_training(
                self.train_loop_per_worker, self.train_loop_config,
                experiment_name=self.run_config.name or "",
                trial_name=self.run_config.name or "",
                trial_dir=trial_dir,
                checkpoint_path=latest_ckpt,
                checkpoint_seq_start=_next_checkpoint_seq(trial_dir),
                dataset_shards=dataset_shards,
            )
            flight_recorder.mark_since("bringup.session", t_session)
            metrics["gang_state"].set(GANG_STATES["RUNNING"], mlabels)
            self._log_gang_up(t_loop)
            metrics["gang_workers"].set(n_workers, mlabels)
            while True:
                results = executor.get_next_results(
                    timeout_s=self.run_config.worker_report_timeout_s)
                if results is None:
                    break
                if not metrics_history:
                    self._log_start(t_loop)
                metrics["report_rounds"].inc(1, mlabels)
                rank0 = results[0]
                last_metrics = rank0.metrics
                metrics_history.append(rank0.metrics)
                ckpts = {r.checkpoint_path for r in results if r.checkpoint_path}
                if ckpts:
                    if len(ckpts) > 1:
                        raise TrainingFailedError(
                            f"ranks persisted to different checkpoint dirs: "
                            f"{sorted(ckpts)}")
                    latest_ckpt = ckpts.pop()
                    self._write_progress(trial_dir, latest_ckpt, last_metrics)
                    self._apply_retention(trial_dir, latest_ckpt)
            metrics["gang_state"].set(GANG_STATES["FINISHED"], mlabels)
        except BaseException:
            metrics["gang_state"].set(GANG_STATES["FAILED"], mlabels)
            raise
        finally:
            metrics["gang_workers"].set(0, mlabels)
            executor.shutdown()
            self._log_rounds(t_loop)

        return Result(
            metrics=last_metrics,
            checkpoint=Checkpoint(latest_ckpt) if latest_ckpt else None,
            path=trial_dir,
            metrics_history=metrics_history,
        )

    @staticmethod
    def _gang_marks(t_loop: float):
        """The session's directory, the time since which its marks are those
        of the gang that ``training_loop`` began at ``t_loop``, and those
        marks; ``None`` where the recorder is off."""
        from ray_tpu._private.worker import global_worker_core

        core = global_worker_core()
        if core is None or not flight_recorder.RECORDING:
            return None
        marks, _ = flight_recorder.bringup_timeline(core.session_dir)
        # not the session's first gang: the runtime's start and the earlier
        # gangs' marks are not part of this one's
        since = t_loop if any(m[1] == "bringup.gang" and m[3] < t_loop
                              for m in marks) else 0.0
        return core.session_dir, since, [m for m in marks if m[3] >= since]

    @classmethod
    def _log_gang_up(cls, t_loop: float) -> None:
        """One INFO line from the session's rings, which outlives their
        wrap in the driver's log; nothing where the recorder is off."""
        found = cls._gang_marks(t_loop)
        line = found and gang_up_line(found[2])
        if line:
            logger.info(line)

    @classmethod
    def _log_start(cls, t_loop: float) -> None:
        """The start's one INFO line, when the first round of reports is in:
        ``_log_gang_up``'s sequel, down to the first ``train.report``."""
        found = cls._gang_marks(t_loop)
        account = found and flight_recorder.start_account(*found[:2])
        if account:
            logger.info(start_line(account))

    @staticmethod
    def _log_rounds(t_loop: float) -> None:
        """The steady state's one INFO line, ``_log_gang_up``'s sibling for
        the loop that followed it."""
        from ray_tpu._private.worker import global_worker_core

        core = global_worker_core()
        if core is None or not flight_recorder.RECORDING:
            return
        line = rounds_line([r for r in flight_recorder.round_timeline(
            core.session_dir) if r.end >= t_loop])
        if line:
            logger.info(line)

    def _write_progress(self, trial_dir: str, ckpt: str, metrics) -> None:
        storage.write_bytes(
            storage.join(trial_dir, _PROGRESS_JSON),
            json.dumps({"latest_checkpoint": ckpt,
                        "metrics": _jsonable(metrics),
                        "time": time.time()}).encode())

    def _apply_retention(self, trial_dir: str, latest: str) -> None:
        keep = self.run_config.checkpoint_config.num_to_keep
        if keep is None:
            return
        ckpts = sorted(
            d for d in storage.listdir(trial_dir)
            if d.startswith("checkpoint_"))
        for d in ckpts[:-keep]:
            full = storage.join(trial_dir, d)
            if full != latest:
                storage.rmtree(full)


def _jsonable(obj):
    try:
        json.dumps(obj)
        return obj
    except (TypeError, ValueError):
        return {k: v for k, v in obj.items()
                if isinstance(v, (int, float, str, bool, type(None)))} \
            if isinstance(obj, dict) else str(obj)

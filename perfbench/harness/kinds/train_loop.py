"""Traffic kind ``train_loop``: a pretraining loop as a user of
``ray_tpu.train`` writes it — build ``ShardedPretrainer``, take batches, call
``trainer.step``, call ``train.report`` — with the benchmark's measurements
around it.

Traffic file keys: ``seq``, ``rows_per_step`` (global), ``mesh`` (arguments of
``MeshConfig``), ``feed`` (``generator``: batches from the in-process seeded
stream; ``dataset``: ``train.get_dataset_shard("train").iter_jax_batches`` over
a ``ray_tpu.data`` dataset of ``dataset_rows`` rows of the same stream, built
in set-up and iterated again when it ends), ``report_every`` (steps between
``train.report`` calls; the loss is read on the host only there), ``trace``
(``from_step``, ``steps``: the sub-window a traced run profiles; both
multiples of ``report_every``, so it opens and closes on an idle device),
``loss_fall_min`` (see ``verdict``), ``why`` and, where a cell's work follows
from the ids (held experts), ``window_ids_seed`` with ``window_ids_why``: the
generator's warm-up and window then take the same ids for every ``--seed``
(``tokens.py``), and the seed draws the reference check's rows alone.

Inside the worker, in order: state initialised on the device under ``jit`` (by
the program), agreement with the plain reference, warm-up of the cell's one
step shape until a step builds nothing, the window, ``block_until_ready`` on
the final state; the measurements go back in the last ``train.report``.
"""

from __future__ import annotations

import dataclasses
import glob
import math
import os
import tempfile
import time
from typing import Any, Dict, Iterator, List, Optional

import numpy as np

from perfbench.harness import (agreement, compile_watch, driver, families,
                               flops, manifest, trace_reduce)
from perfbench.harness.spans import Spans
from perfbench.harness.tokens import ZipfStream

MAX_WARM_STEPS = 6


def run(cell: manifest.Cell, **spec: Any) -> Dict[str, Any]:
    """Driver side: ``ray_tpu.init()`` -> ``JaxTrainer`` -> one train worker
    that owns the cell's chips and runs ``loop`` -> what its last report
    brought back.  ``spec``: ``seed``, ``seconds``, ``trace``, ``t_start``,
    ``trace_dir``."""
    import ray_tpu
    from ray_tpu.train import JaxTrainer, RunConfig, ScalingConfig

    ray_tpu.init()
    try:
        with tempfile.TemporaryDirectory(prefix="perfbench_") as storage:
            result = JaxTrainer(
                loop,
                train_loop_config=dict(spec, cell=dataclasses.asdict(cell)),
                scaling_config=ScalingConfig(num_workers=1,
                                             tpus_per_worker=cell.chips),
                datasets=datasets(cell, spec["seed"]),
                run_config=RunConfig(name=f"perfbench-{cell.name}",
                                     storage_path=storage,
                                     worker_report_timeout_s=1100.0),
            ).fit()
    except BaseException:
        driver.show_worker_logs()
        raise
    finally:
        ray_tpu.shutdown()
    return result.metrics["perfbench"]


def datasets(cell: manifest.Cell, seed: int) -> Optional[Dict[str, Any]]:
    """Driver side, in set-up: the ``datasets=`` of the trainer, where the
    cell's feed is a dataset."""
    traffic = cell.traffic
    if traffic["feed"] != "dataset":
        return None
    import ray_tpu.data

    ids = ZipfStream(cell.config["vocab_size"], seed).rows(
        traffic["dataset_rows"], traffic["seq"])["input_ids"]
    blocks = np.array_split(ids, traffic["dataset_blocks"])
    return {"train": ray_tpu.data.from_numpy(blocks, column="input_ids")}


def _batches(traffic, stream: ZipfStream) -> Iterator[Dict[str, Any]]:
    rows, seq = traffic["rows_per_step"], traffic["seq"]
    if traffic["feed"] == "generator":
        return stream.batches(rows, seq)
    if traffic["feed"] != "dataset":
        raise ValueError(f"unknown feed {traffic['feed']!r}")
    import jax.numpy as jnp

    from ray_tpu import train

    shard = train.get_dataset_shard("train")

    def epochs():
        while True:
            for batch in shard.iter_jax_batches(batch_size=rows):
                ids = batch["input_ids"]
                yield {"input_ids": ids, "targets": jnp.roll(ids, -1, axis=1)}

    return epochs()


class _Profiler:
    """``jax.profiler`` over steps ``[from_step, from_step + steps)`` of the
    window; the ``bench/window`` span marks the traced window on the trace's
    own clock.  What else only a traced run does lives here, so that the
    untraced loop is the parent's statement for statement: the two calls of
    the profiler are stamped on the flight recorder's clock (``calls``: the
    rounds they fall into are the benchmark's, not the program's, and reader
    ``rounds`` leaves them out), and with ``counters`` (a function that gives
    the program's device scalars of the last step, ``trainer.moe_stats``)
    those are read after every ``every``-th step — a report's step, which the
    loop has just synchronised on — outside the traced steps, and averaged
    over the window."""

    def __init__(self, spec: Dict[str, int], out_dir: str, every: int = 1,
                 counters=None):
        self.first, self.last = spec["from_step"], spec["from_step"] + spec["steps"]
        self.steps = spec["steps"]
        self.dir = out_dir
        self._window = None
        self.calls: List[List[float]] = []      # [start, end], time.time()
        self._every, self._counters = every, counters
        self._counted: Dict[str, List[float]] = {}

    def before_step(self, step: int) -> None:
        if step != self.first:
            return
        import jax
        from jax.profiler import ProfileOptions, TraceAnnotation

        options = ProfileOptions()
        options.python_tracer_level = 0     # no Python frames: spans only
        options.host_tracer_level = 2
        t0 = time.time()
        jax.profiler.start_trace(self.dir, profiler_options=options)
        self.calls.append([t0, time.time()])
        self._window = TraceAnnotation(trace_reduce.SPAN_PREFIX + "window")
        self._window.__enter__()

    def after_step(self, step: int) -> None:
        import jax

        if step == self.last and self._window is not None:
            self._window.__exit__(None, None, None)
            self._window = None
            t0 = time.time()
            jax.profiler.stop_trace()
            self.calls.append([t0, time.time()])
        # not while the traced sub-window is open: what the trace shows of
        # the host loop stays what an untraced step does
        if self._counters is not None and self._window is None \
                and step % self._every == 0:
            for name, value in jax.device_get(self._counters()).items():
                self._counted.setdefault(name, []).append(float(value))

    def reduced(self) -> Optional[Dict[str, Any]]:
        """The traced window, reduced and left beside the profiler's own file
        (it is too large for a report): where it is, and its steps; with them
        the profiler's two calls and the window's means of the counters."""
        if self._window is not None:
            # the window closed before the sub-window did: nothing to read
            self.after_step(self.last)
            return None
        files = glob.glob(os.path.join(self.dir, "plugins", "profile", "*",
                                       "*.xplane.pb"))
        if not files:
            return None
        trace = trace_reduce.load(max(files, key=os.path.getmtime))
        if trace.window() is None:
            return None
        path = os.path.join(self.dir, "reduced.json")
        with open(path, "w") as f:
            f.write(trace.clipped(trace.window()).to_json())
        return {"steps": self.steps, "file": path,
                "profiler_calls": self.calls,
                "counters": {name: float(np.mean(values))
                             for name, values in self._counted.items()}}


def _tenth_means(losses: List[float]):
    n = max(1, len(losses) // 10)
    return float(np.mean(losses[:n])), float(np.mean(losses[-n:]))


def loop(run: Dict[str, Any]) -> None:
    """The train worker.  ``run``: ``cell`` (the fields of ``manifest.Cell``),
    ``seed``, ``seconds``, ``trace``, ``t_start`` (host clock at the
    benchmark's start), ``trace_dir``."""
    import jax

    from ray_tpu import train
    from ray_tpu.models.pretrain import ShardedPretrainer
    from ray_tpu.parallel.mesh import MeshConfig

    cell = manifest.Cell(**run["cell"])
    traffic, config = cell.traffic, cell.config
    seen = compile_watch.watch()
    marks = [("to_worker", time.time())]   # set-up, part by part
    devices = jax.devices()
    if len(devices) != cell.chips:
        raise RuntimeError(f"cell {cell.name} wants {cell.chips} devices, the "
                           f"worker has {len(devices)}")

    trainer = ShardedPretrainer(
        families.of(config).model_config(config, cell.chips),
        MeshConfig(**traffic["mesh"]))
    marks.append(("trainer_and_state", time.time()))
    stream = ZipfStream(config["vocab_size"], run["seed"],
                        window_ids_seed=traffic.get("window_ids_seed"))
    replicas = trainer.mesh.shape["dp"] * trainer.mesh.shape["fsdp"]
    agreed = agreement.check(trainer, config,
                             stream.rows(replicas, traffic["seq"]))

    marks.append(("agreement", time.time()))
    batches = _batches(traffic, stream)
    spans = Spans()
    every = traffic["report_every"]
    losses: List[Any] = []

    def one_step(report_every: int) -> None:
        with spans("input"):
            batch = next(batches)
        try:
            with spans("step"):
                losses.append(trainer.step(batch))
        except Exception:
            if not measuring:
                raise
            losses.append(float("nan"))     # a failed step of the window
        if len(losses) % report_every == 0:
            with spans("sync"):
                value = float(losses[-1])
            with spans("report"):
                train.report({"step": len(losses), "loss": value})

    # warm-up: step, read the loss and report, until a step builds nothing
    measuring = False
    build_events: List[compile_watch.Event] = []
    while True:
        n_seen = len(seen)
        one_step(report_every=1)
        jax.block_until_ready(trainer.state)
        if not build_events:    # the first step's, without the one-liners
            build_events = [e for e in seen[n_seen:] if e[2] >= 0.05]
        if len(losses) > 1 and not compile_watch.built(seen[n_seen:]):
            break
        if len(losses) >= MAX_WARM_STEPS:
            raise RuntimeError(f"still compiling after {len(losses)} steps: "
                               f"{compile_watch.built(seen[n_seen:])}")
    warm_steps = len(losses)
    marks.append(("warm_up", time.time()))

    losses.clear()
    spans.clear()
    profiler = _Profiler(traffic["trace"], run["trace_dir"], every,
                         lambda: trainer.moe_stats) \
        if run["trace"] else None
    n_seen = len(seen)
    measuring = True
    setup_s = time.time() - run["t_start"]
    t_open = time.perf_counter()
    deadline = t_open + run["seconds"]
    while time.perf_counter() < deadline or len(losses) % every:
        if profiler:
            profiler.before_step(len(losses))
        one_step(every)
        if profiler:
            profiler.after_step(len(losses))
    jax.block_until_ready(trainer.state)
    elapsed = time.perf_counter() - t_open

    values = [float(x) for x in losses]
    failed = sum(1 for v in values if not math.isfinite(v))
    tokens = (len(values) - failed) * traffic["rows_per_step"] * traffic["seq"]
    first, last = _tenth_means(values)
    compiled_in_window = compile_watch.built(seen[n_seen:])
    stats = [d.memory_stats() or {} for d in devices]
    train.report({"perfbench": {
        "device": {"platform": devices[0].platform,
                   "kind": devices[0].device_kind, "count": len(devices)},
        "setup_s": setup_s,
        "setup_parts_s": {name: t - before for (name, t), before in zip(
            marks, [run["t_start"]] + [t for _, t in marks])},
        "elapsed_s": elapsed,
        "steps": len(values), "warm_steps": warm_steps,
        "failed": failed, "tokens": tokens,
        "loss_first_tenth": first, "loss_last_tenth": last,
        "agreement": agreed,
        "compiled_in_window": [list(e) for e in compiled_in_window],
        "build_events": [list(e) for e in build_events],
        "memory": [{k: int(v) for k, v in s.items()
                    if isinstance(v, (int, float))} for s in stats],
        "spans_ms": {name: spans.durations_ms(name) for name in spans.seen},
        "trace": profiler.reduced() if profiler else None,
    }})


# ------------------------------------------- driver side, after the run
def end_to_end(cell: manifest.Cell, m: Dict[str, Any],
               peak: Dict[str, float]) -> Dict[str, float]:
    """The end-to-end values of a run, from the worker's measurements ``m``
    and the device kind's row of ``peaks.json``."""
    rate = m["tokens"] / m["elapsed_s"] / cell.chips
    required = flops.train_flops_per_token(cell.config, cell.chips,
                                           cell.traffic["seq"])
    return {"tokens_per_s_per_chip": rate,
            "mfu_pct": 100.0 * rate * required / peak["bf16_flops_per_s"],
            "setup_s": m["setup_s"]}


def verdict(cell: manifest.Cell, m: Dict[str, Any]) -> Dict[str, bool]:
    """This kind's conditions of ``correct``, by name; the driver adds those
    of every kind (no compile in the window, the device is the cell's)."""
    fall = m["loss_first_tenth"] - m["loss_last_tenth"]
    return {"agrees_with_reference": bool(m["agreement"]["ok"]),
            "every_loss_finite": m["failed"] == 0 and m["steps"] > 0,
            "loss_fell": fall >= cell.traffic["loss_fall_min"]}


def detail(m: Dict[str, Any]) -> Dict[str, Any]:
    """What a person reading the line wants beside the metrics."""
    return {"agreement": m["agreement"],
            "loss": [m["loss_first_tenth"], m["loss_last_tenth"]],
            "window_s": m["elapsed_s"], "warm_steps": m["warm_steps"],
            "setup_parts_s": m["setup_parts_s"],
            "build_events": m["build_events"]}

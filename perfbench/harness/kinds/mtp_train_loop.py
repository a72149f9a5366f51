"""Traffic kind ``mtp_train_loop``: ``train_loop`` — the pretraining loop as a
user of ``ray_tpu.train`` writes it, with the benchmark's measurements around
it — for a model whose head scores more than one token ahead.

The loop is ``train_loop``'s, and everything of that module that names neither
``agreement`` nor ``flops`` is used as it is (``_Profiler``, ``_batches``,
``datasets``, ``verdict``, ``detail``).  What differs and is therefore written
out here (``run``, ``loop``, ``end_to_end``): the agreement is
``mtp_agreement.check`` (the first head's logits, the loss of all heads, the
gradient norm, against the family's own ``logits_loss_gradnorm``, which
``reference.logits_loss_gradnorm`` with its one next-token loss cannot stand
for); and the required FLOPs a token are the family's own count
(``families/<family>.py::train_flops_per_token``: the head's columns of every
prediction head, the live pairs of the family's mask).

The trainer is handed the batch ``train_loop`` hands over (``input_ids``,
``targets``: the row rolled left by one); the program's objective shifts the
targets further for the later heads.  Traffic file keys: ``train_loop``'s,
and ``hold``: the probability that a position of the stream repeats the id
before it (``held_tokens.HeldZipfStream``: Zipf(1.0) ids held for runs, so
that what lies ``r + 1`` ahead depends on the byte at hand, and differently
for every head).
"""

from __future__ import annotations

import dataclasses
import math
import tempfile
import time
from typing import Any, Dict, List

from perfbench.harness import (compile_watch, driver, families, manifest,
                               mtp_agreement)
from perfbench.harness.kinds.train_loop import (MAX_WARM_STEPS, _batches,
                                                _Profiler, _tenth_means,
                                                datasets, detail, verdict)
from perfbench.harness.held_tokens import HeldZipfStream
from perfbench.harness.spans import Spans

__all__ = ["run", "end_to_end", "verdict", "detail"]


def run(cell: manifest.Cell, **spec: Any) -> Dict[str, Any]:
    """Driver side, as ``train_loop.run``.  The program's configuration is
    built here first, without a device: a checkout whose program lacks a
    field the family fills is refused in seconds, before any process is
    started."""
    try:
        families.of(cell.config).model_config(cell.config, cell.chips)
    except TypeError as e:
        raise driver.Refused(
            f"the program in this checkout cannot build {cell.config['name']}"
            f": {e}") from e
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


def loop(run: Dict[str, Any]) -> None:
    """The train worker: ``train_loop.loop`` with this kind's agreement."""
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
    stream = HeldZipfStream(config["vocab_size"], run["seed"],
                            traffic["hold"])
    replicas = trainer.mesh.shape["dp"] * trainer.mesh.shape["fsdp"]
    agreed = mtp_agreement.check(trainer, config,
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
    and the device kind's row of ``peaks.json``: tokens a second, and the
    family's required FLOPs for each."""
    rate = m["tokens"] / m["elapsed_s"] / cell.chips
    required = families.of(cell.config).train_flops_per_token(
        cell.config, cell.chips, cell.traffic["seq"])
    return {"tokens_per_s_per_chip": rate,
            "mfu_pct": 100.0 * rate * required / peak["bf16_flops_per_s"],
            "setup_s": m["setup_s"]}

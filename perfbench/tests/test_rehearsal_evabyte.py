"""The EvaByte cell's whole path — ``ray_tpu.init()`` -> ``JaxTrainer`` -> one
train worker -> ``mtp_agreement.check``, warm-up, window, measurements —
rehearsed on the CPU at a toy size (``toy/toy-evabyte.json``: two layers, two
heads of 128, windows of 128 and chunks of 16, four prediction heads over 96
ids; ``toy/toy-mtp-gen.json``: two rows of 320 positions, two windows and half
of a third), and then *refused*: no line is made of a run that had no TPU.
And what the parent's program does with the new configuration: it is refused
by the kind before any process starts."""

import functools
import json
import os
import time

import pytest

from perfbench.harness import driver, manifest

TOY = os.path.join(os.path.dirname(os.path.abspath(__file__)), "toy")
CELL = "evabyte-eva-1chip"


def _toy_cell():
    bench = manifest.benchmark()
    load = lambda *p: json.load(open(os.path.join(*p)))  # noqa: E731
    return manifest.Cell(
        "toy", 1, load(TOY, "toy-evabyte.json"), load(TOY, "toy-mtp-gen.json"),
        bench["end_to_end"],
        [dict(m, file=load(manifest.BENCH_DIR, "layer_metrics",
                           m["name"] + ".json")) for m in bench["per_layer"]
         if CELL in m.get("workloads", [CELL])])


def test_cpu_rehearsal_runs_and_is_refused(tmp_path, monkeypatch):
    import ray_tpu.train
    from ray_tpu.train.jax_config import JaxConfig

    scaling = ray_tpu.train.ScalingConfig
    monkeypatch.setattr(
        ray_tpu.train, "ScalingConfig",
        lambda num_workers, tpus_per_worker: scaling(num_workers=num_workers))
    monkeypatch.setattr(ray_tpu.train, "JaxTrainer", functools.partial(
        ray_tpu.train.JaxTrainer,
        jax_config=JaxConfig(platform="cpu", cpu_devices_per_worker=1)))
    monkeypatch.setenv("RAY_TPU_TMPDIR", str(tmp_path / "ray_tpu"))
    cell = _toy_cell()
    m = driver.run_cell(cell, seed=2 ** 31 + 47, seconds=4.0, trace=False,
                        t_start=time.time())
    assert m["device"] == {"platform": "cpu", "kind": "cpu", "count": 1}
    assert m["steps"] > 0 and m["failed"] == 0
    assert m["tokens"] == m["steps"] * 2 * 320
    assert m["agreement"]["ok"], m["agreement"]
    assert m["agreement"]["prefix"] == 256
    assert not m["compiled_in_window"]
    assert m["loss_last_tenth"] < m["loss_first_tenth"]
    checks = driver.verdict(cell, m)
    assert not checks["device_is_the_cells"]
    assert all(v for k, v in checks.items() if k != "device_is_the_cells")
    with pytest.raises(driver.Refused):
        driver.result_line(cell, m, False)
    line = driver.result_line(cell, dict(m, device={
        "platform": "tpu", "kind": "TPU v5 lite", "count": 1}), False)
    assert set(line["metrics"]) == {e["name"] for e in cell.end_to_end}
    assert all(v["value"] > 0 for v in line["metrics"].values())
    assert line["correct"]
    json.dumps(line)


def test_a_program_without_the_fields_is_refused_before_any_process(
        monkeypatch):
    """On the parent's checkout ``LlamaConfig`` has none of this PR's fields:
    ``model_config`` raises ``TypeError``, and ``mtp_train_loop.run`` builds
    the configuration first, so the run is refused in the driver, with no
    runtime started."""
    import dataclasses

    import ray_tpu
    import ray_tpu.models.llama as llama
    from perfbench.harness.families import evabyte

    new = ("eva_window", "eva_chunk", "norm_unit_offset", "residual_dtype",
           "logits_dtype", "n_pred_heads")
    fields = [(f.name, f.type, f) for f in dataclasses.fields(
        llama.LlamaConfig) if f.name not in new]
    monkeypatch.setattr(llama, "LlamaConfig", dataclasses.make_dataclass(
        "LlamaConfig", fields, frozen=True))
    with pytest.raises(TypeError, match="|".join(new)):
        evabyte.model_config(_toy_cell().config, 1)

    def no_runtime(*a, **k):
        raise AssertionError("the runtime was started")

    monkeypatch.setattr(ray_tpu, "init", no_runtime)
    with pytest.raises(driver.Refused, match="cannot build toy-evabyte"):
        driver.run_cell(_toy_cell(), seed=1, seconds=1.0, trace=False,
                        t_start=time.time())

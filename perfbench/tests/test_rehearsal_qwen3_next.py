"""The Qwen3-Next cell's whole path — ``ray_tpu.init()`` -> ``JaxTrainer`` -> one
train worker -> ``agreement.check``, warm-up, window, measurements — rehearsed
on the CPU at a toy size (``toy/toy-qwen3-next.json``: three Gated DeltaNet
layers at chunks of 8, two value heads a key head, and one layer of gated
attention with a quarter of each head turned, 8 of 32 experts held beside
the gated shared one, a quarter of the vocabulary), and then *refused*: no
line is
made of a run that had no TPU.  And what the parent's program does with the
new configuration: it fails at once."""

import functools
import json
import os
import time

import pytest

from perfbench.harness import driver, manifest

TOY = os.path.join(os.path.dirname(os.path.abspath(__file__)), "toy")
CELL = "qwen3-next-s16k-1chip"


def _toy_cell():
    bench = manifest.benchmark()
    load = lambda *p: json.load(open(os.path.join(*p)))  # noqa: E731
    # (a dozen steps of the interpreted scan fit the window, all inside the
    # schedule's warm-up, where the loss moves by less than a batch differs
    # from the next: that it falls is tests/test_qwen3_next.py (c)'s)
    return manifest.Cell(
        "toy", 1, load(TOY, "toy-qwen3-next.json"),
        dict(load(TOY, "toy-gen.json"), loss_fall_min=-1.0),
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
    m = driver.run_cell(cell, seed=2 ** 31 + 11, seconds=4.0, trace=False,
                        t_start=time.time())
    assert m["device"] == {"platform": "cpu", "kind": "cpu", "count": 1}
    assert m["steps"] > 0 and m["failed"] == 0
    assert m["tokens"] == m["steps"] * 4 * 64
    assert m["agreement"]["ok"], m["agreement"]
    assert m["agreement"]["prefix"] == 32
    assert not m["compiled_in_window"]
    assert abs(m["loss_last_tenth"] - m["loss_first_tenth"]) < 1.0
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


def test_a_program_without_the_fields_cannot_build_the_configuration(
        monkeypatch):
    """On the parent's checkout ``LlamaConfig`` has neither of this PR's
    fields: ``model_config`` raises ``TypeError`` in the worker's first
    lines, before any device work."""
    import dataclasses

    import ray_tpu.models.llama as llama
    from perfbench.harness.families import qwen3_next

    fields = [(f.name, f.type, f) for f in dataclasses.fields(
        llama.LlamaConfig)
        if f.name not in ("gdn_key_heads", "shared_expert_gate")]
    monkeypatch.setattr(llama, "LlamaConfig", dataclasses.make_dataclass(
        "LlamaConfig", fields, frozen=True))
    with pytest.raises(TypeError, match="gdn_key_heads|shared_expert_gate"):
        qwen3_next.model_config(_toy_cell().config, 1)

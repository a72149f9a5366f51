"""The block-diffusion cell's whole path — ``ray_tpu.init()`` ->
``JaxTrainer`` -> one train worker -> ``bd_agreement.check``, warm-up, window,
measurements — rehearsed on the CPU at a toy size (``toy/toy-sdar.json``: 4
of 8 experts held, a quarter of the vocabulary, blocks of 4), and then
*refused*: no line is made of a run that had no TPU."""

import functools
import json
import os
import time

import pytest

from perfbench.harness import driver, manifest

TOY = os.path.join(os.path.dirname(os.path.abspath(__file__)), "toy")


def _toy_cell():
    bench = manifest.benchmark()
    load = lambda *p: json.load(open(os.path.join(*p)))  # noqa: E731
    return manifest.Cell(
        "toy", 1, load(TOY, "toy-sdar.json"), load(TOY, "toy-bd.json"),
        bench["end_to_end"],
        [dict(m, file=load(manifest.BENCH_DIR, "layer_metrics",
                           m["name"] + ".json")) for m in bench["per_layer"]
         if "sdar-bd-s4k-1chip" in m.get("workloads", ["sdar-bd-s4k-1chip"])])


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
    m = driver.run_cell(cell, seed=2 ** 31 + 5, seconds=4.0, trace=False,
                        t_start=time.time())
    assert m["device"] == {"platform": "cpu", "kind": "cpu", "count": 1}
    assert m["steps"] > 0 and m["failed"] == 0
    # a token is a data token: rows x seq a step, not the two copies
    assert m["tokens"] == m["steps"] * 4 * 64
    a = m["agreement"]
    assert a["ok"], a
    # counted on both sides; equal in float32 (tests/test_sdar.py), and in
    # bf16 up to the tokens whose k-th and (k+1)-th experts swap
    assert a["moe_rows_held"] == pytest.approx(a["moe_rows_held_reference"],
                                               rel=0.1)
    assert a["moe_rows_held_reference"] > 0
    assert 0.0 < a["masked_share"] < 1.0
    # every position of both copies takes 2 of 8 experts, 4 of them held
    assert 0 < m["moe_rows_held"] <= 4 * 2 * 64 * 2
    assert not m["compiled_in_window"]
    # (a toy step has 64 blocks, each weighted 1/t: its loss is too noisy to
    # fall inside two seconds; tests/test_sdar.py trains at a fixed noise)
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


def test_a_program_without_the_objective_is_refused_at_once(monkeypatch):
    """On a checkout whose ``LlamaConfig`` has no block-diffusion fields the
    kind refuses before it starts a process."""
    from perfbench.harness.families import sdar_moe
    from perfbench.harness.kinds import bd_train_loop

    def old_program(config, chips):
        raise TypeError("LlamaConfig.__init__() got an unexpected keyword "
                        "argument 'head_dim'")
    monkeypatch.setattr(sdar_moe, "model_config", old_program)
    t0 = time.time()
    with pytest.raises(driver.Refused, match="cannot build"):
        bd_train_loop.run(_toy_cell(), seed=1, seconds=1.0, trace=False,
                          t_start=t0, trace_dir="")
    assert time.time() - t0 < 5.0

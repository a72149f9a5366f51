"""Without a chip the benchmark prints no result.

The whole path — ``ray_tpu.init()`` -> ``JaxTrainer`` -> one train worker ->
agreement check, warm-up, window, measurements — is rehearsed on the CPU at a
toy size, and must then *refuse* to make a line of it; ``run.py`` itself, on a
machine that exposes no TPU chip, must exit non-zero with an empty stdout.
"""

import dataclasses
import functools
import json
import os
import subprocess
import sys
import time

import pytest

from perfbench.harness import driver, manifest

TOY = os.path.join(os.path.dirname(os.path.abspath(__file__)), "toy")


def _toy_cell(config, traffic, chips):
    bench = manifest.benchmark()
    load = lambda *p: json.load(open(os.path.join(*p)))  # noqa: E731
    return manifest.Cell(
        "toy", chips, load(TOY, config + ".json"),
        load(TOY, traffic + ".json"), bench["end_to_end"],
        [dict(m, file=load(manifest.BENCH_DIR, "layer_metrics",
                           m["name"] + ".json")) for m in bench["per_layer"]])


def test_run_py_prints_nothing_without_a_chip():
    from ray_tpu.accelerators import tpu_manager

    if tpu_manager().get_current_node_num_accelerators():
        pytest.skip("this host has a TPU")
    cell = manifest.benchmark()["workloads"][0]["name"]
    done = subprocess.run(
        [sys.executable, "perfbench/run.py", "--workload", cell, "--seed",
         "0", "--seconds", "1", "--trace", "0"],
        cwd=manifest.ROOT, capture_output=True, text=True, timeout=120)
    assert done.returncode != 0
    assert done.stdout == ""
    assert "nothing was run" in done.stderr


@pytest.mark.parametrize("config,traffic,chips,trace", [
    ("toy-gpt2", "toy-data", 1, False),
    ("toy-llama", "toy-fsdp4", 4, True),
])
def test_cpu_rehearsal_runs_and_is_refused(config, traffic, chips, trace,
                                           tmp_path, monkeypatch):
    import ray_tpu.train
    from ray_tpu.train.jax_config import JaxConfig

    # the driver asks for TPU chips and nothing else; only here is it handed
    # a trainer that puts its worker on virtual CPU devices instead
    scaling = ray_tpu.train.ScalingConfig
    monkeypatch.setattr(
        ray_tpu.train, "ScalingConfig",
        lambda num_workers, tpus_per_worker: scaling(num_workers=num_workers))
    monkeypatch.setattr(ray_tpu.train, "JaxTrainer", functools.partial(
        ray_tpu.train.JaxTrainer,
        jax_config=JaxConfig(platform="cpu", cpu_devices_per_worker=chips)))
    monkeypatch.setenv("RAY_TPU_TMPDIR", str(tmp_path / "ray_tpu"))
    cell = _toy_cell(config, traffic, chips)
    if trace:   # its trace directory is its name's: not another rehearsal's
        cell = dataclasses.replace(cell, name="toy-traced")
    m = driver.run_cell(cell, seed=3, seconds=4.0, trace=trace,
                        t_start=time.time())
    assert m["device"] == {"platform": "cpu", "kind": "cpu", "count": chips}
    assert m["steps"] > 0 and m["failed"] == 0 and m["tokens"] > 0
    assert m["agreement"]["ok"], m["agreement"]
    assert m["agreement"]["rows"] == chips
    assert not m["compiled_in_window"]
    assert m["loss_last_tenth"] < m["loss_first_tenth"]
    assert any("pretrain_step" in (name or "")
               for _, name, _ in m["build_events"])
    assert set(m["spans_ms"]) == {"input", "step", "sync", "report"}
    checks = driver.verdict(cell, m)
    assert not checks["device_is_the_cells"]
    assert all(v for k, v in checks.items() if k != "device_is_the_cells")
    if trace:   # the host spans came back on the trace's clock
        with open(m["trace"]["file"]) as f:
            reduced = json.load(f)
        assert {"window", "step", "report"} <= {s[0] for s in reduced["spans"]}
    with pytest.raises(driver.Refused):
        driver.result_line(cell, m, trace)
    if not trace:
        # the line's shape alone, from the same measurements under a TPU's
        # name: made in this test and printed nowhere
        line = driver.result_line(cell, dict(m, device={
            "platform": "tpu", "kind": "TPU v5 lite", "count": chips}), trace)
        assert set(line["metrics"]) == {e["name"] for e in cell.end_to_end}
        assert all(v["value"] > 0 for v in line["metrics"].values())
        assert line["correct"] and line["attempted"] == m["steps"]
        assert {"failed", "device", "checks", "agreement"} <= set(line)
        json.dumps(line)

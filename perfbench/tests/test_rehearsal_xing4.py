"""The Xing4.0 cell's whole path — ``ray_tpu.init()`` -> ``JaxTrainer`` -> one
train worker -> ``agreement.check``, warm-up, window, measurements — rehearsed
on the CPU at a toy size (``toy/toy-xing4.json``: three layers over a
residual path four streams wide, latent attention with a query latent under
YaRN, 2 of 16 experts held beside a shared one, a quarter of the vocabulary,
the prediction module off as in the cell), and then *refused*: no line is made
of a run that had no TPU.  And what the parent's program does with the new
configuration: it fails at once."""

import dataclasses
import functools
import json
import os
import time

import pytest

from perfbench.harness import driver, manifest

TOY = os.path.join(os.path.dirname(os.path.abspath(__file__)), "toy")
CELL = "xing4-s8k-1chip"


def _toy_cell():
    bench = manifest.benchmark()
    load = lambda *p: json.load(open(os.path.join(*p)))  # noqa: E731
    return manifest.Cell(
        "toy", 1, load(TOY, "toy-xing4.json"), load(TOY, "toy-gen.json"),
        bench["end_to_end"],
        [dict(m, file=load(manifest.BENCH_DIR, "layer_metrics",
                           m["name"] + ".json")) for m in bench["per_layer"]
         if CELL in m.get("workloads", [CELL])])


@pytest.fixture
def on_the_cpu(tmp_path, monkeypatch):
    """The driver asks for TPU chips and nothing else; only here is it handed
    a trainer that puts its worker on a virtual CPU device instead."""
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


def test_cpu_rehearsal_runs_and_is_refused(on_the_cpu):
    cell = _toy_cell()
    m = driver.run_cell(cell, seed=2 ** 31 + 65, seconds=4.0, trace=False,
                        t_start=time.time())
    assert m["device"] == {"platform": "cpu", "kind": "cpu", "count": 1}
    assert m["steps"] > 0 and m["failed"] == 0
    assert m["tokens"] == m["steps"] * 4 * 64
    assert m["agreement"]["ok"], m["agreement"]
    assert m["agreement"]["prefix"] == 32
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


def test_a_traced_rehearsal_brings_back_the_programs_counters(on_the_cpu):
    """What only a traced run reads (PR 67): whatever
    ``ShardedPretrainer.moe_stats`` held at the window's reports, averaged,
    beside the reduced trace — reader ``measured`` finds ``moe_rows_held``
    and ``hc_res_row_err`` there — and the profiler's two calls stamped on
    the flight recorder's clock, inside the window."""
    from perfbench.harness.readers import measured, rounds

    t0 = time.time()
    # a name, and so a trace directory, of its own (driver.run_cell)
    m = driver.run_cell(dataclasses.replace(_toy_cell(), name="toy-xing4"),
                        seed=67, seconds=4.0, trace=True, t_start=t0)
    assert m["failed"] == 0 and m["trace"] is not None
    counters = m["trace"]["counters"]
    assert {"moe_rows_held", "hc_res_row_err", "max_load"} <= set(counters)
    assert 0 <= counters["hc_res_row_err"] < 0.1
    assert counters["moe_rows_held"] > 0
    ctx = type("Ctx", (), {"measured": m})
    assert measured.read(ctx, key="hc_res_row_err") \
        == counters["hc_res_row_err"]
    assert measured.read(ctx, key="moe_rows_held") == counters["moe_rows_held"]
    assert measured.read(ctx, key="no_such_counter") is None
    (a, b), (c, d) = rounds.profiler_calls(ctx)
    assert t0 < a <= b <= c <= d < time.time()
    # the untraced run reads none of it
    assert "moe_rows_held" not in m and "counters" not in m


def test_the_parents_program_cannot_build_the_configuration(monkeypatch):
    """On the parent's checkout, under this PR's benchmark files,
    ``families/xing4.py::model_config`` fills fields ``LlamaConfig`` does not
    have (``q_lora_rank``, ``hc_mult``, ...): a ``TypeError`` in the worker's
    first lines, before any device work — and before it gets that far its
    import of ``models/pretrain.py::MTP_WEIGHT``, which the parent lacks
    too, is an ``ImportError`` from the same call.  (The parent's dataclass,
    in small: this one without the six new fields.)"""
    import ray_tpu.models.llama as llama
    from perfbench.harness.families import xing4

    new = {"hc_mult", "hc_sinkhorn_iters", "hc_eps", "hc_res_clamp",
           "q_lora_rank", "n_mtp_modules"}
    fields = {f.name for f in dataclasses.fields(llama.LlamaConfig)}
    assert new <= fields and len(fields) == 79

    def parents(**kwargs):
        unknown = sorted(set(kwargs) & new)
        if unknown:
            raise TypeError("LlamaConfig.__init__() got an unexpected "
                            f"keyword argument {unknown[0]!r}")
        return llama.LlamaConfig(**kwargs)

    monkeypatch.setattr(llama, "LlamaConfig", parents)
    with pytest.raises(TypeError, match="unexpected keyword argument"):
        xing4.model_config(_toy_cell().config, 1)

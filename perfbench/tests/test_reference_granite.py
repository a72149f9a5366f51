"""The ``granite_hybrid`` family (PR 29): its plain reference against the
program at toy widths in float32, its five ``mamba_*`` / ``ssd_*`` metrics
against name paths as JAX prints them, the new reader's arithmetic, and its
cell rehearsed on the CPU.  A file of its own: a PR that adds a configuration
edits no file the benchmark has.  (The FLOP counts are in
``test_flops_granite.py``; the scan, the convolution and the sharded meshes
in the program's ``tests/test_mamba.py``.)  Named to sort behind
``test_program_span.py``, which asserts that the test process has not
initialised a JAX backend yet."""

import dataclasses
import functools
import json
import os
import time

import numpy as np
import pytest

from perfbench.harness import driver, flops, manifest, ssd_work
from perfbench.harness.readers import scope_roofline, trace_ops
from perfbench.harness.readers.context import Context
from perfbench.harness.trace_reduce import Op, Trace

HERE = os.path.dirname(os.path.abspath(__file__))
CELL = "granite-h-s8k-1chip"
NEW = ("mamba_scope_share_pct", "ssd_scan_ms_per_step",
       "mamba_conv_ms_per_step", "mamba_proj_ms_per_step",
       "ssd_scan_roofline")


def _load(*parts):
    with open(os.path.join(*parts)) as f:
        return json.load(f)


TOY = _load(HERE, "toy", "toy-granite.json")


@pytest.fixture
def interpreted(monkeypatch):
    """The flash kernel on the CPU runs interpreted, because this asks."""
    monkeypatch.setenv("RAY_TPU_PALLAS_INTERPRET", "1")


def _program_and_reference(config, **wrong_keywords):
    """Program in float32 with XLA attention against
    ``reference.logits_loss_gradnorm`` under ``config``; weights moved off
    their initial values (a ``D`` or a norm scale of 1 would hide a dropped
    one).  40 positions: two chunks of 16 and a padded one."""
    import jax
    import jax.numpy as jnp

    from perfbench.harness import families, reference
    from perfbench.harness.families import granite_hybrid
    from perfbench.harness.tokens import ZipfStream
    from ray_tpu.models.pretrain import init_params, loss_fn

    cfg = dataclasses.replace(
        families.of(TOY).model_config(TOY, 1), dtype=jnp.float32,
        attention_impl="reference")
    model, params = init_params(cfg)
    keys = iter(jax.random.split(jax.random.PRNGKey(1), 1000))
    params = jax.tree_util.tree_map(
        lambda a: a + 0.1 * jax.random.normal(next(keys), a.shape, a.dtype),
        params)
    batch = {k: jnp.asarray(v) for k, v in
             ZipfStream(TOY["vocab_size"], seed=5).rows(2, 40).items()}
    with jax.default_matmul_precision("highest"):
        logits = model.apply({"params": params}, batch["input_ids"])
        loss, grads = jax.value_and_grad(
            lambda p: loss_fn(model, p, batch))(params)
    forward = granite_hybrid.logits
    granite_hybrid.logits = lambda p, i, c: forward(p, i, c, **wrong_keywords)
    try:
        want = reference.logits_loss_gradnorm(
            params, batch["input_ids"], batch["targets"], config)
    finally:
        granite_hybrid.logits = forward
    return (logits[..., :TOY["vocab_size"]], loss,
            reference.global_norm(grads)), want


def test_reference_equals_program_in_float32():
    """Float32 against float32 at matmul precision 'highest': summation
    order alone differs (2e-4 on logits of size 1, as the other families)."""
    got, want = _program_and_reference(TOY)
    np.testing.assert_allclose(got[0], want[0], rtol=2e-4, atol=2e-4)
    assert float(got[1]) == pytest.approx(float(want[1]), rel=1e-5)
    assert float(got[2]) == pytest.approx(float(want[2]), rel=1e-4)


@pytest.mark.parametrize("wrong", [{"attention_multiplier": 0.25},
                                   {"residual_multiplier": 1.0},
                                   {"position_embedding_type": "rope"}])
def test_a_wrong_configuration_is_far_outside_the_tolerance(wrong):
    got, want = _program_and_reference(dict(TOY, **wrong))
    assert float(np.max(np.abs(got[0] - want[0]))) > 20 * 2e-4


def test_the_gate_after_the_norm_is_far_outside_the_tolerance():
    got, want = _program_and_reference(TOY, gate_after_norm=True)
    assert float(np.max(np.abs(got[0] - want[0]))) > 20 * 2e-4


def test_the_reference_follows_the_published_layer_list():
    """The kinds come from ``layer_types``, not from the program's tree: a
    program that put a mixer where the list says ``attention`` has no
    ``attn`` parameters for the reference to read."""
    with pytest.raises(KeyError, match="attn"):
        _program_and_reference(dict(
            TOY, layer_types=["attention", "attention", "mamba"]))


def _args(metric):
    return _load(manifest.BENCH_DIR, "layer_metrics", metric + ".json")["args"]


FWD = "jit(pretrain_step)/jvp(LlamaLMModel)/checkpoint/h_0/"
BWD = ("jit(pretrain_step)/transpose(jvp(LlamaLMModel))/"
       "jvp(LlamaLMModel)/checkpoint/")
ROWS = [
    ("in", "fusion", FWD + "mamba/in_proj/dot_general"),
    ("in_bwd", "fusion", BWD + "h_3/mamba/in_proj/transpose"),
    ("out_again", "fusion",
     BWD + "rematted_computation/h_3/mamba/out_proj/dot_general"),
    ("taps", "fusion", FWD + "mamba/conv/mul"),
    ("taps_bwd", "fusion", BWD + "h_1/mamba/conv/pad"),
    ("masks", "fusion", FWD + "mamba/ssd/exp"),
    ("chunks", "while", BWD + "rematted_computation/h_2/mamba/ssd/while"),
    ("product_bwd", "convolution", BWD + "h_2/mamba/ssd/bcgrij,bcjgrp->bcigrp"
     "/transpose"),
    ("gate", "fusion", FWD + "mamba/gated_norm/mul"),
    ("flash", "custom-call:tpu_custom_call",
     "jit(pretrain_step)/jvp(LlamaLMModel)/checkpoint/h_5/attn/flash_fwd/"
     "pallas_call"),
    ("wq", "fusion",
     "jit(pretrain_step)/jvp(LlamaLMModel)/checkpoint/h_5/attn/wq/"
     "dot_general"),
    ("gate_proj", "fusion", FWD + "mlp/gate_proj/dot_general"),
    ("head", "fusion", "jit(pretrain_step)/jvp(LlamaLMModel)/lm_head/"
     "bsd,vd->bsv/dot_general"),
    ("adam", "fusion", "jit(pretrain_step)/optimizer/mul"),
]


def _ops():
    return [Op(name, kind, path, float(i), float(i + 1))
            for i, (name, kind, path) in enumerate(ROWS)]


def test_the_new_metrics_select_their_scopes():
    """Name paths as the compiled step prints them (remat, so forward work is
    under ``rematted_computation`` too), one operation each, 1 s long."""
    ops = _ops()

    def names(metric):
        args = {k: v for k, v in _args(metric).items()
                if k in ("path", "not_path", "op")}
        return [o.name for o, _ in trace_ops.selected(ops, **args)]

    assert names("mamba_scope_share_pct") == [r[0] for r in ROWS[:9]]
    assert names("mamba_proj_ms_per_step") == ["in", "in_bwd", "out_again"]
    assert names("mamba_conv_ms_per_step") == ["taps", "taps_bwd"]
    scan = ["masks", "chunks", "product_bwd"]
    assert names("ssd_scan_ms_per_step") == scan
    assert names("ssd_scan_roofline") == scan
    # the attention's metrics do not take the mixer in, the head's metric
    # reads the tied head under the name an untied one has, and nothing of
    # the mixer is unscoped
    assert names("attn_scope_share_pct") == ["flash"]
    assert names("flash_fwd_calls_per_step") == ["flash"]
    assert names("head_and_loss_ms_per_step") == ["head"]
    assert names("unscoped_device_share_pct") == []


def _context(ops, steps):
    cell = manifest.cell(CELL)
    return cell, Context(cell, manifest.peaks()["TPU v5 lite"], {},
                         Trace({0: ops}, [("window", 0.0, float(len(ops)))]),
                         steps)


def test_scope_roofline_is_least_time_over_the_scopes_time_a_step():
    """Three operations of 1 s under ``mamba/ssd`` in a window of two steps:
    1.5 s a step, against the least time of one step's scans."""
    cell, ctx = _context(_ops(), steps=2)
    least, bound = flops.roofline_seconds(
        ssd_work.scan_step(cell.config, 1, 1, cell.traffic["seq"]), ctx.peak)
    assert bound == "memory"
    got = scope_roofline.read(ctx, **_args("ssd_scan_roofline"))
    assert got == pytest.approx(100.0 * least / 1.5)
    assert trace_ops.read(ctx, **_args("ssd_scan_ms_per_step")) \
        == pytest.approx(1500.0)


def test_a_program_without_the_mixer_reports_none_of_them():
    """The parent commit's trace has no ``mamba`` scope: every new reader
    returns nothing and raises nothing, and the line leaves the metric out."""
    ops = [Op("wq", "fusion", "jit(pretrain_step)/jvp(M)/h_0/attn/wq/dot",
              0.0, 1.0)]
    cell, ctx = _context(ops, steps=1)
    by_name = {m["name"]: m for m in cell.per_layer}
    assert set(NEW) <= set(by_name)
    for name in NEW:
        file = by_name[name]["file"]
        reader = scope_roofline if file["reader"] == "scope_roofline" \
            else trace_ops
        assert reader.read(ctx, **file["args"]) is None, name


def test_the_cell_reports_the_old_metrics_and_the_new():
    cell = manifest.cell(CELL)
    assert cell.chips == 1 and cell.traffic["seq"] == 8192
    assert cell.config["name"] == "granite-4.0-h-micro"
    names = [m["name"] for m in cell.per_layer]
    assert set(NEW) <= set(names)
    for other in ("gpt2s-b24-s1k", "mistral-s8k-1chip", "olmoe-s4k-1chip"):
        assert not set(NEW) & {m["name"]
                               for m in manifest.cell(other).per_layer}
    assert "collective_ms_per_step" not in names
    assert "moe_scope_share_pct" not in names


def test_cpu_rehearsal_of_the_cell_runs_and_is_refused(tmp_path, monkeypatch,
                                                       interpreted):
    """``test_rehearsal.py``'s rehearsal with the hybrid configuration: the
    whole path through ``JaxTrainer`` at toy widths, bf16 activations, the
    agreement check on a prefix of two chunks."""
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
    bench = manifest.benchmark()
    cell = manifest.Cell(
        "toy", 1, TOY, _load(HERE, "toy", "toy-gen.json"), bench["end_to_end"],
        [dict(m, file=_load(manifest.BENCH_DIR, "layer_metrics",
                            m["name"] + ".json")) for m in bench["per_layer"]])
    # 4 s: with the kernels interpreted (conftest.py, PR 67) a loaded machine
    # makes too few steps in 2 s for this toy's loss to fall
    m = driver.run_cell(cell, seed=2 ** 31 + 7, seconds=4.0, trace=False,
                        t_start=time.time())
    assert m["steps"] > 0 and m["failed"] == 0 and m["tokens"] > 0
    assert m["agreement"]["ok"], m["agreement"]
    assert m["agreement"]["prefix"] == 2 * TOY["mamba_chunk_size"]
    assert not m["compiled_in_window"]
    assert m["loss_last_tenth"] < m["loss_first_tenth"]
    with pytest.raises(driver.Refused):
        driver.result_line(cell, m, False)

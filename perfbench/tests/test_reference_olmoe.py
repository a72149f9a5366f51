"""The ``olmoe`` family (PR 25): its plain reference against the program at
toy widths in float32, its FLOP and byte counts against counts made by hand,
its six ``moe_*`` metrics against name paths as JAX prints them, and its cell
rehearsed on the CPU.  A file of its own: a PR that adds a configuration edits
no file the benchmark has.  Named to sort behind ``test_program_span.py``,
which asserts that the test process has not initialised a JAX backend yet;
the float32 comparisons here do, as ``test_reference.py``'s do."""

import dataclasses
import functools
import json
import os
import time

import numpy as np
import pytest

from perfbench.harness import driver, flops, manifest, moe_work
from perfbench.harness.readers import trace_ops
from perfbench.harness.trace_reduce import Op

HERE = os.path.dirname(os.path.abspath(__file__))


def _load(*parts):
    with open(os.path.join(*parts)) as f:
        return json.load(f)


TOY = _load(HERE, "toy", "toy-olmoe.json")
OLMOE = _load(manifest.BENCH_DIR, "configs", "olmoe-1b-7b-0125.json")


@pytest.fixture
def interpreted(monkeypatch):
    """The routed experts' grouped matmul is a Pallas kernel everywhere: on
    the CPU it runs interpreted, because this asks for it."""
    monkeypatch.setenv("RAY_TPU_PALLAS_INTERPRET", "1")


def _program_and_reference(config):
    """Program in float32 with XLA attention against
    ``reference.logits_loss_gradnorm`` under ``config``; weights moved off
    their initial values (norm scales of 1 would hide a dropped q/k norm)."""
    import jax
    import jax.numpy as jnp

    from perfbench.harness import families, reference
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
             ZipfStream(TOY["vocab_size"], seed=5).rows(2, 48).items()}
    with jax.default_matmul_precision("highest"):
        logits = model.apply({"params": params}, batch["input_ids"])
        loss, grads = jax.value_and_grad(
            lambda p: loss_fn(model, p, batch))(params)
    want = reference.logits_loss_gradnorm(
        params, batch["input_ids"], batch["targets"], config)
    return (logits[..., :TOY["vocab_size"]], loss,
            reference.global_norm(grads)), want


def test_reference_equals_program_in_float32(interpreted):
    got, want = _program_and_reference(TOY)
    np.testing.assert_allclose(got[0], want[0], rtol=2e-4, atol=2e-4)
    assert float(got[1]) == pytest.approx(float(want[1]), rel=1e-5)
    assert float(got[2]) == pytest.approx(float(want[2]), rel=1e-4)


@pytest.mark.parametrize("wrong", [{"norm_topk_prob": True},
                                   {"num_experts_per_tok": 1}])
def test_a_wrong_model_is_far_outside_the_tolerance(wrong, interpreted):
    got, want = _program_and_reference(dict(TOY, **wrong))
    assert float(np.max(np.abs(got[0] - want[0]))) > 100 * 2e-4


def test_olmoe_one_layer_is_1072_mflop_a_token():
    # wq, wk, wv, wo 2048 x 2048; the router 2048 x 64; gate, up, down of
    # the 8 experts a token takes, 2048 x 1024 each
    layer = 4 * 2048 ** 2 + 2048 * 64 + 8 * 3 * 2048 * 1024
    assert layer == 67_239_936
    assert flops.shape(OLMOE, 1)["layer_mm_params"] == layer
    assert flops.shape(OLMOE, 1)["n_layer"] == 1
    n_mm = layer + 2048 * 50304
    assert flops.matmul_params(OLMOE, 1) == n_mm
    want = 6 * n_mm + 6 * 1 * 4096 * 2048
    assert flops.train_flops_per_token(OLMOE, 1, 4096) == want
    assert want == pytest.approx(1071.9e6, rel=1e-4)
    # lm_head is 58% of the required matmul FLOPs at this depth
    assert 6 * 2048 * 50304 / want == pytest.approx(0.577, abs=2e-3)


def test_grouped_matmul_call_and_roofline():
    call = moe_work.grouped_matmul_call(OLMOE, 1, rows=2, seq=4096)
    n = 2 * 4096 * 8      # (token, expert) rows
    assert call["flops"] == 2 * n * 2048 * 1024
    # rows in at one width, 64 matrices, result out at the other width, bf16
    assert call["bytes"] == 2 * (n * 2048 + 64 * 2048 * 1024 + n * 1024)
    least, bound = flops.roofline_seconds(
        call, manifest.peaks()["TPU v5 lite"])
    assert bound == "compute"
    assert least == pytest.approx(1.395e-3, rel=1e-3)


def _args(metric):
    return _load(manifest.BENCH_DIR, "layer_metrics", metric + ".json")["args"]


def test_moe_metrics_select_their_scopes():
    """Name paths as the compiled step prints them (remat, so forward work is
    under ``rematted_computation`` too), one operation each, 1 s long."""
    fwd = "jit(pretrain_step)/jvp(LlamaLMModel)/checkpoint/h_0/"
    bwd = ("jit(pretrain_step)/transpose(jvp(LlamaLMModel))/"
           "jvp(LlamaLMModel)/checkpoint/")
    mosaic, fusion = "custom-call:tpu_custom_call", "fusion"
    rows = [
        ("router", fusion, fwd + "moe/router/router/dot_general"),
        ("topk", fusion, bwd + "rematted_computation/h_0/moe/router/top_k"),
        ("sort", "sort", fwd + "moe/dispatch/sort"),
        ("gather", fusion, bwd + "h_0/moe/dispatch/gather"),
        ("gate", mosaic, fwd + "moe/experts/jit(gmm)/pallas_call"),
        ("gate_again", mosaic,
         bwd + "rematted_computation/h_0/moe/experts/jit(gmm)/pallas_call"),
        ("d_rows", mosaic, bwd + "h_0/moe/experts/jit(gmm)/pallas_call"),
        ("d_weights", mosaic, bwd + "h_0/moe/experts/jit(tgmm)/pallas_call"),
        ("silu", fusion, fwd + "moe/experts/mul"),
        ("sum", fusion, fwd + "moe/combine/reduce_sum"),
        ("flash", mosaic, fwd + "attn/flash_fwd/pallas_call"),
        ("wq", fusion, fwd + "attn/wq/dot_general"),
        ("adam", fusion, "jit(pretrain_step)/optimizer/mul"),
    ]
    ops = [Op(name, kind, path, float(i), float(i + 1))
           for i, (name, kind, path) in enumerate(rows)]

    def names(metric):
        args = {k: v for k, v in _args(metric).items()
                if k in ("path", "not_path", "op")}
        return [o.name for o, _ in trace_ops.selected(ops, **args)]

    assert names("moe_scope_share_pct") == [r[0] for r in rows[:10]]
    assert names("moe_router_ms_per_step") == ["router", "topk"]
    assert names("moe_dispatch_ms_per_step") == ["sort", "gather", "sum"]
    assert names("moe_experts_ms_per_step") == [
        "gate", "gate_again", "d_rows", "d_weights", "silu"]
    calls = ["gate", "gate_again", "d_rows", "d_weights"]
    assert names("moe_experts_calls_per_step") == calls
    assert names("moe_experts_roofline") == calls
    # the attention's metrics do not take the grouped matmul in, nor the
    # routed layer the attention's kernel
    assert names("flash_fwd_calls_per_step") == ["flash"]
    assert names("attn_scope_share_pct") == ["flash"]
    assert names("unscoped_device_share_pct") == []


def test_a_program_without_the_routed_layer_reports_none_of_them():
    """The parent commit's trace has no ``moe`` scope: every reader returns
    nothing and raises nothing, and the line leaves the metric out."""
    from perfbench.harness.readers import kernel_roofline
    from perfbench.harness.readers.context import Context
    from perfbench.harness.trace_reduce import Trace

    ops = [Op("wq", "fusion", "jit(pretrain_step)/jvp(M)/h_0/attn/wq/dot",
              0.0, 1.0)]
    cell = manifest.cell("olmoe-s4k-1chip")
    ctx = Context(cell, manifest.peaks()["TPU v5 lite"], {},
                  Trace({0: ops}, [("window", 0.0, 1.0)]), 1)
    for m in cell.per_layer:
        if not m["name"].startswith("moe_"):
            continue
        reader = kernel_roofline if m["file"]["reader"] == "kernel_roofline" \
            else trace_ops
        assert reader.read(ctx, **m["file"]["args"]) is None, m["name"]


def test_cpu_rehearsal_of_the_cell_runs_and_is_refused(tmp_path, monkeypatch):
    """``test_rehearsal.py``'s rehearsal with the routed configuration: the
    whole path through ``JaxTrainer`` at toy widths, kernels interpreted."""
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
    m = driver.run_cell(cell, seed=2 ** 31 + 7, seconds=4.0, trace=False,
                        t_start=time.time())
    assert m["steps"] > 0 and m["failed"] == 0 and m["tokens"] > 0
    assert m["agreement"]["ok"], m["agreement"]
    assert not m["compiled_in_window"]
    assert m["loss_last_tenth"] < m["loss_first_tenth"]
    with pytest.raises(driver.Refused):
        driver.result_line(cell, m, False)

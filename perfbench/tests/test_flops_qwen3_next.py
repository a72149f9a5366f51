"""``families/qwen3_next.py::shape`` (what ``flops.train_flops_per_token``
counts ``qwen3-next-s16k-1chip`` from), ``scan_flops_per_token`` and
``gdn_work.py`` against sums written out by hand from the published sizes,
the equations of the two mixers and the cut, a brute-force count of the
chunked form's matmuls, and the parameter tree's own matmul leaves; the eight
new metrics on a synthetic trace whose name paths are as the chip's trace
prints them; and the configuration file against the catalog row."""

import json
import os

import pytest

from perfbench.harness import flash_work, flops, gdn_work, manifest
from perfbench.harness.families import qwen3_next
from perfbench.harness.readers import kernel_roofline, scope_roofline, \
    trace_ops
from perfbench.harness.readers.context import Context
from perfbench.harness.trace_reduce import Op, Trace

NAME = "qwen3-next-s16k-1chip"
CELL = manifest.cell(NAME)
CONFIG = CELL.config
PEAK = manifest.peaks()["TPU v5 lite"]
SEQ = 16384
NEW = ["gdn_scope_share_pct", "gdn_scan_ms_per_step", "gdn_scan_roofline",
       "gdn_proj_ms_per_step", "gdn_conv_gate_ms_per_step",
       "gdn_solve_calls_per_step", "gated_attn_fwd_roofline",
       "gated_attn_bwd_roofline"]


def _matmuls_of_a_chunk(c, d, r):
    """(m, k, n) of every dense matmul of one KEY head's chunk of ``c``
    positions in the chunked form: ``K K^T`` and ``Q K^T`` once, and for each
    of its ``r`` value heads the solve as one ``c x c`` by ``c x d`` product,
    ``A U``, and the three that touch the state."""
    return [(c, d, c), (c, d, c)] + r * [
        (c, c, d), (c, c, d), (c, d, d), (c, d, d), (d, c, d)]


@pytest.mark.parametrize("c,d,keys,r", [(64, 128, 16, 2), (8, 16, 2, 2),
                                        (16, 32, 2, 3)])
def test_the_scans_count_is_the_chunked_forms_matmuls(c, d, keys, r):
    config = dict(CONFIG, gdn_chunk=c, linear_key_head_dim=d,
                  linear_value_head_dim=d, linear_num_key_heads=keys,
                  linear_num_value_heads=keys * r)
    a_chunk = sum(2 * m * k * n for m, k, n in _matmuls_of_a_chunk(c, d, r))
    assert qwen3_next.scan_flops_per_token(config) * c == keys * a_chunk


def test_the_scan_is_4_7_mflop_a_token_a_layer():
    assert qwen3_next.scan_flops_per_token(CONFIG) == 16 * 4 * 64 * 128 \
        + 32 * (4 * 64 * 128 + 6 * 128 * 128) == 4_718_592
    # what the broadcast route through ops/kda.py would be charged: 32 heads
    # of 180,224 (test_flops_kimi_linear.py)
    assert 32 * 180_224 / 4_718_592 == pytest.approx(1.22, abs=0.01)


def test_qwen3_next_is_1_60_gflop_a_token_at_the_cut():
    e = 2048
    # qkvz, ba, the convolution over q, k and v, out
    gdn = e * 12_288 + e * 64 + 4 * 8192 + 4096 * e
    assert gdn == 33_718_272
    # wq: 16 heads of 256 + 256; wk, wv: 2 heads of 256; wo
    attn = e * 8192 + 2 * e * 512 + 4096 * e
    assert attn == 27_262_976
    expert, router, shared = 3 * e * 512, e * 512, 3 * e * 512 + e
    # top_k * 32 / 512 = 0.625 held experts a token, at balance
    sparse = router + shared + 10 * 32 * expert // 512
    scan = qwen3_next.scan_flops_per_token(CONFIG) // 2
    layers = 3 * (gdn + scan + sparse) + (attn + sparse)
    head = e * 18_992
    # the one attention layer's causal scores, forward + backward: q.k and
    # p.v over 16 x 256, 2 FLOPs a pair a dimension, half the square
    scores = 3 * 2 * 16 * (256 + 256) * SEQ // 2
    assert scores == 6 * SEQ * 4096
    want = 6 * (layers + head) + scores
    s = qwen3_next.shape(CONFIG, 1)
    assert (s["n_layer"], s["d_model"], s["vocab"]) == (2, 2048, 18_992)
    assert (s["n_head"], s["n_kv_head"], s["head_dim"]) == (16, 2, 256)
    assert 6 * s["n_layer"] * SEQ * s["d_model"] == scores
    got = flops.train_flops_per_token(CONFIG, 1, SEQ)
    lost = layers % 2
    assert want - got == 6 * lost and lost < 2
    assert want == pytest.approx(1.60e9, rel=5e-3)
    assert layers + head - 3 * scan == pytest.approx(192.0e6, rel=1e-3)
    # the shares cut_why states
    assert 6 * 3 * gdn / want == pytest.approx(0.38, abs=0.005)
    assert 6 * 3 * scan / want == pytest.approx(0.027, abs=0.002)
    assert scores / want == pytest.approx(0.25, abs=0.005)
    assert 6 * attn / want == pytest.approx(0.10, abs=0.005)
    assert 6 * 4 * sparse / want == pytest.approx(0.09, abs=0.005)
    assert 6 * head / want == pytest.approx(0.146, abs=0.002)


def test_state_is_10_0_gb_of_the_chip():
    e, expert = 2048, 3 * 2048 * 512
    gdn = e * 12_288 + e * 64 + 4 * 8192 + 4096 * e + 32 + 32 + 128
    attn = e * 8192 + 2 * e * 512 + 4096 * e + 2 * 256
    sparse = e * 512 + 32 * expert + expert + e
    assert gdn == pytest.approx(33.72e6, rel=1e-3)
    assert attn == pytest.approx(27.26e6, rel=1e-3)
    assert sparse == pytest.approx(104.86e6, rel=1e-4)
    assert 512 * expert * 16 == pytest.approx(25.8e9, rel=1e-2)
    total = 3 * (gdn + sparse) + (attn + sparse) + 2 * 18_992 * e + 9 * e
    assert total == pytest.approx(625.67e6, rel=1e-4)
    assert 16 * total == pytest.approx(10.01e9, rel=1e-3)
    # 64 experts a chip, had they fitted
    assert 16 * (total + 4 * 32 * expert) == pytest.approx(16.5e9, rel=1e-2)


def test_shape_counts_the_parameter_trees_matmul_leaves():
    """At the toy's widths: every kernel of the program's parameter tree
    that is a matmul operand (the head's at the unpadded vocabulary; of the
    held experts ``top_k / n_experts`` of each), the convolution's taps and
    the scans are what ``shape`` hands the formula."""
    import jax

    from ray_tpu.models.pretrain import init_params

    with open(os.path.join(os.path.dirname(os.path.abspath(__file__)), "toy",
                           "toy-qwen3-next.json")) as f:
        toy = json.load(f)
    cfg = qwen3_next.model_config(toy, 1)
    params = jax.eval_shape(lambda: init_params(cfg)[1])
    total = 0
    for path, leaf in jax.tree_util.tree_flatten_with_path(params)[0]:
        name = jax.tree_util.keystr(path)
        size = 1
        for n in leaf.shape:
            size *= n
        if "lm_head" in name or "wte" in name:
            continue
        if name.endswith("conv_kernel']") or "kernel" in name:
            total += size
        elif any(name.endswith(f"moe']['{w}_proj']")
                 for w in ("gate", "up", "down")):
            # (held, ., .): top_k of n_experts of them a token
            total += size * toy["num_experts_per_tok"] \
                // qwen3_next.n_experts(toy)
    kinds = qwen3_next.layer_kinds(toy)
    scans = kinds.count("gdn") * (qwen3_next.scan_flops_per_token(toy) // 2)
    s = qwen3_next.shape(toy, 1)
    assert total + scans - s["n_layer"] * s["layer_mm_params"] \
        in range(s["n_layer"])
    assert flops.matmul_params(toy, 1) == s["n_layer"] * s["layer_mm_params"] \
        + 64 * 512


def test_gdn_scan_work():
    work = gdn_work.scan_step(CONFIG, 1, rows=1, seq=SEQ)
    # three gdn layers, forward + twice that backward
    assert work["flops"] == 3 * 3 * SEQ * 4_718_592
    # bf16 q, k at 16 heads, v, o at 32; float32 g and beta a value head; the
    # float32 state a chunk a value head
    assert work["bytes"] == 3 * 3 * (
        SEQ * (2 * 2 * 16 * 128 + 2 * 2 * 32 * 128 + 2 * 4 * 32)
        + 2 * 4 * (SEQ // 64) * 32 * 128 * 128)
    least, bound = flops.roofline_seconds(work, PEAK)
    # 0.70 TFLOP a step are 3.5 ms at the peak; 13.3 GB a step, four fifths
    # of them the chunk-end states, take longer
    assert bound == "memory"
    assert work["flops"] / PEAK["bf16_flops_per_s"] == pytest.approx(
        3.53e-3, rel=1e-2)
    assert least == work["bytes"] / PEAK["hbm_bytes_per_s"]
    assert work["bytes"] == pytest.approx(13.3e9, rel=1e-2)


def _ctx(gdn="gdn/", gated="gated/"):
    """Two steps on one device: a gdn layer's projections, convolutions,
    gates and scan, forward, recomputed and backward, the gated attention
    layer's two kernels and its gate, and other work."""
    ops, t = [], 0.0
    stack = "jit(pretrain_step)/jvp(LlamaLMModel)/"
    back = "jit(pretrain_step)/transpose(jvp(LlamaLMModel))/" \
        "jvp(LlamaLMModel)/checkpoint/"
    call, fusion = "custom-call:tpu_custom_call", "fusion"
    for step in range(2):
        for i, (kind, path, secs) in enumerate([
                (call, stack + f"h_0/{gdn}scan/gdn_solve/pallas_call", 4e-3),
                (call, stack + f"h_0/{gdn}scan/gdn_fwd/pallas_call", 5e-3),
                (call, back + f"rematted_computation/h_0/{gdn}scan/gdn_fwd/pallas_call", 5e-3),
                (call, back + f"h_0/{gdn}scan/gdn_bwd/pallas_call", 10e-3),
                (fusion, back + f"h_0/{gdn}scan/cumsum", 1e-3),
                (fusion, stack + f"h_0/{gdn}in_proj_qkvz/dot_general", 2e-3),
                (fusion, back + f"h_0/{gdn}in_proj_ba/dot_general", 1e-3),
                (fusion, back + f"h_0/{gdn}out_proj/dot_general", 3e-3),
                (call, stack + f"h_0/{gdn}conv/conv_silu_fwd/pallas_call", 4e-3),
                (fusion, stack + f"h_0/{gdn}gate/softplus", 2e-3),
                (fusion, back + f"h_0/{gdn}out_gate/mul", 1e-3),
                (fusion, back + f"h_0/{gdn}o_norm/mul", 1e-3),
                (call, stack + f"h_3/attn/{gated}flash_fwd/flash_fwd/pallas_call", 9e-3),
                (call, back + f"h_3/attn/{gated}flash_bwd/flash_bwd/pallas_call", 21e-3),
                (fusion, stack + "h_3/attn/gate/mul", 1e-3),
                (fusion, stack + "h_3/attn/wq/dot_general", 1e-3),
                (call, stack + "h_1/moe/experts/gmm/pallas_call", 5e-3)]):
            ops.append(Op(f"op.{step}.{i}", kind, path, t, t + secs))
            t += secs
    trace = Trace(ops={0: ops}, spans=[("window", 0.0, t)])
    return Context(CELL, PEAK, {}, trace, traced_steps=2)


def _metric(name):
    with open(os.path.join(manifest.BENCH_DIR, "layer_metrics",
                           name + ".json")) as f:
        return json.load(f)


def test_the_new_metrics_on_a_synthetic_trace():
    ctx = _ctx()
    read = lambda name: trace_ops.read(ctx, **_metric(name)["args"])  # noqa: E731
    assert read("gdn_scan_ms_per_step") == pytest.approx(25.0)
    assert read("gdn_proj_ms_per_step") == pytest.approx(6.0)
    assert read("gdn_conv_gate_ms_per_step") == pytest.approx(8.0)
    assert read("gdn_solve_calls_per_step") == pytest.approx(1.0)
    assert read("gdn_scope_share_pct") == pytest.approx(100 * 39 / 76)
    least = flops.roofline_seconds(
        gdn_work.scan_step(CONFIG, 1, 1, SEQ), PEAK)[0]
    # over everything under the scope, the recomputation's time included
    assert scope_roofline.read(
        ctx, **_metric("gdn_scan_roofline")["args"]) == pytest.approx(
            100 * least / 25e-3)
    # the gated layer's flash pair by flash_work.py at the family's sizes:
    # 16 heads 256 wide over 2
    for name, work, secs in (
            ("gated_attn_fwd_roofline", flash_work.fwd_call, 9e-3),
            ("gated_attn_bwd_roofline", flash_work.bwd_call, 21e-3)):
        least = flops.roofline_seconds(work(CONFIG, 1, 1, SEQ), PEAK)[0]
        assert kernel_roofline.read(ctx, **_metric(name)["args"]) \
            == pytest.approx(100 * least / secs)
    fwd = flash_work.fwd_call(CONFIG, 1, 1, SEQ)
    assert fwd["flops"] == 2 * 16 * SEQ * SEQ / 2 * (256 + 256)
    assert fwd["bytes"] == 2 * SEQ * (16 * 512 + 2 * 512)
    # the list-less metrics read the new cell by their own selectors: the
    # flash kernels are the only Mosaic calls under h_<n>/attn/
    assert trace_ops.read(ctx, **_metric("flash_fwd_ms_per_step")["args"]) \
        == pytest.approx(9.0)
    assert trace_ops.read(ctx, **_metric("flash_bwd_ms_per_step")["args"]) \
        == pytest.approx(21.0)


def test_a_program_without_the_scopes_reports_nothing():
    """A program that has no ``gdn`` module and no ``gated`` scope (the
    parent's, on any cell it can run): the readers give None and do not
    raise."""
    ctx = _ctx()
    ctx.trace.ops[0] = [o for o in ctx.trace.ops[0] if "/gdn/" not in o.path
                        and "/gated/" not in o.path]
    readers = {"trace_ops": trace_ops, "scope_roofline": scope_roofline,
               "kernel_roofline": kernel_roofline}
    for name in NEW:
        metric = _metric(name)
        assert readers[metric["reader"]].read(ctx, **metric["args"]) is None


def test_the_cell_is_on_the_new_entries_lists_alone():
    bench = manifest.benchmark()
    listed = [m["name"] for m in bench["per_layer"]
              if NAME in m.get("workloads", [])]
    assert listed == NEW[:1] + ["gdn_scan_ms_per_step", "gdn_scan_roofline",
                                "gdn_proj_ms_per_step",
                                "gdn_conv_gate_ms_per_step",
                                "gdn_solve_calls_per_step",
                                "gated_attn_fwd_roofline",
                                "gated_attn_bwd_roofline"]
    assert len(bench["per_layer"]) == 121
    (entry,) = [w for w in bench["workloads"] if w["name"] == NAME]
    assert (entry["config"], entry["traffic"], entry["chips"]) == (
        "qwen3-next-80b-a3b-instruct", "s16k-b1-gen", 1)
    assert len(entry["why"]) <= 200


CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"


@pytest.mark.skipif(not os.path.exists(CATALOG),
                    reason="the catalog is beside the builder's guides only")
def test_every_catalog_key_stands_in_the_file():
    """Every key of the catalog row's ``config`` under the same key, verbatim
    but the three of ``reduced``, which stand beside their published
    counts."""
    with open(CATALOG) as f:
        row = next(r for r in map(json.loads, f)
                   if r["name"] == "Qwen3-Next-80B-A3B-Instruct")
    assert row["source_url"] == CONFIG["source"]
    assert sorted(CONFIG["reduced"]) == ["num_experts", "num_hidden_layers",
                                         "vocab_size"]
    for key, value in row["config"].items():
        if key in CONFIG["reduced"]:
            assert CONFIG["published_counts"][key] == value
        else:
            assert CONFIG[key] == value, key
    assert (CONFIG["num_hidden_layers"], CONFIG["num_experts"],
            CONFIG["vocab_size"]) == (4, 32, 18_992)

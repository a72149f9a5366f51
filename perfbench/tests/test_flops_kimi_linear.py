"""``families/kimi_linear.py::shape`` (what ``flops.train_flops_per_token``
counts ``kimi-linear-s16k-1chip`` from), ``scan_flops_per_token`` and
``kda_work.py`` against sums written out by hand from the published sizes,
the equations of the two mixers and the cut, a brute-force count of the
chunked form's matmuls, and the parameter tree's own matmul leaves; the new
metrics on a synthetic trace whose name paths are as the chip's trace prints
them (five of PR 53's six: ``mla_nope_attn_ms_per_step`` was the cell's one
flash pair by another path, ``flash_fwd_ms_per_step`` +
``flash_bwd_ms_per_step``, and went at PR 71)."""

import json
import os

import pytest

from perfbench.harness import flops, kda_work, manifest
from perfbench.harness.families import kimi_linear
from perfbench.harness.readers import scope_roofline, trace_ops
from perfbench.harness.readers.context import Context
from perfbench.harness.trace_reduce import Op, Trace

NAME = "kimi-linear-s16k-1chip"
CELL = manifest.cell(NAME)
CONFIG = CELL.config
PEAK = manifest.peaks()["TPU v5 lite"]
SEQ = 16384
NEW = ["kda_scope_share_pct", "kda_scan_ms_per_step", "kda_scan_roofline",
       "kda_proj_ms_per_step", "kda_conv_gate_ms_per_step"]


def _matmuls_of_a_chunk(c, dk, dv):
    """(m, k, n) of every dense matmul of one head's chunk of ``c``
    positions in the chunked form: ``L`` and ``A`` over ``dk``, the solve as
    one ``c x c`` by ``c x (dk + dv)`` product, ``A U``, and the three that
    touch the state."""
    return [(c, dk, c), (c, dk, c), (c, c, dk + dv), (c, c, dv),
            (c, dk, dv), (c, dk, dv), (dk, c, dv)]


@pytest.mark.parametrize("c,d,heads", [(64, 128, 32), (8, 16, 4), (16, 32, 2)])
def test_the_scans_count_is_the_chunked_forms_matmuls(c, d, heads):
    config = dict(CONFIG, kda_chunk=c, linear_attn_config=dict(
        CONFIG["linear_attn_config"], head_dim=d, num_heads=heads))
    a_chunk = sum(2 * m * k * n for m, k, n in _matmuls_of_a_chunk(c, d, d))
    assert kimi_linear.scan_flops_per_token(config) * c == heads * a_chunk


def test_the_scan_is_180k_flops_a_token_a_head():
    per_head = kimi_linear.scan_flops_per_token(CONFIG) // 32
    assert per_head == 2 * 64 * 5 * 128 + 6 * 128 * 128 == 180_224
    assert kimi_linear.scan_flops_per_token(CONFIG) // 2 == 2_883_584


def test_kimi_linear_is_2_59_gflop_a_token_at_the_cut():
    d, inner = 2304, 4096
    # q, k, v, o; the two low-rank maps through 128; b; three convolutions
    kda = 4 * d * inner + 2 * (d * 128 + 128 * inner) + d * 32 + 3 * 4 * inner
    assert kda == 39_510_016
    # Wq: 32 heads of 128 + 64; Wdkv; Wukv: 32 heads of 128 + 128; Wo
    mla = d * 32 * 192 + d * (512 + 64) + 512 * 32 * 256 + 32 * 128 * d
    assert mla == 29_114_368
    dense, expert, router = 3 * d * 9216, 3 * d * 1024, d * 256
    # top_k * 8 / 256 = 0.25 held experts a token, at balance
    sparse = router + expert + 8 * 8 * expert // 256
    scan = kimi_linear.scan_flops_per_token(CONFIG) // 2
    layers = (kda + scan + dense) + 3 * (kda + scan + sparse) + (mla + sparse)
    head = d * 20_480
    # the one MLA layer's causal scores, forward + backward: q.k over 32 x
    # 192 and p.v over 32 x 128, 2 FLOPs a pair a dimension, half the square
    scores = 3 * 2 * 32 * (192 + 128) * SEQ // 2
    assert scores == 6 * SEQ * 5120
    want = 6 * (layers + head) + scores
    s = kimi_linear.shape(CONFIG, 1)
    assert (s["n_layer"], s["d_model"], s["vocab"]) == (2, 2304, 20_480)
    # the formula's second term charges 2 x 2,304 of the 5,120; the other
    # 512 x seq ride in layer_mm_params
    assert 6 * s["n_layer"] * SEQ * s["d_model"] + 6 * 512 * SEQ == scores
    got = flops.train_flops_per_token(CONFIG, 1, SEQ)
    lost = (layers + 512 * SEQ) % 2
    assert want - got == 6 * lost and lost < 2
    assert want == pytest.approx(2.587e9, rel=1e-3)
    # the shares cut_why states
    assert 6 * 4 * kda / want == pytest.approx(0.367, abs=0.002)
    assert 6 * 4 * scan / want == pytest.approx(0.027, abs=0.002)
    assert scores / want == pytest.approx(0.195, abs=0.002)
    assert 6 * mla / want == pytest.approx(0.068, abs=0.002)
    assert 6 * dense / want == pytest.approx(0.148, abs=0.002)
    assert 6 * 4 * sparse / want == pytest.approx(0.088, abs=0.002)
    assert 6 * head / want == pytest.approx(0.109, abs=0.002)


def test_state_is_9_64_gb_of_the_chip():
    d, inner, expert = 2304, 4096, 3 * 2304 * 1024
    kda = 4 * d * inner + 2 * (d * 128 + 128 * inner) + d * 32 \
        + 3 * 4 * inner + 32 + inner + inner + 128
    mla = d * 6144 + d * 576 + 512 * 8192 + 4096 * d + 512
    sparse = d * 256 + expert + 8 * expert
    assert kda == pytest.approx(39.52e6, rel=1e-3)
    assert mla == pytest.approx(29.11e6, rel=1e-3)
    assert 256 * expert * 16 == pytest.approx(29.0e9, rel=1e-2)
    total = (kda + 3 * d * 9216) + 3 * (kda + sparse) + (mla + sparse) \
        + 2 * 20_480 * d + 11 * d
    assert total == pytest.approx(602.45e6, rel=1e-4)
    assert 16 * total == pytest.approx(9.64e9, rel=1e-3)
    # a sixth layer (KDA, sparse) or sixteen experts a chip, had they fitted
    assert 16 * (total + kda + sparse) == pytest.approx(11.3e9, rel=1e-2)
    assert 16 * (total + 4 * 8 * expert) == pytest.approx(13.3e9, rel=1e-2)


def test_shape_counts_the_parameter_trees_matmul_leaves():
    """At the toy's widths: every kernel of the program's parameter tree
    that is a matmul operand (the head's at the unpadded vocabulary; of the
    held experts ``top_k / n_experts`` of each), the convolutions' taps, the
    scans and the scores' remainder are what ``shape`` hands the formula."""
    import jax

    from ray_tpu.models.pretrain import init_params

    with open(os.path.join(os.path.dirname(os.path.abspath(__file__)), "toy",
                           "toy-kimi-linear.json")) as f:
        toy = json.load(f)
    cfg = kimi_linear.model_config(toy, 1)
    params = jax.eval_shape(lambda: init_params(cfg)[1])
    total = 0
    for path, leaf in jax.tree_util.tree_flatten_with_path(params)[0]:
        name = jax.tree_util.keystr(path)
        size = 1
        for n in leaf.shape:
            size *= n
        if "lm_head" in name or "wte" in name:
            continue
        if name.endswith("_conv']") or (
                "kernel" in name and "moe']['gate" not in name):
            total += size
        elif any(f"moe']['{w}_proj']" in name for w in ("gate", "up", "down")):
            # (held, ., .): top_k of n_experts of them a token
            total += size * toy["num_experts_per_token"] \
                // kimi_linear.n_experts(toy)
    kinds = kimi_linear.layer_kinds(toy)
    scans = kinds.count("kda") * (kimi_linear.scan_flops_per_token(toy) // 2)
    s = kimi_linear.shape(toy, 1)
    scores = kinds.count("full_attention") * 4 * (24 + 16) // 2
    remainder = (scores - s["n_layer"] * s["d_model"]) * 64
    assert total + scans + remainder - s["n_layer"] * s["layer_mm_params"] \
        in range(s["n_layer"])
    assert flops.matmul_params(toy, 1) == s["n_layer"] * s["layer_mm_params"] \
        + 64 * 512


def test_kda_scan_work():
    work = kda_work.scan_step(CONFIG, 1, rows=1, seq=SEQ)
    # four KDA layers, forward + twice that backward
    assert work["flops"] == 3 * 4 * SEQ * 32 * 180_224
    # bf16 q, k, v, o; float32 g and b; the float32 state a chunk a head
    assert work["bytes"] == 3 * 4 * (
        SEQ * 32 * (4 * 2 * 128 + 4 * 128 + 4)
        + 2 * 4 * (SEQ // 64) * 32 * 128 * 128)
    least, bound = flops.roofline_seconds(work, PEAK)
    # 1.13 TFLOP a step are 5.8 ms at the peak; 22.6 GB a step, more than
    # half of them the chunk-end states, take longer
    assert bound == "memory"
    assert work["flops"] / PEAK["bf16_flops_per_s"] == pytest.approx(
        5.76e-3, rel=1e-2)
    assert least == work["bytes"] / PEAK["hbm_bytes_per_s"]
    assert work["bytes"] == pytest.approx(22.6e9, rel=1e-2)


def _ctx(kda="kda/"):
    """Two steps on one device: a KDA layer's projections, convolutions,
    gates and scan, forward, recomputed and backward, the MLA layer's two
    kernels, and other work."""
    ops, t = [], 0.0
    stack = "jit(pretrain_step)/jvp(LlamaLMModel)/"
    back = "jit(pretrain_step)/transpose(jvp(LlamaLMModel))/" \
        "jvp(LlamaLMModel)/checkpoint/"
    call, fusion = "custom-call:tpu_custom_call", "fusion"
    for step in range(2):
        for i, (kind, path, secs) in enumerate([
                (call, stack + f"h_0/{kda}scan/kda_fwd/pallas_call", 20e-3),
                (call, back + f"rematted_computation/h_0/{kda}scan/kda_fwd/pallas_call", 20e-3),
                (call, back + f"h_0/{kda}scan/kda_bwd/pallas_call", 40e-3),
                (fusion, back + f"h_0/{kda}scan/transpose", 1e-3),
                (fusion, stack + f"h_0/{kda}q_proj/dot_general", 2e-3),
                (fusion, back + f"h_0/{kda}f_b/dot_general", 1e-3),
                (fusion, back + f"h_0/{kda}o_proj/dot_general", 3e-3),
                (fusion, stack + f"h_0/{kda}conv/mul", 4e-3),
                (fusion, stack + f"h_0/{kda}gate/softplus", 2e-3),
                (fusion, back + f"h_0/{kda}out_gate/mul", 1e-3),
                (fusion, back + f"h_0/{kda}o_norm/mul", 1e-3),
                (call, stack + "h_3/attn/mla/flash_fwd/flash_fwd/pallas_call", 9e-3),
                (call, back + "h_3/attn/mla/flash_bwd/flash_bwd/pallas_call", 21e-3),
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
    assert read("kda_scan_ms_per_step") == pytest.approx(81.0)
    assert read("kda_proj_ms_per_step") == pytest.approx(6.0)
    assert read("kda_conv_gate_ms_per_step") == pytest.approx(8.0)
    assert read("kda_scope_share_pct") == pytest.approx(100 * 95 / 131)
    least = flops.roofline_seconds(
        kda_work.scan_step(CONFIG, 1, 1, SEQ), PEAK)[0]
    # over everything under the scope, the recomputation's time included
    assert scope_roofline.read(
        ctx, **_metric("kda_scan_roofline")["args"]) == pytest.approx(
            100 * least / 81e-3)
    # the list-less metrics read the new cell by their own selectors: the
    # flash kernels are the only Mosaic calls under h_<n>/attn/
    assert trace_ops.read(ctx, **_metric("flash_fwd_ms_per_step")["args"]) \
        == pytest.approx(9.0)
    assert trace_ops.read(ctx, **_metric("flash_bwd_ms_per_step")["args"]) \
        == pytest.approx(21.0)


def test_a_program_without_the_scopes_reports_nothing():
    """A program that has no ``kda`` module (the parent's, on any cell it can
    run): the readers give None and do not raise."""
    ctx = _ctx()
    ctx.trace.ops[0] = [o for o in ctx.trace.ops[0] if "/kda/" not in o.path
                        and "/mla/" not in o.path]
    for name in NEW:
        reader = scope_roofline if name.endswith("roofline") else trace_ops
        assert reader.read(ctx, **_metric(name)["args"]) is None


CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"


@pytest.mark.skipif(not os.path.exists(CATALOG),
                    reason="the catalog is beside the builder's guides only")
def test_every_catalog_key_stands_in_the_file():
    """Every key of the catalog row's ``config`` under the same key, verbatim
    but those of ``reduced``, which stand beside their published counts; of
    ``linear_attn_config`` only the two layer lists differ."""
    with open(CATALOG) as f:
        row = next(r for r in map(json.loads, f)
                   if r["name"] == "Kimi-Linear-48B-A3B-Instruct")
    assert row["source_url"] == CONFIG["source"]
    for key, value in row["config"].items():
        if key in CONFIG["reduced"]:
            assert CONFIG["published_counts"][key] == value
        else:
            assert CONFIG[key] == value, key
    published = row["config"]["linear_attn_config"]
    for key, value in CONFIG["linear_attn_config"].items():
        if key not in ("kda_layers", "full_attn_layers"):
            assert published[key] == value, key


def test_the_cell_as_the_manifest_has_it():
    bench = manifest.benchmark()
    entry = next(c for c in bench["configs"]
                 if c["name"] == "kimi-linear-48b-a3b-instruct")
    assert entry["reduced"] == CONFIG["reduced"] == [
        "num_hidden_layers", "num_experts", "vocab_size",
        "linear_attn_config"]
    assert CELL.chips == 1 and CELL.traffic["kind"] == "train_loop"
    assert (CELL.traffic["seq"], CELL.traffic["rows_per_step"]) == (SEQ, 1)
    assert CONFIG["flops_counted_at_seq"] == SEQ
    # the very traffic file of the Kimi-VL cell
    assert next(w for w in bench["workloads"] if w["name"] == NAME)[
        "traffic"] == next(w for w in bench["workloads"]
                           if w["name"] == "kimi-vl-s16k-1chip")["traffic"]
    listed = {m["name"] for m in bench["per_layer"]
              if NAME in m.get("workloads", [])}
    assert set(NEW) <= listed    # (a later PR may list it under more)
    assert set(NEW) <= {m["name"] for m in CELL.per_layer}
    for m in bench["per_layer"]:
        if m["name"] in NEW:
            assert m["workloads"] == [NAME]
    assert CONFIG["published_counts"]["num_hidden_layers"] == 27
    assert CONFIG["published_counts"]["num_experts"] == 256
    assert CONFIG["published_counts"]["vocab_size"] == 163840
    assert kimi_linear.held(CONFIG) == (0, 8)
    assert kimi_linear.n_experts(CONFIG) == 256
    assert kimi_linear.layer_kinds(CONFIG) == (
        "kda", "kda", "kda", "full_attention", "kda")
    assert CONFIG["deployment"]["chips_sharing_a_layer"] == 32
    assert CONFIG["deployment"]["chips_sharing_the_vocabulary"] == 8
    assert CONFIG["reference"]["prefix"] >= 1024
    for key in ("cut_why", "assumed", "program_departures", "dtypes"):
        assert CONFIG[key]

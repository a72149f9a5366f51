"""``families/lfm2.py::shape`` (what ``flops.train_flops_per_token`` counts
``lfm2-s16k-1chip`` from) and ``conv_work.py`` against sums written out by
hand from the published sizes, the layer equations and the cut; the four new
metrics on a synthetic trace whose name paths are as the chip's trace prints
them."""

import json
import os

import pytest

from perfbench.harness import conv_work, flops, manifest
from perfbench.harness.families import lfm2
from perfbench.harness.readers import scope_roofline, trace_ops
from perfbench.harness.readers.context import Context
from perfbench.harness.trace_reduce import Op, Trace

CELL = manifest.cell("lfm2-s16k-1chip")
CONFIG = CELL.config
PEAK = manifest.peaks()["TPU v5 lite"]
SEQ = 16384
NEW = ["shortconv_scope_share_pct", "shortconv_mix_ms_per_step",
       "shortconv_proj_ms_per_step", "shortconv_mixer_roofline"]


def test_lfm2_is_1_32_gflop_a_token_at_the_cut():
    d = 2048
    # in_proj to B, C, u and out_proj; the depthwise kernel is no matmul
    mixer = d * 3 * d + d * d
    # Wq and Wo at 32 heads of 64, Wk and Wv at 8
    attention = 2 * d * 32 * 64 + 2 * d * 8 * 64
    assert (mixer, attention) == (16_777_216, 10_485_760)
    dense, expert, router = 3 * d * 11_776, 3 * d * 1536, d * 64
    # top_k * 8 / 64 = 0.5 held experts a token, at balance
    sparse = router + 4 * 8 * expert // 64
    layers = 4 * mixer + attention + dense + 4 * sparse
    head = d * 8192
    # the one attention layer's causal scores, forward + backward: q.k and
    # p.v over 32 x 64, 2 FLOPs a pair a dimension, half the square, x 3
    scores = 3 * 2 * 32 * (64 + 64) * SEQ // 2
    assert scores == 6 * SEQ * d
    want = 6 * (layers + head) + scores
    s = lfm2.shape(CONFIG, 1)
    assert (s["n_layer"], s["d_model"], s["vocab"]) == (1, 2048, 8192)
    assert (s["n_head"], s["n_kv_head"], s["head_dim"]) == (32, 8, 64)
    assert s["layer_mm_params"] == layers
    assert flops.train_flops_per_token(CONFIG, 1, SEQ) == want
    assert want == pytest.approx(1.318e9, rel=1e-3)
    # the shares cut_why states
    assert 6 * 4 * mixer / want == pytest.approx(0.305, abs=0.002)
    assert 6 * dense / want == pytest.approx(0.329, abs=0.002)
    assert scores / want == pytest.approx(0.153, abs=0.002)
    assert 6 * head / want == pytest.approx(0.076, abs=0.002)
    assert 6 * attention / want == pytest.approx(0.048, abs=0.002)
    assert 6 * 4 * 4 * 8 * expert // 64 / want == pytest.approx(0.086,
                                                               abs=0.002)
    # in the whole model a token meets 4 experts in 38 layers: 62% of its
    # matmul parameters, and the two dense layers 6%
    whole = 30 * mixer + 10 * attention + 2 * dense \
        + 38 * (router + 4 * expert) + d * 65_536
    assert 38 * 4 * expert / whole == pytest.approx(0.617, abs=0.002)
    assert 2 * dense / whole == pytest.approx(0.062, abs=0.002)
    # one step of 2 x 16,384 tokens at 45% of the peak: half a second
    assert 2 * SEQ * want / (0.45 * 197e12) == pytest.approx(0.487, abs=0.002)


def test_state_is_7_5_gb_of_the_chip():
    d, expert = 2048, 3 * 2048 * 1536
    mixer = d * 3 * d + d * d + 3 * d       # + the depthwise kernel
    attention = 2 * d * 2048 + 2 * d * 512 + 2 * 64     # + q_norm, k_norm
    sparse = d * 64 + 64 + 8 * expert       # the router, the bias, the held
    dense = 3 * d * 11_776
    assert mixer == pytest.approx(16.78e6, rel=1e-3)
    assert attention == pytest.approx(10.49e6, rel=1e-3)
    assert (dense, expert) == (72_351_744, 9_437_184)
    assert 64 * expert * 16 == pytest.approx(9.66e9, rel=1e-3)
    total = (mixer + dense) + (attention + sparse) + 3 * (mixer + sparse) \
        + 8192 * d + 11 * d                 # the tied table; eleven norms
    assert total == pytest.approx(469.3e6, rel=1e-4)
    assert 16 * total == pytest.approx(7.51e9, rel=1e-3)
    assert 16 * total / 16e9 == pytest.approx(0.47, abs=0.005)
    # six layers (a second period's attention layer) would be 10.4 GB short
    # of nothing, but 2 attention layers to 4 conv
    assert 16 * (total + 4 * (mixer + sparse) + attention + sparse
                 - 3 * (mixer + sparse)) < 16e9
    # the program's own tree says the same
    import jax

    from ray_tpu.models.pretrain import init_params

    shapes = jax.eval_shape(
        lambda: init_params(lfm2.model_config(CONFIG, 1))[1])
    assert sum(int(a.size) for a in jax.tree_util.tree_leaves(shapes)) \
        == total


def test_mixer_work():
    work = conv_work.mixer_step(CONFIG, 1, rows=2, seq=SEQ)
    tokens, d = 2 * SEQ, 2048
    # four conv layers, forward + backward = 3 x the forward's 2 FLOPs a
    # parameter a token over in_proj and out_proj
    assert work["flops"] == 4 * 3 * 2 * tokens * (3 * d * d + d * d)
    # the pass between them: 4 arrays forward, 7 backward, bf16
    assert work["bytes"] == 4 * 11 * 2 * tokens * d
    least, bound = flops.roofline_seconds(work, PEAK)
    assert bound == "compute"
    assert least == pytest.approx(67.0e-3, rel=1e-2)
    assert work["bytes"] / PEAK["hbm_bytes_per_s"] == pytest.approx(
        7.2e-3, rel=1e-2)


def _ctx(conv="conv/"):
    """Two steps on one device: a conv layer's forward, recomputation and
    backward as XLA names them, beside an attention layer's and a routed
    layer's operations and a Mamba layer's ``conv`` scope, which is not
    this."""
    ops, t = [], 0.0
    stack = "jit(pretrain_step)/jvp(LlamaLMModel)/"
    back = "jit(pretrain_step)/transpose(jvp(LlamaLMModel))/" \
        "jvp(LlamaLMModel)/checkpoint/"
    call, fusion = "custom-call:tpu_custom_call", "fusion"
    for step in range(2):
        for i, (kind, path, secs) in enumerate([
                (fusion, stack + f"h_2/{conv}in_proj/...e,ed->...d/dot_general", 12e-3),
                (fusion, stack + f"h_2/{conv}mix/mul", 1e-3),
                (fusion, stack + f"h_2/{conv}out_proj/dot_general", 4e-3),
                (fusion, back + f"rematted_computation/h_2/{conv}in_proj/...e,ed->...d/dot_general", 12e-3),
                (fusion, back + f"rematted_computation/h_2/{conv}mix/mul", 1e-3),
                (fusion, back + f"h_2/{conv}mix/add", 3e-3),
                (fusion, back + f"h_2/{conv}in_proj/...e,ed->...d/dot_general", 24e-3),
                (fusion, back + f"h_2/{conv}out_proj/dot_general", 8e-3),
                (fusion, stack + "h_9/mamba/conv/mul", 5e-3),
                (call, stack + "h_1/attn/flash_fwd/flash_fwd/pallas_call", 14e-3),
                (fusion, stack + "h_1/attn/rope/mul", 2e-3),
                (call, stack + "h_2/moe/experts/gmm/pallas_call", 6e-3)]):
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
    got = {name: trace_ops.read(ctx, **_metric(name)["args"])
           for name in NEW[:3]}
    # everything under h_2/conv/ and nothing of h_9/mamba/conv/
    assert got["shortconv_scope_share_pct"] == pytest.approx(
        100 * 65 / 92)
    assert got["shortconv_mix_ms_per_step"] == pytest.approx(5.0)
    assert got["shortconv_proj_ms_per_step"] == pytest.approx(60.0)
    least = flops.roofline_seconds(
        conv_work.mixer_step(CONFIG, 1, 2, SEQ), PEAK)[0]
    assert scope_roofline.read(
        ctx, **_metric("shortconv_mixer_roofline")["args"]) == pytest.approx(
            100 * least / 65e-3)
    # the Mamba layers' own metric does not read the new module either
    assert trace_ops.read(ctx, **_metric("mamba_conv_ms_per_step")["args"]) \
        == pytest.approx(5.0)


def test_a_program_without_the_module_reports_nothing():
    """A program that has no ``conv`` module (the parent's, on any cell it
    can run): the readers give None and do not raise."""
    ctx = _ctx()
    ctx.trace.ops[0] = [o for o in ctx.trace.ops[0]
                        if "/h_2/conv/" not in o.path]
    for name in NEW[:3]:
        assert trace_ops.read(ctx, **_metric(name)["args"]) is None
    assert scope_roofline.read(
        ctx, **_metric("shortconv_mixer_roofline")["args"]) is None


CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"


@pytest.mark.skipif(not os.path.exists(CATALOG),
                    reason="the catalog is beside the builder's guides only")
def test_every_catalog_key_stands_in_the_file():
    """Every key of the catalog row's ``config`` under the same key, verbatim
    but the five of ``reduced``, which stand beside their published
    counts."""
    with open(CATALOG) as f:
        row = next(r for r in map(json.loads, f)
                   if r["name"] == "LFM2-24B-A2B")
    assert row["source_url"] == CONFIG["source"]
    for key, value in row["config"].items():
        if key in CONFIG["reduced"]:
            assert CONFIG["published_counts"][key] == value
        else:
            assert CONFIG[key] == value, key
    # the cut's layers are the published list's first entry and 2 to 5
    published = row["config"]["layer_types"]
    assert CONFIG["layer_types"] == published[:1] + published[2:6]


def test_the_cell_as_the_manifest_has_it():
    bench = manifest.benchmark()
    entry = next(c for c in bench["configs"] if c["name"] == "lfm2-24b-a2b")
    assert entry["reduced"] == CONFIG["reduced"] == [
        "num_hidden_layers", "layer_types", "num_dense_layers", "num_experts",
        "vocab_size"]
    assert CELL.chips == 1 and CELL.traffic["kind"] == "train_loop"
    assert (CELL.traffic["seq"], CELL.traffic["rows_per_step"]) == (SEQ, 2)
    # the cell has its four metrics (a later PR may list it under more)
    listed = {m["name"] for m in bench["per_layer"]
              if "lfm2-s16k-1chip" in m.get("workloads", [])}
    assert set(NEW) <= listed
    assert set(NEW) <= {m["name"] for m in CELL.per_layer}
    # ... and none of the four lists another configuration's cell
    for m in bench["per_layer"]:
        if m["name"] in NEW:
            assert m["workloads"] == ["lfm2-s16k-1chip"]
    assert CONFIG["published_counts"]["num_hidden_layers"] == 40
    assert CONFIG["published_counts"]["layer_types"].count("conv") == 30
    assert lfm2.held(CONFIG) == (0, 8) and lfm2.n_experts(CONFIG) == 64
    assert lfm2.head_dim(CONFIG) == 64
    assert [lfm2.is_dense(CONFIG, i) for i in range(5)] == [True] + [False] * 4
    assert CONFIG["deployment"]["chips_sharing_a_layer"] == 8
    assert CONFIG["reference"]["prefix"] >= 1024
    for key in ("cut_why", "assumed", "program_departures", "dtypes"):
        assert CONFIG[key]
    for key in ("tie_word_embeddings", "router", "selection_bias",
                "auxiliary_router_loss", "rotary_pairing"):
        assert "alternative" in CONFIG["assumed"][key], key

"""``families/xing4.py::shape`` (what ``flops.train_flops_per_token`` counts
``xing4-s8k-1chip`` from) against a hand count and the program's own
parameter tree, ``mhc_work.py`` against the 50,176 values a position a
sub-layer a direction, ``mla_work.py`` at this file's keys, the cell as the
manifest has it, and the new metric on a synthetic trace whose name paths are
as the chip's trace prints them."""

import json
import os

import pytest

from perfbench.harness import flops, manifest, mhc_work, mla_work
from perfbench.harness.families import xing4
from perfbench.tests.manifest_lists import on_at_least
from perfbench.harness.readers import scope_roofline
from perfbench.harness.readers.context import Context
from perfbench.harness.trace_reduce import Op, Trace

NAME = "xing4-s8k-1chip"
CELL = manifest.cell(NAME)
CONFIG = CELL.config
PEAK = manifest.peaks()["TPU v5 lite"]
SEQ = 8192
D = 3584


def test_xing4_is_3_485_gflop_a_token_at_the_cut():
    attention = D * 768 + 768 * 32 * 192 + D * 576 + 512 * 32 * 256 \
        + 32 * 128 * D
    assert attention == xing4.attention_params(CONFIG) == 28_409_856
    dense, expert, router = 3 * D * 9216, 3 * D * 1024, D * 64
    # one sub-layer's hyper-connection, multiply-adds a position: 14,336
    # values to 24 coefficients, H_pre X, H_res X + H_post^T f
    hc = 4 * D * 24 + 4 * D + (16 + 4) * D
    assert hc == xing4.hc_mm_per_sublayer(CONFIG) == 430_080
    sparse = router + expert + 4 * 8 * expert // 64   # 0.5 held experts
    layers = 5 * (attention + 2 * hc) + dense + 4 * sparse
    head = D * 16_384
    # a layer's causal scores forward: q.k over 32 x 192 and p.v over 32 x
    # 128, 2 FLOPs a pair a dimension, half the square
    scores_fwd = 5 * 2 * 32 * (192 + 128) * SEQ // 2
    forward = 2 * (layers + head) + scores_fwd
    assert forward == pytest.approx(1161.6e6, rel=1e-4)
    want = 3 * forward
    s = xing4.shape(CONFIG, 1)
    assert (s["n_layer"], s["d_model"], s["vocab"]) == (7, D, 16_384)
    # the formula's second term charges 7 x 3,584 of the 25,600; the other
    # 512 x seq ride in layer_mm_params
    assert 6 * s["n_layer"] * SEQ * D + 6 * 512 * SEQ == 3 * scores_fwd
    got = flops.train_flops_per_token(CONFIG, 1, SEQ)
    lost = (layers + 512 * SEQ) % 7     # layer_mm_params is a floor over 7
    assert want - got == 6 * lost and lost < 7
    assert got == pytest.approx(3.485e9, rel=2e-4)
    assert got * SEQ == pytest.approx(28.5e12, rel=2e-3)
    # the shares cut_why states
    for part, share in ((scores_fwd, 0.361), (2 * 5 * attention, 0.245),
                        (2 * dense, 0.171), (2 * 4 * expert, 0.076),
                        (2 * 4 * expert // 2, 0.038), (2 * 4 * router, 0.002),
                        (2 * 10 * hc, 0.007), (2 * head, 0.101)):
        assert part / forward == pytest.approx(share, abs=0.0006)


def test_the_state_is_759_5m_parameters_by_the_programs_own_tree():
    import jax
    import numpy as np

    from ray_tpu.models.pretrain import init_params

    def count(tree):
        return sum(int(np.prod(a.shape))
                   for a in jax.tree_util.tree_leaves(tree))

    shapes = jax.eval_shape(
        lambda: init_params(xing4.model_config(CONFIG, 1))[1])
    hc = 14_336 * 24 + 24 + 3 + 14_336
    attention = xing4.attention_params(CONFIG) + 768 + 512  # + two norms
    assert count(shapes["h_0"]["hc_attn"]) == hc == 358_427
    assert count(shapes["h_0"]["attn"]) == attention == 28_411_136
    dense = attention + 3 * D * 9216 + 2 * hc + 2 * D
    sparse = attention + D * 64 + 64 + 9 * 3 * D * 1024 + 2 * hc + 2 * D
    assert count(shapes["h_0"]) == dense == 128_225_590
    assert count(shapes["h_1"]) == sparse == 128_455_030
    total = dense + 4 * sparse + 2 * 16_384 * D + D
    assert count(shapes) == total == 759_489_806
    assert 16 * total == pytest.approx(12.15e9, rel=1e-3)
    assert not [k for k in shapes if k.startswith("mtp")]
    # the published module, were it on this chip: M, a sparse block, 3 norms
    with_module = jax.eval_shape(lambda: init_params(xing4.model_config(
        dict(CONFIG, num_nextn_predict_layers=1), 1))[1])
    assert count(with_module["mtp_0"]) == 2 * D * D + sparse + 3 * D \
        == 154_155_894
    assert count(with_module) == 913_645_700
    # the model whole, from the published counts: the card's 29B-A4B
    whole = 2 * dense + 38 * (sparse + 56 * 3 * D * 1024) \
        + 2 * 131_072 * D + D
    assert whole == pytest.approx(29.5e9, rel=2e-3)


def test_the_hyper_connections_least_bytes():
    assert mhc_work.values_per_position(CONFIG) == (3 * 4 + 2) * D == 50_176
    step = mhc_work.stream_step(CONFIG, 1, 1, SEQ)
    # ten sub-layers, forward + a backward of twice it, bf16
    assert step["bytes"] == 3 * 10 * SEQ * 50_176 * 2
    assert step["bytes"] == pytest.approx(24.66e9, rel=1e-3)
    # with the recomputed forward, the 32.9 GB a step of the cell's why
    assert step["bytes"] * 4 / 3 == pytest.approx(32.9e9, rel=2e-3)
    assert step["flops"] == 3 * 10 * SEQ * (2 * 430_080 + 4 * 16 * 20)
    least, bound = flops.roofline_seconds(step, PEAK)
    assert bound == "memory"
    assert least == pytest.approx(0.0301, rel=5e-3)
    assert mhc_work.stream_step(dict(CONFIG, stream_dtype="float32"), 1, 1,
                                SEQ)["bytes"] == 2 * step["bytes"]


def test_the_attention_kernels_work_at_this_files_keys():
    """``mla_work.py`` reads the heads and widths as they stand: 32 heads,
    192 over 128, the rotary 64 once a position."""
    fwd = mla_work.flash_fwd_call(CONFIG, 1, 1, SEQ)
    assert fwd["flops"] == 2.0 * 32 * (192 + 128) * SEQ * SEQ / 2
    assert fwd["bytes"] == 2.0 * SEQ * (32 * (192 + 128 + 128 + 128) + 64)
    bwd = mla_work.flash_bwd_call(CONFIG, 1, 1, SEQ)
    assert bwd["flops"] == 2.0 * 32 * (3 * 192 + 2 * 128) * SEQ * SEQ / 2
    assert flops.roofline_seconds(fwd, PEAK)[1] == "compute"


def _context(ops):
    return Context(CELL, PEAK, {}, Trace(
        ops={0: ops}, spans=[("window", 0.0, float(len(ops)))]),
        traced_steps=1)


def test_the_new_metric_on_a_synthetic_trace():
    metric = next(m for m in CELL.per_layer
                  if m["name"] == "mhc_stream_roofline")
    args = metric["file"]["args"]
    paths = ["jit(pretrain_step)/jvp(LlamaLMModel)/h_0/hc_attn/coeff/dot",
             "jit(pretrain_step)/transpose(jvp(LlamaLMModel))/h_3/hc_mlp/"
             "post/mul",
             "jit(pretrain_step)/jvp(LlamaLMModel)/h_2/hc_mlp/sinkhorn/div",
             "jit(pretrain_step)/jvp(LlamaLMModel)/h_2/attn/wq_a/dot",
             "jit(pretrain_step)/jvp(LlamaLMModel)/mtp_0/block/hc_attn/pre/x"]
    ops = [Op(f"fusion.{i}", "fusion", p, float(i), i + 0.05)
           for i, p in enumerate(paths)]
    ctx = _context(ops)
    least = flops.roofline_seconds(
        mhc_work.stream_step(CONFIG, 1, 1, SEQ), PEAK)[0]
    # the three operations under a trunk layer's two modules, 0.15 s
    assert scope_roofline.read(ctx, **args) == pytest.approx(
        100 * least / 0.15, rel=1e-6)
    # a program without the modules: nothing to read, and no error
    assert scope_roofline.read(_context(ops[3:4]), **args) is None


XING4_LISTS = [
    "mhc_stream_roofline", "mhc_ms_per_step", "mla_qlat_ms_per_step",
    "hc_res_row_err", "mla_attn_fwd_roofline", "mla_attn_bwd_roofline",
    "mla_latent_ms_per_step", "mla_assemble_ms_per_step",
    "attn_rope_norm_ms_per_step", "moe_scope_share_pct",
    "moe_router_ms_per_step", "moe_dispatch_ms_per_step",
    "moe_experts_ms_per_step", "moe_shared_ms_per_step",
    "moe_onto_tokens_calls_per_step",
    "moe_rows_held_per_step", "dense_mlp_ms_per_step", "norm_ms_per_step"]


def test_the_cell_as_the_manifest_has_it():
    bench = manifest.benchmark()
    entry = next(c for c in bench["configs"] if c["name"] == "xing4.0-29b-a4b")
    assert entry["reduced"] == CONFIG["reduced"] == [
        "num_hidden_layers", "first_k_dense_replace", "n_routed_experts",
        "vocab_size", "num_nextn_predict_layers"]
    assert entry["file"] == "perfbench/configs/xing4.0-29b-a4b.json"
    assert entry["source"] == CONFIG["source"]
    assert CELL.chips == 1 and CELL.traffic["kind"] == "train_loop"
    assert (CELL.traffic["seq"], CELL.traffic["rows_per_step"]) == (SEQ, 1)
    assert CONFIG["flops_counted_at_seq"] == SEQ
    # the very traffic file of Mistral's and Granite's one-chip cells
    assert next(w for w in bench["workloads"] if w["name"] == NAME)[
        "traffic"] == next(w for w in bench["workloads"] if w["name"]
                           == "mistral-s8k-1chip")["traffic"] == "s8k-b1-gen"
    why = next(w["why"] for w in bench["workloads"] if w["name"] == NAME)
    assert len(why) <= 200
    # PR 65 could add one metric of its own, the 128th; since PR 67 the
    # cell is on the lists its scopes are on, and has the three PRs 65 and
    # 66 had no room for.  By name, and at least these
    listed = on_at_least(bench, NAME, XING4_LISTS)
    assert len(bench["per_layer"]) <= 128
    assert {m["name"] for m in listed} | {
        m["name"] for m in bench["per_layer"] if "workloads" not in m} \
        <= {m["name"] for m in CELL.per_layer}
    assert {m["name"] for m in CELL.end_to_end} == {
        "tokens_per_s_per_chip", "mfu_pct", "setup_s"}
    assert CONFIG["published_counts"] == {
        "num_hidden_layers": 40, "first_k_dense_replace": 2,
        "n_routed_experts": 64, "vocab_size": 131072,
        "num_nextn_predict_layers": 1}
    assert xing4.held(CONFIG) == (0, 8)
    assert CONFIG["deployment"]["chips_sharing_a_layer"] == 8
    for key in ("cut_why", "assumed", "program_departures", "dtypes",
                "reference", "deployment"):
        assert CONFIG[key]
    for limit in ("logits_rel_rms_max", "loss_rel_max", "grad_norm_rel_max"):
        assert 0 < CONFIG["reference"][limit] < 1


def test_every_catalog_number_stands_in_the_file():
    path = "/opt/skills/guides/model-configs/architectures.jsonl"
    if not os.path.exists(path):
        pytest.skip("the catalog is not on this machine")
    with open(path) as f:
        row = next(r for r in map(json.loads, f)
                   if r["name"] == "Xing4.0-29B-A4B")
    assert row["source_url"] == CONFIG["source"]
    for key, value in row["config"].items():
        if key not in CONFIG["reduced"]:
            assert CONFIG[key] == value, key
        else:
            assert CONFIG["published_counts"][key] == value
    # the widths: none is cut
    assert (CONFIG["hidden_size"], CONFIG["num_attention_heads"],
            CONFIG["q_lora_rank"], CONFIG["kv_lora_rank"],
            CONFIG["qk_nope_head_dim"], CONFIG["qk_rope_head_dim"],
            CONFIG["v_head_dim"], CONFIG["intermediate_size"],
            CONFIG["moe_intermediate_size"], CONFIG["num_experts_per_tok"],
            CONFIG["routed_scaling_factor"], CONFIG["hc_mult"],
            CONFIG["hc_sinkhorn_iters"]) == (
                3584, 32, 768, 512, 128, 64, 128, 9216, 1024, 4, 2, 4, 20)
    scaling = CONFIG["rope_scaling"]
    assert (scaling["factor"],
            scaling["original_max_position_embeddings"]) == (64, 4096)

"""``flops.py`` against counts made by hand from the published sizes."""

import json
import os

import pytest

from perfbench.harness import flash_work, flops, manifest


def _config(name):
    bench = manifest.benchmark()
    entry = next(c for c in bench["configs"] if c["name"] == name)
    with open(os.path.join(manifest.ROOT, entry["file"])) as f:
        return json.load(f)


def test_gpt2_small_is_798_mflop_a_token():
    config = _config("gpt2-small")
    # per layer 12 * 768^2 (qkv 3, out 1, mlp 8), 12 layers; head 768 * 50257
    n_mm = 12 * 12 * 768 ** 2 + 768 * 50257
    assert n_mm == 123_532_032
    assert flops.matmul_params(config, 1) == n_mm
    want = 6 * n_mm + 6 * 12 * 1024 * 768
    assert flops.train_flops_per_token(config, 1, 1024) == want
    assert want == pytest.approx(797.8e6, rel=1e-3)


@pytest.mark.parametrize("chips,layers,seq,gflop", [
    (1, 2, 8192, 3.825), (4, 8, 4096, 12.08)])
def test_mistral_by_depth(chips, layers, seq, gflop):
    config = _config("mistral-7b-v0.3")
    # wq, wo 4096x4096; wk, wv 4096x1024; gate, up, down 4096x14336
    layer = 2 * 4096 * 4096 + 2 * 4096 * 1024 + 3 * 4096 * 14336
    assert layer == 218_103_808
    n_mm = layers * layer + 4096 * 32768
    assert flops.shape(config, chips)["n_layer"] == layers
    assert flops.matmul_params(config, chips) == n_mm
    want = 6 * n_mm + 6 * layers * seq * 4096
    assert flops.train_flops_per_token(config, chips, seq) == want
    assert want == pytest.approx(gflop * 1e9, rel=2e-3)


def test_flash_forward_call_and_roofline():
    config = _config("mistral-7b-v0.3")
    call = flash_work.fwd_call(config, 1, rows=1, seq=8192)
    # QK^T and PV, 2 FLOPs a multiply-add, half the square, 32 heads of 128
    assert call["flops"] == 2 * 2 * 32 * 8192 * 8192 * 128 / 2
    # Q and O at 32 heads, K and V at 8, bf16
    assert call["bytes"] == 2 * 8192 * 128 * (32 + 32 + 8 + 8)
    peak = manifest.peaks()["TPU v5 lite"]
    least, bound = flops.roofline_seconds(call, peak)
    assert bound == "compute"
    assert least == pytest.approx(call["flops"] / 197e12)
    tiny = {"flops": 1e6, "bytes": 1e9}
    assert flops.roofline_seconds(tiny, peak) == (1e9 / 819e9, "memory")

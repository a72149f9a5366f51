"""The shared work functions (``flash_work.py``, ``ssd_work.py``; PR 71)
against what each cell's former per-model function counted: the old modules
are gone, their counts of one call (one step for the scan) at the cell's own
rows and sequence stand here as data, taken from the parent of PR 71 on the
files as they were.  No roofline moved by a change of yardstick: every count
is the old one to the last unit, but the whole-row calls of SmallThinker and
Nemotron, whose modules charged the causal triangle with its diagonal, ``seq *
(seq + 1) / 2`` pairs, where ``flops.flash_fwd_call`` charged the seven older
cells half the square, ``seq ** 2 / 2``, as ``mfu_pct``'s own scores are
charged: one in 16,385 fewer FLOPs there, bytes unmoved."""

import json
import os

import pytest

from perfbench.harness import flash_work, flops, manifest, ssd_work

WINDOW = "jit(pretrain_step)/jvp(LlamaLMModel)/h_1/attn/window/flash_fwd/" \
    "flash_fwd/pallas_call"
WHOLE = "jit(pretrain_step)/jvp(LlamaLMModel)/h_0/attn/flash_fwd/" \
    "flash_fwd/pallas_call"
# (cell, entry): (FLOPs, bytes) as the old function counted them, and the
# old function
OLD = {
    ("gpt2s-b24-s1k", "flash_fwd_roofline"):
        (38654705664, 150994944, "flops.flash_fwd_call"),
    ("gpt2s-loop-b8-s1k", "flash_fwd_roofline"):
        (12884901888, 50331648, "flops.flash_fwd_call"),
    ("mistral-s8k-1chip", "flash_fwd_roofline"):
        (549755813888, 167772160, "flops.flash_fwd_call"),
    ("mistral-fsdp4-s4k", "flash_fwd_roofline"):
        (137438953472, 83886080, "flops.flash_fwd_call"),
    ("olmoe-s4k-1chip", "flash_fwd_roofline"):
        (137438953472, 134217728, "flops.flash_fwd_call"),
    ("granite-h-s8k-1chip", "flash_fwd_roofline"):
        (274877906944, 83886080, "flops.flash_fwd_call"),
    ("lfm2-s16k-1chip", "flash_fwd_roofline"):
        (2199023255552, 335544320, "flops.flash_fwd_call"),
    ("smallthinker-s16k-1chip", "flash_fwd_roofline"):
        (1924262789120, 268435456, "smallthinker_work.full_fwd_call"),
    ("smallthinker-s16k-1chip", "flash_bwd_roofline"):
        (4810656972800, 419430400, "smallthinker_work.full_bwd_call"),
    ("smallthinker-s16k-1chip", "window_attn_fwd_roofline"):
        (841842950144, 268435456, "smallthinker_work.window_fwd_call"),
    ("smallthinker-s16k-1chip", "window_attn_bwd_roofline"):
        (2104607375360, 419430400, "smallthinker_work.window_bwd_call"),
    ("nemotron3-nano-s16k-1chip", "flash_fwd_roofline"):
        (2199157473280, 285212672, "nemotron_h_work.attn_fwd_call"),
    ("nemotron3-nano-s16k-1chip", "flash_bwd_roofline"):
        (5497893683200, 436207616, "nemotron_h_work.attn_bwd_call"),
    ("nemotron3-nano-s16k-1chip", "ssd_scan_roofline"):
        (670014898176, 10494148608, "nemotron_h_work.scan_step"),
    ("laguna-s8k-1chip", "window_attn_fwd_roofline"):
        (266304749568, 603979776, "window_work.flash_fwd_call"),
    ("laguna-s8k-1chip", "window_attn_bwd_roofline"):
        (665761873920, 939524096, "window_work.flash_bwd_call"),
    ("granite-h-s8k-1chip", "ssd_scan_roofline"):
        (523449139200, 4105175040, "ssd_work.scan_step"),
}
TRIANGLE = ("smallthinker_work.full_", "nemotron_h_work.attn_")


def _metric(name):
    with open(os.path.join(manifest.BENCH_DIR, "layer_metrics",
                           name + ".json")) as f:
        return json.load(f)


def _count(cell, entry):
    """One call (one step) as the entry's own reader would have it counted
    for ``cell``: the work function its file names, the device's share of the
    rows, a name path its selection takes."""
    args = _metric(entry)["args"]
    module, _, function = args["work"].rpartition(".")
    count = getattr({"flash_work": flash_work, "ssd_work": ssd_work}[module],
                    function)
    mesh = cell.traffic["mesh"]
    rows = cell.traffic["rows_per_step"] // (cell.chips // (
        mesh.get("tp", 1) * mesh.get("sp", 1) * mesh.get("pp", 1)))
    by_path = {} if module == "ssd_work" else {
        "path": WINDOW if entry.startswith("window") else WHOLE}
    return count(cell.config, cell.chips, rows, cell.traffic["seq"],
                 **by_path)


@pytest.mark.parametrize("cell,entry", sorted(OLD),
                         ids=lambda v: v if isinstance(v, str) else None)
def test_the_shared_function_counts_what_the_cells_own_counted(cell, entry):
    old_flops, old_bytes, was = OLD[cell, entry]
    got = _count(manifest.cell(cell), entry)
    assert got["bytes"] == old_bytes
    if was.startswith(TRIANGLE):
        seq = manifest.cell(cell).traffic["seq"]
        assert got["flops"] * (seq + 1) == old_flops * seq
    else:
        assert got["flops"] == old_flops


def test_every_cell_on_a_merged_list_is_held_here():
    """The merged entries' lists and ``OLD`` name the same (cell, entry)
    pairs, but those that no function counted before PR 71: the seven older
    cells' backward, Laguna's two whole-row layers and Phi-4-flash's sliding
    layer (``test_what_no_function_counted_before``)."""
    per_layer = {m["name"]: m for m in manifest.benchmark()["per_layer"]}
    listed = {(c, e) for e in ("flash_fwd_roofline", "flash_bwd_roofline",
                               "window_attn_fwd_roofline",
                               "window_attn_bwd_roofline",
                               "ssd_scan_roofline")
              for c in per_layer[e]["workloads"]}
    new = {(c, "flash_bwd_roofline") for c, e in OLD
           if OLD[c, e][2] == "flops.flash_fwd_call"} | {
        ("laguna-s8k-1chip", "flash_fwd_roofline"),
        ("laguna-s8k-1chip", "flash_bwd_roofline"),
        ("phi4-flash-s16k-1chip", "window_attn_fwd_roofline"),
        ("phi4-flash-s16k-1chip", "window_attn_bwd_roofline")}
    assert listed - new == set(OLD) and new <= listed


def test_what_no_function_counted_before():
    """Laguna's whole-row layers: 48 query heads over 8, 2 rows of 8,192.
    A call of Phi-4-flash's sliding layer: one softmax of the two, 20 query
    heads over 10, scores 64 wide over values 128 wide, under 512."""
    got = _count(manifest.cell("laguna-s8k-1chip"), "flash_fwd_roofline")
    assert got["flops"] == 2 * 2 * 2 * 48 * (8192 * 8192 // 2) * 128
    assert got["bytes"] == 2 * 2 * 8192 * 128 * (48 + 48 + 8 + 8)
    got = _count(manifest.cell("laguna-s8k-1chip"), "flash_bwd_roofline")
    assert got["flops"] == 5 * 2 * 2 * 48 * (8192 * 8192 // 2) * 128
    phi4, seq = manifest.cell("phi4-flash-s16k-1chip"), 16384
    band = 512 * 513 // 2 + (seq - 512) * 512
    got = _count(phi4, "window_attn_fwd_roofline")
    assert got["flops"] == 2 * 20 * band * (64 + 128)
    # q (20 x 64) in, the output (20 x 128) out, k (10 x 64), v (10 x 128) in
    assert got["bytes"] == 2 * seq * (20 * (64 + 128) + 10 * (64 + 128))
    got = _count(phi4, "window_attn_bwd_roofline")
    assert got["flops"] == 2 * 20 * band * (3 * 64 + 2 * 128)
    assert got["bytes"] == 2 * seq * (20 * (2 * 64 + 128)
                                       + 10 * 2 * (64 + 128))


def test_a_call_is_banded_by_its_own_scope():
    """SmallThinker and Laguna run whole-row and banded layers side by side:
    one function, the count by the call's path; Laguna's sliding layers have
    64 query heads where its whole-row layers have 48."""
    cell = manifest.cell("smallthinker-s16k-1chip")
    seq = cell.traffic["seq"]
    whole = flash_work.fwd_call(cell.config, 1, 1, seq, path=WHOLE)
    band = flash_work.fwd_call(cell.config, 1, 1, seq, path=WINDOW)
    assert whole["bytes"] == band["bytes"]
    assert band["flops"] / whole["flops"] == pytest.approx(
        flash_work.band_pairs(seq, 4096) / (seq * seq / 2))
    assert flash_work.fwd_call(cell.config, 1, 1, seq) == whole
    laguna = manifest.cell("laguna-s8k-1chip").config
    s = flops.shape(laguna, 1)
    assert (s["n_head"], s["window_n_head"], s["window"]) == (48, 64, 512)
    assert flash_work.bwd_call(laguna, 1, 2, 8192, path=WINDOW)["flops"] \
        == 5 * 2 * 2 * 64 * flash_work.band_pairs(8192, 512) * 128
    # a window as long as the row is the causal triangle, diagonal included
    assert flash_work.band_pairs(64, 64) == flash_work.band_pairs(64, 100) \
        == 64 * 65 // 2
    # a family that names no window has no banded call to count
    with pytest.raises(KeyError):
        flash_work.fwd_call(manifest.cell("mistral-s8k-1chip").config, 1, 1,
                            8192, path=WINDOW)


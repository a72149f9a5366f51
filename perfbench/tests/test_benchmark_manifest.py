"""``BENCHMARK.json`` against the files it names: pure JSON, no JAX and nothing
of ``perfbench`` imported, so that the file runs wherever it is put.  ISSUE 67
asked for it as ``tests/test_benchmark_manifest.py``, where the driver's
tier-1 floor would see the manifest; a ``benchmark`` PR may add no file
outside ``perfbench/``, so it stands here until a PR that may adds a
``tests/`` file of one line that imports these cases (``PERF.md`` section 7).
"""

import collections
import json
import os

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
CAP = 128       # the contract's most per-layer metrics
COPIES_GONE = (     # PR 67: each was letter for letter another entry's selection
    "relu2_experts_ms_per_step", "reglu_experts_ms_per_step",
    "relu2_shared_ms_per_step", "pre_router_ms_per_step",
    "mamba8g_scope_share_pct", "mamba8g_proj_ms_per_step",
    "ssd8g_scan_ms_per_step", "band4k_attn_ms_per_step",
    "attn_gate_rope_ms_per_step")
GONE = (            # PR 71: another entry's number, no number, or one of
                    # several entries over one selection of calls
    "bringup_gap_s", "tpu_client_s", "report_ms_p50", "input_wait_ms_p50",
    "mla_nope_attn_ms_per_step", "gqa16_attn_ms_per_step",
    "bd_noise_ms_per_step", "attn_gate_ms_per_step",
    "moe_onto_tokens_ms_per_step", "ssd8g_scan_roofline",
    "band4k_attn_fwd_roofline", "band4k_attn_bwd_roofline",
    "gqa7_full_attn_fwd_roofline", "gqa7_full_attn_bwd_roofline",
    "gqa16_attn_fwd_roofline", "gqa16_attn_bwd_roofline")
ROOM = 114      # PR 71's tree: at least fourteen places free


def _load(*parts):
    with open(os.path.join(ROOT, *parts)) as f:
        return json.load(f)


BENCH = _load("BENCHMARK.json")
METRICS = os.path.join(ROOT, "perfbench", "layer_metrics")
CELLS = [w["name"] for w in BENCH["workloads"]]


def _metric_file(name):
    return _load("perfbench", "layer_metrics", name + ".json")


def test_there_is_room_under_the_cap():
    """At most 128 per-layer entries — the contract's cap, which a later PR
    may fill — and what PRs 67 and 71 retired stays retired: a ``tracing`` or
    ``model_config`` PR may only add, and what it adds is entries."""
    assert len(BENCH["per_layer"]) <= CAP
    names = [m["name"] for m in BENCH["per_layer"] + BENCH["end_to_end"]]
    assert len(names) == len(set(names))
    assert not set(COPIES_GONE + GONE) & set(names)


def test_pr_71_left_fourteen_places():
    """PR 71's own tree: 113 entries.  A later PR that adds entries past 114
    moves ``ROOM`` with them (this file is the benchmark's, so that is a
    ``benchmark`` PR's line to change; ``CAP`` is the contract's)."""
    assert len(BENCH["per_layer"]) <= ROOM <= CAP


def test_one_selection_is_one_entry():
    """No two entries with the same ``reader`` and ``args``: a cell that
    enters an entry's scope goes on that entry's list, not into a copy."""
    by_selection = collections.defaultdict(list)
    for m in BENCH["per_layer"]:
        f = _metric_file(m["name"])
        by_selection[json.dumps([f["reader"], f.get("args", {})],
                                sort_keys=True)].append(m["name"])
    assert not [names for names in by_selection.values() if len(names) > 1]


def test_every_entry_has_its_file_and_every_file_its_entry():
    entries = {m["name"] for m in BENCH["per_layer"]}
    files = {n[:-len(".json")] for n in os.listdir(METRICS)
             if n.endswith(".json")}
    assert entries - files == set() and files - entries == set()
    readers = os.path.join(ROOT, "perfbench", "harness", "readers")
    for name in sorted(entries):
        f = _metric_file(name)
        assert set(f) <= {"reader", "args", "note"} and f["note"], name
        assert os.path.exists(os.path.join(readers, f["reader"] + ".py")), name


@pytest.mark.parametrize("metric", BENCH["per_layer"] + BENCH["end_to_end"],
                         ids=lambda m: m["name"])
def test_every_name_in_a_list_is_a_cell(metric):
    listed = metric.get("workloads")
    if listed is None:
        return
    assert listed and len(listed) == len(set(listed))
    assert set(listed) <= set(CELLS), set(listed) - set(CELLS)
    # in the cells' own order, so that a diff of a list is its new cells
    assert listed == [c for c in CELLS if c in listed]
    if metric in BENCH["per_layer"]:
        reported = {c for c in CELLS if any(
            e["name"] == metric["moves"] and c in e.get("workloads", CELLS)
            for e in BENCH["end_to_end"])}
        assert set(listed) <= reported      # each reports what it moves


@pytest.mark.parametrize("cell", BENCH["workloads"], ids=lambda w: w["name"])
def test_every_cells_configuration_and_traffic_files_exist(cell):
    config = next(c for c in BENCH["configs"] if c["name"] == cell["config"])
    assert config["file"].startswith("perfbench/configs/")
    assert _load(config["file"])
    traffic = _load("perfbench", "traffic", cell["traffic"] + ".json")
    kind = os.path.join(ROOT, "perfbench", "harness", "kinds",
                        traffic["kind"] + ".py")
    assert os.path.exists(kind), kind
    assert cell["chips"] in (1, 4)
    # every cell reports set-up, another end-to-end metric and a layer's
    mine = lambda ms: [m for m in ms  # noqa: E731
                       if cell["name"] in m.get("workloads", CELLS)]
    assert {"setup_s"} < {m["name"] for m in mine(BENCH["end_to_end"])}
    assert mine(BENCH["per_layer"])


def test_the_fallbacks_counter_lists_no_cell_where_it_cannot_read():
    """``kv_repeat_ms_per_step`` counts the copy of K and V to the query
    heads where the flash kernels have no grouped form.  Since PR 52 no such
    copy runs at a head width of 128, and the driver's notes said so of the
    two Mistral cells on every PR from 52 to 66."""
    entry = next((m for m in BENCH["per_layer"]
                  if m["name"] == "kv_repeat_ms_per_step"), None)
    if entry is not None:
        assert not [c for c in entry["workloads"] if c.startswith("mistral")]

"""BENCHMARK.json against the builder's contract as far as a test can hold it,
and every cell's files found by name."""

import copy
import importlib
import json
import os
import re

import pytest

from perfbench.harness import manifest

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}
BENCH = manifest.benchmark()


def _line(text, limit=200):
    return 1 <= len(text) <= limit and "\n" not in text and "\t" not in text


def test_keys_and_limits():
    assert set(BENCH) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    assert BENCH["command"][:2] == ["python3", "perfbench/run.py"]
    assert BENCH["paths"] == ["perfbench"]
    assert 1 <= BENCH["run_seconds"] <= 51
    assert os.path.getsize(os.path.join(manifest.ROOT, "BENCHMARK.json")) \
        <= 64 * 1024
    cells = BENCH["workloads"]
    assert 2 <= len(cells) <= 24
    four = [w for w in cells if w["chips"] == 4]
    assert len(four) <= max(1, len(cells) // 4)
    pairs = [(w["config"], w["traffic"]) for w in cells]
    assert len(set(pairs)) == len(pairs)
    for group in ("configs", "workloads", "end_to_end", "per_layer"):
        names = [e["name"] for e in BENCH[group]]
        assert len(set(names)) == len(names), group
        assert all(NAME.match(n) for n in names), group


def test_entries_have_just_their_keys():
    for c in BENCH["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert c["file"].startswith("perfbench/") and _line(c["source"])
        assert any(w["config"] == c["name"] for w in BENCH["workloads"])
    for w in BENCH["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert w["chips"] in (1, 4) and _line(w["why"])
        assert NAME.match(w["traffic"])
    for m in BENCH["end_to_end"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "bound",
                                          "source"}
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.1
    assert any(m["name"] == "setup_s" for m in BENCH["end_to_end"])
    layers = set()
    for m in BENCH["per_layer"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "source",
                                          "layer", "moves"}
        assert m["source"] in SOURCES and _line(m["layer"])
        assert m["moves"] in {e["name"] for e in BENCH["end_to_end"]}
        layers.add(m["layer"])
    for m in BENCH["end_to_end"] + BENCH["per_layer"]:
        assert UNIT.match(m["unit"]) and m["better"] in ("lower", "higher")
    with open(os.path.join(manifest.ROOT, "PERF.md")) as f:
        perf = f.read()
    for layer in layers:    # each layer is a row of PERF.md's list of layers
        assert f"| {layer} |" in perf, layer


def test_configurations_cut_no_width():
    widths = re.compile(r"(hidden|intermediate|latent|state|proj).*size|"
                        r"_dim$|_rank$|head|n_embd|experts_per_tok")
    for c in BENCH["configs"]:
        with open(os.path.join(manifest.ROOT, c["file"])) as f:
            config = json.load(f)
        assert config["reduced"] == c["reduced"]
        assert config["source"] == c["source"]
        assert not [k for k in c["reduced"] if widths.search(k)]
        for cut in config.get("cut_by_chips", {}).values():
            assert set(cut) <= set(c["reduced"])


def _finds_its_files(cell):
    kind = importlib.import_module(
        f"perfbench.harness.kinds.{cell.traffic['kind']}")
    for part in ("run", "end_to_end", "verdict", "detail"):
        assert callable(getattr(kind, part)), part
    family = importlib.import_module(
        f"perfbench.harness.families.{cell.config['family']}")
    for part in ("shape", "model_config", "logits"):
        assert callable(getattr(family, part)), part
    if cell.traffic["kind"] == "train_loop":
        # the traced sub-window opens and closes on an idle device
        every, trace = cell.traffic["report_every"], cell.traffic["trace"]
        assert trace["from_step"] % every == 0 and trace["steps"] % every == 0
    reported = {m["name"] for m in cell.end_to_end}
    assert "setup_s" in reported and len(reported) >= 2
    assert cell.per_layer
    for m in cell.per_layer:
        # the metric's file holds its reader and arguments and nothing that
        # BENCHMARK.json says
        assert set(m["file"]) <= {"reader", "args", "note"}
        reader = importlib.import_module(
            f"perfbench.harness.readers.{m['file']['reader']}")
        assert callable(reader.read)


@pytest.mark.parametrize("name", [w["name"] for w in BENCH["workloads"]])
def test_every_cell_finds_its_files(name):
    _finds_its_files(manifest.cell(name))


def test_a_later_cell_is_one_workloads_entry(monkeypatch):
    """A cell over a configuration and a traffic file that exist is added by
    one entry in a copy of BENCHMARK.json; no file under ``perfbench/`` is
    touched, and it reports every metric that is not kept to named cells."""
    bench = copy.deepcopy(BENCH)
    bench["workloads"].append({
        "name": "throw-away", "config": "gpt2-small", "traffic": "s8k-b1-gen",
        "chips": 1, "why": "a test"})
    monkeypatch.setattr(manifest, "benchmark", lambda: bench)
    cell = manifest.cell("throw-away")
    _finds_its_files(cell)
    assert cell.config["name"] == "gpt2-small" and cell.traffic["seq"] == 8192
    assert [m["name"] for m in cell.per_layer] == [
        m["name"] for m in BENCH["per_layer"] if "workloads" not in m]
    with pytest.raises(KeyError):
        manifest.cell("not-a-cell")


def test_peaks_name_their_source():
    for kind, row in manifest.peaks().items():
        assert row["bf16_flops_per_s"] > 0 and row["hbm_bytes_per_s"] > 0
        assert row["source"], kind

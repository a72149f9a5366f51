"""What a cell's test holds of ``BENCHMARK.json``'s per-layer lists: entries
are looked up by name, and the cell is on *at least* these — a later PR may
list it under more and append entries after them (PR 67: the tests that
pinned "exactly these lists" or "the list's last entries" went red with the
next PR that appended)."""


def on_at_least(bench, cell, names):
    entries = {m["name"]: m for m in bench["per_layer"]}
    assert set(names) <= set(entries), set(names) - set(entries)
    off = [n for n in names
           if cell not in entries[n].get("workloads", [cell])]
    assert not off, f"{cell} is not on the lists of {off}"
    return [entries[n] for n in names]

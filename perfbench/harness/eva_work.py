"""Operations and bytes of EVA attention, counted from shapes:
``kernel_roofline``'s ``work`` for ``eva_attn_fwd_roofline`` and
``eva_attn_bwd_roofline``, ``scope_roofline``'s for ``eva_pool_roofline``.

A query sees the positions of its own aligned window up to itself and one
summary for every chunk of the windows before
(``families/evabyte.py::live_pairs``): 1,472.5 keys a query at 16,384
positions, windows of 2,048 and chunks of 16, where the causal triangle has
8,192.5.  Heads are not grouped: keys and values are at the query heads."""

from __future__ import annotations

from typing import Any, Dict

from perfbench.harness.families.evabyte import head_dim, live_pairs


def _sizes(config: Dict[str, Any], rows: int, seq: int):
    """(heads, head width, live pairs of all heads, elements of one
    position-sized operand, of one summaries-sized operand)."""
    heads, hd = config["num_attention_heads"], head_dim(config)
    window, chunk = config["window_size"], config["chunk_size"]
    # the summaries some query sees: those of every window but the last
    seen = (seq - 1) // window * (window // chunk)
    return (heads, hd,
            rows * heads * live_pairs(seq, window, chunk),
            rows * seq * heads * hd, rows * seen * heads * hd)


def flash_fwd_call(config: Dict[str, Any], chips: int, rows: int,
                   seq: int) -> Dict[str, float]:
    """One layer's attention forward on ``rows`` rows (one device's share):
    QK^T and PV over the live pairs; q, k, v in and the output out, the
    summaries' keys and values in, bf16, once each."""
    _, hd, pairs, position_sized, summary_sized = _sizes(config, rows, seq)
    return {"flops": 2.0 * 2 * pairs * hd,
            "bytes": 2.0 * (4 * position_sized + 2 * summary_sized)}


def flash_bwd_call(config: Dict[str, Any], chips: int, rows: int,
                   seq: int) -> Dict[str, float]:
    """One layer's attention backward: the five matmuls of a flash backward
    (S, dP, dV, dK, dQ) over the live pairs; q, k, v, dO in and dQ, dK, dV
    out, the summaries' keys and values in and their gradients out."""
    _, hd, pairs, position_sized, summary_sized = _sizes(config, rows, seq)
    return {"flops": 2.0 * 5 * pairs * hd,
            "bytes": 2.0 * (7 * position_sized + 4 * summary_sized)}


def pool_step(config: Dict[str, Any], chips: int, rows: int,
              seq: int) -> Dict[str, float]:
    """The pooling that one training step on ``rows`` rows runs under the
    scope ``attn/pool``, every layer of the cut: what the program does there
    and what ``eva_pool_roofline`` therefore times.  The whole row is pooled,
    its last window too (no query sees those summaries; a slice of k and v
    cost more than the eighth it spared), and a rematerialised block pools
    again in the backward pass, so a layer is two forwards — k and v read,
    the summaries written — and one backward: k, v and the summaries'
    cotangents read, k's and v's cotangents written; bf16.  The
    multiply-adds (a dot with ``phi`` and two weighted sums, 6 a value, a
    pass) are far under the bytes' time; they are counted all the same."""
    from perfbench.harness.families import published

    layers = published(config, chips, "num_hidden_layers")
    _, _, _, position_sized, _ = _sizes(config, rows, seq)
    summary_sized = position_sized // config["chunk_size"]
    return {"flops": 3.0 * layers * 6 * position_sized,
            "bytes": layers * 2.0 * (
                2 * (2 * position_sized + 2 * summary_sized)
                + (4 * position_sized + 2 * summary_sized))}

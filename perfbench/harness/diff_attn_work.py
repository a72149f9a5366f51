"""Operations and bytes of the attention kernels under differential
attention over the whole row, counted from shapes: ``kernel_roofline``'s
``work`` for ``diff_attn_fwd_roofline`` and ``diff_attn_bwd_roofline``.

A layer makes two calls, one a softmax: each takes half the query heads (20
of 64) against half the key heads (10 of 64, a key head to two query heads)
over the 10 pairs of value heads side by side (128 wide).  A program that
hands a kernel K or V repeated to the query heads, or anything padded to a
common width, moves more; that is not counted."""

from __future__ import annotations

from typing import Any, Dict

from perfbench.harness.families import phi4_flash


def _widths(config: Dict[str, Any]):
    s = phi4_flash.sizes(config)
    return s["h"] // 2, s["kv"] // 2, s["hd"], 2 * s["hd"]


def flash_fwd_call(config: Dict[str, Any], chips: int, rows: int,
                   seq: int) -> Dict[str, float]:
    """One call's forward on ``rows`` rows (one device's share): q.k at 64
    and p.v at 128 over the causal triangle, 20 heads; bf16: q (20 x 64) in,
    the output (20 x 128) out, k (10 x 64) and v (10 x 128) in."""
    heads, kv, scores, values = _widths(config)
    return {"flops": 2.0 * rows * heads * (scores + values)
            * phi4_flash.live_pairs(seq),
            "bytes": 2.0 * rows * seq * (heads * (scores + values)
                                         + kv * (scores + values))}


def flash_bwd_call(config: Dict[str, Any], chips: int, rows: int,
                   seq: int) -> Dict[str, float]:
    """One call's backward: the five matmuls of a flash backward over the
    causal triangle, S, dK and dQ at 64, dP and dV at 128; q, dO and the
    output in and dQ out at the query heads, k and v in and their gradients
    out at the key/value heads."""
    heads, kv, scores, values = _widths(config)
    return {"flops": 2.0 * rows * heads * (3 * scores + 2 * values)
            * phi4_flash.live_pairs(seq),
            "bytes": 2.0 * rows * seq * (
                heads * (2 * scores + 2 * values)
                + kv * (2 * scores + 2 * values))}

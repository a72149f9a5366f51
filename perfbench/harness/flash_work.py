"""Operations and bytes of the causal flash kernels, whole-row or under a
band, counted from shapes: ``kernel_roofline``'s ``work`` for
``flash_fwd_roofline`` / ``flash_bwd_roofline`` (a whole-row call) and
``window_attn_fwd_roofline`` / ``window_attn_bwd_roofline`` (a call under a
window).  One count for every configuration: the sizes come from the family's
``shape`` (``flops.shape``) — ``n_head`` query heads over ``n_kv_head``
key/value heads, scores ``head_dim`` wide over values ``v_head_dim`` wide (the
same where the family names one width), and for a banded layer ``window`` and
the band layers' own heads a call, ``window_n_head`` / ``window_n_kv_head``,
where they differ (Laguna's sliding layers have more query heads; a call of
Phi-4's differential attention takes half of each).

Which call is banded is the call's own scope: ``ops/attention.py`` opens
``window`` around a ``sliding_attention`` layer's kernel call, so ``/window/``
stands in the name path of both its kernels and of nothing else.  A query of
such a layer sees itself and the ``window - 1`` positions before it, ``sum_i
min(i + 1, window)`` live pairs a row (``band_pairs``); a whole-row call is
charged half the square, ``seq ** 2 / 2`` pairs, as ``flops.py`` charges the
scores of ``mfu_pct``.  Q, O, dO and dQ are at the query heads, K, V, dK and
dV at the key/value heads, bf16: what a program that copies K and V to the
query heads, or sums a gradient a query head beside the kernel, moves more is
not counted."""

from __future__ import annotations

from typing import Any, Dict

from perfbench.harness import flops

BANDED = "/window/"


def band_pairs(seq: int, window: int) -> int:
    """Live (query, key) pairs of one row under the window: ``sum_i min(i +
    1, window)``, the first ``window`` queries' triangle and ``window`` keys
    for each query after them."""
    w = min(window, seq)
    return w * (w + 1) // 2 + (seq - w) * w


def _call(config: Dict[str, Any], chips: int, rows: int, seq: int, path: str,
          scores_at: int, values_at: int, q_wide: int, v_wide: int,
          k_sized: int) -> Dict[str, float]:
    """``scores_at`` / ``values_at``: the matmuls as wide as the scores / the
    values; ``q_wide`` / ``v_wide``: the arrays at the query heads of either
    width; ``k_sized``: the arrays of each width at the key/value heads."""
    s = flops.shape(config, chips)
    banded = BANDED in path
    heads, kv = s["n_head"], s["n_kv_head"]
    if banded:
        heads = s.get("window_n_head", heads)
        kv = s.get("window_n_kv_head", kv)
    scores = s["head_dim"]
    values = s.get("v_head_dim", scores)
    pairs = band_pairs(seq, s["window"]) if banded else seq * seq / 2
    return {"flops": 2.0 * rows * heads * pairs
            * (scores_at * scores + values_at * values),
            "bytes": 2.0 * rows * seq * (
                heads * (q_wide * scores + v_wide * values)
                + kv * k_sized * (scores + values))}


def fwd_call(config: Dict[str, Any], chips: int, rows: int, seq: int,
             path: str = "") -> Dict[str, float]:
    """One layer's attention forward on ``rows`` rows (one device's share):
    QK^T and PV over the live pairs; Q in and O out at the query heads, K and
    V in at the key/value heads."""
    return _call(config, chips, rows, seq, path, 1, 1, 1, 1, 1)


def bwd_call(config: Dict[str, Any], chips: int, rows: int, seq: int,
             path: str = "") -> Dict[str, float]:
    """One layer's attention backward: the five matmuls of a flash backward
    over the live pairs, S, dK and dQ as wide as the scores, dP and dV as the
    values; Q and dO in and dQ out, K and V in and dK and dV out."""
    return _call(config, chips, rows, seq, path, 3, 2, 2, 1, 2)

"""Operations and bytes of the attention kernels under a window, counted from
shapes: ``kernel_roofline``'s ``work`` for ``window_attn_fwd_roofline`` and
``window_attn_bwd_roofline``.

A query of a ``sliding_attention`` layer sees itself and the ``sliding_window
- 1`` positions before it: ``sum_i min(i + 1, window)`` of a row's ``seq **
2`` pairs are live (``families/laguna.py::band_pairs``), 496 a query at 8,192
and 512 where the causal triangle has 4,096.  The heads are the sliding
layers' own count."""

from __future__ import annotations

from typing import Any, Dict

from perfbench.harness.families.laguna import band_pairs


def _call(config: Dict[str, Any], rows: int, seq: int, matmuls: int,
          q_sized: int, k_sized: int) -> Dict[str, float]:
    heads = config["num_attention_heads_per_layer"][
        config["layer_types"].index("sliding_attention")]
    hd, kv = config["head_dim"], config["num_key_value_heads"]
    pairs = rows * heads * band_pairs(seq, config["sliding_window"])
    per_head = rows * seq * hd
    return {"flops": 2.0 * matmuls * pairs * hd,
            "bytes": 2.0 * per_head * (q_sized * heads + k_sized * kv)}


def flash_fwd_call(config: Dict[str, Any], chips: int, rows: int,
                   seq: int) -> Dict[str, float]:
    """One sliding layer's attention forward on ``rows`` rows (one device's
    share): QK^T and PV over the band's live pairs; Q in and O out at the
    query heads, K and V in at the key/value heads, bf16.  A program that
    hands the kernel K and V repeated to the query heads moves more; that is
    not counted."""
    return _call(config, rows, seq, 2, 2, 2)


def flash_bwd_call(config: Dict[str, Any], chips: int, rows: int,
                   seq: int) -> Dict[str, float]:
    """One sliding layer's attention backward: the five matmuls of a flash
    backward (S, dP, dV, dK, dQ) over the live pairs; Q, dO in and dQ out, K,
    V in and dK, dV out."""
    return _call(config, rows, seq, 5, 3, 4)

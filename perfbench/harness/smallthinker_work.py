"""Operations and bytes of SmallThinker's attention kernels, counted from the
configuration file's own keys and from shapes alone: ``kernel_roofline``'s
``work`` for ``band4k_attn_*_roofline`` (a layer under the window) and
``gqa7_full_attn_*_roofline`` (a whole-row layer).

A query of a window layer sees itself and the ``sliding_window_size - 1``
positions before it: ``sum_i min(i + 1, window)`` of a row's ``seq ** 2``
pairs are live (``families/smallthinker.py::band_pairs``), 3,584 a query on
average at 16,384 under 4,096; a whole-row layer's are the causal triangle,
``seq * (seq + 1) / 2``.  Q, O, dO and dQ are at the 28 query heads, K, V, dK
and dV at the 4 key/value heads: what a program that copies K and V to the
query heads, or sums a gradient a query head beside the kernel, moves more is
not counted."""

from __future__ import annotations

from typing import Any, Dict

from perfbench.harness.families.smallthinker import band_pairs


def _call(config: Dict[str, Any], rows: int, seq: int, windowed: bool,
          matmuls: int, q_sized: int, k_sized: int) -> Dict[str, float]:
    heads, kv = config["num_attention_heads"], config["num_key_value_heads"]
    hd = config["head_dim"]
    pairs = band_pairs(seq, config["sliding_window_size"]) if windowed \
        else seq * (seq + 1) // 2
    per_head = rows * seq * hd
    return {"flops": 2.0 * matmuls * rows * heads * pairs * hd,
            "bytes": 2.0 * per_head * (q_sized * heads + k_sized * kv)}


def window_fwd_call(config: Dict[str, Any], chips: int, rows: int,
                    seq: int) -> Dict[str, float]:
    """One window layer's attention forward on ``rows`` rows (one device's
    share): QK^T and PV over the band's live pairs; Q in and O out at the
    query heads, K and V in at the key/value heads, bf16."""
    return _call(config, rows, seq, True, 2, 2, 2)


def window_bwd_call(config: Dict[str, Any], chips: int, rows: int,
                    seq: int) -> Dict[str, float]:
    """One window layer's attention backward: the five matmuls of a flash
    backward (S, dP, dV, dK, dQ) over the live pairs; Q, dO in and dQ out, K,
    V in and dK, dV out."""
    return _call(config, rows, seq, True, 5, 3, 4)


def full_fwd_call(config: Dict[str, Any], chips: int, rows: int,
                  seq: int) -> Dict[str, float]:
    """One whole-row layer's attention forward: the same over the causal
    triangle."""
    return _call(config, rows, seq, False, 2, 2, 2)


def full_bwd_call(config: Dict[str, Any], chips: int, rows: int,
                  seq: int) -> Dict[str, float]:
    """One whole-row layer's attention backward over the causal triangle."""
    return _call(config, rows, seq, False, 5, 3, 4)

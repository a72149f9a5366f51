"""Operations and bytes of the attention kernels under the block-diffusion
mask, counted from shapes: ``kernel_roofline``'s ``work`` for
``bd_attn_fwd_roofline`` and ``bd_attn_bwd_roofline``.

A row of ``seq`` data tokens is run as a noised and a clean copy, ``2 seq``
positions; of their ``4 seq**2`` pairs ``seq**2 + seq * block`` are live (a
noised block sees itself and the clean blocks before it, a clean block the
clean blocks up to itself)."""

from __future__ import annotations

from typing import Any, Dict

from perfbench.harness import flops


def _call(config: Dict[str, Any], chips: int, rows: int, seq: int,
          matmuls: int, q_sized: int, k_sized: int) -> Dict[str, float]:
    s = flops.shape(config, chips)
    pairs = rows * s["n_head"] * (seq * seq + seq * s["block"])
    per_head = rows * 2 * seq * s["head_dim"]       # both copies
    return {"flops": 2.0 * matmuls * pairs * s["head_dim"],
            "bytes": 2.0 * per_head * (q_sized * s["n_head"]
                                       + k_sized * s["n_kv_head"])}


def flash_fwd_call(config: Dict[str, Any], chips: int, rows: int,
                   seq: int) -> Dict[str, float]:
    """One layer's attention forward on ``rows`` rows (one device's share):
    QK^T and PV over the live pairs; Q in and O out at both copies' positions
    and the query heads, K and V in at both copies' positions and the
    key/value heads, bf16.  The program takes the noised blocks' own squares
    (``seq * block`` of the pairs, 0.1% at 4096 and 4) outside the kernel;
    they are counted here, and their time is not in the calls'."""
    return _call(config, chips, rows, seq, 2, 2, 2)


def flash_bwd_call(config: Dict[str, Any], chips: int, rows: int,
                   seq: int) -> Dict[str, float]:
    """One layer's attention backward: the five matmuls of a flash backward
    (S, dP, dV, dK, dQ) over the live pairs; Q, dO in and dQ out, K, V in and
    dK, dV out."""
    return _call(config, chips, rows, seq, 5, 3, 4)

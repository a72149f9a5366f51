"""Operations and bytes of the attention kernels under latent attention
(MLA), counted from shapes: ``kernel_roofline``'s ``work`` for
``mla_attn_fwd_roofline`` and ``mla_attn_bwd_roofline``.

A head's scores are ``qk_nope_head_dim + qk_rope_head_dim`` wide (128 + 64)
and its values ``v_head_dim`` (128).  A head's key is ``[kn ; kr]``: ``kn``
its own 128, ``kr`` the 64 rotary dimensions that are one vector a position
for all heads, counted once and not a head.  A program that hands the kernel
``kr`` repeated to the heads, or anything padded to a common width, moves
more; that is not counted."""

from __future__ import annotations

from typing import Any, Dict


def _widths(config: Dict[str, Any]):
    return (config["num_attention_heads"], config["qk_nope_head_dim"],
            config["qk_nope_head_dim"] + config["qk_rope_head_dim"],
            config["qk_rope_head_dim"], config["v_head_dim"])


def flash_fwd_call(config: Dict[str, Any], chips: int, rows: int,
                   seq: int) -> Dict[str, float]:
    """One layer's attention forward on ``rows`` rows (one device's share):
    q.k at 192 and p.v at 128 over half the square; bf16: q (16 x 192) in,
    the output (16 x 128) out, kn and v (16 x 128 each) in, kr (64) once a
    position."""
    heads, kn, scores, kr, values = _widths(config)
    return {"flops": 2.0 * rows * heads * (scores + values) * seq * seq / 2,
            "bytes": 2.0 * rows * seq * (
                heads * (scores + values + kn + values) + kr)}


def flash_bwd_call(config: Dict[str, Any], chips: int, rows: int,
                   seq: int) -> Dict[str, float]:
    """One layer's attention backward: the five matmuls of a flash backward
    over half the square, S, dK and dQ at 192, dP and dV at 128; q, dO and
    the output in and dQ out at the heads, kn and v in and their gradients
    out at the heads, kr in and its gradient out once a position."""
    heads, kn, scores, kr, values = _widths(config)
    return {"flops": 2.0 * rows * heads * (3 * scores + 2 * values)
            * seq * seq / 2,
            "bytes": 2.0 * rows * seq * (
                heads * (2 * scores + 2 * values + 2 * kn + 2 * values)
                + 2 * kr)}

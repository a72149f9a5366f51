"""Operations and bytes of Kimi Delta Attention's scan, counted from shapes:
``scope_roofline``'s ``work`` for ``kda_scan_roofline``."""

from __future__ import annotations

from typing import Any, Dict

from perfbench.harness.families import kimi_linear


def scan_step(config: Dict[str, Any], chips: int, rows: int,
              seq: int) -> Dict[str, float]:
    """The scans of one training step on ``rows`` sequences (one device's
    share): every ``kda`` layer of the cut, forward and backward, the
    backward at twice the forward as everywhere in ``flops.py``; the
    recomputation under remat is not counted.

    FLOPs a token a layer forward: ``kimi_linear.scan_flops_per_token`` (the
    chunked form's dense matmuls: ``L`` and ``A``, the solve counted as one
    ``C x C`` by ``C x (dk + dv)`` product, ``A U``, ``W S_0``, ``q S_0`` and
    the state's update).  Bytes a layer forward: ``q``, ``k``, ``v`` in and
    ``o`` out in bf16, the log-decays ``g`` and the write strengths ``b`` in
    float32, plus the float32 state at each chunk's end (heads x dk x dv),
    written once and read once.  ``L``, ``A``, the running sums and every
    other intermediate are the implementation's: a scan that writes them out
    moves more, and that is not counted — the same work whether XLA or
    Mosaic does it."""
    linear = config["linear_attn_config"]
    heads, d = linear["num_heads"], linear["head_dim"]
    layers = sum(1 for kind in kimi_linear.layer_kinds(config)
                 if kind == "kda")
    tokens = rows * seq
    chunks = rows * -(-seq // config["kda_chunk"])
    forward_bytes = (tokens * heads * (4 * 2.0 * d + 4.0 * d + 4.0)
                     + 2 * 4.0 * chunks * heads * d * d)
    return {"flops": 3.0 * layers * tokens
            * kimi_linear.scan_flops_per_token(config),
            "bytes": 3.0 * layers * forward_bytes}

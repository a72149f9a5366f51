"""Operations and bytes of the Mamba-2 scan, counted from shapes:
``scope_roofline``'s ``work`` for ``ssd_scan_roofline``."""

from __future__ import annotations

from typing import Any, Dict

from perfbench.harness.families import granite_hybrid, published


def scan_step(config: Dict[str, Any], chips: int, rows: int,
              seq: int) -> Dict[str, float]:
    """The scans of one training step on ``rows`` sequences (one device's
    share): every ``mamba`` layer of the cut, forward and backward, the
    backward at twice the forward as everywhere in ``flops.py``; the
    recomputation under remat is not counted.

    FLOPs a token a layer forward: ``granite_hybrid.scan_flops_per_token``
    (the chunked algorithm's matmuls: ``C B^T`` once a group, the masked
    product with ``X``, the chunk's state and its read-out).  Bytes a layer
    forward: ``X``, ``B``, ``C`` and ``dt`` in and ``y`` out, bf16, plus the
    float32 state at each chunk's end (heads x d_head x d_state), written
    once and read once.  The decay masks, the scores and every other
    intermediate are the implementation's: a scan that writes them out moves
    more, and that is not counted."""
    heads, p = config["mamba_n_heads"], config["mamba_d_head"]
    groups, n = config["mamba_n_groups"], config["mamba_d_state"]
    layers = sum(1 for kind in published(config, chips, "layer_types")
                 if kind == "mamba")
    tokens = rows * seq
    chunks = rows * -(-seq // config["mamba_chunk_size"])
    forward_bytes = (2.0 * tokens * (2 * heads * p + 2 * groups * n + heads)
                     + 2 * 4.0 * chunks * heads * p * n)
    return {"flops": 3.0 * layers * tokens
            * granite_hybrid.scan_flops_per_token(config),
            "bytes": 3.0 * layers * forward_bytes}

"""Operations and bytes of Gated DeltaNet's scan, counted from shapes:
``scope_roofline``'s ``work`` for ``gdn_scan_roofline``."""

from __future__ import annotations

from typing import Any, Dict

from perfbench.harness.families import qwen3_next


def scan_step(config: Dict[str, Any], chips: int, rows: int,
              seq: int) -> Dict[str, float]:
    """The scans of one training step on ``rows`` sequences (one device's
    share): every ``gdn`` layer of the cut, forward and backward, the
    backward at twice the forward as everywhere in ``flops.py``; the
    recomputation under remat is not counted.

    FLOPs a token a layer forward: ``qwen3_next.scan_flops_per_token`` (the
    chunked form's dense matmuls: ``K K^T`` and ``Q K^T`` a KEY head; the
    solve counted as one ``C x C`` by ``C x dv`` product, ``A U``, ``K S_0``,
    ``Q S_0`` and the state's update a VALUE head).  Bytes a layer forward:
    ``q`` and ``k`` at the key heads, ``v`` in and ``o`` out at the value
    heads, bf16; one log-decay ``g`` and one write strength ``beta`` a value
    head a position, float32; plus the float32 state at each chunk's end
    (value heads x dk x dv), written once and read once.  ``K K^T``, the
    masks of exponents, the inverses and every other intermediate are the
    implementation's: a scan that writes them out, broadcasts ``g`` to a
    head's channels or repeats ``q`` and ``k`` to the value heads moves more,
    and that is not counted — the same work whatever implements it."""
    keys, heads, d = qwen3_next.gdn_sizes(config)
    layers = sum(1 for kind in qwen3_next.layer_kinds(config)
                 if kind == "gdn")
    tokens = rows * seq
    chunks = rows * -(-seq // config["gdn_chunk"])
    forward_bytes = (tokens * (2 * 2.0 * keys * d + 2 * 2.0 * heads * d
                               + 2 * 4.0 * heads)
                     + 2 * 4.0 * chunks * heads * d * d)
    return {"flops": 3.0 * layers * tokens
            * qwen3_next.scan_flops_per_token(config),
            "bytes": 3.0 * layers * forward_bytes}

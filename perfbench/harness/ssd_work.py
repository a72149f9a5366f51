"""Operations and bytes of the Mamba-2 scan, counted from shapes:
``scope_roofline``'s ``work`` for ``ssd_scan_roofline``.  One count for every
configuration: the sizes come from the family's ``shape`` (``flops.shape``) —
``ssd_heads`` heads ``ssd_head_dim`` wide, ``ssd_groups`` groups of B and C
``ssd_state`` wide, chunks of ``ssd_chunk`` positions, ``ssd_layers`` Mamba-2
layers in the cut."""

from __future__ import annotations

from typing import Any, Dict

from perfbench.harness import flops


def scan_flops_per_token(heads: int, p: int, groups: int, n: int,
                         q: int) -> int:
    """One layer's recurrence, forward, one token, as the chunked algorithm's
    matmuls: ``2 Q N`` a group for ``C B^T`` inside a chunk of ``Q``
    positions, ``2 Q P`` a head for the masked product with ``X``, ``2 N P``
    a head each for the chunk's state and its read-out."""
    return groups * 2 * q * n + heads * (2 * q * p + 2 * 2 * n * p)


def scan_step(config: Dict[str, Any], chips: int, rows: int,
              seq: int) -> Dict[str, float]:
    """The scans of one training step on ``rows`` sequences (one device's
    share): every Mamba-2 layer of the cut, forward and backward, the
    backward at twice the forward as everywhere in ``flops.py``; the
    recomputation under remat is not counted.  Bytes a layer forward: ``X``,
    ``B``, ``C`` and ``dt`` in and ``y`` out, bf16, plus the float32 state at
    each chunk's end (heads x head width x state), written once and read
    once.  The decay masks, the scores and every other intermediate are the
    implementation's: a scan that writes them out moves more, and that is
    not counted."""
    s = flops.shape(config, chips)
    heads, p, groups, n, q, layers = (s["ssd_" + key] for key in (
        "heads", "head_dim", "groups", "state", "chunk", "layers"))
    tokens = rows * seq
    chunks = rows * -(-seq // q)
    forward_bytes = (2.0 * tokens * (2 * heads * p + 2 * groups * n + heads)
                     + 2 * 4.0 * chunks * heads * p * n)
    return {"flops": 3.0 * layers * tokens
            * scan_flops_per_token(heads, p, groups, n, q),
            "bytes": 3.0 * layers * forward_bytes}

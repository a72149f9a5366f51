"""Operations and bytes of Mamba-1's selective scan, counted from shapes:
``scope_roofline``'s ``work`` for ``selective_scan_roofline``."""

from __future__ import annotations

from typing import Any, Dict

from perfbench.harness.families import phi4_flash


def scan_layer_forward(config: Dict[str, Any], rows: int,
                       seq: int) -> Dict[str, float]:
    """One ``mamba1`` layer's scan, forward, on ``rows`` sequences.
    Operations: ``phi4_flash.scan_ops_per_token`` (7 a state cell, 3 a
    channel), elementwise, none of them a matmul's.  Bytes: ``u`` in and ``y``
    out in bf16 and the step sizes in float32 a channel, ``B`` and ``C`` in
    bf16 a state, plus the float32 state at each block's end (channels x
    states), written once and read once."""
    s = phi4_flash.sizes(config)
    tokens = rows * seq
    blocks = rows * -(-seq // config["scan_block"])
    return {"flops": float(tokens * phi4_flash.scan_ops_per_token(config)),
            "bytes": tokens * (s["d"] * (2.0 + 2.0 + 4.0) + 2 * 2.0 * s["n"])
            + 2 * 4.0 * blocks * s["d"] * s["n"]}


def scan_step(config: Dict[str, Any], chips: int, rows: int,
              seq: int) -> Dict[str, float]:
    """The scans of one training step on ``rows`` sequences (one device's
    share): every ``mamba1`` layer of the cut, forward and backward, the
    backward at twice the forward as everywhere in ``flops.py``; the
    recomputation under remat is not counted.  The decays, the written
    values and every other intermediate are the implementation's: a scan
    that writes them out moves more, and that is not counted — the same work
    whether XLA or Mosaic does it.  ``flops.roofline_seconds`` holds the
    operations against the bf16 matmul peak, which no elementwise operation
    reaches: the bytes bind by far, and the share reads low by nature
    (``PERF.md`` section 5 says what the vector units bound it to)."""
    layers = sum(1 for kind in phi4_flash.layer_kinds(config)
                 if kind == "mamba1")
    forward = scan_layer_forward(config, rows, seq)
    return {k: 3.0 * layers * v for k, v in forward.items()}

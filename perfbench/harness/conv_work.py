"""Operations and bytes of the gated short-convolution mixer, counted from
shapes: ``scope_roofline``'s ``work`` for ``shortconv_mixer_roofline``."""

from __future__ import annotations

from typing import Any, Dict


def mixer_step(config: Dict[str, Any], chips: int, rows: int,
               seq: int) -> Dict[str, float]:
    """The ``conv`` mixers of one training step on ``rows`` sequences (one
    device's share): every ``conv`` layer of the cut, forward and backward,
    the backward at twice the forward as everywhere in ``flops.py``; the
    recomputation under remat is not counted.

    FLOPs a token a layer forward: the two projections, ``hidden -> 3 x
    hidden`` and ``hidden -> hidden``; the depthwise convolution and the two
    gates are a handful of multiply-adds a channel and no matmul.  Bytes a
    layer: the pass between the projections, bf16: ``B``, ``C`` and ``u`` in
    and ``C * m`` out forward, those three and the result's cotangent in and
    three cotangents out backward.  A program that writes ``B * u`` or ``m``
    out, or pads a copy, moves more; that is not counted."""
    d = config["hidden_size"]
    layers = sum(1 for kind in config["layer_types"] if kind == "conv")
    tokens = rows * seq
    return {"flops": 3.0 * layers * tokens * 2 * (d * 3 * d + d * d),
            "bytes": layers * 2.0 * tokens * d * (4 + 7)}

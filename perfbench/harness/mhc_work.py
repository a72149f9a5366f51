"""Operations and bytes of the hyper-connections around a stack's sub-layers
(mHC: a residual path ``hc_mult`` streams wide), counted from the
configuration file's own keys — ``hc_mult``, ``hidden_size``,
``num_hidden_layers``, ``hc_sinkhorn_iters``, ``stream_dtype`` — and from
shapes alone: ``scope_roofline``'s ``work`` for ``mhc_stream_roofline``.  Kept
with the benchmark so that it reads the same work whatever implements it, XLA
fusions or a kernel.

A sub-layer, a position, a direction moves at the least ``(3 n + 2) C``
values of the stream's dtype: the ``n`` streams read once for the
coefficients and the mix ``H_pre X`` and the mix written (``n + 1``), then the
``n`` streams and the branch's output read and the ``n`` streams written
(``2 n + 1``).  The coefficients themselves (``2 n + n^2`` float32 values a
position) and the parameters are not counted: under 1% of the stream's bytes.
A program that reads the streams once for the norm's statistic and again for
the projection, or writes an intermediate in float32, moves more; that is not
counted."""

from __future__ import annotations

from typing import Any, Dict

BYTES = {"bfloat16": 2, "float32": 4}


def values_per_position(config: Dict[str, Any]) -> int:
    """``(3 n + 2) C``: one sub-layer, one position, one direction."""
    return (3 * config["hc_mult"] + 2) * config["hidden_size"]


def flops_per_position(config: Dict[str, Any]) -> int:
    """One sub-layer's forward at a position: the projection of ``n C`` values
    to ``2 n + n^2`` coefficients, the two mixes, and the Sinkhorn's divisions
    and sums (two of each an entry an iteration)."""
    n, c = config["hc_mult"], config["hidden_size"]
    return (2 * (n * c * (2 * n + n * n) + n * c + (n * n + n) * c)
            + 4 * n * n * config["hc_sinkhorn_iters"])


def stream_step(config: Dict[str, Any], chips: int, rows: int,
                seq: int) -> Dict[str, float]:
    """The hyper-connections of one training step on ``rows`` sequences (one
    device's share): two sub-layers a layer, forward and backward, the
    backward at twice the forward as everywhere in ``flops.py``; the
    recomputation under remat is not counted."""
    sublayers = 2 * config["num_hidden_layers"]
    tokens = rows * seq
    width = BYTES[config.get("stream_dtype", "bfloat16")]
    return {"flops": 3.0 * sublayers * tokens * flops_per_position(config),
            "bytes": 3.0 * sublayers * tokens * width
            * values_per_position(config)}

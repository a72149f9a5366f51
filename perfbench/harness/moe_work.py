"""Operations and bytes of the routed experts' grouped matmul, counted from
shapes: ``kernel_roofline``'s ``work`` for ``moe_experts_roofline``."""

from __future__ import annotations

from typing import Any, Dict


def grouped_matmul_call(config: Dict[str, Any], chips: int, rows: int,
                        seq: int) -> Dict[str, float]:
    """One grouped matmul of the experts on ``rows`` sequences (one device's
    share): the ``rows * seq * top_k`` (token, expert) rows, each against its
    own expert's ``hidden_size x intermediate_size`` matrix.  Gate, up and
    down, the same three recomputed under remat, the three that give the rows'
    gradients and the three that give the weights' each cost these FLOPs and
    move these bytes: the rows in at one width, every expert's matrix once,
    the result out at the other width (for a weights' gradient: both sets of
    rows in, every matrix out), bf16.  A kernel that reads an expert's matrix
    once for every tile of rows moves more; that is not counted."""
    d, f = config["hidden_size"], config["intermediate_size"]
    n = rows * seq * config["num_experts_per_tok"]
    return {"flops": 2.0 * n * d * f,
            "bytes": 2.0 * (n * d + config["num_experts"] * d * f + n * f)}

"""Operations and bytes, counted from shapes.  The yardstick: no PR that
claims a gain can change what a token or a kernel call is worth.

Model FLOPs per token (training, forward + backward) are

    6 * N_mm + 6 * n_layer * seq * d_model

``N_mm`` counts every parameter that is a matmul operand — attention and MLP
projections and ``lm_head`` at its *unpadded* vocabulary — and not the
embedding tables, which are gathered, not multiplied.  The second term is
causal attention: QK^T and PV are each ``2 * seq * d_model`` per token over the
full square, half of it under the causal mask, and the backward pass costs
twice the forward.  Recomputation under remat is not counted: these are the
operations the mathematics requires, not the ones the program chose to run.
"""

from __future__ import annotations

from typing import Any, Dict

from perfbench.harness import families


def shape(config: Dict[str, Any], chips: int) -> Dict[str, int]:
    """The sizes the counts below need — ``d_model``, ``n_layer`` (as it is
    cut for ``chips``), ``n_head``, ``n_kv_head``, ``head_dim``, ``vocab``,
    ``layer_mm_params`` — from a configuration file's published keys, by its
    family's module.  A family whose kernels the shared work functions count
    adds their sizes: for ``flash_work.py`` ``window`` (and, where they differ
    from ``n_head``, ``n_kv_head`` and ``head_dim``, ``window_n_head``,
    ``window_n_kv_head`` and ``v_head_dim``), for ``ssd_work.py``
    ``ssd_heads`` ... ``ssd_layers``."""
    return families.of(config).shape(config, chips)


def matmul_params(config: Dict[str, Any], chips: int) -> int:
    s = shape(config, chips)
    return s["n_layer"] * s["layer_mm_params"] + s["d_model"] * s["vocab"]


def train_flops_per_token(config: Dict[str, Any], chips: int, seq: int) -> int:
    s = shape(config, chips)
    return (6 * matmul_params(config, chips)
            + 6 * s["n_layer"] * seq * s["d_model"])


def roofline_seconds(work: Dict[str, float], peak: Dict[str, float]):
    """Least time the chip could take, and which peak bounds it."""
    compute = work["flops"] / peak["bf16_flops_per_s"]
    memory = work["bytes"] / peak["hbm_bytes_per_s"]
    return (compute, "compute") if compute >= memory else (memory, "memory")

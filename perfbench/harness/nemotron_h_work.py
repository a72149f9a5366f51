"""Operations and bytes of Nemotron-H's kernels, counted from the
configuration file's own keys (``mamba_num_heads``, ``n_groups``,
``ssm_state_size``, ``chunk_size``, the pattern's letters) and from shapes
alone: ``scope_roofline``'s ``work`` for ``ssd8g_scan_roofline`` and
``kernel_roofline``'s for ``gqa16_attn_*_roofline``.

The scan: the chunked algorithm's matmuls (``families/nemotron_h.py::
scan_flops_per_token``: ``C B^T`` once a group, the masked product with ``X``,
the chunk's state and its read-out) and ``X``, ``B``, ``C``, ``dt``, ``y`` and
the float32 state at each chunk's end.  The attention: the causal triangle,
``seq * (seq + 1) / 2`` live pairs a head; Q, O, dO and dQ at the 32 query
heads, K, V, dK and dV at the 2 key/value heads — what a program that copies
K and V to the query heads, or sums a gradient a query head beside the
kernel, moves more is not counted."""

from __future__ import annotations

from typing import Any, Dict

from perfbench.harness.families import nemotron_h


def scan_step(config: Dict[str, Any], chips: int, rows: int,
              seq: int) -> Dict[str, float]:
    """The scans of one training step on ``rows`` sequences (one device's
    share): every ``M`` layer of the cut, forward and backward, the backward
    at twice the forward as everywhere in ``flops.py``; the recomputation
    under remat is not counted.  Bytes a layer forward: ``X``, ``B``, ``C``
    and ``dt`` in and ``y`` out, bf16, plus the float32 state at each chunk's
    end (heads x head width x state), written once and read once.  The decay
    masks, the scores and every other intermediate are the implementation's:
    a scan that writes them out moves more, and that is not counted."""
    heads, p, groups, n = nemotron_h.mamba_sizes(config)
    layers = nemotron_h.pattern(config).count("M")
    tokens = rows * seq
    chunks = rows * -(-seq // config["chunk_size"])
    forward_bytes = (2.0 * tokens * (2 * heads * p + 2 * groups * n + heads)
                     + 2 * 4.0 * chunks * heads * p * n)
    return {"flops": 3.0 * layers * tokens
            * nemotron_h.scan_flops_per_token(config),
            "bytes": 3.0 * layers * forward_bytes}


def _attn_call(config: Dict[str, Any], rows: int, seq: int, matmuls: int,
               q_sized: int, k_sized: int) -> Dict[str, float]:
    heads, kv = config["num_attention_heads"], config["num_key_value_heads"]
    hd = config["head_dim"]
    pairs = seq * (seq + 1) // 2
    per_head = rows * seq * hd
    return {"flops": 2.0 * matmuls * rows * heads * pairs * hd,
            "bytes": 2.0 * per_head * (q_sized * heads + k_sized * kv)}


def attn_fwd_call(config: Dict[str, Any], chips: int, rows: int,
                  seq: int) -> Dict[str, float]:
    """One ``*`` layer's attention forward on ``rows`` rows: QK^T and PV over
    the causal triangle; Q in and O out at the query heads, K and V in at the
    key/value heads, bf16."""
    return _attn_call(config, rows, seq, 2, 2, 2)


def attn_bwd_call(config: Dict[str, Any], chips: int, rows: int,
                  seq: int) -> Dict[str, float]:
    """One ``*`` layer's attention backward: the five matmuls of a flash
    backward (S, dP, dV, dK, dQ) over the causal triangle; Q, dO in and dQ
    out, K, V in and dK, dV out."""
    return _attn_call(config, rows, seq, 5, 3, 4)

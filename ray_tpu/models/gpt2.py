"""GPT-2 in flax, TPU-first.

The flagship pretraining model (BASELINE.json config #3: GPT-2-small, ICI
allreduce).  Design choices for the MXU/HBM:

- bfloat16 activations, float32 params + optimizer state (cast at use);
- fused QKV projection (one big matmul instead of three);
- attention through ``ops.attention.attention``: the Pallas blockwise flash
  kernel, or the ring when the batch is sequence-sharded over an ``sp`` axis;
- parameter names line up with ``parallel.sharding.gpt_partition_rules`` so
  dp/fsdp/tp shardings apply by regex;
- the two vocab-sized tables (``wte``, ``lm_head``) are stored padded to a
  multiple of 128 rows so the vocab dim splits over ``tp`` at any vocabulary
  (50257 is odd); the pad columns of the logits are masked to ``NEG_INF``, so
  they carry no probability and no gradient and the loss is the unpadded one;
- the residual stream is pinned to the batch's layout
  (``parallel.sharding.constrain_residual``) so ``fsdp`` splits compute, not
  only storage;
- no data-dependent Python control flow — the whole step is one jit region.
"""

from __future__ import annotations

from dataclasses import dataclass
from typing import Any, Optional

import flax.linen as nn
import jax
import jax.numpy as jnp

from ray_tpu.ops.attention import (FLASH_RESIDUALS, NEG_INF, HeadColumns,
                                   attention)
from ray_tpu.ops.kda import KDA_RESIDUALS
from ray_tpu.parallel.sharding import constrain_residual

VOCAB_ALIGN = 128  # one lane tile; also divisible by every tp size in use


def padded_vocab(vocab_size: int) -> int:
    """Rows of the vocab-sized tables: ``vocab_size`` rounded up to
    ``VOCAB_ALIGN``."""
    return -(-vocab_size // VOCAB_ALIGN) * VOCAB_ALIGN


def mask_vocab_padding(logits, vocab_size: int):
    """Set the pad columns of ``(..., padded_vocab)`` logits to ``NEG_INF``:
    zero probability under softmax, never the argmax, zero gradient into the
    pad rows of the tables."""
    if logits.shape[-1] == vocab_size:
        return logits
    col = jax.lax.broadcasted_iota(jnp.int32, logits.shape, logits.ndim - 1)
    return jnp.where(col < vocab_size, logits, NEG_INF)


@dataclass(frozen=True)
class GPT2Config:
    vocab_size: int = 50257
    n_positions: int = 1024
    n_embd: int = 768
    n_layer: int = 12
    n_head: int = 12
    dropout: float = 0.0
    dtype: Any = jnp.bfloat16
    attention_impl: str = "flash"  # "flash" | "ring" | "reference"
    ring_axis: str = "sp"
    # Rematerialize each block in backward (recompute activations).  Saves HBM
    # at ~+1 forward pass of FLOPs; worth it for long-seq / large models, pure
    # overhead for small models that fit comfortably.
    remat: bool = True
    # "full" recomputes a block from its input, except a flash kernel's own
    # output and logsumexp, which are kept (``remat_block``); "dots" saves
    # matmul outputs and recomputes only cheap elementwise ops
    # (gelu/layernorm/softmax) — near-zero extra MXU FLOPs but longer live
    # ranges (slower compile, more HBM).
    remat_policy: str = "full"  # "full" | "dots"
    # MoE: every `moe_every`-th block swaps its dense MLP for an expert-
    # parallel MoE FFN (0 = dense everywhere).  Experts shard over the `ep`
    # mesh axis (models/moe.py).
    moe_every: int = 0
    n_experts: int = 8
    moe_top_k: int = 2
    moe_capacity_factor: float = 1.25

    @staticmethod
    def small() -> "GPT2Config":
        return GPT2Config()

    @staticmethod
    def tiny() -> "GPT2Config":
        return GPT2Config(vocab_size=512, n_positions=128, n_embd=64,
                          n_layer=2, n_head=4)


class Attention(nn.Module):
    config: GPT2Config

    @nn.compact
    def __call__(self, x, *, deterministic: bool = True):
        cfg = self.config
        E = x.shape[-1]
        H = cfg.n_head
        D = E // H
        qkv = nn.Dense(3 * E, dtype=cfg.dtype, name="qkv_proj")(x)
        # the kernels read q, k and v where the projection wrote them, a
        # third of its columns each, and write what out_proj takes
        q, k, v = (HeadColumns(qkv, H, D, first=i * E) for i in range(3))
        out = attention(q, k, v, impl=cfg.attention_impl,
                        ring_axis=cfg.ring_axis)
        return nn.Dense(E, dtype=cfg.dtype, name="out_proj")(out)


class MlpBlock(nn.Module):
    config: GPT2Config

    @nn.compact
    def __call__(self, x):
        cfg = self.config
        h = nn.Dense(4 * cfg.n_embd, dtype=cfg.dtype, name="fc_in")(x)
        h = jax.nn.gelu(h)
        return nn.Dense(cfg.n_embd, dtype=cfg.dtype, name="fc_out")(h)


class Block(nn.Module):
    config: GPT2Config
    use_moe: bool = False

    @nn.compact
    def __call__(self, x, *, deterministic: bool = True):
        cfg = self.config
        x = x + Attention(cfg, name="attn")(
            nn.LayerNorm(dtype=cfg.dtype, name="ln_1")(x),
            deterministic=deterministic)
        if self.use_moe:
            from ray_tpu.models.moe import MoEConfig, MoEMlpBlock

            moe_cfg = MoEConfig(
                n_experts=cfg.n_experts, top_k=cfg.moe_top_k,
                capacity_factor=cfg.moe_capacity_factor,
                d_model=cfg.n_embd, d_ff=4 * cfg.n_embd, dtype=cfg.dtype)
            x = x + MoEMlpBlock(moe_cfg, name="moe_mlp")(
                nn.LayerNorm(dtype=cfg.dtype, name="ln_2")(x))
        else:
            x = x + MlpBlock(cfg, name="mlp")(
                nn.LayerNorm(dtype=cfg.dtype, name="ln_2")(x))
        return x


def remat_block(block_cls, remat_policy: str):
    """``block_cls`` recomputed in the backward from its input.  Under
    ``"full"`` everything in the block is, except what the kernels name: a
    flash kernel's output and logsumexp (``FLASH_RESIDUALS``), the one thing
    in a block whose recomputation costs a whole kernel for one
    activation-sized array, and every chunk's ``(I + L)^-1`` of a Kimi Delta
    Attention layer's scan (``KDA_RESIDUALS``: a Mosaic call of its own,
    ``ops/kda.py::kda_solve``, 0.13 GB a layer at 16,384 positions for the
    dearest part of the scan's forward).  q, k and v are recomputed like the
    rest, and so is what is left of a recurrent layer's scan: what would
    spare a Kimi Delta Attention layer its second forward whole is its output
    and the float32 state before each chunk, 0.67 GB a layer more, and the
    one cell that has such layers has no room for four of them (``PERF.md``,
    PRs 53 and 56).  A block
    may take and return more than the stream (``models/llama.py``'s
    ``carried``: one layer's scan output, or its keys and values, read by
    later layers): every argument is an input of the recomputation, kept
    and not made again, and what a block returns beside the stream is kept
    as its readers' input; reverse mode sums the readers' cotangents into the
    producer's."""
    policies = jax.checkpoint_policies
    policy = (policies.dots_with_no_batch_dims_saveable
              if remat_policy == "dots"
              else policies.save_only_these_names(*FLASH_RESIDUALS,
                                                  *KDA_RESIDUALS))
    return nn.remat(block_cls, policy=policy)


class GPT2LMModel(nn.Module):
    config: GPT2Config

    @nn.compact
    def __call__(self, input_ids, *, deterministic: bool = True):
        """(B, S) token ids -> (B, S, padded_vocab(vocab_size)) logits, pad
        columns at ``NEG_INF``."""
        cfg = self.config
        B, S = input_ids.shape
        pos = jnp.arange(S)[None, :]
        tok = nn.Embed(padded_vocab(cfg.vocab_size), cfg.n_embd,
                       dtype=cfg.dtype, name="wte")(input_ids)
        pe = nn.Embed(cfg.n_positions, cfg.n_embd, dtype=cfg.dtype, name="wpe")(pos)
        x = constrain_residual(tok + pe)
        if cfg.remat_policy not in ("full", "dots"):
            raise ValueError(f"unknown remat_policy: {cfg.remat_policy!r} "
                             "(expected 'full' or 'dots')")
        block_cls = remat_block(Block, cfg.remat_policy) if cfg.remat \
            else Block
        for i in range(cfg.n_layer):
            # remat each block: trade FLOPs for HBM (activations recomputed in
            # backward) — the standard TPU memory/bandwidth trade.
            use_moe = cfg.moe_every > 0 and (i % cfg.moe_every
                                             == cfg.moe_every - 1)
            x = constrain_residual(block_cls(cfg, use_moe, name=f"h_{i}")(
                x, deterministic=deterministic))
        x = nn.LayerNorm(dtype=cfg.dtype, name="ln_f")(x)
        logits = nn.Dense(padded_vocab(cfg.vocab_size), use_bias=False,
                          dtype=cfg.dtype, name="lm_head")(x)
        return mask_vocab_padding(logits, cfg.vocab_size)


def _is_target(logits, targets):
    """Where each row's target column is, as booleans of the logits' shape."""
    col = jax.lax.broadcasted_iota(jnp.int32, logits.shape, logits.ndim - 1)
    return col == targets[..., None]


@jax.custom_vjp
def _token_nll(logits, targets):
    """``-log_softmax(logits)[target]`` per row, float32, from logits in any
    float dtype.  Written for the memory system: the logits are read in the
    dtype they arrive in and upcast inside the reductions, and nothing of the
    logits' shape is written in float32, here or for the backward."""
    return _token_nll_fwd(logits, targets)[0]


def _token_nll_fwd(logits, targets):
    with jax.named_scope("lm_loss"):
        x = logits.astype(jnp.float32)
        top = jnp.max(x, axis=-1)
        # the target's logit by a select in the pass that sums the
        # exponentials: no gather, and nothing to fetch across ``tp`` shards
        # of the vocabulary
        hit = jnp.sum(jnp.where(_is_target(x, targets), x, 0.0), axis=-1)
        lse = top + jnp.log(jnp.sum(jnp.exp(x - top[..., None]), axis=-1))
        return lse - hit, (logits, lse, targets)


def _token_nll_bwd(res, g):
    logits, lse, targets = res
    # a custom rule's backward is outside the scope its forward was traced in
    with jax.named_scope("lm_loss"):
        x = logits.astype(jnp.float32)
        p = jnp.exp(x - lse[..., None])
        d = (p - _is_target(x, targets)) * g[..., None]
        # rounded once, to the logits' dtype: where the cotangent of
        # ``logits.astype(float32)`` was rounded before
        return d.astype(logits.dtype), None


_token_nll.defvjp(_token_nll_fwd, _token_nll_bwd)


def lm_loss(logits, targets, mask=None, total=None):
    """Mean next-token cross entropy in f32 (max, sum, log and mean), over
    the rows ``mask`` keeps; 0 where it keeps none.  ``mask`` may be any
    per-row weights; ``total``, where given, is what their weighted sum is
    divided by in place of their own sum."""
    nll = _token_nll(logits, targets)
    with jax.named_scope("lm_loss"):
        if mask is None:
            return jnp.mean(nll)
        mask = mask.astype(jnp.float32)
        if total is not None:
            return jnp.sum(nll * mask) / total
        return jnp.sum(nll * mask) / jnp.maximum(jnp.sum(mask), 1.0)


def shifted_heads_loss(logits, targets, mask, heads: int, vocab: int):
    """``lm_loss`` of ``heads`` prediction heads, all weighted alike: the
    logits hold one vocabulary of columns a head side by side, head ``r`` at
    position ``t`` scores ``targets[t + r]`` — the token ``r + 1`` ahead,
    ``targets`` being the row rolled left by one — and its term counts where
    the row has such a position and ``mask[t + r]`` counts it: the mean over
    every term that counts.  One head is ``lm_loss`` itself."""
    if heads == 1:
        return lm_loss(logits, targets, mask)
    b, s = targets.shape
    logits = logits[..., :heads * vocab].reshape(b, s, heads, vocab)
    # position t + r of the row, the last where the row has none: not counted
    at = jnp.arange(s)[:, None] + jnp.arange(heads)[None, :]
    ahead, counted = jnp.minimum(at, s - 1), (at < s).astype(jnp.float32)
    if mask is not None:
        counted = counted * mask.astype(jnp.float32)[:, ahead]
    return lm_loss(logits, targets[:, ahead],
                   jnp.broadcast_to(counted, (b, s, heads)))


def ahead_loss(logits, targets, mask, ahead: int):
    """``lm_loss`` of logits that score, at position ``t``, ``targets[t +
    ahead]`` (``targets`` being the row rolled left by one: the token ``ahead
    + 1`` after ``t``), over the positions where the row has such a token and
    ``mask[t + ahead]`` counts it."""
    s = targets.shape[1]
    counted = (jnp.arange(s) < s - ahead).astype(jnp.float32)
    if mask is not None:
        counted = counted * jnp.roll(mask.astype(jnp.float32), -ahead, axis=1)
    return lm_loss(logits, jnp.roll(targets, -ahead, axis=1),
                   jnp.broadcast_to(counted, targets.shape))

"""Llama-family decoder in flax, TPU-first: RoPE + RMSNorm + SwiGLU + GQA.

Second model family beside GPT-2 (models/gpt2.py), covering the modern
pretraining recipe: rotary position embeddings (no learned positions),
pre-RMSNorm blocks, SwiGLU MLPs, grouped-query attention (n_kv_heads <
n_heads), untied LM head by default.  One block serves dense, sparse and
hybrid models: a layer's feed-forward is the dense ``SwiGLU`` (``mlp``) or,
in every ``moe_every``-th layer, the dropless routed experts of
``models/moe.py``
(``moe``: ``n_experts`` SwiGLU experts of width ``d_expert``,
``moe_top_k`` per token, of which the layer may hold a part:
``experts_held``), and ``qk_norm`` puts an RMSNorm over the whole
query and key projections before the heads are split and rotated
(OLMoE-1B-7B is this block with both), or, as ``"head"``, over each head's
``head_dim`` after the split, one scale shared by the heads, in the rotation's
own pass (``ops/rope.py::rope_qk``: one kernel call a direction over q and k
where ``wq`` / ``wk`` wrote them; or ``apply_rope`` over their heads turned
first, where the shapes are no whole tiles); ``head_dim`` is
a size of its own where it is not ``d_model / n_head``.  A layer's token mixer is a kind
too: ``layer_types`` names each layer ``"attention"`` (``attn``) or
``"mamba"`` (``mamba``: ``models/mamba.py``'s Mamba-2 mixer over the Pallas
kernels of ``ops/ssd.py``'s scan); attention may go without RoPE (``rope``)
and take a score scale of its own (``attn_scale``); the embedding, each
branch before its residual add and the logits take constant multipliers;
and ``tie_embeddings`` makes the head the embedding table itself, its matmul
still under the name path ``lm_head`` (Granite 4.0-H is this block with all
of these).  ``objective="block_diffusion"`` makes the model the denoiser of
block-diffusion training: it takes a noised and a clean copy of each row side
by side, ``[x_t ; x]``, runs both through every layer under
``ops.attention.block_diffusion_mask`` with the positions ``0..L-1`` twice,
and gives logits for the noised copy alone (SDAR is the block with this, the
per-head norm, its own ``head_dim`` and a part of each layer's experts).
Attention layers come in two more kinds, ``"full_attention"`` and
``"sliding_attention"`` — the second under a window of ``sliding_window``
positions (``ops.attention``'s band: a query sees itself and the
``sliding_window - 1`` before it), its kernel calls under the scope
``window`` — and a kind may have a rotary table of its own (``rope_tables``:
``RopeTable``, YaRN-scaled frequencies, a part of each head rotated) and a
layer a head count of its own (``n_head_per_layer``); ``attn_gate`` gives
each head's output a sigmoid gate computed from the layer's normed input
(``attn/wg``'s logits go to ``ops.attention`` as its ``gate``: the forward
kernel multiplies in its last step, the backward kernel meets the gates in
the statistics it is handed, and only the sigmoid of one value a head a token
is an op of its own, under the scope ``gate``); ``mlp_types`` names each
layer's feed-forward ``"dense"`` or ``"sparse"`` where ``moe_every``'s fixed
period cannot; and the routed layer may score its experts by sigmoids, scale
the chosen weights and add a shared expert every token passes
(``router_scoring``, ``routed_scale``, ``d_shared_expert``: ``models/moe.py``)
(Laguna-XS.2 is the block with all of these).  ``kv_lora_rank`` > 0 makes
every attention layer latent (``LatentAttention``, DeepSeek-V2's MLA): keys and
values come from one ``kv_lora_rank``-wide latent a position, normed, the
rotary part of the key (``qk_rope_head_dim``) is one vector a position that
all heads share, and a head's scores are ``qk_nope_head_dim +
qk_rope_head_dim`` wide over values ``v_head_dim`` wide
(``ops.attention``'s ``k_shared``; Kimi-VL-A3B's decoder is the block with
this, the leading dense layer and the sigmoid-routed experts).  A layer of
``layer_types`` may be ``"conv"`` (``conv``: ``ShortConvMixer``, LFM2's gated
short convolution — one projection to three parts ``B``, ``C``, ``u``, a
causal depthwise convolution ``conv_width`` wide over ``B * u``, the gate
``C`` on its result, one projection back), and the routed layer may choose its
experts through a per-expert bias that is state and not a weight
(``router_selection_bias``, ``norm_topk_eps``: ``models/moe.py``)
(LFM2-24B-A2B is the block with these, the per-head norm and GQA at heads 64
wide).  ``eva_window`` > 0 makes every attention layer EVA attention: exact
softmax over the positions of the query's own aligned window of that many,
and beside them, in the same softmax, one summary key and value for every
``eva_chunk`` positions of the windows before it, pooled under the scope
``pool`` by weights that each head learns (``attn/phi``, ``attn/mu``:
``ops.pooling.pool_chunks``; ``ops.attention``'s ``eva_mask``, its kernel
calls under the scope ``eva``); ``norm_unit_offset`` makes every RMSNorm ``x / rms(x) *
(1 + g)``; ``residual_dtype`` holds the residual stream, and with it each
block's saved input, in another dtype than the activations; ``logits_dtype``
is the head's output's; and ``n_pred_heads`` > 1 gives the head that many
times the vocabulary's columns, head ``r`` scoring the token ``r + 1`` ahead
(``models/gpt2.py::shifted_heads_loss``) (EvaByte is the block with these).
A layer of ``layer_types`` may be ``"kda"`` (``kda``: ``models/kda.py``'s Kimi
Delta Attention mixer over ``ops/kda.py``'s chunked scan, a gated delta rule
with a decay a channel), and with ``rope`` off latent attention turns nothing,
the shared key part un-rotated (Kimi-Linear-48B-A3B is the block with these,
the leading dense layer and the sigmoid-routed experts).
A layer of ``layer_types`` may be ``"mamba1"`` (``mamba1``:
``models/mamba.py``'s Mamba-1 mixer over ``ops/selective_scan.py``, a decay a
channel a state), and **a block may hand a tensor on to later blocks**
(``producers``): a ``"mamba1"`` layer its scan's output, which the ``"gmu"``
layers after it read (``gmu``: ``GatedMemoryUnit``), an attention layer its
keys and values, which the ``"cross_attention"`` layers after it read through
a query projection of their own.  Such a tensor is an input of every block
that reads it, so under remat it is kept and not made again, and the
producer's cotangent is the sum over its readers'.  ``diff_attn`` makes every
attention layer differential attention (``DifferentialAttention``: two
softmaxes over a pair of value heads, subtracted), ``norm="layer"`` every norm
of the stream a LayerNorm, ``attn_bias`` gives the attention's projections
biases, and ``layer_depths`` names the layers' places in a deeper model they
are cut from (Phi-4-mini-flash-reasoning, SambaY, is the block with these,
without rotation and with the head tied).
A kind of ``rope_tables`` may name no table (``None``): its layers turn
nothing while another kind's turn; ``router_before_attention`` hands the
routed layer the block's first norm's output as what its router reads
(``models/moe.py``'s ``router_input``), so that each token's experts are a
function of the attention's own input and known before attention runs, the
experts still reading the second norm's; and ``expert_activation="relu"``
gates the routed experts by a ReLU (ReGLU) (SmallThinker-21BA3B is the block
with these: whole-row layers without rotation beside layers under a window
of 4,096 with it, 28 query heads over 4 key/value heads, 64 softmax-routed
experts of which a chip holds a part).
**A layer may be one branch alone**, ``x + f(norm(x))`` behind one norm: an
entry ``"none"`` of ``mlp_types`` leaves a layer its token mixer and no
feed-forward (no ``mlp_norm``, no ``mlp`` / ``moe``), an entry ``"none"`` of
``layer_types`` leaves it its feed-forward and no mixer (no ``attn_norm``);
``remat_block`` then wraps that one branch.  ``expert_activation="relu2"``
makes an expert ``W_down relu(W_up n)^2``, two matrices and no gate, the
shared expert too (``models/moe.py``), and a Mamba-2 layer of
``mamba_n_groups`` groups norms each group's channels apart after its gate
(NVIDIA-Nemotron-3-Nano-30B-A3B, ``nemotron_h``, is the stack with these:
Mamba-2 layers in eight groups, sigmoid-routed squared-ReLU experts beside a
shared one, and a few layers of 32 query heads over 2 without rotation, each
layer one of the three).
``hc_mult`` > 1 makes **the residual path that many streams wide**
(manifold-constrained hyper-connections, mHC: ``HyperConnection``, the
modules ``hc_attn`` and ``hc_mlp`` of a block): the stream is (B, S,
``hc_mult`` x ``d_model``), stream ``j`` the ``j``-th ``d_model`` lanes — the
embedding copied to each, the streams summed before ``norm_f`` — and each of a
block's branches reads one ``d_model``-wide mix of them and is written back to
all while a Sinkhorn-normalised matrix a position mixes the streams among
themselves; ``q_lora_rank`` > 0 gives latent attention a query latent with a
norm of its own (``wq_a``, ``q_norm``, ``wq_b``), and a kind's rotary table
(``rope_tables``) reaches the latent path, YaRN with it; ``n_mtp_modules`` > 0
puts that many prediction modules behind the trunk (``PredictionModule``,
DeepSeek-V3's multi-token prediction: a block of the stack's own kind over the
trunk's output joined with the next token's embedding, the embedding and the
head shared), which the model returns beside its logits where it is asked
(``predict_ahead``) and ``models/pretrain.py`` weighs into the objective
(``MTP_WEIGHT``) (Xing4.0-29B-A4B is the stack with these, a leading dense
layer and sigmoid-routed experts beside a shared one).
A layer of ``layer_types`` may be ``"gdn"`` (``gdn``: ``models/gdn.py``'s
Gated DeltaNet mixer over ``ops/gdn.py``'s chunked scan, a gated delta rule
with ONE decay a value head and ``kda_n_heads`` value heads over
``gdn_key_heads`` key heads); ``attn_gate="channel"`` makes ``wq`` twice as
wide — a head's query and, beside it, a gate logit for every channel of the
head's output, whose sigmoid multiplies the kernels' result before ``wo``
(scope ``gate``), the layer's calls under the scope ``gated``; under
``norm_unit_offset`` the per-head norm's scale is ``1 + g`` too; and
``shared_expert_gate`` puts the shared expert under one sigmoid gate a token
(``models/moe.py``) (Qwen3-Next-80B-A3B is the stack with these: three ``gdn``
layers to one gated attention layer of heads 256 wide, a quarter of each
turned, softmax-routed experts beside the gated shared one).
Every such field at its default leaves the program the dense Llama it was.
``remat`` recomputes each block from its input in the backward; what
``remat_policy="full"`` keeps beside that input is each attention layer's
flash kernel output and logsumexp, so that no attention kernel's forward
runs twice, and each KDA layer's chunk inverses, so that its second forward
solves nothing (``models/gpt2.py::remat_block``; a Mamba layer, a routed
layer's Mosaic calls and reference attention keep nothing).  Same TPU discipline as the GPT stack —
bfloat16 activations, fused QKV-free layout matched to
``llama_partition_rules`` so tp/fsdp shardings apply by regex, attention
through ``ops.attention.attention``, which picks the Pallas flash kernel or,
under an ``sp`` axis, ring attention — and the same ``ShardedPretrainer`` drives
it (reference analogue: the reference trains models through external
libs; the in-repo flagship models are this framework's own).
"""

from __future__ import annotations

import functools
import math
from dataclasses import dataclass
from typing import Any, Optional, Tuple

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np

from ray_tpu.models.gpt2 import (mask_vocab_padding, padded_vocab,
                                 remat_block)
from ray_tpu.models.gdn import GDNMixer
from ray_tpu.models.kda import HeadNorm, KDAMixer
from ray_tpu.models.mamba import (GatedMemoryUnit, Mamba1Mixer, Mamba2Mixer,
                                  SplitDense, _conv_init, gated_short_conv)
from ray_tpu.models.moe import RoutedConfig, RoutedSwiGLU, silu_mul
from ray_tpu.ops import hyper_connection, pooling, rope
from ray_tpu.ops.attention import HeadColumns, attention
from ray_tpu.ops.hyper_connection import sinkhorn  # noqa: F401  (its home)
from ray_tpu.ops.hyper_connection import streams as _streams
from ray_tpu.parallel.sharding import constrain_residual


@dataclass(frozen=True)
class RopeTable:
    """One rotary table: which part of a head turns, and how fast.  (A head
    wider than a lane tile of 128 takes ``apply_rope``'s XLA pass:
    ``ops.rope.takes`` refuses it.)  The
    first ``rotary_fraction`` of each head's ``head_dim`` is rotated
    (rotate-half inside that part), the rest passed through.  ``factor`` > 1:
    YaRN (Peng et al. 2023) over the rotated part's frequencies — those that
    turn more than ``beta_fast`` times in ``original_positions`` are kept,
    those that turn fewer than ``beta_slow`` times are divided by ``factor``,
    a linear ramp over the dimensions between — and cos and sin are
    multiplied by ``attention_factor``."""
    theta: float = 10000.0
    rotary_fraction: float = 1.0
    factor: float = 1.0
    original_positions: int = 0
    beta_fast: float = 32.0
    beta_slow: float = 1.0
    attention_factor: float = 1.0


@dataclass(frozen=True)
class LlamaConfig:
    vocab_size: int = 32000
    n_positions: int = 2048          # max seq (RoPE extrapolates beyond)
    d_model: int = 768
    n_layer: int = 12
    n_head: int = 12
    n_kv_head: int = 4               # GQA: kv heads shared across q groups
    d_ff: int = 2048                 # SwiGLU hidden
    rope_theta: float = 10000.0
    rms_eps: float = 1e-5
    dtype: Any = jnp.bfloat16
    attention_impl: str = "flash"    # "flash" | "ring" | "reference"
    ring_axis: str = "sp"
    remat: bool = True
    # "full": a block is recomputed from its input, its flash kernels' output
    # and logsumexp kept; "dots": matmul outputs kept (gpt2.remat_block)
    remat_policy: str = "full"
    # RMSNorm over the q and k projections: True, over the whole projection
    # before the split into heads; "head", over each head's head_dim after it
    # (one scale of that width for all heads); both before RoPE
    qk_norm: Any = False
    head_dim: Optional[int] = None   # a head's width; None: d_model / n_head
    # every moe_every-th layer (the last of each period) routes its tokens
    # through n_experts SwiGLU experts of width d_expert; 0: all dense
    moe_every: int = 0
    n_experts: int = 0
    moe_top_k: int = 0
    d_expert: int = 0
    norm_topk_prob: bool = False     # renormalise the chosen probabilities
    # (first index, count) of each routed layer's experts that are held here,
    # as one chip of an expert-parallel group holds them; None: all
    experts_held: Optional[Tuple[int, int]] = None
    router_aux_weight: float = 0.01  # x load-balancing loss, in the objective
    router_z_weight: float = 1e-3    # x router z-loss
    # each layer's token mixer, "attention" (or a kind of it), "mamba"
    # (models/mamba.py), "conv" (ShortConvMixer), "kda" (models/kda.py) or
    # "gdn" (models/gdn.py),
    # one entry a layer; empty: attention in every layer.  "mamba1"
    # (Mamba1Mixer) reads mamba_d_state, mamba_d_conv and, as its scan's block
    # of positions, mamba_chunk; "gmu" and "cross_attention" read a producer;
    # "none": the layer is its feed-forward alone, behind mlp_norm
    layer_types: Tuple[str, ...] = ()
    mamba_n_heads: int = 0
    mamba_d_head: int = 0
    mamba_d_state: int = 0           # N: a head's state is d_head x d_state
    # groups of heads that share B and C, and a gated norm of their own
    mamba_n_groups: int = 1
    mamba_d_conv: int = 4
    mamba_chunk: int = 256
    rope: bool = True                # False: no position encoding (NoPE)
    attn_scale: Optional[float] = None   # x the scores; None: 1/sqrt(head)
    embedding_multiplier: float = 1.0    # x the embedding
    residual_multiplier: float = 1.0     # x each branch, before its residual add
    logits_scaling: float = 1.0          # the logits are divided by it
    tie_embeddings: bool = False         # the head is the embedding table
    # what the trainer minimises (models/pretrain.py): "next_token", or
    # "block_diffusion" — the masked-token loss of a noised copy of each row,
    # in blocks of diffusion_block, each at its own noise level in
    # (diffusion_t_min, 1], masked positions holding mask_token_id
    objective: str = "next_token"
    diffusion_block: int = 0
    diffusion_t_min: float = 1e-3
    mask_token_id: int = 0
    # "sliding_attention" layers of layer_types see this many positions, the
    # query's own among them; "full_attention" ones all before them
    sliding_window: int = 0
    # a layer's query heads, one entry a layer; empty: n_head in every layer
    n_head_per_layer: Tuple[int, ...] = ()
    # (layer kind, RopeTable) pairs: the rotary table of the attention layers
    # of that kind, None for a kind that turns nothing; a kind that is not
    # named rotates the whole head by rope_theta
    rope_tables: Tuple[Tuple[str, Optional[RopeTable]], ...] = ()
    # a sigmoid gate on the attention's output: True, a head's, its logit from
    # a projection of its own (wg); "channel", a channel's, the logits the
    # second half of each head's columns of a wq twice as wide
    attn_gate: Any = False
    # each layer's feed-forward, "dense", "sparse" (the routed experts) or
    # "none" (the layer is its mixer alone, behind attn_norm), one entry a
    # layer; empty: by moe_every
    mlp_types: Tuple[str, ...] = ()
    router_scoring: str = "softmax"  # or "sigmoid": the experts' scores
    routed_scale: float = 1.0        # x the chosen experts' weights
    d_shared_expert: int = 0         # a SwiGLU every token passes, beside the routed
    shared_expert_gate: bool = False  # x one sigmoid gate a token (moe/shared/gate)
    # latent attention (``LatentAttention``) in every attention layer: the
    # width of the latent keys and values are made from; 0: none
    kv_lora_rank: int = 0
    qk_nope_head_dim: int = 0        # a head's key part made from the latent
    qk_rope_head_dim: int = 0        # the rotary key part all heads share
    v_head_dim: int = 0              # a head's values
    conv_width: int = 3              # positions a "conv" layer's kernel spans
    # the routed layers choose their experts by score + a bias an expert that
    # no gradient and no optimizer update reaches (models/moe.py)
    router_selection_bias: bool = False
    norm_topk_eps: float = 0.0       # + the sum norm_topk_prob divides by
    # EVA attention in every attention layer: a query sees the positions of
    # its own aligned window of eva_window, and one learned-pooled summary a
    # chunk of eva_chunk positions of the windows before; 0: none
    eva_window: int = 0
    eva_chunk: int = 0
    norm_unit_offset: bool = False   # every RMSNorm's scale is 1 + g, g from 0
    residual_dtype: Any = None       # the residual stream's; None: dtype
    logits_dtype: Any = None         # the head's output's; None: dtype
    # the head scores this many tokens ahead of each position, one vocabulary
    # of columns each, all in the objective alike
    n_pred_heads: int = 1
    # "kda" layers of layer_types (models/kda.py: Kimi Delta Attention):
    # heads of kda_head_dim for keys and values alike, the width of the three
    # causal convolutions and the chunk of the scan (ops/kda.py).  "gdn"
    # layers (models/gdn.py: Gated DeltaNet) read the same four — kda_n_heads
    # their VALUE heads — and gdn_key_heads, the key heads those read
    kda_n_heads: int = 0
    kda_head_dim: int = 0
    kda_d_conv: int = 4
    kda_chunk: int = 64
    gdn_key_heads: int = 0
    norm: str = "rms"                # or "layer": LayerNorm, a scale and a bias
    attn_bias: bool = False          # biases on the attention's projections
    # differential attention in every attention layer (``DifferentialAttention``)
    diff_attn: bool = False
    # each layer's index in the whole model, where the layers here are a cut
    # of it (differential attention's lam0 is a function of it); empty: its own
    layer_depths: Tuple[int, ...] = ()
    # the layers that hand a tensor on to later layers: a "mamba1" layer its
    # scan's output, which the "gmu" layers after it read; an attention layer
    # its keys and values, which the "cross_attention" layers after it read
    producers: Tuple[int, ...] = ()
    # the routed layers' routers read the block's first norm's output, the
    # attention's own input, and not the second's (the experts still do)
    router_before_attention: bool = False
    # the routed experts' gate, "silu" or "relu"; "relu2": experts (the
    # shared one too) of two matrices and no gate, relu(up) ** 2
    expert_activation: str = "silu"
    # the residual path's streams (``HyperConnection``: mHC); 1: one stream,
    # x + f(norm(x)).  Each Sinkhorn iteration divides every row of the
    # streams' mixing matrix by its sum + hc_eps, then every column; the
    # matrix's logits are clipped to +- hc_res_clamp before the exp
    hc_mult: int = 1
    hc_sinkhorn_iters: int = 20
    hc_eps: float = 1e-6
    hc_res_clamp: float = 30.0
    # latent attention's query latent (wq_a, q_norm, wq_b); 0: wq, whole
    q_lora_rank: int = 0
    # prediction modules behind the trunk (``PredictionModule``), module k
    # scoring the token k + 1 ahead; their mean loss x MTP_WEIGHT is added to
    # the objective (models/pretrain.py)
    n_mtp_modules: int = 0

    @staticmethod
    def tiny() -> "LlamaConfig":
        return LlamaConfig(vocab_size=512, n_positions=128, d_model=64,
                           n_layer=2, n_head=4, n_kv_head=2, d_ff=128)


def rope_frequencies(head_dim: int, positions, theta: float):
    """(S, head_dim/2) cos/sin tables for the given absolute positions."""
    return rope_table(head_dim, positions, RopeTable(theta=theta))


def rope_table(head_dim: int, positions, table: RopeTable):
    """(S, rot/2) cos/sin of ``table`` for the given positions, ``rot`` =
    ``head_dim * table.rotary_fraction`` the rotated part of a head."""
    import math

    rot = int(head_dim * table.rotary_fraction)
    inv = 1.0 / (table.theta ** (jnp.arange(0, rot, 2,
                                            dtype=jnp.float32) / rot))
    if table.factor > 1.0:
        def turns_at(turns):    # the dimension that turns so often in the
            return rot * math.log(      # original context
                table.original_positions / (turns * 2 * math.pi)) \
                / (2 * math.log(table.theta))
        low = max(math.floor(turns_at(table.beta_fast)), 0)
        high = min(math.ceil(turns_at(table.beta_slow)), rot - 1)
        ramp = jnp.clip((jnp.arange(rot // 2, dtype=jnp.float32) - low)
                        / max(high - low, 1e-3), 0.0, 1.0)
        inv = inv / table.factor * ramp + inv * (1.0 - ramp)
    ang = positions.astype(jnp.float32)[:, None] * inv[None, :]
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    if table.attention_factor != 1.0:
        cos, sin = cos * table.attention_factor, sin * table.attention_factor
    return cos, sin


def _half_swap(width: int, rot: int):
    """``(P, perm)`` of rotate-half over the first ``rot`` lanes of a head
    ``width`` wide: ``(x @ P)[j] = -x[j + rot/2]`` in the part's first half,
    ``+x[j - rot/2]`` in its second and 0 past it, a signed permutation;
    ``perm[j]`` is the lane ``j`` takes its value from (itself past the
    part)."""
    perm, sign = np.arange(width), np.zeros(width, np.float32)
    half = rot // 2
    perm[:half], sign[:half] = np.arange(half, rot), -1.0
    perm[half:rot], sign[half:rot] = np.arange(half), 1.0
    P = np.zeros((width, width), np.float32)
    P[perm, np.arange(width)] = sign
    return P, perm


def _turn(x, C, S, P):
    """``x * C + (x @ P) * S`` in float32.  ``P`` holds only 0 and +-1 and
    comes in ``x``'s dtype, so each product is exact and each output one
    term; ``HIGHEST`` keeps that true of a float32 ``x`` on the MXU and
    changes nothing for bfloat16."""
    swapped = jnp.einsum("...d,de->...e", x, P,
                         preferred_element_type=jnp.float32,
                         precision=jax.lax.Precision.HIGHEST)
    return x.astype(jnp.float32) * C + swapped * S


@jax.custom_vjp
def _rotate(x, C, S, P):
    return _turn(x, C, S, P).astype(x.dtype)


def _rotate_fwd(x, C, S, P):
    return _rotate(x, C, S, P), (C, S, P)


def _rotate_bwd(res, g):
    # a rotation's transpose is the opposite rotation: the cotangent goes
    # through the same pass, the matmul's operand g itself as it comes
    C, S, P = res
    return (_turn(g, C, S, P.T).astype(g.dtype), jnp.zeros_like(C),
            jnp.zeros_like(S), jnp.zeros_like(P))


_rotate.defvjp(_rotate_fwd, _rotate_bwd)


def _rstd(x, eps):
    x = x.astype(jnp.float32)
    return jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps)


@jax.custom_vjp
def _norm_rotate(x, scale, swapped_scale, C, S, P, eps):
    """``_rotate`` of each head's RMSNorm, nothing rounded between them: the
    scale goes into the tables (``swapped_scale`` is ``scale`` where the
    swapped term's lanes come from, so the matmul takes ``x`` as it is) and
    the statistic multiplies last."""
    return (_turn(x, C * scale, S * swapped_scale, P)
            * _rstd(x, eps)).astype(x.dtype)


def _norm_rotate_fwd(x, scale, swapped_scale, C, S, P, eps):
    return (_norm_rotate(x, scale, swapped_scale, C, S, P, eps),
            (x, scale, swapped_scale, C, S, P, eps))


def _norm_rotate_bwd(res, g):
    # the norm's transpose at the opposite rotation of g; that one is rounded
    # as the cotangent between a separate norm and rotation was, being
    # written once for the two passes that read it (the sums, then dx)
    x, scale, swapped_scale, C, S, P, eps = res
    g = _turn(g, C, S, P.T).astype(x.dtype).astype(jnp.float32)
    xf, rstd = x.astype(jnp.float32), _rstd(x, eps)
    xg = xf * g
    d_scale = jnp.sum(xg * rstd, axis=tuple(range(x.ndim - 1)))
    along = jnp.sum(xg * scale, axis=-1, keepdims=True)
    dx = rstd * (g * scale - xf * (rstd * rstd / x.shape[-1]) * along)
    # swapped_scale's part is in scale's
    return (dx.astype(x.dtype), d_scale.astype(scale.dtype),
            *map(jnp.zeros_like, (swapped_scale, C, S, P, eps)))


_norm_rotate.defvjp(_norm_rotate_fwd, _norm_rotate_bwd)


def apply_rope(x, cos, sin, norm_scale=None, eps: float = 1e-6):
    """x: (B, H, S, D); rotate-half (GPT-NeoX) convention — pairs
    (x_i, x_{i+rot/2}) rotate by the position angle.  NOT the interleaved
    Meta-original layout: checkpoints using that convention need their
    wq/wk columns permuted before loading.  Tables ``rot / 2`` wide turn the
    first ``rot`` dimensions and pass the rest through.

    One float32 pass between ``x`` and the result, every operand ``D`` lanes
    wide: the tables are widened to ``D`` once (1 and 0 on the lanes that
    pass) and the half-swap is a matmul by a constant signed permutation,
    whose epilogue the multiply-adds fuse into (``_turn``); the backward is
    the same pass over the cotangent.  With ``norm_scale`` (``D`` wide) each
    head is RMS-normed before it is turned, in that pass: the statistic in
    float32 from ``x`` as it comes, one rounding at the end.

    This is the XLA pass, and the reference of ``ops/rope.py``'s kernels,
    which do the same arithmetic on q and k as ``(B, S, H * D)``:
    ``LlamaAttention`` calls those wherever ``ops.rope.takes`` says the shapes
    allow (heads that fill blocks of 128 lanes whole on every device) and
    this, after a turn to heads, everywhere else; latent attention's rotary
    slice and its shared key call this as they did."""
    D, rot = x.shape[-1], 2 * cos.shape[-1]
    lanes = ((0, 0), (0, D - rot))
    C = jnp.pad(jnp.tile(cos, 2), lanes, constant_values=1.0)
    S = jnp.pad(jnp.tile(sin, 2), lanes)
    P, perm = _half_swap(D, rot)
    P = jnp.asarray(P, x.dtype)
    if norm_scale is None:
        return _rotate(x, C, S, P)
    return _norm_rotate(x, norm_scale, norm_scale[perm], C, S, P,
                        jnp.float32(eps))


class UnitOffsetRMSNorm(nn.Module):
    """``x / rms(x) * (1 + scale)``, ``scale`` from zeros: the statistic and
    the product in float32, the result in ``dtype``."""
    epsilon: float = 1e-5
    dtype: Any = jnp.bfloat16

    @nn.compact
    def __call__(self, x):
        scale = self.param("scale", nn.initializers.zeros, (x.shape[-1],),
                           jnp.float32)
        x = x.astype(jnp.float32)
        return (x * _rstd(x, self.epsilon) * (1.0 + scale)).astype(self.dtype)


def rms_norm(cfg: "LlamaConfig", name: str):
    """The configuration's norm of the residual stream, under ``name``: an
    RMSNorm, or LayerNorm (``norm="layer"``: mean and variance, a scale and a
    bias)."""
    if cfg.norm not in ("rms", "layer"):
        raise ValueError(f"unknown norm {cfg.norm!r} (expected 'rms' or "
                         "'layer')")
    cls = nn.LayerNorm if cfg.norm == "layer" else \
        UnitOffsetRMSNorm if cfg.norm_unit_offset else nn.RMSNorm
    return cls(epsilon=cfg.rms_eps, dtype=cfg.dtype, name=name)


def _pool_init(key, shape, dtype=jnp.float32):
    return jnp.clip(jax.random.normal(key, shape, dtype), -1.0, 1.0) \
        / np.sqrt(shape[-1])


class HeadNormScale(nn.Module):
    """The scale of a per-head RMSNorm that ``apply_rope`` applies, under the
    path and with the shape, dtype and start ``nn.RMSNorm`` gives its own
    (``<name>/scale``, ones): checkpoints load as before.  ``unit_offset``:
    ``1 + scale``, ``scale`` from zeros, as ``UnitOffsetRMSNorm``'s."""
    unit_offset: bool = False

    @nn.compact
    def __call__(self, width: int):
        if self.unit_offset:
            return 1.0 + self.param("scale", nn.initializers.zeros, (width,),
                                    jnp.float32)
        return self.param("scale", nn.initializers.ones, (width,), jnp.float32)


def _attend(cfg: LlamaConfig, kind: str, q, k, v, k_shared=None,
            head_dim=None, pooled=None, gate=None):
    """A layer's one call into ``ops.attention``: what the configuration and
    the layer's kind say of the mask — a window for a sliding layer, under
    block diffusion (x is [noised ; clean]) the block mask in place of the
    causal one, under EVA the window's own keys and ``pooled``, the
    summaries' keys and values; ``gate`` (B, S, H), the logits of a sigmoid
    gate a head a token on the result.  Which implementation takes it, and
    whether one does, is ``attention``'s to say.  Operands of rank 4 are (B, H, S, D), of rank 3
    (B, S, H * D) as their projection wrote them; the result is
    (B, S, H * Dv), as the output projection takes it."""
    return attention(
        q, k, v, impl=cfg.attention_impl, k_shared=k_shared,
        head_dim=head_dim, gate=gate,
        window=cfg.sliding_window if kind == "sliding_attention" else 0,
        diffusion_block=cfg.diffusion_block
        if cfg.objective == "block_diffusion" else 0,
        sm_scale=cfg.attn_scale, ring_axis=cfg.ring_axis,
        **(dict(eva_window=cfg.eva_window, eva_chunk=cfg.eva_chunk,
                k_pooled=pooled[0], v_pooled=pooled[1]) if pooled else {}))


class LlamaAttention(nn.Module):
    """Grouped-query attention over ``wq`` / ``wk`` / ``wv`` / ``wo``.  Between
    the projections and ``_attend`` (scopes ``q_norm`` / ``k_norm``, an
    RMSNorm over a whole projection, and ``rope``): the rotation of q and k
    by the kind's table, each head RMS-normed first under ``qk_norm="head"``.
    Which pass takes it depends on the operands' shapes and the ambient mesh
    alone (``ops.rope.takes``): where ``H * D`` and ``KV * D`` are whole
    blocks of 128 lanes a device (a head of 128, two of 64 a block), the
    rotary width even and inside the head, no ``sp``, and ``tp`` cuts between
    blocks, one Pallas call a direction reads and writes q and k as their
    projections wrote them, (B, S, H * D), and the flash kernels read that;
    everywhere else q and k are turned to (B, H, S, D) and ``apply_rope``'s
    XLA pass runs, as for every layer before PR 70."""
    config: LlamaConfig
    kind: str = "attention"     # or "full_attention", "sliding_attention"
    n_head: int = 0             # this layer's query heads; 0: config.n_head

    @nn.compact
    def __call__(self, x, positions):
        cfg = self.config
        B, S, E = x.shape
        H, KV = self.n_head or cfg.n_head, cfg.n_kv_head
        # the kind's own rotary table (None: it turns nothing), or the whole
        # head turned by rope_theta
        table = dict(cfg.rope_tables).get(
            self.kind, RopeTable(theta=cfg.rope_theta) if cfg.rope else None)
        D = cfg.head_dim or E // H
        assert H % KV == 0, "n_head must be a multiple of n_kv_head"
        if cfg.attn_gate not in (False, True, "channel"):
            raise ValueError(f"unknown attn_gate {cfg.attn_gate!r} (expected "
                             "False, True or 'channel')")
        by_channel = None
        if cfg.attn_gate == "channel":
            # a head's columns of wq: its query, then its gate's logits
            q, by_channel = (
                t.reshape(B, S, H * D) for t in jnp.split(nn.Dense(
                    2 * H * D, use_bias=False, dtype=cfg.dtype, name="wq")(
                        x).reshape(B, S, H, 2 * D), 2, axis=-1))
        else:
            q = nn.Dense(H * D, use_bias=False, dtype=cfg.dtype,
                         name="wq")(x)
        k = nn.Dense(KV * D, use_bias=False, dtype=cfg.dtype, name="wk")(x)
        v = nn.Dense(KV * D, use_bias=False, dtype=cfg.dtype, name="wv")(x)
        if cfg.qk_norm not in (False, True, "head"):
            raise ValueError(f"unknown qk_norm {cfg.qk_norm!r} (expected "
                             "False, True or 'head')")

        def norm(name, a):
            return rms_norm(cfg, name)(a)

        def heads(a):
            return a.reshape(B, S, -1, D).transpose(0, 2, 1, 3)

        q_scale = k_scale = None
        if cfg.qk_norm is True:
            q, k = norm("q_norm", q), norm("k_norm", k)
        elif cfg.qk_norm == "head":
            # applied to each head in the rotation's own pass, below
            q_scale = HeadNormScale(cfg.norm_unit_offset, name="q_norm")(D)
            k_scale = HeadNormScale(cfg.norm_unit_offset, name="k_norm")(D)
        if table is not None or cfg.qk_norm == "head":
            # one kernel pass over q and k where they lie, if their shapes
            # and the mesh allow; else the XLA pass, which is a head's
            as_written = rope.takes(
                q.shape, k.shape, D,
                int(D * table.rotary_fraction) if table else 0)
            if not as_written:
                q, k = heads(q), heads(k)
            with jax.named_scope("rope"):
                # NoPE: tables of no width turn nothing
                cos, sin = rope_table(D, positions, table) if table \
                    else (jnp.zeros((S, 0), jnp.float32),) * 2
                if as_written:
                    q, k = rope.rope_qk(q, k, cos, sin, q_scale, k_scale,
                                        cfg.rms_eps, head_dim=D)
                else:
                    q = apply_rope(q, cos, sin, q_scale, cfg.rms_eps)
                    k = apply_rope(k, cos, sin, k_scale, cfg.rms_eps)
        # (GQA: k and v go on with the KV heads their projections gave them;
        # ``attention`` reads the group from their shapes)
        pooled = None
        if cfg.eva_window:
            if KV != H:
                raise NotImplementedError(
                    "EVA's pooling takes a key/value head a query head "
                    f"(n_head {H}, n_kv_head {KV})")
            phi = self.param("phi", _pool_init, (H, D))
            mu = self.param("mu", _pool_init, (H, D))
            # (a row of one window sees no summary, and is causal.  The whole
            # row is pooled, its last window too, whose summaries nothing
            # reads: a slice of k and v costs more than an eighth of the
            # pass)
            if S > cfg.eva_window:
                with jax.named_scope("pool"):
                    pooled = pooling.pool_chunks(
                        heads(k) if k.ndim == 3 else k, v, phi, mu,
                        cfg.eva_chunk, cfg.attn_scale or D ** -0.5,
                        impl=cfg.attention_impl)
        # a gated layer's one logit a head a token, from the layer's normed
        # input: the sigmoid and the multiply are ``attention``'s, inside the
        # kernels' own passes
        gate = nn.Dense(H, use_bias=False, dtype=cfg.dtype, name="wg")(x) \
            if cfg.attn_gate is True else None
        # what came straight from its projection goes to the kernels as it
        # lies, (B, S, H * D), and so does the result to ``wo``
        if by_channel is None:
            out = _attend(cfg, self.kind, q, k, v, head_dim=D, pooled=pooled,
                          gate=gate)
        else:
            # the scope tells these calls from another kind's in a trace
            with jax.named_scope("gated"):
                out = _attend(cfg, self.kind, q, k, v, head_dim=D,
                              pooled=pooled)
            with jax.named_scope("gate"):
                out = out * jax.nn.sigmoid(
                    by_channel.astype(jnp.float32)).astype(cfg.dtype)
        return nn.Dense(E, use_bias=False, dtype=cfg.dtype, name="wo")(out)


def _lambda_init(key, shape, dtype=jnp.float32):
    return 0.1 * jax.random.normal(key, shape, dtype)


class DifferentialAttention(nn.Module):
    """Differential attention (Ye et al. 2024, arXiv:2410.05258) as SambaY's
    decoders run it: the query heads come in pairs (the even and the odd),
    and so do the key heads; each pair's two softmaxes — under the causal mask,
    or a window's — are taken over the same values, a pair of value heads side
    by side (twice a head's width), and the second is subtracted ``lam`` times:

        a1 = softmax(q1 k1^T / sqrt(D)) V     a2 = softmax(q2 k2^T / sqrt(D)) V
        lam = exp(lq1 . lk1) - exp(lq2 . lk2) + lam0,  lam0 = 0.8 - 0.6 exp(-0.3 depth)
        out = wo ((1 - lam0) RMSNorm_2D(a1 - lam a2))

    with four learned vectors of ``D`` and one norm scale of ``2 D`` a layer.
    The two softmaxes are two calls into ``ops.attention.attention`` (the
    scope ``diff``), each reading its heads where the projection wrote them:
    scores ``D`` wide over values ``2 D`` wide, a key/value head to two query
    heads.  What follows them is the scope ``combine``.  A layer that hands on
    (``hands_on``) returns its keys and values beside its output, as one array
    (B, S, 2 KV D): the keys' columns, then the values'; a
    ``"cross_attention"`` layer has a query projection alone and takes that
    array."""
    config: LlamaConfig
    kind: str = "full_attention"    # or "sliding_attention", "cross_attention"
    depth: int = 0

    @nn.compact
    def __call__(self, x, kv=None):
        cfg = self.config
        H, KV, E = cfg.n_head, cfg.n_kv_head, x.shape[-1]
        D = cfg.head_dim or E // H
        if H % 2 or KV % 2 or (H // 2) % (KV // 2):
            raise ValueError(f"differential attention pairs {H} query heads "
                             f"and {KV} key/value heads")

        def dense(width, name):
            return nn.Dense(width, use_bias=cfg.attn_bias, dtype=cfg.dtype,
                            name=name)

        if self.kind == "cross_attention":
            q = dense(H * D, "wq")(x)
        else:
            qkv = dense((H + 2 * KV) * D, "wqkv")(x)
            q, kv = qkv, qkv[..., H * D:]

        def pair(x, heads, first):
            return HeadColumns(x, heads // 2, D, first=first, stride=2 * D)

        v = HeadColumns(kv, KV // 2, 2 * D, first=KV * D)
        mask = "sliding_attention" if self.kind == "sliding_attention" \
            else "full_attention"
        with jax.named_scope("diff"):
            a1, a2 = (_attend(cfg, mask, pair(q, H, first), pair(kv, KV, first),
                              v) for first in (0, D))
        lq1, lk1, lq2, lk2 = (self.param(name, _lambda_init, (D,)) for name in
                              ("lambda_q1", "lambda_k1", "lambda_q2",
                               "lambda_k2"))
        lam0 = 0.8 - 0.6 * math.exp(-0.3 * self.depth)
        sub_norm = HeadNorm(H // 2, cfg.rms_eps, cfg.dtype, name="sub_norm")
        with jax.named_scope("combine"):
            lam = jnp.exp(jnp.sum(lq1 * lk1)) - jnp.exp(jnp.sum(lq2 * lk2)) \
                + lam0
            out = sub_norm(a1.astype(jnp.float32)
                           - lam * a2.astype(jnp.float32)) * (1.0 - lam0)
        return dense(E, "wo")(out.astype(cfg.dtype)), kv


class LatentAttention(nn.Module):
    """Multi-head latent attention (DeepSeek-V2, section 2.1), the query
    projected whole (``wq``) or, under ``q_lora_rank``, through a latent of
    that width with a norm of its own (``wq_b(q_norm(wq_a(x)))``): ``wdkv``
    takes the layer's input down
    to a latent ``c`` of ``kv_lora_rank`` and one rotary key ``kr`` of
    ``qk_rope_head_dim`` a position; ``kv_norm`` norms ``c``; ``wukv`` makes
    each head's key part ``kn`` (``qk_nope_head_dim``) and values
    (``v_head_dim``) from it.  A head's query is ``[qn ; R(qr)]``, its key
    ``[kn ; R(kr)]`` with the one ``kr`` for all heads, the scores are scaled
    by the inverse root of their whole width, and ``wo`` takes the heads'
    values back to the model's width.  The kernels take the key's parts as
    they are (``flash_attention``'s ``k_shared``): nothing is broadcast to
    the heads or joined in HBM, and the scope ``assemble`` that would hold it
    stays empty.  The rotary table is the kind's own where ``rope_tables``
    names one (YaRN: the scores' ``mscale ** 2`` comes in ``attn_scale``)."""
    config: LlamaConfig
    kind: str = "attention"     # or "full_attention"

    @nn.compact
    def __call__(self, x, positions):
        cfg = self.config
        B, S, E = x.shape
        H, rank = cfg.n_head, cfg.kv_lora_rank
        dn, dr, dv = cfg.qk_nope_head_dim, cfg.qk_rope_head_dim, cfg.v_head_dim

        def dense(width, name):
            return nn.Dense(width, use_bias=False, dtype=cfg.dtype, name=name)

        def heads(a):
            return a.reshape(B, S, H, -1).transpose(0, 2, 1, 3)

        if cfg.q_lora_rank:
            q = dense(H * (dn + dr), "wq_b")(nn.RMSNorm(
                epsilon=cfg.rms_eps, dtype=cfg.dtype, name="q_norm")(
                    dense(cfg.q_lora_rank, "wq_a")(x)))
        else:
            q = dense(H * (dn + dr), "wq")(x)
        q = heads(q)
        down = dense(rank + dr, "wdkv")(x)
        latent = nn.RMSNorm(epsilon=cfg.rms_eps, dtype=cfg.dtype,
                            name="kv_norm")(down[..., :rank])
        # each head's key part and values, read by the kernels where wukv
        # wrote them: [kn ; v] a head, side by side
        kv = dense(H * (dn + dv), "wukv")(latent)
        k = HeadColumns(kv, H, dn, first=0, stride=dn + dv)
        v = HeadColumns(kv, H, dv, first=dn, stride=dn + dv)
        kr = down[:, None, :, rank:]
        table = dict(cfg.rope_tables).get(
            self.kind, RopeTable(theta=cfg.rope_theta)) if cfg.rope else None
        if table is not None:
            with jax.named_scope("rope"):
                cos, sin = rope_table(dr, positions, table)
                # the 64 rotary lanes turned as a head of their own and
                # joined to the rest again: against the whole 192-wide head
                # through the pass the scope read 7.1 ms a step for 11.7 on
                # the chip (PR 39)
                q = jnp.concatenate(
                    [q[..., :dn], apply_rope(q[..., dn:], cos, sin)], axis=-1)
                kr = apply_rope(kr, cos, sin)
        # (NoPE: q and the shared key part go to the kernels as projected)
        # the scope tells these calls from another kind's in a trace
        with jax.named_scope("mla"):
            out = _attend(cfg, self.kind, q, k, v, kr)
        return dense(E, "wo")(out)


# ``x -> (x W_0, x W_1, x W_2)``, the kernel (in, 3, out): ``SplitDense`` at
# its three parts
ThreeWayDense = SplitDense


class ShortConvMixer(nn.Module):
    """LFM2's gated short convolution, a layer's token mixer in place of
    attention: ``[B ; C ; u] = W_in n``, ``m = causal depthwise conv(B * u)``
    over ``conv_width`` positions with no bias and no activation, ``out =
    W_out (C * m)``.  The pass between the projections is the scope ``mix``,
    which needs no collective under ``tp``: ``in_proj`` cuts ``B``, ``C`` and
    ``u`` each by channel (``ThreeWayDense``) and so is the depthwise kernel
    cut."""
    config: LlamaConfig

    @nn.compact
    def __call__(self, x):
        cfg = self.config
        E = x.shape[-1]
        # (by channel, whatever the layers around cut: a routed layer's
        # tokens are cut along the sequence over tp)
        b, c, u = (constrain_residual(part, channels="tp") for part in
                   ThreeWayDense(E, cfg.dtype, name="in_proj")(x))
        kernel = self.param("conv_kernel", _conv_init(cfg.conv_width),
                            (cfg.conv_width, E))
        with jax.named_scope("mix"):
            y = gated_short_conv(b, c, u, kernel.astype(cfg.dtype))
        return nn.Dense(E, use_bias=False, dtype=cfg.dtype,
                        name="out_proj")(y)


def _hc_bias_init(n: int):
    """The coefficients' biases at the start, ``[b_pre ; b_post ; b_res]``:
    ``H_pre`` near ``1 / n`` a stream and ``H_post`` near 1, so that a block
    starts near a pre-norm block on the streams' mean, each tilted along the
    streams (the gates' sums stay ``1`` and ``n``) so that the streams differ
    from the first branch on; ``H_res`` near the identity (off the diagonal
    ``exp(-3)`` before the Sinkhorn)."""
    tilt = np.linspace(-1.0, 1.0, n)
    return np.concatenate([
        -math.log(n - 1) - tilt, tilt,
        (-3.0 * (1.0 - np.eye(n))).reshape(-1)]).astype(np.float32)


class HyperConnection(nn.Module):
    """One sub-layer's manifold-constrained hyper-connection (mHC,
    arXiv:2512.24880, over Hyper-Connections, arXiv:2409.19606) around a
    branch ``F`` of a block whose residual stream ``X`` is ``n = hc_mult``
    streams of ``C = d_model`` side by side, (B, S, n C).  A position:

        x' = RMSNorm(vec(X))                          over all n C values
        H_pre  = sigmoid(alpha_pre (x' phi_pre) + b_pre)             (n)
        H_post = 2 sigmoid(alpha_post (x' phi_post) + b_post)        (n)
        H_res  = Sinkhorn(exp(clip(alpha_res mat(x' phi_res) + b_res)))  (n, n)
        X <- H_res X + H_post^T F(H_pre X)

    Called on the stream alone it gives ``(H_pre X, coefficients)`` — the
    branch's ``C``-wide input in the activations' dtype (scopes ``coeff``,
    ``sinkhorn``, ``pre``), and ``H_post`` (n, B, S) and ``H_res`` (n, n, B,
    S) as the first two of the coefficients —, called with the branch's
    output and those coefficients the stream after it (``post``).  ``phi`` is
    one (n C, 2 n + n^2) matrix, its columns ``[pre ; post ; res]`` (``res``
    row by row), ``bias`` the same, ``alpha`` the three scalars.  The
    statistic, the projection (``HIGHEST``), the gates and the Sinkhorn are
    float32 whatever the dtypes around.

    The module keeps the parameters, the two call forms and the two ``sow``s;
    the arithmetic is ``ops/hyper_connection.py``'s since PR 66: the lines
    that stood here are ``hyper_connection_xla`` and ``write_back_xla`` there
    (with ``sinkhorn`` and the streams' slices), and where ``C`` is whole
    tiles of 128 lanes its two entries take their Pallas passes instead — one over the
    stream for the coefficients and the mix (under ``coeff``), the
    write-back's backward and the first call's backward one pass each (under
    ``post`` and ``coeff``) — and the second call reads the stream as the
    first handed it on."""
    config: LlamaConfig

    @nn.compact
    def __call__(self, x, branch=None, coefficients=None):
        cfg, n = self.config, self.config.hc_mult
        spec = hyper_connection.Spec(n, cfg.hc_sinkhorn_iters, cfg.hc_eps,
                                     cfg.hc_res_clamp, cfg.rms_eps, cfg.dtype)
        if branch is not None:
            return hyper_connection.write_back(x, branch, coefficients, spec)
        scale = self.param("scale", nn.initializers.ones, (x.shape[-1],),
                           jnp.float32)
        phi = self.param("phi", nn.initializers.lecun_normal(),
                         (x.shape[-1], spec.k), jnp.float32)
        bias = self.param("bias", lambda key: jnp.asarray(_hc_bias_init(n)))
        alpha = self.param("alpha", nn.initializers.constant(0.01), (3,),
                           jnp.float32)
        mixed, coefficients, (row_err, pre_max) = \
            hyper_connection.hyper_connection(x, scale, phi, bias, alpha, spec)
        self.sow("intermediates", "hc_res_row_err", row_err)
        self.sow("intermediates", "hc_pre_max", pre_max)
        return mixed, coefficients


class SwiGLU(nn.Module):
    config: LlamaConfig

    @nn.compact
    def __call__(self, x):
        cfg = self.config
        gate = nn.Dense(cfg.d_ff, use_bias=False, dtype=cfg.dtype,
                        name="gate_proj")(x)
        up = nn.Dense(cfg.d_ff, use_bias=False, dtype=cfg.dtype,
                      name="up_proj")(x)
        return nn.Dense(cfg.d_model, use_bias=False, dtype=cfg.dtype,
                        name="down_proj")(silu_mul(gate, up))


ATTENTION_KINDS = ("attention", "full_attention", "sliding_attention")
# what a layer of a kind hands on to later layers where it is named a
# producer (``LlamaConfig.producers``; an attention layer under ``diff_attn``
# alone), and the kind of layer that reads it
HANDS_ON = {"mamba1": "scan output",
            **{kind: "keys and values" for kind in ATTENTION_KINDS}}
READERS = {"gmu": "scan output", "cross_attention": "keys and values"}


def carried_plan(cfg: LlamaConfig):
    """For each layer, the producer it reads (None: it reads none), checked:
    a reader takes the nearest producer before it that hands on what it
    reads; a reader with none, and a producer that no later layer reads, are
    refused by name."""
    kinds = cfg.layer_types or ("attention",) * cfg.n_layer
    for i in cfg.producers:
        if not 0 <= i < cfg.n_layer or kinds[i] not in HANDS_ON or (
                kinds[i] != "mamba1" and not cfg.diff_attn):
            raise ValueError(
                f"layer {i} is named a producer and has nothing to hand on "
                "(a 'mamba1' layer hands on its scan's output, a "
                "differential attention layer its keys and values)")
    reads = []
    for i, kind in enumerate(kinds):
        before = [j for j in cfg.producers
                  if j < i and HANDS_ON[kinds[j]] == READERS.get(kind)]
        if kind in READERS and not before:
            raise ValueError(
                f"layer {i} ({kind!r}) reads the {READERS[kind]} of an "
                "earlier layer, and no layer before it hands one on "
                f"(producers: {cfg.producers})")
        reads.append(max(before, default=None))
    for i in cfg.producers:
        if i not in reads:
            raise ValueError(
                f"layer {i} ({kinds[i]!r}) hands on its {HANDS_ON[kinds[i]]} "
                "and no later layer reads it")
    return tuple(reads)


class LlamaBlock(nn.Module):
    config: LlamaConfig
    # this layer's feed-forward: "dense", "sparse" (routed experts) or "none"
    mlp: str = "dense"
    # this layer's token mixer: "mamba", "conv", "kda", "gdn", "mamba1", "gmu",
    # "cross_attention", attention of a kind, or "none"
    mixer: str = "attention"
    n_head: int = 0         # this layer's query heads; 0: config.n_head
    depth: int = 0          # this layer's index in the whole model
    # the block returns (x, what its mixer hands on) and not x alone
    hands_on: bool = False

    @nn.compact
    def __call__(self, x, positions, carried=None):
        """``carried``: what a "gmu" or "cross_attention" layer reads of an
        earlier layer, an input of the block like ``x``: under remat it is
        kept and not made again, and its cotangent is one term of the
        producer's."""
        cfg = self.config
        handed = None
        if self.mlp not in ("dense", "sparse", "none") or (
                self.mlp == self.mixer == "none"):
            raise ValueError(
                f"a layer with the mixer {self.mixer!r} and the feed-forward "
                f"{self.mlp!r} (expected 'dense', 'sparse' or, for one of "
                "the two, 'none')")

        write = None    # the branch at hand's way back onto n streams

        def add(x, branch):
            if cfg.residual_multiplier != 1.0:
                branch = branch * cfg.residual_multiplier
            return x + branch if write is None else write(x, branch)

        def branch_input(x, name):
            """What the branch's norm reads: the stream, or of ``hc_mult``
            streams the mix ``H_pre X`` (and then how ``add`` writes back)."""
            if cfg.hc_mult == 1:
                return x, None
            hc = HyperConnection(cfg, name=name)
            mixed, coefficients = hc(x)
            return mixed, lambda x, branch: hc(x, branch, coefficients)

        # (a layer that is its feed-forward alone has no first norm)
        y = attn_in = None
        if self.mixer != "none":
            y, write = branch_input(x, "hc_attn")
            y = attn_in = rms_norm(cfg, "attn_norm")(y)
        if self.mixer == "none":
            if cfg.router_before_attention:
                raise ValueError("a layer without a mixer has no attention "
                                 "input for its router to read")
        elif self.mixer == "mamba":
            x = add(x, Mamba2Mixer(cfg, name="mamba")(y))
        elif self.mixer == "conv":
            x = add(x, ShortConvMixer(cfg, name="conv")(y))
        elif self.mixer == "kda":
            x = add(x, KDAMixer(cfg, name="kda")(y))
        elif self.mixer == "gdn":
            x = add(x, GDNMixer(cfg, name="gdn")(y))
        elif self.mixer == "mamba1":
            out, handed = Mamba1Mixer(cfg, name="mamba1")(y)
            x = add(x, out)
        elif self.mixer == "gmu":
            x = add(x, GatedMemoryUnit(cfg, name="gmu")(y, carried))
        elif cfg.diff_attn and self.mixer in ATTENTION_KINDS + (
                "cross_attention",):
            out, handed = DifferentialAttention(
                cfg, self.mixer, self.depth, name="attn")(y, carried)
            x = add(x, out)
        elif self.mixer in ATTENTION_KINDS:
            attn = LatentAttention(cfg, self.mixer, name="attn") \
                if cfg.kv_lora_rank else LlamaAttention(
                    cfg, self.mixer, self.n_head, name="attn")
            x = add(x, attn(y, positions))
        else:
            raise ValueError(f"unknown layer type {self.mixer!r} (expected "
                             "'none', 'mamba1', 'gmu', 'cross_attention' "
                             "(under diff_attn), 'kda', 'gdn', 'mamba', "
                             "'conv' or "
                             f"one of {ATTENTION_KINDS})")
        if self.mlp == "none":      # the layer is its mixer alone
            return (x, handed) if self.hands_on else x
        y, write = branch_input(x, "hc_mlp")
        y = rms_norm(cfg, "mlp_norm")(y)
        if self.mlp == "sparse":
            x = add(x, RoutedSwiGLU(RoutedConfig(
                n_experts=cfg.n_experts, top_k=cfg.moe_top_k,
                d_model=cfg.d_model, d_ff=cfg.d_expert,
                norm_topk_prob=cfg.norm_topk_prob, dtype=cfg.dtype,
                experts_held=cfg.experts_held, scoring=cfg.router_scoring,
                routed_scale=cfg.routed_scale,
                d_shared=cfg.d_shared_expert,
                shared_gate=cfg.shared_expert_gate,
                selection_bias=cfg.router_selection_bias,
                norm_topk_eps=cfg.norm_topk_eps,
                activation=cfg.expert_activation), name="moe")(
                    y, attn_in if cfg.router_before_attention else None))
        else:
            x = add(x, SwiGLU(cfg, name="mlp")(y))
        return (x, handed) if self.hands_on else x


def _to_streams(x, cfg: LlamaConfig):
    """One stream copied to ``hc_mult``, side by side."""
    return x if cfg.hc_mult == 1 else jnp.tile(x, (1, 1, cfg.hc_mult))


def _from_streams(x, cfg: LlamaConfig):
    """``hc_mult`` streams summed to one (in float32, rounded once)."""
    if cfg.hc_mult == 1:
        return x
    return sum(part.astype(jnp.float32)
               for part in _streams(x, cfg.hc_mult)).astype(x.dtype)


class PredictionModule(nn.Module):
    """One multi-token-prediction module (DeepSeek-V3, arXiv:2412.19437,
    section 2.2) behind the trunk: ``h' = proj [h_norm(h) ; emb_norm(e)]`` of
    the trunk's (or the module before's) output ``h`` and the embedding ``e``
    of the token its depth ahead, then one block of the stack's own kind
    (``block_cls`` with the last layer's ``mlp``, ``mixer`` and ``n_head``;
    under ``hc_mult`` ``h'`` is copied to the streams and the streams summed,
    as the trunk does).  -> (the block's output, which the next module reads;
    the same through the module's own final norm, which the shared head
    reads)."""
    config: LlamaConfig
    block_cls: Any      # LlamaBlock, or it under remat
    mlp: str
    mixer: str
    n_head: int
    depth: int

    @nn.compact
    def __call__(self, h, emb, positions):
        cfg = self.config
        joined = jnp.concatenate(
            [rms_norm(cfg, "h_norm")(h), rms_norm(cfg, "emb_norm")(emb)],
            axis=-1)
        x = nn.Dense(cfg.d_model, use_bias=False, dtype=cfg.dtype,
                     name="proj")(joined).astype(h.dtype)
        x = constrain_residual(_to_streams(x, cfg))
        block = self.block_cls(cfg, self.mlp, self.mixer, self.n_head,
                               self.depth, name="block")
        x = _from_streams(block(x, positions), cfg)
        return x, rms_norm(cfg, "norm_f")(x)


class LlamaLMModel(nn.Module):
    config: LlamaConfig

    @nn.compact
    def __call__(self, input_ids, *, deterministic: bool = True,
                 predict_ahead: bool = False):
        """(B, S) token ids -> (B, S, padded vocabulary) logits; under the
        block-diffusion objective ``input_ids`` is ``[x_t ; x]``, (B, 2 L),
        and the logits are the noised copy's, (B, L, .).  ``predict_ahead``
        (with ``n_mtp_modules``): -> (logits, the prediction modules' logits,
        module ``k``'s at position ``t`` scoring the token ``t + k + 2``)."""
        cfg = self.config
        B, S = input_ids.shape
        if cfg.objective not in ("next_token", "block_diffusion"):
            raise ValueError(f"unknown objective {cfg.objective!r} (expected "
                             "'next_token' or 'block_diffusion')")
        two_copies = cfg.objective == "block_diffusion"
        if cfg.n_pred_heads > 1 and (two_copies or cfg.tie_embeddings):
            raise ValueError(
                f"{cfg.n_pred_heads} prediction heads are an untied head "
                "under the next-token objective")
        if two_copies and (S // 2) % cfg.diffusion_block:
            raise ValueError(f"a copy of {S // 2} positions is not whole "
                             f"blocks of {cfg.diffusion_block}")
        for name in ("layer_types", "mlp_types", "n_head_per_layer",
                     "layer_depths"):
            if getattr(cfg, name) and len(getattr(cfg, name)) != cfg.n_layer:
                raise ValueError(f"{name} names {len(getattr(cfg, name))} "
                                 f"layers, n_layer is {cfg.n_layer}")
        wte = nn.Embed(padded_vocab(cfg.vocab_size), cfg.d_model,
                       dtype=cfg.dtype, name="wte")
        x = wte(input_ids)
        if cfg.embedding_multiplier != 1.0:
            x = x * cfg.embedding_multiplier
        if cfg.residual_dtype is not None:
            # every add onto the stream promotes to it; the norms hand the
            # branches their input in ``dtype``
            x = x.astype(cfg.residual_dtype)
        x = constrain_residual(_to_streams(x, cfg))
        # both copies of a row count their positions from 0
        positions = jnp.tile(jnp.arange(S // 2), 2) if two_copies \
            else jnp.arange(S)
        block_cls = remat_block(LlamaBlock, cfg.remat_policy) if cfg.remat \
            else LlamaBlock
        reads, handed = carried_plan(cfg), {}
        for i in range(cfg.n_layer):
            if cfg.mlp_types:
                mlp = cfg.mlp_types[i]
            else:
                mlp = "sparse" if cfg.moe_every > 0 \
                    and i % cfg.moe_every == cfg.moe_every - 1 else "dense"
            mixer = cfg.layer_types[i] if cfg.layer_types else "attention"
            n_head = cfg.n_head_per_layer[i] if cfg.n_head_per_layer else 0
            block = block_cls(
                cfg, mlp, mixer, n_head,
                cfg.layer_depths[i] if cfg.layer_depths else i,
                i in cfg.producers, name=f"h_{i}")
            x = block(x, positions) if reads[i] is None \
                else block(x, positions, handed[reads[i]])
            if i in cfg.producers:
                # by channel, or by head, where a tp axis cuts the projection
                # that wrote it; along the batch as the stream is
                x, made = x
                handed[i] = constrain_residual(made, channels="tp")
            x = constrain_residual(x)
        x = _from_streams(x, cfg)
        if two_copies:
            x = x[:, :S // 2]       # the head sees the noised copy alone
        columns = cfg.n_pred_heads * cfg.vocab_size
        # (n_pred_heads vocabularies of columns side by side, head r the
        # columns from r * vocab_size)
        lm_head = None if cfg.tie_embeddings else nn.Dense(
            padded_vocab(columns), use_bias=False, dtype=cfg.dtype,
            name="lm_head", **({} if cfg.logits_dtype is None else dict(
                dot_general=functools.partial(
                    jax.lax.dot_general,
                    preferred_element_type=cfg.logits_dtype))))

        def head(x):
            if cfg.logits_scaling != 1.0:
                # on the narrow side of the head's matmul: the logits stay
                # bf16
                x = x / cfg.logits_scaling
            if lm_head is not None:
                return mask_vocab_padding(lm_head(x), columns)
            # the head's matmul against the embedding table itself, under the
            # name path an untied head has; the table's gradient is the sum
            # of the gather's and this matmul's
            with jax.named_scope("lm_head"):
                return mask_vocab_padding(jnp.einsum(
                    "bsd,vd->bsv", x, wte.embedding.astype(cfg.dtype)),
                    columns)

        logits = head(rms_norm(cfg, "norm_f")(x))
        # the modules are parameters of the model whether or not they are
        # asked for
        if not cfg.n_mtp_modules or not (predict_ahead
                                         or self.is_initializing()):
            return logits
        if two_copies or cfg.producers:
            raise ValueError("prediction modules stand behind a next-token "
                             "stack whose last block reads no earlier one")
        ahead = []
        for k in range(cfg.n_mtp_modules):
            emb = wte(jnp.roll(input_ids, -(k + 1), axis=1))
            if cfg.embedding_multiplier != 1.0:
                emb = emb * cfg.embedding_multiplier
            # (the last k + 1 positions read a token of the row's start:
            # ``models/gpt2.py::ahead_loss`` does not count them)
            x, normed = PredictionModule(
                cfg, block_cls, mlp, mixer, n_head, cfg.n_layer + k,
                name=f"mtp_{k}")(x, emb, positions)
            ahead.append(head(normed))
        return logits, tuple(ahead)


def llama_partition_rules():
    """Megatron-style tp x fsdp rules for the Llama layout (lives beside
    gpt_partition_rules in parallel/sharding.py)."""
    from ray_tpu.parallel.sharding import llama_partition_rules as _rules

    return _rules()

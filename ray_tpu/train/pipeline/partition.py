"""Stage partitioning + the public GSPMD sharding API for MPMD pipelines.

Three jobs (arXiv:2412.14374 §3: each pipeline stage is an SPMD program over
its own gang; MPMD is the outer product):

- split a model's layer stack into N contiguous stages, keyed by the model's
  CANONICAL parameter names (``wte``, ``h_3``, ``ln_f``, ...) so per-stage
  checkpoint shards merge back into one tree and re-split onto a *different*
  stage count without translation;
- a regex-rule sharding API over arbitrary pytrees
  (``match_partition_rules`` / ``make_shard_and_gather_fns``, the
  t5x/EasyLM-style public pattern — SNIPPETS.md [3]) so each stage is itself
  GSPMD-sharded over its gang's mesh;
- a named-axis mesh builder that degrades gracefully from pod slices to one
  chip (SNIPPETS.md [2]) so the same stage program runs on whatever devices
  the gang actually owns.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Dict, List, Optional, Tuple

import numpy as np

from ray_tpu.parallel.mesh import MeshConfig, build_mesh
from ray_tpu.parallel.sharding import (  # noqa: F401 (public re-exports)
    PartitionRules,
    gpt_partition_rules,
    host_to_global,
    match_partition_rules,
    shard_pytree,
)


# ------------------------------------------------------------- stage layout
def stage_ranges(n_layer: int, n_stages: int) -> List[Tuple[int, int]]:
    """Contiguous near-equal [lo, hi) layer ranges, one per stage.  The
    remainder layers go to the EARLIEST stages: stage 0 also owns the
    embedding lookup and the last stage owns ln_f + lm_head + loss, so the
    extra transformer block lands where the fixed costs are smallest."""
    if n_stages < 1:
        raise ValueError(f"n_stages must be >= 1, got {n_stages}")
    if n_layer < n_stages:
        raise ValueError(
            f"cannot split {n_layer} layers into {n_stages} stages")
    base, rem = divmod(n_layer, n_stages)
    ranges, lo = [], 0
    for s in range(n_stages):
        hi = lo + base + (1 if s < rem else 0)
        ranges.append((lo, hi))
        lo = hi
    return ranges


# ----------------------------------------------------------- gang factoring
@dataclasses.dataclass(frozen=True)
class GangCoords:
    """One worker's position in the 3D factoring dp × pp × tp.

    Replica-major layout over world ranks: with ``g`` workers per stage
    gang, rank r maps to replica ``r // (P*g)``, stage ``(r // g) % P``,
    in-gang index ``r % g``.  All stage gangs of one replica are
    contiguous, so a replica is a contiguous rank block — the per-replica
    data shard is then just a contiguous row slice of the global batch."""
    replica: int
    stage: int
    gang_rank: int
    dp: int
    n_stages: int
    gang_size: int

    def dp_group_name(self, job: str) -> str:
        """Name (= KV-rendezvous key under ``collective/``) of this
        stage's cross-replica collective group: one persistent group per
        stage carrying the gradient allreduce, namespaced by job so two
        concurrent trainers never collide."""
        return f"train/{job}/stage{self.stage}/dp"


def factor_gang(world_rank: int, world_size: int, *, dp: int,
                n_stages: int) -> GangCoords:
    """Factor a flat trainer world into dp replicas × n_stages stage
    gangs (replica-major).  ``world_size`` must be divisible by
    ``dp * n_stages``; the quotient is the per-stage gang size."""
    worlds = dp * n_stages
    if dp < 1 or n_stages < 1:
        raise ValueError(f"dp={dp} and n_stages={n_stages} must be >= 1")
    if world_size % worlds:
        raise ValueError(
            f"world size {world_size} not divisible by dp*stages={worlds}")
    gang_size = world_size // worlds
    if not 0 <= world_rank < world_size:
        raise ValueError(f"rank {world_rank} out of range")
    w = world_rank // gang_size
    return GangCoords(replica=w // n_stages, stage=w % n_stages,
                      gang_rank=world_rank % gang_size, dp=dp,
                      n_stages=n_stages, gang_size=gang_size)


# ------------------------------------------------- graceful mesh degradation
def pipeline_mesh(devices=None, *, max_dp: Optional[int] = None):
    """A gang-local mesh for one stage, shaped to whatever devices the gang
    owns: pod slice -> (dp, tp) rectangle, four chips -> 2x2, two -> 1x2,
    one chip -> 1x1 (SNIPPETS.md [2] ladder).  Axis names match
    ``gpt_partition_rules`` so the same stage program runs unchanged at
    every scale; unused axes stay at size 1."""
    import jax

    devs = list(devices) if devices is not None else jax.devices()
    n = len(devs)
    if n >= 8:
        dp, tp = 2, n // 2
    elif n >= 4:
        dp, tp = 2, 2
    elif n >= 2:
        dp, tp = 1, 2
    else:
        dp, tp = 1, 1
    if max_dp is not None and dp > max_dp:
        tp, dp = dp * tp // max_dp, max_dp
    return build_mesh(MeshConfig(dp=dp, tp=tp), devices=devs)


# ------------------------------------------------- shard / gather fn builder
def make_shard_and_gather_fns(partition_specs, mesh, dtype_specs=None):
    """Per-leaf shard/gather callables for a pytree of PartitionSpecs
    (SNIPPETS.md [3] shape of the idea).

    ``shard_fns``: host value -> global jax.Array under the leaf's
    NamedSharding (multi-process safe via host_to_global), optionally cast
    to the matching ``dtype_specs`` leaf.  ``gather_fns``: sharded array ->
    full host ndarray (replicated gather then device_get), optionally cast
    back — the checkpoint-interchange primitive that lets an N-stage shard
    set restore onto a different stage count.
    """
    import jax
    from jax.sharding import NamedSharding, PartitionSpec

    def _make_pair(spec, dtype):
        sharding = NamedSharding(mesh, spec)
        repl = NamedSharding(mesh, PartitionSpec())

        def shard_fn(x):
            arr = x if dtype is None else np.asarray(x).astype(dtype)
            return host_to_global(arr, sharding)

        def gather_fn(x):
            full = jax.jit(lambda t: t, out_shardings=repl)(x)
            out = np.asarray(jax.device_get(full))
            return out if dtype is None else out.astype(dtype)

        return shard_fn, gather_fn

    if dtype_specs is None:
        pairs = jax.tree_util.tree_map(
            lambda s: _make_pair(s, None), partition_specs,
            is_leaf=lambda x: isinstance(x, PartitionSpec))
    else:
        pairs = jax.tree_util.tree_map(
            lambda s, d: _make_pair(s, d), partition_specs, dtype_specs,
            is_leaf=lambda x: isinstance(x, PartitionSpec))
    shard_fns = jax.tree_util.tree_map(
        lambda p: p[0], pairs, is_leaf=lambda x: isinstance(x, tuple))
    gather_fns = jax.tree_util.tree_map(
        lambda p: p[1], pairs, is_leaf=lambda x: isinstance(x, tuple))
    return shard_fns, gather_fns


# --------------------------------------------------------- GPT-2 stage module
class GPT2StageModule:
    """One pipeline stage of ``GPT2LMModel``, keyed by canonical param names.

    Stage 0 owns the embeddings (wte/wpe) plus its block range; the last
    stage owns its blocks plus ln_f/lm_head and computes the loss.  The
    forward is built from the SAME flax modules GPT2LMModel composes
    (``Block``/``LayerNorm``/``Dense`` applied with param sub-dicts), so a
    1-stage pipeline reproduces the monolithic model's math exactly.
    """

    def __init__(self, config, stage: int, n_stages: int):
        from ray_tpu.models.gpt2 import Block

        # the ring/flash kernels want an active SPMD mesh and block-aligned
        # shapes; stage programs run under plain GSPMD jit where the
        # reference impl is robust at any size
        if config.attention_impl != "reference":
            config = dataclasses.replace(config, attention_impl="reference")
        if config.moe_every:
            raise NotImplementedError("pipeline stages + MoE not composed yet")
        self.config = config
        self.stage = int(stage)
        self.n_stages = int(n_stages)
        self.lo, self.hi = stage_ranges(config.n_layer, n_stages)[self.stage]
        self.is_first = self.stage == 0
        self.is_last = self.stage == self.n_stages - 1
        self._block = Block(config, False)

    # ------------------------------------------------------------ params
    def param_keys(self) -> List[str]:
        keys = [f"h_{i}" for i in range(self.lo, self.hi)]
        if self.is_first:
            keys = ["wte", "wpe"] + keys
        if self.is_last:
            keys = keys + ["ln_f", "lm_head"]
        return keys

    def init_params(self, seed: int = 0) -> Dict[str, Any]:
        """Init the FULL model with a fixed seed and select this stage's
        slice — every stage derives from the same deterministic tree, so a
        1-stage and an N-stage job start from identical weights."""
        from ray_tpu.models.pretrain import init_params

        _, full = init_params(self.config, rng=_seed_key(seed))
        return self.select_params(full)

    def select_params(self, full_params: Dict[str, Any]) -> Dict[str, Any]:
        return {k: full_params[k] for k in self.param_keys()}

    # ----------------------------------------------------------- forward
    def forward(self, params, x, batch):
        """(params, carried activation, host batch) -> activation, or the
        scalar loss on the last stage."""
        import jax
        import jax.numpy as jnp
        from flax import linen as nn

        from ray_tpu.models.gpt2 import (lm_loss, mask_vocab_padding,
                                         padded_vocab)

        cfg = self.config
        if self.is_first:
            ids = batch["input_ids"]
            pos = jnp.arange(ids.shape[1])[None, :]
            x = params["wte"]["embedding"][ids].astype(cfg.dtype) + \
                params["wpe"]["embedding"][pos].astype(cfg.dtype)
        block = jax.remat(self._block.apply) if cfg.remat else self._block.apply
        for i in range(self.lo, self.hi):
            x = block({"params": params[f"h_{i}"]}, x)
        if not self.is_last:
            return x
        x = nn.LayerNorm(dtype=cfg.dtype, name="ln_f").apply(
            {"params": params["ln_f"]}, x)
        logits = nn.Dense(padded_vocab(cfg.vocab_size), use_bias=False,
                          dtype=cfg.dtype,
                          name="lm_head").apply({"params": params["lm_head"]}, x)
        return lm_loss(mask_vocab_padding(logits, cfg.vocab_size),
                       batch["targets"], batch.get("mask"))

    # ---------------------------------------------------------- sharding
    def specs(self, params):
        return match_partition_rules(gpt_partition_rules(), params)

    def shard_over(self, params, mesh):
        with mesh:
            return shard_pytree(params, self.specs(params), mesh)


def _seed_key(seed: int):
    import jax

    return jax.random.PRNGKey(seed)


# -------------------------------------------------- checkpoint shard helpers
_META_KEY = "__pipeline_meta__"


def flatten_params(params: Dict[str, Any]) -> Dict[str, np.ndarray]:
    """Nested canonical tree -> {'h_0/attn/qkv_proj/kernel': ndarray, ...}."""
    import jax

    flat = {}
    for path, leaf in jax.tree_util.tree_flatten_with_path(params)[0]:
        name = "/".join(str(getattr(k, "key", getattr(k, "idx", k)))
                        for k in path)
        flat[name] = np.asarray(leaf)
    return flat


def unflatten_params(flat: Dict[str, np.ndarray]) -> Dict[str, Any]:
    tree: Dict[str, Any] = {}
    for name, arr in flat.items():
        node = tree
        parts = name.split("/")
        for p in parts[:-1]:
            node = node.setdefault(p, {})
        node[parts[-1]] = arr
    return tree


def save_stage_shard(path: str, params: Dict[str, Any], *, stage: int,
                     n_stages: int, step: int,
                     gather_fns=None) -> None:
    """Write one stage's gathered params as an npz shard.  ``gather_fns``
    (from make_shard_and_gather_fns) pulls gang-sharded arrays back to full
    host ndarrays first; merged shards are stage-count independent."""
    import jax

    if gather_fns is not None:
        params = jax.tree_util.tree_map(
            lambda fn, x: fn(x), gather_fns, params)
    flat = flatten_params(params)
    flat[_META_KEY] = np.array([stage, n_stages, step], dtype=np.int64)
    np.savez(path, **flat)


def load_pipeline_checkpoint(ckpt_dir: str,
                             filename: str = "pipe_stage.npz"):
    """Merge every stage shard under a trainer checkpoint directory (the
    canonical dir plus the rank_<k>/ sibling shards _persist_checkpoint
    lays down) into (full param tree, step).  The union is keyed by
    canonical layer names, so the caller re-selects per-stage slices for
    ANY stage count."""
    import glob
    import os

    paths = sorted(glob.glob(os.path.join(ckpt_dir, filename)) +
                   glob.glob(os.path.join(ckpt_dir, "rank_*", filename)))
    if not paths:
        raise FileNotFoundError(
            f"no pipeline stage shards ({filename}) under {ckpt_dir}")
    flat: Dict[str, np.ndarray] = {}
    step = 0
    for p in paths:
        with np.load(p) as z:
            for k in z.files:
                if k == _META_KEY:
                    step = max(step, int(z[k][2]))
                else:
                    flat[k] = z[k]
    return unflatten_params(flat), step

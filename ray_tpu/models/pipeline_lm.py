"""Pipeline-parallel GPT-2 pretraining: GPipe over ``pp`` composed with dp/tp.

The reference has no pipeline engine in core (SURVEY §2.3 — PP "absent from
core"; its intended substrate is compiled DAGs + NCCL channels,
reference: python/ray/dag/compiled_dag_node.py:480,
experimental/channel/torch_tensor_nccl_channel.py:191).  The TPU-native
design needs no channel runtime: transformer blocks are stacked into S stage
groups whose params carry a leading ``pp``-sharded stage dim; every rank runs
the same program under ``shard_map`` with ONLY ``pp`` manual (dp/tp stay
under GSPMD, so batch sharding and Megatron-style tp compose untouched);
activations rotate ranks via ``jax.lax.ppermute`` in a static fill-drain
schedule (`parallel/pipeline.py`).

Embedding and LM head run replicated-per-pp-rank (their FLOPs are small next
to the blocks); their grads stay correct because every rank computes the same
values.
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Any, Dict, Optional

import jax
import jax.numpy as jnp
import numpy as np
import optax

from ray_tpu.models.gpt2 import (Block, GPT2Config, GPT2LMModel, lm_loss,
                                 mask_vocab_padding)
from ray_tpu.models.pretrain import make_optimizer
from ray_tpu.parallel.mesh import MeshConfig, build_mesh
from ray_tpu.parallel.pipeline import pipeline_apply


def split_lm_params(params: Dict[str, Any], n_layer: int, n_stages: int):
    """Full GPT2LMModel param tree -> (outer, stacked_blocks).

    outer holds embeddings + final ln + head (replicated); stacked_blocks is
    the per-block trees stacked to leading dims (S, K) for S stages of K
    blocks each.
    """
    assert n_layer % n_stages == 0, (n_layer, n_stages)
    k = n_layer // n_stages
    blocks = [params[f"h_{i}"] for i in range(n_layer)]
    outer = {name: sub for name, sub in params.items()
             if not name.startswith("h_")}
    # stack blocks within a stage -> (K, ...), then stages -> (S, K, ...)
    stages = []
    for s in range(n_stages):
        stages.append(jax.tree_util.tree_map(
            lambda *ls: jnp.stack(ls), *blocks[s * k:(s + 1) * k]))
    stacked = jax.tree_util.tree_map(lambda *ls: jnp.stack(ls), *stages)
    return outer, stacked


def merge_lm_params(outer, stacked, n_layer: int, n_stages: int):
    """Inverse of split_lm_params (for checkpoint interchange)."""
    k = n_layer // n_stages
    params = dict(outer)
    for s in range(n_stages):
        for j in range(k):
            params[f"h_{s * k + j}"] = jax.tree_util.tree_map(
                lambda a: a[s, j], stacked)
    return params


def stacked_block_specs(stacked, mesh_axes=("tp", "fsdp")):
    """PartitionSpecs for the stacked block tree: leading stage dim on
    ``pp``; the Megatron tp/fsdp rules of ``gpt_partition_rules`` applied to
    the trailing weight dims (kernels are (S, K, in, out))."""
    from jax.sharding import PartitionSpec as P

    def spec(path, leaf):
        name = "/".join(str(getattr(p, "key", p)) for p in path)
        if leaf.ndim < 4:  # (S, K) scalars / (S, K, d) biases+ln
            return P("pp")
        if "qkv_proj" in name or "fc_in" in name:
            return P("pp", None, "fsdp", "tp")
        if "out_proj" in name or "fc_out" in name:
            return P("pp", None, "tp", "fsdp")
        return P("pp")

    return jax.tree_util.tree_map_with_path(spec, stacked)


class PipelinedPretrainer:
    """ShardedPretrainer counterpart for meshes with pp > 1.

    State = ((outer_params, stacked_blocks), opt_state); one jitted
    fwd+bwd+adamw step; microbatch count M defaults to 2*S (bubble fraction
    (S-1)/(M+S-1)).
    """

    def __init__(self, config: GPT2Config,
                 mesh_config: Optional[MeshConfig] = None,
                 lr: float = 3e-4, devices=None, total_steps: int = 10_000,
                 n_microbatches: Optional[int] = None):
        assert config.moe_every == 0, "pp + MoE not composed yet"
        self.config = config
        self.mesh = build_mesh(mesh_config or MeshConfig(pp=2),
                               devices=devices)
        self.n_stages = int(self.mesh.shape["pp"])
        assert self.n_stages > 1, "use ShardedPretrainer for pp=1"
        self.n_micro = n_microbatches or 2 * self.n_stages
        # blocks run inside shard_map where the sp axis is not manual;
        # flash/ring kernels want aligned shapes — the reference impl is
        # robust at any size and the pipeline's win is orthogonal
        config = dataclasses.replace(config, attention_impl="reference")
        self._block = Block(config)
        model = GPT2LMModel(config)
        dummy = jnp.zeros((1, min(8, config.n_positions)), jnp.int32)
        params = model.init(jax.random.PRNGKey(0), dummy)["params"]
        outer, stacked = split_lm_params(params, config.n_layer,
                                         self.n_stages)

        from jax.sharding import NamedSharding, PartitionSpec as P

        self.outer_specs = jax.tree_util.tree_map(lambda _: P(), outer)
        self.block_specs = stacked_block_specs(stacked)
        self.tx = make_optimizer(lr, total_steps=total_steps)
        pstate = (outer, stacked)
        opt_state = self.tx.init(pstate)
        param_specs = (self.outer_specs, self.block_specs)
        # optax state trees contain copies of the param tree (adam mu/nu)
        # plus scalars; give the copies the param specs, replicate the rest.
        self.opt_specs = _match_opt_specs(opt_state, pstate, param_specs)

        with self.mesh:
            pstate = _shard_tree(pstate, param_specs, self.mesh)
            opt_state = _shard_tree(opt_state, self.opt_specs, self.mesh)
        self.state = (pstate, opt_state)

        self.batch_sharding = {
            "input_ids": NamedSharding(self.mesh, P(("dp", "fsdp"))),
            "targets": NamedSharding(self.mesh, P(("dp", "fsdp"))),
        }
        state_shardings = (
            jax.tree_util.tree_map(
                lambda s: NamedSharding(self.mesh, s), param_specs),
            jax.tree_util.tree_map(
                lambda s: NamedSharding(self.mesh, s), self.opt_specs),
        )
        self._step = jax.jit(
            functools.partial(_pp_train_step, self),
            in_shardings=(state_shardings, self.batch_sharding),
            out_shardings=(state_shardings, None),
            donate_argnums=(0,),
        )

    # ------------------------------------------------------------ forward
    def forward(self, pstate, input_ids):
        outer, stacked = pstate
        cfg = self.config
        B, S = input_ids.shape
        pos = jnp.arange(S)[None, :]
        x = outer["wte"]["embedding"][input_ids].astype(cfg.dtype) + \
            outer["wpe"]["embedding"][pos].astype(cfg.dtype)

        M = self.n_micro
        assert B % M == 0, f"batch {B} not divisible by microbatches {M}"
        xs = x.reshape(M, B // M, S, cfg.n_embd)

        def stage_fn(stage_params, h):
            # stage_params: (K, ...) block trees; scan the K blocks
            def body(carry, bp):
                out = self._block.apply({"params": bp}, carry)
                return out, None

            h, _ = jax.lax.scan(body, h, stage_params)
            return h

        ys = pipeline_apply(stage_fn, stacked, xs, self.mesh, axis="pp")
        y = ys.reshape(B, S, cfg.n_embd)

        # final LN + head (replicated)
        ln = outer["ln_f"]
        mean = y.mean(-1, keepdims=True)
        var = ((y - mean) ** 2).mean(-1, keepdims=True)
        y = (y - mean) * jax.lax.rsqrt(var + 1e-6)
        y = y * ln["scale"] + ln["bias"]
        logits = y.astype(cfg.dtype) @ outer["lm_head"]["kernel"].astype(cfg.dtype)
        return mask_vocab_padding(logits, cfg.vocab_size)

    def shard_batch(self, batch):
        return {k: jax.device_put(jnp.asarray(v), self.batch_sharding[k])
                for k, v in batch.items() if k in self.batch_sharding}

    def step(self, batch: Dict[str, Any]):
        with self.mesh:
            self.state, loss = self._step(self.state, self.shard_batch(batch))
        return loss

    def tokens_per_batch(self, batch) -> int:
        return int(batch["input_ids"].size)


def _pp_train_step(trainer: PipelinedPretrainer, state, batch):
    pstate, opt_state = state

    def _loss(p):
        logits = trainer.forward(p, batch["input_ids"])
        return lm_loss(logits, batch["targets"], batch.get("mask"))

    loss, grads = jax.value_and_grad(_loss)(pstate)
    updates, opt_state = trainer.tx.update(grads, opt_state, pstate)
    pstate = optax.apply_updates(pstate, updates)
    return (pstate, opt_state), loss


def _shard_tree(tree, specs, mesh):
    from jax.sharding import NamedSharding

    return jax.tree_util.tree_map(
        lambda leaf, s: jax.device_put(leaf, NamedSharding(mesh, s)),
        tree, specs)


def _match_opt_specs(opt_state, pstate, param_specs):
    """Specs for an optax state: subtrees shaped like the param tree get the
    param specs; everything else (counts, schedules) replicates."""
    from jax.sharding import PartitionSpec as P

    pleaves = jax.tree_util.tree_structure(pstate)

    def per_node(node):
        try:
            if jax.tree_util.tree_structure(node) == pleaves:
                return param_specs
        except Exception:
            pass
        return None

    # optax states are tuples/namedtuples of either param-shaped trees or
    # scalars; walk one level deep.
    def walk(node):
        mapped = per_node(node)
        if mapped is not None:
            return mapped
        if isinstance(node, tuple) and not hasattr(node, "shape"):
            out = tuple(walk(c) for c in node)
            if hasattr(node, "_fields"):  # namedtuple
                return type(node)(*out)
            return out
        return jax.tree_util.tree_map(lambda _: P(), node)

    return walk(opt_state)

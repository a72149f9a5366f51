"""Sharded pretraining step: the program every cell of ``BENCHMARK.json`` runs.

Build a (dp, fsdp, sp, tp) mesh, shard params by ``gpt_partition_rules``, and
run a fused forward+backward+optimizer step under one jit.  XLA/GSPMD inserts
the ICI collectives (grad reduce over dp/fsdp, weight all-gathers for
tp/fsdp, ring ppermute for sp attention).  ``ShardedPretrainer`` is what a
train worker holds.

The model's configuration chooses the objective.  ``"next_token"`` (every
model but one): the cross entropy of ``targets`` under a causal model.
With ``LlamaConfig.n_pred_heads`` > 1 it is that of every head, head ``r``
against the token ``r + 1`` ahead, all weighted alike
(``gpt2.shifted_heads_loss``).
``"block_diffusion"`` (``LlamaConfig.objective``): the step noises the
batch's ``input_ids`` itself, on the device, from a key it folds its own step
count into (``noise_blocks``), runs the noised and the clean copy through the
model side by side, and takes the cross entropy of the tokens that were masked,
each weighted by one over its block's noise level; ``targets`` is not read.
With ``LlamaConfig.n_mtp_modules`` > 0 the next-token objective is ``L_main +
MTP_WEIGHT * L_mtp``: ``L_mtp`` the mean over the prediction modules behind
the trunk of each one's cross entropy, module ``k`` at position ``t`` against
the token ``t + k + 2`` (``gpt2.ahead_loss``).
"""

from __future__ import annotations

import dataclasses
from typing import Any, Dict, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
import optax
from jax.sharding import NamedSharding, PartitionSpec as P

from ray_tpu._private import flight_recorder
from ray_tpu.models.gpt2 import (GPT2Config, GPT2LMModel, ahead_loss, lm_loss,
                                 shifted_heads_loss)
from ray_tpu.parallel.mesh import MeshConfig, build_mesh
from ray_tpu.parallel.sharding import (
    gpt_partition_rules,
    host_to_global,
    match_partition_rules,
)
from ray_tpu.util.tracing import profiler_span

# the prediction modules' weight in the objective (``n_mtp_modules``):
# DeepSeek-V3's first value, assumed for the one configuration that has
# modules (Xing4.0 publishes none).  A constant until a second value exists
MTP_WEIGHT = 0.3


def make_optimizer(lr: float = 3e-4, weight_decay: float = 0.1,
                   warmup: int = 100, total_steps: int = 10_000):
    sched = optax.warmup_cosine_decay_schedule(0.0, lr, warmup, max(total_steps, warmup + 1))
    return optax.chain(
        optax.clip_by_global_norm(1.0),
        optax.adamw(sched, b1=0.9, b2=0.95, weight_decay=weight_decay),
    )


def _model_family(config):
    """(model_class, partition_rules_fn) by config type — GPT-2 and the
    Llama family share the whole sharded-pretrain stack."""
    from ray_tpu.models import llama

    if isinstance(config, llama.LlamaConfig):
        return llama.LlamaLMModel, llama.llama_partition_rules
    return GPT2LMModel, gpt_partition_rules


def init_params(config, rng=None):
    cls, _ = _model_family(config)
    model = cls(config)
    # Param shapes are independent of the attention impl; init with the
    # reference impl so initialization never needs an active mesh (ring
    # attention requires one) nor block-aligned dummy shapes (flash).
    init_model = cls(dataclasses.replace(config, attention_impl="reference"))
    rng = rng if rng is not None else jax.random.PRNGKey(0)
    dummy = jnp.zeros((1, min(8, config.n_positions)), jnp.int32)
    return model, init_model.init(rng, dummy)["params"]


def noise_blocks(key, ids, block: int, mask_id: int, t_min: float):
    """Block diffusion's forward process on a batch of rows (rows, L): each
    block of ``block`` positions draws its own noise level ``t`` uniformly
    from ``(t_min, 1]`` (the linear schedule: a token is masked with
    probability ``t``), and each of its tokens is replaced by ``mask_id``
    independently with that probability.  -> (``x_t``, which positions were
    masked, the loss weights: ``1 / t`` on the masked positions, 0 elsewhere).
    The same key gives the same noise."""
    with jax.named_scope("noise"):
        rows, length = ids.shape
        level_key, mask_key = jax.random.split(key)
        # uniform() is in [0, 1): 1 - u is in (0, 1]
        t = t_min + (1.0 - t_min) * (1.0 - jax.random.uniform(
            level_key, (rows, length // block), jnp.float32))
        t = jnp.repeat(t, block, axis=1)
        masked = jax.random.uniform(mask_key, ids.shape, jnp.float32) < t
        return (jnp.where(masked, jnp.asarray(mask_id, ids.dtype), ids),
                masked, jnp.where(masked, 1.0 / t, 0.0))


def _is_block_diffusion(config) -> bool:
    return getattr(config, "objective", "next_token") == "block_diffusion"


def _noised(config, batch, key):
    """``batch`` with ``x_t`` and ``weights`` drawn from ``key``."""
    x_t, _, weights = noise_blocks(
        key, batch["input_ids"], config.diffusion_block, config.mask_token_id,
        config.diffusion_t_min)
    return dict(batch, x_t=x_t, weights=weights)


def _model_inputs(config, batch):
    """(what the model is applied to, the targets, their weights, what the
    weighted sum is divided by: None for the weights' own sum)."""
    if _is_block_diffusion(config):
        # [x_t ; x] -> logits at x_t's positions, each predicting the token
        # that was there (no shift); the mean is over every data token
        ids = batch["input_ids"]
        return (jnp.concatenate([batch["x_t"], ids], axis=1), ids,
                batch["weights"], ids.size)
    return batch["input_ids"], batch["targets"], batch.get("mask"), None


def loss_fn(model: GPT2LMModel, params, batch):
    """The language-model cross entropy, for every model.  Under the
    block-diffusion objective ``batch`` holds ``x_t`` and ``weights`` as
    ``noise_blocks`` gives them, and the loss is
    ``sum(weights * nll) / input_ids.size``.  With prediction modules
    (``n_mtp_modules``) it is ``L_main + MTP_WEIGHT * L_mtp``."""
    return _losses(model, params, batch)[0]


def _losses(model, params, batch, sown: bool = False):
    """One forward pass -> (the loss, its terms where it has more than one:
    ``loss_main`` and ``loss_mtp``, what the modules sowed if ``sown``)."""
    cfg = model.config
    inputs, targets, weights, total = _model_inputs(cfg, batch)
    modules = getattr(cfg, "n_mtp_modules", 0)
    out = model.apply({"params": params}, inputs,
                      **(dict(predict_ahead=True) if modules else {}),
                      **(dict(mutable=["intermediates"]) if sown else {}))
    out, intermediates = (out[0], out[1]["intermediates"]) if sown \
        else (out, None)
    if not modules:
        return (_cross_entropy(cfg, out, targets, weights, total), {},
                intermediates)
    logits, ahead = out
    main = _cross_entropy(cfg, logits, targets, weights, total)
    mtp = sum(ahead_loss(a, targets, weights, k + 1)
              for k, a in enumerate(ahead)) / modules
    return (main + MTP_WEIGHT * mtp, {"loss_main": main, "loss_mtp": mtp},
            intermediates)


def _cross_entropy(config, logits, targets, weights, total):
    """``lm_loss``, or where the head scores more than one token ahead
    (``LlamaConfig.n_pred_heads``) the mean over every head's terms."""
    heads = getattr(config, "n_pred_heads", 1)
    if heads > 1:
        return shifted_heads_loss(logits, targets, weights, heads,
                                  config.vocab_size)
    return lm_loss(logits, targets, weights, total)


def objective_fn(model, params, batch, key=None):
    """What ``train_step`` differentiates, and what it reports beside it:
    (cross entropy + the MoE layers' auxiliary terms, (cross entropy, the
    step's MoE statistics)); with prediction modules the objective is
    ``L_main + MTP_WEIGHT * L_mtp`` (+ the auxiliary terms), what is reported
    ``L_main``, and both terms are among the statistics (``loss_main``,
    ``loss_mtp``), as are the hyper-connections' (``hc_res_row_err``,
    ``hc_pre_max``) where the residual path is more than one stream.  A dense
    model on one stream has no such terms and no statistics, and its
    objective is ``loss_fn``.  Under the block-diffusion objective a batch
    that brings no ``x_t`` is noised here, from ``key``."""
    cfg = model.config
    if _is_block_diffusion(cfg) and "x_t" not in batch:
        batch = _noised(cfg, batch, key)
    sown = cfg.moe_every > 0 or "sparse" in getattr(cfg, "mlp_types", ()) \
        or getattr(cfg, "hc_mult", 1) > 1
    loss, terms, intermediates = _losses(model, params, batch, sown)
    reported = terms.get("loss_main", loss)
    if not sown:
        return loss, (reported, terms)
    from ray_tpu.models.moe import collect_aux

    aux, stats = collect_aux(intermediates,
                             getattr(cfg, "router_aux_weight", 0.0),
                             getattr(cfg, "router_z_weight", 0.0))
    return loss + aux, (reported, {**stats, **terms})


def _hold_state(new, old):
    """``new`` with every leaf that is state and not a weight as ``old`` has
    it: a routed layer's ``selection_bias`` (models/moe.py), which no gradient
    reaches and which weight decay must not shrink either.  A tree without
    one comes back as it is."""
    return jax.tree_util.tree_map_with_path(
        lambda path, n, o: o if getattr(path[-1], "key", None)
        == "selection_bias" else n, new, old)


def train_step(model, tx, state, batch):
    """state = (params, opt_state). One fused fwd+bwd+update ->
    (state, the cross entropy, the step's MoE statistics: ``{}`` for a dense
    model, else ``load_balance``, ``z``, ``max_load`` and, where the layers
    hold a part of their experts, ``moe_rows_held``, as device scalars)."""
    params, opt_state = state
    if _is_block_diffusion(model.config):
        # the step's own noise, drawn before (and outside) the differentiated
        # forward: the optimizer's count of steps, folded into the key
        count = optax.tree_utils.tree_get_all_with_path(opt_state, "count")
        batch = _noised(model.config, batch, jax.random.fold_in(
            jax.random.PRNGKey(0), count[0][1]))
    (_, (loss, stats)), grads = jax.value_and_grad(
        lambda p: objective_fn(model, p, batch), has_aux=True)(params)
    with jax.named_scope("optimizer"):
        updates, opt_state = tx.update(grads, opt_state, params)
        params = _hold_state(optax.apply_updates(params, updates), params)
    return (params, opt_state), loss, stats


class ShardedStep(NamedTuple):
    step: Any         # jitted (state, batch) -> (state, loss, MoE statistics)
    model: Any        # the module the step applies
    init_state: Any   # () -> (params, opt_state), seeded; jit it into `state`'s shardings
    state: Any        # (params, opt_state) as ShapeDtypeStructs with shardings
    batch_sharding: Dict[str, NamedSharding]


def sharded_train_step(config, mesh, tx) -> ShardedStep:
    """The jitted train step for ``config`` on ``mesh`` and the layouts it
    runs under, built from shapes alone — nothing is allocated on a device.

    ``ShardedPretrainer`` fills the state in and steps it; the compile-only
    pre-flight (tests/test_chip_compile.py) lowers the very same step against
    a TPU topology with no chip attached.  Trace/run it under
    ``jax.set_mesh(mesh)``: the attention kernels and the residual-stream
    constraint read the ambient mesh.
    """
    model_cls, rules_fn = _model_family(config)
    model = model_cls(config)

    def init_state():
        params = init_params(config)[1]
        return params, tx.init(params)

    shapes = jax.eval_shape(init_state)
    state = jax.tree_util.tree_map(
        lambda x, spec: jax.ShapeDtypeStruct(
            x.shape, x.dtype, sharding=NamedSharding(mesh, spec)),
        shapes, match_partition_rules(rules_fn(), shapes))
    state_shardings = jax.tree_util.tree_map(lambda a: a.sharding, state)
    batch_sharding = {k: NamedSharding(mesh, P(("dp", "fsdp"), "sp"))
                      for k in ("input_ids", "targets")}

    def pretrain_step(state, batch):  # the name the compiled program carries
        return train_step(model, tx, state, batch)

    step = jax.jit(
        pretrain_step,
        in_shardings=(state_shardings, batch_sharding),
        out_shardings=(state_shardings, None, None),
        donate_argnums=(0,),
    )
    return ShardedStep(step, model, init_state, state, batch_sharding)


class ShardedPretrainer:
    """Owns mesh + sharded state + compiled step for one jax (multi-)process."""

    def __init__(self, config, mesh_config: Optional[MeshConfig] = None,
                 lr: float = 3e-4, devices=None, total_steps: int = 10_000):
        # the mesh, the optimizer, and the step with its layouts from shapes
        # alone (an eval_shape of the whole state, the partition rules)
        with flight_recorder.timed("bringup.trainer_build"):
            self.mesh = build_mesh(mesh_config or MeshConfig(),
                                   devices=devices)
            self.tx = make_optimizer(lr, total_steps=total_steps)
            self._compiled, self.model, init_state, layout, \
                self.batch_sharding = sharded_train_step(
                    config, self.mesh, self.tx)
            self.config = self.model.config
            self.param_specs, self.opt_specs = jax.tree_util.tree_map(
                lambda a: a.sharding.spec, layout)
        self._step = self._first_run
        # Initialized under jit straight into its shards: no device ever
        # holds the whole state, and every process of a multi-host mesh
        # builds only what it addresses (same seed, same values: the RNG
        # does not depend on the layout).
        with flight_recorder.timed("bringup.state_init"):
            self.state = jax.block_until_ready(jax.jit(
                init_state, out_shardings=jax.tree_util.tree_map(
                    lambda a: a.sharding, layout))())
        self._steps = 0     # calls of step(): the profiler's step number
        # the last step's MoE statistics (load_balance, z, max_load, and
        # moe_rows_held where the layers hold a part of their experts), device
        # scalars that nothing has synchronised on; {} for a dense model
        self.moe_stats: Dict[str, Any] = {}

    # -------------------------------------------------- sharded checkpoints
    def save_checkpoint(self, path: str) -> None:
        """Write the full training state (params + optimizer) as a sharded
        orbax checkpoint: each host writes its own shards, and restore lays
        them back out over the CURRENT mesh (reference analogue: the
        framework-level checkpointing the reference delegates to its
        training libraries; here the multi-chip state is ours to persist —
        SURVEY §5.4)."""
        import os

        import orbax.checkpoint as ocp

        ckptr = ocp.StandardCheckpointer()
        ckptr.save(os.path.abspath(path), self.state)
        ckptr.wait_until_finished()
        ckptr.close()

    def restore_checkpoint(self, path: str) -> None:
        """Restore into THIS trainer's mesh/shardings: the checkpoint may
        have been written under a different host count — orbax reshards on
        load against the abstract target built from the live state."""
        import os

        import jax as _jax
        import orbax.checkpoint as ocp

        abstract = _jax.tree_util.tree_map(
            lambda x: _jax.ShapeDtypeStruct(x.shape, x.dtype,
                                            sharding=x.sharding),
            self.state)
        ckptr = ocp.StandardCheckpointer()
        self.state = ckptr.restore(os.path.abspath(path), abstract)
        ckptr.close()

    def shard_batch(self, batch: Dict[str, Any]):
        with profiler_span("step/shard_batch"):
            return {k: host_to_global(jnp.asarray(v), self.batch_sharding[k])
                    for k, v in batch.items() if k in self.batch_sharding}

    def step(self, batch: Dict[str, Any]):
        """One step, enqueued: the loss comes back as a device array.  In a
        profiler session each call is one step of the trace's Steps line,
        with the host's two parts of it — laying the batch out, and the call
        that enqueues the compiled program — as spans under it."""
        with profiler_span("step", step_num=self._steps), \
                jax.set_mesh(self.mesh):
            self._steps += 1
            batch = self.shard_batch(batch)
            with profiler_span("step/dispatch"):
                self.state, loss, self.moe_stats = self._step(self.state,
                                                              batch)
        return loss

    def _first_run(self, state, batch):
        """The step's first call, as the mark ``bringup.first_run``: its
        trace, lowering and build or load are ``compile`` records inside it,
        and what they leave of it is the program's first dispatch (its
        upload, its arguments' donation); the calls after it go to the
        jitted function itself."""
        self._step = self._compiled
        with flight_recorder.timed("bringup.first_run", "pretrain_step"):
            return self._compiled(state, batch)

    def lower(self, batch: Dict[str, Any]):
        """The step lowered for this batch's shapes: ``.compile()`` it for the
        HLO text and memory analysis of what ``step`` runs."""
        with jax.set_mesh(self.mesh):
            return self._compiled.lower(self.state, self.shard_batch(batch))

    def tokens_per_batch(self, batch) -> int:
        return int(batch["input_ids"].size)

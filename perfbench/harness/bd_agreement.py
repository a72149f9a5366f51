"""Program against plain reference for a block-diffusion cell, at published
widths, on the device, in set-up: ``agreement.py``'s check for an objective
that is not next-token and a mask that is not causal.

One seeded row per data-parallel replica at the cell's own length, noised by
the program's own ``noise_blocks`` from a key made of the cell's seed; both
sides are handed that ``x_t``, that masked set and those weights.  Under the
block mask a noised block sees itself and the clean blocks before it, and a
clean block the clean blocks up to itself, so both copies of the first
``prefix`` positions (a multiple of the block) depend on that prefix alone:
the program's first ``prefix`` noised-half logits at full length must equal
the reference's on the prefix, and with the weights zeroed past it so must the
loss (both divided by the full row's token count) and the gradients.  Beside
them, the held experts' assignments on those rows (``moe_rows_held``) are
counted on both sides.  The thresholds and their reason are in the
configuration file.
"""

from __future__ import annotations

from typing import Any, Dict, Optional

import numpy as np

from perfbench.harness import families, reference


class Checker:
    """The check for one trainer, built once: the three programs (the
    program's side, the reference's, the comparison) are traced and compiled
    on the first row and run on every later one.  ``wrong``: one of the
    family's wrong models in place of the reference (the on-chip script's
    controls)."""

    def __init__(self, trainer, config: Dict[str, Any], chips: int,
                 wrong: Optional[str] = None):
        import jax
        import jax.numpy as jnp

        from ray_tpu.models.pretrain import noise_blocks, objective_fn

        family = families.of(config)
        cfg = trainer.config
        self.trainer, self.limits = trainer, config["reference"]
        prefix, vocab = self.limits["prefix"], cfg.vocab_size
        assert prefix % cfg.diffusion_block == 0

        def program(params, ids, key):
            x_t, masked, weights = noise_blocks(
                key, ids, cfg.diffusion_block, cfg.mask_token_id,
                cfg.diffusion_t_min)
            weights = weights * (jnp.arange(ids.shape[1]) < prefix)
            batch = {"input_ids": ids, "x_t": x_t, "weights": weights}
            (_, (loss, _)), grads = jax.value_and_grad(
                lambda p: objective_fn(trainer.model, p, batch),
                has_aux=True)(params)
            logits = trainer.model.apply(
                {"params": params}, jnp.concatenate([x_t, ids], axis=1))
            # the held experts' assignments on the prefix alone, as the
            # reference counts them
            _, (_, stats) = objective_fn(trainer.model, params, {
                k: v[:, :prefix] for k, v in batch.items()})
            return ((logits[:, :prefix, :vocab].astype(jnp.float32), loss,
                     reference.global_norm(grads), stats["moe_rows_held"]),
                    x_t, weights, jnp.mean(masked[:, :prefix]))

        def plain(params, ids, x_t, weights):
            return family.logits_loss_gradnorm(
                params, ids[:, :prefix], x_t[:, :prefix], weights[:, :prefix],
                config, ids.size, chips, wrong)

        def compare(got, want):
            (gl, gloss, gnorm, grows), (wl, wloss, wnorm, wrows) = got, want
            return {
                "logits_rel_rms": jnp.sqrt(jnp.sum((gl - wl) ** 2)
                                           / jnp.sum(wl ** 2)),
                "loss_rel": jnp.abs(gloss - wloss) / jnp.abs(wloss),
                "grad_norm_rel": jnp.abs(gnorm - wnorm) / wnorm,
                "loss": wloss, "grad_norm": wnorm,
                "moe_rows_held": grows, "moe_rows_held_reference": wrows,
            }

        self._program, self._plain, self._compare = (
            jax.jit(f) for f in (program, plain, compare))

    def __call__(self, ids: np.ndarray, seed: int) -> Dict[str, Any]:
        """``ids``: (replicas, seq) token ids; ``seed`` makes the noise."""
        import jax

        trainer, limits = self.trainer, self.limits
        params = trainer.state[0]
        with jax.set_mesh(trainer.mesh):
            ids = jax.device_put(ids, trainer.batch_sharding["input_ids"])
            got, x_t, weights, masked_share = self._program(
                params, ids, jax.random.PRNGKey(seed % 2 ** 31))
            want = self._plain(params, ids, x_t, weights)
            out = {k: float(v) for k, v in self._compare(got, want).items()}
        out["masked_share"] = float(masked_share)
        out["ok"] = bool(
            out["logits_rel_rms"] <= limits["logits_rel_rms_max"]
            and out["loss_rel"] <= limits["loss_rel_max"]
            and out["grad_norm_rel"] <= limits["grad_norm_rel_max"])
        out["rows"], out["prefix"] = int(ids.shape[0]), limits["prefix"]
        return out


def check(trainer, config: Dict[str, Any], chips: int, ids: np.ndarray,
          seed: int) -> Dict[str, Any]:
    return Checker(trainer, config, chips)(ids, seed)

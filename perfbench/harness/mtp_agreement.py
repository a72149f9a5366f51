"""Program against plain reference for a cell whose head scores more than one
token ahead, at published widths, on the device, in set-up: ``agreement.py``'s
check where ``reference.logits_loss_gradnorm``'s one next-token loss cannot
stand for the objective, and the family brings its own
(``families/<family>.py::logits_loss_gradnorm``).

One seeded row per data-parallel replica at the cell's own length, so the
program's kernels run at the shape the window uses.  Attention is causal (a
window's own keys and the summaries of windows that are past), so the
program's first ``prefix`` logits at full length must equal the reference's on
the prefix alone — whole windows of it, so that the later ones meet summaries
— and with the objective's mask on those positions (a head's term counts where
the position it scores is inside the prefix) so must the loss of all heads and
the gradients.  What is compared: the first head's logits (relative RMS error
over its vocabulary), the loss, and the global gradient norm.  The thresholds
and their reason are in the configuration file.
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

    def __init__(self, trainer, config: Dict[str, Any],
                 wrong: Optional[str] = None):
        import jax
        import jax.numpy as jnp

        from ray_tpu.models.pretrain import loss_fn

        family = families.of(config)
        self.trainer, self.limits = trainer, config["reference"]
        prefix, vocab = self.limits["prefix"], config["vocab_size"]

        def program(params, batch):
            loss, grads = jax.value_and_grad(
                lambda p: loss_fn(trainer.model, p, batch))(params)
            logits = trainer.model.apply({"params": params},
                                         batch["input_ids"])
            return (logits[:, :prefix, :vocab].astype(jnp.float32), loss,
                    reference.global_norm(grads))

        def plain(params, ids, targets):
            logits, loss, norm = family.logits_loss_gradnorm(
                params, ids, targets, config, wrong)
            return logits[..., :vocab], loss, norm

        def compare(got, want):
            (gl, gloss, gnorm), (wl, wloss, wnorm) = got, want
            return {
                "logits_rel_rms": jnp.sqrt(jnp.sum((gl - wl) ** 2)
                                           / jnp.sum(wl ** 2)),
                "loss_rel": jnp.abs(gloss - wloss) / jnp.abs(wloss),
                "grad_norm_rel": jnp.abs(gnorm - wnorm) / wnorm,
                "loss": wloss, "grad_norm": wnorm,
            }

        self._program, self._plain, self._compare = (
            jax.jit(f) for f in (program, plain, compare))

    def program(self, rows: Dict[str, np.ndarray]):
        """The program's side on ``rows`` (``input_ids`` and ``targets`` of
        shape (replicas, seq)) -> (the batch on the device, its logits, loss
        and gradient norm): what every reference and wrong model is held
        against."""
        import jax

        trainer, prefix = self.trainer, self.limits["prefix"]
        layout = trainer.batch_sharding["input_ids"]
        mask = np.zeros(rows["input_ids"].shape, np.float32)
        mask[:, :prefix] = 1.0
        with jax.set_mesh(trainer.mesh):
            batch = {k: jax.device_put(v, layout)
                     for k, v in dict(rows, mask=mask).items()}
            return batch, self._program(trainer.state[0], batch)

    def against(self, batch, got) -> Dict[str, Any]:
        """``got`` against this checker's reference on the batch's prefix."""
        import jax

        trainer, limits = self.trainer, self.limits
        prefix = limits["prefix"]
        with jax.set_mesh(trainer.mesh):
            want = self._plain(trainer.state[0],
                               batch["input_ids"][:, :prefix],
                               batch["targets"][:, :prefix])
            out = {k: float(v) for k, v in self._compare(got, want).items()}
        out["ok"] = bool(
            out["logits_rel_rms"] <= limits["logits_rel_rms_max"]
            and out["loss_rel"] <= limits["loss_rel_max"]
            and out["grad_norm_rel"] <= limits["grad_norm_rel_max"])
        out["rows"], out["prefix"] = int(batch["mask"].shape[0]), int(prefix)
        return out

    def __call__(self, rows: Dict[str, np.ndarray]) -> Dict[str, Any]:
        return self.against(*self.program(rows))


def check(trainer, config: Dict[str, Any], rows: Dict[str, np.ndarray]
          ) -> Dict[str, Any]:
    return Checker(trainer, config)(rows)

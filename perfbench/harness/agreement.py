"""Program against plain reference, at published widths, on the device, in
set-up.

One seeded sequence per data-parallel replica at the cell's own sequence
length, so the program's kernels run at the shape the window uses.  Attention
is causal, so the program's first ``prefix`` logits at full length must equal
the reference's on the prefix alone, and with ``lm_loss``'s mask on those
positions so must the loss and the gradients.  What is compared: the logits
(relative RMS error over the unpadded vocabulary), the loss, and the global
gradient norm.  The thresholds and their reason are in the configuration file.
"""

from __future__ import annotations

from typing import Any, Dict

import numpy as np

from perfbench.harness import reference


def check(trainer, config: Dict[str, Any], rows: Dict[str, np.ndarray]
          ) -> Dict[str, Any]:
    """``rows``: ``input_ids`` and ``targets`` of shape (replicas, seq)."""
    import jax
    import jax.numpy as jnp

    from ray_tpu.models.pretrain import loss_fn

    limits = config["reference"]
    prefix, vocab = limits["prefix"], config["vocab_size"]
    params = trainer.state[0]
    layout = trainer.batch_sharding["input_ids"]
    mask = np.zeros(rows["input_ids"].shape, np.float32)
    mask[:, :prefix] = 1.0
    batch = {k: jax.device_put(v, layout)
             for k, v in dict(rows, mask=mask).items()}

    def program(params, batch):
        loss, grads = jax.value_and_grad(
            lambda p: loss_fn(trainer.model, p, batch))(params)
        logits = trainer.model.apply({"params": params}, batch["input_ids"])
        return (logits[:, :prefix, :vocab].astype(jnp.float32), loss,
                reference.global_norm(grads))

    def plain(params, ids, targets):
        return reference.logits_loss_gradnorm(params, ids, targets, config)

    def compare(got, want):
        (gl, gloss, gnorm), (wl, wloss, wnorm) = got, want
        return {
            "logits_rel_rms": jnp.sqrt(jnp.sum((gl - wl) ** 2)
                                       / jnp.sum(wl ** 2)),
            "loss_rel": jnp.abs(gloss - wloss) / jnp.abs(wloss),
            "grad_norm_rel": jnp.abs(gnorm - wnorm) / wnorm,
            "loss": wloss, "grad_norm": wnorm,
        }

    with jax.set_mesh(trainer.mesh):
        got = jax.jit(program)(params, batch)
        want = jax.jit(plain)(params, batch["input_ids"][:, :prefix],
                              batch["targets"][:, :prefix])
        out = {k: float(v) for k, v in jax.jit(compare)(got, want).items()}
    out["ok"] = bool(
        out["logits_rel_rms"] <= limits["logits_rel_rms_max"]
        and out["loss_rel"] <= limits["loss_rel_max"]
        and out["grad_norm_rel"] <= limits["grad_norm_rel_max"])
    out["rows"], out["prefix"] = int(mask.shape[0]), int(prefix)
    return out

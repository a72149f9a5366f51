"""python3 perfbench/tests/olmoe_on_chip.py [--seed N]   (on the chip; not a test)

What ``agreement.check`` cannot compare, because the harness differentiates
the cross entropy alone: at published widths, on one seeded row of 4096 tokens
and in one process that owns the chip,

- the program's auxiliary terms and ``max_load`` (``objective_fn``'s
  statistics, bf16 activations) against ``families/olmoe.py::aux_losses``
  (float32, matmul precision 'highest');
- the share of tokens whose top-8 set differs between the two routers;
- the agreement check of the cell against the reference as it is, and against
  three wrong models — top-k weights renormalised, top-7, a bf16 router
  softmax — which must land outside the configuration's limits.

Prints one JSON object.  Exits 1 without a TPU.
"""

from __future__ import annotations

import argparse
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)


def main() -> int:
    parser = argparse.ArgumentParser(description=__doc__)
    parser.add_argument("--seed", type=int, default=2500001)
    args = parser.parse_args()

    import jax
    import jax.numpy as jnp

    from perfbench.harness import agreement, families, manifest, reference
    from perfbench.harness.families import olmoe
    from perfbench.harness.tokens import ZipfStream
    from ray_tpu.models.pretrain import ShardedPretrainer, objective_fn
    from ray_tpu.parallel.mesh import MeshConfig

    if jax.default_backend() != "tpu":
        print("olmoe_on_chip: no TPU; nothing was run", file=sys.stderr)
        return 1
    cell = manifest.cell("olmoe-s4k-1chip")
    config, seq = cell.config, cell.traffic["seq"]
    trainer = ShardedPretrainer(
        families.of(config).model_config(config, 1), MeshConfig())
    rows = ZipfStream(config["vocab_size"], args.seed).rows(1, seq)
    params = trainer.state[0]
    batch = {k: jnp.asarray(v) for k, v in rows.items()}
    out = {"seed": args.seed, "device": jax.devices()[0].device_kind}

    def program(params, batch):
        _, (loss, stats) = objective_fn(trainer.model, params, batch)
        _, sown = trainer.model.apply(
            {"params": params}, batch["input_ids"],
            capture_intermediates=lambda m, _: m.name == "router")
        router = sown["intermediates"]["h_0"]["moe"]["router"]["__call__"][0]
        return loss, stats, router

    def plain(params, ids):
        with jax.default_matmul_precision("highest"):
            return (olmoe.aux_losses(params, ids, config),
                    olmoe._forward(params, ids, config)[1][0])

    with jax.set_mesh(trainer.mesh):
        loss, got, router = jax.jit(program)(params, batch)
        want, router_ref = jax.jit(plain)(params, batch["input_ids"])
    out["cross_entropy"] = float(loss)
    out["aux"] = {name: {"program": float(got[name]),
                         "reference": float(want[name]),
                         "rel": abs(float(got[name]) / float(want[name]) - 1)}
                  for name in want}

    def chosen(logits):
        k, e = config["num_experts_per_tok"], config["num_experts"]
        _, idx = jax.lax.top_k(jax.nn.softmax(logits, axis=-1), k)
        return jnp.sum(jax.nn.one_hot(idx, e), axis=-2)     # (B, S, E) of 0/1
    differ = jnp.sum(chosen(router) != chosen(router_ref), axis=-1) // 2
    prefix = config["reference"]["prefix"]
    out["flipped"] = {
        "tokens": int(differ.size),
        "share_of_tokens": float(jnp.mean(differ > 0)),
        "share_in_prefix": float(jnp.mean(differ[:, :prefix] > 0)),
        "experts_changed_per_flipped_token": float(
            jnp.sum(differ) / jnp.maximum(jnp.sum(differ > 0), 1)),
        "router_logit_max_abs_error": float(
            jnp.max(jnp.abs(router - router_ref))),
        "router_logit_std": float(jnp.std(router_ref)),
    }

    limits = config["reference"]

    def outside(a):
        return {k: bool(a[k] > limits[k + "_max"]) for k in
                ("logits_rel_rms", "loss_rel", "grad_norm_rel")}

    checks = {"as_published": config,
              "renormalised_top_k": dict(config, norm_topk_prob=True),
              "top_7": dict(config, num_experts_per_tok=7)}
    out["agreement"] = {}
    for name, wrong in checks.items():
        a = agreement.check(trainer, wrong, rows)
        out["agreement"][name] = dict(a, outside=outside(a))
    # a bf16 router softmax: the one control that is not a configuration key
    forward = olmoe._forward
    olmoe.logits = lambda p, i, c: forward(p, i, c, jnp.bfloat16)[0]
    a = agreement.check(trainer, config, rows)
    out["agreement"]["bf16_router_softmax"] = dict(a, outside=outside(a))
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())

"""python3 perfbench/tests/sdar_on_chip.py [--seed N] [--rows R] [--control-rows C]
(on the chip; not a test)

The runs behind the limits in ``configs/sdar-30b-a3b-chat.json``: at published
widths, in one process that owns the chip, the cell's own agreement check
(``bd_agreement.Checker``: noised-half logits, masked-token loss, gradient
norm, the held experts' assignments; one row of 4096 tokens, its noise from
the row's seed) on ``--rows`` seeded rows, and on the first ``--control-rows``
of them against each wrong model of ``families/sdar_moe.py::WRONG`` — the
noised block also seeing its own clean block, the noised block made causal
inside, weights renormalised over the held chosen experts only, top-7, 1/t
left out — which must land outside at least one limit, as must the reference
itself computed with float8 activations (``PRECISION_BELOW``: the nearest
precision below the configuration's bf16).  Beside them the
program's statistics on a whole row (``max_load``, ``moe_rows_held``).

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
    parser.add_argument("--seed", type=int, default=3100001)
    parser.add_argument("--rows", type=int, default=16)
    parser.add_argument("--control-rows", type=int, default=3)
    args = parser.parse_args()

    import jax
    import jax.numpy as jnp

    from perfbench.harness import bd_agreement, families, manifest
    from perfbench.harness.families import sdar_moe
    from perfbench.harness.tokens import ZipfStream
    from ray_tpu._private.platform import enable_compile_cache
    from ray_tpu.models.pretrain import ShardedPretrainer, objective_fn
    from ray_tpu.parallel.mesh import MeshConfig

    if jax.default_backend() != "tpu":
        print("sdar_on_chip: no TPU; nothing was run", file=sys.stderr)
        return 1
    enable_compile_cache()
    cell = manifest.cell("sdar-bd-s4k-1chip")
    config, seq = cell.config, cell.traffic["seq"]
    trainer = ShardedPretrainer(
        families.of(config).model_config(config, 1), MeshConfig())
    limits = config["reference"]
    out = {"seed": args.seed, "device": jax.devices()[0].device_kind,
           "limits": {k: v for k, v in limits.items() if k != "why"}}

    def outside(a):
        return {k: bool(a[k] > limits[k + "_max"]) for k in
                ("logits_rel_rms", "loss_rel", "grad_norm_rel")}

    seeds = [args.seed + 1000 * i for i in range(args.rows)]
    rows = {s: ZipfStream(trainer.config.vocab_size, s).rows(
        1, seq)["input_ids"] for s in seeds}
    as_published = bd_agreement.Checker(trainer, config, 1)
    out["agreement"] = {}
    for s in seeds:
        a = as_published(rows[s], s)
        out["agreement"][str(s)] = dict(a, outside=outside(a))
    keys = ("logits_rel_rms", "loss_rel", "grad_norm_rel")
    out["worst"] = {k: max(a[k] for a in out["agreement"].values())
                    for k in keys}
    out["rows_held_rel_max"] = max(
        abs(a["moe_rows_held"] / a["moe_rows_held_reference"] - 1)
        for a in out["agreement"].values())
    out["wrong"] = {}
    for wrong in sdar_moe.WRONG + (sdar_moe.PRECISION_BELOW,):
        checker = bd_agreement.Checker(trainer, config, 1, wrong)
        out["wrong"][wrong] = {}
        for s in seeds[:args.control_rows]:
            a = checker(rows[s], s)
            out["wrong"][wrong][str(s)] = dict(a, outside=outside(a))
        del checker
    out["every_wrong_model_is_outside"] = all(
        any(a["outside"].values())
        for runs in out["wrong"].values() for a in runs.values())

    # the program's own statistics on whole rows, noise from the step's key
    def stats(params, ids, key):
        return objective_fn(trainer.model, params, {"input_ids": ids},
                            key)[1]
    with jax.set_mesh(trainer.mesh):
        loss, got = jax.jit(stats)(
            trainer.state[0], jnp.asarray(rows[seeds[0]]),
            jax.random.PRNGKey(0))
    out["whole_row"] = dict({k: float(v) for k, v in got.items()},
                            loss=float(loss))
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())

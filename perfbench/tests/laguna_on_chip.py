"""python3 perfbench/tests/laguna_on_chip.py [--seed N] [--rows R] [--control-rows C] [--steps S]
(on the chip; not a test)

The runs behind the limits in ``configs/laguna-xs.2.json``: at published
widths, in one process that owns the chip, the cell's own agreement check
(``agreement.check``: the bf16 program on a seeded row of 8192 tokens of the
cell's traffic against ``families/laguna.py`` in float32 on its first 1,024
positions — half of them have keys the window masks out — logits, loss,
gradient norm) on ``--rows`` seeded rows, and on the first ``--control-rows``
of them against each wrong model of ``families/laguna.py::WRONG`` — the window
left off, a window of 513, the two rotary tables swapped, the gate left out,
softmax scores, the routed scale 1.0, the shared expert left out, top-7 —
which must land outside at least one limit on every row (but the window of
513, which bf16 cannot tell from 512: ``UNSEEN_IN_BF16``), as must the
reference itself computed with float8 activations (``PRECISION_BELOW``: the
nearest precision below the configuration's bf16).  Beside them the program's
routing statistics (``max_load``, ``moe_rows_held``, ``moe_buffer_rows``) on
the cell's own batches at initialisation and over ``--steps`` training steps,
the losses of those steps, and the device's peak memory.

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

MEASURES = ("logits_rel_rms", "loss_rel", "grad_norm_rel")


def main() -> int:
    parser = argparse.ArgumentParser(description=__doc__)
    parser.add_argument("--seed", type=int, default=3500001)
    parser.add_argument("--rows", type=int, default=16)
    parser.add_argument("--control-rows", type=int, default=3)
    parser.add_argument("--steps", type=int, default=24)
    args = parser.parse_args()

    import jax

    from perfbench.harness import agreement, families, manifest
    from perfbench.harness.families import laguna
    from perfbench.harness.tokens import ZipfStream
    from ray_tpu._private.platform import enable_compile_cache
    from ray_tpu.models.pretrain import ShardedPretrainer
    from ray_tpu.parallel.mesh import MeshConfig

    if jax.default_backend() != "tpu":
        print("laguna_on_chip: no TPU; nothing was run", file=sys.stderr)
        return 1
    enable_compile_cache()
    cell = manifest.cell("laguna-s8k-1chip")
    config, traffic = cell.config, cell.traffic
    limits = config["reference"]
    trainer = ShardedPretrainer(
        families.of(config).model_config(config, 1), MeshConfig())
    right = laguna.logits

    def check(rows, wrong=None):
        if wrong:   # the reference as the wrong model, the program as it is
            laguna.logits = lambda p, i, c: laguna._forward(
                p, i, c, wrong)[0][..., :c["vocab_size"]]
        try:
            a = agreement.check(trainer, config, rows)
        finally:
            laguna.logits = right
        a["outside"] = {k: bool(a[k] > limits[k + "_max"]) for k in MEASURES}
        # as the run goes, for a call that is cut before the object is printed
        print(wrong or "as_published", {k: a[k] for k in MEASURES},
              file=sys.stderr, flush=True)
        return a

    out = {"seed": args.seed, "device": jax.devices()[0].device_kind,
           "prefix": limits["prefix"],
           "limits": {k: limits[k + "_max"] for k in MEASURES},
           "as_published": [],
           "wrong": {w: [] for w in laguna.WRONG + (laguna.PRECISION_BELOW,)}}
    for r in range(args.rows):
        rows = ZipfStream(config["vocab_size"], args.seed + 1000 * r).rows(
            1, traffic["seq"])
        out["as_published"].append(check(rows))
        if r < args.control_rows:
            for wrong, runs in out["wrong"].items():
                runs.append(check(rows, wrong))
    out["worst"] = {k: max(a[k] for a in out["as_published"])
                    for k in MEASURES}
    out["wrong_outside"] = {
        wrong: {k: [a["outside"][k] for a in runs] for k in MEASURES}
        for wrong, runs in out["wrong"].items()}
    # (a window of 513 is run and reported like the others, and is not
    # expected outside: families/laguna.py::UNSEEN_IN_BF16)
    out["every_wrong_model_is_outside_on_every_row"] = all(
        any(a["outside"].values())
        for wrong, runs in out["wrong"].items() for a in runs
        if wrong not in laguna.UNSEEN_IN_BF16)

    # the timed path on the cell's own batches: the routing statistics at
    # initialisation and as the steps go, the losses, the memory's peak
    batches = ZipfStream(config["vocab_size"], args.seed).batches(
        traffic["rows_per_step"], traffic["seq"])
    out["steps"] = []
    for _ in range(args.steps):
        loss = float(trainer.step(next(batches)))
        out["steps"].append(dict(
            {k: float(v) for k, v in trainer.moe_stats.items()}, loss=loss))
    stats = jax.devices()[0].memory_stats() or {}
    out["memory"] = {k: int(stats[k]) for k in
                     ("peak_bytes_in_use", "bytes_limit") if k in stats}
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())

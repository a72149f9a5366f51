"""python3 perfbench/tests/xing4_on_chip.py [--seed N] [--rows R] [--control-rows C] [--steps S] [--wrong a,b,..] [--out NAME]
(on the chip; not a test)

The runs behind the limits in ``configs/xing4.0-29b-a4b.json``: at published
widths, in one process that owns the chip, the cell's own agreement check
(``agreement.check``: the bf16 program on a seeded row of 8,192 tokens of the
cell's traffic against ``families/xing4.py`` in float32 on its first
``reference.prefix`` positions — logits, loss, gradient norm) on ``--rows``
seeded rows, and on the first ``--control-rows`` of them against each wrong
model of ``families/xing4.py::WRONG`` and ``BF16_WHERE_FLOAT32`` (or those
``--wrong`` names) — the Sinkhorn at 1 iteration, ``H_post`` without its 2,
``H_res`` the identity, the stream's norm left out, ``q_norm`` left out, plain
RoPE for YaRN, the scores without ``m^2``, top-3, the routed scale left out; a
Sinkhorn and coefficients in bf16 —, which must land outside at least one
limit on every row (but those of ``UNSEEN_IN_BF16``), as must the reference
itself computed with float8 activations (``PRECISION_BELOW``: the nearest
precision below the configuration's bf16); and against the right model with
its stream rounded to bf16 after every sub-layer (``STREAM_AS_HELD``: how
much of a reading is the bf16 stream's own rounding).  Beside them the program's own
statistics (``hc_res_row_err``, ``hc_pre_max``, ``max_load``,
``moe_rows_held``, ``moe_buffer_rows``) on the cell's own batches at
initialisation and over ``--steps`` training steps, the losses of those
steps, and the device's peak memory.

Prints one JSON object, and keeps ``chiprun_out/<--out>.json`` up to date
after every reading, so that a call cut at its time limit still brings back
what it had read (a wrong model is a compile of the reference, minutes each:
``--control-rows 0`` reads the rows as published and the steps alone).  Exits
1 without a TPU.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

MEASURES = ("logits_rel_rms", "loss_rel", "grad_norm_rel")


def main() -> int:
    parser = argparse.ArgumentParser(description=__doc__)
    parser.add_argument("--seed", type=int, default=6500001)
    parser.add_argument("--rows", type=int, default=12)
    parser.add_argument("--control-rows", type=int, default=3)
    parser.add_argument("--steps", type=int, default=24)
    parser.add_argument("--wrong", default="")
    parser.add_argument("--out", default="xing4_on_chip")
    args = parser.parse_args()

    import jax

    from perfbench.harness import agreement, families, manifest
    from perfbench.harness.families import xing4
    from perfbench.harness.tokens import ZipfStream
    from ray_tpu._private.platform import enable_compile_cache
    from ray_tpu.models.pretrain import ShardedPretrainer
    from ray_tpu.parallel.mesh import MeshConfig

    if jax.default_backend() != "tpu":
        print("xing4_on_chip: no TPU; nothing was run",
              file=sys.stderr)
        return 1
    enable_compile_cache()
    out = {"seed": args.seed, "device": jax.devices()[0].device_kind}
    cell = manifest.cell("xing4-s8k-1chip")
    config, traffic = cell.config, cell.traffic
    limits = config["reference"]
    trainer = ShardedPretrainer(
        families.of(config).model_config(config, 1), MeshConfig())
    right = xing4.logits
    os.makedirs(os.path.join(ROOT, "chiprun_out"), exist_ok=True)

    def save():
        with open(os.path.join(ROOT, "chiprun_out", args.out + ".json"),
                  "w") as f:
            json.dump(out, f)

    def check(rows, wrong=None):
        if wrong:   # the reference as the wrong model, the program as it is
            xing4.logits = lambda p, i, c: xing4._forward(
                p, i, c, wrong)[0][..., :c["vocab_size"]]
        try:
            a = agreement.check(trainer, config, rows)
        finally:
            xing4.logits = right
        a["outside"] = {k: bool(a[k] > limits[k + "_max"]) for k in MEASURES}
        # as the run goes, for a call that is cut before the object is printed
        print(wrong or "as_published", {k: a[k] for k in MEASURES},
              file=sys.stderr, flush=True)
        return a

    out.update(
        prefix=limits["prefix"],
        limits={k: limits[k + "_max"] for k in MEASURES}, as_published=[],
        wrong={w: [] for w in (
            tuple(args.wrong.split(",")) if args.wrong else xing4.WRONG
            + xing4.BF16_WHERE_FLOAT32 + (xing4.PRECISION_BELOW,
                                          xing4.STREAM_AS_HELD))})
    for r in range(args.rows):
        rows = ZipfStream(config["vocab_size"], args.seed + 1000 * r).rows(
            1, traffic["seq"])
        out["as_published"].append(check(rows))
        save()
        if r < args.control_rows:
            for wrong, runs in out["wrong"].items():
                runs.append(check(rows, wrong))
                save()
    out["worst"] = {k: max(a[k] for a in out["as_published"])
                    for k in MEASURES}
    out["wrong_outside"] = {
        wrong: {k: [a["outside"][k] for a in runs] for k in MEASURES}
        for wrong, runs in out["wrong"].items()}
    out["every_wrong_model_is_outside_on_every_row"] = all(
        any(a["outside"].values())
        for wrong, runs in out["wrong"].items() for a in runs
        if wrong not in xing4.UNSEEN_IN_BF16 + (xing4.STREAM_AS_HELD,))

    # the timed path on the cell's own batches: the routing statistics at
    # initialisation and as the steps go, the losses, the memory's peak
    batches = ZipfStream(config["vocab_size"], args.seed).batches(
        traffic["rows_per_step"], traffic["seq"])
    out["steps"] = []
    for _ in range(args.steps):
        t = time.perf_counter()
        loss = float(trainer.step(next(batches)))
        out["steps"].append(dict(
            {k: float(v) for k, v in trainer.moe_stats.items()}, loss=loss,
            seconds=time.perf_counter() - t))
        save()
    stats = jax.devices()[0].memory_stats() or {}
    out["memory"] = {k: int(stats[k]) for k in
                     ("peak_bytes_in_use", "bytes_limit") if k in stats}
    save()
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())

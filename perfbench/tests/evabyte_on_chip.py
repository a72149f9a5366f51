"""python3 perfbench/tests/evabyte_on_chip.py [--seed N] [--rows R] [--control-rows C] [--steps S] [--forms 0|1]
(on the chip; not a test)

The runs behind the limits in ``configs/evabyte.json``: at published widths,
in one process that owns the chip, the cell's own agreement check
(``mtp_agreement.py``: the bf16 program on a seeded row of the cell's traffic
— the kernels run at the full length — against ``families/evabyte.py`` in
float32 on its first 8,192 positions, four whole windows, the last of which
meets three tiles of 128 summaries: the first head's
logits, the loss of all eight heads, the gradient norm) on ``--rows`` seeded
rows, and on the first ``--control-rows`` of them against each wrong model of
``families/evabyte.py::WRONG`` — ``mu`` left out, mean pooling, the pooling's
scale left out, a window's own summaries seen, chunk-by-chunk visibility,
window 1,024, chunk 32, the norms without their unit offset, heads 1 to 7
scoring the next byte, RoPE left off, the residual in bf16 — which must land
outside at least one limit on every row (but those of ``UNSEEN_IN_BF16``), as
must the reference itself computed with float8 activations
(``PRECISION_BELOW``: the nearest precision below the configuration's bf16).
``phi`` and ``mu`` are as initialised; ``moved`` is the right model with every
norm's ``g``, ``phi`` and ``mu`` moved off their start by a seeded tenth,
which must stay inside.  Beside them the losses of ``--steps`` training steps
on the cell's own batches and the device's peak memory.

``--forms 1`` first times the pooling alone at the cell's shape (1 x 16,384 x
4,096, bf16, the seven windows before the last), forward and forward +
backward, as XLA fuses ``ops/pooling.py``'s plain form and as its Pallas
pass, beside the least time its bytes take, and the whole
training step, ms a step, with the pooling in either form; ``--forms 2`` does
that and nothing else.

Prints one JSON object.  Exits 1 without a TPU.
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
CELL = "evabyte-eva-1chip"


def pool_forms(config, seq: int, peak, calls: int = 20):
    """ms a call of the pooling alone in each form, forward and forward +
    backward (k's, v's, ``phi``'s and ``mu``'s gradients), the operands made
    once on the device; and the least its bytes take over the HBM
    bandwidth."""
    import jax
    import jax.numpy as jnp

    from perfbench.harness import eva_work
    from ray_tpu.ops.pooling import pool_chunks

    h, d = config["num_attention_heads"], config["hidden_size"]
    hd, window, chunk = d // h, config["window_size"], config["chunk_size"]
    seen = (seq - 1) // window * window
    keys = jax.random.split(jax.random.PRNGKey(0), 6)
    k = jax.random.normal(keys[0], (1, h, seen, hd), jnp.bfloat16)
    v = jax.random.normal(keys[1], (1, seen, d), jnp.bfloat16)
    phi, mu = (jax.random.normal(key, (h, hd), jnp.float32) / hd ** 0.5
               for key in keys[2:4])
    gk = jax.random.normal(keys[4], (1, h, seen // chunk, hd), jnp.bfloat16)
    gv = jax.random.normal(keys[5], (1, seen // chunk, d), jnp.bfloat16)

    out = {}
    for name, impl in (("xla", "reference"), ("kernels", "flash")):
        def f(k, v, phi, mu, impl=impl):
            return pool_chunks(k, v, phi, mu, chunk, hd ** -0.5, impl=impl)

        for label, fn in (("fwd_ms", jax.jit(f)), ("fwd_bwd_ms", jax.jit(
                lambda *a, f=f: jax.vjp(f, *a)[1]((gk, gv))))):
            jax.block_until_ready(fn(k, v, phi, mu))
            t0 = time.perf_counter()
            for _ in range(calls):
                r = fn(k, v, phi, mu)
            jax.block_until_ready(r)
            out[f"{name}.{label}"] = 1e3 * (time.perf_counter() - t0) / calls
    work = eva_work.pool_step(config, 1, 1, seq)
    layers = config["num_hidden_layers"]
    out["least_fwd_bwd_ms_a_layer"] = 1e3 * work["bytes"] / layers \
        / peak["hbm_bytes_per_s"]
    return out


def step_forms(config, traffic, seed: int, steps: int = 8):
    """ms a training step of the cell with the pooling in either form, a
    trainer a form, one after the other (two do not fit the chip)."""
    import gc

    import jax

    from perfbench.harness import families
    from perfbench.harness.held_tokens import HeldZipfStream
    from ray_tpu.models.pretrain import ShardedPretrainer
    from ray_tpu.ops import pooling
    from ray_tpu.parallel.mesh import MeshConfig

    shipped, out = pooling.fits, {}
    for name, fits in (("kernels", shipped), ("xla", lambda k, v: False),
                       ("kernels_again", shipped)):
        pooling.fits = fits
        try:
            trainer = ShardedPretrainer(
                families.of(config).model_config(config, 1), MeshConfig())
            batches = HeldZipfStream(config["vocab_size"], seed,
                                     traffic["hold"]).batches(
                traffic["rows_per_step"], traffic["seq"])
            for _ in range(2):
                jax.block_until_ready(trainer.step(next(batches)))
            t0 = time.perf_counter()
            for _ in range(steps):
                loss = trainer.step(next(batches))
            jax.block_until_ready((loss, trainer.state))
            out[name] = 1e3 * (time.perf_counter() - t0) / steps
        finally:
            pooling.fits = shipped
        del trainer
        gc.collect()
        print(name, round(out[name], 2), file=sys.stderr, flush=True)
    return out


def main() -> int:
    parser = argparse.ArgumentParser(description=__doc__)
    parser.add_argument("--seed", type=int, default=4700001)
    parser.add_argument("--rows", type=int, default=12)
    parser.add_argument("--control-rows", type=int, default=3)
    parser.add_argument("--steps", type=int, default=24)
    parser.add_argument("--forms", type=int, default=0)
    args = parser.parse_args()

    import jax

    from perfbench.harness import families, manifest, mtp_agreement
    from perfbench.harness.families import evabyte
    from perfbench.harness.held_tokens import HeldZipfStream
    from ray_tpu._private.platform import enable_compile_cache
    from ray_tpu.models.pretrain import ShardedPretrainer
    from ray_tpu.parallel.mesh import MeshConfig

    if jax.default_backend() != "tpu":
        print("evabyte_on_chip: no TPU; nothing was run", file=sys.stderr)
        return 1
    enable_compile_cache()
    cell = manifest.cell(CELL)
    config, traffic = cell.config, cell.traffic
    limits, hold = config["reference"], traffic["hold"]
    out = {"seed": args.seed, "hold": hold, "device": jax.devices()[0].device_kind,
           "seq": traffic["seq"], "prefix": limits["prefix"],
           "limits": {k: limits[k + "_max"] for k in MEASURES}}
    if args.forms:
        out["pool_forms_ms"] = pool_forms(
            config, traffic["seq"],
            manifest.peaks()[jax.devices()[0].device_kind])
        print(out["pool_forms_ms"], file=sys.stderr, flush=True)
        out["step_forms_ms"] = step_forms(config, traffic, args.seed)
        if args.forms == 2:
            print(json.dumps(out), flush=True)
            return 0
    trainer = ShardedPretrainer(
        families.of(config).model_config(config, 1), MeshConfig())
    as_initialised = trainer.state
    keys = iter(jax.random.split(jax.random.PRNGKey(args.seed), 64))
    moved = (jax.tree_util.tree_map_with_path(
        lambda path, a: a + 0.1 * jax.random.normal(next(keys), a.shape,
                                                    a.dtype)
        if getattr(path[-1], "key", None) in ("phi", "mu", "scale") else a,
        as_initialised[0]), as_initialised[1])
    right = mtp_agreement.Checker(trainer, config)
    controls = {w: mtp_agreement.Checker(trainer, config, w)
                for w in evabyte.WRONG + (evabyte.PRECISION_BELOW,)}

    def read(name, checker, batch, got):
        a = checker.against(batch, got)
        a["outside"] = {k: bool(not a[k] <= limits[k + "_max"])
                        for k in MEASURES}
        # as the run goes, for a call that is cut before the object is printed
        print(name, {k: a[k] for k in MEASURES}, file=sys.stderr, flush=True)
        return a

    out["as_published"], out["moved"] = [], []
    out["wrong"] = {w: [] for w in controls}
    for r in range(args.rows):
        rows = HeldZipfStream(config["vocab_size"], args.seed + 1000 * r,
                              hold).rows(1, traffic["seq"])
        out["as_published"].append(read("as_published", right,
                                        *right.program(rows)))
        if r < args.control_rows:
            trainer.state = moved
            try:
                batch, got = right.program(rows)
                out["moved"].append(read("moved", right, batch, got))
                for wrong, checker in controls.items():
                    out["wrong"][wrong].append(read(wrong, checker, batch,
                                                    got))
            finally:
                trainer.state = as_initialised
    out["worst"] = {k: max(a[k] for a in out["as_published"] + out["moved"])
                    for k in MEASURES}
    out["wrong_outside"] = {
        wrong: {k: [a["outside"][k] for a in runs] for k in MEASURES}
        for wrong, runs in out["wrong"].items()}
    # (the members of UNSEEN_IN_BF16 are run and reported like the others,
    # and are not expected outside)
    out["every_wrong_model_is_outside_on_every_row"] = all(
        any(a["outside"].values())
        for wrong, runs in out["wrong"].items() for a in runs
        if wrong not in evabyte.UNSEEN_IN_BF16)

    del moved
    batches = HeldZipfStream(config["vocab_size"], args.seed, hold).batches(
        traffic["rows_per_step"], traffic["seq"])
    out["losses"] = []
    t0 = time.perf_counter()
    for _ in range(args.steps):
        out["losses"].append(float(trainer.step(next(batches))))
    out["steps_s"] = time.perf_counter() - t0
    stats = jax.devices()[0].memory_stats() or {}
    out["memory"] = {k: int(stats[k]) for k in
                     ("peak_bytes_in_use", "bytes_limit") if k in stats}
    print(json.dumps(out), flush=True)
    os.makedirs(os.path.join(ROOT, "chiprun_out"), exist_ok=True)
    # (a file a seed and a --forms: a later call does not overwrite it)
    with open(os.path.join(ROOT, "chiprun_out", "evabyte_on_chip."
                           f"{args.seed}.forms{args.forms}.json"), "w") as f:
        json.dump(out, f)
    return 0


if __name__ == "__main__":
    sys.exit(main())

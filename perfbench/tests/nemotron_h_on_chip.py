"""python3 perfbench/tests/nemotron_h_on_chip.py [--seed N] [--rows R] [--control-rows C] [--steps S] [--kernels 1] [--wrong a,b,..]
(on the chip; not a test)

The runs behind the limits in ``configs/nemotron-3-nano-30b-a3b.json``: at
published widths, in one process that owns the chip, the cell's own agreement
check (``agreement.check``: the bf16 program on a seeded row of 16,384 tokens
of the cell's traffic against ``families/nemotron_h.py`` in float32 on its
first ``reference.prefix`` positions — logits, loss, gradient norm) on
``--rows`` seeded rows, and on the first ``--control-rows`` of them against
each wrong model of ``families/nemotron_h.py::WRONG`` (or those ``--wrong``
names), which must land outside at least one limit on every row (but those
of ``UNSEEN_IN_BF16``), as must the reference itself computed with float8 activations (``PRECISION_BELOW``: the
nearest precision below the configuration's bf16).  Beside them the program's
routing statistics (``max_load``, ``moe_rows_held``, ``moe_buffer_rows``) on
the cell's own batches at initialisation and over ``--steps`` training steps,
the losses of those steps, and the device's peak memory.

``--kernels 1`` first settles the backward's grid at the cell's attention
shape (32 query heads over 2 key/value heads of 128): at 16,384 positions a
group of sixteen's dQ is 256 MiB and does not fit the VMEM, so
``ops/attention.py::_flash_backward`` walks the query heads and the sum of dK
and dV over each group runs beside the kernel — the pair's time there — and
at 7,168 positions, the longest at which the group's dQ does fit, both grids,
that they agree and the time of each: what the other grid would be worth.

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


def kernels(heads: int = 32, kv: int = 2, d: int = 128):
    """The attention calls alone: {seq: {grid: ms of forward + backward}}."""
    import functools

    import jax
    import jax.numpy as jnp

    from ray_tpu.ops import attention as ops

    whole = ops._VMEM_BYTES
    out = {"vmem_bytes": whole}
    for seq in (16384, 7168):
        keys = jax.random.split(jax.random.PRNGKey(0), 4)
        q, k, v, g = (jax.random.normal(key, (1, seq, n * d), jnp.bfloat16)
                      for key, n in zip(keys, (heads, kv, kv, heads)))
        row = out[f"s{seq}"] = {"group_dq_vmem_bytes": int(
            ops._bwd_vmem_bytes(heads // kv * seq, d, jnp.bfloat16))}
        grads = {}
        for grid, limit in (("kv_grid", whole), ("beside", 1)):
            if grid == "kv_grid" and row["group_dq_vmem_bytes"] > whole:
                row["kv_grid_refused"] = "the group's dQ is past the VMEM"
                continue
            ops._VMEM_BYTES = limit     # read where the backward is traced

            def both(q, k, v, g):
                y, vjp = jax.vjp(functools.partial(
                    ops.flash_attention, head_dim=d), q, k, v)
                return y, vjp(g)

            run = jax.jit(both)
            try:
                jax.block_until_ready(run(q, k, v, g))
                t = time.perf_counter()
                for _ in range(5):
                    got = run(q, k, v, g)
                jax.block_until_ready(got)
                row[grid + "_ms"] = (time.perf_counter() - t) / 5 * 1e3
                grads[grid] = got
            except Exception as e:      # what the chip refuses
                row[grid + "_refused"] = str(e)[-400:]
            ops._flash_backward.clear_cache()
        ops._VMEM_BYTES = whole
        if len(grads) == 2:
            row["max_abs_diff"] = max(
                float(jnp.max(jnp.abs(a.astype(jnp.float32)
                                      - b.astype(jnp.float32))))
                for a, b in zip(jax.tree_util.tree_leaves(grads["kv_grid"]),
                                jax.tree_util.tree_leaves(grads["beside"])))
    return out


def main() -> int:
    parser = argparse.ArgumentParser(description=__doc__)
    parser.add_argument("--seed", type=int, default=6300001)
    parser.add_argument("--rows", type=int, default=12)
    parser.add_argument("--control-rows", type=int, default=3)
    parser.add_argument("--steps", type=int, default=24)
    parser.add_argument("--kernels", type=int, default=0)
    parser.add_argument("--wrong", default="")
    args = parser.parse_args()

    import jax

    from perfbench.harness import agreement, families, manifest
    from perfbench.harness.families import nemotron_h
    from perfbench.harness.tokens import ZipfStream
    from ray_tpu._private.platform import enable_compile_cache
    from ray_tpu.models.pretrain import ShardedPretrainer
    from ray_tpu.parallel.mesh import MeshConfig

    if jax.default_backend() != "tpu":
        print("nemotron_h_on_chip: no TPU; nothing was run",
              file=sys.stderr)
        return 1
    enable_compile_cache()
    out = {"seed": args.seed, "device": jax.devices()[0].device_kind}
    if args.kernels:
        out["kernels"] = kernels()
        print("kernels", out["kernels"], file=sys.stderr, flush=True)
    cell = manifest.cell("nemotron3-nano-s16k-1chip")
    config, traffic = cell.config, cell.traffic
    limits = config["reference"]
    trainer = ShardedPretrainer(
        families.of(config).model_config(config, 1), MeshConfig())
    right = nemotron_h.logits

    def check(rows, wrong=None):
        if wrong:   # the reference as the wrong model, the program as it is
            nemotron_h.logits = lambda p, i, c: nemotron_h._forward(
                p, i, c, wrong)[0][..., :c["vocab_size"]]
        try:
            a = agreement.check(trainer, config, rows)
        finally:
            nemotron_h.logits = right
        a["outside"] = {k: bool(a[k] > limits[k + "_max"]) for k in MEASURES}
        # as the run goes, for a call that is cut before the object is printed
        print(wrong or "as_published", {k: a[k] for k in MEASURES},
              file=sys.stderr, flush=True)
        return a

    out.update(
        prefix=limits["prefix"],
        limits={k: limits[k + "_max"] for k in MEASURES}, as_published=[],
        wrong={w: [] for w in (
            tuple(args.wrong.split(",")) if args.wrong else nemotron_h.WRONG
            + (nemotron_h.PRECISION_BELOW,))})
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
    out["every_wrong_model_is_outside_on_every_row"] = all(
        any(a["outside"].values())
        for wrong, runs in out["wrong"].items() for a in runs
        if wrong not in nemotron_h.UNSEEN_IN_BF16)

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
    stats = jax.devices()[0].memory_stats() or {}
    out["memory"] = {k: int(stats[k]) for k in
                     ("peak_bytes_in_use", "bytes_limit") if k in stats}
    os.makedirs(os.path.join(ROOT, "chiprun_out"), exist_ok=True)
    with open(os.path.join(ROOT, "chiprun_out", "nemotron_h_on_chip.json"),
              "w") as f:
        json.dump(out, f)
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())

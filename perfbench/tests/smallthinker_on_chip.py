"""python3 perfbench/tests/smallthinker_on_chip.py [--seed N] [--rows R] [--control-rows C] [--steps S] [--kernels 1]
(on the chip; not a test)

The runs behind the limits in ``configs/smallthinker-21b-a3b-instruct.json``:
at published widths, in one process that owns the chip, the cell's own
agreement check (``agreement.check``: the bf16 program on a seeded row of
16,384 tokens of the cell's traffic against ``families/smallthinker.py`` in
float32 on its first 8,192 positions — half of them have keys the window of
4,096 masks out — logits, loss, gradient norm) on ``--rows`` seeded rows, and
on the first ``--control-rows`` of them against each wrong model of
``families/smallthinker.py::WRONG``, which must land outside at least one
limit on every row (but those of ``UNSEEN_IN_BF16``), as must the reference
itself computed with float8 activations (``PRECISION_BELOW``: the nearest
precision below the configuration's bf16).  Beside them the reference's
share of the held rows' gate units that the ReLU sets to zero, the program's
routing statistics (``max_load``, ``moe_rows_held``, ``moe_buffer_rows``) on
the cell's own batches at initialisation and over ``--steps`` training steps,
the losses of those steps, and the device's peak memory.

``--kernels 1`` first settles the backward's grid at the cell's attention
shape (1 x 16,384, 28 query heads over 4 key/value heads of 128), causal and
under the band of 4,096: the flash forward and backward as the criterion of
``ops/attention.py::_flash_backward`` picks them — a group of seven's dQ in
VMEM at the whole of the chip's 128 MiB, dK and dV written at the key/value
heads — and, with the criterion forced the other way, a gradient a query
head summed beside the kernel: that both run, that they agree, and the time
of each.

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


def kernels(seq: int = 16384, heads: int = 28, kv: int = 4, d: int = 128):
    """The attention calls alone, both backward grids: {mask: {grid: ms of
    forward + backward, ...}, "max_abs_diff": ...}."""
    import functools

    import jax
    import jax.numpy as jnp

    from ray_tpu.ops import attention as ops

    keys = jax.random.split(jax.random.PRNGKey(0), 4)
    q, k, v, g = (jax.random.normal(key, (1, seq, n * d), jnp.bfloat16)
                  for key, n in zip(keys, (heads, kv, kv, heads)))
    out = {"vmem_limit_bytes": int(ops._bwd_vmem_bytes(
        heads // kv * seq, d, jnp.bfloat16)), "vmem_bytes": ops._VMEM_BYTES}
    whole = ops._VMEM_BYTES
    for name, window in (("causal", 0), ("band_4096", 4096)):
        grads = {}
        for grid, limit in (("kv_grid", whole), ("beside", whole - 1)):
            ops._VMEM_BYTES = limit     # read where the backward is traced

            def both(q, k, v, g):
                y, vjp = jax.vjp(functools.partial(
                    ops.flash_attention, head_dim=d, window=window), q, k, v)
                return y, vjp(g)

            run = jax.jit(both)
            try:
                jax.block_until_ready(run(q, k, v, g))
                t = time.perf_counter()
                for _ in range(5):
                    got = run(q, k, v, g)
                jax.block_until_ready(got)
                out.setdefault(name, {})[grid + "_ms"] = (
                    time.perf_counter() - t) / 5 * 1e3
                grads[grid] = got
            except Exception as e:      # what the chip refuses
                out.setdefault(name, {})[grid + "_refused"] = str(e)[-400:]
            ops._flash_backward.clear_cache()
        ops._VMEM_BYTES = whole
        if len(grads) == 2:
            out[name]["max_abs_diff"] = max(
                float(jnp.max(jnp.abs(a.astype(jnp.float32)
                                      - b.astype(jnp.float32))))
                for a, b in zip(jax.tree_util.tree_leaves(grads["kv_grid"]),
                                jax.tree_util.tree_leaves(grads["beside"])))
            out[name]["finite"] = all(
                bool(jnp.all(jnp.isfinite(a.astype(jnp.float32))))
                for a in jax.tree_util.tree_leaves(grads["kv_grid"]))
    return out


def main() -> int:
    parser = argparse.ArgumentParser(description=__doc__)
    parser.add_argument("--seed", type=int, default=5900001)
    parser.add_argument("--rows", type=int, default=12)
    parser.add_argument("--control-rows", type=int, default=3)
    parser.add_argument("--steps", type=int, default=24)
    parser.add_argument("--kernels", type=int, default=0)
    args = parser.parse_args()

    import jax

    from perfbench.harness import agreement, families, manifest
    from perfbench.harness.families import smallthinker
    from perfbench.harness.tokens import ZipfStream
    from ray_tpu._private.platform import enable_compile_cache
    from ray_tpu.models.pretrain import ShardedPretrainer
    from ray_tpu.parallel.mesh import MeshConfig

    if jax.default_backend() != "tpu":
        print("smallthinker_on_chip: no TPU; nothing was run",
              file=sys.stderr)
        return 1
    enable_compile_cache()
    out = {"seed": args.seed, "device": jax.devices()[0].device_kind}
    if args.kernels:
        out["kernels"] = kernels()
        print("kernels", out["kernels"], file=sys.stderr, flush=True)
    cell = manifest.cell("smallthinker-s16k-1chip")
    config, traffic = cell.config, cell.traffic
    limits = config["reference"]
    trainer = ShardedPretrainer(
        families.of(config).model_config(config, 1), MeshConfig())
    right = smallthinker.logits

    def check(rows, wrong=None):
        if wrong:   # the reference as the wrong model, the program as it is
            smallthinker.logits = lambda p, i, c: smallthinker._forward(
                p, i, c, wrong)[0][..., :c["vocab_size"]]
        try:
            a = agreement.check(trainer, config, rows)
        finally:
            smallthinker.logits = right
        a["outside"] = {k: bool(a[k] > limits[k + "_max"]) for k in MEASURES}
        # as the run goes, for a call that is cut before the object is printed
        print(wrong or "as_published", {k: a[k] for k in MEASURES},
              file=sys.stderr, flush=True)
        return a

    out.update(
        prefix=limits["prefix"],
        limits={k: limits[k + "_max"] for k in MEASURES}, as_published=[],
        wrong={w: [] for w in smallthinker.WRONG
               + (smallthinker.PRECISION_BELOW,)})
    for r in range(args.rows):
        rows = ZipfStream(config["vocab_size"], args.seed + 1000 * r).rows(
            1, traffic["seq"])
        out["as_published"].append(check(rows))
        if r < args.control_rows:
            for wrong, runs in out["wrong"].items():
                runs.append(check(rows, wrong))
        if r == 0:
            # the sparsity the model is built around, at initial weights:
            # the share of the held rows' gate units at exactly zero, a layer
            with jax.default_matmul_precision("highest"):
                zero = jax.jit(lambda p, ids: smallthinker._forward(
                    p, ids, config)[2])(trainer.state[0],
                                        rows["input_ids"][:, :2048])
            out["reference_gate_zero_share"] = [float(z) for z in zero]
    out["worst"] = {k: max(a[k] for a in out["as_published"])
                    for k in MEASURES}
    out["wrong_outside"] = {
        wrong: {k: [a["outside"][k] for a in runs] for k in MEASURES}
        for wrong, runs in out["wrong"].items()}
    out["every_wrong_model_is_outside_on_every_row"] = all(
        any(a["outside"].values())
        for wrong, runs in out["wrong"].items() for a in runs
        if wrong not in smallthinker.UNSEEN_IN_BF16)

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
    with open(os.path.join(ROOT, "chiprun_out", "smallthinker_on_chip.json"),
              "w") as f:
        json.dump(out, f)
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())

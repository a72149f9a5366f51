"""python3 perfbench/tests/phi4_flash_on_chip.py [--seed N] [--rows R] [--control-rows C] [--steps S] [--forms 0|1] [--form-impls kernels,xla] [--scan-block N] [--probe-exp 0|1] [--unroll N]
(on the chip; not a test)

The runs behind the limits in ``configs/phi-4-mini-flash-reasoning.json``: at
published widths, in one process that owns the chip, the cell's own agreement
check (``agreement.check``: the bf16 program on a seeded row of 16,384 tokens
of the cell's traffic — the scan's kernels and the flash kernels run at the
full length — against ``families/phi4_flash.py`` in float32 on its first
1,024 positions, two windows and four blocks of the scan: logits, loss,
gradient norm) on ``--rows`` seeded rows, and on the first ``--control-rows``
of them against each wrong model of ``families/phi4_flash.py::WRONG``, which
must land outside at least one limit on every row (but those of
``UNSEEN_IN_BF16``), as must the reference itself computed with float8
activations (``PRECISION_BELOW``: the nearest precision below the
configuration's bf16).  Beside them the losses and the wall time of
``--steps`` training steps on the cell's own batches, and the device's peak
memory.

``--forms 1`` first times the selective scan alone at the cell's shape (1 x
16,384 x 5,120 channels x 16 states), forward and forward + backward (all six
gradients), as the Mosaic kernels (``ops/selective_scan.py::selective_scan``)
and as XLA compiles the blocked ``jax.numpy`` form (``selective_scan_xla``,
the yardstick; at ``--xla-block`` positions a block, since its
``associative_scan`` holds a block's every state); ``--scan-block`` sets the
kernels' block of positions, for timing another than the one that ships;
``--probe-exp 1`` times the kernels once more with every ``exp`` replaced by
a multiply-add, to say whether the transcendental unit or the vector unit
bounds them; ``--unroll`` sets the positions a trip of the kernels' loops
takes (``ops/selective_scan.py::_UNROLL``), for timing another than the one
that ships.

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


def scan_forms(config, seq: int, impls, block: int, xla_block: int,
               calls: int = 5, probe_exp: bool = False):
    """ms a call of ``ops/selective_scan.py``'s scan in each form at the
    cell's shape (1 x seq x 5,120 x 16: bf16 u, B, C; float32 step sizes as
    the mixer's initial values make them), forward alone and forward +
    backward, the operands made once on the device."""
    import jax
    import jax.numpy as jnp

    from perfbench.harness.families import phi4_flash
    from ray_tpu.ops.selective_scan import selective_scan, selective_scan_xla

    forms = {"kernels": (selective_scan, block),
             "xla": (selective_scan_xla, xla_block)}
    s = phi4_flash.sizes(config)
    d, n = s["d"], s["n"]
    keys = jax.random.split(jax.random.PRNGKey(0), 6)
    u = jax.nn.silu(jax.random.normal(keys[0], (1, seq, d))
                    ).astype(jnp.bfloat16)
    # steps log-uniform in [1e-3, 0.1], A = -(1 .. 16): the mixer's start
    delta = jnp.exp(jax.random.uniform(keys[1], (1, seq, d),
                                       minval=jnp.log(1e-3),
                                       maxval=jnp.log(0.1)))
    a = -jnp.broadcast_to(jnp.arange(1.0, n + 1), (d, n))
    b, c = (jax.random.normal(k, (1, seq, n)).astype(jnp.bfloat16)
            for k in keys[2:4])
    skip = jnp.ones((d,))
    dy = jax.random.normal(keys[4], u.shape, jnp.bfloat16)
    operands = (u, delta, a, b, c, skip)
    out = {}

    def timed(fn) -> float:
        jax.block_until_ready(fn(*operands))
        t0 = time.perf_counter()
        for _ in range(calls):
            r = fn(*operands)
        jax.block_until_ready(r)
        return 1e3 * (time.perf_counter() - t0) / calls

    def both_ways(name, scan, size):
        f = lambda *x: scan(*x, block=size)  # noqa: E731
        out[f"{name}.fwd_ms"] = timed(jax.jit(f))
        out[f"{name}.fwd_bwd_ms"] = timed(
            jax.jit(lambda *x: jax.vjp(f, *x)[1](dy)))
        print({k: round(x, 3) for k, x in out.items() if k.startswith(name)},
              file=sys.stderr, flush=True)

    for impl in impls:
        both_ways(impl, *forms[impl])
    if probe_exp:
        # which unit binds: the kernels once more with every ``exp`` a
        # multiply-add (another function: a timing and nothing else)
        from unittest import mock

        with mock.patch.object(jnp, "exp", lambda x: 1.0 + 0.5 * x):
            both_ways("kernels_without_exp", selective_scan, block)
    if len(impls) > 1:
        # the two forms on the same operands: y and all six gradients, the
        # kernels' in the grid the cell runs (five channel blocks of eight
        # rows, 64 blocks of positions), which no CPU case reaches
        def y_and_grads(impl):
            scan, size = forms[impl]
            y, vjp = jax.vjp(lambda *x: scan(*x, block=size), *operands)
            return y, *vjp(dy)

        def rel_rms(x, y):
            x, y = x.astype(jnp.float32), y.astype(jnp.float32)
            return float(jnp.sqrt(jnp.sum((x - y) ** 2) / jnp.sum(y ** 2)))

        first, second = (jax.jit(lambda i=i: y_and_grads(i))()
                         for i in impls[:2])
        out["forms_rel_rms"] = rel_rms(first[0], second[0])
        out["forms_grad_rel_rms"] = {
            name: rel_rms(x, y) for name, x, y in zip(
                ("u", "delta", "A", "B", "C", "D"), first[1:], second[1:])}
        # bf16 y, du, dB, dC round apart by 2 ** -9 or so; the float32
        # gradients differ in the order of their sums alone
        out["forms_agree"] = max(out["forms_rel_rms"],
                                 *out["forms_grad_rel_rms"].values()) < 0.02
    return out


def main() -> int:
    parser = argparse.ArgumentParser(description=__doc__)
    parser.add_argument("--seed", type=int, default=5500001)
    parser.add_argument("--rows", type=int, default=12)
    parser.add_argument("--control-rows", type=int, default=3)
    parser.add_argument("--steps", type=int, default=16)
    parser.add_argument("--forms", type=int, default=0)
    parser.add_argument("--form-impls", default="kernels,xla")
    parser.add_argument("--scan-block", type=int, default=0)
    parser.add_argument("--xla-block", type=int, default=64)
    parser.add_argument("--probe-exp", type=int, default=0)
    parser.add_argument("--unroll", type=int, default=0)
    args = parser.parse_args()

    import jax

    from perfbench.harness import agreement, families, manifest
    from perfbench.harness.families import phi4_flash
    from perfbench.harness.tokens import ZipfStream
    from ray_tpu._private.platform import enable_compile_cache
    from ray_tpu.models.pretrain import ShardedPretrainer
    from ray_tpu.parallel.mesh import MeshConfig

    if jax.default_backend() != "tpu":
        print("phi4_flash_on_chip: no TPU; nothing was run", file=sys.stderr)
        return 1
    enable_compile_cache()
    cell = manifest.cell("phi4-flash-s16k-1chip")
    config, traffic = cell.config, cell.traffic
    if args.scan_block:
        config = dict(config, scan_block=args.scan_block)
    limits = config["reference"]
    out = {"seed": args.seed, "device": jax.devices()[0].device_kind,
           "prefix": limits["prefix"], "scan_block": config["scan_block"],
           "limits": {k: limits[k + "_max"] for k in MEASURES}}
    if args.unroll:     # positions a trip of the kernels' loops takes
        from ray_tpu.ops import selective_scan

        selective_scan._UNROLL = out["unroll"] = args.unroll
    if args.forms:
        out["scan_forms_ms"] = scan_forms(
            config, traffic["seq"], args.form_impls.split(","),
            config["scan_block"], args.xla_block, probe_exp=bool(args.probe_exp))
        print(out["scan_forms_ms"], file=sys.stderr, flush=True)
    trainer = ShardedPretrainer(
        families.of(config).model_config(config, 1), MeshConfig())
    right = phi4_flash.logits

    def check(rows, wrong=None):
        if wrong:   # the reference as the wrong model, the program as it is
            phi4_flash.logits = lambda p, i, c: phi4_flash._forward(
                p, i, c, wrong)[..., :c["vocab_size"]]
        try:
            a = agreement.check(trainer, config, rows)
        finally:
            phi4_flash.logits = right
        a["outside"] = {k: bool(not a[k] <= limits[k + "_max"])
                        for k in MEASURES}
        # as the run goes, for a call that is cut before the object is printed
        print(wrong or "as_published", {k: a[k] for k in MEASURES},
              file=sys.stderr, flush=True)
        return a

    out["as_published"] = []
    out["wrong"] = {w: [] for w in
                    phi4_flash.WRONG + (phi4_flash.PRECISION_BELOW,)}
    for r in range(args.rows):
        rows = ZipfStream(config["vocab_size"], args.seed + 1000 * r).rows(
            1, traffic["seq"])
        out["as_published"].append(check(rows))
        if r < args.control_rows:
            for wrong, runs in out["wrong"].items():
                runs.append(check(rows, wrong))
    out["worst"] = {k: max((a[k] for a in out["as_published"]), default=None)
                    for k in MEASURES}
    out["wrong_outside"] = {
        wrong: {k: [a["outside"][k] for a in runs] for k in MEASURES}
        for wrong, runs in out["wrong"].items()}
    # (the members of UNSEEN_IN_BF16 are run and reported like the others,
    # and are not expected outside)
    out["every_wrong_model_is_outside_on_every_row"] = all(
        any(a["outside"].values())
        for wrong, runs in out["wrong"].items() for a in runs
        if wrong not in phi4_flash.UNSEEN_IN_BF16)

    # the timed path on the cell's own batches: the losses, the memory's peak
    batches = ZipfStream(config["vocab_size"], args.seed).batches(
        traffic["rows_per_step"], traffic["seq"])
    out["steps"] = []
    for _ in range(args.steps):
        t0 = time.perf_counter()
        loss = float(trainer.step(next(batches)))
        out["steps"].append({"loss": loss,
                             "wall_ms": 1e3 * (time.perf_counter() - t0)})
    stats = jax.devices()[0].memory_stats() or {}
    out["memory"] = {k: int(stats[k]) for k in
                     ("peak_bytes_in_use", "bytes_limit") if k in stats}
    print(json.dumps(out), flush=True)
    return 0 if out.get("scan_forms_ms", {}).get("forms_agree", True) else 1


if __name__ == "__main__":
    sys.exit(main())

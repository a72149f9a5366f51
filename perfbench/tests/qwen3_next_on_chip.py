"""python3 perfbench/tests/qwen3_next_on_chip.py [--seed N] [--rows R] [--first-row F] [--control-rows C] [--steps S] [--forms 0|1] [--out NAME]
(on the chip; not a test)

The runs behind the limits in ``configs/qwen3-next-80b-a3b-instruct.json``: at
published widths, in one process that owns the chip, the cell's own agreement
check (``agreement.check``: the bf16 program on a seeded row of 16,384 tokens
of the cell's traffic — the scan and the flash kernels run at the full length
— against ``families/qwen3_next.py`` in float32 on its first 1,024 positions,
16 chunks of the scan: logits, loss, gradient norm) on ``--rows`` seeded rows
(row ``r``'s ids from ``--seed`` + 1000 ``r``, from ``--first-row`` on, so
that a second call goes on where a cut one stopped), and on the first
``--control-rows`` of them against each wrong model of
``families/qwen3_next.py::WRONG``, which must land outside at least one limit
on every row (but those of ``UNSEEN_IN_BF16``), as must the reference itself
computed with float8 activations (``PRECISION_BELOW``).  Beside them the
program's routing statistics on the cell's own batches over ``--steps``
training steps, the losses and the wall time of those steps, and the device's
peak memory.

``--forms 1`` first times the scan alone at the cell's shape (1 x 16,384 x 32
value heads over 16 key heads x 128), forward and forward + backward, three
ways: the Mosaic kernels (``ops/gdn.py::gdn_scan``), XLA's fusions of the
chunked ``jax.numpy`` form (``gdn_scan_xla``, the yardstick), and the
broadcast route — ``ops/kda.py::kda_scan`` fed ``g`` broadcast to a head's 128
channels and ``q``, ``k`` repeated to the 32 value heads, the broadcasts
inside the timed function as a mixer that took that route would make them —,
and how far the three lie from each other on the same bf16 operands.

Prints one JSON object, and keeps ``chiprun_out/<--out>.json`` up to date
after every reading, for a call that is cut.  Exits 1 without a TPU.
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


def scan_forms(config, seq: int, impls, calls: int = 5):
    """ms a call of the scan in each form at the cell's shape (bf16 q, k, v;
    float32 log-decays as the mixer's initial values make them), forward
    alone and forward + backward (all five gradients), the operands made once
    on the device."""
    import jax
    import jax.numpy as jnp

    from perfbench.harness.families import qwen3_next
    from ray_tpu.ops.gdn import gdn_scan, gdn_scan_xla
    from ray_tpu.ops.kda import kda_scan

    keys_, heads, d = qwen3_next.gdn_sizes(config)
    r, chunk = heads // keys_, config["gdn_chunk"]

    def broadcast(q, k, v, g, beta, chunk):
        def of_value_heads(t):
            return jnp.repeat(t.reshape(1, seq, keys_, d), r, axis=2
                              ).reshape(1, seq, heads * d)
        return kda_scan(of_value_heads(q), of_value_heads(k), v,
                        jnp.repeat(g, d, axis=-1), beta, chunk=chunk)

    forms = {"pallas": gdn_scan, "xla": gdn_scan_xla, "kda": broadcast}
    keys = jax.random.split(jax.random.PRNGKey(0), 6)

    def unit(key):
        t = jax.random.normal(key, (1, seq, keys_, d))
        return (t * jax.lax.rsqrt(jnp.sum(t * t, -1, keepdims=True))
                ).reshape(1, seq, keys_ * d)

    q = (unit(keys[0]) * d ** -0.5).astype(jnp.bfloat16)
    k = unit(keys[1]).astype(jnp.bfloat16)
    v = jax.nn.silu(jax.random.normal(keys[2], (1, seq, heads * d))
                    ).astype(jnp.bfloat16)
    # A in [1, 16], steps in [1e-3, 0.1]: the mixer's start
    g = -jax.random.uniform(keys[3], (heads,), minval=1.0, maxval=16.0) \
        * jnp.exp(jax.random.uniform(keys[4], (1, seq, heads),
                                     minval=jnp.log(1e-3),
                                     maxval=jnp.log(0.1)))
    beta = jax.nn.sigmoid(jax.random.normal(keys[5], (1, seq, heads)))
    do = jax.random.normal(keys[5], v.shape, jnp.bfloat16)
    out, results = {}, {}
    for impl in impls:
        f = lambda *a, impl=impl: forms[impl](*a, chunk=chunk)  # noqa: E731
        fwd = jax.jit(f)
        both = jax.jit(lambda *a, f=f: jax.vjp(f, *a)[1](do))
        for label, fn in (("fwd_ms", fwd), ("fwd_bwd_ms", both)):
            jax.block_until_ready(fn(q, k, v, g, beta))
            t0 = time.perf_counter()
            for _ in range(calls):
                res = fn(q, k, v, g, beta)
            jax.block_until_ready(res)
            out[f"{impl}.{label}"] = 1e3 * (time.perf_counter() - t0) / calls
        results[impl] = (fwd(q, k, v, g, beta).astype(jnp.float32),
                         [t.astype(jnp.float32) for t in res])
        print(impl, {n: round(x, 3) for n, x in out.items()
                     if n.startswith(impl)}, file=sys.stderr, flush=True)

    def rel(a, b):
        return float(jnp.sqrt(jnp.sum((a - b) ** 2) / jnp.sum(b ** 2)))

    for impl in impls[1:]:      # the kernels against each other form, bf16
        (o, grads), (o_, grads_) = results[impls[0]], results[impl]
        out[f"{impls[0]}_vs_{impl}.rel_rms"] = {
            "o": rel(o, o_), **{name: rel(a, b) for name, a, b in zip(
                ("dq", "dk", "dv", "dg", "dbeta"), grads, grads_)}}
    return out


def main() -> int:
    parser = argparse.ArgumentParser(description=__doc__)
    parser.add_argument("--seed", type=int, default=7200001)
    parser.add_argument("--rows", type=int, default=12)
    parser.add_argument("--first-row", type=int, default=0)
    parser.add_argument("--control-rows", type=int, default=3)
    parser.add_argument("--steps", type=int, default=24)
    parser.add_argument("--forms", type=int, default=0)
    parser.add_argument("--form-impls", default="pallas,xla,kda")
    parser.add_argument("--out", default="qwen3_next_on_chip")
    args = parser.parse_args()

    import jax

    from perfbench.harness import agreement, families, manifest
    from perfbench.harness.families import qwen3_next
    from perfbench.harness.tokens import ZipfStream
    from ray_tpu._private.platform import enable_compile_cache
    from ray_tpu.models.pretrain import ShardedPretrainer
    from ray_tpu.parallel.mesh import MeshConfig

    if jax.default_backend() != "tpu":
        print("qwen3_next_on_chip: no TPU; nothing was run", file=sys.stderr)
        return 1
    enable_compile_cache()
    cell = manifest.cell("qwen3-next-s16k-1chip")
    config, traffic = cell.config, cell.traffic
    limits = config["reference"]
    out = {"seed": args.seed, "device": jax.devices()[0].device_kind,
           "prefix": limits["prefix"],
           "limits": {k: limits[k + "_max"] for k in MEASURES}}
    os.makedirs(os.path.join(ROOT, "chiprun_out"), exist_ok=True)

    def save():
        with open(os.path.join(ROOT, "chiprun_out", args.out + ".json"),
                  "w") as f:
            json.dump(out, f)

    if args.forms:
        out["scan_forms_ms"] = scan_forms(config, traffic["seq"],
                                          args.form_impls.split(","))
        save()
    trainer = ShardedPretrainer(
        families.of(config).model_config(config, 1), MeshConfig())
    right = qwen3_next.logits

    def check(rows, wrong=None):
        if wrong:   # the reference as the wrong model, the program as it is
            qwen3_next.logits = lambda p, i, c: qwen3_next._forward(
                p, i, c, wrong)[0][..., :c["vocab_size"]]
        try:
            a = agreement.check(trainer, config, rows)
        finally:
            qwen3_next.logits = right
        a["outside"] = {k: bool(not a[k] <= limits[k + "_max"])
                        for k in MEASURES}
        # as the run goes, for a call that is cut before the object is printed
        print(wrong or "as_published", {k: a[k] for k in MEASURES},
              file=sys.stderr, flush=True)
        return a

    out["as_published"] = []
    out["wrong"] = {w: [] for w in qwen3_next.WRONG
                    + (qwen3_next.PRECISION_BELOW,)}

    def row(r):
        return ZipfStream(config["vocab_size"], args.seed
                          + 1000 * (args.first_row + r)).rows(
                              1, traffic["seq"])

    for r in range(args.rows):
        out["as_published"].append(check(row(r)))
        save()
    out["worst"] = {k: max((a[k] for a in out["as_published"]), default=None)
                    for k in MEASURES}
    # (the controls after the rows: a compile a wrong model, the long part of
    # a call that may be cut)
    for r in range(min(args.control_rows, args.rows)):
        for wrong, runs in out["wrong"].items():
            runs.append(check(row(r), wrong))
            save()
    out["wrong_outside"] = {
        wrong: {k: [a["outside"][k] for a in runs] for k in MEASURES}
        for wrong, runs in out["wrong"].items()}
    # (the members of UNSEEN_IN_BF16 are run and reported like the others,
    # and are not expected outside)
    out["every_wrong_model_is_outside_on_every_row"] = all(
        any(a["outside"].values())
        for wrong, runs in out["wrong"].items() for a in runs
        if wrong not in qwen3_next.UNSEEN_IN_BF16)
    save()

    # the timed path on the cell's own batches: the routing statistics at
    # initialisation and as the steps go, the losses, the memory's peak
    batches = ZipfStream(config["vocab_size"], args.seed).batches(
        traffic["rows_per_step"], traffic["seq"])
    out["steps"] = []
    for _ in range(args.steps):
        t0 = time.perf_counter()
        loss = float(trainer.step(next(batches)))
        out["steps"].append(dict(
            {k: float(v) for k, v in trainer.moe_stats.items()}, loss=loss,
            wall_ms=1e3 * (time.perf_counter() - t0)))
    stats = jax.devices()[0].memory_stats() or {}
    out["memory"] = {k: int(stats[k]) for k in
                     ("peak_bytes_in_use", "bytes_limit") if k in stats}
    save()
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())

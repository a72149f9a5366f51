"""python3 perfbench/tests/kimi_linear_on_chip.py [--seed N] [--rows R] [--control-rows C] [--steps S] [--forms 0|1] [--head-block N]
(on the chip; not a test)

The runs behind the limits in ``configs/kimi-linear-48b-a3b-instruct.json``:
at published widths, in one process that owns the chip, the cell's own
agreement check (``agreement.check``: the bf16 program on a seeded row of
16,384 tokens of the cell's traffic — the scan and the flash kernels run at
the full length — against ``families/kimi_linear.py`` in float32 on its first
1,024 positions, 16 chunks of the scan: logits, loss, gradient norm) on
``--rows`` seeded rows, and on the first ``--control-rows`` of them against
each wrong model of ``families/kimi_linear.py::WRONG`` — no decay, one decay
a head, ``b`` = 1, the delta correction dropped, the decay applied after the
correction, no l2norm on q and k, ``q`` unscaled, no convolution, the output
gate ``silu``, no output norm, MLA with a rotation at theta 10,000, ``kr`` a
head's own, softmax scores, top-6, routed scale 1, no renormalisation — which
must land outside at least one limit on every row (but those of
``UNSEEN_IN_BF16``), as must the reference itself computed with float8
activations (``PRECISION_BELOW``: the nearest precision below the
configuration's bf16).  Beside them the program's routing statistics on the
cell's own batches over ``--steps`` training steps, the losses and the wall
time of those steps, and the device's peak memory.

``--forms 1`` first times the scan alone at the cell's shape (1 x 16,384 x 32
heads x 128), forward and forward + backward, as the Mosaic kernels
(``ops/kda.py::kda_scan``) and as XLA fuses the chunked ``jax.numpy`` form
(``kda_scan_xla``, the yardstick); ``--head-block`` sets the heads a grid
step of the kernels takes (``ops/kda.py::_HEAD_BLOCK``), for timing another
than the one that ships.

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


def scan_forms(config, seq: int, impls, calls: int = 5):
    """ms a call of ``ops/kda.py``'s scan in each form at the cell's shape
    (1 x seq x 32 heads x 128, bf16 q, k, v; float32 log-decays as the
    mixer's initial values make them), forward alone and forward + backward
    (all five gradients), the operands made once on the device."""
    import jax
    import jax.numpy as jnp

    from ray_tpu.ops.kda import kda_scan, kda_scan_xla

    forms = {"pallas": kda_scan, "xla": kda_scan_xla}

    linear = config["linear_attn_config"]
    heads, d, chunk = linear["num_heads"], linear["head_dim"], \
        config["kda_chunk"]
    keys = jax.random.split(jax.random.PRNGKey(0), 6)

    def unit(key):
        t = jax.random.normal(key, (1, seq, heads, d))
        return (t * jax.lax.rsqrt(jnp.sum(t * t, -1, keepdims=True))
                ).reshape(1, seq, heads * d)

    q = (unit(keys[0]) * d ** -0.5).astype(jnp.bfloat16)
    k = unit(keys[1]).astype(jnp.bfloat16)
    v = jax.nn.silu(jax.random.normal(keys[2], (1, seq, heads * d))
                    ).astype(jnp.bfloat16)
    # A in [1, 16], steps in [1e-3, 0.1]: the mixer's start
    g = -jnp.repeat(jax.random.uniform(keys[3], (heads,), minval=1.0,
                                       maxval=16.0), d) \
        * jnp.exp(jax.random.uniform(keys[4], (1, seq, heads * d),
                                     minval=jnp.log(1e-3),
                                     maxval=jnp.log(0.1)))
    beta = jax.nn.sigmoid(jax.random.normal(keys[5], (1, seq, heads)))
    do = jax.random.normal(keys[5], v.shape, jnp.bfloat16)
    out = {}
    for impl in impls:
        f = lambda *a, impl=impl: forms[impl](*a, chunk=chunk)  # noqa: E731
        fwd = jax.jit(f)
        both = jax.jit(lambda *a, f=f: jax.vjp(f, *a)[1](do))
        for label, fn in (("fwd_ms", fwd), ("fwd_bwd_ms", both)):
            jax.block_until_ready(fn(q, k, v, g, beta))
            t0 = time.perf_counter()
            for _ in range(calls):
                r = fn(q, k, v, g, beta)
            jax.block_until_ready(r)
            out[f"{impl}.{label}"] = 1e3 * (time.perf_counter() - t0) / calls
        print(impl, {n: round(x, 3) for n, x in out.items()
                     if n.startswith(impl)}, file=sys.stderr, flush=True)
    if len(impls) > 1:      # the two forms on the same operands, bf16
        a, b = (jax.jit(lambda *a, impl=impl: forms[impl](*a, chunk=chunk))(
            q, k, v, g, beta).astype(jnp.float32) for impl in impls[:2])
        out["forms_rel_rms"] = float(jnp.sqrt(jnp.sum((a - b) ** 2)
                                              / jnp.sum(b ** 2)))
    return out


def main() -> int:
    parser = argparse.ArgumentParser(description=__doc__)
    parser.add_argument("--seed", type=int, default=3700001)
    parser.add_argument("--rows", type=int, default=12)
    parser.add_argument("--control-rows", type=int, default=3)
    parser.add_argument("--steps", type=int, default=24)
    parser.add_argument("--forms", type=int, default=0)
    parser.add_argument("--form-impls", default="pallas,xla")
    parser.add_argument("--head-block", type=int, default=0)
    args = parser.parse_args()

    import jax

    from perfbench.harness import agreement, families, manifest
    from perfbench.harness.families import kimi_linear
    from perfbench.harness.tokens import ZipfStream
    from ray_tpu._private.platform import enable_compile_cache
    from ray_tpu.models.pretrain import ShardedPretrainer
    from ray_tpu.parallel.mesh import MeshConfig

    if jax.default_backend() != "tpu":
        print("kimi_linear_on_chip: no TPU; nothing was run", file=sys.stderr)
        return 1
    enable_compile_cache()
    cell = manifest.cell("kimi-linear-s16k-1chip")
    config, traffic = cell.config, cell.traffic
    limits = config["reference"]
    out = {"seed": args.seed, "device": jax.devices()[0].device_kind,
           "prefix": limits["prefix"],
           "limits": {k: limits[k + "_max"] for k in MEASURES}}
    if args.head_block:     # the heads a grid step of the kernels takes
        from ray_tpu.ops import kda

        kda._HEAD_BLOCK = out["head_block"] = args.head_block
    if args.forms:
        out["scan_forms_ms"] = scan_forms(config, traffic["seq"],
                                          args.form_impls.split(","))
    trainer = ShardedPretrainer(
        families.of(config).model_config(config, 1), MeshConfig())
    right = kimi_linear.logits

    def check(rows, wrong=None):
        if wrong:   # the reference as the wrong model, the program as it is
            kimi_linear.logits = lambda p, i, c: kimi_linear._forward(
                p, i, c, wrong)[0][..., :c["vocab_size"]]
        try:
            a = agreement.check(trainer, config, rows)
        finally:
            kimi_linear.logits = right
        a["outside"] = {k: bool(not a[k] <= limits[k + "_max"])
                        for k in MEASURES}
        # as the run goes, for a call that is cut before the object is printed
        print(wrong or "as_published", {k: a[k] for k in MEASURES},
              file=sys.stderr, flush=True)
        return a

    out["as_published"] = []
    out["wrong"] = {w: [] for w in kimi_linear.WRONG + (kimi_linear.PRECISION_BELOW,)}
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
        if wrong not in kimi_linear.UNSEEN_IN_BF16)

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
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())

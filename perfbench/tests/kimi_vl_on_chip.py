"""python3 perfbench/tests/kimi_vl_on_chip.py [--seed N] [--rows R] [--control-rows C] [--steps S] [--forms 0|1]
(on the chip; not a test)

The runs behind the limits in ``configs/kimi-vl-a3b-instruct.json``: at
published widths, in one process that owns the chip, the cell's own agreement
check (``agreement.check``: the bf16 program on a seeded row of 16,384 tokens
of the cell's traffic — the kernels run at the full length — against
``families/kimi_vl.py`` in float32 on its first 1,024 positions: logits, loss,
gradient norm) on ``--rows`` seeded rows, and on the first ``--control-rows``
of them against each wrong model of ``families/kimi_vl.py::WRONG`` — the
latent's norm left out, RoPE left off the shared key, the scores scaled by
``128 ** -0.5``, the values taken from the key half of ``Wukv``, one shared
expert of 1,408, the routed scale 1, top-5, softmax scores — which must land
outside at least one limit on every row (but those of ``UNSEEN_IN_BF16``), as
must the reference itself computed with float8 activations
(``PRECISION_BELOW``: the nearest precision below the configuration's bf16).
Beside them the program's routing statistics (``max_load``,
``moe_rows_held``, ``moe_buffer_rows``) on the cell's own batches at
initialisation and over ``--steps`` training steps, the losses of those steps,
and the device's peak memory.

``--forms 1`` first times the forms the attention kernels could take at the
cell's shape (16 heads, 16,384 positions, bf16; forward, and forward +
backward, ms a call): the key's parts as they are (what ships), the shared
rotary key broadcast to the heads and joined to each head's part in HBM
before a kernel 192 / 128 wide, and everything zero-padded to 256.

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


def kernel_forms(config, seq: int, calls: int = 10):
    """ms a call of each form, forward alone and forward + backward (all
    gradients), the operands made once on the device."""
    import jax
    import jax.numpy as jnp

    from ray_tpu.ops.attention import flash_attention

    h, dn, dr, dv = (config["num_attention_heads"], config["qk_nope_head_dim"],
                     config["qk_rope_head_dim"], config["v_head_dim"])
    keys = jax.random.split(jax.random.PRNGKey(0), 5)
    q, kn, v, kr, g = (
        jax.random.normal(key, (1, heads, seq, d), jnp.bfloat16)
        for key, heads, d in zip(keys, (h, h, h, 1, h),
                                 (dn + dr, dn, dv, dr, dv)))
    scale = (dn + dr) ** -0.5

    def parts(q, kn, v, kr):
        return flash_attention(q, kn, v, k_shared=kr)

    def joined(q, kn, v, kr):
        k = jnp.concatenate(
            [kn, jnp.broadcast_to(kr, (*kn.shape[:-1], dr))], axis=-1)
        return flash_attention(q, k, v)

    def padded(q, kn, v, kr):
        wide = 2 * dn
        k = jnp.concatenate(
            [kn, jnp.broadcast_to(kr, (*kn.shape[:-1], dr)),
             jnp.zeros((*kn.shape[:-1], wide - dn - dr), kn.dtype)], axis=-1)
        q = jnp.pad(q, ((0, 0),) * 3 + ((0, wide - dn - dr),))
        v = jnp.pad(v, ((0, 0),) * 3 + ((0, wide - dv),))
        return flash_attention(q, k, v, sm_scale=scale)[..., :dv]

    out = {}
    for name, f in (("parts", parts), ("joined_in_hbm", joined),
                    ("padded_to_256", padded)):
        fwd = jax.jit(f)
        both = jax.jit(lambda *a, f=f: jax.vjp(f, *a)[1](g))
        for label, fn in (("fwd_ms", fwd), ("fwd_bwd_ms", both)):
            jax.block_until_ready(fn(q, kn, v, kr))
            t0 = time.perf_counter()
            for _ in range(calls):
                r = fn(q, kn, v, kr)
            jax.block_until_ready(r)
            out[f"{name}.{label}"] = 1e3 * (time.perf_counter() - t0) / calls
        print(name, {k: round(x, 3) for k, x in out.items()
                     if k.startswith(name)}, file=sys.stderr, flush=True)
    return out


def main() -> int:
    parser = argparse.ArgumentParser(description=__doc__)
    parser.add_argument("--seed", type=int, default=3700001)
    parser.add_argument("--rows", type=int, default=12)
    parser.add_argument("--control-rows", type=int, default=3)
    parser.add_argument("--steps", type=int, default=24)
    parser.add_argument("--forms", type=int, default=0)
    args = parser.parse_args()

    import jax

    from perfbench.harness import agreement, families, manifest
    from perfbench.harness.families import kimi_vl
    from perfbench.harness.tokens import ZipfStream
    from ray_tpu._private.platform import enable_compile_cache
    from ray_tpu.models.pretrain import ShardedPretrainer
    from ray_tpu.parallel.mesh import MeshConfig

    if jax.default_backend() != "tpu":
        print("kimi_vl_on_chip: no TPU; nothing was run", file=sys.stderr)
        return 1
    enable_compile_cache()
    cell = manifest.cell("kimi-vl-s16k-1chip")
    config, traffic = cell.config, cell.traffic
    limits = config["reference"]
    out = {"seed": args.seed, "device": jax.devices()[0].device_kind,
           "prefix": limits["prefix"],
           "limits": {k: limits[k + "_max"] for k in MEASURES}}
    if args.forms:
        out["kernel_forms_ms"] = kernel_forms(config, traffic["seq"])
    trainer = ShardedPretrainer(
        families.of(config).model_config(config, 1), MeshConfig())
    right = kimi_vl.logits

    def check(rows, wrong=None):
        if wrong:   # the reference as the wrong model, the program as it is
            kimi_vl.logits = lambda p, i, c: kimi_vl._forward(
                p, i, c, wrong)[0][..., :c["vocab_size"]]
        try:
            a = agreement.check(trainer, config, rows)
        finally:
            kimi_vl.logits = right
        a["outside"] = {k: bool(not a[k] <= limits[k + "_max"])
                        for k in MEASURES}
        # as the run goes, for a call that is cut before the object is printed
        print(wrong or "as_published", {k: a[k] for k in MEASURES},
              file=sys.stderr, flush=True)
        return a

    out["as_published"] = []
    out["wrong"] = {w: [] for w in kimi_vl.WRONG + (kimi_vl.PRECISION_BELOW,)}
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
    # (the members of UNSEEN_IN_BF16 are run and reported like the others,
    # and are not expected outside)
    out["every_wrong_model_is_outside_on_every_row"] = all(
        any(a["outside"].values())
        for wrong, runs in out["wrong"].items() for a in runs
        if wrong not in kimi_vl.UNSEEN_IN_BF16)

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

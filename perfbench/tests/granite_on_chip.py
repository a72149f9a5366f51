"""python3 perfbench/tests/granite_on_chip.py [--seed N] [--rows R] [--control-rows C]   (on the chip; not a test)

The agreement check of ``granite-h-s8k-1chip`` outside a benchmark run, at
published widths and in one process that owns the chip: the bf16 program on a
seeded row of 8192 tokens against ``families/granite_hybrid.py`` on its first
512 positions (two chunks of the scan, so the carried state is inside),

- on ``--rows`` rows, each another seed: what the configuration's limits are
  set from;
- on the first ``--control-rows`` of them, against five wrong models, which
  must land outside those limits: the scores scaled by 1/8 where 1/64 is
  stated, ``residual_multiplier`` 1, RoPE on, the gate applied after the
  mixer's norm, and the scan's running sums of log-decays kept in bf16.

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
    parser.add_argument("--seed", type=int, default=2900001)
    parser.add_argument("--rows", type=int, default=12)
    parser.add_argument("--control-rows", type=int, default=3)
    args = parser.parse_args()

    import jax
    import jax.numpy as jnp

    from perfbench.harness import agreement, families, manifest
    from perfbench.harness.families import granite_hybrid
    from perfbench.harness.tokens import ZipfStream
    from ray_tpu.models.pretrain import ShardedPretrainer
    from ray_tpu.parallel.mesh import MeshConfig

    if jax.default_backend() != "tpu":
        print("granite_on_chip: no TPU; nothing was run", file=sys.stderr)
        return 1
    cell = manifest.cell("granite-h-s8k-1chip")
    config, seq = cell.config, cell.traffic["seq"]
    limits = config["reference"]
    trainer = ShardedPretrainer(
        families.of(config).model_config(config, 1), MeshConfig())
    forward = granite_hybrid.logits

    def check(rows, wrong_config=None, **wrong_keywords):
        granite_hybrid.logits = \
            lambda p, i, c: forward(p, i, c, **wrong_keywords)
        try:
            a = agreement.check(trainer, wrong_config or config, rows)
        finally:
            granite_hybrid.logits = forward
        a["outside"] = {k: bool(a[k] > limits[k + "_max"]) for k in MEASURES}
        return a

    controls = {
        "scores_times_1_8": dict(wrong_config=dict(
            config, attention_multiplier=0.125)),
        "residual_multiplier_1": dict(wrong_config=dict(
            config, residual_multiplier=1.0)),
        "rope_on": dict(wrong_config=dict(
            config, position_embedding_type="rope")),
        "gate_after_norm": dict(gate_after_norm=True),
        "bf16_running_sums": dict(decay_dtype=jnp.bfloat16),
    }
    out = {"seed": args.seed, "device": jax.devices()[0].device_kind,
           "limits": {k: limits[k + "_max"] for k in MEASURES},
           "as_published": [], "controls": {name: [] for name in controls}}
    for r in range(args.rows):
        rows = ZipfStream(config["vocab_size"], args.seed + r).rows(1, seq)
        out["as_published"].append(check(rows))
        if r < args.control_rows:
            for name, wrong in controls.items():
                out["controls"][name].append(check(rows, **wrong))
    out["worst"] = {k: max(a[k] for a in out["as_published"])
                    for k in MEASURES}
    out["controls_outside"] = {
        name: {k: [a["outside"][k] for a in runs] for k in MEASURES}
        for name, runs in out["controls"].items()}
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())

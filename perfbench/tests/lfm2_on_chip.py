"""python3 perfbench/tests/lfm2_on_chip.py [--seed N] [--rows R] [--control-rows C] [--steps S] [--forms 0|1|2]
(on the chip; not a test)

The runs behind the limits in ``configs/lfm2-24b-a2b.json``: at published
widths, in one process that owns the chip, the cell's own agreement check
(``agreement.check``: the bf16 program on a seeded row of 16,384 tokens of the
cell's traffic — the kernels run at the full length — against
``families/lfm2.py`` in float32 on its first 1,024 positions: logits, loss,
gradient norm) on ``--rows`` seeded rows, and on the first ``--control-rows``
of them, with a seeded non-zero selection bias on both sides, against each
wrong model of ``families/lfm2.py::WRONG`` — a gate left out (``B``, then
``C``), a convolution 4 wide, one that sees a position ahead, no q / k norm,
RoPE left off, top-3, softmax scores, no renormalisation, the bias added to
the weights — which must land outside at least one limit on every row (but
those of ``UNSEEN_IN_BF16``), as must the reference itself computed with
float8 activations (``PRECISION_BELOW``: the nearest precision below the
configuration's bf16); ``biased`` is the right model under that bias, which
must stay inside.  Beside them the program's routing statistics
(``max_load``, ``moe_rows_held``, ``moe_buffer_rows``) on the cell's own
batches at initialisation and over ``--steps`` training steps, the losses of
those steps, and the device's peak memory.

``--forms 1`` first times the forms the mixer's pass could take at the cell's
shape (2 x 16,384 x 2,048, bf16): the pass alone, forward and forward +
backward, by reverse mode through ``causal_conv``'s padded slices and by
``gated_short_conv``'s written-out backward; and the whole training step, ms a
step, with the mixer in each of four forms — ``in_proj`` as one matmul to 3 x
2,048 whose result is sliced, or as three matmuls whose results are arrays of
their own (``ThreeWayDense``: XLA folds the gates into their epilogues), each
with either backward; ``--forms 2`` the three-matmul form's two backwards
twice each, in turn, over 16 steps.

Prints one JSON object.  Exits 1 without a TPU.
"""

from __future__ import annotations

import argparse
import gc
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

MEASURES = ("logits_rel_rms", "loss_rel", "grad_norm_rel")


def _plain_mix(b, c, u, kernel):
    from ray_tpu.models.mamba import causal_conv

    return c * causal_conv(b * u, kernel)


def pass_forms(shape, calls: int = 20):
    """ms a call of the pass alone in each form, forward and forward +
    backward (all four gradients), the operands made once on the device."""
    import jax
    import jax.numpy as jnp

    from ray_tpu.models.mamba import gated_short_conv

    keys = jax.random.split(jax.random.PRNGKey(0), 5)
    b, c, u, g = (jax.random.normal(k, shape, jnp.bfloat16) for k in keys[:4])
    kernel = jax.random.normal(keys[4], (3, shape[-1]), jnp.bfloat16)
    out = {}
    for name, f in (("reverse_mode", _plain_mix),
                    ("written_backward", gated_short_conv)):
        fwd = jax.jit(f)
        both = jax.jit(lambda *a, f=f: jax.vjp(f, *a)[1](g))
        for label, fn in (("fwd_ms", fwd), ("fwd_bwd_ms", both)):
            jax.block_until_ready(fn(b, c, u, kernel))
            t0 = time.perf_counter()
            for _ in range(calls):
                r = fn(b, c, u, kernel)
            jax.block_until_ready(r)
            out[f"{name}.{label}"] = 1e3 * (time.perf_counter() - t0) / calls
    return out


def _mixer_form(one_dot: bool, written: bool):
    """``ShortConvMixer`` in another form, the parameter tree the same."""
    import flax.linen as nn
    import jax

    from ray_tpu.models import llama
    from ray_tpu.models.mamba import _conv_init, gated_short_conv

    class Mixer(nn.Module):
        config: llama.LlamaConfig

        @nn.compact
        def __call__(self, x):
            cfg, E = self.config, x.shape[-1]
            if one_dot:
                parts = nn.DenseGeneral((3, E), use_bias=False,
                                        dtype=cfg.dtype, name="in_proj")(x)
                b, c, u = (parts[..., i, :] for i in range(3))
            else:
                b, c, u = llama.ThreeWayDense(E, cfg.dtype, name="in_proj")(x)
            kernel = self.param("conv_kernel", _conv_init(cfg.conv_width),
                                (cfg.conv_width, E)).astype(cfg.dtype)
            with jax.named_scope("mix"):
                y = (gated_short_conv if written else _plain_mix)(
                    b, c, u, kernel)
            return nn.Dense(E, use_bias=False, dtype=cfg.dtype,
                            name="out_proj")(y)

    return Mixer


def step_forms(config, traffic, seed: int, forms, steps: int):
    """ms a training step of the cell with the mixer in each of ``forms``
    ((one matmul, written backward) pairs, in that order: a form may come
    twice), a trainer a form, one after the other (two do not fit the
    chip)."""
    import jax

    from perfbench.harness import families
    from perfbench.harness.tokens import ZipfStream
    from ray_tpu.models import llama
    from ray_tpu.models.pretrain import ShardedPretrainer
    from ray_tpu.parallel.mesh import MeshConfig

    shipped, out = llama.ShortConvMixer, {}
    for one_dot, written in forms:
        name = ("one_dot" if one_dot else "three_dots") + (
            ".written_backward" if written else ".reverse_mode")
        llama.ShortConvMixer = _mixer_form(one_dot, written)
        try:
            trainer = ShardedPretrainer(
                families.of(config).model_config(config, 1), MeshConfig())
            batches = ZipfStream(config["vocab_size"], seed).batches(
                traffic["rows_per_step"], traffic["seq"])
            for _ in range(2):
                jax.block_until_ready(trainer.step(next(batches)))
            t0 = time.perf_counter()
            for _ in range(steps):
                loss = trainer.step(next(batches))
            jax.block_until_ready((loss, trainer.state))
            out.setdefault(name, []).append(
                1e3 * (time.perf_counter() - t0) / steps)
        finally:
            llama.ShortConvMixer = shipped
        del trainer
        gc.collect()
        print(name, round(out[name][-1], 2), file=sys.stderr, flush=True)
    return out


# --forms: 1, each of the four once over 8 steps; 2, the two backwards of the
# three-matmul form twice each, in turn, over 16
FORMS = {1: ([(False, True), (False, False), (True, True), (True, False)], 8),
         2: ([(False, True), (False, False)] * 2, 16)}


def main() -> int:
    parser = argparse.ArgumentParser(description=__doc__)
    parser.add_argument("--seed", type=int, default=4100001)
    parser.add_argument("--rows", type=int, default=12)
    parser.add_argument("--control-rows", type=int, default=3)
    parser.add_argument("--steps", type=int, default=24)
    parser.add_argument("--forms", type=int, default=0)
    args = parser.parse_args()

    import jax

    from perfbench.harness import agreement, families, manifest
    from perfbench.harness.families import lfm2
    from perfbench.harness.tokens import ZipfStream
    from ray_tpu._private.platform import enable_compile_cache
    from ray_tpu.models.pretrain import ShardedPretrainer
    from ray_tpu.parallel.mesh import MeshConfig

    if jax.default_backend() != "tpu":
        print("lfm2_on_chip: no TPU; nothing was run", file=sys.stderr)
        return 1
    enable_compile_cache()
    cell = manifest.cell("lfm2-s16k-1chip")
    config, traffic = cell.config, cell.traffic
    limits = config["reference"]
    out = {"seed": args.seed, "device": jax.devices()[0].device_kind,
           "prefix": limits["prefix"],
           "limits": {k: limits[k + "_max"] for k in MEASURES}}
    if args.forms:
        out["pass_forms_ms"] = pass_forms(
            (traffic["rows_per_step"], traffic["seq"], config["hidden_size"]))
        print(out["pass_forms_ms"], file=sys.stderr, flush=True)
        out["step_forms_ms"] = step_forms(config, traffic, args.seed,
                                          *FORMS[args.forms])
    trainer = ShardedPretrainer(
        families.of(config).model_config(config, 1), MeshConfig())
    right = lfm2.logits
    # the controls' bias: seeded, a fifth of the spread of the scores
    keys = iter(jax.random.split(jax.random.PRNGKey(args.seed), 64))
    unbiased = trainer.state
    biased = (jax.tree_util.tree_map_with_path(
        lambda path, a: 0.1 * jax.random.normal(next(keys), a.shape, a.dtype)
        if path[-1].key == "selection_bias" else a, unbiased[0]),
        unbiased[1])

    def check(rows, wrong=None, state=unbiased):
        if wrong not in (None, "biased"):
            # the reference as the wrong model, the program as it is
            lfm2.logits = lambda p, i, c: lfm2._forward(
                p, i, c, wrong)[0][..., :c["vocab_size"]]
        trainer.state = state
        try:
            a = agreement.check(trainer, config, rows)
        finally:
            lfm2.logits, trainer.state = right, unbiased
        a["outside"] = {k: bool(not a[k] <= limits[k + "_max"])
                        for k in MEASURES}
        # as the run goes, for a call that is cut before the object is printed
        print(wrong or "as_published", {k: a[k] for k in MEASURES},
              file=sys.stderr, flush=True)
        return a

    out["as_published"], out["biased"] = [], []
    out["wrong"] = {w: [] for w in lfm2.WRONG + (lfm2.PRECISION_BELOW,)}
    for r in range(args.rows):
        rows = ZipfStream(config["vocab_size"], args.seed + 1000 * r).rows(
            1, traffic["seq"])
        out["as_published"].append(check(rows))
        if r < args.control_rows:
            out["biased"].append(check(rows, "biased", biased))
            for wrong, runs in out["wrong"].items():
                runs.append(check(rows, wrong, biased))
    out["worst"] = {k: max(a[k] for a in out["as_published"] + out["biased"])
                    for k in MEASURES}
    out["wrong_outside"] = {
        wrong: {k: [a["outside"][k] for a in runs] for k in MEASURES}
        for wrong, runs in out["wrong"].items()}
    # (the members of UNSEEN_IN_BF16 are run and reported like the others,
    # and are not expected outside)
    out["every_wrong_model_is_outside_on_every_row"] = all(
        any(a["outside"].values())
        for wrong, runs in out["wrong"].items() for a in runs
        if wrong not in lfm2.UNSEEN_IN_BF16)

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
    os.makedirs(os.path.join(ROOT, "chiprun_out"), exist_ok=True)
    # (a file a seed and a --forms: a later call does not overwrite it)
    with open(os.path.join(ROOT, "chiprun_out", "lfm2_on_chip."
                           f"{args.seed}.forms{args.forms}.json"), "w") as f:
        json.dump(out, f)
    return 0


if __name__ == "__main__":
    sys.exit(main())

"""On-chip flash-attention validation + tuning + flash-vs-XLA microbenchmark.

Run on a real TPU (JAX default backend must be tpu):

    python benchmarks/attention_tpu.py [--quick] [--out chiprun_out/ATTENTION_TPU.md]

Three phases:
  1. Correctness: ``ops.attention.flash_attention`` forward AND backward vs
     ``mha_reference`` (fp32 ground truth) on-chip, causal + non-causal,
     ragged seq lengths (non-block-multiple), bf16 inputs.
  2. Tile sweep: the forward alone over square tiles of 256 / 512 / 1024 at
     the shapes the benchmark's cells run, beside the rule's own choice
     (``attention._block``), and fwd+bwd at the rule's tile.
  3. flash vs XLA attention: fwd and fwd+bwd wall time + achieved FLOPs at
     several sequence lengths, bf16.

Writes a markdown report and prints one JSON summary line at the end.

Reference for the bench shape: the reference repo has no attention kernels at
all (SURVEY §5.7 — sequence parallelism is greenfield here); the comparison
axis is our own XLA-attention lowering on the same chip.
"""

from __future__ import annotations

import argparse
import functools
import json
import os
import sys
import time

import jax
import jax.numpy as jnp

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from ray_tpu.ops.attention import _block, flash_attention, mha_reference  # noqa: E402


def _time_fn(fn, q, k, v, iters=20, warmup=2):
    """Mean time of `iters` dependent calls (output feeds the next q, so the
    device cannot overlap them), ended by one block_until_ready — per-iter
    time = chip compute + amortized dispatch."""
    for _ in range(warmup):
        out = fn(q, k, v)
    jax.block_until_ready(out)
    t0 = time.perf_counter()
    cur = q
    for _ in range(iters):
        cur = fn(cur, k, v)
    jax.block_until_ready(cur)
    return (time.perf_counter() - t0) / iters, cur


def attn_flops(b, h, s_q, s_k, d, causal, bwd=False):
    # fwd: QK^T (2*s_q*s_k*d) + PV (2*s_q*s_k*d) per (b,h); causal halves it.
    f = 4.0 * b * h * s_q * s_k * d
    if causal:
        f *= 0.5
    if bwd:
        f *= 3.5  # dV, dP, dS·K, dS^T·Q recompute ≈ 2.5x fwd, + fwd recompute
    return f


def phase_correctness(report):
    rows = []
    key = jax.random.PRNGKey(0)
    cases = [
        ("causal 1024 bf16", 2, 4, 1024, 1024, 64, True, jnp.bfloat16),
        ("noncausal 512 bf16", 2, 4, 512, 512, 64, False, jnp.bfloat16),
        ("ragged 1000/72 f32", 1, 2, 1000, 72, 64, True, jnp.float32),
        ("cross 256q/1024k bf16", 1, 4, 256, 1024, 128, False, jnp.bfloat16),
    ]
    ok_all = True
    for name, b, h, sq, sk, d, causal, dt in cases:
        k1, k2, k3, key = jax.random.split(key, 4)
        q = jax.random.normal(k1, (b, h, sq, d), dt)
        k = jax.random.normal(k2, (b, h, sk, d), dt)
        v = jax.random.normal(k3, (b, h, sk, d), dt)

        def loss_flash(q, k, v):
            return jnp.sum(flash_attention(q, k, v, causal=causal) ** 2)

        def loss_ref(q, k, v):
            return jnp.sum(mha_reference(q, k, v, causal=causal) ** 2)

        o_f = flash_attention(q, k, v, causal=causal)
        o_r = mha_reference(q, k, v, causal=causal)
        fwd_err = float(jnp.max(jnp.abs(o_f.astype(jnp.float32)
                                        - o_r.astype(jnp.float32))))
        g_f = jax.grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
        g_r = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
        bwd_err = max(float(jnp.max(jnp.abs(a.astype(jnp.float32)
                                            - b_.astype(jnp.float32))))
                      for a, b_ in zip(g_f, g_r))
        # f32 tolerance is TPU-loose: the MXU's default f32 matmul uses
        # bf16 multiplies (jax default_matmul_precision), so the XLA
        # reference itself carries ~1e-2 error vs true f32
        tol = 5e-2 if dt == jnp.bfloat16 else 2e-2
        # grads scale with values; use a looser relative-ish cap
        gtol = tol * 40
        ok = fwd_err < tol and bwd_err < gtol
        ok_all &= ok
        rows.append((name, fwd_err, bwd_err, "PASS" if ok else "FAIL"))
    report.append("## 1. Correctness on-chip (max abs err vs fp32 reference)\n")
    report.append("| case | fwd err | bwd err | verdict |")
    report.append("|---|---|---|---|")
    for name, fe, be, v in rows:
        report.append(f"| {name} | {fe:.2e} | {be:.2e} | {v} |")
    report.append("")
    return ok_all


def phase_tuning(report, quick):
    """The forward alone over square tiles, at the shapes the benchmark's cells
    run (PERF.md, PR 26, has the runs that chose ``attention._block``).  The
    backward takes the rule's tile only, so its column is one number a shape."""
    shapes = [("gpt2 b24 h12 s1024 d64", 24, 12, 1024, 64),
              ("gpt2 b8 h12 s1024 d64", 8, 12, 1024, 64)]
    if not quick:
        shapes += [("mistral b1 h32 s8192 d128", 1, 32, 8192, 128),
                   ("olmoe b2 h16 s4096 d128", 2, 16, 4096, 128)]
    tiles = [256, 512, 1024]
    best_cfg = {}
    report.append("## 2. Tile sweep (forward alone, causal bf16; "
                  "`rule` is what `block_q=None` picks)\n")
    for name, b, h, s, d in shapes:
        key = jax.random.PRNGKey(1)
        k1, k2, k3 = jax.random.split(key, 3)
        q = jax.random.normal(k1, (b, h, s, d), jnp.bfloat16)
        k = jax.random.normal(k2, (b, h, s, d), jnp.bfloat16)
        v = jax.random.normal(k3, (b, h, s, d), jnp.bfloat16)
        rule = _block(s, d, jnp.bfloat16)
        report.append(f"### {name} (rule: {rule})\n")
        report.append("| tile | fwd ms | fwd TFLOP/s |")
        report.append("|---|---|---|")
        results = []
        for tile in tiles:
            if tile > s:
                continue
            f = jax.jit(functools.partial(
                flash_attention, causal=True, block_q=tile, block_k=tile))
            try:
                t_f, _ = _time_fn(f, q, k, v, iters=10)
            except Exception as e:  # compile failure at this tile
                report.append(f"| {tile} | ERR {type(e).__name__} | |")
                continue
            tf = attn_flops(b, h, s, s, d, True) / t_f / 1e12
            results.append((t_f, tile))
            report.append(f"| {tile} | {t_f*1e3:.2f} | {tf:.1f} |")
        f = jax.jit(functools.partial(flash_attention, causal=True))
        _g = jax.jit(jax.grad(
            lambda q, k, v: jnp.sum(f(q, k, v).astype(jnp.float32) ** 2),
            argnums=(0, 1, 2)))
        t_b, _ = _time_fn(lambda q, k, v: _g(q, k, v)[0], q, k, v, iters=10)
        report.append(f"\nfwd+bwd at the rule's tile: {t_b*1e3:.2f} ms\n")
        if results:
            best_cfg[name] = (min(results)[1], rule)
            report.append(f"Fastest forward: tile {best_cfg[name][0]}\n")
    return best_cfg


def phase_vs_xla(report, quick, summary):
    report.append("## 3. flash vs XLA attention (causal bf16, b*h=32, d=64)\n")
    report.append("| seq | flash fwd ms | xla fwd ms | speedup | flash f+b ms | xla f+b ms | speedup |")
    report.append("|---|---|---|---|---|---|---|")
    seqs = [1024, 4096] if quick else [1024, 2048, 4096, 8192, 16384]
    b, h, d = 4, 8, 64
    flash_j = jax.jit(functools.partial(flash_attention, causal=True))
    ref_j = jax.jit(functools.partial(mha_reference, causal=True))

    def lflash(q, k, v):
        return jnp.sum(flash_j(q, k, v).astype(jnp.float32) ** 2)

    def lref(q, k, v):
        return jnp.sum(ref_j(q, k, v).astype(jnp.float32) ** 2)

    _gflash = jax.jit(jax.grad(lflash, argnums=(0, 1, 2)))
    _gref = jax.jit(jax.grad(lref, argnums=(0, 1, 2)))
    gflash = lambda q, k, v: _gflash(q, k, v)[0]  # noqa: E731
    gref = lambda q, k, v: _gref(q, k, v)[0]  # noqa: E731
    for s in seqs:
        key = jax.random.PRNGKey(2)
        k1, k2, k3 = jax.random.split(key, 3)
        q = jax.random.normal(k1, (b, h, s, d), jnp.bfloat16)
        k = jax.random.normal(k2, (b, h, s, d), jnp.bfloat16)
        v = jax.random.normal(k3, (b, h, s, d), jnp.bfloat16)
        t_ff, _ = _time_fn(flash_j, q, k, v, iters=10)
        t_fb, _ = _time_fn(gflash, q, k, v, iters=10)
        try:
            t_rf, _ = _time_fn(ref_j, q, k, v, iters=10)
            t_rb, _ = _time_fn(gref, q, k, v, iters=10)
        except Exception:  # OOM at long seq: O(S^2) materialized
            report.append(f"| {s} | {t_ff*1e3:.2f} | OOM | — | {t_fb*1e3:.2f} | OOM | — |")
            summary.setdefault("xla_oom_at", s)
            continue
        report.append(
            f"| {s} | {t_ff*1e3:.2f} | {t_rf*1e3:.2f} | {t_rf/t_ff:.2f}x "
            f"| {t_fb*1e3:.2f} | {t_rb*1e3:.2f} | {t_rb/t_fb:.2f}x |")
        summary[f"speedup_fwd_s{s}"] = round(t_rf / t_ff, 3)
        summary[f"speedup_fwdbwd_s{s}"] = round(t_rb / t_fb, 3)
    report.append("")


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--quick", action="store_true")
    ap.add_argument("--out", default="chiprun_out/ATTENTION_TPU.md")
    args = ap.parse_args()

    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print(json.dumps({"error": "no TPU attached", "platform": dev.platform}))
        return 1
    report = [f"# Flash attention on {dev.device_kind} — validation + tuning\n"]
    report.append(f"Generated by `benchmarks/attention_tpu.py` (jax {jax.__version__}).\n")
    summary = {"device": dev.device_kind, "platform": "tpu"}

    t0 = time.time()
    print("phase 1: correctness...", flush=True)
    ok = phase_correctness(report)
    summary["correctness"] = "pass" if ok else "FAIL"
    print(f"phase 1 done ({time.time()-t0:.0f}s); phase 2: block sweep...",
          flush=True)
    best = phase_tuning(report, args.quick)
    summary["fastest_tile_and_rule"] = {k: list(v) for k, v in best.items()}
    print(f"phase 2 done ({time.time()-t0:.0f}s); phase 3: vs XLA...",
          flush=True)
    phase_vs_xla(report, args.quick, summary)
    summary["wall_s"] = round(time.time() - t0, 1)

    os.makedirs(os.path.dirname(os.path.abspath(args.out)), exist_ok=True)
    with open(args.out, "w") as f:
        f.write("\n".join(report) + "\n")
    print(json.dumps(summary))
    return 0 if ok else 2


if __name__ == "__main__":
    raise SystemExit(main())

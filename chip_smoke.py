"""chip_smoke.py — the quickest proof that the trainer's main path starts on the chip.

    python3 chip_smoke.py

Drives, through the entry points a user calls, ``ray_tpu.init()`` ->
``JaxTrainer(ScalingConfig(num_workers=1, tpus_per_worker=<chips on the host>))``
-> one train worker that builds ``ShardedPretrainer(GPT2Config(remat=False))``
— GPT-2-small at its full width (768 x 12 layers x vocab 50257), seq 1024,
bf16, Pallas flash attention, batch 16 per data-parallel replica, random
weights from a fixed seed — and takes STEPS optimizer steps on one fixed seeded
batch.  The worker checks what came out (finite, falling loss; the compiled
step holds two Mosaic calls per layer, the flash forward and the one kernel of
its backward; nothing compiles after the first step;
on four chips the shards and the loss are what the mesh implies; the flash
kernel agrees with the XLA reference) and raises on any miss, which fails the
run: there is no path from a failed phase to exit code 0.

This driver process never initializes a JAX backend: a chip belongs to one
process, and that process is the train worker.  With no chip the script exits
non-zero and takes no step on the CPU.  The last two lines of stdout are JSON
objects: first the report (model, phases, attention check, compile cache,
``"claim": null``), whose timings are smoke timings of one short run and not
benchmark metrics; then, last, the verdict and nothing else,
``{"ok": true, "device": {"platform": "tpu", "kind": "...", "count": N}}``,
with the device as the worker's JAX reports it.
"""

from __future__ import annotations

import json
import sys
import tempfile

SEQ = 1024
BATCH_PER_REPLICA = 16
STEPS = 10          # the first compiles; the rest are the warm window
SEED = 0
# flash vs mha_reference at the GPT-2 shape (b16 h12 s1024 d64 bf16), max abs
ATTN_FWD_TOL = 2e-2
ATTN_BWD_TOL = 1e-1
LONG_K, LONG_Q = 32768, 512  # the forward past the old whole-K/V-in-VMEM ceiling


def _require(ok: bool, what: str) -> None:
    if not ok:
        raise RuntimeError(f"chip_smoke: {what}")


def _memory_peaks(devices, key):
    return [int(d.memory_stats()[key]) for d in devices]


_BUILT = "/jax/core/compile/backend_compile_duration"


def _watch_compiles():
    """JAX's own compile events from here on, as ``(event, fun_name,
    seconds)``.  A ``backend_compile_duration`` event is one executable built,
    by the compiler or loaded from the persistent cache alike;
    ``cache_retrieval_time_sec`` is the part of it a cache hit spent loading."""
    import jax

    seen = []
    jax.monitoring.register_event_duration_secs_listener(
        lambda event, secs, **kw: seen.append(
            (event, kw.get("fun_name"), round(secs, 3)))
        if event.startswith(("/jax/core/compile/", "/jax/compilation_cache/"))
        else None)
    return seen


def _built(events):
    return [e for e in events if e[0] == _BUILT]


def _train_phase(config, mesh_config, devices, base_batch, seen):
    """STEPS steps of ``config`` on ``devices`` under ``mesh_config``; the
    global batch is ``base_batch`` once per data-parallel replica, so every
    layout of the same model and seed sees the same loss."""
    import re
    import time

    import jax
    import numpy as np

    from ray_tpu.models.pretrain import ShardedPretrainer

    trainer = ShardedPretrainer(config, mesh_config, devices=devices,
                                total_steps=STEPS)
    replicas = trainer.mesh.shape["dp"] * trainer.mesh.shape["fsdp"]
    batch = {k: np.tile(v, (replicas, 1)) for k, v in base_batch.items()}

    n_seen = len(seen)
    t0 = time.perf_counter()
    losses = [jax.block_until_ready(trainer.step(batch))]
    first_step_s = time.perf_counter() - t0
    first_step_events = seen[n_seen:]
    n_seen = len(seen)

    t0 = time.perf_counter()
    for _ in range(STEPS - 1):
        losses.append(trainer.step(batch))
    jax.block_until_ready(losses)
    warm_ms = (time.perf_counter() - t0) / (STEPS - 1) * 1e3
    late_compiles = _built(seen[n_seen:])
    peaks = _memory_peaks(devices, "peak_bytes_in_use")

    losses = [float(x) for x in losses]
    _require(all(np.isfinite(losses)), f"non-finite loss: {losses}")
    _require(losses[-1] < losses[0],
             f"loss did not fall over {STEPS} steps: {losses}")
    _require(not late_compiles,
             f"compiled after the first step: {late_compiles}")

    # what the step is made of, from the executable itself (a persistent-
    # cache hit: the step was compiled by the first trainer.step above)
    compiled = trainer.lower(batch).compile()
    mosaic_calls = len(re.findall(r'custom_call_target="tpu_custom_call"',
                                  compiled.as_text()))
    _require(mosaic_calls == 2 * config.n_layer,
             f"{mosaic_calls} tpu_custom_calls in the compiled step, expected "
             f"a flash forward and the backward's one kernel per layer = "
             f"{2 * config.n_layer}")
    mem = compiled.memory_analysis()

    out = {
        "mesh": {a: n for a, n in trainer.mesh.shape.items() if n > 1},
        "devices": len(devices),
        "global_batch": int(batch["input_ids"].shape[0]),
        "steps": STEPS,
        "warm_steps": STEPS - 1,
        "first_loss": losses[0],
        "last_loss": losses[-1],
        "first_step_s": round(first_step_s, 2),
        # seconds inside JAX while the step was built: tracing, lowering,
        # then the compiler — or the load from the persistent cache (whose
        # events carry no function name)
        "step_build_events": [
            [event.rsplit("/", 1)[-1], secs]
            for event, name, secs in first_step_events
            if name is None or "pretrain_step" in name],
        "step_compile_s": round(sum(
            secs for _, name, secs in _built(first_step_events)
            if "pretrain_step" in name), 2),
        "step_loaded_from_cache": any(
            event.endswith("cache_retrieval_time_sec")
            for event, _, _ in first_step_events),
        "compiles_after_first_step": len(late_compiles),
        "smoke_ms_per_step_warm": round(warm_ms, 1),
        "tpu_custom_calls": mosaic_calls,
        "compiler_argument_bytes": int(mem.argument_size_in_bytes),
        "compiler_temp_bytes": int(mem.temp_size_in_bytes),
        "peak_bytes_in_use": peaks,
        # the runtime's reservation for program temporaries, which
        # peak_bytes_in_use (live buffers) does not include
        "peak_bytes_reserved": _memory_peaks(devices, "peak_bytes_reserved"),
    }
    if len(devices) > 1:
        out.update(_layout_checks(trainer, batch, devices, peaks))
    return out


def _layout_checks(trainer, batch, devices, peaks):
    """Four chips do four chips' work: the batch is split over the replicas,
    every parameter lives in the shards its spec implies on every device, and
    no device carries more than the others."""
    import jax

    replicas = trainer.mesh.shape["dp"] * trainer.mesh.shape["fsdp"]
    global_batch = batch["input_ids"].shape[0]
    rows = [s.data.shape[0] for s in
            trainer.shard_batch(batch)["input_ids"].addressable_shards]
    _require(rows == [global_batch // replicas] * len(devices),
             f"input_ids rows per device {rows}, expected "
             f"{global_batch // replicas} on each of {len(devices)}")
    for path, leaf in jax.tree_util.tree_leaves_with_path(trainer.state[0]):
        want = leaf.sharding.shard_shape(leaf.shape)
        got = {s.device: s.data.shape for s in leaf.addressable_shards}
        _require(set(got) == set(devices) and set(got.values()) == {want},
                 f"{jax.tree_util.keystr(path)} {leaf.shape} under "
                 f"{leaf.sharding.spec}: shards {sorted(map(str, got.items()))}"
                 f", expected {want} on every device")
    spread = (max(peaks) - min(peaks)) / max(peaks)
    _require(spread <= 0.10,
             f"peak bytes differ by {spread:.1%} across devices: {peaks}")
    head = trainer.state[0]["lm_head"]["kernel"]
    return {
        "input_rows_per_device": rows,
        "lm_head_shape": list(head.shape),
        "lm_head_shard_shape": list(head.sharding.shard_shape(head.shape)),
        "peak_bytes_spread": round(spread, 4),
    }


def _attention_check():
    """Flash forward and backward against ``mha_reference`` at the GPT-2
    shape, and one forward over 32768 keys (past what a head's whole K/V in
    VMEM allowed: the kernel's KV grid axis at a length no cell has), on the
    chip."""
    import jax
    import jax.numpy as jnp

    from ray_tpu.ops.attention import flash_attention, mha_reference

    def max_abs_err(a, b):
        return float(jnp.max(jnp.abs(a.astype(jnp.float32)
                                     - b.astype(jnp.float32))))

    shape = (BATCH_PER_REPLICA, 12, SEQ, 64)
    q, k, v, g = (jax.random.normal(key, shape, jnp.bfloat16)
                  for key in jax.random.split(jax.random.PRNGKey(SEED), 4))

    def fwd_bwd(attn):
        out, vjp = jax.vjp(lambda q, k, v: attn(q, k, v, causal=True), q, k, v)
        return (out,) + vjp(g)

    got = jax.jit(lambda: fwd_bwd(flash_attention))()
    want = jax.jit(lambda: fwd_bwd(mha_reference))()
    errs = [max_abs_err(a, b) for a, b in zip(got, want)]
    _require(errs[0] <= ATTN_FWD_TOL and max(errs[1:]) <= ATTN_BWD_TOL,
             f"flash vs reference max abs err fwd {errs[0]:.3g} "
             f"(tol {ATTN_FWD_TOL}), dq/dk/dv {errs[1:]} (tol {ATTN_BWD_TOL})")

    # the last LONG_Q queries of a LONG_K-key sequence, through q_offset
    long_shape = (1, 2, LONG_K, 128)
    lq, lk, lv = (jax.random.normal(key, long_shape, jnp.bfloat16)
                  for key in jax.random.split(jax.random.PRNGKey(SEED + 1), 3))
    lq = lq[:, :, -LONG_Q:]
    long_err = max_abs_err(*(
        jax.jit(lambda attn=attn: attn(lq, lk, lv, causal=True,
                                       q_offset=LONG_K - LONG_Q))()
        for attn in (flash_attention, mha_reference)))
    _require(long_err <= ATTN_FWD_TOL,
             f"flash vs reference over {LONG_K} keys: max abs err "
             f"{long_err:.3g} (tol {ATTN_FWD_TOL})")
    return {"shape_bhsd": list(shape), "dtype": "bfloat16",
            "fwd_max_abs_err": errs[0], "bwd_max_abs_err": max(errs[1:]),
            "long_shape_bhsd": list(long_shape), "long_queries": LONG_Q,
            "long_fwd_max_abs_err": long_err,
            "fwd_tol": ATTN_FWD_TOL, "bwd_tol": ATTN_BWD_TOL}


def _cache_entries(path: str) -> int:
    import os

    return len(os.listdir(path)) if os.path.isdir(path) else 0


def _worker_loop(_config):
    """The train worker: owns every chip of the host, runs every phase."""
    import jax
    import numpy as np

    from ray_tpu import train
    from ray_tpu.models.gpt2 import GPT2Config
    from ray_tpu.parallel.mesh import MeshConfig

    devices = jax.local_devices()
    _require(devices[0].platform == "tpu" and jax.default_backend() == "tpu",
             f"worker is on {jax.default_backend()!r}, not the TPU")
    cache_dir = jax.config.jax_compilation_cache_dir
    _require(bool(cache_dir), "no persistent compile cache configured")
    cache_before = _cache_entries(cache_dir)

    seen = _watch_compiles()
    config = GPT2Config(remat=False)
    _require(config.attention_impl == "flash", "flash attention is the default")
    ids = np.random.default_rng(SEED).integers(
        0, config.vocab_size, (BATCH_PER_REPLICA, SEQ))
    base_batch = {"input_ids": ids, "targets": np.roll(ids, -1, axis=1)}

    phases = {}

    def run(name, mesh_config, devs):
        phases[name] = _train_phase(config, mesh_config, devs, base_batch,
                                    seen)
        # shown as it happens, so a later failure does not take it along
        print(f"chip_smoke: phase {name}: {json.dumps(phases[name])}",
              flush=True)

    # The mesh phase runs first: peak_bytes_in_use is a high-water mark for
    # the life of the process, and device 0 must not carry the one-chip
    # phase's peak into the comparison across devices.
    if len(devices) == 4:
        run("dp2_tp2", MeshConfig(dp=2, tp=2), devices)
    run("one_chip", MeshConfig(), devices[:1])
    if "dp2_tp2" in phases:
        a = phases["one_chip"]["first_loss"]
        b = phases["dp2_tp2"]["first_loss"]
        _require(abs(a - b) <= 1e-2 * abs(a),
                 f"first-step loss {b} on dp=2 x tp=2 vs {a} on one chip")
    attention = _attention_check()

    dev = jax.devices()[0]
    train.report({
        "device": {"platform": dev.platform, "kind": dev.device_kind,
                   "count": len(jax.devices())},
        "model": {"name": "gpt2-small", "n_embd": config.n_embd,
                  "n_layer": config.n_layer, "n_head": config.n_head,
                  "vocab_size": config.vocab_size, "seq": SEQ,
                  "dtype": "bfloat16", "attention": config.attention_impl,
                  "remat": config.remat,
                  "batch_per_replica": BATCH_PER_REPLICA, "seed": SEED},
        "phases": phases,
        "attention_check": attention,
        "compile_cache": {"dir": cache_dir, "entries_before": cache_before,
                          "entries_after": _cache_entries(cache_dir)},
    })


def _driver_touched_backend() -> bool:
    if "jax" not in sys.modules:
        return False
    from jax._src import xla_bridge

    return xla_bridge.backends_are_initialized()


def main() -> int:
    import ray_tpu
    from ray_tpu.accelerators import tpu_manager
    from ray_tpu.train import JaxTrainer, RunConfig, ScalingConfig

    chips = tpu_manager().get_current_node_num_accelerators()
    if chips == 0:
        print("chip_smoke: this host exposes no TPU chip; nothing was run",
              file=sys.stderr)
        return 1

    ray_tpu.init()
    try:
        with tempfile.TemporaryDirectory(prefix="chip_smoke_") as storage:
            result = JaxTrainer(
                _worker_loop,
                scaling_config=ScalingConfig(num_workers=1,
                                             tpus_per_worker=chips),
                run_config=RunConfig(name="chip-smoke", storage_path=storage,
                                     worker_report_timeout_s=1000.0),
            ).fit()
    finally:
        ray_tpu.shutdown()

    report = result.metrics
    device = report["device"]
    _require(device["platform"] == "tpu", f"ran on {device}")
    _require(device["count"] == chips,
             f"worker saw {device['count']} devices, host exposes {chips}")
    _require(not _driver_touched_backend(),
             "the driver process initialized a JAX backend")
    print(json.dumps({
        "device": device,
        "model": report["model"],
        "phases": report["phases"],
        "attention_check": report["attention_check"],
        "compile_cache": report["compile_cache"],
        "driver_backend_initialized": False,
        "claim": None,
    }))
    # the verdict, alone on the last line: exactly these keys
    print(json.dumps({"ok": True, "device": {
        "platform": str(device["platform"]), "kind": str(device["kind"]),
        "count": int(device["count"])}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())

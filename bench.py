"""Headline benchmark: single-chip GPT-2 pretraining step throughput.

Run by the driver on real TPU hardware at the end of every round; prints ONE
JSON line:

    {"metric": ..., "value": N, "unit": ..., "vs_baseline": N, ...extras}

Benchmark shape (BASELINE.json config #3 scaled to one chip): GPT-2-small
(124M params), seq 1024, bf16 activations, fused fwd+bwd+adamw step under one
jit via ``ShardedPretrainer`` on a 1-device mesh, Pallas flash attention.

``vs_baseline``: the reference repo publishes no GPT-2 tokens/sec number
(BASELINE.json "published": {}), so the comparable axis is MFU.  The
north-star target is >=90% of A100-NCCL throughput; A100 GPT-2-small trainers
typically reach ~40% MFU, so vs_baseline = measured_mfu / 0.40 (1.0 = parity
with a 40%-MFU A100-class baseline).

Without a TPU nothing is measured and the exit code is non-zero: a CPU timing
is never written under the name of a device metric.

Reference bench shape: release/release_logs/2.9.3/microbenchmark.json,
python/ray/_private/ray_perf.py.
"""

from __future__ import annotations

import json
import os
import time

# bf16 peak FLOP/s per chip, keyed by ``device_kind`` as JAX reports it (Google
# Cloud documentation, "TPU v5e": 197 TFLOP/s).  A device that is not in the
# table is an error, not a default.
TPU_PEAK_FLOPS = {
    "TPU v5 lite": 197e12,
}
A100_BASELINE_MFU = 0.40


def _llm_decode_bench(num_requests: int = 8, prompt_len: int = 32,
                      max_tokens: int = 32) -> dict:
    """Continuous-batching decode throughput + TTFT of the tiny-model
    engine (ray_tpu.llm): submit a burst, step inline to completion."""
    import numpy as np

    from ray_tpu.llm.engine import EngineCore
    from ray_tpu.llm.scheduler import SamplingParams

    core = EngineCore(engine_name="bench", num_pages=256, page_size=16,
                      max_batch_tokens=512)
    rng = np.random.default_rng(0)
    prompts = [rng.integers(1, core.config.vocab_size,
                            prompt_len).tolist()
               for _ in range(num_requests)]
    t0 = time.perf_counter()
    rids = [core.submit(p, SamplingParams(max_tokens=max_tokens))
            for p in prompts]
    core.run_until_done(rids)
    dt = time.perf_counter() - t0
    reqs = [core._requests[r] for r in rids]
    ttfts = [r.first_token_at - r.submitted_at for r in reqs
             if r.first_token_at is not None]
    stats = core.stats()
    return {
        "tokens_per_sec": round(stats["total_generated"] / dt, 1),
        "ttft_mean_s": round(sum(ttfts) / len(ttfts), 4) if ttfts else None,
        "requests": num_requests,
        "prompt_len": prompt_len,
        "max_tokens": max_tokens,
        "max_decode_batch": stats["max_decode_batch"],
        "preemptions": stats["preemptions"],
        "backend": core.cache.backend,
    }


def _lint_bench() -> dict:
    """Wall-clock of the full static-analysis suite over ray_tpu/ (the
    tier-1 lint gate).  Budget: < 10 s on CPU."""
    from ray_tpu import _lint

    from ray_tpu._lint import wire_contract as _wc

    t0 = time.perf_counter()
    result = _lint.run_lint()
    dt = time.perf_counter() - t0
    # the wire-contract extraction alone (it runs again inside run_lint's
    # wire-contract pass): the generated-IDL cost and surface size, so the
    # contract gate's footprint is tracked as the protocol grows
    t1 = time.perf_counter()
    pkg_dir = os.path.dirname(os.path.dirname(
        os.path.abspath(_lint.__file__)))
    contract = _wc.extract_contract(_lint.collect_files([pkg_dir]))
    dt_contract = time.perf_counter() - t1
    return {
        "seconds": round(dt, 3),
        "budget_s": 10.0,
        "within_budget": dt < 10.0,
        "files": result.files_checked,
        "checkers": len(result.checkers_run),
        "findings": len(result.findings),
        "baselined": len(result.baselined),
        "contract_extract_seconds": round(dt_contract, 3),
        "contract_methods": len(contract["methods"]),
        "contract_call_sites": sum(len(v)
                                   for v in contract["callers"].values()),
    }


def main() -> None:
    import sys

    import jax

    from ray_tpu._private.platform import enable_compile_cache

    enable_compile_cache()
    device = jax.devices()[0]
    if device.platform != "tpu":
        sys.exit(f"bench: needs a TPU, jax came up on {device.platform!r}; "
                 f"nothing was measured")
    if device.device_kind not in TPU_PEAK_FLOPS:
        sys.exit(f"bench: no peak FLOP/s on record for device_kind "
                 f"{device.device_kind!r}; add it to TPU_PEAK_FLOPS with its "
                 f"source")
    peak = TPU_PEAK_FLOPS[device.device_kind]

    import numpy as np

    from ray_tpu.models.gpt2 import GPT2Config
    from ray_tpu.models.pretrain import ShardedPretrainer
    from ray_tpu.parallel.mesh import MeshConfig

    # GPT-2 small (124M).  remat off: at this size every activation fits
    # v5e HBM comfortably, and full-remat costs ~+1 forward of MXU time
    # (~25% of the step) for memory we don't need.  Sweep knobs kept as
    # env overrides so on-chip tuning runs don't need code edits.
    config = GPT2Config(
        attention_impl=os.environ.get("RAY_TPU_BENCH_ATTN", "flash"),
        remat=os.environ.get("RAY_TPU_BENCH_REMAT", "0") == "1")
    batch = int(os.environ.get("RAY_TPU_BENCH_BS", "16"))
    seq = int(os.environ.get("RAY_TPU_BENCH_SEQ", "1024"))
    warmup, iters = 3, 10

    trainer = ShardedPretrainer(
        config, MeshConfig(dp=-1, fsdp=1, tp=1, sp=1),
        devices=[device], total_steps=warmup + iters + 1)

    n_params = sum(x.size for x in jax.tree_util.tree_leaves(trainer.state[0]))
    rng = np.random.default_rng(0)

    def make_batch():
        return {
            "input_ids": rng.integers(0, config.vocab_size, (batch, seq)),
            "targets": rng.integers(0, config.vocab_size, (batch, seq)),
        }

    data = make_batch()
    for _ in range(warmup):
        loss = trainer.step(data)
    jax.block_until_ready(trainer.state)

    t0 = time.perf_counter()
    for _ in range(iters):
        loss = trainer.step(data)
    # the final state depends on every step's fwd+bwd+adamw
    jax.block_until_ready(trainer.state)
    dt = time.perf_counter() - t0

    tokens = batch * seq * iters
    tokens_per_sec = tokens / dt
    # Training FLOPs/token ~= 6*N (fwd 2N + bwd 4N); attention term omitted
    # (underestimates slightly, so MFU is conservative).
    flops_per_step = 6 * n_params * batch * seq
    mfu = flops_per_step * iters / dt / peak

    result = {
        "metric": "gpt2_pretrain_tokens_per_sec_per_chip",
        "value": round(tokens_per_sec, 1),
        "unit": "tokens/s/chip",
        "vs_baseline": round(mfu / A100_BASELINE_MFU, 4),
        "mfu": round(mfu, 4),
        "step_ms": round(dt / iters * 1e3, 2),
        "n_params": int(n_params),
        "batch": batch,
        "seq": seq,
        "platform": device.platform,
        "device_kind": device.device_kind,
        "device_count": len(jax.devices()),
        "final_loss": round(float(loss), 4),
    }

    # Bench rig (ISSUE 12): pin bench workers to dedicated cores where the
    # box allows it and stamp every row with the topology it measured on.
    # RAY_TPU_BENCH_RIG=0 skips pinning; rows then carry pinned=false.
    from ray_tpu._private import bench_rig

    rig = bench_rig.metadata()
    result["rig"] = rig
    # pool exported to the subprocess benches below: their runtime workers
    # pin themselves in worker_main (empty dict on 1-core / rig-off)
    rig_env = bench_rig.pin_env(max(rig["num_cpus"], 2))

    # Core-runtime microbenchmarks (reference: ray_perf.py / BASELINE.md),
    # in a subprocess so runtime processes can't disturb the TPU number and
    # a runtime bug can't take down the headline line.
    if os.environ.get("RAY_TPU_BENCH_MICRO", "1") != "0":
        import subprocess
        import sys

        # Size the micro cluster like the reference's ray.init() does: to
        # the CPUs actually available (cgroup/affinity-aware).  Hard-coding
        # 4 workers oversubscribed the 1-core bench VM with context
        # switching (3.4k/s vs 8.6k/s async tasks at 1 worker).
        code = ("import json, ray_tpu; from ray_tpu._private.ray_perf "
                "import host_cpu_count, run_microbenchmarks; "
                "n = host_cpu_count(); "
                "ray_tpu.init(num_cpus=n, object_store_memory=1024**3); "
                "out = run_microbenchmarks(); out['num_cpus'] = n; "
                "print('MICRO=' + json.dumps(out))")
        env = dict(os.environ)
        env["JAX_PLATFORMS"] = "cpu"
        env.update(rig_env)
        try:
            # own process group: on timeout the WHOLE runtime tree (gcs,
            # nodelet, workers + their shm store) must die, not just the
            # direct child
            proc = subprocess.Popen([sys.executable, "-c", code],
                                    stdout=subprocess.PIPE,
                                    stderr=subprocess.PIPE, text=True,
                                    env=env, start_new_session=True)
            try:
                stdout, stderr = proc.communicate(timeout=120)
            except subprocess.TimeoutExpired:
                import signal

                os.killpg(proc.pid, signal.SIGKILL)
                proc.wait()
                raise
            for line in stdout.splitlines():
                if line.startswith("MICRO="):
                    result["micro"] = json.loads(line[len("MICRO="):])
                    break
            else:
                result["micro_error"] = (stderr or "no output")[-500:]
        except Exception as e:
            result["micro_error"] = repr(e)

    # Shared noop round-trip rate probe: a fresh runtime in a subprocess
    # measures sync-task throughput under `extra_env`.  Both the watchdog
    # and flight-recorder overhead guards A/B against it.
    import subprocess
    import sys

    rate_code = (
        "import json, time, ray_tpu\n"
        "from ray_tpu._private.ray_perf import host_cpu_count\n"
        "ray_tpu.init(num_cpus=host_cpu_count(), "
        "object_store_memory=1024**3)\n"
        "@ray_tpu.remote\n"
        "def noop():\n"
        "    return None\n"
        "ray_tpu.get(noop.remote())\n"
        "t0 = time.perf_counter(); n = 0\n"
        "while time.perf_counter() - t0 < 2.0:\n"
        "    ray_tpu.get(noop.remote()); n += 1\n"
        "print('RATE=' + json.dumps(round(n / "
        "(time.perf_counter() - t0), 1)))\n")

    def _noop_rate(extra_env):
        env = dict(os.environ)
        env["JAX_PLATFORMS"] = "cpu"
        env.update(extra_env)
        proc = subprocess.Popen([sys.executable, "-c", rate_code],
                                stdout=subprocess.PIPE,
                                stderr=subprocess.PIPE, text=True,
                                env=env, start_new_session=True)
        try:
            stdout, _ = proc.communicate(timeout=90)
        except subprocess.TimeoutExpired:
            import signal

            os.killpg(proc.pid, signal.SIGKILL)
            proc.wait()
            return None
        for line in stdout.splitlines():
            if line.startswith("RATE="):
                return json.loads(line[len("RATE="):])
        return None

    # Watchdog/sampler overhead guard (ISSUE 3): the hang watchdog polls
    # every busy worker and the stack sampler rides the worker RPC loop —
    # both must be free on the task hot path.  Measure the same noop
    # round-trip rate with the watchdog at a hot 0.5 s interval and fully
    # disabled; both numbers land in the bench record so a regression shows
    # up as a ratio drift, not a silent slowdown.
    if os.environ.get("RAY_TPU_BENCH_MICRO", "1") != "0":
        try:
            on = _noop_rate({"RAY_TPU_HANG_WATCHDOG_INTERVAL_S": "0.5"})
            off = _noop_rate({"RAY_TPU_HANG_WATCHDOG_INTERVAL_S": "0"})
            result["watchdog_overhead"] = {
                "tasks_sync_watchdog_on": on,
                "tasks_sync_watchdog_off": off,
                "ratio": round(on / off, 3) if on and off else None,
            }
        except Exception as e:
            result["watchdog_overhead"] = {"error": repr(e)}

    # Flight-recorder overhead guard (ISSUE 16): the black-box ring write
    # rides every task start/end (plus collective/pipeline/lease seams), so
    # its cost must be invisible on the sync hot path — the same bar the
    # watchdog met.  Interleaved A/B (alternating recorder-on/off rounds,
    # best-of per arm) cancels machine drift out of the ratio.
    if os.environ.get("RAY_TPU_BENCH_FLIGHTREC", "1") != "0":
        try:
            on = off = None
            for _ in range(2):
                r_on = _noop_rate({})  # recorder on: the shipped default
                r_off = _noop_rate({"RAY_TPU_FLIGHT_RECORDER_BYTES": "0"})
                on = max(on or 0.0, r_on) if r_on else on
                off = max(off or 0.0, r_off) if r_off else off
            result["flight_recorder"] = {
                "tasks_sync_recorder_on": on,
                "tasks_sync_recorder_off": off,
                "ratio": round(on / off, 3) if on and off else None,
            }
        except Exception as e:
            result["flight_recorder"] = {"error": repr(e)}

    # Continuous-profiler overhead guard (ISSUE 18): the sampler wakes at
    # profile_hz per process and walks every thread's frames, so its cost
    # must stay within noise at the canonical 19 Hz rate (and be exactly
    # one attribute read when disabled — the shipped default).  Same
    # interleaved A/B discipline as the flight recorder, one extra round:
    # the measured per-tick fold cost is ~44 us (sub-1% of a core at
    # 19 Hz), so any ratio drift past noise is a sampler regression.
    if os.environ.get("RAY_TPU_BENCH_PROFILER", "1") != "0":
        try:
            on = off = None
            for _ in range(3):
                r_on = _noop_rate({"RAY_TPU_PROFILE_HZ": "19"})
                r_off = _noop_rate({})  # profiler off: the shipped default
                on = max(on or 0.0, r_on) if r_on else on
                off = max(off or 0.0, r_off) if r_off else off
            result["profiler"] = {
                "tasks_sync_profiler_19hz": on,
                "tasks_sync_profiler_off": off,
                "ratio": round(on / off, 3) if on and off else None,
            }
        except Exception as e:
            result["profiler"] = {"error": repr(e)}

    # LLM continuous-batching decode throughput (ISSUE 4): tiny model on
    # the numpy engine — in-process (no runtime), so the number isolates
    # scheduler+cache+runner cost.  Recorded on every platform; the engine
    # backend is host-side either way (the TPU paged-attention path is the
    # planned upgrade), so the row is tagged with the backend it measured.
    if os.environ.get("RAY_TPU_BENCH_LLM", "1") != "0":
        try:
            result["llm_decode_throughput"] = _llm_decode_bench()
        except Exception as e:
            result["llm_decode_throughput"] = {"error": repr(e)}

    # Collective data-path A/B (ISSUE 8): allreduce sweep (64 KiB -> 64 MiB,
    # worlds 2/4) with serial vs chunk-pipelined vs int8-quantized vs
    # hierarchical variants interleaved on the same actor group.  Runs in a
    # subprocess that owns its runtime, like the microbenchmarks.
    if os.environ.get("RAY_TPU_BENCH_COLLECTIVE", "1") != "0":
        import subprocess
        import sys

        code = ("import json, ray_tpu; from ray_tpu._private.ray_perf "
                "import host_cpu_count; "
                "from ray_tpu._private.collective_bench "
                "import run_collective_bench; "
                "ray_tpu.init(num_cpus=max(host_cpu_count(), 4), "
                "object_store_memory=1024**3); "
                "print('COLLECTIVE=' + json.dumps(run_collective_bench()))")
        env = dict(os.environ)
        env["JAX_PLATFORMS"] = "cpu"
        env.update(rig_env)
        try:
            proc = subprocess.Popen([sys.executable, "-c", code],
                                    stdout=subprocess.PIPE,
                                    stderr=subprocess.PIPE, text=True,
                                    env=env, start_new_session=True)
            try:
                stdout, stderr = proc.communicate(timeout=420)
            except subprocess.TimeoutExpired:
                import signal

                os.killpg(proc.pid, signal.SIGKILL)
                proc.wait()
                raise
            for line in stdout.splitlines():
                if line.startswith("COLLECTIVE="):
                    result["collective"] = json.loads(
                        line[len("COLLECTIVE="):])
                    break
            else:
                result["collective_error"] = (stderr or "no output")[-500:]
        except Exception as e:
            result["collective_error"] = repr(e)

    # Failure-recovery rows (ISSUE 9): chaos-engine-scheduled worker kill
    # mid sync task + rank kill mid-allreduce (world 4), timing detection
    # and recovery so regressions in the fault paths show up as numbers.
    if os.environ.get("RAY_TPU_BENCH_RECOVERY", "1") != "0":
        import subprocess
        import sys

        code = ("import json, ray_tpu; from ray_tpu._private.ray_perf "
                "import host_cpu_count; "
                "from ray_tpu._private.recovery_bench "
                "import run_recovery_bench; "
                "ray_tpu.init(num_cpus=max(host_cpu_count(), 5), "
                "object_store_memory=1024**3); "
                "print('RECOVERY=' + json.dumps(run_recovery_bench()))")
        env = dict(os.environ)
        env["JAX_PLATFORMS"] = "cpu"
        env.update(rig_env)
        try:
            proc = subprocess.Popen([sys.executable, "-c", code],
                                    stdout=subprocess.PIPE,
                                    stderr=subprocess.PIPE, text=True,
                                    env=env, start_new_session=True)
            try:
                stdout, stderr = proc.communicate(timeout=300)
            except subprocess.TimeoutExpired:
                import signal

                os.killpg(proc.pid, signal.SIGKILL)
                proc.wait()
                raise
            for line in stdout.splitlines():
                if line.startswith("RECOVERY="):
                    result["recovery"] = json.loads(
                        line[len("RECOVERY="):])
                    break
            else:
                result["recovery_error"] = (stderr or "no output")[-500:]
        except Exception as e:
            result["recovery_error"] = repr(e)

    # Pipeline-parallel A/B (ISSUE 10): tiny-GPT-2 tokens/sec, 1-stage vs
    # 2-stage 1F1B at M in {1,4,8}, interleaved rounds with min-of-rounds,
    # measured bubble fraction next to the theoretical (S-1)/(S-1+M) and
    # the overlap-accounted projection for boxes that serialize the stages.
    # Subprocess so the forced 1-device CPU jax config can't leak into the
    # headline TPU measurement.
    if os.environ.get("RAY_TPU_BENCH_PIPELINE", "1") != "0":
        import subprocess
        import sys

        code = ("import json; from ray_tpu._private.pipeline_bench "
                "import run_pipeline_bench; "
                "print('PIPELINE=' + json.dumps(run_pipeline_bench()))")
        env = dict(os.environ)
        env["JAX_PLATFORMS"] = "cpu"
        env.update(rig_env)
        env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=1"
        try:
            proc = subprocess.Popen([sys.executable, "-c", code],
                                    stdout=subprocess.PIPE,
                                    stderr=subprocess.PIPE, text=True,
                                    env=env, start_new_session=True)
            try:
                stdout, stderr = proc.communicate(timeout=420)
            except subprocess.TimeoutExpired:
                import signal

                os.killpg(proc.pid, signal.SIGKILL)
                proc.wait()
                raise
            for line in stdout.splitlines():
                if line.startswith("PIPELINE="):
                    result["pipeline"] = json.loads(
                        line[len("PIPELINE="):])
                    break
            else:
                result["pipeline_error"] = (stderr or "no output")[-500:]
        except Exception as e:
            result["pipeline_error"] = repr(e)

    # 3D-parallel train sweep (ARCHITECTURE §4d): (dp, tp, pp) in
    # {(2,1,1), (1,1,2), (2,1,2)} on tiny-GPT-2, recording step wall,
    # comm-bucket seconds, dp wire bytes and measured overlap fraction per
    # config, plus the fp32 -> int8 wire ratio on the (2,1,1) dp exchange.
    # Subprocess for the same 1-device CPU isolation as the pipeline rows.
    if os.environ.get("RAY_TPU_BENCH_TRAIN3D", "1") != "0":
        import subprocess
        import sys

        code = ("import json; from ray_tpu._private.pipeline_bench "
                "import run_train_3d_bench; "
                "print('TRAIN3D=' + json.dumps(run_train_3d_bench()))")
        env = dict(os.environ)
        env["JAX_PLATFORMS"] = "cpu"
        env.update(rig_env)
        env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=1"
        try:
            proc = subprocess.Popen([sys.executable, "-c", code],
                                    stdout=subprocess.PIPE,
                                    stderr=subprocess.PIPE, text=True,
                                    env=env, start_new_session=True)
            try:
                stdout, stderr = proc.communicate(timeout=420)
            except subprocess.TimeoutExpired:
                import signal

                os.killpg(proc.pid, signal.SIGKILL)
                proc.wait()
                raise
            for line in stdout.splitlines():
                if line.startswith("TRAIN3D="):
                    result["train_3d"] = json.loads(
                        line[len("TRAIN3D="):])
                    break
            else:
                result["train_3d_error"] = (stderr or "no output")[-500:]
        except Exception as e:
            result["train_3d_error"] = repr(e)

    # Serving-at-scale rows (ISSUE 13): prefix-cache prefill reduction,
    # chunked-prefill ITL A/B, and the SSE load harness (hundreds of
    # concurrent streams against a 2-replica deployment through the real
    # HTTP proxy).  Subprocess so the serve runtime can't leak into later
    # sections.
    if os.environ.get("RAY_TPU_BENCH_SERVE", "1") != "0":
        import subprocess
        import sys

        code = ("import json, ray_tpu; from ray_tpu._private.ray_perf "
                "import host_cpu_count; "
                "from ray_tpu._private.serve_load_bench "
                "import run_serve_load_bench; "
                "ray_tpu.init(num_cpus=max(host_cpu_count(), 4), "
                "object_store_memory=1024**3); "
                "print('SERVE_LOAD=' + json.dumps(run_serve_load_bench()))")
        env = dict(os.environ)
        env["JAX_PLATFORMS"] = "cpu"
        env.update(rig_env)
        try:
            proc = subprocess.Popen([sys.executable, "-c", code],
                                    stdout=subprocess.PIPE,
                                    stderr=subprocess.PIPE, text=True,
                                    env=env, start_new_session=True)
            try:
                stdout, stderr = proc.communicate(timeout=540)
            except subprocess.TimeoutExpired:
                import signal

                os.killpg(proc.pid, signal.SIGKILL)
                proc.wait()
                raise
            for line in stdout.splitlines():
                if line.startswith("SERVE_LOAD="):
                    result["serve_load"] = json.loads(
                        line[len("SERVE_LOAD="):])
                    break
            else:
                result["serve_load_error"] = (stderr or "no output")[-500:]
        except Exception as e:
            result["serve_load_error"] = repr(e)

    # RL sampling-loop rows (ISSUE 19): interleaved best-of-3 A/B of the
    # relaunch IMPALA loop vs the podracer streaming loop (env-steps/s),
    # plus a Sebulba row recording inference-batch occupancy and fragment
    # staleness p50/p95.  Subprocess so actor runtimes can't leak.
    if os.environ.get("RAY_TPU_BENCH_RL", "1") != "0":
        import subprocess
        import sys

        code = ("import json, ray_tpu; from ray_tpu._private.ray_perf "
                "import host_cpu_count; "
                "from ray_tpu._private.rl_bench import run_rl_bench; "
                "ray_tpu.init(num_cpus=max(host_cpu_count(), 4), "
                "object_store_memory=512 * 1024**2); "
                "print('RL_STEPS=' + json.dumps(run_rl_bench()))")
        env = dict(os.environ)
        env["JAX_PLATFORMS"] = "cpu"
        env.update(rig_env)
        try:
            proc = subprocess.Popen([sys.executable, "-c", code],
                                    stdout=subprocess.PIPE,
                                    stderr=subprocess.PIPE, text=True,
                                    env=env, start_new_session=True)
            try:
                stdout, stderr = proc.communicate(timeout=540)
            except subprocess.TimeoutExpired:
                import signal

                os.killpg(proc.pid, signal.SIGKILL)
                proc.wait()
                raise
            for line in stdout.splitlines():
                if line.startswith("RL_STEPS="):
                    result["rl_steps"] = json.loads(line[len("RL_STEPS="):])
                    break
            else:
                result["rl_steps_error"] = (stderr or "no output")[-500:]
        except Exception as e:
            result["rl_steps_error"] = repr(e)

    # Lint gate wall-clock (ISSUE 5): `ray_tpu lint` runs as a tier-1 test
    # on every PR; record its full-tree cost so the gate visibly stays
    # inside its < 10 s CPU budget instead of quietly becoming the slow
    # step as checkers accumulate.
    if os.environ.get("RAY_TPU_BENCH_LINT", "1") != "0":
        try:
            result["lint_tree"] = _lint_bench()
        except Exception as e:
            result["lint_tree"] = {"error": repr(e)}

    # Stamp the topology into every sub-bench row: a BENCH_*.json diff must
    # never compare a pinned 8-core number against an unpinned 1-core one
    # without seeing the difference in the row itself.
    for key in ("micro", "collective", "recovery", "pipeline", "train_3d",
                "llm_decode_throughput", "watchdog_overhead",
                "flight_recorder", "profiler", "lint_tree", "serve_load",
                "rl_steps"):
        if isinstance(result.get(key), dict):
            bench_rig.stamp(result[key], rig)

    print(json.dumps(result))


if __name__ == "__main__":
    main()

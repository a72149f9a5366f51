"""Compile-only pre-flight: what the Pallas interpreter used to hide.

The CPU suite runs the flash kernel interpreted, as plain XLA ops that GSPMD
partitions freely, so it cannot see what Mosaic and the TPU compiler refuse.
libtpu compiles for a ``v5e:2x2`` topology with no chip attached — the same
compiler the chip machine has, so its refusals are real.  Here the real
``train_step`` of GPT-2-small at full width (768 wide, vocab 50257, seq 1024,
bf16, flash attention; depth cut to 2 to stay cheap) is lowered and compiled
for one device and for the four-chip meshes.

Every case runs in a subprocess (this file, as a script): the libtpu client
must never meet the forced-CPU test process, and the child must NOT inherit
the request for the Pallas interpreter.

The full-compile cases take ~1 min and are ``slow``-marked: run them as the
pre-flight before spending chip time (``.claude/skills/verify/SKILL.md``).
"""

from __future__ import annotations

import json
import os
import subprocess
import sys

import pytest

N_LAYER = 2
SEQ = 1024
GLOBAL_BATCH = 32
# name -> (MeshConfig kwargs, devices of the 2x2 topology used)
CASES = {
    "one": ({}, 1),
    "dp4": ({"dp": 4}, 4),
    "fsdp4": ({"dp": 1, "fsdp": 4}, 4),
    "dp2_tp2": ({"dp": 2, "tp": 2}, 4),
}


def _build(case: str, compile_: bool) -> dict:
    """In the child: lower (and compile) the step for one case."""
    import re

    import jax
    import jax.numpy as jnp
    from jax.experimental import topologies

    from ray_tpu.models.gpt2 import GPT2Config
    from ray_tpu.models.pretrain import make_optimizer, sharded_train_step
    from ray_tpu.parallel.mesh import MeshConfig, build_mesh

    mesh_kwargs, n_devices = CASES[case]
    topo = topologies.get_topology_desc(
        platform="tpu", topology_name="v5e:2x2",
        chips_per_host_bounds=[2, 2, 1], num_slices=1)
    mesh = build_mesh(MeshConfig(**mesh_kwargs),
                      devices=topo.devices[:n_devices])
    config = GPT2Config(n_layer=N_LAYER, remat=False)
    assert config.vocab_size == 50257 and config.attention_impl == "flash"
    s = sharded_train_step(config, mesh, make_optimizer())
    batch = {k: jax.ShapeDtypeStruct((GLOBAL_BATCH, SEQ), jnp.int32,
                                     sharding=sh)
             for k, sh in s.batch_sharding.items()}
    with jax.set_mesh(mesh):
        lowered = s.step.trace(s.state, batch).lower(
            lowering_platforms=("tpu",))
    out = {"case": case,
           "lowered_has_mosaic": "tpu_custom_call" in lowered.as_text()}
    if compile_:
        compiled = lowered.compile()
        mem = compiled.memory_analysis()
        out["tpu_custom_calls"] = len(re.findall(
            r'custom_call_target="tpu_custom_call"', compiled.as_text()))
        out["temp_bytes"] = int(mem.temp_size_in_bytes)
        out["argument_bytes"] = int(mem.argument_size_in_bytes)
    return out


def _child(cases, compile_: bool) -> dict:
    env = {k: v for k, v in os.environ.items()
           if k not in ("RAY_TPU_PALLAS_INTERPRET", "XLA_FLAGS")}
    env["JAX_PLATFORMS"] = "cpu"  # no backend but the host; libtpu only compiles
    proc = subprocess.run(
        [sys.executable, os.path.abspath(__file__),
         "compile" if compile_ else "lower", *cases],
        env=env, capture_output=True, text=True, timeout=600)
    assert proc.returncode == 0, proc.stderr[-3000:]
    rows = [json.loads(line[len("CASE="):])
            for line in proc.stdout.splitlines() if line.startswith("CASE=")]
    assert [r["case"] for r in rows] == list(cases), proc.stdout[-2000:]
    return {r["case"]: r for r in rows}


def test_flash_step_lowers_for_a_sharded_v5e_mesh():
    """The cheap guard, in tier-1: under dp=2 x tp=2 at vocab 50257 the step
    lowers for the TPU with the Mosaic kernel in it.  At the seed this raised
    twice over — 'Mosaic kernels cannot be automatically partitioned' and an
    odd vocab dim under PartitionSpec('fsdp', 'tp')."""
    row = _child(["dp2_tp2"], compile_=False)["dp2_tp2"]
    assert row["lowered_has_mosaic"]


@pytest.mark.slow
def test_train_step_compiles_on_one_chip_and_every_four_chip_mesh():
    rows = _child(list(CASES), compile_=True)
    for case, row in rows.items():
        # per layer one flash forward and the two kernels of its backward
        # (dK/dV, dQ), as Mosaic calls, not interpreted
        assert row["tpu_custom_calls"] == 3 * N_LAYER, (case, row)
    # the fsdp axis splits the batch's compute, not only parameter storage:
    # at the same global batch a device holds about what it holds under dp
    assert rows["fsdp4"]["temp_bytes"] <= 1.3 * rows["dp4"]["temp_bytes"], rows
    # ... and it does shard the parameters
    assert rows["fsdp4"]["argument_bytes"] < 0.5 * rows["dp4"]["argument_bytes"]


if __name__ == "__main__":
    sys.path.insert(0, os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))
    for name in sys.argv[2:]:
        print("CASE=" + json.dumps(_build(name, sys.argv[1] == "compile")),
              flush=True)

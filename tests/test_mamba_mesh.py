"""Granite 4.0-H's toy through ``ShardedPretrainer`` (split from
``tests/test_mamba.py``, which holds the scan, the convolution and the hybrid
stack against its reference): (i) ``mamba/*`` under the partition rules on a
virtual mesh, whose steps give one device's losses, run once for both meshes
— the toy's one group of 128 channels takes the gated norm's kernels under
``fsdp`` and, cut by ``tp``, the reference's lines — and (i2) at eight groups
of 128, where ``tp`` takes whole groups and the kernels run inside their
``shard_map``; (j) the normal path at toy size learns.
"""

import jax
import numpy as np
import pytest

import toys
from perfbench.harness.families import granite_hybrid
from perfbench.harness.tokens import ZipfStream
from ray_tpu.models.pretrain import ShardedPretrainer
from ray_tpu.parallel.mesh import MeshConfig

TOY = toys.toy("toy-granite")


@pytest.mark.parametrize("mesh", [{"dp": 1, "fsdp": 2}, {"dp": 2, "tp": 2}])
def test_i_a_sharded_mesh_gives_the_single_device_loss(mesh):
    """``mamba/*`` under the partition rules (in_proj and out_proj as the
    attention's projections, the small leaves replicated) on a CPU virtual
    mesh: two steps equal the single-device steps, which both meshes read.
    No chip has run this."""
    P = jax.sharding.PartitionSpec
    n = int(np.prod(list(mesh.values())))
    one = toys.one_device("toy-granite", 4, 32, 2, seed=4, want=False)
    many = ShardedPretrainer(toys.config("toy-granite"), MeshConfig(**mesh),
                             devices=jax.devices()[:n])
    spec = many.param_specs["h_0"]["mamba"]
    assert spec["in_proj"]["kernel"] == P("fsdp", "tp")
    assert spec["out_proj"]["kernel"] == P("tp", "fsdp")
    for leaf in ("conv_kernel", "conv_bias", "dt_bias", "A_log", "D",
                 "norm_scale"):
        assert spec[leaf] == P(), leaf
    assert many.param_specs["wte"]["embedding"] == P("tp", "fsdp")
    for want in one.losses:     # the second step has been through an update
        assert float(many.step(one.rows)) == pytest.approx(want, rel=2e-5)


def _norm_calls(trainer, rows) -> dict:
    """{the gated norm takes its kernels, .. inside a ``shard_map``} of the
    trainer's forward on its mesh."""
    found = []

    def walk(jaxpr, sharded):
        for eqn in jaxpr.eqns:
            if eqn.primitive.name == "pallas_call":
                if eqn.params["name"] == "gated_norm_fwd":
                    found.append(sharded)
            for sub in jax.core.jaxprs_in_params(eqn.params):
                walk(sub, sharded or eqn.primitive.name == "shard_map")

    with jax.set_mesh(trainer.mesh):
        walk(jax.make_jaxpr(lambda p: trainer.model.apply(
            {"params": p}, rows["input_ids"]))(trainer.state[0]).jaxpr, False)
    return {"kernels": bool(found), "sharded": bool(found) and all(found)}


def test_i_the_gated_norm_runs_its_kernels_where_no_group_is_cut():
    """The toy's one group of 128 channels: the kernels on one device and
    under ``fsdp``; under ``tp`` = 2 the group would be cut, its statistic
    needs the other shard, and the reference's lines run (the losses of both
    meshes are (i)'s)."""
    one = toys.one_device("toy-granite", 4, 32, 2, seed=4, want=False)
    assert _norm_calls(one.trainer, one.rows)["kernels"]
    for mesh, kernels in (({"dp": 1, "fsdp": 2}, True),
                          ({"dp": 2, "tp": 2}, False)):
        n = int(np.prod(list(mesh.values())))
        many = ShardedPretrainer(toys.config("toy-granite"),
                                 MeshConfig(**mesh), devices=jax.devices()[:n])
        assert _norm_calls(many, one.rows)["kernels"] == kernels, mesh


def test_i2_eight_groups_under_a_tp_that_divides_them_give_one_devices_loss():
    """Eight groups of 128 channels (64 heads of 16): ``tp`` = 2 takes four
    whole groups a shard, the norm's kernels run inside their ``shard_map``
    as the scan's and the convolution's do, and two steps equal the
    single-device steps.  No chip has run this."""
    wide = dict(TOY, mamba_expand=16, mamba_n_heads=64, mamba_n_groups=8)
    one = toys.one_device(wide, 4, 32, 2, seed=4, want=False)
    many = ShardedPretrainer(toys.config(wide), MeshConfig(dp=2, tp=2),
                             devices=jax.devices()[:4])
    assert _norm_calls(many, one.rows) == {"kernels": True, "sharded": True}
    for want in one.losses:
        assert float(many.step(one.rows)) == pytest.approx(want, rel=2e-5)


def test_j_train_step_lowers_the_loss():
    """The normal path at toy size, bf16 activations, flash attention
    interpreted: the hybrid model learns the Zipf stream's unigrams."""
    trainer = ShardedPretrainer(granite_hybrid.model_config(TOY, 1),
                                MeshConfig(), lr=3e-2, total_steps=60,
                                devices=jax.devices()[:1])
    assert trainer.config.layer_types == ("mamba", "mamba", "attention")
    batches = ZipfStream(TOY["vocab_size"], seed=3).batches(4, 64)
    losses = [float(trainer.step(next(batches))) for _ in range(40)]
    assert all(np.isfinite(losses))
    # 6.23 to 5.3 when this was written; ln(512) is 6.24
    assert np.mean(losses[-3:]) < np.mean(losses[:3]) - 0.5
    assert trainer.moe_stats == {}

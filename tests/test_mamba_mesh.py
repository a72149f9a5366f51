"""Granite 4.0-H's toy through ``ShardedPretrainer`` (split from
``tests/test_mamba.py``, which holds the scan, the convolution and the hybrid
stack against its reference): (i) ``mamba/*`` under the partition rules on a
virtual mesh, whose steps give one device's losses, run once for both meshes;
(j) the normal path at toy size learns.
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

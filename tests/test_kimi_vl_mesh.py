"""Kimi-VL-A3B's decoder toy through ``ShardedPretrainer`` (split from
``tests/test_kimi_vl.py``, which holds the two-width kernels, the stack against
its reference and the share of a sparse layer): (b) a step on one device takes
the reference's loss down; (e) latent attention's partition rules on a virtual
mesh, whose steps give one device's losses, run once for both meshes.
"""

import jax
import pytest

import toys

TOY = toys.toy("toy-kimi-vl")


def test_b_the_trainers_step_takes_the_references_loss_down():
    """Through ``ShardedPretrainer``, the path the benchmark times: the first
    step's loss is the reference's on the same batch and weights, the steps
    report the held experts' counters, and the loss falls."""
    # (the schedule warms up over 100 steps: 0.1 is 0.011 by the twelfth)
    want, losses, stats, *_ = toys.one_device(TOY, 2, 64, 12, lr=0.1)
    assert losses[0] == pytest.approx(want, rel=1e-4)
    assert losses[-1] < losses[0] - 0.5
    assert set(stats) == {"load_balance", "z", "max_load", "moe_rows_held",
                          "moe_buffer_rows"}
    # two rows of 64 tokens take 3 of 16 experts each, 2 of them held here
    assert 0 < float(stats["moe_rows_held"]) <= 2 * 64 * 2


# ------------------------------------------------- (e) on a virtual mesh
@pytest.mark.parametrize("mesh", [{"dp": 1, "fsdp": 4}, {"dp": 2, "tp": 2}])
def test_e_a_sharded_mesh_gives_the_single_device_loss(mesh):
    """``attn/wdkv`` and ``attn/wukv`` shard by the Llama rules — the latent
    and the shared rotary key belong to no head, the up-projection's columns
    to the heads — and the step under them (the kernels inside ``shard_map``,
    the shared key whole on every device of a ``tp`` group and its gradient
    summed over the group) gives one device's losses."""
    from jax.sharding import PartitionSpec as P

    from ray_tpu.models.pretrain import ShardedPretrainer
    from ray_tpu.parallel.mesh import MeshConfig
    from ray_tpu.parallel.sharding import (llama_partition_rules,
                                           match_partition_rules)

    attn = match_partition_rules(llama_partition_rules(),
                                 toys.weights(TOY)[1])["h_1"]["attn"]
    assert attn["wq"]["kernel"] == attn["wukv"]["kernel"] == P("fsdp", "tp")
    assert attn["wdkv"]["kernel"] == P("fsdp", None)
    assert attn["wo"]["kernel"] == P("tp", "fsdp")
    assert attn["kv_norm"]["scale"] == P()

    one = toys.one_device(TOY, 4, 64, 2, want=False)    # for both meshes
    many = ShardedPretrainer(toys.config(TOY), MeshConfig(**mesh),
                             devices=jax.devices()[:4])
    for want in one.losses:     # the second step sees the first's gradients
        assert float(many.step(one.rows)) == pytest.approx(want, rel=1e-5)

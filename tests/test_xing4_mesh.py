"""Xing4.0's toy through ``ShardedPretrainer`` (split from
``tests/test_xing4.py``, which holds the stack against its reference): a step
on one device takes the reference's loss down and reports the
hyper-connections' statistics; the partition rules of the query latent, the
hyper-connections and the prediction module on a virtual mesh, whose steps
give one device's losses.
"""

import jax
import pytest

import toys

TOY = toys.toy("toy-xing4")
MTP = dict(TOY, num_nextn_predict_layers=1)


def test_the_trainers_step_takes_the_references_loss_down():
    """Through ``ShardedPretrainer``, the path the benchmark times, the module
    off as in the cell: the first step's loss is the reference's on the same
    batch and weights, the steps report the hyper-connections' statistics
    beside the routers', and the loss falls."""
    # (the schedule warms up over 100 steps: 0.1 is 0.011 by the twelfth)
    want, losses, stats, *_ = toys.one_device(TOY, 4, 64, 12, lr=0.1)
    assert losses[0] == pytest.approx(want, rel=1e-4)
    assert losses[-1] < losses[0] - 0.5
    assert set(stats) == {"load_balance", "z", "max_load", "moe_rows_held",
                          "moe_buffer_rows", "hc_res_row_err", "hc_pre_max"}
    assert 0 < float(stats["moe_rows_held"]) <= 4 * 64 * 2
    # alpha starts at 0.01: 20 iterations still converge after twelve steps
    assert float(stats["hc_res_row_err"]) < 1e-3
    assert 0.4 < float(stats["hc_pre_max"]) < 1.0


def test_a_sharded_mesh_gives_the_single_device_loss():
    """``attn/wq_a`` shards as ``wdkv`` (a latent belongs to no head),
    ``attn/wq_b`` as ``wukv`` (its columns are the heads'), the
    hyper-connections' four leaves are whole on every device — and no rule
    written for ``attn/`` takes ``hc_attn/phi`` —, the module's projection as
    a dense layer's; and the step under them on ``dp`` 2 x ``tp`` 2, the
    module on, gives one device's losses with both terms."""
    from jax.sharding import PartitionSpec as P

    from ray_tpu.models.pretrain import ShardedPretrainer
    from ray_tpu.parallel.mesh import MeshConfig
    from ray_tpu.parallel.sharding import (llama_partition_rules,
                                           match_partition_rules)

    specs = match_partition_rules(llama_partition_rules(),
                                  toys.weights(MTP)[1])
    attn = specs["h_1"]["attn"]
    assert attn["wq_a"]["kernel"] == attn["wdkv"]["kernel"] == P("fsdp", None)
    assert attn["wq_b"]["kernel"] == attn["wukv"]["kernel"] == P("fsdp", "tp")
    assert attn["q_norm"]["scale"] == P()
    for name in ("hc_attn", "hc_mlp"):
        assert set(specs["h_1"][name].values()) == {P()}
        assert set(specs["mtp_0"]["block"][name].values()) == {P()}
    assert specs["mtp_0"]["proj"]["kernel"] == P("fsdp", "tp")
    assert specs["mtp_0"]["block"]["attn"]["wq_b"]["kernel"] == P("fsdp", "tp")
    assert specs["mtp_0"]["norm_f"]["scale"] == P()

    one = toys.one_device(MTP, 4, 64, 2, want=False)
    assert {"loss_main", "loss_mtp"} <= set(one.stats)
    many = ShardedPretrainer(toys.config(MTP), MeshConfig(dp=2, tp=2),
                             devices=jax.devices()[:4])
    for want in one.losses:     # the second step sees the first's gradients
        assert float(many.step(one.rows)) == pytest.approx(want, rel=1e-5)
    assert float(many.moe_stats["loss_mtp"]) == pytest.approx(
        float(one.stats["loss_mtp"]), rel=1e-5)

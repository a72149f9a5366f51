"""Kimi-Linear-48B-A3B's toy through ``ShardedPretrainer`` (split from
``tests/test_kimi_linear.py``, which holds the mixers, the stack against its
reference and the share of a sparse layer): (c) a step on one device takes the
reference's loss down; (e) the new parameters' partition rules on a virtual
mesh, whose steps give the one device's losses, and a sharded sequence
refused.  One run on one device, three of the five layers (``SHORT``), is read
by (c) and by both meshes.
"""

import jax
import jax.numpy as jnp
import pytest

import toys
from ray_tpu.models.pretrain import init_params

TOY = toys.toy("toy-kimi-linear")
# three of the five layers, one of each kind (KDA + dense, KDA + sparse, MLA
# + sparse), for the steps that are compiled under a mesh
SHORT = dict(TOY, num_hidden_layers=3, linear_attn_config=dict(
    TOY["linear_attn_config"], kda_layers=[1, 2], full_attn_layers=[3]))


def _one_device():
    """Twelve steps of ``ShardedPretrainer`` on one device, four rows of 64,
    of the toy's first, second and fourth layer (``SHORT``), once for (c) and
    for both meshes of (e).  (The schedule warms up over 100 steps: 0.1 is
    0.011 by the twelfth.)"""
    return toys.one_device(SHORT, 4, 64, 12, lr=0.1)


def test_c_the_trainers_step_takes_the_references_loss_down():
    """Through ``ShardedPretrainer``, the path the benchmark times: the first
    step's loss is the reference's on the same batch and weights, the steps
    report the held experts' counters, and the loss falls."""
    want, losses, stats, *_ = _one_device()
    assert losses[0] == pytest.approx(want, rel=1e-4)
    assert losses[-1] < losses[0] - 0.5
    assert set(stats) == {"load_balance", "z", "max_load", "moe_rows_held",
                          "moe_buffer_rows"}
    # four rows of 64 tokens take 3 of 8 experts each, 2 of them held here
    assert 0 < float(stats["moe_rows_held"]) <= 4 * 64 * 2


# ------------------------------------------------- (e) on a virtual mesh
@pytest.mark.parametrize("mesh", [{"dp": 1, "fsdp": 4}, {"dp": 2, "tp": 2}])
def test_e_a_sharded_mesh_gives_the_single_device_loss(mesh):
    """The mixer's parameters shard by the Llama rules — the projections,
    the convolutions, ``A_log`` and ``dt_bias`` by head, the low-rank maps'
    down side by no head — and the step under them (the scan's kernels inside
    ``shard_map``, a ``tp`` group's heads each on its own device) gives one
    device's losses."""
    from jax.sharding import PartitionSpec as P

    from ray_tpu.models.pretrain import ShardedPretrainer
    from ray_tpu.parallel.mesh import MeshConfig
    from ray_tpu.parallel.sharding import (llama_partition_rules,
                                           match_partition_rules)

    cfg = toys.config(SHORT)
    kda = match_partition_rules(llama_partition_rules(), jax.eval_shape(
        lambda: init_params(cfg)[1]))["h_0"]["kda"]
    assert kda["q_proj"]["kernel"] == kda["b_proj"]["kernel"] \
        == P("fsdp", "tp")
    assert kda["f_a"]["kernel"] == kda["g_a"]["kernel"] == P("fsdp", None)
    assert kda["f_b"]["kernel"] == kda["g_b"]["kernel"] == P(None, "tp")
    assert kda["g_b"]["bias"] == kda["A_log"] == kda["dt_bias"] == P("tp")
    assert kda["q_conv"] == kda["v_conv"] == P(None, "tp")
    assert kda["o_proj"]["kernel"] == P("tp", "fsdp")
    assert kda["o_norm"]["scale"] == P()

    one = _one_device()
    many = ShardedPretrainer(cfg, MeshConfig(**mesh),
                             devices=jax.devices()[:4], lr=0.1)
    for want in one.losses[:2]:     # the second sees the first's gradients
        assert float(many.step(one.rows)) == pytest.approx(want, rel=1e-5)


def test_e_a_sharded_sequence_is_refused():
    """A ``kda`` layer carries its state across every position: under an
    ``sp`` axis it raises, in the words ``ops.attention`` refuses with."""
    from ray_tpu.parallel.mesh import MeshConfig, build_mesh

    from ray_tpu.models.llama import LlamaLMModel

    model = LlamaLMModel(toys.config(SHORT))
    mesh = build_mesh(MeshConfig(dp=1, sp=2), devices=jax.devices()[:2])
    with jax.set_mesh(mesh), pytest.raises(
            NotImplementedError, match="sharded on 'sp' has no 'kda' layer"):
        jax.eval_shape(model.init, jax.random.PRNGKey(0),
                       jnp.zeros((1, 16), jnp.int32))

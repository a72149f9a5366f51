"""Qwen3-Next-80B-A3B's toy through ``ShardedPretrainer`` (beside
``tests/test_qwen3_next.py``, which holds the mixers, the stack against its
reference and the share of a sparse layer): a step on one device takes the
reference's loss down; the new parameters' partition rules on a virtual mesh,
whose steps give the one device's losses; and what a mesh refuses: a sharded
sequence, and a ``tp`` that does not divide the key heads.  One run on one
device is read by the first and by both meshes.
"""

import jax
import jax.numpy as jnp
import pytest

import toys
from ray_tpu.models.pretrain import init_params

TOY = toys.toy("toy-qwen3-next")
# two of the four layers, one of each kind, for the steps that are compiled
# under a mesh
SHORT = dict(TOY, num_hidden_layers=2, full_attention_interval=2)


def _one_device():
    """Twelve steps of ``ShardedPretrainer`` on one device, four rows of 64,
    of a ``gdn`` and a gated attention layer (``SHORT``), once for the first
    test and for both meshes.  (The schedule warms up over 100 steps: 0.1 is
    0.011 by the twelfth.)"""
    return toys.one_device(SHORT, 4, 64, 12, lr=0.1)


def test_the_trainers_step_takes_the_references_loss_down():
    """Through ``ShardedPretrainer``, the path the benchmark times: the first
    step's loss is the reference's on the same batch and weights, the steps
    report the held experts' counters, and the loss falls."""
    want, losses, stats, *_ = _one_device()
    assert losses[0] == pytest.approx(want, rel=1e-4)
    assert losses[-1] < losses[0] - 0.5
    assert set(stats) == {"load_balance", "z", "max_load", "moe_rows_held",
                          "moe_buffer_rows"}
    # four rows of 64 tokens take 10 of 32 experts each, 8 of them held here
    assert 0 < float(stats["moe_rows_held"]) <= 4 * 64 * 8


@pytest.mark.parametrize("mesh", [{"dp": 1, "fsdp": 4}, {"dp": 2, "tp": 2}])
def test_a_sharded_mesh_gives_the_single_device_loss(mesh):
    """The mixer's parameters shard by the Llama rules — the two input
    projections and the convolution's kernel between key heads, ``A_log`` and
    ``dt_bias`` by value head in the key heads' order, ``out_proj`` by row —
    and the step under them (the scan's kernels inside ``shard_map``, a
    ``tp`` group's key heads each with its value heads on its own device)
    gives one device's losses."""
    from jax.sharding import PartitionSpec as P

    from ray_tpu.models.pretrain import ShardedPretrainer
    from ray_tpu.parallel.mesh import MeshConfig
    from ray_tpu.parallel.sharding import (llama_partition_rules,
                                           match_partition_rules)

    cfg = toys.config(SHORT)
    specs = match_partition_rules(llama_partition_rules(), jax.eval_shape(
        lambda: init_params(cfg)[1]))
    gdn = specs["h_0"]["gdn"]
    assert gdn["in_proj_qkvz"]["kernel"] == gdn["in_proj_ba"]["kernel"] \
        == P("fsdp", "tp", None)
    assert gdn["conv_kernel"] == P(None, "tp", None)
    assert gdn["A_log"] == gdn["dt_bias"] == P("tp")
    assert gdn["out_proj"]["kernel"] == P("tp", "fsdp")
    assert gdn["o_norm"]["scale"] == P()
    assert specs["h_1"]["attn"]["wq"]["kernel"] == P("fsdp", "tp")
    assert specs["h_0"]["moe"]["shared"]["gate"]["kernel"] == P("fsdp", None)

    one = _one_device()
    many = ShardedPretrainer(cfg, MeshConfig(**mesh),
                             devices=jax.devices()[:4], lr=0.1)
    for want in one.losses[:2]:     # the second sees the first's gradients
        assert float(many.step(one.rows)) == pytest.approx(want, rel=1e-5)


def test_a_sharded_sequence_is_refused():
    """A ``gdn`` layer carries its state across every position: under an
    ``sp`` axis it raises, in the words ``ops.attention`` refuses with."""
    from ray_tpu.models.llama import LlamaLMModel
    from ray_tpu.parallel.mesh import MeshConfig, build_mesh

    model = LlamaLMModel(toys.config(SHORT))
    mesh = build_mesh(MeshConfig(dp=1, sp=2), devices=jax.devices()[:2])
    with jax.set_mesh(mesh), pytest.raises(
            NotImplementedError, match="sharded on 'sp' has no 'gdn' layer"):
        jax.eval_shape(model.init, jax.random.PRNGKey(0),
                       jnp.zeros((1, 16), jnp.int32))


def test_a_tp_that_does_not_divide_the_key_heads_is_refused():
    """Two key heads over ``tp=4``: refused under ``jax.eval_shape``, before
    anything runs."""
    from ray_tpu.models.llama import LlamaLMModel
    from ray_tpu.parallel.mesh import MeshConfig, build_mesh

    model = LlamaLMModel(toys.config(SHORT))
    mesh = build_mesh(MeshConfig(dp=1, tp=4), devices=jax.devices()[:4])
    with jax.set_mesh(mesh), pytest.raises(
            ValueError, match="2 key heads over tp=4"):
        jax.eval_shape(model.init, jax.random.PRNGKey(0),
                       jnp.zeros((1, 16), jnp.int32))

"""LFM2-24B-A2B's toy through ``ShardedPretrainer`` (split from
``tests/test_lfm2.py``, which holds the mixer, the stack against its reference
and the layer's selection bias): (b) a step on one device takes the
reference's loss down, (d) and leaves the selection bias bit for bit, on the
same trainer; (e) the new parameters' partition rules on a virtual mesh, whose
steps give one device's losses, run once for both meshes.
"""

import jax
import numpy as np
import pytest

import toys
from perfbench.harness.tokens import ZipfStream

TOY = toys.toy("toy-lfm2")


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
    # two rows of 64 tokens take 4 of 16 experts each, 2 of them held here
    assert 0 < float(stats["moe_rows_held"]) <= 2 * 64 * 2


def test_d_an_optimizer_step_leaves_the_bias_bit_for_bit():
    """Under AdamW with weight decay the bias is what it was, bit for bit,
    after a step (and after a second, whose moments are no longer zero), and
    its moments stay zero; the router beside it moves."""
    # (b)'s trainer, stepped on from where (b) left it: the same compiled
    # step, and moments that are no longer zero from the first step here on
    trainer = toys.one_device(TOY, 2, 64, 12, lr=0.1).trainer
    keys = iter(jax.random.split(jax.random.PRNGKey(2), 8))
    params = jax.tree_util.tree_map_with_path(
        lambda path, a: 0.3 * jax.random.normal(next(keys), a.shape, a.dtype)
        if path[-1].key == "selection_bias" else a, trainer.state[0])
    trainer.state = (params, trainer.state[1])
    before = {k: np.array(params[k]["moe"]["selection_bias"])
              for k in ("h_1", "h_2", "h_3")}
    router = np.array(params["h_1"]["moe"]["router"]["kernel"])
    assert all(np.any(b) for b in before.values())
    rows = ZipfStream(trainer.config.vocab_size, seed=5).rows(2, 64)
    for _ in range(2):
        trainer.step(rows)
        after = trainer.state[0]
        for k, b in before.items():
            assert np.array(after[k]["moe"]["selection_bias"]).tobytes() \
                == b.tobytes()
    assert np.any(np.array(after["h_1"]["moe"]["router"]["kernel"])
                  != router)
    moments = [np.array(leaf) for path, leaf in
               jax.tree_util.tree_flatten_with_path(trainer.state[1])[0]
               if "selection_bias" in jax.tree_util.keystr(path)]
    assert len(moments) == 6 and not any(np.any(m) for m in moments)


# ------------------------------------------------- (e) on a virtual mesh
@pytest.mark.parametrize("mesh", [{"dp": 1, "fsdp": 4}, {"dp": 2, "tp": 2}])
def test_e_a_sharded_mesh_gives_the_single_device_loss(mesh):
    """``conv/in_proj`` shards each of ``B``, ``C``, ``u`` by channel, the
    depthwise kernel with them and ``conv/out_proj`` by rows, the selection
    bias is whole everywhere; the step under them gives one device's losses,
    and under ``tp`` the compiled step has no collective inside
    ``conv/mix``."""
    import re

    from jax.sharding import PartitionSpec as P

    from ray_tpu.models.pretrain import ShardedPretrainer
    from ray_tpu.parallel.mesh import MeshConfig
    from ray_tpu.parallel.sharding import (llama_partition_rules,
                                           match_partition_rules)

    specs = match_partition_rules(llama_partition_rules(),
                                  toys.weights(TOY)[1])
    conv = specs["h_0"]["conv"]
    assert conv["in_proj"]["kernel"] == P("fsdp", None, "tp")
    assert conv["out_proj"]["kernel"] == P("tp", "fsdp")
    assert conv["conv_kernel"] == P(None, "tp")
    assert specs["h_1"]["moe"]["selection_bias"] == P()
    assert specs["h_1"]["attn"]["q_norm"]["scale"] == P()

    one = toys.one_device(TOY, 4, 64, 2, want=False)    # for both meshes
    rows = one.rows
    many = ShardedPretrainer(toys.config(TOY), MeshConfig(**mesh),
                             devices=jax.devices()[:4])
    if "tp" in mesh:
        text = many.lower(rows).compile().as_text()
        collectives = [line for line in text.splitlines() if re.search(
            r"= \S+ (all-reduce|all-gather|all-to-all|collective-permute|"
            r"reduce-scatter)", line)]
        assert collectives     # the step has them: out_proj's sum, for one
        assert not [line for line in collectives if "/conv/mix/" in line]
    for want in one.losses:     # the second step sees the first's gradients
        assert float(many.step(rows)) == pytest.approx(want, rel=1e-5)

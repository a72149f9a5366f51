"""``flash_bwd_calls_per_step`` (PR 24): the Mosaic calls of the attention
backward, and none of the forward's.  A file of its own beside
``test_trace_reduce.py::test_flash_forward_metrics_leave_every_other_kernel_out``
because a PR that claims a gain edits no file the benchmark has."""

import json
import os

from perfbench.harness.readers import trace_ops
from perfbench.harness.trace_reduce import Op
from perfbench.tests.recorded import mistral_step

HERE = os.path.dirname(os.path.abspath(__file__))


def _args(metric):
    with open(os.path.join(os.path.dirname(HERE), "layer_metrics",
                           metric + ".json")) as f:
        return json.load(f)["args"]


def test_flash_backward_calls_are_the_backward_kernels_and_no_forward_one():
    """The recorded Mistral step (PR 22: four forward calls, two of them
    recomputation, and an XLA-scan backward) with the two kernels of a
    rematted and of a plain block added under the name paths JAX gives them."""
    ops = mistral_step().ops[0]      # its forward calls under today's scope
    end = ops[-1].end
    bwd = [Op(f"attn.{90 + i}", "custom-call:tpu_custom_call", path,
              end + i, end + i + 0.5) for i, path in enumerate([
        "jit(pretrain_step)/transpose(jvp(LlamaLMModel))/jvp(LlamaLMModel)/"
        "checkpoint/h_1/attn/flash_bwd/flash_bwd_dkv/pallas_call",
        "jit(pretrain_step)/transpose(jvp(LlamaLMModel))/jvp(LlamaLMModel)/"
        "checkpoint/h_1/attn/flash_bwd/flash_bwd_dq/pallas_call",
        "jit(pretrain_step)/transpose(jvp(GPT2LMModel))/h_3/attn/flash_bwd/"
        "flash_bwd_dkv/pallas_call",
        "jit(pretrain_step)/transpose(jvp(GPT2LMModel))/h_3/attn/flash_bwd/"
        "flash_bwd_dq/pallas_call",
    ])]
    args = _args("flash_bwd_calls_per_step")
    assert args.pop("as_") == "calls_per_step"
    # at the parent of PR 24 the scope holds no Mosaic call: the metric is null
    assert trace_ops.selected(ops, **args) == []
    found = trace_ops.selected(ops + bwd, **args)
    assert [o.name for o, _ in found] == [
        "attn.90", "attn.91", "attn.92", "attn.93"]
    # ... and the forward's selection still finds its four and none of these
    fwd = _args("flash_fwd_calls_per_step")
    fwd.pop("as_")
    assert [o.name for o, _ in trace_ops.selected(ops + bwd, **fwd)] == [
        "attn.4", "attn.5", "attn.6", "attn.7"]

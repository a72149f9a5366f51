"""The training path's own spans and scopes (ISSUE 23): host spans in the JAX
profiler's trace at the layer boundaries of the step loop, named scopes inside
the jitted step, and the two always-on histograms beside them.

CPU only: what is checked is that the names are there, nest, cost nothing
without a session and leave the compiled program alone — never a time.
"""

import contextlib
import glob
import os
import re
import subprocess
import sys

import numpy as np
import pytest

HOST_SPANS = {
    "ray_tpu/train/report", "ray_tpu/train/report/heartbeat",
    "ray_tpu/train/report/persist", "ray_tpu/train/report/handoff_wait",
    "ray_tpu/data/pull_block", "ray_tpu/data/rebatch",
    "ray_tpu/data/device_put",
    "ray_tpu/step", "ray_tpu/step/shard_batch", "ray_tpu/step/dispatch"}
STEPS = 3


def _toy_config(family):
    from ray_tpu.models.gpt2 import GPT2Config
    from ray_tpu.models.llama import LlamaConfig

    if family == "gpt2":
        return GPT2Config(vocab_size=256, n_positions=64, n_embd=32,
                          n_layer=1, n_head=2, remat=False)
    return LlamaConfig(vocab_size=256, n_positions=64, d_model=32, n_layer=1,
                       n_head=4, n_kv_head=2, d_ff=64, remat=True,
                       attention_impl="reference"
                       if family == "llama-reference" else "flash")


def _toy_trainer(family):
    import jax

    from ray_tpu.models.pretrain import ShardedPretrainer
    from ray_tpu.parallel.mesh import MeshConfig

    return ShardedPretrainer(_toy_config(family), MeshConfig(),
                             devices=jax.devices()[:1])


def _toy_ids(rows, seq=64):
    return np.random.default_rng(0).integers(
        0, 256, (rows, seq)).astype(np.int32)


def _program_spans(trace_dir):
    """thread -> [(name, start_ns, end_ns)] of the trace's ``ray_tpu/*``
    events, from the host plane."""
    from jax.profiler import ProfileData

    (path,) = glob.glob(os.path.join(trace_dir, "plugins", "profile", "*",
                                     "*.xplane.pb"))
    (host,) = [p for p in ProfileData.from_file(path).planes
               if p.name == "/host:CPU"]
    threads = []
    for line in host.lines:
        spans = [(e.name, e.start_ns, e.start_ns + e.duration_ns)
                 for e in line.events if e.name.startswith("ray_tpu/")]
        if spans:
            threads.append(spans)
    return threads


@pytest.fixture(scope="module")
def toy_loop(tmp_path_factory):
    """Three steps of the loop a Ray-Train example shows — a ``from_numpy``
    shard's ``iter_jax_batches``, ``ShardedPretrainer.step``, ``float(loss)``,
    ``report`` through a ``_TrainSession`` (one of them with a checkpoint) —
    run twice on the session's loop thread: with no profiler session, then
    inside one.  Gives the spans each left and what the histograms counted."""
    import jax
    import jax.numpy as jnp
    from conftest import ensure_shared_runtime

    ray_tpu = ensure_shared_runtime()
    import ray_tpu.data
    from ray_tpu.data._metrics import data_metrics
    from ray_tpu.train._checkpoint import Checkpoint
    from ray_tpu.train._metrics import train_metrics
    from ray_tpu.train._session import TrainContext, _TrainSession

    tmp = tmp_path_factory.mktemp("spans")
    (tmp / "ckpt").mkdir()
    (tmp / "ckpt" / "state.txt").write_text("x")
    trainer = _toy_trainer("gpt2")
    (shard,) = ray_tpu.data.from_numpy(
        list(_toy_ids(64).reshape(8, 8, 64)),
        column="input_ids").streaming_split(1)
    batches = shard.iter_jax_batches(batch_size=4)      # one epoch for both
    experiment = "spans-toy"
    counts = {}

    def observations():
        return (train_metrics()["report_wait"]._counts.get(
                    (("experiment", experiment),), [0])[:],
                data_metrics()["iter_wait"]._counts.get((), [0])[:])

    def three_steps(session):
        for i in range(STEPS):
            ids = next(batches)["input_ids"]
            loss = trainer.step({"input_ids": ids,
                                 "targets": jnp.roll(ids, -1, axis=1)})
            session.report(
                {"loss": float(loss)},
                Checkpoint.from_directory(str(tmp / "ckpt"))
                if i == 1 else None)

    def loop():
        session = holder[0]
        three_steps(session)            # warm: compiles, and no session
        jax.profiler.start_trace(str(tmp / "off"))
        jax.profiler.stop_trace()       # what a session started now finds
        counts["before"] = observations()
        jax.profiler.start_trace(str(tmp / "on"))
        three_steps(session)
        jax.profiler.stop_trace()
        counts["after"] = observations()

    holder = [None]
    holder[0] = session = _TrainSession(
        loop, {}, TrainContext(experiment_name=experiment,
                               trial_dir=str(tmp / "trial")))
    session.start()
    try:
        while True:
            result = session.get_next(timeout=120)
            assert result is not None, "the toy loop stalled"
            if result.final:
                assert result.error is None, result.error
                break
    finally:
        ray_tpu.kill(shard._coord)
    return {"on": _program_spans(str(tmp / "on")),
            "off": _program_spans(str(tmp / "off")), "counts": counts}


def test_a_profiler_session_finds_every_span_of_the_loop(toy_loop):
    (spans,) = toy_loop["on"]           # one thread: the loop's
    assert {name for name, _, _ in spans} == HOST_SPANS
    count = lambda name: sum(1 for n, _, _ in spans if n == name)  # noqa: E731
    assert count("ray_tpu/step") == STEPS
    assert count("ray_tpu/step/dispatch") == STEPS
    assert count("ray_tpu/train/report") == STEPS
    assert count("ray_tpu/train/report/handoff_wait") == STEPS
    assert count("ray_tpu/train/report/persist") == 1
    assert count("ray_tpu/data/device_put") >= STEPS


def test_child_spans_lie_inside_their_parents(toy_loop):
    (spans,) = toy_loop["on"]
    children = [s for s in spans if s[0].rsplit("/", 1)[0] in HOST_SPANS]
    assert {n for n, _, _ in children} == {
        "ray_tpu/train/report/heartbeat", "ray_tpu/train/report/persist",
        "ray_tpu/train/report/handoff_wait", "ray_tpu/step/shard_batch",
        "ray_tpu/step/dispatch"}
    for name, start, end in children:
        parent = name.rsplit("/", 1)[0]
        assert any(n == parent and a <= start and end <= b
                   for n, a, b in spans), name
    # the data spans wrap the work between two yields, never a step
    steps = [(a, b) for n, a, b in spans if n == "ray_tpu/step"]
    for name, start, end in spans:
        if name.startswith("ray_tpu/data/"):
            assert all(end <= a or start >= b for a, b in steps), name


def test_without_a_session_the_loop_records_nothing(toy_loop):
    assert toy_loop["off"] == []


def test_profiler_span_is_a_shared_no_op_where_jax_is_not_loaded():
    done = subprocess.run([sys.executable, "-c", """
import sys
from ray_tpu.util.tracing import profiler_span
assert "jax" not in sys.modules
span = profiler_span("train/report")
with span:
    pass
assert profiler_span("data/rebatch") is span
assert "jax" not in sys.modules
import jax.profiler
assert isinstance(profiler_span("step"), jax.profiler.TraceAnnotation)
"""], capture_output=True, text=True, timeout=120)
    assert done.returncode == 0, done.stderr


@pytest.mark.parametrize("histogram", ["report_wait", "iter_wait"])
def test_histograms_move_once_per_report_and_per_batch(toy_loop, histogram):
    which = 0 if histogram == "report_wait" else 1
    before = sum(toy_loop["counts"]["before"][which])
    after = sum(toy_loop["counts"]["after"][which])
    assert after - before == STEPS


# ------------------------------------------------- inside the jitted step
# (``kv_repeat`` is K and V copied to the query heads: what "reference" and
# "ring" take, and what the flash kernels read through ``h // rep`` instead)
SCOPES = {"gpt2": ("optimizer", "lm_loss", "flash_bwd", "flash_fwd"),
          "llama": ("optimizer", "lm_loss", "flash_bwd", "flash_fwd", "rope"),
          "llama-reference": ("optimizer", "lm_loss", "kv_repeat", "rope")}


def _instructions(hlo_text):
    """The instructions of a compiled module, metadata aside."""
    return [re.sub(r", metadata=\{[^}]*\}", "", line)
            for line in hlo_text.splitlines()
            if " = " in line and "(" in line and not line.startswith(
                ("FileNames", "FunctionNames", "FileLocations",
                 "StackFrames"))]


@pytest.fixture(scope="module", params=list(SCOPES))
def toy_step(request):
    """(family, the compiled step's text, the same with ``jax.named_scope``
    a null context: a switch of this test's, the program has none)."""
    import jax

    def compiled_text():
        ids = _toy_ids(2)
        return _toy_trainer(request.param).lower(
            {"input_ids": ids, "targets": np.roll(ids, -1, 1)}
        ).compile().as_text()

    scoped = compiled_text()
    with pytest.MonkeyPatch.context() as patch:
        patch.setattr(jax, "named_scope",
                      lambda name: contextlib.nullcontext())
        bare = compiled_text()
    return request.param, scoped, bare


def test_the_step_carries_its_scopes_in_op_name(toy_step):
    family, scoped, bare = toy_step
    op_names = set(re.findall(r'op_name="([^"]+)"', scoped))
    for scope in SCOPES[family]:
        assert any(re.search(rf"[/(]{scope}[/)]", n) for n in op_names), scope
    # where each stands: the optimizer outside the model, the backward rule's
    # operations under the transpose, the kernel in attn — and GQA's copies
    # there too, under "reference" alone
    assert any(n.startswith("jit(pretrain_step)/optimizer/")
               for n in op_names)
    flash = "flash_fwd" in SCOPES[family]
    assert any(re.search(r"transpose\(.*/h_0/attn/flash_bwd/", n)
               for n in op_names) == flash
    assert any(re.search(r"/h_0/attn/flash_fwd/", n)
               for n in op_names) == flash
    assert any("/h_0/attn/kv_repeat/" in n for n in op_names) == (
        family == "llama-reference")
    bare_names = set(re.findall(r'op_name="([^"]+)"', bare))
    # (the backward's kernel keeps its own name, which is the scope's: it is
    # the scope, a path component of its own above the kernel's, that must be
    # gone)
    assert any("/flash_bwd/flash_bwd/" in n for n in op_names) == flash
    assert not any("optimizer" in n or "flash_bwd/flash_bwd" in n
                   or "kv_repeat" in n for n in bare_names)


def test_the_loss_rules_carry_lm_loss_forward_and_backward(toy_step):
    """``lm_loss``'s cross entropy is a ``custom_vjp``: its backward rule is
    traced outside the scope its forward ran in and opens the scope itself,
    or its pass over the logits would read as unscoped device time."""
    _, scoped, bare = toy_step
    op_names = set(re.findall(r'op_name="([^"]+)"', scoped))
    forward = {n for n in op_names if re.search(r"/jvp\(lm_loss\)/", n)
               and "transpose(" not in n}
    backward = {n for n in op_names
                if re.search(r"/transpose\(jvp\(lm_loss\)\)/", n)}
    # the two reductions of the forward, the softmax of the backward
    assert any(n.endswith("/reduce_max") for n in forward), forward
    assert any(n.endswith("/exp") for n in forward), forward
    assert any(n.endswith("/exp") for n in backward), backward
    # and no exponential of the step outside a module lacks the scope
    assert not [n for n in op_names if n.endswith("/exp")
                and not re.search(r"/(h_\d+)/|[/(]lm_loss[/)]", n)]
    assert not any("lm_loss" in n
                   for n in re.findall(r'op_name="([^"]+)"', bare))


def test_scopes_change_metadata_and_nothing_else(toy_step):
    _, scoped, bare = toy_step
    assert scoped != bare
    assert _instructions(scoped) == _instructions(bare)
    assert len(_instructions(scoped)) > 100

"""ray_tpu.llm: paged KV cache, continuous-batching scheduler, inference
engine, and the serve streaming integration (reference test strategy:
vLLM's block-manager/scheduler unit tests + serve streaming e2e)."""

import threading
import time

import numpy as np
import pytest

import ray_tpu
from ray_tpu.llm.kv_cache import CacheConfig, CacheExhausted, PagedKVCache
from ray_tpu.llm.scheduler import (
    FAILED,
    Request,
    SamplingParams,
    Scheduler,
)


def _cache(num_pages=8, page_size=4, layers=2, heads=4, dim=16,
           backend="numpy"):
    return PagedKVCache(CacheConfig(
        num_layers=layers, num_heads=heads, head_dim=dim,
        num_pages=num_pages, page_size=page_size, backend=backend))


def _tiny_config(**over):
    from ray_tpu.models.gpt2 import GPT2Config

    base = dict(vocab_size=512, n_positions=64, n_embd=64, n_layer=2,
                n_head=4)
    base.update(over)
    return GPT2Config(**base)


# ================================================================ cache

def test_cache_alloc_free_leak_accounting():
    c = _cache(num_pages=8, page_size=4)
    c.reserve("a", 6)          # 2 pages
    c.reserve("b", 9)          # 3 pages
    assert c.used_pages == 5 and c.free_pages == 3
    assert c.utilization() == pytest.approx(5 / 8)
    c.check_leaks()
    # growing within the last page allocates nothing
    c.reserve("a", 8)
    assert c.used_pages == 5
    # growing past it allocates one more
    c.reserve("a", 9)
    assert c.used_pages == 6
    assert c.free("a") == 3
    assert c.free("b") == 3
    assert c.free("b") == 0    # double free is a no-op
    assert c.free_pages == 8
    c.check_leaks()
    assert c.peak_pages_used == 6


def test_cache_exhaustion_is_all_or_nothing():
    c = _cache(num_pages=4, page_size=4)
    c.reserve("a", 8)          # 2 pages
    with pytest.raises(CacheExhausted):
        c.reserve("b", 12)     # needs 3, only 2 free
    # the failed reservation must not leak a partial allocation
    assert c.used_pages == 2
    c.check_leaks()
    c.reserve("b", 8)          # 2 pages fits
    assert c.free_pages == 0


def test_cache_write_gather_roundtrip_across_pages():
    c = _cache(num_pages=6, page_size=4, layers=2, heads=2, dim=3)
    T = 10  # spans 3 pages
    k = np.arange(T * 2 * 3, dtype=np.float32).reshape(T, 2, 3)
    v = -k
    c.reserve("s", T)
    for layer in (0, 1):
        c.write("s", layer, 0, k * (layer + 1), v * (layer + 1))
    c.commit("s", T)
    for layer in (0, 1):
        K, V = c.gather_kv("s", layer)
        np.testing.assert_array_equal(K, k * (layer + 1))
        np.testing.assert_array_equal(V, v * (layer + 1))
    # partial gather + incremental append at an unaligned offset
    c.reserve("s", T + 1)
    c.write("s", 0, T, k[:1], v[:1])
    c.commit("s", T + 1)
    assert c.gather("s", 0).shape == (T + 1, 2, 3)
    np.testing.assert_array_equal(c.gather("s", 0, 4), k[:4])


def test_cache_jax_backend_roundtrip():
    jax = pytest.importorskip("jax")  # noqa: F841
    c = _cache(num_pages=4, page_size=2, layers=1, heads=2, dim=2,
               backend="jax")
    k = np.random.default_rng(0).normal(size=(5, 2, 2)).astype(np.float32)
    c.reserve("s", 5)
    c.write("s", 0, 0, k, k + 1)
    c.commit("s", 5)
    K, V = c.gather_kv("s", 0)
    np.testing.assert_allclose(K, k)
    np.testing.assert_allclose(V, k + 1)
    c.free("s")
    c.check_leaks()


# =============================================================== runner

def test_runner_prefill_decode_consistency():
    """Prefill(full prompt) and prefill(prefix)+decode(token by token) must
    produce the same last-position logits — the cache-correctness
    invariant recompute-on-resume relies on."""
    from ray_tpu.llm.model_runner import GPT2Runner

    cfg = _tiny_config()
    runner = GPT2Runner.init_random(cfg, seed=3)
    ids = [7, 300, 12, 9, 44, 501, 2, 17]

    c1 = _cache(num_pages=8, page_size=4)
    c1.reserve("full", len(ids))
    ref = runner.prefill("full", ids, 0, c1)

    c2 = _cache(num_pages=8, page_size=4)
    c2.reserve("inc", 3)
    runner.prefill("inc", ids[:3], 0, c2)
    for i in range(3, len(ids)):
        c2.reserve("inc", i + 1)
        logits = runner.decode([("inc", ids[i], i)], c2)
    np.testing.assert_allclose(logits[0], ref, rtol=1e-4, atol=1e-4)


def test_runner_matches_flax_model():
    """The numpy serving forward reproduces `models/gpt2.GPT2LMModel` —
    the engine really serves the training stack's model."""
    jax = pytest.importorskip("jax")
    import jax.numpy as jnp

    from ray_tpu.llm.model_runner import GPT2Runner
    from ray_tpu.models.gpt2 import GPT2LMModel

    cfg = _tiny_config(dtype=jnp.float32, attention_impl="reference",
                       remat=False)
    runner = GPT2Runner.from_flax(cfg, seed=0)
    model = GPT2LMModel(cfg)
    variables = model.init(jax.random.PRNGKey(0),
                           jnp.zeros((1, 2), jnp.int32), deterministic=True)
    ids = np.array([3, 7, 11, 200, 401, 5, 9, 12])
    ref = np.asarray(model.apply(variables, ids[None],
                                 deterministic=True))[0]
    cache = _cache(num_pages=8, page_size=4)
    cache.reserve("s", len(ids))
    mine = runner.prefill("s", ids, 0, cache, return_all=True)
    np.testing.assert_allclose(mine, ref, rtol=1e-3, atol=1e-4)


# ============================================================ scheduler

def _apply_plan(plan):
    """Simulate the engine's side of the contract: a planned prefill
    advances num_computed; a completed prefill or decode samples one
    token."""
    for r, toks, start in plan.prefills:
        r.num_computed = start + len(toks)
        if r.num_computed == r.total_len:
            r.outputs.append(9)
    for r in plan.decodes:
        r.num_computed += 1
        r.outputs.append(9)


def test_scheduler_fcfs_admission_and_token_budget():
    cache = _cache(num_pages=64, page_size=4)
    sched = Scheduler(cache, max_batch_tokens=10)
    a = Request("a", [1] * 6, SamplingParams())
    b = Request("b", [1] * 6, SamplingParams())
    c = Request("c", [1] * 4, SamplingParams())
    for r in (a, b, c):
        sched.add(r)
    plan = sched.plan()
    # a fits (6 <= 10); b would exceed the leftover budget (4) and, being
    # head of line, blocks c (strict FCFS — no skipping)
    assert [r.rid for r, _, _ in plan.prefills] == ["a"]
    _apply_plan(plan)
    plan = sched.plan()
    # next step: a decodes (1 token), b prefills into the remaining budget
    assert [r.rid for r in plan.decodes] == ["a"]
    assert [r.rid for r, _, _ in plan.prefills] == ["b"]
    _apply_plan(plan)
    plan = sched.plan()
    assert [r.rid for r in plan.decodes] == ["a", "b"]
    assert [r.rid for r, _, _ in plan.prefills] == ["c"]


def test_scheduler_preempts_newest_with_recompute_state():
    cache = _cache(num_pages=4, page_size=2)  # 8 token slots
    sched = Scheduler(cache, max_batch_tokens=64)
    a = Request("a", [1, 2, 3], SamplingParams(max_tokens=8))
    b = Request("b", [4, 5, 6], SamplingParams(max_tokens=8))
    sched.add(a)
    sched.add(b)
    plan = sched.plan()
    assert len(plan.prefills) == 2
    # simulate the engine: prefill committed 3 tokens each + 1 sampled
    for r in (a, b):
        r.num_computed = 3
        r.outputs.append(9)
    # a:4 tokens (2 pages), b:4 tokens (2 pages) -> 0 free; next decode for
    # a needs... total_len 4 fits its 2 pages; grow until a needs a 3rd page
    for _ in range(4):
        plan = sched.plan()
        for r in plan.decodes:
            r.num_computed += 1
            r.outputs.append(9)
        if plan.preempted:
            break
    assert plan.preempted and plan.preempted[0] is b, \
        "newest-arrival running request must be the victim"
    assert b.state == "WAITING" and b.num_computed == 0
    assert b.outputs, "preemption must keep generated tokens for recompute"
    assert not cache.has_seq("b")
    cache.check_leaks()
    # a alone: keeps decoding; b re-admits once a finishes
    sched.finish(a, "length")
    plan = sched.plan()
    assert [r.rid for r, toks, start in plan.prefills] == ["b"]
    _, toks, start = plan.prefills[0]
    assert start == 0 and toks == b.prompt + b.outputs


def test_scheduler_fails_request_that_can_never_fit():
    cache = _cache(num_pages=2, page_size=2)  # 4 slots
    sched = Scheduler(cache, max_batch_tokens=64)
    r = Request("big", [1] * 6, SamplingParams(max_tokens=4))
    sched.add(r)
    plan = sched.plan()
    assert plan.failed == [r] and r.state == FAILED
    assert "pages" in (r.error or "") or "fit" in (r.error or "")
    cache.check_leaks()


# ========================================================== engine core

def _core(**kw):
    from ray_tpu.llm.engine import EngineCore

    kw.setdefault("engine_name", f"test-{kw.get('seed', 0)}")
    return EngineCore(**kw)


def test_engine_greedy_deterministic_and_stats():
    core = _core(num_pages=32, page_size=8, seed=0)
    out1 = core.generate([1, 2, 3, 4], SamplingParams(max_tokens=8))
    out2 = core.generate([1, 2, 3, 4], SamplingParams(max_tokens=8))
    assert out1["tokens"] == out2["tokens"]
    assert len(out1["tokens"]) == 8
    assert out1["finish_reason"] == "length"
    st = core.stats()
    assert st["total_generated"] == 16
    core.cache.check_leaks()


def test_engine_preempt_resume_identical_tokens():
    """Page-exhaustion preemption + recompute-on-resume must not change a
    single token vs an unpreempted run (greedy, same weights)."""
    ample = _core(num_pages=64, page_size=8, seed=1)
    expected = [ample.generate([5, 6, 7], SamplingParams(max_tokens=6))
                ["tokens"]]

    tight = _core(num_pages=4, page_size=2, seed=1)  # 8 token slots
    rids = [tight.submit([5, 6, 7], SamplingParams(max_tokens=6))
            for _ in range(3)]
    tight.run_until_done(rids)
    assert tight.stats()["preemptions"] >= 1, \
        "test must actually exercise preemption"
    for rid in rids:
        res = tight.result(rid)
        assert res["tokens"] == expected[0], res
    tight.cache.check_leaks()


def test_engine_mid_decode_join():
    """A request admitted while another decodes joins the running batch at
    the next iteration (continuous batching), and co-batched decoding
    produces the same tokens as a solo run."""
    solo = _core(num_pages=64, page_size=8, seed=2)
    want_a = solo.generate([10, 11, 12], SamplingParams(max_tokens=10))
    want_b = solo.generate([20, 21], SamplingParams(max_tokens=6))

    core = _core(num_pages=64, page_size=8, seed=2)
    ra = core.submit([10, 11, 12], SamplingParams(max_tokens=10))
    for _ in range(3):
        core.step()
    assert core.scheduler.num_running == 1
    rb = core.submit([20, 21], SamplingParams(max_tokens=6))
    core.run_until_done([ra, rb])
    assert core.max_decode_batch >= 2, "b never joined the running batch"
    assert core.result(ra)["tokens"] == want_a["tokens"]
    assert core.result(rb)["tokens"] == want_b["tokens"]


def test_engine_sampling_seeded_and_top_k():
    core = _core(num_pages=32, page_size=8, seed=3)
    p = SamplingParams(max_tokens=6, temperature=0.8, seed=42)
    t1 = core.generate([1, 2], p)["tokens"]
    t2 = core.generate([1, 2], p)["tokens"]
    assert t1 == t2, "seeded sampling must be reproducible"
    t3 = core.generate([1, 2], SamplingParams(max_tokens=6, temperature=0.8,
                                              seed=43))["tokens"]
    assert t1 != t3  # overwhelmingly likely with 512-way logits

    # top_k=1 at any temperature is greedy
    greedy = core.generate([1, 2], SamplingParams(max_tokens=6))["tokens"]
    k1 = core.generate([1, 2], SamplingParams(max_tokens=6, temperature=2.0,
                                              top_k=1, seed=7))["tokens"]
    assert k1 == greedy


def test_engine_adapter_logit_bias():
    core = _core(num_pages=32, page_size=8, seed=4)
    base = core.generate([1, 2, 3], SamplingParams(max_tokens=4))["tokens"]
    a1 = core.generate([1, 2, 3], SamplingParams(max_tokens=4,
                                                 adapter="a1"))["tokens"]
    a1_again = core.generate([1, 2, 3],
                             SamplingParams(max_tokens=4,
                                            adapter="a1"))["tokens"]
    a2 = core.generate([1, 2, 3], SamplingParams(max_tokens=4,
                                                 adapter="a2"))["tokens"]
    assert a1 == a1_again, "adapter bias must be deterministic per id"
    assert a1 != base and a1 != a2
    assert core.loaded_adapters() == ["a1", "a2"]


def test_engine_infeasible_and_invalid_requests():
    core = _core(num_pages=2, page_size=2, seed=5)  # 4 token slots
    rid = core.submit([1] * 7, SamplingParams(max_tokens=4))
    core.run_until_done([rid])
    res = core.result(rid)
    assert res["state"] == FAILED and res["error"]
    with pytest.raises(ValueError):
        core.submit([], SamplingParams())
    with pytest.raises(ValueError):
        core.submit([9999], SamplingParams())  # out of vocab
    core.cache.check_leaks()


def test_engine_abort_releases_pages():
    core = _core(num_pages=32, page_size=8, seed=6)
    rid = core.submit([1, 2, 3], SamplingParams(max_tokens=1000))
    for _ in range(3):
        core.step()
    assert core.abort(rid)
    core.step()  # reap
    assert core.result(rid)["state"] == "ABORTED"
    core.cache.check_leaks()
    assert core.cache.used_pages == 0
    assert not core.abort(rid)  # terminal: no-op


# ========================================================= metrics fold

def test_summarize_llm_view_fold():
    """Engine metrics land in the process registry and fold back through
    the exposition-text parser into the per-engine view (the /api/llm and
    `ray_tpu summary llm` read path)."""
    from ray_tpu._private import metrics_view as mv
    from ray_tpu._private.metrics import default_registry

    core = _core(num_pages=32, page_size=8, seed=7,
                 engine_name="fold-unit")
    core.generate([1, 2, 3], SamplingParams(max_tokens=5))
    samples = mv.parse_prometheus(default_registry.prometheus_text())
    view = mv.summarize_llm(samples)
    d = view["fold-unit"]
    assert d["requests"] == 1
    assert d["generated_tokens"] == 5
    assert d["prompt_tokens"] == 3
    assert d["ttft_p50_s"] > 0
    assert d["itl_p50_s"] > 0
    assert d["tokens_per_second"] > 0
    assert d["decode_batch_mean"] >= 1
    # history point carries the compact llm series
    point = mv.history_point(samples)
    assert point["llm"]["fold-unit"]["tokens"] == 5


# ============================================================ actor api

@pytest.fixture
def cluster():
    from conftest import ensure_shared_runtime

    yield ensure_shared_runtime()


@pytest.fixture
def serve_instance():
    from conftest import ensure_shared_runtime

    rt = ensure_shared_runtime()
    yield rt
    from ray_tpu import serve

    serve.shutdown()


def test_engine_actor_stream_dynamic_and_incremental(cluster):
    from ray_tpu.llm.engine import InferenceEngine

    # per-step floor: without it the tiny model can finish a whole request
    # inside one long-poll round trip on a loaded box, making the
    # incrementality assertion below timing-dependent
    eng = InferenceEngine.options(num_cpus=0).remote(
        engine_name="actor-test", num_pages=32, page_size=8,
        step_delay_s=0.05)
    try:
        full = ray_tpu.get(
            eng.generate.remote([1, 2, 3], {"max_tokens": 6}), timeout=60)
        assert len(full["tokens"]) == 6

        # dynamic-generator machinery: one ref per token
        gen = eng.stream.options(num_returns="dynamic").remote(
            [1, 2, 3], {"max_tokens": 6})
        toks = [ray_tpu.get(r, timeout=30) for r in gen]
        assert toks == full["tokens"], \
            "streamed token order must match the buffered result"

        # incremental long-poll path: tokens arrive before completion
        rid = ray_tpu.get(
            eng.submit.remote([4, 5], {"max_tokens": 8}), timeout=30)
        seen = []
        cursor = 0
        polls = 0
        while True:
            out = ray_tpu.get(
                eng.next_output.remote(rid, cursor, 10.0), timeout=40)
            seen.extend(out["tokens"])
            cursor += len(out["tokens"])
            polls += 1
            if out["finished"]:
                break
        assert len(seen) == 8 and polls >= 2, \
            "next_output should deliver incrementally, not one batch"
        res = ray_tpu.get(eng.result.remote(rid), timeout=30)
        assert res["tokens"] == seen
    finally:
        ray_tpu.kill(eng)


# ====================================================== serve streaming

def test_llm_serve_streaming_e2e(serve_instance):
    """Acceptance: >=8 concurrent streaming requests through a
    serve-deployed tiny-model engine — continuous batching observed
    (decode batch > 1), preemption exercised with identical outputs vs an
    unpreempted run, and summarize_llm reports non-zero TTFT / tokens/s."""
    from ray_tpu import serve
    from ray_tpu.llm import EngineCore, llm_deployment
    from ray_tpu.util import state

    # One request ends at 5 + 32 = 37 tokens = 10 pages of 4 slots and the
    # pool has 12: any two requests that overlap for a few steps cannot both
    # finish, so preemption MUST trigger; it does not take three or four
    # arrivals to land together.  The per-step floor makes a request live
    # for ~1.6 s (the tiny model would otherwise finish each one before
    # the next arrives), and all eight are submitted before any stream is
    # awaited.
    engine_kwargs = dict(num_pages=12, page_size=4, max_batch_tokens=128,
                         seed=0, engine_name="serve-e2e",
                         step_delay_s=0.05)
    app = llm_deployment(engine_kwargs=engine_kwargs)
    h = serve.run(app, name="llmapp", route_prefix="/llm")
    try:
        prompts = [[i + 1, i + 2, i + 3, i + 4, i + 5] for i in range(8)]
        max_tokens = 32

        # expected outputs: same weights (seed=0), ample cache, no serving
        ample = EngineCore(seed=0, num_pages=256, page_size=8,
                           engine_name="e2e-reference")
        expected = [ample.generate(p, {"max_tokens": max_tokens})["tokens"]
                    for p in prompts]

        responses = [h.remote({"prompt_ids": p, "max_tokens": max_tokens,
                               "stream": True}) for p in prompts]
        # (how long the replica takes to hand the streams over is the host's:
        # what is asserted is that it does, and what they hold)
        t0 = time.monotonic()
        streams = [r.result(240) for r in responses]
        print(f"eight streams handed over after {time.monotonic() - t0:.1f} s")
        results = [None] * len(streams)
        errors = []

        def consume(i, s):
            try:
                events = list(s)
                assert events[-1].get("done") is True
                results[i] = [e["token"] for e in events[:-1]]
            except Exception as e:  # surfaces in the main thread
                errors.append((i, repr(e)))

        threads = [threading.Thread(target=consume, args=(i, s))
                   for i, s in enumerate(streams)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(120)
        assert not errors, errors
        assert results == expected, \
            "streamed tokens must match the unpreempted reference run"

        stats = h.options(method_name="engine_stats").remote().result(30)
        assert stats["max_decode_batch"] > 1, \
            f"continuous batching never overlapped requests: {stats}"
        assert stats["preemptions"] >= 1, \
            f"preemption was not exercised: {stats}"
        assert stats["kv_pages_free"] == stats["kv_pages_total"], \
            "engine leaked cache pages after the run"

        # metrics reach the cluster view (engine worker -> nodelet push)
        deadline = time.monotonic() + 45
        view = {}
        while time.monotonic() < deadline:
            view = state.summarize_llm().get("serve-e2e", {})
            if view.get("requests", 0) >= 8 and \
                    view.get("tokens_per_second", 0) > 0 and \
                    view.get("generated_tokens", 0) >= 8 * max_tokens:
                break  # the counters arrive push by push, not together
            time.sleep(0.5)
        assert view.get("requests", 0) >= 8, view
        assert view.get("ttft_p50_s", 0) > 0, view
        assert view.get("tokens_per_second", 0) > 0, view
        assert view.get("generated_tokens", 0) >= 8 * max_tokens, view
    finally:
        serve.delete("llmapp")


def test_llm_multiplexed_adapter_routing(serve_instance):
    """Adapter selection rides the multiplex machinery: the model id set by
    handle.options(multiplexed_model_id=...) reaches the engine as a logit
    bias, deterministically, and registers on the replica's loaded set."""
    from ray_tpu import serve
    from ray_tpu.llm import llm_deployment

    app = llm_deployment(engine_kwargs=dict(
        num_pages=32, page_size=8, seed=0, engine_name="mux-llm"))
    h = serve.run(app, name="llmmux", route_prefix="/llmmux")
    try:
        body = {"prompt_ids": [1, 2, 3], "max_tokens": 4, "stream": False}
        base = h.remote(dict(body)).result(60)
        a1 = h.options(multiplexed_model_id="ad1").remote(
            dict(body)).result(60)
        a1_again = h.options(multiplexed_model_id="ad1").remote(
            dict(body)).result(60)
        a2 = h.options(multiplexed_model_id="ad2").remote(
            dict(body)).result(60)
        assert a1["tokens"] == a1_again["tokens"]
        assert a1["tokens"] != base["tokens"]
        assert a1["tokens"] != a2["tokens"]
        stats = h.options(method_name="engine_stats").remote().result(30)
        assert set(stats["adapters"]) >= {"ad1", "ad2"}
    finally:
        serve.delete("llmmux")


@pytest.mark.slow
def test_llm_http_sse_stream(serve_instance):
    """Token stream over HTTP: SSE events arrive incrementally, terminated
    by the final done event and [DONE]."""
    import http.client
    import json

    from ray_tpu import serve
    from ray_tpu.llm import llm_deployment

    app = llm_deployment(engine_kwargs=dict(
        num_pages=32, page_size=8, seed=0, engine_name="http-llm"))
    serve.run(app, name="llmhttp", route_prefix="/llmhttp")
    try:
        port = serve.start(http_port=0)
        conn = http.client.HTTPConnection("127.0.0.1", port, timeout=60)
        conn.request("POST", "/llmhttp",
                     body=json.dumps({"prompt_ids": [1, 2, 3],
                                      "max_tokens": 8}),
                     headers={"Content-Type": "application/json"})
        resp = conn.getresponse()
        assert resp.status == 200
        assert resp.getheader("Content-Type") == "text/event-stream"
        events = []
        while True:
            line = resp.fp.readline()
            if not line:
                break
            line = line.strip()
            if not line.startswith(b"data:"):
                continue
            if line == b"data: [DONE]":
                events.append("DONE")
                break
            events.append(json.loads(line[len(b"data:"):]))
        conn.close()
        assert events[-1] == "DONE"
        assert events[-2].get("done") is True
        tokens = [e["token"] for e in events[:-2]]
        assert len(tokens) == 8
    finally:
        serve.delete("llmhttp")

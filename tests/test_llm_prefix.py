"""Serving fast path (PR 13): radix prefix caching, chunked prefill,
speculative-decode hooks, and admission control / load shedding
(reference test strategy: SGLang's radix-cache correctness suite + vLLM's
prefix-caching block tests; admission per Orca-style bounded queues)."""

import asyncio
import json
import threading
import time

import numpy as np
import pytest

import ray_tpu
from ray_tpu.exceptions import RequestShed
from ray_tpu.llm.admission import AdmissionController
from ray_tpu.llm.kv_cache import CacheConfig, PagedKVCache


def _pcache(num_pages=8, page_size=4, layers=1, heads=1, dim=2):
    return PagedKVCache(CacheConfig(
        num_layers=layers, num_heads=heads, head_dim=dim,
        num_pages=num_pages, page_size=page_size, backend="numpy",
        enable_prefix_cache=True))


def _core(enable=False, chunk=0, **over):
    from ray_tpu.llm import EngineCore

    kw = dict(seed=0, num_pages=128, page_size=4, max_batch_tokens=64,
              engine_name=f"prefix-{enable}-{chunk}",
              enable_prefix_cache=enable, prefill_chunk_tokens=chunk)
    kw.update(over)
    return EngineCore(**kw)


# ====================================================== cache-level trie

def test_trie_match_fork_refcounts_and_leak_balance():
    c = _pcache(num_pages=8, page_size=4)
    tokens = list(range(1, 13))  # 3 full pages
    c.reserve("a", 12)
    k = np.arange(12 * 1 * 2, dtype=np.float32).reshape(12, 1, 2)
    c.write("a", 0, 0, k, -k)
    c.commit("a", 12)
    assert c.insert_prefix("a", tokens) == 3
    assert c.trie_pages == 3
    c.check_leaks()

    # a second sequence with a 2-page overlap adopts exactly those pages
    other = tokens[:8] + [99, 98, 97, 96]
    adopted = c.fork_from_prefix("b", other)
    assert adopted == 8
    assert c.pages_of("b") == c.pages_of("a")[:2]
    assert c.prefix_hit_tokens == 8
    c.check_leaks()
    # shared pages are read-only for everyone
    with pytest.raises(AssertionError):
        c.write("b", 0, 4, k[:1], k[:1])
    c.reserve("b", 12)
    c.write("b", 0, 8, k[:4], -k[:4])
    c.free("b")
    c.free("a")
    # trie keeps the cached pages alive after both sequences retire
    assert c.trie_pages == 3
    c.check_leaks()


def test_boundary_page_cow_fork_does_not_corrupt_sibling():
    c = _pcache(num_pages=8, page_size=4)
    tokens = list(range(1, 9))  # 2 full pages
    c.reserve("a", 8)
    k = np.arange(8 * 1 * 2, dtype=np.float32).reshape(8, 1, 2)
    c.write("a", 0, 0, k, -k)
    c.commit("a", 8)
    c.insert_prefix("a", tokens)

    # identical prompt: match is capped at len-1 = 7 -> mid-page boundary
    # -> the second page must be CoW-forked, not shared
    adopted = c.fork_from_prefix("b", tokens)
    assert adopted == 7
    a_pages, b_pages = c.pages_of("a"), c.pages_of("b")
    assert b_pages[0] == a_pages[0] and b_pages[1] != a_pages[1]
    before = c.gather("a", 0, 8).copy()
    # b recomputes position 7 into its private boundary page
    new = np.full((1, 1, 2), 555.0, np.float32)
    c.write("b", 0, 7, new, new)
    c.commit("b", 8)
    assert np.array_equal(c.gather("a", 0, 8), before), \
        "CoW fork leaked a write into the sibling's page"
    got = c.gather("b", 0, 8)
    assert np.array_equal(got[:7], before[:7])
    assert np.array_equal(got[7], new[0])
    c.check_leaks()
    c.free("a")
    c.free("b")
    c.check_leaks()


def test_eviction_under_pressure_then_reuse():
    c = _pcache(num_pages=4, page_size=4)
    tokens = list(range(1, 17))  # exactly the whole pool
    c.reserve("a", 16)
    k = np.zeros((16, 1, 2), np.float32)
    c.write("a", 0, 0, k, k)
    c.commit("a", 16)
    c.insert_prefix("a", tokens)
    c.free("a")
    assert c.free_pages == 0 and c.trie_pages == 4
    c.check_leaks()

    # reuse: same prompt adopts the cached pages (capped at 15 -> the
    # partial boundary page is dropped back to the 12-token alignment
    # because no page is free to fork into)
    adopted = c.fork_from_prefix("b", tokens)
    assert adopted == 12
    # pressure: growing to the full prompt must evict the one trie page
    # nothing else holds, never fail
    assert c.can_reserve("b", 16)
    c.reserve("b", 16)
    assert c.trie_pages == 3
    c.check_leaks()
    c.free("b")
    c.check_leaks()
    # eviction never touches pages a live sequence shares
    c2 = _pcache(num_pages=2, page_size=4)
    c2.reserve("x", 8)
    c2.write("x", 0, 0, k[:8], k[:8])
    c2.commit("x", 8)
    c2.insert_prefix("x", tokens[:8])
    with pytest.raises(Exception):
        c2.reserve("y", 4)  # both pages shared with live "x": no eviction
    c2.check_leaks()


# ================================================ engine-level identity

def test_prefix_cache_bit_identical_outputs():
    """Overlapping, disjoint, and nested prompts produce bit-identical
    token streams with the prefix cache on vs off (greedy and sampled)."""
    base = [7 + (i % 30) for i in range(20)]
    prompts = [
        base + [101, 102],             # populates the trie
        base + [201, 202, 203],        # overlapping prefix
        [400 + i for i in range(16)],  # disjoint
        base[:8],                      # nested: shorter than cached
        base,                          # exact cached prefix (cap at len-1)
        base + [101, 102],             # full repeat
    ]
    for params in ({"max_tokens": 8},
                   {"max_tokens": 8, "temperature": 0.8, "seed": 11}):
        off = _core(enable=False)
        on = _core(enable=True)
        out_off = [off.generate(p, dict(params))["tokens"] for p in prompts]
        out_on = [on.generate(p, dict(params))["tokens"] for p in prompts]
        assert out_on == out_off
        assert on.scheduler.prefix_hit_tokens > 0
        assert on.scheduler.prefilled_tokens < off.scheduler.prefilled_tokens
        on.cache.check_leaks()
        off.cache.check_leaks()


def test_chunked_prefill_deterministic_across_chunk_sizes():
    prompt = [3 + (i % 40) for i in range(40)]
    reference = None
    for chunk in (0, 3, 8, 17, 64):
        core = _core(chunk=chunk, num_pages=64, page_size=8)
        out = core.generate(prompt, {"max_tokens": 10, "temperature": 0.7,
                                     "seed": 5})["tokens"]
        if reference is None:
            reference = out
        assert out == reference, f"chunk={chunk} diverged"
        core.cache.check_leaks()


def test_chunked_prefill_interleaves_decodes():
    """With chunking on, running decodes advance during a long prompt's
    prefill instead of stalling behind it."""
    core = _core(chunk=8, num_pages=64, page_size=4,
                 max_batch_tokens=16)
    first = core.submit([1, 2, 3], {"max_tokens": 12})
    for _ in range(3):
        core.step()
    produced_before = len(core.result(first)["tokens"])
    long_rid = core.submit([5 + (i % 40) for i in range(40)],
                           {"max_tokens": 2})
    core.step()  # long prompt admits its first chunk only
    core.step()
    produced_after = len(core.result(first)["tokens"])
    assert produced_after > produced_before, \
        "decode stalled behind a chunked prefill"
    core.run_until_done([first, long_rid])
    core.cache.check_leaks()


def test_abort_mid_chunked_prefill_releases_pages():
    """Regression (satellite 1): abort between prefill chunks frees the
    tail pages and drops seq refcounts; trie-cached pages survive and are
    reusable; check_leaks stays clean throughout."""
    prompt = [9 + (i % 25) for i in range(40)]
    core = _core(enable=True, chunk=8, num_pages=32, page_size=8)
    rid = core.submit(prompt, {"max_tokens": 4})
    core.step()  # exactly one 8-token chunk computed + inserted
    assert core.cache.trie_pages >= 1
    assert core.abort(rid)
    for _ in range(3):
        core.step()  # reap
    core.cache.check_leaks()
    assert not core.cache.has_seq(rid)
    cached = core.cache.trie_pages
    assert cached >= 1, "committed chunk pages should stay trie-cached"

    # the survivor pages are adoptable by a retry of the same prompt
    out = core.generate(prompt, {"max_tokens": 4})
    assert core.scheduler.prefix_hit_tokens >= 8
    ref = _core(enable=False, num_pages=32, page_size=8)
    assert out["tokens"] == ref.generate(prompt,
                                         {"max_tokens": 4})["tokens"]
    core.cache.check_leaks()


# ================================================= speculative hooks

def test_spec_decode_hooks_default_noop_and_called():
    """Satellite 2: the runner exposes propose/verify hooks; the default
    is a no-op draft (empty proposals, verify == plain decode), and the
    engine routes every decode step through them."""
    core = _core()
    calls = {"propose": 0, "verify": 0}
    orig_propose = core.runner.propose_tokens
    orig_verify = core.runner.verify_tokens

    def spy_propose(items, cache, max_draft=0):
        calls["propose"] += 1
        drafts = orig_propose(items, cache, max_draft)
        assert drafts == [[] for _ in items]
        return drafts

    def spy_verify(items, drafts, cache):
        calls["verify"] += 1
        return orig_verify(items, drafts, cache)

    core.runner.propose_tokens = spy_propose
    core.runner.verify_tokens = spy_verify
    out = core.generate([1, 2, 3, 4], {"max_tokens": 6})
    assert calls["propose"] >= 5 and calls["verify"] == calls["propose"]
    ref = _core(engine_name="spec-ref")
    assert out["tokens"] == ref.generate([1, 2, 3, 4],
                                         {"max_tokens": 6})["tokens"]


# ==================================================== admission control

def test_admission_two_tenant_fairness():
    """A flooding tenant (40 queued) cannot starve a light one (10
    queued): with equal weights the stride scheduler alternates, so the
    light tenant gets >= 40% of the first 20 dispatches."""
    async def run():
        ac = AdmissionController(max_inflight=4, max_queue=128,
                                 queue_deadline_s=30.0)
        for _ in range(4):
            await ac.admit("flood")
        order = []

        async def park(tenant):
            await ac.admit(tenant)
            order.append(tenant)

        tasks = [asyncio.ensure_future(park("flood")) for _ in range(40)]
        tasks += [asyncio.ensure_future(park("light")) for _ in range(10)]
        await asyncio.sleep(0)
        assert ac.queued == 50
        for _ in range(20):
            ac.release()
            await asyncio.sleep(0)
        first20 = order[:20]
        share = first20.count("light") / 20.0
        assert share >= 0.4, f"light tenant starved: {share:.0%} {first20}"
        for t in tasks:
            t.cancel()
        await asyncio.gather(*tasks, return_exceptions=True)

    asyncio.run(run())


def test_admission_queue_full_and_deadline_shed():
    async def run():
        ac = AdmissionController(max_inflight=1, max_queue=1,
                                 queue_deadline_s=0.3)
        assert await ac.admit() == 0.0
        parked = asyncio.ensure_future(ac.admit())
        await asyncio.sleep(0)
        with pytest.raises(RequestShed) as ei:
            await ac.admit()
        assert ei.value.reason == "queue_full"
        assert ei.value.retry_after_s > 0
        with pytest.raises(RequestShed) as e2:
            await parked  # never released -> deadline shed, not a hang
        assert e2.value.reason == "deadline"
        assert ac.stats()["shed"] == {"queue_full": 1, "deadline": 1}
        assert ac.queued == 0

    asyncio.run(run())


def test_admission_saturated_projected_wait_shed():
    async def run():
        now = [0.0]
        ac = AdmissionController(max_inflight=1, max_queue=10,
                                 queue_deadline_s=1.0,
                                 clock=lambda: now[0])
        await ac.admit("a")
        ac.release()             # seeds the release timestamp
        await ac.admit("a")
        parked = asyncio.ensure_future(ac.admit("a"))
        await asyncio.sleep(0)
        now[0] = 10.0
        ac.release()             # 10s interval -> drain rate 0.1/s
        assert await asyncio.wait_for(parked, 5) >= 0.0
        waiter = asyncio.ensure_future(ac.admit("a"))
        await asyncio.sleep(0)
        # projected wait (2/0.1 = 20s) >> deadline: shed at the door
        with pytest.raises(RequestShed) as ei:
            await ac.admit("a")
        assert ei.value.reason == "saturated"
        waiter.cancel()
        await asyncio.gather(waiter, return_exceptions=True)

    asyncio.run(run())


def test_admission_release_dispatches_in_wait_order():
    async def run():
        ac = AdmissionController(max_inflight=1, max_queue=8,
                                 queue_deadline_s=10.0)
        await ac.admit()
        waits = []

        async def park():
            waits.append(await ac.admit())

        tasks = [asyncio.ensure_future(park()) for _ in range(3)]
        await asyncio.sleep(0.05)
        for _ in range(3):
            ac.release()
            await asyncio.sleep(0)
        await asyncio.wait_for(asyncio.gather(*tasks), 5)
        assert len(waits) == 3
        assert all(w >= 0.0 for w in waits)
        assert ac.inflight == 1 and ac.queued == 0
        await asyncio.gather(*tasks, return_exceptions=True)

    asyncio.run(run())


# ======================================================== serve e2e

@pytest.fixture
def serve_instance():
    from conftest import ensure_shared_runtime

    rt = ensure_shared_runtime()
    yield rt
    from ray_tpu import serve

    serve.shutdown()


def test_serve_shed_429_and_sse_error_never_hang(serve_instance):
    """At saturation the proxy answers shed requests immediately: HTTP
    429 + Retry-After for JSON clients, a terminal SSE error event for
    event-stream clients — while the admitted stream keeps decoding to
    completion."""
    import urllib.error
    import urllib.request

    from ray_tpu import serve
    from ray_tpu.llm import llm_deployment

    app = llm_deployment(
        engine_kwargs=dict(num_pages=64, page_size=4, seed=0,
                           engine_name="shed-e2e", step_delay_s=0.05),
        admission_kwargs=dict(max_inflight=1, max_queue=0,
                              queue_deadline_s=5.0))
    serve.run(app, name="shedapp", route_prefix="/shed")
    port = serve.start(http_port=0)
    url = f"http://127.0.0.1:{port}/shed"
    try:
        got_first = threading.Event()
        stream_tokens = []
        stream_done = threading.Event()
        errors = []

        def consume():
            req = urllib.request.Request(
                url, method="POST",
                data=json.dumps({"prompt_ids": [1, 2, 3],
                                 "max_tokens": 30,
                                 "stream": True}).encode(),
                headers={"Content-Type": "application/json"})
            try:
                with urllib.request.urlopen(req, timeout=120) as resp:
                    for raw in resp:
                        line = raw.strip()
                        if not line.startswith(b"data:"):
                            continue
                        payload = line[5:].strip()
                        if payload == b"[DONE]":
                            stream_done.set()
                            return
                        event = json.loads(payload)
                        if "token" in event:
                            stream_tokens.append(event["token"])
                            got_first.set()
            except Exception as e:
                errors.append(repr(e))
                got_first.set()

        t = threading.Thread(target=consume)
        t0 = time.monotonic()
        t.start()
        # the first token or the consumer's own end, whichever the program
        # produces: no limit of this test's on how long the host takes
        while not got_first.wait(1.0):
            assert t.is_alive(), "admitted stream ended with no token"
        print(f"first token after {time.monotonic() - t0:.1f} s")
        assert not errors, errors

        # JSON client: immediate 429 + Retry-After
        body = json.dumps({"prompt_ids": [4, 5], "max_tokens": 4}).encode()
        t0 = time.monotonic()
        with pytest.raises(urllib.error.HTTPError) as ei:
            urllib.request.urlopen(urllib.request.Request(
                url, method="POST", data=body,
                headers={"Content-Type": "application/json"}), timeout=30)
        assert ei.value.code == 429
        assert int(ei.value.headers["Retry-After"]) >= 1
        shed_body = json.loads(ei.value.read())
        assert shed_body["error"] == "shed"
        assert shed_body["reason"] == "queue_full"
        assert time.monotonic() - t0 < 10, "shed path must not hang"

        # SSE client: the refusal is a terminal error event, same status
        with pytest.raises(urllib.error.HTTPError) as e2:
            urllib.request.urlopen(urllib.request.Request(
                url, method="POST", data=body,
                headers={"Content-Type": "application/json",
                         "Accept": "text/event-stream"}), timeout=30)
        assert e2.value.code == 429
        assert b"event: error" in e2.value.read()

        # the admitted stream was never disturbed
        t.join(120)
        assert stream_done.is_set() and len(stream_tokens) == 30, \
            (len(stream_tokens), errors)
    finally:
        serve.delete("shedapp")


def test_sse_load_smoke_8_streams(serve_instance):
    """Tier-1-sized slice of the SSE load generator: 8 concurrent
    SSE streams over 2 replicas through the real proxy — all complete,
    none half-delivered."""
    from ray_tpu._private.sse_load import run_sse_load

    out = run_sse_load(num_streams=8, num_replicas=2, max_tokens=6,
                       metrics_wait_s=0.0)
    assert out["completed"] == 8, out
    assert out["half_streams"] == 0, out
    assert out["shed"] == 0, out
    assert out["goodput_tokens_per_s"] > 0

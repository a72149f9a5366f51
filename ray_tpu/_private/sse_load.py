"""SSE load generator for an `llm_deployment` behind the real HTTP proxy.

:func:`run_sse_load` deploys a replicated engine inside the caller's
runtime, opens ``num_streams`` concurrent server-sent-event streams against
it (half extend a shared system prompt, half are unique; two tenants) and
returns what a client saw: completed, shed and half-finished streams, TTFT
and ITL percentiles on the host's clock, completed tokens per second and
the engines' own prefix-hit counters.  ``tests/test_llm_prefix.py`` drives
a tier-1-sized slice of it.
"""

from __future__ import annotations

import asyncio
import json
import time
from typing import Dict, List


def _pct(values: List[float], q: float) -> float:
    if not values:
        return 0.0
    s = sorted(values)
    i = min(len(s) - 1, max(0, int(round(q * (len(s) - 1)))))
    return s[i]


def _latency_row(values: List[float]) -> Dict[str, float]:
    return {
        "p50_ms": round(_pct(values, 0.50) * 1e3, 3),
        "p95_ms": round(_pct(values, 0.95) * 1e3, 3),
        "p99_ms": round(_pct(values, 0.99) * 1e3, 3),
    }


async def _drive_stream(session, url: str, prompt: List[int], tenant: str,
                        max_tokens: int, rec: Dict[str, object]) -> None:
    t0 = time.perf_counter()
    last = None
    try:
        async with session.post(
                url, json={"prompt_ids": prompt, "max_tokens": max_tokens,
                           "stream": True, "tenant": tenant},
                headers={"Accept": "text/event-stream"}) as resp:
            if resp.status == 429:
                rec["shed"] = True
                await resp.read()
                return
            async for raw in resp.content:
                line = raw.strip()
                if not line.startswith(b"data:"):
                    if line.startswith(b"event: error"):
                        rec["error"] = True
                    continue
                payload = line[len(b"data:"):].strip()
                if payload == b"[DONE]":
                    rec["done"] = True
                    return
                event = json.loads(payload)
                if event.get("done"):
                    continue
                now = time.perf_counter()
                if last is None:
                    rec["ttft"] = now - t0
                else:
                    rec["itls"].append(now - last)
                last = now
                rec["tokens"] += 1
    except Exception as e:
        rec["error"] = True
        rec["exc"] = repr(e)


async def _drive_load(port: int, num_streams: int,
                      max_tokens: int) -> List[Dict[str, object]]:
    import aiohttp

    url = f"http://127.0.0.1:{port}/llm"
    system = [7 + (i % 40) for i in range(32)]
    records: List[Dict[str, object]] = []
    conn = aiohttp.TCPConnector(limit=num_streams + 16)
    timeout = aiohttp.ClientTimeout(total=240)
    async with aiohttp.ClientSession(connector=conn,
                                     timeout=timeout) as session:
        tasks = []
        for i in range(num_streams):
            # shared-system-prompt mix: half the streams extend the common
            # system prompt, half are fully unique; two tenants
            if i % 2 == 0:
                prompt = system + [60 + (i % 100)] * 8
            else:
                prompt = [(11 + 5 * i + j) % 500 + 1 for j in range(24)]
            rec = {"shed": False, "done": False, "error": False,
                   "tokens": 0, "ttft": None, "itls": []}
            records.append(rec)
            tasks.append(_drive_stream(session, url, prompt,
                                       f"tenant-{i % 2}", max_tokens, rec))
        await asyncio.gather(*tasks)
    return records


def run_sse_load(num_streams: int = 256, num_replicas: int = 2,
                 max_tokens: int = 8,
                 metrics_wait_s: float = 30.0) -> Dict[str, object]:
    from ray_tpu import serve
    from ray_tpu.llm import llm_deployment
    from ray_tpu.util import state

    engine_kwargs = dict(num_pages=256, page_size=8, max_batch_tokens=256,
                         max_running=32, seed=0,
                         engine_name="sse-load",
                         enable_prefix_cache=True,
                         prefill_chunk_tokens=64)
    app = llm_deployment(engine_kwargs=engine_kwargs,
                         num_replicas=num_replicas,
                         max_ongoing_requests=max(num_streams, 64),
                         admission_kwargs=dict(max_inflight=64,
                                               max_queue=num_streams,
                                               queue_deadline_s=120.0))
    serve.run(app, name="llm-load", route_prefix="/llm")
    port = serve.start(http_port=0)
    try:
        t0 = time.perf_counter()
        records = asyncio.new_event_loop().run_until_complete(
            _drive_load(port, num_streams, max_tokens))
        wall = time.perf_counter() - t0

        completed = [r for r in records if r["done"]]
        shed = [r for r in records if r["shed"]
                or (r["error"] and r["tokens"] == 0)]
        half = [r for r in records
                if r["tokens"] > 0 and not r["done"]]
        ttfts = [r["ttft"] for r in completed if r["ttft"] is not None]
        itls = [g for r in completed for g in r["itls"]]
        tokens = sum(r["tokens"] for r in completed)

        # per-engine metric fold (both replicas push under one engine
        # label); the push is periodic, so poll briefly for it to land
        view: Dict[str, float] = {}
        deadline = time.monotonic() + metrics_wait_s
        while time.monotonic() < deadline:
            view = state.summarize_llm().get("sse-load", {})
            if view.get("requests", 0) >= len(completed):
                break
            time.sleep(1.0)
        return {
            "streams": num_streams,
            "replicas": num_replicas,
            "completed": len(completed),
            "shed": len(shed),
            "half_streams": len(half),
            "wall_s": round(wall, 2),
            "goodput_tokens_per_s": round(tokens / max(wall, 1e-9), 1),
            "ttft": _latency_row(ttfts),
            "itl": _latency_row(itls),
            "prefix_hit_rate": round(view.get("prefix_hit_rate", 0.0), 3),
            "prefix_hit_tokens": view.get("prefix_hit_tokens", 0.0),
            "sheds_by_engine_metric": view.get("shed", 0.0),
        }
    finally:
        serve.delete("llm-load")

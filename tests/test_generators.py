"""Dynamic generator returns (reference: num_returns='dynamic',
python/ray/tests/test_generators.py)."""

import numpy as np
import pytest

import ray_tpu


@pytest.fixture
def cluster():
    from conftest import ensure_shared_runtime

    yield ensure_shared_runtime()


def test_dynamic_generator_basic(cluster):
    @ray_tpu.remote(num_returns="dynamic")
    def gen(n):
        for i in range(n):
            yield i * 10

    g = gen.remote(5)
    refs = list(g)
    assert len(refs) == 5 and len(g) == 5
    assert [ray_tpu.get(r, timeout=60) for r in refs] == [0, 10, 20, 30, 40]
    # indexable + re-iterable
    assert ray_tpu.get(g[2], timeout=30) == 20
    assert [ray_tpu.get(r, timeout=30) for r in g] == [0, 10, 20, 30, 40]


def test_dynamic_generator_large_items_and_args(cluster):
    """Yielded items above the inline threshold ride plasma; the refs are
    passable to downstream tasks like any ObjectRef."""

    @ray_tpu.remote(num_returns="dynamic")
    def chunks():
        for i in range(3):
            yield np.full(200_000, i, np.float64)

    @ray_tpu.remote
    def total(x):
        return float(x.sum())

    refs = list(chunks.remote())
    sums = ray_tpu.get([total.remote(r) for r in refs], timeout=120)
    assert sums == [0.0, 200_000.0, 400_000.0]


def test_dynamic_generator_actor_method(cluster):
    """num_returns='dynamic' on ACTOR methods: generator methods drain
    through the same dynamic-return packing as tasks; refs materialize at
    method completion.  Both the per-call .options() route and the
    @ray_tpu.method annotation route work."""

    @ray_tpu.remote
    class Gen:
        def __init__(self):
            self.base = 100

        def items(self, n):
            for i in range(n):
                yield self.base + i

        @ray_tpu.method(num_returns="dynamic")
        def annotated(self, n):
            for i in range(n):
                yield -i

    g = Gen.remote()
    out = g.items.options(num_returns="dynamic").remote(4)
    refs = list(out)
    assert len(refs) == 4 and len(out) == 4
    assert [ray_tpu.get(r, timeout=30) for r in refs] == [100, 101, 102, 103]

    out2 = g.annotated.remote(3)
    assert [ray_tpu.get(r, timeout=30) for r in out2] == [0, -1, -2]


def test_streaming_generator_task(cluster):
    """num_returns='streaming' on a TASK: items are consumable as they are
    produced (each yield seals to plasma immediately); stream() yields
    in order and the generator still materializes the full ref list."""
    import time

    @ray_tpu.remote(num_returns="streaming")
    def slow_gen(n):
        for i in range(n):
            time.sleep(0.05)
            yield i * 2

    g = slow_gen.remote(4)
    assert g.streaming
    got = [ray_tpu.get(r, timeout=60) for r in g.stream(timeout_s=60)]
    assert got == [0, 2, 4, 6]


def test_streaming_generator_actor_method(cluster, tmp_path):
    """Streaming ACTOR methods: the first item is gettable BEFORE the
    method completes — the property that lets a consumer overlap with a
    long-running producer loop."""
    import time

    @ray_tpu.remote
    class Gen:
        def items(self, n, seen_path):
            import os

            for i in range(n):
                yield 100 + i
                # the method goes on only once the consumer has the first
                # item: a drain that is no stream never gets there
                deadline = time.monotonic() + 300
                while not os.path.exists(seen_path):
                    assert time.monotonic() < deadline
                    time.sleep(0.02)

        items.__ray_method_options__ = {"num_returns": "streaming"}

    g = Gen.remote()
    seen = tmp_path / "first-item-seen"
    t0 = time.monotonic()
    out = g.items.remote(5, str(seen))
    first = ray_tpu.get(out.item_ref(0), timeout=120)
    print(f"first item after {time.monotonic() - t0:.2f} s")
    assert first == 100
    seen.touch()
    assert [ray_tpu.get(r, timeout=60) for r in out.stream(timeout_s=60)] \
        == [100, 101, 102, 103, 104]


def test_dynamic_generator_zero_and_error(cluster):
    @ray_tpu.remote(num_returns="dynamic")
    def empty():
        return
        yield  # pragma: no cover

    assert list(empty.remote()) == []

    @ray_tpu.remote(num_returns="dynamic")
    def explode():
        yield 1
        raise RuntimeError("mid-generation failure")

    g = explode.remote()
    with pytest.raises(Exception, match="mid-generation"):
        list(g)

"""ray_tpu.util.collective: eager (cpu) backend across actor ranks + in-jit
xla lowering on the virtual CPU mesh.

Mirrors the reference's collective CPU suite
(reference: python/ray/util/collective/tests/single_node_cpu_tests/) with the
xla backend replacing NCCL (SURVEY §2.3 collectives row).
"""

import numpy as np
import pytest

import ray_tpu

WORLD = 4


@ray_tpu.remote
class Member:
    """One collective rank living in its own worker process."""

    def __init__(self, rank: int, world: int, name: str):
        from ray_tpu.util import collective as col

        self.col = col
        self.rank = rank
        col.init_collective_group(world, rank, backend="cpu", group_name=name)

    def allreduce(self, value, op="sum"):
        return self.col.allreduce(np.asarray(value), group_name=self._g(), op=op)

    def allgather(self, value):
        return self.col.allgather(np.asarray(value), group_name=self._g())

    def reducescatter(self, value, op="sum"):
        return self.col.reducescatter(np.asarray(value), group_name=self._g(), op=op)

    def broadcast(self, value, src_rank=0):
        return self.col.broadcast(np.asarray(value), src_rank=src_rank,
                                  group_name=self._g())

    def barrier(self):
        self.col.barrier(group_name=self._g())
        return True

    def send_many(self, dst, values, tag=0):
        for v in values:
            self.col.send(np.asarray(v), dst, group_name=self._g(), tag=tag)
        return True

    def recv_many(self, src, n, tag=0):
        return [self.col.recv(src, group_name=self._g(), tag=tag) for _ in range(n)]

    def barrier_timeout(self, timeout_s):
        self.col.barrier(group_name=self._g(), timeout_s=timeout_s)
        return True

    def recv_timeout(self, src, timeout_s, tag=0):
        return self.col.recv(src, group_name=self._g(), tag=tag,
                             timeout_s=timeout_s)

    def group_progress(self):
        return self.col.get_group_progress(self._g())

    def set_group(self, name):
        self._group = name

    def _g(self):
        return getattr(self, "_group", None) or self._group_default

    def init_done(self, name):
        self._group_default = name
        return self.rank

    # ---- fast-collectives additions (quant / topology / quorum / A-B) ----

    def allreduce_kw(self, value, kw):
        return self.col.allreduce(np.asarray(value), group_name=self._g(),
                                  **kw)

    def timed_allreduce(self, value, kw):
        import time

        t0 = time.perf_counter()
        out = self.col.allreduce(np.asarray(value), group_name=self._g(),
                                 **kw)
        return time.perf_counter() - t0, out

    def quorum_allreduce(self, value, quorum, delay=0.0, timeout_s=None):
        import time

        if delay:
            time.sleep(delay)
        return self.col.allreduce(np.asarray(value), group_name=self._g(),
                                  quorum=quorum, timeout_s=timeout_s)

    def broadcast_kw(self, value, src_rank, kw):
        return self.col.broadcast(np.asarray(value), src_rank=src_rank,
                                  group_name=self._g(), **kw)

    def set_config(self, name, value):
        from ray_tpu._private.config import RayConfig

        RayConfig.set(name, value)
        return True

    def set_ack_delay(self, delay_s):
        from ray_tpu.util.collective import collective as ccore

        ccore._groups[self._g()]._ack_delay_s = delay_s
        return True

    def group_stats(self):
        from ray_tpu.util.collective import collective as ccore

        g = ccore._groups[self._g()]
        return {"last_quant_error": g.last_quant_error,
                "last_quorum_late": g.last_quorum_late}

    def shm_stats(self):
        from ray_tpu.util.collective import collective as ccore

        g = ccore._groups[self._g()]
        return {"tx_active": g._shm_tx is not None,
                "rx_attached": len(g._shm_rx._att)}

    def allgather_kw(self, value, kw):
        return self.col.allgather(np.asarray(value), group_name=self._g(),
                                  **kw)

    def patch_nodes(self, node_of_rank):
        """Simulate a multi-node world on one host: override the
        rendezvous node map and count shm descriptors arriving from
        cross-node senders — a real remote host could never attach those
        segments by name, so receiving one IS the relay bug."""
        from ray_tpu.util.collective import collective as ccore
        from ray_tpu.util.collective import shm_channel as shm_ch

        g = ccore._groups[self._g()]
        g._member_nodes = {int(r): n for r, n in node_of_rank.items()}
        g._test_cross_descs = 0
        orig = g._on_message

        async def counting(conn, msg):
            if shm_ch.is_desc(msg.get("data")) and \
                    g._member_nodes.get(msg["src"]) != \
                    g._member_nodes.get(g.rank):
                g._test_cross_descs += 1
            return await orig(conn, msg)

        g.core.server.handlers[g._handler_name] = counting
        return True

    def cross_desc_count(self):
        from ray_tpu.util.collective import collective as ccore

        return ccore._groups[self._g()]._test_cross_descs

    def op_capture_posted(self, op, value, kw):
        """Run one op with a spy on _post_send: snapshot every inline
        ndarray at post time, mutate the input right after the op
        returns, and report whether any posted buffer changed afterward
        (a queued fire-and-forget frame must own stable bytes)."""
        import types

        from ray_tpu.util.collective import collective as ccore

        g = ccore._groups[self._g()]
        posted = []
        orig = ccore.Group._post_send

        def spy(gself, rank, data, seq, tag=0):
            if isinstance(data, np.ndarray):
                posted.append((data, data.copy()))
            return orig(gself, rank, data, seq, tag)

        g._post_send = types.MethodType(spy, g)
        try:
            arr = np.asarray(value).copy()
            out = np.array(getattr(self.col, op)(
                arr, group_name=self._g(), **kw))
            arr.fill(-1e9)  # caller reuses its buffer right after return
            corrupted = sum(1 for obj, snap in posted
                            if not np.array_equal(obj, snap))
            return {"posted": len(posted), "corrupted": corrupted,
                    "out": out}
        finally:
            del g._post_send  # instance attr shadowing the class method

    def allgather_then_churn(self, value, churn_value, rounds):
        """allgather, hold the results, run ``rounds`` more allreduces,
        THEN return the gathered list — catches results that alias shm
        arena memory the later ops reuse."""
        got = self.col.allgather(np.asarray(value), group_name=self._g())
        for _ in range(rounds):
            self.col.allreduce(np.asarray(churn_value),
                               group_name=self._g())
        return got


@pytest.fixture(scope="module")
def members():
    import uuid

    import tests.conftest as c

    c.ensure_shared_runtime()
    name = f"testgrp-{uuid.uuid4().hex[:6]}"
    actors = [Member.remote(r, WORLD, name) for r in range(WORLD)]
    ray_tpu.get([a.init_done.remote(name) for a in actors])
    yield actors
    for a in actors:
        ray_tpu.kill(a)


def test_allreduce_sum(members):
    outs = ray_tpu.get([a.allreduce.remote(np.full((4,), float(i + 1)))
                        for i, a in enumerate(members)])
    expect = np.full((4,), float(sum(range(1, WORLD + 1))))
    for o in outs:
        np.testing.assert_allclose(o, expect)


def test_allreduce_max(members):
    outs = ray_tpu.get([a.allreduce.remote(np.array([float(i)]), "max")
                        for i, a in enumerate(members)])
    for o in outs:
        np.testing.assert_allclose(o, [float(WORLD - 1)])


def test_allgather(members):
    outs = ray_tpu.get([a.allgather.remote(np.array([i * 10.0]))
                        for i, a in enumerate(members)])
    for o in outs:
        assert len(o) == WORLD
        np.testing.assert_allclose(np.concatenate(o),
                                   [0.0, 10.0, 20.0, 30.0])


def test_reducescatter(members):
    data = np.arange(WORLD, dtype=np.float64)
    outs = ray_tpu.get([a.reducescatter.remote(data) for a in members])
    for r, o in enumerate(outs):
        np.testing.assert_allclose(o, [r * WORLD])


def test_broadcast_nonzero_root(members):
    outs = ray_tpu.get([
        a.broadcast.remote(np.array([100.0 + i]), 2)
        for i, a in enumerate(members)])
    for o in outs:
        np.testing.assert_allclose(o, [102.0])


def test_barrier(members):
    assert all(ray_tpu.get([a.barrier.remote() for a in members]))


def test_p2p_queue_same_tag(members):
    """Two sends with the same (src, tag) before any recv must both arrive in
    order (round-1 advisor bug: the second overwrote the first)."""
    vals = [np.array([1.0]), np.array([2.0]), np.array([3.0])]
    send = members[1].send_many.remote(0, vals, 7)
    got, _ = ray_tpu.get([members[0].recv_many.remote(1, 3, 7), send])
    np.testing.assert_allclose(np.concatenate(got), [1.0, 2.0, 3.0])


class TestXlaLowering:
    """The ICI path: in-jit collectives over a shard_map axis on the CPU mesh."""

    def _mesh(self, n=4):
        import jax
        from jax.sharding import Mesh

        return Mesh(np.asarray(jax.devices()[:n]), ("dp",))

    def _run(self, fn, x, n=4):
        import jax
        from jax.sharding import PartitionSpec as P

        mesh = self._mesh(n)
        return jax.jit(jax.shard_map(fn, mesh=mesh, in_specs=(P("dp"),),
                                     out_specs=P("dp")))(x)

    def test_allreduce(self):
        from ray_tpu.util.collective import xla

        x = np.arange(8, dtype=np.float32)
        out = self._run(lambda s: xla.allreduce(s, "dp"), x)
        # each shard of 2 elements is replaced by the sum over shards
        expect = np.tile(x.reshape(4, 2).sum(0), 4)
        np.testing.assert_allclose(np.asarray(out), expect)

    def test_reducescatter_matches_allreduce_shard(self):
        import jax
        from jax.sharding import PartitionSpec as P

        from ray_tpu.util.collective import xla

        x = np.arange(16, dtype=np.float32)
        mesh = self._mesh(4)
        out = jax.jit(jax.shard_map(
            lambda s: xla.reducescatter(s, "dp"),
            mesh=mesh, in_specs=(P("dp"),), out_specs=P("dp")))(x)
        shards = x.reshape(4, 4)
        total = shards.sum(0)  # (4,)
        np.testing.assert_allclose(np.asarray(out), total)

    def test_permute_ring(self):
        from ray_tpu.util.collective import xla

        x = np.arange(4, dtype=np.float32)
        perm = [(i, (i + 1) % 4) for i in range(4)]
        out = self._run(lambda s: xla.permute(s, "dp", perm), x)
        np.testing.assert_allclose(np.asarray(out), [3.0, 0.0, 1.0, 2.0])

    def test_alltoall(self):
        from ray_tpu.util.collective import xla

        # 4 devices, each holding (4,) -> all_to_all transposes block layout.
        x = np.arange(16, dtype=np.float32)
        out = self._run(lambda s: xla.alltoall(s, "dp"), x)
        expect = np.arange(16, dtype=np.float32).reshape(4, 4).T.reshape(-1)
        np.testing.assert_allclose(np.asarray(out), expect)


def test_reducescatter_2d_shape_parity(members):
    # shard shapes must match v1's array_split(allreduce(x), n, axis=0)
    data = np.arange(float(WORLD * 2 * 3)).reshape(WORLD * 2, 3)
    outs = ray_tpu.get([a.reducescatter.remote(data) for a in members])
    full = data * WORLD
    for r, o in enumerate(outs):
        np.testing.assert_allclose(o, np.array_split(full, WORLD, axis=0)[r])
        assert o.shape == (2, 3)


# --------------------------------------------------- timeouts / stragglers

def _fresh_group(n, prefix):
    """Dedicated actors + group: a timed-out collective leaves per-rank seq
    counters misaligned, so these tests must never share the module group."""
    import uuid

    name = f"{prefix}-{uuid.uuid4().hex[:6]}"
    actors = [Member.remote(r, n, name) for r in range(n)]
    ray_tpu.get([a.init_done.remote(name) for a in actors])
    return actors


def test_barrier_timeout_names_absent_rank(ray_start_regular):
    """A barrier with one rank missing raises CollectiveTimeout naming that
    rank (ISSUE 3 acceptance) instead of hanging forever."""
    from ray_tpu.exceptions import CollectiveTimeout

    actors = _fresh_group(3, "tmo-barrier")
    try:
        # ranks 0 and 1 enter the barrier; rank 2 never does
        refs = [actors[0].barrier_timeout.remote(3.0),
                actors[1].barrier_timeout.remote(3.0)]
        for ref in refs:
            with pytest.raises(CollectiveTimeout, match="rank 2"):
                ray_tpu.get(ref)
        # progress through the KV rendezvous names the straggler: rank 2 is
        # still at the init stamp while 0/1 advanced to the barrier seq
        prog = ray_tpu.get(actors[0].group_progress.remote())
        assert prog[2]["seq"] < prog[0]["seq"]
    finally:
        for a in actors:
            ray_tpu.kill(a)


def test_recv_timeout_raises_instead_of_blocking(ray_start_regular):
    from ray_tpu.exceptions import CollectiveTimeout

    actors = _fresh_group(2, "tmo-recv")
    try:
        with pytest.raises(CollectiveTimeout, match="rank 1"):
            ray_tpu.get(actors[0].recv_timeout.remote(1, 2.0))
    finally:
        for a in actors:
            ray_tpu.kill(a)


# ------------------------------------------- wire quantization (unit level)

def test_quantization_roundtrip_error_bound():
    """Measured round-trip error never exceeds the analytic max block
    scale / 2 bound, for assorted shapes and block sizes."""
    from ray_tpu.util.collective.quantization import (
        dequantize_blockwise, max_error_bound, quantize_blockwise,
        wire_bytes)

    rng = np.random.default_rng(7)
    for shape, block in [((1000,), 64), ((33, 7), 16), ((5,), 256),
                         ((4096,), 256)]:
        x = rng.uniform(-3.0, 3.0, size=shape).astype(np.float32)
        rec, err = quantize_blockwise(x, block=block)
        y = dequantize_blockwise(rec)
        assert y.shape == x.shape and y.dtype == np.float32
        measured = float(np.abs(y - x).max())
        assert measured <= max_error_bound(rec) + 1e-6
        assert abs(measured - err) <= 1e-6  # reported error IS the actual
        # int8 payload + fp32 scales must beat fp32 wire bytes by ~4x
        assert wire_bytes(rec) < x.nbytes / 2


def test_quantization_zero_blocks_safe():
    from ray_tpu.util.collective.quantization import (
        dequantize_blockwise, quantize_blockwise)

    rec, err = quantize_blockwise(np.zeros(100, np.float32), block=32)
    assert err == 0.0
    assert np.all(dequantize_blockwise(rec) == 0.0)


def test_topology_selection():
    from ray_tpu.util.collective import topology as topo

    two_nodes = {0: "a", 1: "a", 2: "b", 3: "b"}
    big, small = 1 << 20, 1024
    assert topo.select(4, two_nodes, big) == "hier"
    assert topo.select(4, two_nodes, small) == "ring"       # latency-bound
    assert topo.select(4, {r: "a" for r in range(4)}, big) == "ring"
    assert topo.select(4, {0: "a", 1: "b", 2: "c", 3: "d"}, big) == "ring"
    assert topo.select(4, two_nodes, small, "hier") == "hier"  # explicit
    p = topo.plan(2, 4, two_nodes, big)
    assert p.kind == "hier" and p.leaders == [0, 2]
    assert p.is_leader and p.members == [3]
    p1 = topo.plan(1, 4, two_nodes, big)
    assert not p1.is_leader and p1.leader == 0 and p1.members == []


# ----------------------------------------- quant / topology / quorum (e2e)

def test_allreduce_int8_error_bounded(members):
    """int8 allreduce lands within the documented bound: one quant stage
    per ring hop, each <= (partial-sum absmax)/254, summing to roughly
    n(n+1)/(2*254) for inputs in [-1, 1]."""
    rng = np.random.default_rng(11)
    data = [rng.uniform(-1.0, 1.0, 1024).astype(np.float32)
            for _ in range(WORLD)]
    exact = np.sum(data, axis=0)
    outs = ray_tpu.get([a.allreduce_kw.remote(data[i], {"quant": "int8"})
                        for i, a in enumerate(members)])
    bound = WORLD * (WORLD + 1) / (2 * 254) + 1e-3
    for o in outs:
        assert float(np.abs(o - exact).max()) <= bound
    # every rank reported a measured (nonzero, bounded) quant error
    stats = ray_tpu.get([a.group_stats.remote() for a in members])
    for s in stats:
        assert 0.0 < s["last_quant_error"] <= bound


def test_broadcast_int8_single_stage(members):
    """Broadcast quantizes once at the root and relays verbatim: error is
    one stage, <= absmax/254."""
    rng = np.random.default_rng(13)
    val = rng.uniform(-1.0, 1.0, 512).astype(np.float32)
    outs = ray_tpu.get([a.broadcast_kw.remote(val, 1, {"quant": "int8"})
                        for a in members])
    for o in outs:
        assert float(np.abs(np.asarray(o, np.float32) - val).max()) \
            <= 1.0 / 254 + 1e-6
    # all receivers dequantize the SAME record -> identical results
    # (the root returns its own exact array, so compare non-root ranks)
    recv_outs = [o for i, o in enumerate(outs) if i != 1]
    for o in recv_outs[1:]:
        np.testing.assert_array_equal(np.asarray(o), np.asarray(recv_outs[0]))


def test_allreduce_multichunk_exact(ray_start_regular):
    """Payloads spanning many wire chunks reduce exactly (tag-per-chunk
    stream reassembly)."""
    actors = _fresh_group(2, "chunks")
    try:
        ray_tpu.get([a.set_config.remote("collective_chunk_bytes", 1024)
                     for a in actors])
        data = [np.arange(2000, dtype=np.float64) * (i + 1)
                for i in range(2)]
        outs = ray_tpu.get([a.allreduce_kw.remote(data[i], {})
                            for i, a in enumerate(actors)])
        expect = data[0] + data[1]
        for o in outs:
            np.testing.assert_array_equal(o, expect)
    finally:
        for a in actors:
            ray_tpu.kill(a)


def test_hierarchical_matches_ring_bitwise(ray_start_regular):
    """Two-level (virtual 2-node) allreduce must produce bit-identical
    fp32 output to the flat ring on integer-valued data."""
    n = 4
    actors = _fresh_group(n, "hier")
    try:
        ray_tpu.get([a.set_config.remote("collective_virtual_nodes", 2)
                     for a in actors])
        rng = np.random.default_rng(17)
        data = [rng.integers(-8, 8, size=(64, 3)).astype(np.float32)
                for _ in range(n)]
        ring = ray_tpu.get([
            a.allreduce_kw.remote(data[i], {"topology": "ring"})
            for i, a in enumerate(actors)])
        hier = ray_tpu.get([
            a.allreduce_kw.remote(data[i], {"topology": "hier"})
            for i, a in enumerate(actors)])
        expect = np.sum(data, axis=0)
        for r, h in zip(ring, hier):
            np.testing.assert_array_equal(r, expect)
            np.testing.assert_array_equal(h, expect)  # bit-identical
    finally:
        for a in actors:
            ray_tpu.kill(a)


def test_quorum_returns_early_then_folds_in(ray_start_regular):
    """allreduce(quorum=K) returns without the straggler; its late
    contribution folds into the next quorum op so cumulative sums match
    full participation (arXiv:2505.23523 shape)."""
    import time

    n = 3
    actors = _fresh_group(n, "quorum")
    v = [np.full(8, float(10 ** i)) for i in range(n)]  # 1, 10, 100
    w = [np.full(8, 2.0 * (i + 1)) for i in range(n)]   # 2, 4, 6
    try:
        # round 1: ranks 0/1 contribute now, rank 2 is 2.5 s late
        t0 = time.perf_counter()
        fast = [actors[0].quorum_allreduce.remote(v[0], 2),
                actors[1].quorum_allreduce.remote(v[1], 2)]
        late = actors[2].quorum_allreduce.remote(v[2], 2, delay=2.5)
        r0, r1 = ray_tpu.get(fast)
        elapsed = time.perf_counter() - t0
        assert elapsed < 2.0, f"quorum waited for the straggler ({elapsed:.2f}s)"
        np.testing.assert_allclose(r0, v[0] + v[1])  # 11, not 111
        np.testing.assert_allclose(r1, v[0] + v[1])
        # the straggler still gets round 1's (quorum-only) result
        np.testing.assert_allclose(ray_tpu.get(late), v[0] + v[1])
        assert ray_tpu.get(actors[0].group_stats.remote())[
            "last_quorum_late"] == [2]
        # round 2 (full quorum): rank 2's parked round-1 payload folds in
        outs = ray_tpu.get([a.quorum_allreduce.remote(w[i], n)
                            for i, a in enumerate(actors)])
        round2 = w[0] + w[1] + w[2] + v[2]
        for o in outs:
            np.testing.assert_allclose(o, round2)
        # cumulative across rounds == full participation
        np.testing.assert_allclose(r0 + outs[0], np.sum(v + w, axis=0))
        assert ray_tpu.get(actors[0].group_stats.remote())[
            "last_quorum_late"] == []
    finally:
        for a in actors:
            ray_tpu.kill(a)


def test_pipelined_ring_overlaps_delayed_acks(ray_start_regular):
    """With one rank's ACK path delayed by 0.25 s, a ring that waited for
    each hop's ACK would pay the delay on every one of its 2 (n - 1) = 4
    hops; the ring's sends are fire-and-forget, so the allreduce gives the
    sums in less than the waits this test injected itself (the bound is the
    test's own delay, not the host's speed)."""
    n, delay = 3, 0.25
    actors = _fresh_group(n, "overlap")
    try:
        ray_tpu.get(actors[1].set_ack_delay.remote(delay))
        piped = ray_tpu.get([
            a.timed_allreduce.remote(np.full(8, float(i)), {})
            for i, a in enumerate(actors)])
        t_piped = max(t for t, _ in piped)
        print(f"allreduce under {delay} s ACK delay: {t_piped:.3f} s")
        expect = np.full(8, float(sum(range(n))))
        for _, o in piped:
            np.testing.assert_allclose(o, expect)
        hops = 2 * (n - 1)
        assert t_piped < hops * delay, \
            f"the ring waited on ACKs: {t_piped:.2f}s"
    finally:
        for a in actors:
            ray_tpu.kill(a)


def test_timeout_names_rank_under_new_paths(ray_start_regular):
    """CollectiveTimeout still names the lagging rank on the hierarchical
    and quorum paths."""
    from ray_tpu.exceptions import CollectiveTimeout

    actors = _fresh_group(3, "tmo-hier")
    try:
        ray_tpu.get([a.set_config.remote("collective_virtual_nodes", 2)
                     for a in actors[:2]])
        # ranks 0 (leader) and 1 (member) enter; rank 2 (other node) never
        refs = [actors[0].allreduce_kw.remote(
                    np.ones(4), {"topology": "hier", "timeout_s": 3.0}),
                actors[1].allreduce_kw.remote(
                    np.ones(4), {"topology": "hier", "timeout_s": 3.0})]
        for ref in refs:
            with pytest.raises(CollectiveTimeout, match="rank 2"):
                ray_tpu.get(ref)
    finally:
        for a in actors:
            ray_tpu.kill(a)

    actors = _fresh_group(2, "tmo-quorum")
    try:
        with pytest.raises(CollectiveTimeout, match="rank 1"):
            ray_tpu.get(actors[0].quorum_allreduce.remote(
                np.ones(4), 2, timeout_s=2.0))
    finally:
        for a in actors:
            ray_tpu.kill(a)


# ------------------------------------------ shared-memory chunk channel

def test_shm_arena_place_resolve_unit():
    """TxArena/RxCache round trip plus the reuse rules: fan-out descriptor
    caching, parity-half alternation, growth keeping the old segment
    attachable for two placing ops before unlinking."""
    import os
    import uuid

    from ray_tpu.util.collective import shm_channel as shm_ch

    tx = shm_ch.TxArena(f"shmt-{os.getpid()}-{uuid.uuid4().hex[:6]}")
    rx = shm_ch.RxCache()
    try:
        a = np.arange(65536, dtype=np.float32)
        d1 = tx.place(a, seq=1, tag=5, min_bytes=1024)
        assert shm_ch.is_desc(d1) and shm_ch.desc_bytes(d1) == a.nbytes
        np.testing.assert_array_equal(rx.resolve(d1), a)
        # fan-out sends of the same payload within one op share the desc
        assert tx.place(a, seq=1, tag=5, min_bytes=1024) is d1
        # tiny payloads decline (caller sends them inline)
        assert tx.place(np.ones(4, np.float32), seq=2, tag=5,
                        min_bytes=1024) is None
        # consecutive placing ops land in alternating halves...
        b = a * 2.0
        d2 = tx.place(b, seq=3, tag=5, min_bytes=1024)
        assert d2["seg"] == d1["seg"]
        assert d2["bufs"][0][0] != d1["bufs"][0][0]
        # ...and the third reuses the first op's half
        c = a * 3.0
        d3 = tx.place(c, seq=4, tag=5, min_bytes=1024)
        assert d3["bufs"][0][0] == d1["bufs"][0][0]
        np.testing.assert_array_equal(rx.resolve(d3), c)
        # growth: a payload over half the cap moves to a larger segment;
        # the old one stays attachable for two more placing ops
        big = np.ones(3 * 1024 * 1024, np.float32)  # 12 MiB > 8 MiB cap
        d4 = tx.place(big, seq=5, tag=5, min_bytes=1024)
        assert d4["seg"] != d1["seg"]
        np.testing.assert_array_equal(rx.resolve(d4), big)
        shm_ch._attach(d1["seg"]).close()  # still linked
        tx.place(a, seq=6, tag=5, min_bytes=1024)
        tx.place(a, seq=7, tag=5, min_bytes=1024)  # retire point passed
        with pytest.raises(FileNotFoundError):
            shm_ch._attach(d1["seg"])
    finally:
        rx.close()
        tx.close()


def test_allreduce_large_shm_engages_and_matches_tcp(ray_start_regular):
    """Bulk same-node chunks ride the shm arena (descriptors on the wire)
    and produce the identical result as the TCP inline path."""
    actors = _fresh_group(2, "shm-ring")
    try:
        rng = np.random.default_rng(23)
        data = [rng.standard_normal(256 * 1024).astype(np.float32)
                for _ in range(2)]
        with_shm = ray_tpu.get([a.allreduce_kw.remote(data[i], {})
                                for i, a in enumerate(actors)])
        stats = ray_tpu.get([a.shm_stats.remote() for a in actors])
        assert all(s["tx_active"] for s in stats), stats
        assert all(s["rx_attached"] >= 1 for s in stats), stats
        # shm off -> same bytes through the TCP inline path
        ray_tpu.get([a.set_config.remote("collective_shm_min_bytes", 0)
                     for a in actors])
        no_shm = ray_tpu.get([a.allreduce_kw.remote(data[i], {})
                              for i, a in enumerate(actors)])
        expect = data[0] + data[1]
        for w, t in zip(with_shm, no_shm):
            np.testing.assert_array_equal(w, expect)
            np.testing.assert_array_equal(t, expect)
    finally:
        for a in actors:
            ray_tpu.kill(a)


def test_allgather_large_results_detached_from_arena(ray_start_regular):
    """allgather results must be copies, not views of arena memory:
    subsequent ops reuse the arena halves, so a rank that holds gathered
    arrays across later collectives must still see the original bytes."""
    n = 3
    actors = _fresh_group(n, "shm-ag")
    try:
        data = [np.full(64 * 1024, float(i + 1), np.float32)
                for i in range(n)]
        churn = np.ones(128 * 1024, np.float32)  # cycles both parity halves
        outs = ray_tpu.get([
            a.allgather_then_churn.remote(data[i], churn, 3)
            for i, a in enumerate(actors)])
        for got in outs:
            assert len(got) == n
            for r in range(n):
                np.testing.assert_array_equal(got[r], data[r])
    finally:
        for a in actors:
            ray_tpu.kill(a)


def test_hierarchical_large_shm_exact(ray_start_regular):
    """The two-level path's gather + leader-broadcast legs ride the arena
    for bulk payloads and still reduce exactly."""
    n = 4
    actors = _fresh_group(n, "shm-hier")
    try:
        ray_tpu.get([a.set_config.remote("collective_virtual_nodes", 2)
                     for a in actors])
        rng = np.random.default_rng(29)
        data = [rng.integers(-8, 8, size=256 * 1024).astype(np.float32)
                for _ in range(n)]
        outs = ray_tpu.get([
            a.allreduce_kw.remote(data[i], {"topology": "hier"})
            for i, a in enumerate(actors)])
        expect = np.sum(data, axis=0)
        for o in outs:
            np.testing.assert_array_equal(o, expect)
        stats = ray_tpu.get([a.shm_stats.remote() for a in actors])
        assert any(s["tx_active"] for s in stats), stats
    finally:
        for a in actors:
            ray_tpu.kill(a)


# --------------------------------------------- PR 7 review regressions

def test_ring_relay_never_ships_desc_cross_node(ray_start_regular):
    """A shm descriptor names a POSIX segment that exists only on its
    origin node: relays whose next hop lives on another node must resolve
    it to an inline copy (on a real two-node world the raw relay is a
    FileNotFoundError on attach, or worse, a stale same-name segment).
    Single-host runs can attach cross-'node', so assert the invariant
    directly: no rank ever RECEIVES a descriptor from a cross-node
    sender, on both relay paths (ring allgather phase, whole-payload
    allgather rotation), while same-node hops still ride the arena."""
    n = 4
    actors = _fresh_group(n, "xnode")
    try:
        nodes = {0: "nodeA", 1: "nodeA", 2: "nodeB", 3: "nodeB"}
        ray_tpu.get([a.patch_nodes.remote(nodes) for a in actors])
        rng = np.random.default_rng(31)
        data = [rng.integers(-8, 8, size=256 * 1024).astype(np.float32)
                for _ in range(n)]
        outs = ray_tpu.get([
            a.allreduce_kw.remote(data[i], {"topology": "ring"})
            for i, a in enumerate(actors)])
        expect = np.sum(data, axis=0)
        for o in outs:
            np.testing.assert_array_equal(o, expect)
        ag = ray_tpu.get([a.allgather_kw.remote(data[i], {})
                          for i, a in enumerate(actors)])
        for got in ag:
            for r in range(n):
                np.testing.assert_array_equal(got[r], data[r])
        counts = ray_tpu.get([a.cross_desc_count.remote() for a in actors])
        assert all(c == 0 for c in counts), \
            f"descriptors crossed 'nodes': {counts}"
        stats = ray_tpu.get([a.shm_stats.remote() for a in actors])
        assert any(s["tx_active"] for s in stats), stats
    finally:
        for a in actors:
            ray_tpu.kill(a)


def test_pipelined_inflight_frames_own_their_bytes(ray_start_regular):
    """Inline pipelined sends above the RPC out-of-band threshold must be
    detached copies: the allgather phase overwrites exactly the slices
    reduce-scatter posted, and a caller may mutate its tensor the moment
    an op returns, while the frames can still be queued behind a slow
    peer.  Snapshot every posted array at post time and verify none
    changed afterward."""
    n = 2
    actors = _fresh_group(n, "detach")
    try:
        # force the TCP inline path so the posted payloads are ndarrays
        ray_tpu.get([a.set_config.remote("collective_shm_min_bytes", 0)
                     for a in actors])
        data = [np.full(64 * 1024, float(i + 1), np.float32)
                for i in range(n)]
        r0, _ = ray_tpu.get([
            actors[0].op_capture_posted.remote("allreduce", data[0], {}),
            actors[1].allreduce_kw.remote(data[1], {})])
        np.testing.assert_array_equal(r0["out"], data[0] + data[1])
        assert r0["posted"] > 0
        assert r0["corrupted"] == 0, \
            f"{r0['corrupted']}/{r0['posted']} in-flight buffers mutated"
        # broadcast: the root returns before the fan-out frames drain;
        # mutating the returned/input tensor must not corrupt them
        r0, _ = ray_tpu.get([
            actors[0].op_capture_posted.remote("broadcast", data[0], {}),
            actors[1].broadcast_kw.remote(data[1], 0, {})])
        assert r0["posted"] > 0
        assert r0["corrupted"] == 0, \
            f"{r0['corrupted']}/{r0['posted']} broadcast frames mutated"
    finally:
        for a in actors:
            ray_tpu.kill(a)


def test_allgather_int8_symmetric_across_ranks(members):
    """Quantized allgather is symmetric: every rank sees the IDENTICAL
    list, each entry being the owner's single quantize->dequantize round
    trip in the owner's dtype (the own entry is not kept exact — that
    made list entries differ per rank)."""
    rng = np.random.default_rng(37)
    data = [rng.uniform(-1.0, 1.0, 300).astype(np.float32)
            for _ in range(WORLD)]
    outs = ray_tpu.get([a.allgather_kw.remote(data[i], {"quant": "int8"})
                        for i, a in enumerate(members)])
    for o in outs:
        for r in range(WORLD):
            assert o[r].dtype == np.float32
            # one quant stage per entry, inputs in [-1, 1]
            assert float(np.abs(o[r] - data[r]).max()) <= 1.0 / 254 + 1e-6
    for o in outs[1:]:
        for r in range(WORLD):
            np.testing.assert_array_equal(o[r], outs[0][r])

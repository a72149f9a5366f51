"""Core API tests: tasks, objects, errors (reference: python/ray/tests/test_basic.py)."""

import time

import numpy as np
import pytest

import ray_tpu
from ray_tpu.exceptions import GetTimeoutError, RayTaskError


@ray_tpu.remote
def echo(x):
    return x


@ray_tpu.remote
def add(a, b):
    return a + b


class TestTasks:
    def test_release_last_ref_on_io_loop_no_deadlock(self, ray_start_regular):
        """Regression (round-1 advisor): task completion releasing the last
        Python ref to a plasma-mapped object ran ObjectRef.__del__ ->
        plasma.release -> blocking call_sync ON the IO loop, hanging the
        driver.  Repro: put big; get (maps shm); pass to task; del ref."""
        big = np.zeros(2_000_000)  # large enough to go to plasma
        ref = ray_tpu.put(big)
        assert ray_tpu.get(ref, timeout=60).shape == big.shape  # map locally

        @ray_tpu.remote
        def consume(x):
            return float(x.sum())

        out = consume.remote(ref)
        del ref  # the task's hold is now the last reference
        assert ray_tpu.get(out, timeout=60) == 0.0
        # driver loop still functional:
        assert ray_tpu.get(add.remote(1, 1), timeout=60) == 2

    def test_large_function_blob(self, ray_start_regular):
        """Functions above the function-table threshold ship via GCS KV; the
        worker-side kv_get must not run on (and deadlock) its IO loop."""
        payload = bytes(900_000)

        @ray_tpu.remote
        def bigfn():
            return len(payload)

        assert ray_tpu.get(bigfn.remote(), timeout=120) == 900_000

    def test_async_actor_large_return(self, ray_start_regular):
        """Async actor methods returning plasma-bound objects must pack
        returns off the IO loop (plasma.put blocks on it)."""

        @ray_tpu.remote
        class A:
            async def big(self):
                return np.ones(200_000)

        a = A.remote()
        assert ray_tpu.get(a.big.remote(), timeout=120).shape == (200_000,)

    def test_simple_task(self, ray_start_regular):
        assert ray_tpu.get(add.remote(1, 2), timeout=60) == 3

    def test_many_tasks(self, ray_start_regular):
        refs = [add.remote(i, i) for i in range(64)]
        assert ray_tpu.get(refs, timeout=60) == [2 * i for i in range(64)]

    def test_kwargs_and_options(self, ray_start_regular):
        @ray_tpu.remote
        def f(a, b=2, *, c=3):
            return a + b + c

        assert ray_tpu.get(f.remote(1), timeout=60) == 6
        assert ray_tpu.get(f.remote(1, b=5, c=10), timeout=30) == 16
        assert ray_tpu.get(f.options(name="renamed").remote(1), timeout=30) == 6

    def test_multiple_returns(self, ray_start_regular):
        @ray_tpu.remote(num_returns=3)
        def three():
            return 1, 2, 3

        a, b, c = three.remote()
        assert ray_tpu.get([a, b, c], timeout=30) == [1, 2, 3]

    def test_nested_tasks(self, ray_start_regular):
        @ray_tpu.remote
        def outer(x):
            return ray_tpu.get(echo.remote(x * 2))

        assert ray_tpu.get(outer.remote(21), timeout=60) == 42

    def test_chained_refs_as_args(self, ray_start_regular):
        r1 = add.remote(1, 1)
        r2 = add.remote(r1, 1)
        r3 = add.remote(r2, r1)
        assert ray_tpu.get(r3, timeout=60) == 5

    def test_task_error_propagates_type(self, ray_start_regular):
        @ray_tpu.remote
        def boom():
            raise KeyError("missing!")

        with pytest.raises(KeyError):
            ray_tpu.get(boom.remote(), timeout=60)
        with pytest.raises(RayTaskError):
            ray_tpu.get(boom.remote(), timeout=30)

    def test_error_in_dependency_propagates(self, ray_start_regular):
        @ray_tpu.remote
        def boom():
            raise ValueError("upstream")

        r = echo.remote(boom.remote())
        with pytest.raises(Exception):
            ray_tpu.get(r, timeout=60)


class TestObjects:
    def test_put_get_small(self, ray_start_regular):
        ref = ray_tpu.put({"a": 1, "b": [1, 2, 3]})
        assert ray_tpu.get(ref, timeout=30) == {"a": 1, "b": [1, 2, 3]}

    def test_put_get_large_numpy(self, ray_start_regular):
        arr = np.random.rand(500_000)
        ref = ray_tpu.put(arr)
        out = ray_tpu.get(ref, timeout=30)
        np.testing.assert_array_equal(out, arr)

    def test_large_arg_and_return(self, ray_start_regular):
        arr = np.ones(300_000)

        @ray_tpu.remote
        def double(x):
            return x * 2

        out = ray_tpu.get(double.remote(arr), timeout=60)
        np.testing.assert_array_equal(out, arr * 2)

    def test_ref_in_container_arg(self, ray_start_regular):
        inner = ray_tpu.put(41)

        @ray_tpu.remote
        def deref(d):
            return ray_tpu.get(d["ref"]) + 1

        assert ray_tpu.get(deref.remote({"ref": inner}), timeout=60) == 42

    def test_get_timeout(self, ray_start_regular):
        @ray_tpu.remote
        def slow():
            time.sleep(10)

        with pytest.raises(GetTimeoutError):
            ray_tpu.get(slow.remote(), timeout=0.5)

    def test_wait(self, ray_start_regular):
        @ray_tpu.remote
        def sleep_then(x, t):
            time.sleep(t)
            return x

        fast = [sleep_then.remote(i, 0.0) for i in range(3)]
        slow = [sleep_then.remote(99, 5.0)]
        ready, pending = ray_tpu.wait(fast + slow, num_returns=3, timeout=30)
        assert len(ready) == 3 and len(pending) == 1

    def test_wait_timeout(self, ray_start_regular):
        @ray_tpu.remote
        def slow():
            time.sleep(10)

        ready, pending = ray_tpu.wait([slow.remote()], num_returns=1, timeout=0.3)
        assert ready == [] and len(pending) == 1


class TestClusterInfo:
    def test_nodes_and_resources(self, ray_start_regular):
        ns = ray_tpu.nodes()
        assert len(ns) == 1 and ns[0]["Alive"]
        assert ray_tpu.cluster_resources()["CPU"] >= 4.0

    def test_runtime_context_in_task(self, ray_start_regular):
        @ray_tpu.remote
        def ctx_info():
            ctx = ray_tpu.get_runtime_context()
            return ctx.get_task_id(), ctx.get_worker_id()

        task_id, worker_id = ray_tpu.get(ctx_info.remote(), timeout=60)
        assert task_id and worker_id


class TestReturnedRefs:
    def test_ref_returned_by_actor_survives_owner_release(
            self, ray_start_regular):
        """An ObjectRef nested in an actor's RETURN value must stay alive
        after the actor drops its own handle: the executor pins it under a
        synthetic borrower until the caller registers its holds (reference:
        reference_count.h borrower protocol for refs in task returns).
        Regression: the owner used to free the object in that window and the
        borrower's get() hung forever."""
        import gc
        import time

        @ray_tpu.remote
        class Maker:
            def make(self):
                ref = ray_tpu.put({"payload": 123})
                return ref  # only copy: dropped when this frame exits

            def collect(self):
                gc.collect()
                return True

        m = Maker.remote()
        inner = ray_tpu.get(m.make.remote(), timeout=30)
        assert ray_tpu.get(m.collect.remote(), timeout=30)
        time.sleep(0.5)  # let any stray free propagate
        assert ray_tpu.get(inner, timeout=30) == {"payload": 123}

    def test_ref_created_by_task_returned_through_actor(
            self, ray_start_regular):
        """Same protocol, with the inner object produced by a task the actor
        submitted (the streaming-Data coordinator pattern)."""
        import gc
        import time

        @ray_tpu.remote
        def produce():
            return list(range(100))

        @ray_tpu.remote
        class Coord:
            def run(self):
                ref = produce.remote()
                ray_tpu.wait([ref], num_returns=1, timeout=30)
                return ref

            def collect(self):
                gc.collect()
                return True

        c = Coord.remote()
        inner = ray_tpu.get(c.run.remote(), timeout=30)
        assert ray_tpu.get(c.collect.remote(), timeout=30)
        time.sleep(0.5)
        assert ray_tpu.get(inner, timeout=30) == list(range(100))


def test_spilled_lease_never_queues_on_infeasible_node(ray_start_regular):
    """A lease request that arrives pre-spilled at a node which can NEVER
    satisfy it must bounce back ('retry'), not queue forever (the old
    hard 2-hop cap skipped the feasibility check for spilled requests)."""
    from ray_tpu._private.worker import require_core

    core = require_core()
    # the shared runtime's single node: ask for more CPU than it has
    info = core.io.run(core.nodelet_conn.call("node_info", None))
    too_big = {"CPU": float(info["resources_total"].get("CPU", 1)) + 64}

    resp = core.io.run(core.nodelet_conn.call(
        "request_worker_lease",
        {"resources": too_big, "strategy": {"kind": "hybrid"},
         "bundle": None, "spillback_count": 5, "token": "t-spill-test"},
        timeout=30))
    assert resp["type"] == "retry", resp


def test_spill_chain_end_bounces_off_small_node():
    """End-of-chain semantics: a request at its spillback cap, on a node too
    small for it while a BIGGER node exists, bounces 'retry' (and records
    demand) instead of queueing forever on the small node."""
    from ray_tpu._private import rpc as _rpc
    from ray_tpu._private.worker import require_core
    from ray_tpu.cluster_utils import Cluster

    ray_tpu.shutdown()
    cluster = Cluster()
    try:
        small = cluster.add_node(num_cpus=1)
        ray_tpu.init(address=cluster.address)
        cluster.add_node(num_cpus=8)
        cluster.wait_for_nodes()
        core = require_core()

        async def ask():
            conn = await _rpc.connect(*small.nodelet_addr,
                                      name="test->small-nodelet")
            try:
                # CPU:4 fits the big node (so a spill target EXISTS) but the
                # request is already at its hop cap -> must bounce, since
                # this node can never run it
                return await conn.call(
                    "request_worker_lease",
                    {"resources": {"CPU": 4.0},
                     "strategy": {"kind": "hybrid"}, "bundle": None,
                     "spillback_count": 99, "token": "t-chain-end"},
                    timeout=30)
            finally:
                await conn.close()

        resp = core.io.run(ask())
        assert resp["type"] == "retry", resp
    finally:
        ray_tpu.shutdown()
        cluster.shutdown()


def test_node_label_scheduling_strategy():
    """NodeLabelSchedulingStrategy (reference: util/scheduling_strategies +
    node_label_scheduling_policy): hard selectors pin tasks to matching
    nodes; soft selectors prefer them and never block; an unmatched hard
    selector keeps the task pending rather than landing on a wrong node."""
    from ray_tpu.cluster_utils import Cluster
    from ray_tpu.util.scheduling_strategies import NodeLabelSchedulingStrategy

    ray_tpu.shutdown()
    cluster = Cluster()
    try:
        cluster.add_node(num_cpus=2, labels={"zone": "us-a", "tier": "cpu"})
        ray_tpu.init(address=cluster.address)
        cluster.add_node(num_cpus=2, labels={"zone": "us-b", "tier": "tpu"})
        cluster.wait_for_nodes()

        @ray_tpu.remote
        def where():
            return ray_tpu.get_runtime_context().node_id.hex()

        n1 = cluster.head_node.node_id_hex
        n2 = cluster.worker_nodes[0].node_id_hex

        # hard selector routes to the tpu-tier node (node2)
        hard = NodeLabelSchedulingStrategy(hard={"tier": "tpu"})
        outs = ray_tpu.get(
            [where.options(scheduling_strategy=hard).remote()
             for _ in range(4)], timeout=120)
        assert all(o == n2 for o in outs), (outs, n2)

        # soft selector: the labelled node wins while it has capacity.  One
        # task at a time, so node1 (which has run nothing yet) always has a
        # free CPU when the lease is placed; a burst of four may drain
        # through node2's warm workers before node1 has booted one, which
        # is the scheduler conserving work, not breaking the preference.
        soft = NodeLabelSchedulingStrategy(soft={"zone": "us-a"})
        outs = [ray_tpu.get(where.options(scheduling_strategy=soft).remote(),
                            timeout=120) for _ in range(4)]
        assert all(o == n1 for o in outs), (outs, n1)

        # a soft selector no node matches still runs: it ranks, never filters
        nowhere = NodeLabelSchedulingStrategy(soft={"zone": "nowhere"})
        out = ray_tpu.get(where.options(scheduling_strategy=nowhere).remote(),
                          timeout=120)
        assert out in (n1, n2), (out, n1, n2)

        # unmatched hard selector: stays pending, never lands anywhere
        none = NodeLabelSchedulingStrategy(hard={"tier": "gpu"})
        ref = where.options(scheduling_strategy=none).remote()
        ready, not_ready = ray_tpu.wait([ref], timeout=4)
        assert not ready and not_ready, "task ran despite no labeled node"
    finally:
        ray_tpu.shutdown()
        cluster.shutdown()

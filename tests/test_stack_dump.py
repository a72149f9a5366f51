"""Live-stack introspection + hang watchdog (ISSUE 3: `ray_tpu stack`,
`state.get_stacks`, nodelet hang watchdog, `summarize_hangs`).

Mirrors the reference's live-debugging surface (`ray stack`, hanging-task
diagnosis from task events) — here the dump rides the RPC plane
(GCS -> nodelet -> per-process sys._current_frames sampler) with zero
external deps instead of py-spy.
"""

import os
import threading
import time

import ray_tpu
from ray_tpu.util import state


@ray_tpu.remote
def _multi_thread_sleep(seconds):
    inner = threading.Thread(target=time.sleep, args=(seconds,),
                             name="stacktest-inner", daemon=True)
    inner.start()
    time.sleep(seconds)
    return True


@ray_tpu.remote
def _getpid():
    return os.getpid()


@ray_tpu.remote
def _watchdog_sleep(until_path):
    """Sleeps until the test has made ``until_path`` (or for two minutes)."""
    import os

    deadline = time.monotonic() + 120.0
    while not os.path.exists(until_path) and time.monotonic() < deadline:
        time.sleep(0.1)
    return True


@ray_tpu.remote
class _AsyncSleeper:
    async def sleepy(self, seconds):
        import asyncio

        await asyncio.sleep(seconds)
        return True


def _wait_for(predicate, timeout=30.0, interval=0.3):
    deadline = time.monotonic() + timeout
    while time.monotonic() < deadline:
        out = predicate()
        if out:
            return out
        time.sleep(interval)
    return None


def _worker_running(dumps, task_id):
    """The worker payload currently executing ``task_id``, if any."""
    for node in dumps:
        for w in node.get("workers", []):
            if any(t["task_id"] == task_id
                   for t in w.get("running_tasks", [])):
                return w
    return None


def test_dump_stacks_idle_is_well_formed(ray_start_regular):
    """A process with nothing to run dumps an empty-but-well-formed payload:
    node id, worker list, per-worker thread stacks, no task attribution.
    Idle by construction: the driver, which never runs a task, and the
    worker that has just returned one.  What other suites left running on
    the shared runtime is held to form only, and to attributing threads to
    nothing but its own running tasks."""
    pid = ray_tpu.get(_getpid.remote())

    def returned_worker_is_idle():
        dumps = state.get_stacks()
        for node in dumps:
            for w in node.get("workers", []):
                if w["pid"] == pid and not w["running_tasks"]:
                    return dumps
        return None

    dumps = _wait_for(returned_worker_is_idle)
    assert dumps is not None, "the worker that returned its task never idled"
    assert any(node.get("node_id") for node in dumps)
    idle_pids = set()
    for node in dumps:
        assert "workers" in node
        for w in node["workers"]:
            assert isinstance(w["threads"], list)
            running = {t["task_id"] for t in w["running_tasks"]}
            if not running:
                idle_pids.add(w["pid"])
            for t in w["threads"]:
                assert t["stack"]  # every live thread has a stack
                assert t["task_id"] is None or t["task_id"] in running
    assert {pid, os.getpid()} <= idle_pids, (pid, os.getpid(), idle_pids)


def test_multithreaded_task_stack_has_all_threads_and_task_id(
        ray_start_regular):
    ref = _multi_thread_sleep.remote(12.0)
    tid = ref.task_id().hex()

    def running_with_inner_thread():
        # a dump can catch the task tracked-but-not-yet-in-its-body (the
        # inner thread spawns on the first body line); poll until BOTH the
        # running task and its spawned thread are visible together
        w = _worker_running(state.get_stacks(task_id=tid), tid)
        if w is None:
            return None
        if "stacktest-inner" not in [t["thread_name"] for t in w["threads"]]:
            return None
        return w

    w = _wait_for(running_with_inner_thread)
    assert w is not None, \
        "running task with its inner thread never appeared in a stack dump"
    names = [t["thread_name"] for t in w["threads"]]
    owned = [t for t in w["threads"] if t["task_id"] == tid]
    assert owned, f"no thread attributed to task {tid}: {names}"
    assert owned[0]["task_name"] == "_multi_thread_sleep"
    assert "sleep" in owned[0]["stack"]
    assert ray_tpu.get(ref) is True


def test_async_actor_stack_lists_owning_task(ray_start_regular):
    a = _AsyncSleeper.remote()
    ref = a.sleepy.remote(12.0)
    tid = ref.task_id().hex()
    w = _wait_for(lambda: _worker_running(state.get_stacks(task_id=tid), tid))
    assert w is not None, "async actor task never appeared in a stack dump"
    running = [t for t in w["running_tasks"] if t["task_id"] == tid]
    assert running and running[0]["name"] == "sleepy"
    # async tasks share the IO loop thread: no per-thread attribution, but
    # the dump still carries every thread of the actor process
    assert w["threads"]
    assert ray_tpu.get(ref) is True
    ray_tpu.kill(a)


def test_watchdog_flags_sleeping_task_then_clears(ray_start_regular,
                                                  tmp_path):
    """A task sleeping past RAY_TPU_HANG_THRESHOLD_S shows up in
    summarize_hangs with the one-shot stack attached, and drops out once it
    finishes (ISSUE 3 acceptance)."""
    # live-tunable via the nodelet's test-hook env RPC: the watchdog reads
    # these keys per tick, not through RayConfig's first-read cache
    state._nodelet_call(None, "set_env",
                        {"key": "RAY_TPU_HANG_THRESHOLD_S", "value": "1"})
    state._nodelet_call(None, "set_env",
                        {"key": "RAY_TPU_HANG_WATCHDOG_INTERVAL_S",
                         "value": "0.5"})
    # the task sleeps until the watchdog has been seen to flag it: what is
    # asserted is the order (flagged while it runs, cleared once it is done),
    # however long the host takes over either
    done = tmp_path / "seen"
    started = time.monotonic()
    try:
        ref = _watchdog_sleep.remote(str(done))
        tid = ref.task_id().hex()
        hang = _wait_for(
            lambda: next((h for h in state.summarize_hangs()
                          if h["task_id"] == tid), None),
            timeout=100.0)
        print(f"flagged after {time.monotonic() - started:.1f} s")
        assert hang is not None, "watchdog never flagged the sleeping task"
        assert hang["name"] == "_watchdog_sleep"
        assert hang["elapsed_s"] > 1.0
        assert hang["stack"] and "sleep" in hang["stack"]
        # the gauge rides the node's ordinary scrape
        text = state._nodelet_call(None, "get_metrics_text")
        assert "ray_tpu_suspected_hung_tasks" in text
        done.touch()
        assert ray_tpu.get(ref) is True
        cleared = _wait_for(
            lambda: (all(h["task_id"] != tid
                         for h in state.summarize_hangs()) or None),
            timeout=60.0)
        print(f"cleared after {time.monotonic() - started:.1f} s")
        assert cleared, "finished task is still listed as hung"
    finally:
        done.touch()
        state._nodelet_call(None, "set_env",
                            {"key": "RAY_TPU_HANG_THRESHOLD_S", "value": ""})
        state._nodelet_call(None, "set_env",
                            {"key": "RAY_TPU_HANG_WATCHDOG_INTERVAL_S",
                             "value": ""})

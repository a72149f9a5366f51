"""Shared test config.

Force JAX onto a virtual 8-device CPU platform (multi-chip sharding is tested on a
host-device mesh; real TPU runs are `perfbench/run.py` and `chip_smoke.py`
through `chiprun`, not pytest) — mirrors how the reference tests TPU
scheduling on CPU by faking topology (reference:
python/ray/tests/accelerators/test_tpu.py).
"""

import os
import sys

_TESTS_DIR = os.path.dirname(os.path.abspath(__file__))
_REPO_ROOT = os.path.dirname(_TESTS_DIR)
sys.path.insert(0, _REPO_ROOT)

# Worker processes must be able to import test modules: cloudpickle serializes
# module-level test functions BY REFERENCE (only __main__ goes by value), so a
# task/actor defined in tests/test_x.py deserializes on a worker as
# `import test_x`.  Spawned nodes/workers inherit this env.
os.environ["PYTHONPATH"] = os.pathsep.join(
    [_REPO_ROOT, _TESTS_DIR, os.environ.get("PYTHONPATH", "")]).rstrip(os.pathsep)

# fault-injection RPCs (nodelet set_env) are production-disabled; tests and
# every node they spawn get them via this inherited env override
os.environ["RAY_TPU_TEST_HOOKS"] = "1"

# Hang forensics: RAY_TPU_TEST_HANG_DUMP=<seconds> dumps every thread's
# stack and exits if the suite stalls that long with no progress (the
# watchdog is re-armed per test in the autouse fixture below).
_HANG_DUMP_S = float(os.environ.get("RAY_TPU_TEST_HANG_DUMP", "0") or 0)
_HANG_DUMP_FILE = None
if _HANG_DUMP_S > 0:
    import faulthandler

    # a REAL file: pytest's capture machinery swallows sys.stderr, so a
    # default-armed dump would vanish with the dying process
    _HANG_DUMP_FILE = open(
        os.environ.get("RAY_TPU_TEST_HANG_DUMP_FILE",
                       "/tmp/ray_tpu_hang_dump.txt"), "a")
    # startup (imports + collection + first runtime spin-up) gets a wider
    # budget than a single test; the per-test fixture re-arms with
    # _HANG_DUMP_S once tests start
    faulthandler.dump_traceback_later(max(_HANG_DUMP_S * 3, 300.0),
                                      exit=True, file=_HANG_DUMP_FILE)

# FORCE cpu: tests must never touch the real chip — the virtual 8-device CPU
# mesh is the test substrate, with Pallas kernels interpreted because this
# call asks for it (see _private/platform.py).
from ray_tpu._private.platform import force_cpu_platform  # noqa: E402

force_cpu_platform(8)

import pytest  # noqa: E402

SHARED_CPUS = 8.0


def ensure_shared_runtime():
    """Idempotently (re)start the shared single-node runtime.

    Per-test clusters are too slow on a 1-CPU box (gcs+nodelet+workers at ~2s
    python startup each), so tests share one runtime like the reference's
    shared ray_start fixtures (python/ray/tests/conftest.py); tests that tear
    clusters down (ray_start_isolated / ray_start_cluster) leave the runtime
    stopped and the next shared test restarts it here.
    """
    import ray_tpu

    if not ray_tpu.is_initialized():
        ray_tpu.init(num_cpus=SHARED_CPUS, object_store_memory=256 * 1024**2)
    return ray_tpu


@pytest.fixture
def ray_start_regular():
    """A view on the shared runtime (reference: conftest.py:419 shared mode).
    Tests may create actors/tasks freely; they must not assume exclusive
    cluster resources."""
    yield ensure_shared_runtime()


@pytest.fixture
def ray_start_isolated():
    """A fresh runtime for tests that mutate cluster state (node death etc.)."""
    import ray_tpu

    ray_tpu.shutdown()
    ray_tpu.init(num_cpus=4, object_store_memory=128 * 1024**2)
    yield ray_tpu
    ray_tpu.shutdown()


@pytest.fixture
def ray_start_cluster():
    """Multi-node in-process cluster factory (reference: conftest.py:500 +
    cluster_utils.Cluster).  The test is responsible for init(address=...)."""
    import ray_tpu
    from ray_tpu.cluster_utils import Cluster

    ray_tpu.shutdown()
    cluster = Cluster()
    yield cluster
    ray_tpu.shutdown()
    cluster.shutdown()


def pytest_configure(config):
    config.addinivalue_line(
        "markers",
        "slow: heavy end-to-end cases excluded from the tier-1 run "
        "(-m 'not slow')")


def pytest_sessionfinish(session, exitstatus):
    try:
        import ray_tpu

        ray_tpu.shutdown()
    except Exception:
        pass


@pytest.fixture
def flash_names_off(monkeypatch):
    """The flash forward rules without the names on their output and
    logsumexp (``ops.attention.FLASH_RESIDUALS``, PR 38): the traced and
    lowered programs that older tests pin by hash were taken before the
    names, and with them off they are still those programs; what the names
    do is ``tests/test_flash_remat.py``'s."""
    from ray_tpu.ops import attention

    monkeypatch.setattr(attention, "checkpoint_name", lambda x, name: x)


@pytest.fixture(autouse=True)
def _rearm_hang_watchdog():
    """Re-arm the stall watchdog at every test boundary so the dump fires
    only when ONE test exceeds the budget, not cumulative runtime."""
    if _HANG_DUMP_S > 0:
        import faulthandler

        faulthandler.dump_traceback_later(_HANG_DUMP_S, exit=True,
                                         file=_HANG_DUMP_FILE)
    yield

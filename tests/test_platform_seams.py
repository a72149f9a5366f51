"""The device-selection seams: nothing between a request for the TPU and the
chip may quietly answer with the CPU.

Each case that touches jax's platform or cache configuration runs in a
subprocess — the test process itself is pinned to the forced-CPU substrate.
"""

from __future__ import annotations

import os
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _python(*argv: str, **env_overrides) -> subprocess.Popen:
    env = {k: v for k, v in os.environ.items()
           if k != "JAX_COMPILATION_CACHE_DIR"}
    env["JAX_PLATFORMS"] = "cpu"  # what this sandbox exports
    env["PYTHONPATH"] = REPO
    env.update(env_overrides)
    return subprocess.Popen([sys.executable, *argv], env=env, cwd=REPO,
                            stdout=subprocess.PIPE, stderr=subprocess.PIPE,
                            text=True)


_ASK_FOR_TPU = (
    "from ray_tpu.train.jax_config import _setup_jax_distributed\n"
    "print('CAME_UP_ON', _setup_jax_distributed(None, 1, 0, 'tpu', 1))")
_ENABLE_CACHE = (
    "import jax\n"
    "from ray_tpu._private.platform import enable_compile_cache\n"
    "print('DIR', enable_compile_cache())\n"
    "print('CFG', jax.config.jax_compilation_cache_dir)")


@pytest.fixture(scope="module")
def children(tmp_path_factory):
    """Every child this module needs, started together (each spends seconds
    importing jax or failing to find a TPU): name -> (returncode, out, err)."""
    outside = str(tmp_path_factory.mktemp("cache"))
    procs = {
        "ask_for_tpu": _python("-c", _ASK_FOR_TPU),
        "cache_a": _python("-c", _ENABLE_CACHE),
        "cache_b": _python("-c", _ENABLE_CACHE),
        "cache_env": _python("-c", _ENABLE_CACHE,
                             JAX_COMPILATION_CACHE_DIR=outside),
        "chip_smoke": _python("chip_smoke.py"),
    }
    done = {"outside": outside}
    for name, proc in procs.items():
        out, err = proc.communicate(timeout=120)
        done[name] = (proc.returncode, out, err)
    return done


def test_worker_asked_for_tpu_raises_on_a_chipless_machine(children):
    """An inherited JAX_PLATFORMS=cpu used to turn ``platform="tpu"`` into a
    CPU run under the name "tpu"; now the worker pins the platform itself and
    the missing chip is an error."""
    rc, out, err = children["ask_for_tpu"]
    assert rc != 0, out
    assert "CAME_UP_ON" not in out
    assert "Unable to initialize backend 'tpu'" in err, err[-2000:]


def test_tpu_scaling_config_implies_the_tpu_platform(monkeypatch):
    import ray_tpu
    from ray_tpu.train import JaxConfig, ScalingConfig
    from ray_tpu.train.jax_config import _JaxBackend, _setup_jax_distributed

    asked = []

    class _Worker:
        class execute:
            @staticmethod
            def remote(fn, *args):
                assert fn is _setup_jax_distributed
                asked.append(args[3])  # the platform argument
                return {"global_device_count": 1, "platform": args[3]}

    class _Group:
        workers = [_Worker()]

        def __init__(self, scaling):
            self.resources_per_worker = scaling._worker_resources

        def __len__(self):
            return 1

    monkeypatch.setattr(ray_tpu, "get", lambda refs, timeout=None: refs)
    for scaling, config, want in [
            (ScalingConfig(use_tpu=True), JaxConfig(), "tpu"),
            (ScalingConfig(tpus_per_worker=4), JaxConfig(), "tpu"),
            (ScalingConfig(), JaxConfig(), "cpu"),
            (ScalingConfig(use_tpu=True), JaxConfig(platform="cpu"), "cpu")]:
        _JaxBackend().on_start(_Group(scaling), config)
        assert asked.pop() == want, (scaling, config)


def test_compile_cache_dir_is_fixed_per_checkout_and_yields_to_the_env(children):
    outs = {}
    for name in ("cache_a", "cache_b", "cache_env"):
        rc, out, err = children[name]
        assert rc == 0, err[-2000:]
        outs[name] = dict(line.split(" ", 1) for line in out.splitlines())
    default = os.path.join(REPO, ".jax_cache")
    # no variable: <checkout>/.jax_cache, the same in every process
    assert outs["cache_a"] == outs["cache_b"] == {"DIR": default, "CFG": default}
    # variable set: JAX's own reading of it stands, nothing else is set
    outside = children["outside"]
    assert outs["cache_env"] == {"DIR": outside, "CFG": outside}


def test_chip_smoke_fails_without_a_chip_and_takes_no_step(children):
    rc, out, err = children["chip_smoke"]
    assert rc != 0
    assert '"ok"' not in out and "loss" not in out, out
    assert "no TPU chip" in err, err[-2000:]

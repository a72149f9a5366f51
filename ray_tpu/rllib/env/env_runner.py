"""EnvRunner: CPU actor that samples fixed-length rollout fragments.

Counterpart of the reference's SingleAgentEnvRunner (reference:
rllib/env/single_agent_env_runner.py:131 sample; EnvRunnerGroup
rllib/env/env_runner_group.py:71).  Each runner owns K vectorized envs and a
copy of the policy params; ``sample()`` returns time-major arrays
(T, K, ...) plus the value bootstrap for each fragment tail, ready for the
Learner's GAE scan — no per-episode postprocessing on the driver
(the reference's GAE-on-learner new-stack layout).

Podracer extensions (rllib/podracer/):

- ``run_stream(n)`` is the continuous sample loop: a
  ``num_returns="streaming"`` generator that seals each fragment into
  plasma as it is produced, polling the job's weight mailbox between
  fragments so no weight pytree ever rides a task argument;
- with an ``inference`` pool handle the runner is a *Sebulba* actor: it
  performs ZERO local forward passes — every action, logp and bootstrap
  value comes from the pool's batched forwards, and fragments carry the
  policy version the pool stamped on the responses.
"""

from __future__ import annotations

import collections
from typing import Dict, Optional

import numpy as np

from ray_tpu.rllib.core.rl_module import DiscretePolicyModule
from ray_tpu.rllib.env import make_vector_env


class EnvRunner:
    def __init__(self, env_name: str, num_envs: int, rollout_length: int,
                 module_spec: Dict, seed: int = 0, job: str = "",
                 runner_idx: int = 0, inference=None):
        # Rollouts are a HOST program: policy inference here is tiny and
        # latency-bound, so pin this process to the CPU backend.  Without
        # this, a TPU host's default backend is the chip: every per-step
        # dispatch would cross to it, and a rollout process would take the
        # chip from the learner that needs it.
        # The Learner is the device program, not the runner (SURVEY §3.5).
        # Exception: if this process already initialized a jax backend (local
        # debug mode sharing the driver with a learner), re-pinning is
        # impossible — keep the existing backend and say so.
        import sys

        if "jax" in sys.modules:
            import jax._src.xla_bridge as _xb

            initialized = _xb.backends_are_initialized()
        else:
            initialized = False
        if initialized:
            import logging

            logging.getLogger(__name__).warning(
                "EnvRunner created after the jax backend initialized; "
                "rollout inference shares that backend (use actor "
                "env-runners for the CPU-rollout/device-learner split)")
        else:
            from ray_tpu._private.platform import force_cpu_platform

            force_cpu_platform(1)
        import jax

        self.env = make_vector_env(env_name, num_envs, seed=seed)
        self.num_envs = num_envs
        self.rollout_length = rollout_length
        self.module = DiscretePolicyModule(**module_spec)
        self.params = None
        self._key = jax.random.PRNGKey(seed)
        self.obs = self.env.reset()
        self.job = job
        self.runner_idx = runner_idx
        self._pool = inference
        self._version = 0
        self._local_forwards = 0  # Sebulba contract: stays 0 with a pool
        self._mailbox = None
        if job and inference is None:
            from ray_tpu.rllib.podracer.weights import WeightMailbox

            self._mailbox = WeightMailbox(job)
        # episode-return bookkeeping (reference: metrics on the EnvRunner)
        self._ep_return = np.zeros(num_envs, np.float32)
        self._recent_returns: collections.deque = collections.deque(maxlen=100)
        self._lifetime_steps = 0

        self._explore = jax.jit(self.module.forward_exploration)
        self._value = jax.jit(self.module.value)

    def set_weights(self, params, version: int = 0) -> None:
        self.params = params
        self._version = int(version)

    # ------------------------------------------------------------ policy
    def _poll_weights(self) -> None:
        if self._mailbox is not None:
            v, params = self._mailbox.poll()
            if params is not None:
                self.params, self._version = params, v

    def _pool_act(self, obs, sub):
        import ray_tpu

        actions, logp, values, version = ray_tpu.get(
            self._pool.act.remote(np.asarray(obs, np.float32),
                                  np.asarray(sub)), timeout=120)
        self._version = int(version)
        return actions, logp, values

    def _values_of(self, obs) -> np.ndarray:
        """Bootstrap values — pooled in Sebulba mode (the runner never
        touches the value net locally either)."""
        import jax

        if self._pool is not None:
            self._key, sub = jax.random.split(self._key)
            _, _, values = self._pool_act(obs, sub)
            return np.asarray(values)
        self._local_forwards += 1
        return np.asarray(self._value(self.params, obs))

    def _chaos_tick(self) -> None:
        from ray_tpu._private import fault_injection

        if fault_injection.ENABLED:
            action = fault_injection.hit(
                "rllib.sample", f"runner{self.runner_idx}")
            if action == "kill":
                fault_injection.kill_self()

    # ------------------------------------------------------------ sample
    def sample(self, weights=None) -> Dict[str, np.ndarray]:
        """One fragment of rollout_length steps across all K envs."""
        import jax

        self._chaos_tick()
        if weights is not None:
            self.params = weights
        elif self._mailbox is not None:
            # every fragment starts with a version check: one cheap KV
            # read; the weight payload only transfers on a version change
            self._poll_weights()
        if self._pool is None:
            assert self.params is not None, "set_weights before sample"
        T, K = self.rollout_length, self.num_envs
        out = {
            "obs": np.empty((T, K, self.env.observation_size), np.float32),
            "actions": np.empty((T, K), np.int32),
            "logp": np.empty((T, K), np.float32),
            "values": np.empty((T, K), np.float32),
            "rewards": np.empty((T, K), np.float32),
            "terminated": np.empty((T, K), bool),
            "truncated": np.empty((T, K), bool),
        }
        final_obs = np.empty((T, K, self.env.observation_size), np.float32)
        for t in range(T):
            self._key, sub = jax.random.split(self._key)
            if self._pool is not None:
                actions, logp, values = self._pool_act(self.obs, sub)
            else:
                self._local_forwards += 1
                actions, logp, values = self._explore(
                    self.params, self.obs, sub)
            actions = np.asarray(actions)
            out["obs"][t] = self.obs
            out["actions"][t] = actions
            out["logp"][t] = np.asarray(logp)
            out["values"][t] = np.asarray(values)
            next_obs, rewards, terminated, truncated, info = \
                self.env.step(actions)
            out["rewards"][t] = rewards
            out["terminated"][t] = terminated
            out["truncated"][t] = truncated
            final_obs[t] = info["final_obs"]

            self._ep_return += rewards
            for i in np.nonzero(terminated | truncated)[0]:
                self._recent_returns.append(float(self._ep_return[i]))
                self._ep_return[i] = 0.0
            self.obs = next_obs
        self._lifetime_steps += T * K
        from ray_tpu.rllib._metrics import rllib_metrics

        rllib_metrics()["env_steps"].inc(
            T * K, {"job": self.job or "default"})

        # next_values[t] = V of the TRUE successor state: values[t+1] inside
        # an episode, V(obs after the fragment) at the tail, 0 at termination,
        # V(pre-reset final obs) at truncation (time-limit bootstrapping —
        # truncation is not failure, the episode just stopped being observed).
        tail_value = self._values_of(self.obs)
        next_values = np.concatenate(
            [out["values"][1:], tail_value[None]], axis=0)
        next_values[out["terminated"]] = 0.0
        if out["truncated"].any():
            # evaluate on the full fixed (T*K, obs) shape and index after:
            # a data-dependent batch (the truncation count) would recompile
            # the jit for every distinct count
            tr = np.nonzero(out["truncated"])
            v_final = self._values_of(
                final_obs.reshape(T * K, -1)).reshape(T, K)
            next_values[tr] = v_final[tr]
        out["next_values"] = next_values.astype(np.float32)
        return out

    # ----------------------------------------------------------- streaming
    def run_stream(self, num_fragments: int):
        """Continuous sample loop (declare ``num_returns="streaming"`` at
        the call site / via method meta): each yielded fragment is sealed
        into plasma immediately, and the weight mailbox is polled between
        fragments — the driver never relaunches per fragment and never
        ships weights as arguments."""
        for _ in range(int(num_fragments)):
            batch = self.sample()  # polls the weight mailbox itself
            yield {
                "batch": batch,
                "policy_version": int(self._version),
                "runner_idx": self.runner_idx,
                "episode_return_mean": (
                    float(np.mean(self._recent_returns))
                    if self._recent_returns else float("nan")),
                "num_episodes": len(self._recent_returns),
                "lifetime_steps": self._lifetime_steps,
            }

    run_stream.__ray_method_options__ = {"num_returns": "streaming"}

    def get_metrics(self) -> Dict:
        return {
            "episode_return_mean": (float(np.mean(self._recent_returns))
                                    if self._recent_returns else float("nan")),
            "num_episodes": len(self._recent_returns),
            "num_env_steps_sampled_lifetime": self._lifetime_steps,
        }

    def get_debug(self) -> Dict:
        return {"local_forwards": self._local_forwards,
                "policy_version": self._version,
                "lifetime_steps": self._lifetime_steps}

    def ping(self) -> bool:
        return True

"""RLlib slice tests: native CartPole, GAE, PPO learning through actors.

The learning test mirrors the reference's tuned-example stop criteria
(reference: rllib/tuned_examples/ppo/cartpole_ppo.py:46-49 — eval return
>= 350 within 200k env steps), run with EnvRunner ACTORS sampling in
parallel and the jitted JaxLearner updating (BASELINE.md RL row).
"""

import numpy as np
import pytest

from ray_tpu.rllib import PPOConfig
from ray_tpu.rllib.env.cartpole import CartPoleVectorEnv


def test_cartpole_semantics():
    env = CartPoleVectorEnv(4, seed=0)
    obs = env.reset()
    assert obs.shape == (4, 4)
    assert np.all(np.abs(obs) <= 0.05)
    obs, rew, term, trunc, info = env.step(np.array([1, 0, 1, 0]))
    assert rew.tolist() == [1.0] * 4
    assert not term.any() and not trunc.any()
    # drive one env to termination with constant action
    env2 = CartPoleVectorEnv(1, seed=0)
    steps = 0
    done = False
    while not done and steps < 200:
        obs, _, term, trunc, info = env2.step(np.array([1]))
        done = bool(term[0] | trunc[0])
        steps += 1
    assert done and steps < 200, "constant push must topple the pole"
    # the pre-reset state is exposed, the live state was reset
    assert np.abs(info["final_obs"][0][2]) > CartPoleVectorEnv.THETA_THRESHOLD
    assert np.all(np.abs(env2.state[0]) <= 0.05)


def test_cartpole_truncation_at_500():
    env = CartPoleVectorEnv(1, seed=3)
    env.state[:] = 0.0  # balanced: alternate pushes keep it up for a while
    for t in range(500):
        env.state[0, 1] = 0.0
        env.state[0, 3] = 0.0
        env.state[0, 0] = 0.0
        env.state[0, 2] = 0.0
        _, _, term, trunc, _ = env.step(np.array([t % 2]))
    assert trunc.any() or env.steps[0] < 500  # truncated & auto-reset


def test_gae_from_fragments_matches_loop():
    from ray_tpu.ops.gae import gae_from_fragments

    rng = np.random.default_rng(0)
    T, K = 17, 3
    rewards = rng.standard_normal((T, K)).astype(np.float32)
    values = rng.standard_normal((T, K)).astype(np.float32)
    next_values = rng.standard_normal((T, K)).astype(np.float32)
    dones = rng.random((T, K)) < 0.2
    gamma, lam = 0.97, 0.9

    adv, targets = gae_from_fragments(rewards, values, next_values, dones,
                                      gamma, lam)
    # slow reference recurrence
    expect = np.zeros((T, K), np.float32)
    running = np.zeros(K, np.float32)
    for t in reversed(range(T)):
        delta = rewards[t] + gamma * next_values[t] - values[t]
        running = delta + gamma * lam * (1.0 - dones[t]) * running
        expect[t] = running
    np.testing.assert_allclose(np.asarray(adv), expect, rtol=1e-4, atol=1e-5)
    np.testing.assert_allclose(np.asarray(targets), expect + values,
                               rtol=1e-4, atol=1e-5)


def test_ppo_cartpole_learns_to_350_through_actors(ray_start_regular):
    """PPO reaches return >= 350 within 200k env steps with parallel actor
    env-runners (reference stop criteria: cartpole_ppo.py:46-49)."""
    config = (PPOConfig()
              .environment("CartPole-v1")
              .env_runners(num_env_runners=2, num_envs_per_env_runner=16,
                           rollout_fragment_length=64)
              .training(vf_clip_param=100.0, lr=1e-3, entropy_coeff=0.01)
              .debugging(seed=0))
    algo = config.build()
    try:
        best = -np.inf
        for _ in range(100):  # <= 204.8k env steps
            result = algo.train()
            best = max(best, result["episode_return_mean"])
            if result["episode_return_mean"] >= 350:
                break
        assert result["episode_return_mean"] >= 350, (
            f"did not reach 350 within "
            f"{result['num_env_steps_sampled_lifetime']} steps (best {best})")
        assert result["num_env_steps_sampled_lifetime"] <= 200_000
    finally:
        algo.stop()


def test_learner_group_actor_mode(ray_start_regular):
    """num_learners=1: the update runs in a Learner ACTOR, weights round-trip
    through the object store."""
    config = (PPOConfig()
              .environment("CartPole-v1")
              .env_runners(num_env_runners=0, num_envs_per_env_runner=4,
                           rollout_fragment_length=16)
              .learners(num_learners=1, platform="cpu")
              .debugging(seed=0))
    algo = config.build()
    try:
        r1 = algo.train()
        r2 = algo.train()
        assert np.isfinite(r2["learner/total_loss"])
        assert r2["num_env_steps_sampled_lifetime"] == 128
    finally:
        algo.stop()


def test_vtrace_matches_reference_loop():
    """V-trace scan vs a slow backward-loop transcription of the IMPALA
    paper's recursion (reference math: vtrace_torch.py)."""
    from ray_tpu.ops.vtrace import vtrace_from_fragments

    rng = np.random.default_rng(0)
    T, K = 19, 4
    gamma, rho_clip, c_clip = 0.97, 1.0, 1.0
    behavior_logp = rng.standard_normal((T, K)).astype(np.float32) * 0.3
    target_logp = behavior_logp + \
        rng.standard_normal((T, K)).astype(np.float32) * 0.2
    rewards = rng.standard_normal((T, K)).astype(np.float32)
    values = rng.standard_normal((T, K)).astype(np.float32)
    next_values = rng.standard_normal((T, K)).astype(np.float32)
    dones = rng.random((T, K)) < 0.15

    vs, pg_adv = vtrace_from_fragments(
        behavior_logp, target_logp, rewards, values, next_values, dones,
        gamma, rho_clip, c_clip)

    rhos = np.exp(target_logp - behavior_logp)
    rho = np.minimum(rhos, rho_clip)
    c = np.minimum(rhos, c_clip)
    not_done = 1.0 - dones.astype(np.float32)
    # backward recursion: a_t = vs_t - V_t
    a = np.zeros((T, K), np.float32)
    running = np.zeros(K, np.float32)
    for t in reversed(range(T)):
        delta = rho[t] * (rewards[t] + gamma * next_values[t] - values[t])
        running = delta + gamma * c[t] * not_done[t] * running
        a[t] = running
    vs_ref = values + a
    vs_next_ref = np.concatenate([vs_ref[1:], next_values[-1:]], axis=0)
    vs_next_ref = np.where(dones, next_values, vs_next_ref)
    pg_ref = rho * (rewards + gamma * vs_next_ref - values)

    np.testing.assert_allclose(np.asarray(vs), vs_ref, rtol=1e-4, atol=1e-5)
    np.testing.assert_allclose(np.asarray(pg_adv), pg_ref, rtol=1e-4,
                               atol=1e-5)


def test_impala_cartpole_learns_through_async_actors(ray_start_regular):
    """IMPALA (async sampling + V-trace) learns CartPole within 400k env
    steps: the best running return clears the first iterations' mean (a
    random policy, ~20) by 100, and every loss along the way is finite.
    How far it gets (350 on a quiet host) and the sampling throughput are
    printed, not asserted: how stale the fragments are, and so how fast the
    return climbs, depends on the host's load.  Pinned to the relaunch path
    (async_stream=False), which nothing else trains through; the streaming
    default is covered in test_podracer.py."""
    from ray_tpu.rllib import IMPALAConfig

    config = (IMPALAConfig()
              .environment("CartPole-v1")
              .env_runners(num_env_runners=2, num_envs_per_env_runner=16,
                           rollout_fragment_length=64)
              .training(lr=7e-4, entropy_coeff=0.01)
              .podracer(async_stream=False)
              .debugging(seed=0))
    algo = config.build()
    try:
        returns = []
        result = None
        for _ in range(400):
            result = algo.train()
            returns.append(result["episode_return_mean"])
            for k in ("policy_loss", "vf_loss", "total_loss"):
                assert np.isfinite(result[f"learner/{k}"]), (k, result)
            if max(returns) >= 350:
                break
            if result["num_env_steps_sampled_lifetime"] > 390_000:
                break
        first, best = float(np.mean(returns[:5])), max(returns)
        print(f"IMPALA: best return {best:.0f} (first iterations {first:.0f})"
              f", {result['env_steps_per_s']:.0f} env steps/s, "
              f"{result['num_env_steps_sampled_lifetime']} steps total")
        assert best >= first + 100, (
            f"return did not improve within "
            f"{result['num_env_steps_sampled_lifetime']} steps: "
            f"first iterations {first}, best {best}")
        assert result["num_env_steps_sampled_lifetime"] <= 400_000
    finally:
        algo.stop()


def test_dqn_replay_buffer_and_nstep_semantics():
    """Replay ring wraps correctly; n-step windows carry their own
    discount and flush at episode ends with done=terminated only."""
    from ray_tpu.rllib.algorithms.dqn import QEnvRunner, ReplayBuffer

    buf = ReplayBuffer(capacity=8, observation_size=2, seed=0)
    for i in range(12):  # wraps past capacity
        buf.add_batch(np.full((1, 2), i, np.float32), [i], [float(i)],
                      np.full((1, 2), i + 1, np.float32), [0.9], [0.0])
    assert buf.size == 8
    idx = buf.sample_indices(2, 4)
    got = buf.gather(idx)
    assert got["obs"].shape == (2, 4, 2)
    # surviving entries are the last 8 writes
    assert set(np.unique(got["actions"])) <= set(range(4, 12))

    import jax

    runner = QEnvRunner("CartPole-v1", num_envs=2, rollout_length=40,
                        module_spec={"observation_size": 4, "num_actions": 2},
                        seed=0, n_step=3, gamma=0.9)
    runner.params = runner.module.init(jax.random.PRNGKey(0))
    batch = runner.sample(epsilon=1.0)
    # n-step discounts are gamma^len for len in 1..3
    uniq = np.unique(batch["discounts"])
    allowed = np.array([0.9, 0.81, 0.729], np.float32)
    assert all(np.abs(allowed - u).min() < 1e-5 for u in uniq), uniq
    # with a 40-step fragment nothing truncates, so every episode end is
    # a termination: mid-episode emissions must be FULL windows (gamma^3);
    # short windows may only appear in terminal flushes
    short = np.abs(batch["discounts"] - 0.9 ** 3) > 1e-5
    assert (batch["dones"][short] == 1.0).all(), \
        "short n-step window emitted mid-episode"
    assert short.any(), "terminal flushes should emit short windows"


def test_dqn_cartpole_learns_to_350(ray_start_regular):
    """DQN (replay buffer + double/dueling Q + n-step + target net) reaches
    return >= 350 on CartPole (reference stop criteria:
    rllib/tuned_examples/dqn/cartpole_dqn.py)."""
    from ray_tpu.rllib import DQNConfig

    cfg = (DQNConfig().environment("CartPole-v1")
           .env_runners(num_env_runners=0)
           .learners(platform="cpu")
           .debugging(seed=1))
    algo = cfg.build()
    best = 0.0
    try:
        for _ in range(5000):  # <= 640k env steps
            result = algo.train()
            ret = result["episode_return_mean"]
            if np.isfinite(ret):
                best = max(best, ret)
            if ret >= 350:
                break
        assert best >= 350, (
            f"DQN did not reach 350 within "
            f"{result['num_env_steps_sampled_lifetime']} steps (best {best})")
        assert result["replay_buffer_size"] > 0
    finally:
        algo.stop()


def test_multi_agent_two_policies_e2e(ray_start_regular):
    """Two agents mapped to two distinct policies learn a shared-fate env
    end-to-end (reference: multi_agent_env.py + per-module updates)."""
    from ray_tpu.rllib import PPOConfig

    cfg = (PPOConfig().environment("MultiCartPole")
           .env_runners(num_env_runners=0, num_envs_per_env_runner=8,
                        rollout_fragment_length=64)
           .learners(platform="cpu")
           .multi_agent(
               policies=["left", "right"],
               policy_mapping_fn=lambda aid: "left" if aid == "agent_0"
               else "right")
           .debugging(seed=0))
    algo = cfg.build()
    try:
        last = None
        for _ in range(120):
            last = algo.train()
            if last["episode_return_mean"] >= 100:
                break
        # both policies trained, and the shared-fate return improved well
        # beyond the random-policy ~20
        assert last["episode_return_mean"] >= 100
        assert any(k.startswith("learner/left/") for k in last)
        assert any(k.startswith("learner/right/") for k in last)
    finally:
        algo.stop()


def test_multi_agent_validation():
    from ray_tpu.rllib import PPOConfig

    cfg = (PPOConfig().environment("MultiCartPole")
           .learners(platform="cpu")
           .multi_agent(policies=["only"],
                        policy_mapping_fn=lambda aid: "mystery"))
    with pytest.raises(ValueError, match="unknown policies"):
        cfg.build()


def test_multi_agent_unmapped_policy_rejected():
    from ray_tpu.rllib import PPOConfig

    cfg = (PPOConfig().environment("MultiCartPole")
           .learners(platform="cpu")
           .multi_agent(policies=["shared", "ghost"],
                        policy_mapping_fn=lambda aid: "shared"))
    with pytest.raises(ValueError, match="mapped to no"):
        cfg.build()


def test_offline_bc_and_marwil_learn_from_dataset(ray_start_regular, tmp_path):
    """Offline RL (reference: rllib/offline + marwil/bc): record a heuristic
    dataset through ray_tpu.data, train BC and MARWIL from it, and verify
    the cloned policy reaches the behavior policy's return level."""
    from ray_tpu.rllib import BCConfig, MARWILConfig
    from ray_tpu.rllib.offline import record_dataset

    path = str(tmp_path / "cartpole-offline")
    stats = record_dataset(path, "CartPole-v1", n_episodes=30, seed=3)
    assert stats["steps"] > 300
    behavior_return = stats["mean_return"]

    cfg = (BCConfig().environment("CartPole-v1")
           .offline_data(input_path=path)
           .learners(platform="cpu").debugging(seed=1)
           .training(train_batch_size=1024, minibatch_size=128, lr=1e-3))
    algo = cfg.build()
    for _ in range(40):
        out = algo.train()
    assert out["policy_loss"] == out["policy_loss"]  # finite
    ev = algo.evaluate(n_episodes=5)
    # the clone should roughly match the behavior policy (within 40%)
    assert ev["episode_return_mean"] >= 0.6 * behavior_return, (
        ev, behavior_return)

    mcfg = (MARWILConfig().environment("CartPole-v1")
            .offline_data(input_path=path)
            .learners(platform="cpu").debugging(seed=1)
            .training(train_batch_size=1024, minibatch_size=128, lr=1e-3,
                      beta=1.0))
    malgo = mcfg.build()
    for _ in range(150):   # the advantage weights need the value head to
        mout = malgo.train()  # fit first (converges ~it 120 on this data)
    assert mout["vf_loss"] < 10_000  # value head actually fit something
    mev = malgo.evaluate(n_episodes=5)
    assert mev["episode_return_mean"] >= 0.6 * behavior_return, (
        mev, behavior_return)


def test_pendulum_env_semantics():
    """Native Pendulum matches Gymnasium-v1 constants: reward bounds,
    truncation at 200, velocity clamp."""
    import numpy as np

    from ray_tpu.rllib.env import make_vector_env

    env = make_vector_env("Pendulum-v1", 4, seed=0)
    obs = env.reset()
    assert obs.shape == (4, 3)
    # cos^2 + sin^2 = 1
    np.testing.assert_allclose(obs[:, 0] ** 2 + obs[:, 1] ** 2, 1.0,
                               atol=1e-5)
    for t in range(200):
        obs, r, term, trunc, info = env.step(np.zeros(4, np.float32))
        assert (r <= 0).all() and (r >= -17).all()
        assert not term.any()
    assert trunc.all(), "no truncation at 200 steps"
    assert np.abs(obs[:, 2]).max() <= env.MAX_SPEED + 1e-5


@pytest.mark.slow
def test_sac_pendulum_learns(ray_start_regular):
    """SAC (reference: rllib/algorithms/sac) learns Pendulum swing-up:
    greedy eval return well above the random-policy floor (~-1200);
    observed ~-120 at 45 iters with the 1:1 update ratio."""
    from ray_tpu.rllib import SACConfig

    cfg = (SACConfig().environment("Pendulum-v1")
           .learners(platform="cpu").debugging(seed=0))
    algo = cfg.build()
    for _ in range(45):
        out = algo.train()
    assert out["steps_sampled"] >= 20_000
    ev = algo.evaluate(n_episodes=5)
    assert ev["episode_return_mean"] >= -400.0, (ev, out)
    # the temperature auto-tuned DOWN from its 1.0 init as the policy
    # sharpened
    assert out["alpha"] < 0.9

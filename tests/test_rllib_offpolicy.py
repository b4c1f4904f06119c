"""SAC (continuous control) and MARWIL/BC (offline) algorithms.

Reference analog: ``rllib/algorithms/sac/tests`` and
``rllib/algorithms/marwil|bc/tests`` — short learning/improvement runs on
toy problems plus checkpoint roundtrips.
"""
import numpy as np
import pytest

from ray_tpu.rllib import BCConfig, MARWILConfig, SACConfig


class TargetReachEnv:
    """1-step continuous env: reward = -(a - 0.5)^2 per dim. The optimal
    squashed-gaussian policy concentrates at a=0.5, return -> 0."""

    class _Space:
        def __init__(self, low, high, shape):
            self.low = np.full(shape, low, np.float32)
            self.high = np.full(shape, high, np.float32)
            self.shape = shape

    def __init__(self):
        self.observation_space = self._Space(-1, 1, (3,))
        self.action_space = self._Space(-1, 1, (1,))
        self._rng = np.random.RandomState(0)

    def reset(self, seed=None):
        if seed is not None:
            self._rng = np.random.RandomState(seed)
        return np.zeros(3, np.float32), {}

    def step(self, action):
        a = np.asarray(action, np.float32).ravel()
        reward = -float(np.sum((a - 0.5) ** 2))
        return np.zeros(3, np.float32), reward, True, False, {}

    def close(self):
        pass


def _sac_config():
    return (
        SACConfig()
        .environment(env_creator=TargetReachEnv)
        .env_runners(num_env_runners=2, num_envs_per_env_runner=4,
                     rollout_fragment_length=32)
        .debugging(seed=0)
        .training(lr=3e-3)
    )


def test_sac_learns_target(rl_cluster):
    cfg = _sac_config()
    cfg.min_replay_size = 200
    cfg.updates_per_step = 32
    algo = cfg.build_algo()
    try:
        first, last = None, None
        for _ in range(20):
            r = algo.train()
            if first is None and np.isfinite(r["episode_return_mean"]):
                first = r["episode_return_mean"]
            last = r["episode_return_mean"]
        # optimal return is 0; random tanh actions average about -0.58
        assert last > -0.25, f"SAC did not improve: first={first} last={last}"
        assert "alpha" in r and r["alpha"] > 0
    finally:
        algo.stop()


class WideBoundsEnv(TargetReachEnv):
    """Bounds [-2, 2], optimum at a=1.5 — unreachable unless the runner
    rescales tanh actions to the env's action space."""

    def __init__(self):
        super().__init__()
        self.action_space = self._Space(-2, 2, (1,))

    def step(self, action):
        a = np.asarray(action, np.float32).ravel()
        reward = -float(np.sum((a - 1.5) ** 2))
        return np.zeros(3, np.float32), reward, True, False, {}


def test_sac_rescales_actions_to_env_bounds(rl_cluster):
    cfg = (
        SACConfig()
        .environment(env_creator=WideBoundsEnv)
        .env_runners(num_env_runners=2, num_envs_per_env_runner=4,
                     rollout_fragment_length=32)
        .debugging(seed=0)
        .training(lr=3e-3)
    )
    cfg.min_replay_size = 200
    cfg.updates_per_step = 32
    algo = cfg.build_algo()
    try:
        last = None
        for _ in range(20):
            last = algo.train()["episode_return_mean"]
        # without rescaling the best reachable return is -(1.5-1)^2 = -0.25
        assert last > -0.2, f"actions not rescaled to env bounds: {last}"
    finally:
        algo.stop()


def test_sac_rejects_discrete_env(rl_cluster):
    with pytest.raises(ValueError, match="continuous"):
        SACConfig().environment("CartPole-v1").build_algo()


def test_sac_checkpoint_roundtrip(rl_cluster, tmp_path):
    import jax

    cfg = _sac_config()
    cfg.min_replay_size = 100
    cfg.updates_per_step = 4
    algo = cfg.build_algo()
    try:
        for _ in range(3):
            algo.train()
        path = algo.save(str(tmp_path / "sac_ckpt"))
        w0 = algo.get_weights()
        algo2 = cfg.build_algo()
        try:
            algo2.restore(path)
            w1 = algo2.get_weights()
            for a, b in zip(jax.tree.leaves(w0), jax.tree.leaves(w1)):
                np.testing.assert_array_equal(a, b)
            assert algo2.iteration == algo.iteration
        finally:
            algo2.stop()
    finally:
        algo.stop()


# ----------------------------------------------------------------- offline


def _cartpole_expert_episodes(n_episodes=30, seed=0):
    """Scripted CartPole expert (push toward the pole's fall direction)."""
    import gymnasium as gym

    env = gym.make("CartPole-v1")
    episodes = []
    for ep in range(n_episodes):
        obs, _ = env.reset(seed=seed * 1000 + ep)
        ep_obs, ep_act, ep_rew = [], [], []
        done = False
        t = 0
        while not done and t < 200:
            angle, ang_vel = obs[2], obs[3]
            action = 1 if (angle + 0.5 * ang_vel) > 0 else 0
            ep_obs.append(np.asarray(obs, np.float32))
            ep_act.append(action)
            nobs, rew, term, trunc, _ = env.step(action)
            ep_rew.append(float(rew))
            obs = nobs
            done = term or trunc
            t += 1
        episodes.append({
            "obs": np.stack(ep_obs),
            "actions": np.asarray(ep_act, np.int64),
            "rewards": np.asarray(ep_rew, np.float32),
        })
    env.close()
    return episodes


def test_bc_clones_cartpole_expert(rl_cluster):
    episodes = _cartpole_expert_episodes()
    cfg = (
        BCConfig()
        .environment("CartPole-v1")
        .env_runners(num_env_runners=2, num_envs_per_env_runner=4,
                     rollout_fragment_length=128)
        .debugging(seed=0)
        .training(lr=3e-3)
        .offline_data(episodes=episodes)
    )
    algo = cfg.build_algo()
    try:
        last = None
        for _ in range(12):
            r = algo.train()
            last = r
        # scripted expert scores ~180+; random policy ~20
        assert last["episode_return_mean"] > 60, last
        assert last["num_offline_transitions"] > 1000
    finally:
        algo.stop()


def test_marwil_runs_without_env():
    """Offline-only: no env configured, loss decreases on the data."""
    episodes = _cartpole_expert_episodes(n_episodes=10)
    cfg = MARWILConfig().debugging(seed=0).offline_data(episodes=episodes)
    cfg.updates_per_step = 16
    algo = cfg.build_algo()
    first = algo.training_step()["total_loss"]
    for _ in range(8):
        m = algo.training_step()
    assert m["total_loss"] < first
    # no eval env: train() must still work and report nan return
    r = algo.train()
    assert np.isnan(r["episode_return_mean"])


def test_marwil_dataset_input(rl_cluster):
    """Offline episodes arriving through the Data layer."""
    from ray_tpu import data as rt_data

    episodes = [
        {
            "obs": ep["obs"].tolist(),       # arrow-friendly nested lists
            "actions": ep["actions"].tolist(),
            "rewards": ep["rewards"].tolist(),
        }
        for ep in _cartpole_expert_episodes(n_episodes=6)
    ]
    ds = rt_data.from_items(episodes)
    cfg = MARWILConfig().debugging(seed=0).offline_data(dataset=ds)
    algo = cfg.build_algo()
    m = algo.training_step()
    assert m["num_offline_transitions"] > 100


def _pointmass_episodes(n_episodes=20, T=40, seed=0):
    """1-D regulator: x' = x + 0.1 a, r = -x'^2; behavior policy is a noisy
    expert (a = -clip(10 x, -1, 1) + noise). Good offline algorithms
    extract the de-noised regulator."""
    rng = np.random.RandomState(seed)
    eps = []
    for _ in range(n_episodes):
        x = rng.uniform(-1, 1)
        obs, acts, rews = [[x]], [], []
        for _ in range(T):
            a = float(np.clip(-10 * x, -1, 1) + rng.normal(0, 0.3))
            a = float(np.clip(a, -1, 1))
            x = x + 0.1 * a
            obs.append([x])
            acts.append([a])
            rews.append(-x * x)
        eps.append({
            "obs": np.asarray(obs, np.float32),
            "actions": np.asarray(acts, np.float32),
            "rewards": np.asarray(rews, np.float32),
            "terminated": False,
        })
    return eps


def test_iql_learns_regulator_offline():
    """IQL: expectile value + AWR extraction improves on the data without
    ever querying out-of-distribution actions."""
    from ray_tpu.rllib import IQLConfig
    from ray_tpu.rllib import module as rl_module

    cfg = IQLConfig().debugging(seed=0).offline_data(
        episodes=_pointmass_episodes()
    )
    cfg.updates_per_step = 64
    algo = cfg.build_algo()
    first = algo.training_step()
    for _ in range(12):
        m = algo.training_step()
    assert m["critic_loss"] < first["critic_loss"]
    # extracted policy regulates: mean action opposes the state
    import jax.numpy as jnp

    mean, _ = rl_module.forward_policy(
        algo.pi_params, algo.module_config, jnp.asarray([[0.5], [-0.5]])
    ), None
    mean = np.asarray(mean[0] if isinstance(mean, tuple) else mean)
    acts = np.tanh(mean[:, :1]) if mean.shape[-1] > 1 else np.tanh(mean)
    assert acts[0, 0] < 0 < acts[1, 0], f"policy not regulating: {acts}"


def test_cql_learns_conservative_critic_offline():
    """CQL: bellman + conservative penalty both optimize; the conservative
    gap (logsumexp - data Q) shrinks as OOD actions get pushed down."""
    from ray_tpu.rllib import CQLConfig

    cfg = CQLConfig().debugging(seed=0).offline_data(
        episodes=_pointmass_episodes()
    )
    cfg.updates_per_step = 48
    algo = cfg.build_algo()
    first = algo.training_step()
    for _ in range(10):
        m = algo.training_step()
    assert m["conservative_gap"] < first["conservative_gap"]
    assert np.isfinite(m["critic_loss"])


def test_cql_iql_checkpoint_roundtrip(tmp_path):
    from ray_tpu.rllib import IQLConfig

    cfg = IQLConfig().debugging(seed=0).offline_data(
        episodes=_pointmass_episodes(n_episodes=4)
    )
    cfg.updates_per_step = 4
    algo = cfg.build_algo()
    algo.training_step()
    p = algo.save(str(tmp_path / "ck"))
    algo2 = IQLConfig().debugging(seed=1).offline_data(
        episodes=_pointmass_episodes(n_episodes=4)
    ).build_algo()
    algo2.restore(p)
    import jax

    a = jax.tree.leaves(algo.pi_params)
    b = jax.tree.leaves(algo2.pi_params)
    assert all(np.allclose(x, y) for x, y in zip(a, b))

"""ConnectorV2 pipelines and the TQC algorithm.

Reference analog: ``rllib/connectors/`` (ConnectorV2 / ConnectorPipelineV2 /
MeanStdFilter state merge) and the reference's TQC (truncated quantile
critics) roster entry — unit transforms, state-merge math, runner
integration, and a short TQC learning run.
"""
import numpy as np
import pytest

from ray_tpu.rllib import TQCConfig
from ray_tpu.rllib.connectors import (
    ClipObs,
    ConnectorPipelineV2,
    FlattenObs,
    FrameStack,
    MeanStdFilter,
    RescaleActions,
)


# ----------------------------------------------------------- unit transforms


def test_pipeline_applies_in_order():
    p = ConnectorPipelineV2([FlattenObs(), ClipObs(-1.0, 1.0)])
    out = p({"obs": np.full((2, 3, 4), 5.0, np.float32)})
    assert out["obs"].shape == (2, 12)
    assert out["obs"].max() == 1.0


def test_mean_std_filter_normalizes():
    f = MeanStdFilter()
    rng = np.random.RandomState(0)
    data = rng.normal(3.0, 2.0, (4096, 5)).astype(np.float32)
    f({"obs": data})
    out = f({"obs": data}, training=False)["obs"]
    assert abs(out.mean()) < 0.05
    assert abs(out.std() - 1.0) < 0.05
    # training=False must not touch statistics
    count = f.count
    f({"obs": data * 100}, training=False)
    assert f.count == count


def test_mean_std_merge_matches_pooled_moments():
    rng = np.random.RandomState(1)
    a = rng.normal(0.0, 1.0, (500, 3))
    b = rng.normal(5.0, 3.0, (1500, 3))
    fa, fb = MeanStdFilter(), MeanStdFilter()
    fa({"obs": a})
    fb({"obs": b})
    merged = MeanStdFilter.merge_states([fa.get_state(), fb.get_state()])
    pooled = np.concatenate([a, b])
    assert np.allclose(merged["mean"], pooled.mean(0), atol=1e-8)
    assert np.allclose(
        merged["m2"] / merged["count"], pooled.var(0), atol=1e-8
    )


def test_frame_stack_resets_on_done():
    fs = FrameStack(k=3)
    o1 = np.array([[1.0, 1.0]], np.float32)
    o2 = np.array([[2.0, 2.0]], np.float32)
    o3 = np.array([[9.0, 9.0]], np.float32)
    assert fs({"obs": o1})["obs"].shape == (1, 6)
    out = fs({"obs": o2})["obs"]
    assert out[0, 0] == 1.0 and out[0, -1] == 2.0  # oldest..newest
    # done resets the column: history becomes [o3, o3, o3]
    out = fs({"obs": o3}, dones=np.array([1.0]))["obs"]
    assert np.all(out == 9.0)
    # stateless probe does not touch history
    probe = fs({"obs": o1}, training=False)["obs"]
    assert np.all(probe == 1.0)
    out = fs({"obs": o2})["obs"]
    assert out[0, 0] == 9.0 and out[0, -1] == 2.0


def test_rescale_actions():
    r = RescaleActions(low=[-2.0], high=[6.0])
    out = r({"actions": np.array([[-1.0], [0.0], [1.0]], np.float32)})
    assert np.allclose(out["actions"].ravel(), [-2.0, 2.0, 6.0])


# ------------------------------------------------------- runner integration


class ShiftedObsEnv:
    """1-step env whose observations sit at mean ~100: PPO-style learners
    choke on unnormalized inputs; MeanStdFilter centers them."""

    class _Space:
        def __init__(self, low, high, shape):
            self.low = np.full(shape, low, np.float32)
            self.high = np.full(shape, high, np.float32)
            self.shape = shape

    def __init__(self):
        self.observation_space = self._Space(-200, 200, (3,))
        self.action_space = self._Space(-1, 1, (1,))
        self._rng = np.random.RandomState(0)

    def reset(self, seed=None):
        if seed is not None:
            self._rng = np.random.RandomState(seed)
        return self._obs(), {}

    def _obs(self):
        return (100.0 + self._rng.randn(3)).astype(np.float32)

    def step(self, action):
        a = np.asarray(action, np.float32).ravel()
        reward = -float(np.sum((a - 0.5) ** 2))
        return self._obs(), reward, True, False, {}

    def close(self):
        pass


def test_runner_applies_and_syncs_connector_state(rl_cluster):
    cfg = (
        TQCConfig()
        .environment(env_creator=ShiftedObsEnv)
        .env_runners(
            num_env_runners=2, num_envs_per_env_runner=2,
            rollout_fragment_length=16,
            env_to_module_connector=lambda: ConnectorPipelineV2(
                [MeanStdFilter()]
            ),
        )
        .debugging(seed=0)
    )
    cfg.min_replay_size = 10_000_000  # sampling only; no updates needed
    algo = cfg.build_algo()
    try:
        algo.train()
        merged = algo.runner_group.sync_connector_states()
        # both runners contributed: 2 runners x 2 envs x 16 steps
        assert merged and merged[0]["count"] == 2 * 2 * 16
        assert np.allclose(merged[0]["mean"], 100.0, atol=2.0)
        # runners saw normalized observations (stored in the batch)
        frags = algo.runner_group.sample()
        obs = np.concatenate([f["obs"] for f in frags], axis=1)
        assert abs(float(obs.mean())) < 3.0
    finally:
        algo.stop()


def test_frame_stack_integration_in_runner():
    """FrameStack changes the module obs dim, gets episode-boundary resets
    from the runner's dones, and the bootstrap value rides the transformed
    obs (it would shape-crash on raw obs)."""
    from ray_tpu.rllib.env_runner import SingleAgentEnvRunner

    class CountingEnv:
        """obs = [step_count]; episodes end after 3 steps."""

        class _Space:
            def __init__(self, n):
                self.low = np.full((n,), -100, np.float32)
                self.high = np.full((n,), 100, np.float32)
                self.shape = (n,)

        def __init__(self):
            self.observation_space = self._Space(1)
            self.action_space = self._Space(1)
            self._t = 0

        def reset(self, seed=None):
            self._t = 0
            return np.array([0.0], np.float32), {}

        def step(self, action):
            self._t += 1
            done = self._t >= 3
            return (
                np.array([float(self._t)], np.float32), 0.0, done, False, {}
            )

        def close(self):
            pass

    k = 2
    runner = SingleAgentEnvRunner(
        CountingEnv, num_envs=1, fragment_len=8,
        module_config={"obs_dim": k, "action_dim": 1, "discrete": False},
        env_to_module=lambda: FrameStack(k=k),
    )
    import jax

    from ray_tpu.rllib import module as rl_module

    runner.set_weights(rl_module.init_params(
        rl_module.RLModuleConfig(obs_dim=k, action_dim=1, discrete=False),
        jax.random.PRNGKey(0),
    ))
    frag = runner.sample()
    obs = frag["obs"][:, 0, :]              # [T, k]
    assert obs.shape == (8, k)
    # env obs: 0,1,2,(done)->0,1,2,(done)->0,...; stacked pairs
    # step 3 is the first frame after a reset: history must be [0, 0],
    # not [2, 0] (episode bleed)
    done_steps = np.nonzero(frag["dones"][:, 0])[0]
    first_after = int(done_steps[0]) + 1
    assert np.allclose(obs[first_after], 0.0), obs
    assert frag["bootstrap_value"].shape == (1,)

    # episode ending exactly on a fragment's LAST step: the reset must
    # still reach the connector at the next fragment's first step
    runner2 = SingleAgentEnvRunner(
        CountingEnv, num_envs=1, fragment_len=3,
        module_config={"obs_dim": k, "action_dim": 1, "discrete": False},
        env_to_module=lambda: FrameStack(k=k),
    )
    runner2.set_weights(runner.params)
    f1 = runner2.sample()
    assert f1["dones"][-1, 0] == 1.0  # done on the fragment edge
    f2 = runner2.sample()
    # fresh episode: stacked history is [0, 0], not [2, 0]
    assert np.allclose(f2["obs"][0, 0], 0.0), f2["obs"][0, 0]


# ----------------------------------------------------------------- TQC algo


class TargetReachEnv:
    """1-step continuous env: reward = -(a - 0.5)^2 per dim (same shape as
    the SAC test target)."""

    class _Space:
        def __init__(self, low, high, shape):
            self.low = np.full(shape, low, np.float32)
            self.high = np.full(shape, high, np.float32)
            self.shape = shape

    def __init__(self):
        self.observation_space = self._Space(-1, 1, (3,))
        self.action_space = self._Space(-1, 1, (1,))

    def reset(self, seed=None):
        return np.zeros(3, np.float32), {}

    def step(self, action):
        a = np.asarray(action, np.float32).ravel()
        reward = -float(np.sum((a - 0.5) ** 2))
        return np.zeros(3, np.float32), reward, True, False, {}

    def close(self):
        pass


def _tqc_config():
    return (
        TQCConfig()
        .environment(env_creator=TargetReachEnv)
        .env_runners(num_env_runners=2, num_envs_per_env_runner=4,
                     rollout_fragment_length=32)
        .debugging(seed=0)
        .training(lr=3e-3)
    )


def test_tqc_learns_target(rl_cluster):
    cfg = _tqc_config()
    cfg.min_replay_size = 200
    cfg.updates_per_step = 32
    algo = cfg.build_algo()
    try:
        last = None
        for _ in range(20):
            r = algo.train()
            last = r["episode_return_mean"]
        # optimal return is 0; random tanh actions average about -0.58
        assert last > -0.25, f"TQC did not improve: last={last}"
        assert "alpha" in r and r["alpha"] > 0
        assert np.isfinite(r["critic_loss"])
    finally:
        algo.stop()


def test_tqc_truncation_drops_top_atoms():
    """The pooled-sort-truncate target keeps the N*M - N*d smallest atoms."""
    import jax.numpy as jnp

    N, M, d = 2, 5, 2
    z = jnp.asarray(
        [[[10.0, 1.0, 7.0, 3.0, 5.0], [2.0, 8.0, 4.0, 6.0, 9.0]]]
    )  # [1, N, M]
    pooled = jnp.sort(z.reshape(1, N * M), -1)
    kept = pooled[:, : N * M - N * d]
    assert kept.shape == (1, 6)
    assert float(kept.max()) == 6.0  # 7,8,9,10 dropped


def test_tqc_checkpoint_roundtrip(rl_cluster, tmp_path):
    cfg = _tqc_config()
    cfg.min_replay_size = 50
    cfg.updates_per_step = 4
    algo = cfg.build_algo()
    try:
        for _ in range(3):
            algo.train()
        path = algo.save(str(tmp_path / "ck"))
        w_before = algo.get_weights()

        algo2 = _tqc_config().build_algo()
        try:
            algo2.restore(path)
            w_after = algo2.get_weights()
            import jax

            for a, b in zip(jax.tree.leaves(w_before),
                            jax.tree.leaves(w_after)):
                assert np.allclose(a, b)
            assert algo2.iteration == algo.iteration
        finally:
            algo2.stop()
    finally:
        algo.stop()


def test_tqc_rejects_all_atoms_dropped(rl_cluster):
    cfg = _tqc_config()
    cfg.n_critics = 2
    cfg.n_quantiles = 3
    cfg.top_quantiles_to_drop_per_net = 3
    with pytest.raises(ValueError, match="drops every atom"):
        cfg.build_algo()

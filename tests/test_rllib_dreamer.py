"""DreamerV3: world-model learning, imagination actor-critic, recurrent
acting.

Reference analog: ``rllib/algorithms/dreamerv3/`` learning tests. The
learning test uses a parity environment whose reward depends on the ACTION
at each phase — solvable only if the RSSM carries actions through its
recurrent state (random ≈ 4/8, optimal 8/8). Unit tests pin the symlog
pair, replay windowing, and checkpoint roundtrip.
"""
import gymnasium as gym
import numpy as np

from ray_tpu.rllib import DreamerV3Config


class ParityEnv:
    """8-step episodes; obs one-hot phase; reward 1 iff action == phase%2."""

    observation_space = gym.spaces.Box(-1, 1, (8,))
    action_space = gym.spaces.Discrete(2)

    def __init__(self):
        self._t = 0

    def _obs(self):
        o = np.zeros(8, np.float32)
        o[self._t % 8] = 1.0
        return o

    def reset(self, seed=None):
        self._t = 0
        return self._obs(), {}

    def step(self, action):
        r = 1.0 if int(action) == (self._t % 2) else 0.0
        self._t += 1
        return self._obs(), r, self._t >= 8, False, {}

    def close(self):
        pass


def _config():
    cfg = (
        DreamerV3Config()
        .environment(env_creator=ParityEnv)
        .env_runners(num_env_runners=2, num_envs_per_env_runner=4,
                     rollout_fragment_length=32)
        .debugging(seed=0)
    )
    cfg.min_replay_size = 64
    cfg.updates_per_step = 8
    cfg.units = 64
    cfg.deter_dim = 64
    cfg.imagine_horizon = 8
    return cfg


def test_symlog_roundtrip():
    from ray_tpu.rllib.algorithms.dreamerv3 import symexp, symlog

    x = np.array([-100.0, -1.0, 0.0, 0.5, 10.0, 1e4])
    assert np.allclose(np.asarray(symexp(symlog(x))), x, rtol=1e-5)


def test_sequence_replay_windows_and_boundaries():
    from ray_tpu.rllib.algorithms.dreamerv3 import SequenceReplay

    buf = SequenceReplay(64, num_envs=2, obs_dim=3, seed=0)
    T = 10
    batch = {
        "obs": np.arange(T * 2 * 3, dtype=np.float32).reshape(T, 2, 3),
        "actions": np.zeros((T, 2), np.int32),
        "rewards": np.arange(T * 2, dtype=np.float32).reshape(T, 2),
        "dones": np.zeros((T, 2), np.float32),
    }
    batch["dones"][4] = 1.0  # episode boundary mid-fragment
    buf.add_fragments(batch)
    win = buf.sample(4, 8)
    assert win["obs"].shape == (4, 8, 3)
    assert np.all(win["is_first"][:, 0] == 1.0)  # window starts reset
    # boundary flag lands on the step AFTER the done
    buf2 = SequenceReplay(64, num_envs=1, obs_dim=1, seed=0)
    b = {
        "obs": np.zeros((6, 1, 1), np.float32),
        "actions": np.zeros((6, 1), np.int32),
        "rewards": np.zeros((6, 1), np.float32),
        "dones": np.zeros((6, 1), np.float32),
    }
    b["dones"][2] = 1.0
    buf2.add_fragments(b)
    assert buf2.is_first[0, 3] == 1.0
    assert buf2.is_first[0, 2] == 0.0


def test_sequence_replay_survives_column_count_change():
    """Runner loss shrinks the fragment's env axis: the buffer remaps
    streams onto its columns and forces a reset flag (no bogus
    continuity across the outage)."""
    from ray_tpu.rllib.algorithms.dreamerv3 import SequenceReplay

    buf = SequenceReplay(32, num_envs=4, obs_dim=2, seed=0)
    full = {
        "obs": np.ones((4, 4, 2), np.float32),
        "actions": np.zeros((4, 4), np.int32),
        "rewards": np.zeros((4, 4), np.float32),
        "dones": np.zeros((4, 4), np.float32),
    }
    buf.add_fragments(full)
    # outage: only 2 columns arrive
    half = {
        "obs": 2 * np.ones((4, 2, 2), np.float32),
        "actions": np.zeros((4, 2), np.int32),
        "rewards": np.zeros((4, 2), np.float32),
        "dones": np.zeros((4, 2), np.float32),
    }
    buf.add_fragments(half)
    assert buf.size == 8
    # every column restarted at the outage boundary
    assert np.all(buf.is_first[:, 4] == 1.0)
    assert np.all(buf.obs[:, 4:8] == 2.0)


def test_dreamer_learns_action_conditioned_reward(rl_cluster):
    """Return climbs from ~4 (random) toward 8 once the world model's
    reward head becomes action-discriminative and the actor exploits it
    in imagination. ~70 iterations on CPU."""
    algo = _config().build_algo()
    try:
        rets = []
        for _ in range(70):
            r = algo.train()
            rets.append(r["episode_return_mean"])
        last = float(np.mean(rets[-3:]))
        assert last > 6.0, f"DreamerV3 did not learn: last={last} rets tail {rets[-10:]}"
        assert r["reward_loss"] < 0.05, r["reward_loss"]
    finally:
        algo.stop()


def test_dreamer_checkpoint_roundtrip(rl_cluster, tmp_path):
    algo = _config().build_algo()
    try:
        for _ in range(3):
            algo.train()
        path = algo.save(str(tmp_path / "ck"))
        import jax

        w = jax.device_get(algo.params)
        algo2 = _config().build_algo()
        try:
            algo2.restore(path)
            for a, b in zip(jax.tree.leaves(w),
                            jax.tree.leaves(algo2.params)):
                assert np.allclose(a, np.asarray(b))
            assert algo2.iteration == algo.iteration
        finally:
            algo2.stop()
    finally:
        algo.stop()

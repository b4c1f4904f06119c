"""Multi-agent episodes: per-policy learners over one env (reference:
``rllib/env/multi_agent_env_runner.py`` + multi_agent config)."""
import numpy as np

from ray_tpu.rllib.multi_agent import MultiAgentPPOConfig


class TwoGuessersEnv:
    """Two agents; each sees its private target bit (+noise) and earns +1
    for guessing it. agent 'b' terminates halfway — exercising per-agent
    done masking."""

    possible_agents = ["a", "b"]

    def __init__(self):
        import gymnasium as gym

        self._obs_space = gym.spaces.Box(-1.0, 2.0, (2,), np.float32)
        self._act_space = gym.spaces.Discrete(2)
        self._rng = np.random.RandomState(0)
        self.t = 0

    def observation_space(self, agent):
        return self._obs_space

    def action_space(self, agent):
        return self._act_space

    def _obs(self):
        return {
            a: np.array(
                [self.targets[a], self._rng.rand() * 0.1], np.float32
            )
            for a in self.possible_agents
        }

    def reset(self, seed=None):
        self._rng = np.random.RandomState(seed or 0)
        self.targets = {
            a: float(self._rng.randint(0, 2)) for a in self.possible_agents
        }
        self.t = 0
        return self._obs(), {}

    def step(self, actions):
        self.t += 1
        rews = {
            a: float(actions.get(a, -1) == self.targets[a])
            for a in self.possible_agents
        }
        terms = {a: False for a in self.possible_agents}
        truncs = {a: False for a in self.possible_agents}
        terms["b"] = self.t >= 10  # b leaves early
        done_all = self.t >= 20
        terms["__all__"] = done_all
        truncs["__all__"] = False
        # re-randomize targets so the policy must read the observation
        self.targets = {
            a: float(self._rng.randint(0, 2)) for a in self.possible_agents
        }
        return self._obs(), rews, terms, truncs, {}


def test_multi_agent_ppo_learns_per_policy(rl_cluster):
    cfg = (MultiAgentPPOConfig()
           .environment(env_creator=TwoGuessersEnv)
           .env_runners(num_env_runners=2, rollout_fragment_length=40)
           .multi_agent(
               policies=["pa", "pb"],
               policy_mapping_fn=lambda agent: f"p{agent}",
           )
           .debugging(seed=0))
    algo = cfg.build_algo()
    try:
        first, last = None, None
        for _ in range(40):
            r = algo.train()
            assert np.isfinite(r["total_loss"])
            assert "pa/policy_loss" in r and "pb/policy_loss" in r
            if first is None and r["num_episodes"] > 0:
                first = r["episode_return_mean"]
            last = r["episode_return_mean"]
            # max return: a earns up to 20, b up to 10 -> 30
            if last >= 24:
                break
        assert last is not None and last >= 18, (
            f"multi-agent PPO did not learn: {first} -> {last}"
        )
    finally:
        algo.stop()


def test_shared_policy_mapping(rl_cluster):
    cfg = (MultiAgentPPOConfig()
           .environment(env_creator=TwoGuessersEnv)
           .env_runners(num_env_runners=1, rollout_fragment_length=20)
           .multi_agent(
               policies=["shared"],
               policy_mapping_fn=lambda agent: "shared",
           )
           .debugging(seed=1))
    algo = cfg.build_algo()
    try:
        r = algo.train()
        assert "shared/policy_loss" in r
        w = algo.get_policy_weights("shared")
        assert w is not None
    finally:
        algo.stop()


def test_multi_agent_save_restore(rl_cluster, tmp_path):
    cfg = (MultiAgentPPOConfig()
           .environment(env_creator=TwoGuessersEnv)
           .env_runners(num_env_runners=1, rollout_fragment_length=20)
           .multi_agent(policies=["shared"],
                        policy_mapping_fn=lambda a: "shared")
           .debugging(seed=2))
    algo = cfg.build_algo()
    try:
        algo.train()
        path = algo.save(str(tmp_path / "ckpt"))
        w_before = algo.get_policy_weights("shared")
        algo.train()
        algo.restore(path)
        w_after = algo.get_policy_weights("shared")
        import jax

        for a, b in zip(jax.tree.leaves(w_before), jax.tree.leaves(w_after)):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    finally:
        algo.stop()

"""RLlib-equivalent layer: learning, estimators, fault tolerance, tune glue.

Reference analog: per-algorithm learning tests under
``rllib/algorithms/*/tests`` (CartPole-learns gates) and env-runner fault
tolerance tests in ``rllib/env/``.
"""
import numpy as np

import ray_tpu
from ray_tpu.rllib import IMPALAConfig, PPOConfig, make_trainable
from ray_tpu.rllib.learner import compute_gae, vtrace


# ---------------------------------------------------------- pure estimators


def test_gae_matches_numpy_reference():
    import jax.numpy as jnp

    rng = np.random.RandomState(0)
    T, N = 17, 3
    rewards = rng.randn(T, N).astype(np.float32)
    dones = (rng.rand(T, N) < 0.15).astype(np.float32)
    values = rng.randn(T, N).astype(np.float32)
    bootstrap = rng.randn(N).astype(np.float32)
    gamma, lam = 0.97, 0.9

    advs, targets = compute_gae(
        jnp.asarray(rewards), jnp.asarray(dones), jnp.asarray(values),
        jnp.asarray(bootstrap), gamma, lam,
    )
    # reference: explicit reverse loop
    ref = np.zeros((T, N), np.float32)
    acc = np.zeros(N, np.float32)
    next_v = bootstrap.copy()
    for t in range(T - 1, -1, -1):
        delta = rewards[t] + gamma * next_v * (1 - dones[t]) - values[t]
        acc = delta + gamma * lam * (1 - dones[t]) * acc
        ref[t] = acc
        next_v = values[t]
    np.testing.assert_allclose(np.asarray(advs), ref, rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(
        np.asarray(targets), ref + values, rtol=1e-5, atol=1e-5
    )


def test_vtrace_matches_numpy_reference():
    import jax.numpy as jnp

    rng = np.random.RandomState(1)
    T, N = 11, 2
    logp_t = rng.randn(T, N).astype(np.float32) * 0.3
    logp_b = rng.randn(T, N).astype(np.float32) * 0.3
    rewards = rng.randn(T, N).astype(np.float32)
    dones = (rng.rand(T, N) < 0.2).astype(np.float32)
    values = rng.randn(T, N).astype(np.float32)
    bootstrap = rng.randn(N).astype(np.float32)
    gamma, rho_c, c_c = 0.99, 1.0, 1.0

    vs, pg = vtrace(
        jnp.asarray(logp_t), jnp.asarray(logp_b), jnp.asarray(rewards),
        jnp.asarray(dones), jnp.asarray(values), jnp.asarray(bootstrap),
        gamma, rho_c, c_c,
    )
    rhos = np.minimum(np.exp(logp_t - logp_b), rho_c)
    cs = np.minimum(np.exp(logp_t - logp_b), c_c)
    disc = gamma * (1 - dones)
    next_v = np.concatenate([values[1:], bootstrap[None]], 0)
    deltas = rhos * (rewards + disc * next_v - values)
    acc = np.zeros(N, np.float32)
    dv = np.zeros((T, N), np.float32)
    for t in range(T - 1, -1, -1):
        acc = deltas[t] + disc[t] * cs[t] * acc
        dv[t] = acc
    vs_ref = values + dv
    np.testing.assert_allclose(np.asarray(vs), vs_ref, rtol=1e-4, atol=1e-4)
    next_vs = np.concatenate([vs_ref[1:], bootstrap[None]], 0)
    pg_ref = rhos * (rewards + disc * next_vs - values)
    np.testing.assert_allclose(np.asarray(pg), pg_ref, rtol=1e-4, atol=1e-4)


def test_vtrace_on_policy_equals_nstep():
    """With identical policies (rhos = 1), vs is the Bellman evaluation of
    the trajectory return — check against discounted rollup on a done-free
    fragment."""
    import jax.numpy as jnp

    T, N = 8, 1
    rewards = np.ones((T, N), np.float32)
    dones = np.zeros((T, N), np.float32)
    values = np.zeros((T, N), np.float32)
    bootstrap = np.zeros(N, np.float32)
    logp = np.zeros((T, N), np.float32)
    vs, _ = vtrace(
        jnp.asarray(logp), jnp.asarray(logp), jnp.asarray(rewards),
        jnp.asarray(dones), jnp.asarray(values), jnp.asarray(bootstrap),
        0.9, 1.0, 1.0,
    )
    expected0 = sum(0.9 ** t for t in range(T))
    assert abs(float(vs[0, 0]) - expected0) < 1e-4


# ------------------------------------------------------------- learning


def _ppo_config(**training):
    cfg = (PPOConfig()
           .environment("CartPole-v1")
           .env_runners(num_env_runners=2, num_envs_per_env_runner=8,
                        rollout_fragment_length=64)
           .debugging(seed=0))
    if training:
        cfg.training(**training)
    return cfg


def test_ppo_cartpole_learns(rl_cluster):
    algo = _ppo_config().build_algo()
    try:
        first, last = None, None
        for _ in range(40):
            r = algo.train()
            if first is None and r["num_episodes"] > 0:
                first = r["episode_return_mean"]
            last = r["episode_return_mean"]
            if last >= 150:
                break
        assert last is not None and first is not None
        assert last >= 120, f"PPO failed to learn: {first} -> {last}"
    finally:
        algo.stop()


def test_impala_cartpole_improves(rl_cluster):
    cfg = (IMPALAConfig()
           .environment("CartPole-v1")
           .env_runners(num_env_runners=2, num_envs_per_env_runner=8,
                        rollout_fragment_length=32)
           .debugging(seed=0))
    algo = cfg.build_algo()
    try:
        first, last = None, None
        for _ in range(60):
            r = algo.train()
            assert np.isfinite(r.get("total_loss", 0.0))
            if first is None and r["num_episodes"] > 0:
                first = r["episode_return_mean"]
            last = r["episode_return_mean"]
            if last >= 80:
                break
        assert last >= max(40.0, 1.5 * first), (
            f"IMPALA did not improve: {first} -> {last}"
        )
    finally:
        algo.stop()


def test_checkpoint_save_restore(rl_cluster, tmp_path):
    import jax

    algo = _ppo_config().build_algo()
    try:
        for _ in range(3):
            algo.train()
        path = algo.save(str(tmp_path / "ckpt"))
        w0 = algo.get_weights()
        it0 = algo.iteration
    finally:
        algo.stop()

    algo2 = _ppo_config().build_algo()
    try:
        algo2.restore(path)
        assert algo2.iteration == it0
        w1 = algo2.get_weights()
        for a, b in zip(jax.tree.leaves(w0), jax.tree.leaves(w1)):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
        algo2.train()  # resumes cleanly
    finally:
        algo2.stop()


def test_env_runner_restart_after_kill(rl_cluster):
    algo = _ppo_config().build_algo()
    try:
        algo.train()
        # kill one runner actor out from under the group
        ray_tpu.kill(algo.runner_group.runners[0])
        r = algo.train()  # dead runner skipped, then respawned
        assert r["training_iteration"] == 2
        r = algo.train()  # respawned runner participates again
        frags = algo.runner_group.sample()
        assert len(frags) == 2
    finally:
        algo.stop()


def test_tune_integration(rl_cluster, tmp_path):
    from ray_tpu import tune

    trainable = make_trainable(
        _ppo_config().env_runners(num_env_runners=1,
                                  num_envs_per_env_runner=4),
        stop_iters=2,
    )
    tuner = tune.Tuner(
        trainable,
        param_space={"lr": tune.grid_search([3e-4, 1e-3])},
        tune_config=tune.TuneConfig(
            metric="episode_return_mean", mode="max",
        ),
        run_config=ray_tpu.train.RunConfig(
            storage_path=str(tmp_path), name="rl_tune"
        ),
    )
    grid = tuner.fit()
    assert len(grid) == 2
    assert grid.num_errors == 0
    best = grid.get_best_result()
    assert "episode_return_mean" in best.metrics


def test_learner_spmd_mesh_update():
    """Learner DP over a device mesh: batch sharded on the data axis, params
    replicated; XLA inserts the gradient psum (no host-loop DDP)."""
    import jax
    from jax.sharding import Mesh

    from ray_tpu.rllib.learner import Learner, LearnerHyperparams
    from ray_tpu.rllib.module import RLModuleConfig

    devices = np.array(jax.devices()[:4]).reshape(4)
    mesh = Mesh(devices, ("data",))
    cfg = RLModuleConfig(obs_dim=4, action_dim=2, discrete=True)
    hp = LearnerHyperparams(minibatch_count=2, num_sgd_epochs=2)
    learner = Learner("ppo", cfg, hp, seed=0, mesh=mesh)
    rng = np.random.RandomState(0)
    T, N = 16, 8  # N divides the data axis
    batch = {
        "obs": rng.randn(T, N, 4).astype(np.float32),
        "actions": rng.randint(0, 2, (T, N)).astype(np.int32),
        "rewards": rng.randn(T, N).astype(np.float32),
        "dones": np.zeros((T, N), np.float32),
        "logp": (-np.log(2) * np.ones((T, N))).astype(np.float32),
        "values": rng.randn(T, N).astype(np.float32),
        "bootstrap_value": rng.randn(N).astype(np.float32),
    }
    m1 = learner.update(batch)
    m2 = learner.update(batch)
    assert np.isfinite(m1["total_loss"]) and np.isfinite(m2["total_loss"])


def test_dqn_cartpole_learns(rl_cluster):
    from ray_tpu.rllib import DQNConfig

    cfg = (DQNConfig()
           .environment("CartPole-v1")
           .env_runners(num_env_runners=2, num_envs_per_env_runner=8,
                        rollout_fragment_length=32)
           .debugging(seed=0))
    algo = cfg.build_algo()
    try:
        first, last = None, None
        for _ in range(120):
            r = algo.train()
            if first is None and r["num_episodes"] > 0:
                first = r["episode_return_mean"]
            last = r["episode_return_mean"]
            if last >= 120:
                break
        assert last >= 100, f"DQN failed to learn: {first} -> {last}"
        assert r["epsilon"] < 0.5  # annealing in effect (broadcast in params)
    finally:
        algo.stop()


def test_dqn_checkpoint_roundtrip(rl_cluster, tmp_path):
    import jax
    import numpy as np

    from ray_tpu.rllib import DQNConfig

    cfg = (DQNConfig()
           .environment("CartPole-v1")
           .env_runners(num_env_runners=1, num_envs_per_env_runner=4,
                        rollout_fragment_length=16)
           .debugging(seed=0))
    algo = cfg.build_algo()
    try:
        for _ in range(3):
            algo.train()
        path = algo.save(str(tmp_path / "dqn_ckpt"))
        w0 = algo.get_weights()
    finally:
        algo.stop()
    algo2 = cfg.build_algo()
    try:
        algo2.restore(path)
        for a, b in zip(jax.tree.leaves(w0),
                        jax.tree.leaves(algo2.get_weights())):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
        algo2.train()
    finally:
        algo2.stop()


def test_appo_cartpole_improves(rl_cluster):
    """APPO (reference: rllib/algorithms/appo): IMPALA-style stale
    sampling + V-trace with the PPO clipped surrogate."""
    from ray_tpu.rllib.algorithms import APPOConfig

    cfg = (APPOConfig()
           .environment("CartPole-v1")
           .env_runners(num_env_runners=2, num_envs_per_env_runner=8,
                        rollout_fragment_length=32)
           .debugging(seed=0))
    algo = cfg.build_algo()
    try:
        first, last = None, None
        for _ in range(60):
            r = algo.train()
            assert np.isfinite(r.get("total_loss", 0.0))
            assert "kl" in r  # the clip-surrogate loss reports kl
            if first is None and r["num_episodes"] > 0:
                first = r["episode_return_mean"]
            last = r["episode_return_mean"]
            if last >= 80:
                break
        assert last >= max(40.0, 1.5 * first), (
            f"APPO did not improve: {first} -> {last}"
        )
    finally:
        algo.stop()

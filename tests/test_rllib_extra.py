"""SAC + offline RL (BC/CQL) learning tests (reference:
rllib/algorithms/sac, rllib/algorithms/bc, rllib/algorithms/cql test
strategy: assert the algorithm LEARNS a trivial env, not just runs)."""

import numpy as np
import pytest

import ray_tpu


@pytest.fixture(scope="module")
def rt(ray_start_module):
    yield ray_start_module


def test_sac_learns_randomwalk(rt):
    from ray_tpu.rllib.sac import SACConfig

    algo = (SACConfig()
            .environment("RandomWalk")
            .env_runners(2, rollout_steps=128)
            # gamma 0.9: a long entropy-farming horizon (alpha*H/(1-gamma))
            # can outweigh the chain's terminal +1 and teach avoidance
            .training(lr=3e-3, gamma=0.9, updates_per_iter=64,
                      learning_starts=200, tau=0.05)
            .build())
    try:
        result = {}
        for _ in range(12):
            result = algo.train()
        ev = algo.evaluate(num_episodes=5, max_steps=50)
        assert ev["episode_return_mean"] >= 0.8, (result, ev)
        assert result["entropy"] >= 0.0
    finally:
        algo.stop()


def test_bc_clones_expert(tmp_path):
    """BC on episodes recorded from a scripted expert reproduces its
    behavior (always-right on RandomWalk reaches the +1 end)."""
    from ray_tpu.rllib.offline import BCConfig, record_episodes

    path = record_episodes(
        "RandomWalk", lambda obs: 1, str(tmp_path / "expert.npz"),
        num_episodes=50)
    algo = (BCConfig()
            .environment("RandomWalk")
            .training(lr=1e-2, input_=path, updates_per_iter=100)
            .build())
    result = algo.train()
    assert result["bc_loss"] < 0.1, result
    ev = algo.evaluate(num_episodes=5, max_steps=50)
    assert ev["episode_return_mean"] == 1.0


def test_cql_learns_from_mixed_offline_data(tmp_path):
    """CQL on a mixed random+expert dataset recovers the good policy
    without ever touching the env during training."""
    from ray_tpu.rllib.offline import CQLConfig, record_episodes

    rng = np.random.default_rng(0)
    expert = str(tmp_path / "expert.npz")
    random_ = str(tmp_path / "random.npz")
    record_episodes("RandomWalk", lambda obs: 1, expert, num_episodes=30)
    record_episodes("RandomWalk", lambda obs: int(rng.integers(0, 2)),
                    random_, num_episodes=60)
    # merge into one dataset file
    a, b = np.load(expert), np.load(random_)
    merged = str(tmp_path / "mixed.npz")
    np.savez(merged, **{k: np.concatenate([a[k], b[k]]) for k in a.files})

    algo = (CQLConfig()
            .environment("RandomWalk")
            .training(lr=1e-2, input_=merged, updates_per_iter=200,
                      cql_alpha=1.0)
            .build())
    for _ in range(3):
        result = algo.train()
    assert result["td_loss"] < 1.0
    ev = algo.evaluate(num_episodes=5, max_steps=50)
    assert ev["episode_return_mean"] == 1.0


def test_offline_data_from_ray_dataset(rt, tmp_path):
    """The offline path composes with ray_tpu.data (the reference routes
    offline episodes through Ray Data, rllib/offline/offline_data.py)."""
    from ray_tpu import data as rtd
    from ray_tpu.rllib.offline import OfflineData, record_episodes

    path = record_episodes("RandomWalk", lambda obs: 1,
                           str(tmp_path / "eps.npz"), num_episodes=10)
    z = np.load(path)
    ds = rtd.from_items([
        {"obs": z["obs"][i], "actions": int(z["actions"][i]),
         "rewards": float(z["rewards"][i]), "next_obs": z["next_obs"][i],
         "dones": float(z["dones"][i])} for i in range(len(z["obs"]))])
    od = OfflineData(ds)
    assert len(od) == len(z["obs"])
    batch = od.sample(16)
    assert batch["obs"].shape == (16, 9)
    assert batch["actions"].dtype == np.int32


def test_appo_learns_randomwalk(rt):
    """APPO (IMPALA machinery + PPO clip + target network, reference
    rllib/algorithms/appo/) must solve RandomWalk."""
    from ray_tpu.rllib import APPOConfig

    algo = (APPOConfig()
            .environment("RandomWalk")
            .env_runners(num_env_runners=2, rollout_steps=256)
            .training(lr=2e-3, gamma=0.95, entropy_coeff=0.003,
                      target_update_freq=2)
            .build())
    try:
        for _ in range(12):
            r = algo.train()
        assert r["training_iteration"] == 12
        ev = algo.evaluate(num_episodes=10, max_steps=50)
        assert ev["episode_return_mean"] >= 0.9
    finally:
        algo.stop()


def test_multi_agent_ppo_learns_coordination(rt):
    """Per-policy learners over a multi-agent env (reference
    multi_agent_env_runner.py + policy_mapping_fn): two independent
    policies must learn the coordination game far beyond random play."""
    from ray_tpu.rllib import MatchingGame, MultiAgentPPO

    trainer = MultiAgentPPO(
        MatchingGame,
        policies=["p0", "p1"],
        policy_mapping=lambda agent: "p0" if agent == "a0" else "p1",
        num_env_runners=2, rollout_steps=128, lr=5e-3, seed=3)
    try:
        for _ in range(15):
            r = trainer.train()
        assert r["training_iteration"] == 15
        assert set(r["policy_loss"]) == {"p0", "p1"}  # both policies trained
        # random play earns 0.25/tick per agent; coordinated >= ~0.8
        assert trainer.mean_step_reward(num_steps=128) >= 0.7
    finally:
        trainer.stop()


def test_connector_pipeline_and_mean_std_filter():
    """Connector composition + the stateful running filter incl. state
    sync (reference: rllib/connectors/ ConnectorV2 pipelines)."""
    import numpy as np

    from ray_tpu.rllib.connectors import (ClipRewards, ConnectorPipeline,
                                          MeanStdFilter, StandardizeFields)

    f = MeanStdFilter(shape=(3,))
    rng = np.random.default_rng(0)
    data = rng.normal(5.0, 2.0, (500, 3))
    out = np.stack([f(row) for row in data])
    # after enough samples, normalized stream is ~zero-mean unit-std
    assert abs(out[-100:].mean()) < 0.3
    assert 0.5 < out[-100:].std() < 1.5
    # state sync: a fresh filter with copied state normalizes identically
    g = MeanStdFilter(shape=(3,), update=False)
    g.set_state(f.get_state())
    probe = rng.normal(5.0, 2.0, (3,))
    f.update_enabled = False
    assert np.allclose(f(probe), g(probe))

    pipe = ConnectorPipeline([ClipRewards(1.0),
                              StandardizeFields(["advantages"])])
    batch = {"rewards": np.array([-5.0, 0.5, 7.0]),
             "advantages": np.array([1.0, 2.0, 3.0])}
    out = pipe(batch)
    assert np.allclose(out["rewards"], [-1.0, 0.5, 1.0])
    assert abs(out["advantages"].mean()) < 1e-6
    # original batch untouched (connectors copy)
    assert batch["rewards"][0] == -5.0


def test_prioritized_replay_buffer_sampling():
    import numpy as np

    from ray_tpu.rllib.buffer import PrioritizedReplayBuffer

    b = PrioritizedReplayBuffer(128, 2, seed=0, alpha=1.0, beta=1.0)
    for i in range(8):
        b.add_batch({"obs": np.ones((16, 2)) * i,
                     "next_obs": np.zeros((16, 2)),
                     "actions": np.full(16, i, np.int32),
                     "rewards": np.ones(16), "dones": np.zeros(16)})
    s = b.sample(64)
    assert set(s) >= {"obs", "actions", "weights", "idx"}
    # after spiking one index's priority it dominates sampling
    prios = np.full(128, 1e-3)
    prios[42] = 50.0
    b.update_priorities(np.arange(128), prios)
    s2 = b.sample(512)
    assert (s2["idx"] == 42).mean() > 0.5
    # IS weights are <= 1 and smallest for the over-sampled index
    assert s2["weights"].max() <= 1.0 + 1e-6
    w42 = s2["weights"][s2["idx"] == 42]
    assert w42.mean() < np.median(s2["weights"]) + 1e-6


def test_dqn_prioritized_learns(ray_start_regular):
    """DQN with the PER buffer still learns the chain env (the composable
    extension point exercised through a full algorithm)."""
    from ray_tpu import rllib

    algo = (rllib.DQNConfig()
            .environment("RandomWalk")
            .env_runners(1, rollout_steps=128)
            .training(lr=1e-3, gamma=0.95, seed=3,
                      replay_buffer="prioritized",
                      buffer_size=10_000, learning_starts=200,
                      epsilon_anneal_iters=5)
            .build())
    try:
        for _ in range(10):
            res = algo.train()
        assert res["loss"] is not None
        ev = algo.evaluate(num_episodes=10, max_steps=50)
        assert ev["episode_return_mean"] >= 0.9, ev
    finally:
        algo.stop()


def test_env_to_module_connector_in_runner(ray_start_regular):
    """A MeanStdFilter env-to-module pipeline threads through config ->
    runner group -> sample batches, with state retrievable for sync."""
    import numpy as np

    from ray_tpu import rllib
    from ray_tpu.rllib.connectors import ConnectorPipeline, MeanStdFilter

    algo = (rllib.PPOConfig()
            .environment("CartPole")
            .env_runners(1, rollout_steps=128)
            .connectors(env_to_module=lambda: ConnectorPipeline(
                [MeanStdFilter(shape=(4,))]))
            .training(seed=0)
            .build())
    try:
        algo.train()
        states = algo.runners.connector_states()
        assert states and states[0] is not None
        count = states[0][0]["count"]
        assert count > 100  # the filter saw the rollout stream
    finally:
        algo.stop()


def test_frame_stack_connector_resizes_module(ray_start_regular):
    """A shape-changing env-to-module connector (FrameStack) widens the
    module input and runs end to end, with the stack window cleared at
    episode boundaries."""
    from ray_tpu import rllib
    from ray_tpu.rllib.connectors import FrameStack

    algo = (rllib.PPOConfig()
            .environment("CartPole")
            .env_runners(1, rollout_steps=64)
            .connectors(env_to_module=lambda: FrameStack(shape=(4,), n=3))
            .training(seed=0)
            .build())
    try:
        assert algo.module.observation_dim == 12  # 3 stacked frames
        res = algo.train()
        assert res["training_iteration"] == 1
        # evaluation path uses the driver's pipeline: must not crash on dim
        algo.evaluate(num_episodes=1, max_steps=20)
    finally:
        algo.stop()


def _mixed_dataset(tmp_path) -> str:
    """Half expert (always-right), half random RandomWalk transitions."""
    from ray_tpu.rllib.offline import record_episodes

    rng = np.random.default_rng(0)
    expert = str(tmp_path / "expert.npz")
    random_ = str(tmp_path / "random.npz")
    record_episodes("RandomWalk", lambda obs: 1, expert, num_episodes=30)
    record_episodes("RandomWalk", lambda obs: int(rng.integers(0, 2)),
                    random_, num_episodes=60)
    a, b = np.load(expert), np.load(random_)
    merged = str(tmp_path / "mixed.npz")
    np.savez(merged, **{k: np.concatenate([a[k], b[k]]) for k in a.files})
    return merged


def test_marwil_distills_good_trajectories(ray_start_regular, tmp_path):
    """MARWIL on mixed-quality data beats plain BC's behavior match: the
    exp(beta*advantage) weight imitates the expert transitions harder."""
    from ray_tpu.rllib.offline import MARWILConfig

    algo = (MARWILConfig()
            .environment("RandomWalk")
            .training(lr=1e-2, gamma=0.95, input_=_mixed_dataset(tmp_path),
                      updates_per_iter=300, beta=2.0)
            .build())
    for _ in range(3):
        res = algo.train()
    assert res["policy_loss"] == res["policy_loss"]  # finite
    ev = algo.evaluate(num_episodes=10, max_steps=50)
    assert ev["episode_return_mean"] >= 0.9, ev


def test_iql_learns_from_mixed_offline_data(ray_start_regular, tmp_path):
    """Discrete IQL recovers the good policy from mixed data without OOD
    Q queries (expectile V + advantage-weighted BC)."""
    from ray_tpu.rllib.offline import IQLConfig

    algo = (IQLConfig()
            .environment("RandomWalk")
            .training(lr=1e-2, gamma=0.95, input_=_mixed_dataset(tmp_path),
                      updates_per_iter=300, expectile=0.8, temperature=3.0)
            .build())
    for _ in range(3):
        res = algo.train()
    assert res["q_loss"] == res["q_loss"]
    ev = algo.evaluate(num_episodes=10, max_steps=50)
    assert ev["episode_return_mean"] >= 0.9, ev

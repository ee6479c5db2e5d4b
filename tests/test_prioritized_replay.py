"""Prioritized replay (pure-JAX sum-tree) + device-resident evaluation.

Pins the PER contract from three sides: the sum-tree itself (sampling
frequencies track priorities, IS weights normalize to max 1, priorities
survive ring wraparound), the uniform-equivalence guarantee (``alpha == 0``
bit-matches the uniform sampler; the weighted update with unit weights
bit-matches the unweighted one; the forced-PER engine bit-matches the
uniform engine end-to-end), and the numpy mirror (identical tree layout and
queries, so the scalar loop's prioritized path is the same distribution).
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import (
    DQNConfig, EnvConfig, TrainConfig, make_zoo, train_agent,
)
from repro.core.agent import DQNAgent, _dqn_update, _dqn_update_per, beta_at
from repro.core.env import VecCoScheduleEnv
from repro.core.metrics import relative_throughput
from repro.core.replay import (
    PrioritizedReplayBuffer, _tree_query, per_init, per_push, per_sample,
    per_update, replay_init, replay_sample,
)
from repro.core.scheduler import RLScheduler
from repro.core.train import _build_eval
from repro.core.workloads import QUEUE_KINDS, make_queue

ZOO = make_zoo()


def _block(v, n=4, dim=3, acts=2):
    return {"s": jnp.full((n, dim), v, jnp.float32),
            "a": jnp.full((n,), v, jnp.int32),
            "r": jnp.full((n,), v, jnp.float32),
            "s2": jnp.full((n, dim), v, jnp.float32),
            "done": jnp.zeros((n,), jnp.float32),
            "mask2": jnp.ones((n, acts), bool)}


def _filled_per(capacity=8, priorities=None):
    ps = per_init(capacity, 3, 2)
    for v in range(capacity // 4):
        ps = per_push(ps, _block(v + 1))
    if priorities is not None:
        idx = jnp.arange(capacity)
        ps = per_update(ps, idx, jnp.asarray(priorities, jnp.float32),
                        alpha=1.0, eps=0.0)
    return ps


# ---------------------------------------------------------------- sum-tree

def test_sum_tree_root_is_total_mass():
    ps = _filled_per(8, priorities=[1, 2, 3, 4, 5, 6, 7, 8])
    assert np.isclose(float(ps.tree[1]), 36.0)
    leaves = np.asarray(ps.tree[8:16])
    np.testing.assert_allclose(leaves, np.arange(1, 9, dtype=np.float32))


def test_sampling_frequencies_match_priorities():
    pri = np.array([1, 2, 3, 4, 5, 6, 7, 8], np.float32)
    ps = _filled_per(8, priorities=pri)
    counts = np.zeros(8)
    n, rounds = 256, 16
    for k in range(rounds):
        _, idx, _ = per_sample(ps, jax.random.PRNGKey(k), n, alpha=1.0, beta=0.4)
        counts += np.bincount(np.asarray(idx), minlength=8)
    freq = counts / (n * rounds)
    np.testing.assert_allclose(freq, pri / pri.sum(), atol=0.02)


def test_is_weights_normalized_and_correct():
    pri = np.array([1, 2, 3, 4, 5, 6, 7, 8], np.float32)
    ps = _filled_per(8, priorities=pri)
    beta = 0.7
    _, idx, w = per_sample(ps, jax.random.PRNGKey(3), 64, alpha=1.0, beta=beta)
    w, idx = np.asarray(w), np.asarray(idx)
    assert np.isclose(w.max(), 1.0)
    probs = pri[idx] / pri.sum()
    expect = (8 * probs) ** (-beta)
    np.testing.assert_allclose(w, expect / expect.max(), rtol=1e-4)


def test_alpha_zero_bit_matches_uniform_sampler():
    ps = _filled_per(8)
    key = jax.random.PRNGKey(11)
    batch, idx, w = per_sample(ps, key, 32, alpha=0.0, beta=0.4)
    ref = replay_sample(ps.ring, key, 32)
    for f, v in ref.items():
        assert np.array_equal(np.asarray(batch[f]), np.asarray(v)), f
    assert np.all(np.asarray(w) == 1.0)


def test_priorities_survive_ring_wraparound():
    ps = per_init(8, 3, 2)
    ps = per_push(ps, _block(1))
    ps = per_push(ps, _block(2))
    ps = per_update(ps, jnp.arange(4, 8), jnp.array([0.5, 0.6, 0.7, 0.8]),
                    alpha=1.0, eps=0.0)
    ps = per_push(ps, _block(3))            # wraps: overwrites slots 0..3
    leaves = np.asarray(ps.tree[8:16])
    np.testing.assert_allclose(leaves[4:], [0.5, 0.6, 0.7, 0.8])
    # the overwritten block re-enters at the running max priority (1.0)
    np.testing.assert_allclose(leaves[:4], 1.0)
    assert np.isclose(float(ps.tree[1]), leaves.sum())
    assert set(np.asarray(ps.ring.a).tolist()) == {2, 3}


def test_tree_query_never_returns_zero_mass_leaf():
    ps = per_init(8, 3, 2)
    ps = per_push(ps, _block(1))            # only slots 0..3 filled
    _, idx, _ = per_sample(ps, jax.random.PRNGKey(0), 64, alpha=1.0, beta=0.4)
    assert np.asarray(idx).max() < 4


def test_incremental_ancestor_updates_bit_match_full_rebuild():
    """per_push/per_update recompute only ancestor paths (O(B log C)); the
    result must be bit-identical to a from-scratch rebuild of the same
    leaves — every touched node is the exact sum of its children, so no
    float32 drift can accumulate either."""
    from repro.core.replay import _tree_rebuild

    rng = np.random.default_rng(0)
    for capacity in (8, 12, 32):           # 12: leaves > capacity (padding)
        ps = per_init(capacity, 3, 2)
        for step in range(12):
            if step % 2 == 0:
                ps = per_push(ps, _block(step + 1))
            else:
                n_idx = int(rng.integers(1, 6))
                idx = jnp.asarray(rng.integers(0, capacity, size=n_idx))
                td = jnp.asarray(rng.gamma(1.0, 2.0, size=n_idx), jnp.float32)
                ps = per_update(ps, idx, td, alpha=0.7, eps=1e-3)
            rebuilt = np.asarray(_tree_rebuild(ps.tree))
            assert np.array_equal(np.asarray(ps.tree), rebuilt), (
                capacity, step)


def test_sample_empty_ring_asserts():
    rs = replay_init(8, 2, 2)
    with pytest.raises(AssertionError):
        replay_sample(rs, jax.random.PRNGKey(0), 4)
    ps = per_init(8, 2, 2)
    with pytest.raises(AssertionError):
        per_sample(ps, jax.random.PRNGKey(0), 4, alpha=0.6, beta=0.4)


# ------------------------------------------------------------ numpy mirror

def test_numpy_mirror_matches_jax_tree():
    ps = _filled_per(8, priorities=[1, 2, 3, 4, 5, 6, 7, 8])
    buf = PrioritizedReplayBuffer(8, 3, 2, alpha=1.0, eps=0.0)
    for v in range(2):
        for _ in range(4):
            buf.push(np.full(3, v + 1), v + 1, v + 1, np.full(3, v + 1),
                     0.0, np.ones(2, bool))
    buf.update_priorities(np.arange(8), np.arange(1, 9, dtype=np.float64))
    np.testing.assert_allclose(np.asarray(ps.tree), buf.tree, rtol=1e-6)
    # identical descent for targets placed away from segment boundaries
    targets = np.cumsum([1, 2, 3, 4, 5, 6, 7, 8]) - 0.5
    jidx = np.asarray(_tree_query(ps.tree, jnp.asarray(targets, jnp.float32)))
    nidx = np.array([buf._query(t) for t in targets])
    assert np.array_equal(jidx, nidx)
    assert np.array_equal(jidx, np.arange(8))


def test_beta_anneals_to_one():
    assert beta_at(0.4, 0, 100) == pytest.approx(0.4)
    assert beta_at(0.4, 50, 100) == pytest.approx(0.7)
    assert beta_at(0.4, 100, 100) == pytest.approx(1.0)
    assert beta_at(0.4, 10**9, 100) == pytest.approx(1.0)
    assert float(beta_at(0.4, jnp.int32(50), 100)) == pytest.approx(0.7)


# ------------------------------------------------- uniform-equivalence path

def test_weighted_update_with_unit_weights_bit_matches_uniform():
    agent = DQNAgent(24, 6, DQNConfig(batch_size=16), seed=0)
    k = jax.random.PRNGKey(5)
    ks = jax.random.split(k, 4)
    batch = {
        "s": jax.random.normal(ks[0], (16, 24)),
        "a": jax.random.randint(ks[1], (16,), 0, 6),
        "r": jax.random.normal(ks[2], (16,)) * 10.0,
        "s2": jax.random.normal(ks[3], (16, 24)),
        "done": jnp.zeros((16,)),
        "mask2": jnp.ones((16, 6), bool),
    }
    p1, o1, l1 = _dqn_update(agent.params, agent.target_params, agent.opt,
                             batch, agent.cfg)
    p2, o2, l2, td = _dqn_update_per(agent.params, agent.target_params,
                                     agent.opt, batch, jnp.ones((16,)),
                                     agent.cfg)
    assert float(l1) == float(l2)
    for x, y in zip(jax.tree.leaves(p1), jax.tree.leaves(p2)):
        assert np.array_equal(np.asarray(x), np.asarray(y))
    for x, y in zip(jax.tree.leaves(o1), jax.tree.leaves(o2)):
        assert np.array_equal(np.asarray(x), np.asarray(y))
    assert np.all(np.isfinite(np.asarray(td)))


def _small_cfg(seed=0, **kw):
    return TrainConfig(episodes=40, eval_every=20, n_train_queues=4,
                       batch_envs=4, update_every=4, seed=seed,
                       dqn=DQNConfig(buffer_size=512, batch_size=32,
                                     eps_decay_steps=400), **kw)


def test_per_alpha_zero_engine_matches_uniform_engine_bit_exactly():
    """Regression parity: the PER machinery at alpha=0 IS the uniform engine."""
    env_cfg = EnvConfig(window=4, c_max=3)
    a_uni, h_uni = train_agent(ZOO, env_cfg, _small_cfg())
    a_per, h_per = train_agent(ZOO, env_cfg, _small_cfg(), _force_per=True)
    assert h_uni == h_per
    for x, y in zip(jax.tree.leaves(a_uni.params), jax.tree.leaves(a_per.params)):
        assert np.array_equal(np.asarray(x), np.asarray(y))


def test_prioritized_training_runs_and_decays():
    env_cfg = EnvConfig(window=4, c_max=3)
    agent, hist = train_agent(ZOO, env_cfg, _small_cfg(per_alpha=0.6))
    assert hist and hist[-1]["episode"] >= 40
    for rec in hist:
        assert set(rec) == {"episode", "eps", "ep_reward", "eval_throughput",
                            "heldout_throughput"}
        assert np.isfinite(rec["ep_reward"]) and np.isfinite(rec["eval_throughput"])
    assert hist[-1]["eps"] < 1.0
    assert agent.per_alpha == 0.6


def test_scalar_prioritized_buffer_drives_updates():
    """The numpy mirrored path trains: sample -> weighted update -> re-rank."""
    agent = DQNAgent(24, 6, DQNConfig(batch_size=8, buffer_size=64),
                     seed=0, per_alpha=0.6)
    rng = np.random.default_rng(0)
    for _ in range(16):
        agent.observe(rng.normal(size=24).astype(np.float32), 1, 1.0,
                      rng.normal(size=24).astype(np.float32), False,
                      np.ones(6, bool))
    assert isinstance(agent.replay, PrioritizedReplayBuffer)
    loss = agent.update()
    assert loss is not None and np.isfinite(loss)
    # TD-driven priorities replaced the entry max: leaves now differ
    leaves = agent.replay.tree[agent.replay.leaves:agent.replay.leaves + 16]
    assert len(np.unique(leaves.round(9))) > 1


# ------------------------------------------------- device-resident eval

def test_device_eval_matches_scalar_scheduler_throughput():
    """The jitted step_batch eval reproduces the Python RLScheduler metric."""
    env_cfg = EnvConfig(window=6, c_max=4)
    venv = VecCoScheduleEnv(env_cfg)
    agent = DQNAgent(venv.state_dim, venv.n_actions, DQNConfig(), seed=2)
    rng = np.random.default_rng(2)
    queues = [make_queue(ZOO, QUEUE_KINDS[i % len(QUEUE_KINDS)], 6, rng)
              for i in range(5)]
    qa = venv.queue_batch(queues)
    eval_fn = _build_eval(venv)
    env, obs, mask = venv.reset_batch(qa)
    tp = np.asarray(eval_fn(agent.params, env, obs, mask))
    sched = RLScheduler(agent, env_cfg)
    ref = np.array([relative_throughput(sched.schedule(q)) for q in queues])
    np.testing.assert_allclose(tp, ref, rtol=5e-3)

"""Vectorized pure-functional training engine: parity + determinism.

The contract: ``EnvState.step`` (jitted, float32, in-graph reward model)
reproduces the seed ``CoScheduleEnv`` semantics (Python float64 perfmodel)
transition-for-transition — identical states, masks, and done flags, and
rewards equal to numerical tolerance — and the scanned ``train_agent`` is
bit-deterministic under a fixed seed.
"""
import numpy as np
import jax
import jax.numpy as jnp
import pytest

from repro.core import (
    DQNConfig, EnvConfig, TrainConfig, make_zoo, train_agent,
)
from repro.core.agent import act_batch, DQNAgent
from repro.core.env import CoScheduleEnv, VecCoScheduleEnv
from repro.core.replay import replay_init, replay_push, replay_sample
from repro.core.workloads import QUEUE_KINDS, make_queue

ZOO = make_zoo()


def _rollout_pair(env_cfg, queue, seed):
    """Drive reference + functional envs with the same valid action stream."""
    ref = CoScheduleEnv(env_cfg)
    venv = VecCoScheduleEnv(env_cfg)
    rng = np.random.default_rng(seed)
    s_ref, m_ref = ref.reset(queue)
    st, obs, m = venv.reset(venv.queue_arrays(queue))
    np.testing.assert_allclose(np.asarray(obs), s_ref, atol=1e-6)
    assert np.array_equal(np.asarray(m), m_ref)
    while not ref.done:
        a = int(rng.choice(np.flatnonzero(m_ref)))
        s_ref, r_ref, d_ref, m_ref, _ = ref.step(a)
        st, obs, r, d, m = venv.step(st, jnp.int32(a))
        np.testing.assert_allclose(np.asarray(obs), s_ref, atol=1e-6)
        assert np.array_equal(np.asarray(m), m_ref), "mask diverged"
        assert bool(d) == d_ref, "done diverged"
        assert abs(float(r) - r_ref) <= 1e-3 + 2e-3 * abs(r_ref), (
            float(r), r_ref)


@pytest.mark.parametrize("seed", range(4))
def test_envstate_step_matches_reference_env(seed):
    env_cfg = EnvConfig(window=6, c_max=4)
    rng = np.random.default_rng(seed)
    queue = make_queue(ZOO, QUEUE_KINDS[seed % len(QUEUE_KINDS)], 6, rng)
    _rollout_pair(env_cfg, queue, seed)


def test_envstate_parity_with_padded_window():
    """Queues shorter than W exercise the padding flags and mask rows."""
    env_cfg = EnvConfig(window=8, c_max=3)
    rng = np.random.default_rng(7)
    queue = make_queue(ZOO, "balanced", 5, rng)
    _rollout_pair(env_cfg, queue, 7)


def test_envstate_invalid_action_penalty_and_no_mutation():
    env_cfg = EnvConfig(window=6, c_max=4)
    ref = CoScheduleEnv(env_cfg)
    venv = VecCoScheduleEnv(env_cfg)
    rng = np.random.default_rng(3)
    queue = make_queue(ZOO, "balanced", 6, rng)
    s_ref, m_ref = ref.reset(queue)
    st, obs, m = venv.reset(venv.queue_arrays(queue))
    bad = int(np.flatnonzero(~m_ref)[0])
    s_ref, r_ref, _, m_ref, _ = ref.step(bad)
    st, obs, r, d, m = venv.step(st, jnp.int32(bad))
    assert float(r) == r_ref == env_cfg.invalid_penalty
    np.testing.assert_allclose(np.asarray(obs), s_ref, atol=1e-6)
    assert np.array_equal(np.asarray(m), m_ref)


def test_batched_step_matches_single_step():
    """vmapped reset/step must equal per-env application."""
    env_cfg = EnvConfig(window=6, c_max=4)
    venv = VecCoScheduleEnv(env_cfg)
    rng = np.random.default_rng(0)
    queues = [make_queue(ZOO, k, 6, rng) for k in QUEUE_KINDS]
    qa = venv.queue_batch(queues)
    st_b, obs_b, m_b = venv.reset_batch(qa)
    actions = jnp.asarray([int(np.flatnonzero(np.asarray(m_b[i]))[0])
                           for i in range(len(queues))], jnp.int32)
    st2_b, obs2_b, r_b, d_b, m2_b = venv.step_batch(st_b, actions)
    for i, q in enumerate(queues):
        st, obs, m = venv.reset(venv.queue_arrays(q))
        st2, obs2, r, d, m2 = venv.step(st, actions[i])
        np.testing.assert_allclose(np.asarray(obs2_b[i]), np.asarray(obs2), atol=1e-6)
        np.testing.assert_allclose(float(r_b[i]), float(r), rtol=1e-5, atol=1e-5)
        assert np.array_equal(np.asarray(m2_b[i]), np.asarray(m2))


def test_functional_replay_wraparound():
    """Aligned ring writes wrap and overwrite the oldest block."""
    rs = replay_init(8, 3, 2)
    def block(v, n=4):
        return {"s": jnp.full((n, 3), v, jnp.float32), "a": jnp.full((n,), v, jnp.int32),
                "r": jnp.full((n,), v, jnp.float32), "s2": jnp.full((n, 3), v, jnp.float32),
                "done": jnp.zeros((n,), jnp.float32), "mask2": jnp.ones((n, 2), bool)}
    rs = replay_push(rs, block(1))
    assert int(rs.size) == 4 and int(rs.ptr) == 4
    rs = replay_push(rs, block(2))
    assert int(rs.size) == 8 and int(rs.ptr) == 0
    rs = replay_push(rs, block(3))          # wraps: overwrites block 1
    assert int(rs.size) == 8 and int(rs.ptr) == 4
    vals = set(np.asarray(rs.a).tolist())
    assert vals == {2, 3}, vals
    batch = replay_sample(rs, jax.random.PRNGKey(0), 64)
    assert batch["s"].shape == (64, 3)
    assert set(np.asarray(batch["a"]).tolist()) <= {2, 3}


def test_functional_replay_sample_respects_fill_level():
    rs = replay_init(16, 2, 2)
    rs = replay_push(rs, {"s": jnp.ones((4, 2)), "a": jnp.ones((4,), jnp.int32),
                          "r": jnp.ones((4,)), "s2": jnp.ones((4, 2)),
                          "done": jnp.zeros((4,)), "mask2": jnp.ones((4, 2), bool)})
    batch = replay_sample(rs, jax.random.PRNGKey(1), 32)
    # only the 4 filled rows may be drawn: every sampled action is 1
    assert np.asarray(batch["a"]).min() == 1


def test_unaligned_push_rejected():
    rs = replay_init(8, 3, 2)
    with pytest.raises(AssertionError):
        replay_push(rs, {"s": jnp.zeros((3, 3)), "a": jnp.zeros((3,), jnp.int32),
                         "r": jnp.zeros((3,)), "s2": jnp.zeros((3, 3)),
                         "done": jnp.zeros((3,)), "mask2": jnp.ones((3, 2), bool)})


def test_act_batch_respects_mask_and_explores():
    agent = DQNAgent(12, 6, DQNConfig(), seed=0)
    obs = jnp.zeros((32, 12))
    mask = jnp.tile(jnp.array([[False, True, False, True, False, True]]), (32, 1))
    for eps in (0.0, 1.0):
        a = act_batch(agent.params, jax.random.PRNGKey(0), obs, mask, eps)
        assert bool(np.asarray(mask)[np.arange(32), np.asarray(a)].all()), eps
    # full exploration across keys covers multiple valid actions
    seen = set()
    for k in range(5):
        a = act_batch(agent.params, jax.random.PRNGKey(k), obs, mask, 1.0)
        seen |= set(np.asarray(a).tolist())
    assert seen <= {1, 3, 5} and len(seen) > 1


def _small_cfg(seed=0):
    return TrainConfig(episodes=40, eval_every=20, n_train_queues=4,
                       batch_envs=4, update_every=4, seed=seed,
                       dqn=DQNConfig(buffer_size=512, batch_size=32,
                                     eps_decay_steps=400))


def test_train_agent_deterministic_under_fixed_seed():
    env_cfg = EnvConfig(window=4, c_max=3)
    a1, h1 = train_agent(ZOO, env_cfg, _small_cfg())
    a2, h2 = train_agent(ZOO, env_cfg, _small_cfg())
    assert h1 == h2
    for x, y in zip(jax.tree.leaves(a1.params), jax.tree.leaves(a2.params)):
        assert np.array_equal(np.asarray(x), np.asarray(y))


def test_train_agent_history_contract():
    env_cfg = EnvConfig(window=4, c_max=3)
    agent, hist = train_agent(ZOO, env_cfg, _small_cfg(seed=1))
    assert hist, "history must not be empty"
    for rec in hist:
        assert set(rec) == {"episode", "eps", "ep_reward", "eval_throughput",
                            "heldout_throughput"}
        # the zoo has held-out jobs, so the generalization metric is live
        assert np.isfinite(rec["heldout_throughput"])
    assert hist[-1]["episode"] >= 40
    assert agent.env_steps > 0 and agent.updates > 0
    # ε decayed from its start value
    assert hist[-1]["eps"] < 1.0


def test_heldout_throughput_none_without_heldout_jobs():
    """heldout=set() (e.g. re-training on a live repository) disables the
    generalization batch instead of crashing or faking a number."""
    env_cfg = EnvConfig(window=4, c_max=3)
    _, hist = train_agent(ZOO, env_cfg, _small_cfg(seed=2), heldout=set())
    assert all(rec["heldout_throughput"] is None for rec in hist)


def test_train_agent_warm_start_copies_and_continues():
    env_cfg = EnvConfig(window=4, c_max=3)
    a1, _ = train_agent(ZOO, env_cfg, _small_cfg())
    snap = [np.asarray(x).copy() for x in jax.tree.leaves(a1.params)]
    a2, h2 = train_agent(ZOO, env_cfg, _small_cfg(seed=3), warm_start=a1)
    assert h2
    # donation must not invalidate or mutate the caller's agent
    for x, y in zip(snap, jax.tree.leaves(a1.params)):
        assert np.array_equal(x, np.asarray(y))
    # warm start actually seeds the run: same seed, different outcome
    a3, _ = train_agent(ZOO, env_cfg, _small_cfg(seed=3))
    diffs = [not np.array_equal(np.asarray(x), np.asarray(y))
             for x, y in zip(jax.tree.leaves(a2.params),
                             jax.tree.leaves(a3.params))]
    assert any(diffs)


def test_train_agent_default_still_validates_job_classes():
    """strict_classes=True (default) keeps the historical guard: a pool
    missing a class fails loudly instead of silently remapping recipes."""
    ci_only = [j for j in ZOO if j.job_class == "CI"]
    env_cfg = EnvConfig(window=4, c_max=3)
    with pytest.raises(ValueError, match="no .* jobs"):
        train_agent(ci_only, env_cfg, _small_cfg(), heldout=set())


def test_train_agent_warm_start_shape_mismatch_rejected():
    env_cfg = EnvConfig(window=4, c_max=3)
    wrong = DQNAgent(10, 5, DQNConfig(), seed=0)
    with pytest.raises(AssertionError, match="warm_start"):
        train_agent(ZOO, env_cfg, _small_cfg(), warm_start=wrong)

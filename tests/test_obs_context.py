"""Arrival-aware observation contract (docs/observation.md).

Pins the three guarantees the context block ships with:

  * **zero-context parity** — with ``obs_context=True`` and no context, the
    observation prefix bit-matches the profile-only layout (scalar and
    vectorized paths), rewards/masks/done are unchanged, and the appended
    block is all-zero; with ``obs_context=False`` nothing changes at all;
  * **scalar/vectorized agreement** — a real ``DispatchContext`` produces
    the same observation and the same fit-shaped close rewards through
    ``CoScheduleEnv`` and ``VecCoScheduleEnv``;
  * **widen warm-start** — ``widen_dqn_params`` computes the identical
    Q-function at zero context, and context training is deterministic.
"""
import dataclasses

import numpy as np
import jax
import jax.numpy as jnp
import pytest

from repro.core import (
    DQNAgent, DQNConfig, DispatchContext, EnvConfig, RLScheduler,
    TrainConfig, dispatch_obs_context, make_zoo, train_agent,
    widen_dqn_params,
)
from repro.core.env import (
    CoScheduleEnv, VecCoScheduleEnv, age_feature, context_dim, depth_feature,
)
from repro.core.network import dqn_apply
from repro.core.partition import N_UNITS
from repro.core.scheduler import submission_protocol
from repro.core.workloads import make_queue

ZOO = make_zoo()

BASE = EnvConfig(window=6, c_max=3)
CTX = EnvConfig(window=6, c_max=3, obs_context=True)


def _queue(seed=0, n=6):
    return make_queue(ZOO, "balanced", n, np.random.default_rng(seed))


def _blocked_ctx(queue):
    """Half the pod busy: full-pod partitions cannot fit, narrow ones can."""
    return DispatchContext(free_units=(False,) * 4 + (True,) * 4,
                           ages_s=tuple(10.0 * i for i in range(len(queue))),
                           queue_depth=7, now_s=100.0)


# ------------------------------------------------------- zero-context parity

def test_context_dims():
    assert context_dim(BASE) == 0
    assert context_dim(CTX) == N_UNITS + 6 + 1
    assert CoScheduleEnv(CTX).state_dim == \
        CoScheduleEnv(BASE).state_dim + context_dim(CTX)
    assert VecCoScheduleEnv(CTX).state_dim == CoScheduleEnv(CTX).state_dim


@pytest.mark.parametrize("seed", range(2))
def test_zero_context_bitmatches_profile_only_scalar(seed):
    """Same queue, same random action stream: the obs prefix is bit-equal,
    the context suffix all-zero, and rewards/masks/done identical."""
    queue = _queue(seed)
    ref, ctx = CoScheduleEnv(BASE), CoScheduleEnv(CTX)
    d = ref.state_dim
    s_r, m_r = ref.reset(queue)
    s_c, m_c = ctx.reset(queue)          # context=None -> zero block
    rng = np.random.default_rng(seed)
    while True:
        assert np.array_equal(s_c[:d], s_r)
        assert not s_c[d:].any()
        assert np.array_equal(m_c, m_r)
        if ref.done:
            break
        a = int(rng.choice(np.flatnonzero(m_r)))
        s_r, r_r, d_r, m_r, _ = ref.step(a)
        s_c, r_c, d_c, m_c, _ = ctx.step(a)
        assert r_c == r_r and d_c == d_r


def test_zero_context_bitmatches_profile_only_vectorized():
    queue = _queue(1)
    ref, ctx = VecCoScheduleEnv(BASE), VecCoScheduleEnv(CTX)
    d = ref.state_dim
    st_r, o_r, m_r = ref.reset(ref.queue_arrays(queue))
    st_c, o_c, m_c = ctx.reset(ctx.queue_arrays(queue))
    rng = np.random.default_rng(1)
    while True:
        assert np.array_equal(np.asarray(o_c)[:d], np.asarray(o_r))
        assert not np.asarray(o_c)[d:].any()
        assert np.array_equal(np.asarray(m_c), np.asarray(m_r))
        valid = np.flatnonzero(np.asarray(m_r))
        if not valid.size:
            break
        a = jnp.int32(rng.choice(valid))
        st_r, o_r, r_r, done, m_r = ref.step(st_r, a)
        st_c, o_c, r_c, done_c, m_c = ctx.step(st_c, a)
        # fit table row 0 (all free) makes the shaping an exact -0.0
        assert float(r_c) == float(r_r)
        assert bool(done) == bool(done_c)
        if bool(done):
            break


# --------------------------------------------- scalar vs vectorized context

def test_real_context_scalar_vs_vectorized_parity():
    queue = _queue(2)
    dctx = _blocked_ctx(queue)
    sc, ve = CoScheduleEnv(CTX), VecCoScheduleEnv(CTX)
    s, m = sc.reset(queue, dctx)
    st, o, mv = ve.reset_ctx(ve.queue_arrays(queue),
                             dispatch_obs_context(dctx, CTX.window))
    rng = np.random.default_rng(2)
    while not sc.done:
        np.testing.assert_allclose(np.asarray(o), s, atol=1e-6)
        assert np.array_equal(np.asarray(mv), m)
        a = int(rng.choice(np.flatnonzero(m)))
        s, r, _, m, _ = sc.step(a)
        st, o, rv, _, mv = ve.step(st, jnp.int32(a))
        assert abs(float(rv) - r) <= 1e-3 + 2e-3 * abs(r), (float(rv), r)


def test_fit_penalty_blocks_nonfitting_close_only():
    """With half the pod busy a full-pod close pays ctx_fit_weight; the same
    close at zero context does not — scalar and vectorized agree exactly."""
    queue = _queue(3)
    dctx = _blocked_ctx(queue)
    blocked, free = CoScheduleEnv(CTX), CoScheduleEnv(CTX)
    s_b, m_b = blocked.reset(queue, dctx)
    s_f, m_f = free.reset(queue)
    a_sel = int(np.flatnonzero(m_b)[0])
    _, _, _, m_b, _ = blocked.step(a_sel)
    _, _, _, m_f, _ = free.step(a_sel)
    solo_close = CTX.window                   # partition 0: [{1.0},1m] solo
    assert m_b[solo_close] and m_f[solo_close]
    _, r_b, _, _, _ = blocked.step(solo_close)
    _, r_f, _, _, _ = free.step(solo_close)
    assert r_b == pytest.approx(r_f - CTX.ctx_fit_weight)

    ve = VecCoScheduleEnv(CTX)
    st, _, _ = ve.reset_ctx(ve.queue_arrays(queue),
                            dispatch_obs_context(dctx, CTX.window))
    st, _, _, _, _ = ve.step(st, jnp.int32(a_sel))
    _, _, rv, _, _ = ve.step(st, jnp.int32(solo_close))
    assert float(rv) == pytest.approx(r_b, rel=1e-4, abs=1e-3)


def test_age_depth_feature_normalization():
    assert age_feature(0.0) == 0.0
    assert age_feature(1e6 - 1.0) == pytest.approx(1.0)
    assert age_feature(-5.0) == 0.0                       # clamped
    assert depth_feature(0, 8) == 0.0
    assert depth_feature(32, 8) == 1.0
    assert depth_feature(64, 8) == 1.0                    # saturates


# ------------------------------------------------------- widen warm-start

def test_widen_dqn_params_identical_q_at_zero_context():
    agent = DQNAgent(20, 7, DQNConfig(), seed=0)
    wide = widen_dqn_params(agent.params, 6)
    assert wide["w0"].shape[0] == 26
    x = np.random.default_rng(0).normal(size=(4, 20)).astype(np.float32)
    xw = np.concatenate([x, np.zeros((4, 6), np.float32)], axis=1)
    np.testing.assert_allclose(np.asarray(dqn_apply(agent.params, jnp.asarray(x))),
                               np.asarray(dqn_apply(wide, jnp.asarray(xw))),
                               rtol=1e-6, atol=1e-6)


def _ctx_train_cfg(seed=0):
    return TrainConfig(episodes=30, eval_every=15, n_train_queues=4,
                       batch_envs=4, update_every=4, seed=seed,
                       obs_context=True,
                       dqn=DQNConfig(buffer_size=512, batch_size=32,
                                     eps_decay_steps=400))


def test_train_agent_obs_context_deterministic_and_warmstartable():
    env_cfg = EnvConfig(window=4, c_max=3)
    a1, h1 = train_agent(ZOO, env_cfg, _ctx_train_cfg())
    a2, h2 = train_agent(ZOO, env_cfg, _ctx_train_cfg())
    assert h1 == h2
    for x, y in zip(jax.tree.leaves(a1.params), jax.tree.leaves(a2.params)):
        assert np.array_equal(np.asarray(x), np.asarray(y))
    # widen a profile-only agent into the context input and keep training
    base_cfg = dataclasses.replace(_ctx_train_cfg(), obs_context=False)
    base, _ = train_agent(ZOO, env_cfg, base_cfg)
    extra = context_dim(dataclasses.replace(env_cfg, obs_context=True))
    warm = DQNAgent(base.params["w0"].shape[0] + extra,
                    base.params["wA"].shape[1], base.cfg, seed=0)
    warm.params = widen_dqn_params(base.params, extra)
    warm.target_params = widen_dqn_params(base.target_params, extra)
    warm.opt = {"m": widen_dqn_params(base.opt["m"], extra),
                "v": widen_dqn_params(base.opt["v"], extra),
                "t": base.opt["t"]}
    a3, h3 = train_agent(ZOO, env_cfg, _ctx_train_cfg(seed=1), warm_start=warm)
    assert h3 and np.isfinite(h3[-1]["eval_throughput"])


# ------------------------------------------- protocol context re-chunking

def test_submission_protocol_rechunks_context():
    """Ages follow the *profiled* subset and later chunks inflate depth."""
    from repro.core.profiles import ProfileRepository

    repo = ProfileRepository()
    jobs = _queue(4, 5)
    for j in jobs[1:]:                     # jobs[0] stays unprofiled
        repo.insert(f"bin://{j.name}#{id(j)}", j)
    paths = ["bin://ghost"] + [f"bin://{j.name}#{id(j)}" for j in jobs[1:]]
    subs = list(zip(paths, [None] * len(paths)))
    ctx = DispatchContext(free_units=(True,) * N_UNITS,
                          ages_s=(99.0, 1.0, 2.0, 3.0, 4.0),
                          queue_depth=10, now_s=0.0)
    seen = []

    def plan(chunk, chunk_ctx):
        seen.append((tuple(j.name for j in chunk), chunk_ctx))
        from repro.core.problem import Schedule
        from repro.core.partition import solo_partition
        s = Schedule()
        for j in chunk:
            s.add([j], solo_partition())
        return s

    submission_protocol(repo, subs, plan, window=3, context=ctx)
    assert len(seen) == 2                  # 4 profiled jobs, window 3
    names1, ctx1 = seen[0]
    names2, ctx2 = seen[1]
    assert len(names1) == 3 and len(names2) == 1
    # the unprofiled ghost's 99.0 age is filtered out
    assert ctx1.ages_s == (1.0, 2.0, 3.0)
    assert ctx2.ages_s == (4.0,)
    # chunk 1 sees the 1 profiled job still waiting behind it
    assert ctx1.queue_depth == 11 and ctx2.queue_depth == 10


def test_rl_scheduler_accepts_context_for_profile_only_agent():
    """A context snapshot must be harmless for a context-blind agent."""
    env_cfg = EnvConfig(window=4, c_max=3)
    agent = DQNAgent(CoScheduleEnv(env_cfg).state_dim,
                     CoScheduleEnv(env_cfg).n_actions, DQNConfig(), seed=0)
    sched = RLScheduler(agent, env_cfg)
    queue = _queue(5, 4)
    ctx = DispatchContext(free_units=(True,) * N_UNITS,
                          ages_s=(0.0,) * 4, queue_depth=0)
    s1 = sched.schedule(queue)
    s2 = sched.schedule(queue, ctx)
    assert [p.label for p in s1.partitions] == [p.label for p in s2.partitions]

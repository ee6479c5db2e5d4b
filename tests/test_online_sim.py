"""Online cluster simulator: traces, event loop, policies, re-training.

Determinism contract: a trace is fully determined by its seed and the
simulator adds no randomness of its own, so (trace, policy) pairs replay
bit-identically.  Accounting contract: every arrival is dispatched exactly
once, time sharing's busy time equals the summed solo work, and any policy
honoring the constraint-1 guard retires the trace with no more pod-busy
time than time sharing.
"""
import functools

import jax
import numpy as np
import pytest

from repro.core import DQNAgent, DQNConfig, EnvConfig, TrainConfig, make_zoo, train_agent
from repro.core.env import CoScheduleEnv
from repro.online import (
    Arrival, ClusterSimulator, GreedyPackerPolicy, OnlineRetrainer,
    RLDispatchPolicy, StaticPartitionPolicy, TRACE_FAMILIES,
    TimeSharingPolicy, heavy_tailed_trace, poisson_trace,
)

ZOO = make_zoo()
ENV_CFG = EnvConfig(window=4, c_max=3)


def _fresh_agent(seed=0):
    env = CoScheduleEnv(ENV_CFG)
    return DQNAgent(env.state_dim, env.n_actions, DQNConfig(), seed=seed)


def _tiny_train_cfg(seed=0, episodes=20):
    # mirrors the engine shape of the other suites so the compiled scan is
    # shared across test files (same dqn/batch/update cadence)
    return TrainConfig(episodes=episodes, eval_every=episodes,
                       n_train_queues=2, n_heldout_queues=0,
                       strict_classes=False, batch_envs=4,
                       update_every=4, seed=seed,
                       dqn=DQNConfig(buffer_size=512, batch_size=32,
                                     eps_decay_steps=400))


@functools.lru_cache(maxsize=1)
def _trained_agent():
    agent, _ = train_agent(ZOO, ENV_CFG, _tiny_train_cfg(episodes=40),
                           heldout=set())
    return agent


# ------------------------------------------------------------------- traces

@pytest.mark.parametrize("family", sorted(TRACE_FAMILIES))
def test_trace_families_deterministic_sorted_and_sized(family):
    fn = TRACE_FAMILIES[family]
    t1 = fn(ZOO, n=30, seed=5)
    t2 = fn(ZOO, n=30, seed=5)
    assert [a.t for a in t1] == [a.t for a in t2]
    assert [a.binary for a in t1] == [a.binary for a in t2]
    assert len(t1) == 30
    times = [a.t for a in t1]
    assert times == sorted(times) and times[0] > 0
    assert all(a.binary.startswith("bin://") for a in t1)
    # different seed -> different arrivals
    t3 = fn(ZOO, n=30, seed=6)
    assert [a.t for a in t3] != times


def test_trace_mix_weights_dominant_class():
    trace = poisson_trace(ZOO, n=600, mix="ci", seed=1)
    frac = np.mean([a.profile.job_class == "CI" for a in trace])
    assert 0.4 < frac < 0.6, frac


def test_heavy_tailed_trace_scales_job_steps():
    trace = heavy_tailed_trace(ZOO, n=200, seed=2)
    scaled = [a for a in trace if "@x" in a.profile.name]
    assert scaled, "no elephants drawn in 200 arrivals"
    base = {j.name: j.steps for j in ZOO}
    for a in scaled:
        root, _, sfx = a.profile.name.rpartition("@x")
        assert a.profile.steps == base[root] * int(sfx)
    # one profile object per (binary, scale): repository keys stay coherent
    by_bin = {}
    for a in trace:
        assert by_bin.setdefault(a.binary, a.profile) is a.profile


# ---------------------------------------------------------------- simulator

def test_simulator_deterministic_given_seeded_trace():
    trace = poisson_trace(ZOO, n=25, seed=3)
    r1 = ClusterSimulator(TimeSharingPolicy(), window=4).run(trace)
    r2 = ClusterSimulator(TimeSharingPolicy(), window=4).run(trace)
    assert r1.summary() == r2.summary()
    assert [(j.dispatch, j.finish) for j in r1.jobs] == \
           [(j.dispatch, j.finish) for j in r2.jobs]


def test_time_sharing_accounting_invariants():
    trace = poisson_trace(ZOO, n=25, seed=3)
    res = ClusterSimulator(TimeSharingPolicy(), window=4).run(trace)
    assert len(res.jobs) == 25
    assert all(j.group_size == 1 for j in res.jobs)
    assert np.isclose(res.busy_time, res.total_solo_time, rtol=1e-9)
    for j in res.jobs:
        assert j.dispatch >= j.arrival - 1e-9
        assert j.finish > j.dispatch
    assert 0.0 < res.utilization <= 1.0 + 1e-9
    assert res.makespan >= res.busy_time - 1e-6
    # timeline covers exactly the busy span
    assert np.isclose(sum(s.t1 - s.t0 for s in res.timeline), res.busy_time)


def test_coincident_arrivals_share_one_dispatch_window():
    """All events at one timestamp drain before dispatching: a batch
    submission must be visible to a single policy window, not split."""
    trace = [Arrival(t=10.0, binary=f"bin://co{i}", profile=ZOO[i])
             for i in range(4)]
    res = ClusterSimulator(TimeSharingPolicy(), window=4).run(trace)
    assert res.dispatches == 1
    assert all(j.dispatch >= 10.0 for j in res.jobs)


def test_reused_arrival_object_keeps_distinct_records():
    """Records are keyed by trace position, not object identity: submitting
    the same Arrival instance twice must yield two complete job records."""
    a = Arrival(t=10.0, binary="bin://dup", profile=ZOO[0])
    res = ClusterSimulator(TimeSharingPolicy(), window=4).run([a, a])
    assert len(res.jobs) == 2
    for j in res.jobs:
        assert np.isfinite(j.dispatch) and np.isfinite(j.finish)
    assert np.isfinite(res.makespan) and res.throughput > 0


def test_first_sight_jobs_run_solo_and_enter_repository():
    trace = poisson_trace(ZOO, n=30, seed=4)
    pol = RLDispatchPolicy(_fresh_agent(), ENV_CFG)
    res = ClusterSimulator(pol, window=4).run(trace)
    distinct = {a.binary for a in trace}
    assert len(pol.repository) == len(distinct)
    # PolicyStats stay live through the delegated RL protocol: every binary
    # is profiled exactly once, everything else is planned
    assert pol.stats.unprofiled_jobs == len(distinct)
    assert pol.stats.planned_jobs == len(trace) - len(distinct)
    assert pol.scheduler.stats.unprofiled_jobs == len(distinct)
    first_seen: dict[str, object] = {}
    for j in sorted(res.jobs, key=lambda j: j.dispatch):
        first_seen.setdefault(j.binary, j)
    for j in first_seen.values():
        assert j.group_size == 1, f"{j.binary} first sight not solo"


@pytest.mark.parametrize("make_policy", [
    lambda: RLDispatchPolicy(_fresh_agent(), ENV_CFG),
    lambda: GreedyPackerPolicy(c_max=3),
    lambda: StaticPartitionPolicy("mig_only", c_max=3),
])
def test_guarded_policies_use_no_more_busy_time_than_time_sharing(make_policy):
    """Constraint 1 (CoRunTime <= SoloRunTime per group) bounds total pod
    work by time sharing's, regardless of dispatch boundaries."""
    trace = poisson_trace(ZOO, n=25, seed=5)
    ts = ClusterSimulator(TimeSharingPolicy(), window=4).run(trace)
    res = ClusterSimulator(make_policy(), window=4).run(trace)
    assert len(res.jobs) == len(ts.jobs)
    assert res.busy_time <= ts.busy_time * (1.0 + 1e-9)


def test_trained_rl_beats_time_sharing_on_poisson_throughput():
    """Makespan-derived throughput: the acceptance-criterion shape, small."""
    trace = poisson_trace(ZOO, n=40, load=1.3, seed=6)
    ts = ClusterSimulator(TimeSharingPolicy(), window=4).run(trace)
    rl = ClusterSimulator(RLDispatchPolicy(_trained_agent(), ENV_CFG),
                          window=4).run(trace)
    assert rl.throughput >= ts.throughput * 0.99, (
        rl.throughput, ts.throughput)


# --------------------------------------------------------------- re-training

def test_retrainer_fires_and_hot_swaps_params():
    trace = poisson_trace(ZOO, n=30, load=1.3, seed=7)
    agent = _trained_agent()
    before = [np.asarray(x).copy() for x in jax.tree.leaves(agent.params)]
    pol = RLDispatchPolicy(agent, ENV_CFG)
    rt = OnlineRetrainer(policy=pol, train_cfg=_tiny_train_cfg(episodes=20),
                         interval_s=trace[-1].t / 3.0, min_jobs=3)
    res = ClusterSimulator(pol, window=4, tick_interval_s=rt.interval_s,
                           on_tick=rt).run(trace)
    assert res.ticks >= 1
    assert len(rt.history) >= 1
    for h in rt.history:
        assert h["repository_jobs"] >= 3
        assert np.isfinite(h["train_eval_throughput"])
    # the policy now serves a different (re-trained) agent...
    assert pol.agent is not agent
    # ...and warm-start copied rather than donated: original params intact
    after = jax.tree.leaves(agent.params)
    for x, y in zip(before, after):
        assert np.array_equal(x, np.asarray(y))


def test_retrainer_waits_for_min_jobs():
    trace = poisson_trace(ZOO, n=12, seed=8)
    pol = RLDispatchPolicy(_fresh_agent(), ENV_CFG)
    rt = OnlineRetrainer(policy=pol, train_cfg=_tiny_train_cfg(),
                         interval_s=1.0, min_jobs=10**6)
    res = ClusterSimulator(pol, window=4, tick_interval_s=rt.interval_s,
                           on_tick=rt).run(trace)
    assert res.ticks > 0 and rt.history == []

"""The wall-clock span recorder (``repro.spans``) and its spans on the live
dispatch path.

Contract: off (the default) a span is one shared no-op that reads no clock
and calls no JAX; on, spans nest by parent and root and are summarised per
name; and the recorder observes without steering, so a simulation makes
the same decisions with it on or off.
"""
import dataclasses
import random
from pathlib import Path

import jax
import pytest

from repro import spans
from repro.core import DQNAgent, DQNConfig, EnvConfig, RLScheduler, make_zoo
from repro.core.env import CoScheduleEnv
from repro.core.perfmodel import corun_time, solo_run_time
from repro.online import ClusterSimulator, RLDispatchPolicy, poisson_trace

ZOO = make_zoo()
ENV_CFG = EnvConfig(window=4, c_max=3)


@pytest.fixture(autouse=True)
def _recorder_off():
    spans.disable()
    spans.reset()
    yield
    spans.disable()
    spans.reset()


class _Clock:
    """A ``time`` stand-in whose ``perf_counter_ns`` steps by 10 ns."""

    def __init__(self):
        self.t = 0

    def perf_counter_ns(self):
        self.t += 10
        return self.t


class _Refuse:
    def __getattr__(self, name):
        raise AssertionError(f"the recorder touched {name} while off")

    def __call__(self, *a, **kw):
        raise AssertionError("the recorder called the profiler while off")


def _agent(seed=0):
    env = CoScheduleEnv(ENV_CFG)
    return DQNAgent(env.state_dim, env.n_actions, DQNConfig(), seed=seed)


def _serve(trace, seed=0):
    policy = RLDispatchPolicy(_agent(seed), ENV_CFG)
    return ClusterSimulator(policy, window=ENV_CFG.window).run(trace)


def test_off_returns_the_shared_noop_and_records_nothing(monkeypatch):
    monkeypatch.setattr(spans, "time", _Refuse())
    monkeypatch.setattr(spans, "_annotation", _Refuse())
    a, b = spans.span("repro.a"), spans.span("repro.b")
    assert a is spans.NOOP and b is spans.NOOP
    with a, b:
        spans.count("repro.n", 3)
    assert spans.records() == [] and spans.summary() == {}
    assert spans.counters() == {}


def test_nested_spans_parent_root_and_self_time(monkeypatch):
    spans.enable()
    monkeypatch.setattr(spans, "time", _Clock())
    with spans.span("repro.outer"):          # start 10
        with spans.span("repro.mid"):        # start 20
            with spans.span("repro.leaf"):   # 30 .. 40
                pass
        # mid ends at 50
        with spans.span("repro.leaf"):       # 60 .. 70
            pass
    # outer ends at 80
    with spans.span("repro.other"):          # a second root: 90 .. 100
        pass
    by = {}
    for s in spans.records():
        by.setdefault(s.name, []).append(s)
    (outer,), (mid,), (other,) = by["repro.outer"], by["repro.mid"], \
        by["repro.other"]
    leaf1, leaf2 = by["repro.leaf"]
    assert outer.parent is None and outer.root == outer.id
    assert mid.parent == outer.id and mid.root == outer.id
    assert leaf1.parent == mid.id and leaf2.parent == outer.id
    assert {leaf1.root, leaf2.root} == {outer.id}
    assert other.parent is None and other.root == other.id != outer.id
    summ = spans.summary()
    assert summ["repro.outer"]["total_s"] == pytest.approx(70e-9)
    # outer 70 ns less mid (30) and the second leaf (10)
    assert summ["repro.outer"]["self_s"] == pytest.approx(30e-9)
    assert summ["repro.mid"]["self_s"] == pytest.approx(20e-9)
    assert summ["repro.leaf"]["self_s"] == pytest.approx(20e-9)
    assert summ["repro.leaf"]["median_us"] == pytest.approx(0.01)


def test_summary_counts_counters_and_reset():
    spans.enable()
    for _ in range(3):
        with spans.span("repro.x"):
            with spans.span("repro.y"):
                pass
    with spans.span("repro.y"):
        pass
    spans.count("repro.n", 2)
    spans.count("repro.n", 5)
    spans.disable()
    with spans.span("repro.x"):               # off again: not recorded
        spans.count("repro.n", 9)
    summ = spans.summary()
    assert {k: v["count"] for k, v in summ.items()} == {"repro.x": 3,
                                                        "repro.y": 4}
    assert set(summ["repro.x"]) == {"count", "total_s", "self_s",
                                    "median_us"}
    assert spans.counters() == {"repro.n": [2, 5]}
    spans.reset()
    assert spans.summary() == {} and spans.counters() == {}


def test_spans_observe_and_never_steer():
    trace = poisson_trace(ZOO, n=60, load=1.25, seed=3)
    off = _serve(trace)
    spans.enable()
    on = _serve(trace)
    spans.disable()
    assert [dataclasses.astuple(r) for r in on.jobs] == \
        [dataclasses.astuple(r) for r in off.jobs]
    assert on.timeline == off.timeline
    assert (on.dispatches, on.backfills, on.refits, on.busy_time) == \
        (off.dispatches, off.backfills, off.refits, off.busy_time)
    assert on.summary() == off.summary()
    summ = spans.summary()
    assert summ["repro.sim.run"]["count"] == 1
    assert summ["repro.sim.prepare"]["count"] == 1
    assert summ["repro.sim.window"]["count"] == on.dispatches
    assert summ["repro.policy.decide"]["count"] == on.dispatches
    episodes = summ["repro.sched.episode"]["count"]
    assert summ["repro.sched.guard"]["count"] == episodes
    # one device round trip per episode, and no scalar env or act on the
    # serving path
    for name in ("repro.agent.episode", "repro.agent.episode.put",
                 "repro.agent.episode.launch", "repro.agent.episode.fetch"):
        assert summ[name]["count"] == episodes
    steps = spans.counters()["repro.sched.steps"]
    assert len(steps) == episodes and all(n >= 2 for n in steps)
    assert "repro.agent.act" not in summ and "repro.sched.env" not in summ
    assert all(s.name.startswith("repro.") for s in spans.records())
    (run,) = [s for s in spans.records() if s.name == "repro.sim.run"]
    assert {s.root for s in spans.records()} == {run.id}


@pytest.mark.parametrize("seed", [0, 1, 2])
def test_guard_counts_its_groups_and_the_splits(seed, monkeypatch):
    """One ``schedule`` call: ``repro.sched.guard_groups`` takes the plan's
    multi-job groups and ``repro.sched.fallback`` the groups split, which
    is what ``stats.fallback_groups`` adds."""
    sched = RLScheduler(_agent(seed), ENV_CFG)
    plans = []
    guard = sched._enforce_constraints

    def spy(plan):
        plans.append(plan)
        return guard(plan)

    monkeypatch.setattr(sched, "_enforce_constraints", spy)
    rng = random.Random(0)
    spans.enable()
    groups = splits = 0
    for _ in range(6):
        spans.reset()
        before = sched.stats.fallback_groups
        out = sched.schedule(rng.sample(ZOO, 4))
        multi = [(g, p) for g, p in zip(plans[-1].groups, plans[-1].partitions)
                 if len(g) > 1]
        split = sum(corun_time(g, p) > solo_run_time(g) for g, p in multi)
        counters = spans.counters()
        assert counters["repro.sched.guard_groups"] == [len(multi)]
        assert counters["repro.sched.fallback"] == [split]
        assert sched.stats.fallback_groups - before == split
        assert len(out.groups) == len(plans[-1].groups) + sum(
            len(g) - 1 for g, p in multi if corun_time(g, p) > solo_run_time(g))
        groups, splits = groups + len(multi), splits + split
    assert groups >= splits > 0


def test_act_spans_nest_put_launch_fetch():
    env = CoScheduleEnv(ENV_CFG)
    agent = _agent()
    state, mask = env.reset(list(ZOO[:4]))
    want = agent.act(state, mask, greedy=True)
    spans.enable()
    got = agent.act(state, mask, greedy=True)
    spans.disable()
    assert got == want
    recs = spans.records()
    (act,) = [s for s in recs if s.name == "repro.agent.act"]
    kids = sorted((s for s in recs if s.parent == act.id),
                  key=lambda s: s.start_ns)
    assert [s.name for s in kids] == ["repro.agent.act.put",
                                      "repro.agent.act.launch",
                                      "repro.agent.act.fetch"]
    assert act.parent is None and all(s.root == act.id for s in kids)
    assert act.start_ns <= kids[0].start_ns
    assert all(a.end_ns <= b.start_ns for a, b in zip(kids, kids[1:]))
    assert kids[-1].end_ns <= act.end_ns


def test_episode_spans_nest_put_launch_fetch():
    agent = _agent()
    sched = RLScheduler(agent, ENV_CFG)
    packed = sched._pack(list(ZOO[:4]), None)
    want = agent.greedy_episode(packed, ENV_CFG.window, ENV_CFG.c_max, False)
    spans.enable()
    got = agent.greedy_episode(packed, ENV_CFG.window, ENV_CFG.c_max, False)
    spans.disable()
    assert (got == want).all()
    recs = spans.records()
    (ep,) = [s for s in recs if s.name == "repro.agent.episode"]
    kids = sorted((s for s in recs if s.parent == ep.id),
                  key=lambda s: s.start_ns)
    assert [s.name for s in kids] == ["repro.agent.episode.put",
                                      "repro.agent.episode.launch",
                                      "repro.agent.episode.fetch"]
    assert ep.parent is None and all(s.root == ep.id for s in kids)
    assert ep.start_ns <= kids[0].start_ns
    assert all(a.end_ns <= b.start_ns for a, b in zip(kids, kids[1:]))
    assert kids[-1].end_ns <= ep.end_ns


def test_spans_land_on_the_profiler_host_plane(tmp_path):
    from jax.profiler import ProfileData

    agent = _agent()
    env = CoScheduleEnv(ENV_CFG)
    state, mask = env.reset(list(ZOO[:4]))
    agent.act(state, mask, greedy=True)          # compile outside the trace
    spans.enable()
    jax.profiler.start_trace(str(tmp_path))
    try:
        agent.act(state, mask, greedy=True)
    finally:
        jax.profiler.stop_trace()
        spans.disable()
    (path,) = Path(tmp_path).rglob("*.xplane.pb")
    pd = ProfileData.from_file(str(path))
    host = {}
    for plane in pd.planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    if e.name.startswith("repro."):
                        host[e.name] = (e.start_ns, e.start_ns + e.duration_ns)
    assert set(host) == {"repro.agent.act", "repro.agent.act.put",
                         "repro.agent.act.launch", "repro.agent.act.fetch"}
    lo, hi = host["repro.agent.act"]
    assert all(lo <= s <= e <= hi for s, e in host.values())
    # the recorder's own durations match the profiler's to the microsecond
    # scale: both time the same enter/exit on the host
    (act,) = [s for s in spans.records() if s.name == "repro.agent.act"]
    assert abs((act.end_ns - act.start_ns) - (hi - lo)) < 1e6


def test_online_sim_profile_reads_the_recorder():
    from benchmarks.online_sim import _simulate

    trace = poisson_trace(ZOO, n=40, load=1.25, seed=4)
    policy = RLDispatchPolicy(_agent(), ENV_CFG)
    out = _simulate(policy, trace, ENV_CFG.window, profile=True)
    prof = out["profile"]
    assert set(prof) == {"policy_s", "retrain_s", "sim_s"}
    assert 0.0 < prof["policy_s"] <= out["sim_wall_s"]
    assert prof["retrain_s"] == 0.0
    assert prof["sim_s"] == pytest.approx(
        out["sim_wall_s"] - prof["policy_s"])
    assert spans.span("repro.x") is spans.NOOP and spans.records() == []
    assert "decide" not in vars(policy)       # no instance patch left behind
    plain = _simulate(RLDispatchPolicy(_agent(), ENV_CFG), trace,
                      ENV_CFG.window)
    assert out["throughput"] == plain["throughput"]

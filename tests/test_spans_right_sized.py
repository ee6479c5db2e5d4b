"""The spans of the right-sized, arrival-aware dispatch path: window
packing and its context block, width fitting of the plan, and EASY
backfill.

Off (the default) they record nothing and a run makes the decisions it
makes with them on; on, each is recorded where the work happens, and the
counters agree with the simulation's own totals.
"""
import dataclasses

import pytest

from repro import spans
from repro.core import DQNAgent, DQNConfig, EnvConfig, make_zoo
from repro.core.env import CoScheduleEnv
from repro.core.partition import N_UNITS, enumerate_partitions
from repro.core.problem import Schedule
from repro.core.scheduler import to_placements
from repro.online import ClusterSimulator, RLDispatchPolicy
from repro.online.traces import fragmented_trace

ZOO = make_zoo()
ENV_CFG = EnvConfig(window=4, c_max=3, obs_context=True)


@pytest.fixture(autouse=True)
def _recorder_off():
    spans.disable()
    spans.reset()
    yield
    spans.disable()
    spans.reset()


def _serve(trace):
    env = CoScheduleEnv(ENV_CFG)
    agent = DQNAgent(env.state_dim, env.n_actions, DQNConfig(), seed=0)
    policy = RLDispatchPolicy(agent, ENV_CFG)
    return ClusterSimulator(policy, window=ENV_CFG.window).run(trace)


def _astuple(res):
    return ([dataclasses.astuple(r) for r in res.jobs], res.timeline,
            res.dispatches, res.backfills, res.refits, res.busy_time)


@pytest.fixture(scope="module")
def trace():
    return fragmented_trace(ZOO, n=120, load=1.5, seed=5)


def test_off_records_nothing_and_on_never_steers(trace):
    off = _serve(trace)
    assert spans.records() == [] and spans.counters() == {}
    spans.enable()
    on = _serve(trace)
    spans.disable()
    assert _astuple(on) == _astuple(off)
    assert on.summary() == off.summary()
    again = _serve(trace)                   # off again: nothing added
    assert _astuple(again) == _astuple(off)
    names = {s.name for s in spans.records()}
    assert {"repro.sched.pack", "repro.sched.context", "repro.policy.fit",
            "repro.sim.backfill"} <= names


def test_pack_context_and_fit_nest_where_the_work_happens(trace):
    spans.enable()
    res = _serve(trace)
    spans.disable()
    recs = spans.records()
    by_id = {s.id: s for s in recs}
    summ = spans.summary()
    episodes = summ["repro.sched.episode"]["count"]
    assert summ["repro.sched.pack"]["count"] == episodes
    for s in recs:
        if s.name == "repro.sched.pack":
            assert by_id[s.parent].name == "repro.sched.episode"
        if s.name == "repro.sched.context":
            assert by_id[s.parent].name == "repro.sched.pack"
        if s.name == "repro.policy.fit":
            assert by_id[s.parent].name == "repro.policy.decide"
        if s.name == "repro.sim.backfill":
            assert by_id[s.root].name == "repro.sim.run"
    # every packed window of a served trace carries a dispatch snapshot
    assert summ["repro.sched.context"]["count"] == episodes
    assert summ["repro.policy.fit"]["count"] == res.dispatches
    shrunk = spans.counters()["repro.policy.shrunk"]
    assert len(shrunk) == res.dispatches
    narrow = sum(1 for r in res.jobs if r.units < N_UNITS
                 and r.group_size == 1)
    assert sum(shrunk) >= 1 and narrow >= 1


def test_backfilled_counter_sums_to_the_results_backfills(trace):
    spans.enable()
    res = _serve(trace)
    spans.disable()
    started = spans.counters()["repro.sim.backfilled"]
    assert len(started) == spans.summary()["repro.sim.backfill"]["count"]
    assert res.backfills > 0
    assert sum(started) == res.backfills
    assert sum(r.backfilled for r in res.jobs) >= res.backfills


def test_fit_counts_the_slices_it_shrinks():
    narrow = [dataclasses.replace(j, name=f"{j.name}@u{w}",
                                  meta={**j.meta, "units": w})
              for j, w in zip(ZOO[:3], (1, 2, 8))]
    pair = next(p for p in enumerate_partitions(3)
                if p.arity == 2 and all(len(s.shares) == 1
                                        for s in p.slices))
    sched = Schedule()
    sched.add([narrow[0]], enumerate_partitions(1)[0])
    sched.add([narrow[1], narrow[2]], pair)
    spans.enable()
    pls = to_placements(sched)
    spans.disable()
    assert [p.partition.total_units for p in pls][0] == 1
    want = 1 + sum(1 for j, s in zip([narrow[1], narrow[2]], pair.slices)
                   if j.requested_units < s.units)
    assert spans.counters()["repro.policy.shrunk"] == [want]
    assert spans.summary()["repro.policy.fit"]["count"] == 1

"""Fleet-scale serving: N-pod claim model, routers, unified dispatch API.

Contracts pinned here:

* **Pod isolation** — a claim never spans pods: every Segment's slice
  ranges lie inside its pod's width, and a job's record carries the pod
  the router assigned it.
* **Router determinism** — routers are deterministic functions of
  (arrival, view, seed): same seed replays identical assignments, a
  different hash seed produces a different (but still deterministic)
  spread, and width eligibility never lands a request on a too-narrow
  pod.
* **Single-pod parity** — ``SimConfig(pods=(8,))`` (and the legacy
  keyword construction path) is bit-identical to the historical
  single-pod simulator on every trace family.
* **Unified dispatch API** — ``decide()`` is every policy's one entry
  point: it returns the planned schedule with its width-fitted
  placements, counts every submission as first sight or planned, and
  never warns.
* **Vectorized fleet parity** — the hash-routed fleet decomposes into
  independent per-pod lanes, so ``VectorizedFleetSimulator`` matches the
  heap fleet's decisions exactly and its clock to float32.
"""
import dataclasses
import math

import numpy as np
import pytest

from strategies import ZOO, make_trace

from repro.core import DQNAgent, EnvConfig
from repro.core.env import CoScheduleEnv
from repro.core.partition import N_UNITS, Partition, Slice, slice_label
from repro.core.scheduler import to_placements
from repro.online import (
    ClusterSimulator, RLDispatchPolicy, SimConfig, StaticPartitionPolicy,
    TRACE_FAMILIES, TimeSharingPolicy, VectorizedFleetSimulator, make_router,
    poisson_trace,
)
from repro.online.policies import DispatchPolicy, GreedyPackerPolicy
from repro.online.router import (
    FleetView, PodView, fragmentation_units,
)

HET = (8, 8, 4, 4)          # the heterogeneous fleet under test


def _trace(n=80, seed=3, load=1.0, pods=HET, fam="fragmented"):
    return make_trace(fam, n, seed, load, capacity=sum(pods) / N_UNITS)


def _run(pods=HET, router="frag", seed=0, trace=None, policy=None):
    cfg = SimConfig(pods=pods, router=router, router_seed=seed)
    sim = ClusterSimulator(policy or TimeSharingPolicy(), cfg)
    return sim.run(trace if trace is not None else _trace(pods=pods))


# ----------------------------------------------------------- pod isolation

@pytest.mark.parametrize("router", ["hash", "least_loaded", "frag"])
def test_claims_never_span_pods(router):
    res = _run(router=router)
    assert res.pods == HET
    for seg in res.timeline:
        width = res.pods[seg.pod]
        for start, w in seg.slices:
            assert 0 <= start and start + w <= width, (seg.pod, seg.slices)


@pytest.mark.parametrize("router", ["hash", "least_loaded", "frag"])
def test_every_job_served_on_an_eligible_pod(router):
    res = _run(router=router)
    for rec in res.jobs:
        assert 0 <= rec.pod < len(res.pods)
        assert not math.isnan(rec.finish)
        # the slice the job ran on fits its pod
        assert rec.units <= res.pods[rec.pod]


def test_slice_busy_spans_fleet_axis_and_upper_units_stay_idle():
    res = _run()
    assert len(res.slice_busy_s) == sum(HET)
    # per-pod busy never exceeds what the pod's units could serve
    offs = res.pod_offsets
    m = res.makespan
    for p, w in enumerate(HET):
        for u in range(w):
            assert res.slice_busy_s[offs[p] + u] <= m + 1e-6


# ------------------------------------------------------- router determinism

def test_router_fixed_seed_replays_identically():
    a = _run(router="hash", seed=7)
    b = _run(router="hash", seed=7)
    assert [r.pod for r in a.jobs] == [r.pod for r in b.jobs]
    assert a.summary() == b.summary()


def test_hash_router_seed_changes_assignment():
    a = _run(router="hash", seed=0)
    b = _run(router="hash", seed=1)
    assert [r.pod for r in a.jobs] != [r.pod for r in b.jobs]


def test_hash_router_is_tenant_affine_and_width_eligible():
    res = _run(router="hash")
    by_binary = {}
    for rec in res.jobs:
        assert by_binary.setdefault(rec.binary, rec.pod) == rec.pod
    # full-width requests never land on a narrow pod
    router = make_router("hash")
    view = FleetView(pods=tuple(
        PodView(idx=i, width=w, free=(True,) * w, pending=0, ready=0,
                queue_units=0, busy_units=0) for i, w in enumerate(HET)))
    for a in _trace():
        p = router.route(a, view)
        assert HET[p] >= min(a.profile.requested_units, N_UNITS)


def test_frag_router_prefers_snug_pod_for_mice():
    # an empty 4-pod fragments less than an empty 8-pod under a 1-unit job
    empty4 = (True,) * 4
    empty8 = (True,) * 8
    after4 = (False,) + (True,) * 3
    after8 = (False,) + (True,) * 7
    d4 = fragmentation_units(after4) - fragmentation_units(empty4)
    d8 = fragmentation_units(after8) - fragmentation_units(empty8)
    assert d4 < d8


# ------------------------------------------------------- single-pod parity

@pytest.mark.parametrize("family", sorted(TRACE_FAMILIES))
def test_single_pod_fleet_bit_matches_legacy_simulator(family):
    trace = TRACE_FAMILIES[family](ZOO, n=40, seed=2, load=1.25)
    legacy = ClusterSimulator(TimeSharingPolicy(), window=8).run(trace)
    fleet = ClusterSimulator(
        TimeSharingPolicy(), SimConfig(pods=(N_UNITS,))).run(trace)
    assert legacy.summary() == fleet.summary()
    assert [(r.dispatch, r.finish, r.units) for r in legacy.jobs] == \
           [(r.dispatch, r.finish, r.units) for r in fleet.jobs]


def test_capacity_scaled_poisson_halves_interarrivals_exactly():
    t1 = poisson_trace(ZOO, n=30, seed=4, load=1.0, capacity=1.0)
    t2 = poisson_trace(ZOO, n=30, seed=4, load=1.0, capacity=2.0)
    assert np.allclose([a.t for a in t2],
                       [a.t / 2.0 for a in t1], rtol=0, atol=0)
    assert [a.binary for a in t1] == [a.binary for a in t2]


# ----------------------------------------------------------- configuration

def test_simconfig_is_frozen_and_validates():
    cfg = SimConfig(pods=[8, 4])            # lists coerce to tuples
    assert cfg.pods == (8, 4)
    assert cfg.n_pods == 2 and cfg.total_units == 12
    with pytest.raises(dataclasses.FrozenInstanceError):
        cfg.window = 3
    with pytest.raises(AssertionError):
        SimConfig(pods=(4, 4))              # widest pod must be full-width
    with pytest.raises(AssertionError):
        SimConfig(pods=(8, 3))              # MIG-valid widths only
    with pytest.raises(AssertionError):
        SimConfig(router="nope") and make_router("nope")


def test_summary_schema_v2_records_fleet_fields():
    s = _run(router="least_loaded").summary()
    assert s["schema"] == 2
    assert s["n_pods"] == len(HET) and s["pods"] == list(HET)
    assert s["router"] == "least_loaded"
    assert "refits" in s and "p99_wait_s" in s


# ------------------------------------------------------ unified decide API

def _rl_policy():
    env_cfg = EnvConfig(window=4, c_max=3)
    env = CoScheduleEnv(env_cfg)
    return RLDispatchPolicy(DQNAgent(env.state_dim, env.n_actions, seed=0),
                            env_cfg)


@pytest.mark.parametrize("make_policy", [
    TimeSharingPolicy,
    GreedyPackerPolicy,
    lambda: StaticPartitionPolicy("mig_mps_default"),
    _rl_policy,
], ids=["time_sharing", "greedy_packer", "mig_mps_default", "rl"])
def test_decide_returns_the_width_fitted_schedule(make_policy):
    # one call only: a second would find the binaries already profiled
    subs = [(a.binary, a.profile) for a in _trace(n=20, pods=(N_UNITS,))]
    dec = make_policy().decide(subs)

    def labels(pls):
        return [([j.name for j in pl.group], pl.partition.label) for pl in pls]

    assert labels(dec.placements) == labels(to_placements(dec.schedule))
    assert dec.first_sight + dec.planned == len(subs)


def test_decide_itself_never_warns():
    """The unified entry point must stay warning-free: only the
    ``dispatch()``/``placements()`` shims are deprecated, and a policy
    without legacy overrides routes straight through ``decide()``."""
    import warnings

    subs = [(a.binary, a.profile) for a in _trace(n=6, pods=(N_UNITS,))]
    with warnings.catch_warnings():
        warnings.simplefilter("error", DeprecationWarning)
        dec = TimeSharingPolicy().decide(subs)
    assert dec.schedule is not None and len(dec.placements) > 0


# ----------------------------------------------------------- refit guard

class _PairEverything(DispatchPolicy):
    """Pathological policy: pairs consecutive jobs onto a full-width
    two-slice MIG partition regardless of the serving pod — forcing the
    fleet's pod-width refit guard on narrow pods."""

    name = "pair_everything"

    def plan(self, queue):
        from repro.core.problem import Schedule
        sched = Schedule()
        half = Slice(N_UNITS // 2, (1.0,))
        pair = Partition((half, half), slice_label((half, half)))
        q = list(queue)
        while len(q) >= 2:
            sched.add([q.pop(0), q.pop(0)], pair)
        if q:
            from repro.core.partition import solo_partition
            sched.add([q.pop()], solo_partition())
        return sched


def test_overwide_placements_refit_to_narrow_pods():
    # a burst of 4-unit-hinted re-arrivals spreads over an (8, 4) fleet
    # under least-loaded routing; pairing two of them into an 8-unit MIG
    # partition cannot fit the 4-pod and must decompose
    from repro.online import Arrival
    base = ZOO[0]
    j4 = dataclasses.replace(base, name=base.name + "@u4",
                             meta={**base.meta, "units": 4})
    trace = [Arrival(t=0.0, binary="bin://j4", profile=j4)]
    trace += [Arrival(t=1e5 + 0.1 * k, binary="bin://j4", profile=j4)
              for k in range(12)]
    cfg = SimConfig(pods=(8, 4), router="least_loaded")
    res = ClusterSimulator(_PairEverything(), cfg).run(trace)
    assert res.refits > 0
    assert res.summary()["refits"] == res.refits
    for seg in res.timeline:          # decomposed placements still pod-local
        for start, w in seg.slices:
            assert start + w <= res.pods[seg.pod]
    assert all(not math.isnan(r.finish) for r in res.jobs)


# ------------------------------------------------- vectorized fleet parity

@pytest.mark.parametrize("pods", [(8, 8), HET])
def test_vectorized_fleet_matches_heap_fleet(pods):
    trace = _trace(n=100, seed=5, pods=pods)
    cfg = SimConfig(pods=pods, router="hash")
    heap = ClusterSimulator(TimeSharingPolicy(), cfg).run(trace)
    vec = VectorizedFleetSimulator(TimeSharingPolicy(), cfg,
                                   capacity=128).run(trace)
    assert [r.pod for r in heap.jobs] == [r.pod for r in vec.jobs]
    assert [r.units for r in heap.jobs] == [r.units for r in vec.jobs]
    assert [r.backfilled for r in heap.jobs] == \
           [r.backfilled for r in vec.jobs]
    assert heap.dispatches == vec.dispatches
    assert heap.backfills == vec.backfills
    for a, b in zip(heap.jobs, vec.jobs):
        assert b.dispatch == pytest.approx(a.dispatch, rel=1e-5, abs=1e-2)
        assert b.finish == pytest.approx(a.finish, rel=1e-5, abs=1e-2)
    assert vec.summary()["p99_wait_s"] == pytest.approx(
        heap.summary()["p99_wait_s"], rel=1e-5, abs=1e-2)


def test_vectorized_fleet_rejects_stateful_routers_and_other_policies():
    with pytest.raises(ValueError):
        VectorizedFleetSimulator(TimeSharingPolicy(),
                                 SimConfig(pods=HET, router="frag"))
    with pytest.raises(ValueError):
        VectorizedFleetSimulator(GreedyPackerPolicy(),
                                 SimConfig(pods=HET, router="hash"))

"""Slice-level concurrent dispatch: placement arithmetic, occupancy
invariants, EASY backfill, and blocking-mode bit-compatibility.

Invariant contract of the concurrent event model:

  * no two groups whose segments overlap in time claim overlapping slice
    units (the occupancy map is exclusive);
  * FREE events reconcile with the timeline — per-unit busy seconds summed
    from segments equal ``SimResult.slice_busy_s``, and the union of
    segment intervals equals ``busy_time``;
  * backfill never delays the blocked head's start (EASY reservation);
  * on traces without sub-pod width hints, ``mode="concurrent"`` is
    bit-compatible with the PR-3 ``mode="blocking"`` dispatch, which stays
    available for regression.
"""
import dataclasses

import numpy as np
import pytest

from repro.core import make_zoo
from repro.core.partition import (
    N_UNITS, Partition, Slice, aligned_offsets, find_offsets, slice_label,
    solo_partition,
)
from repro.core.perfmodel import corun
from repro.core.problem import Schedule
from repro.core.scheduler import to_placements
from repro.online import (
    Arrival, ClusterSimulator, GreedyPackerPolicy, StaticPartitionPolicy,
    TimeSharingPolicy, fragmented_trace, poisson_trace,
)

ZOO = make_zoo()


def _unit_set(seg):
    return {u for start, w in seg.slices for u in range(start, start + w)}


def _assert_no_overlap(res):
    segs = res.timeline
    for i in range(len(segs)):
        for j in range(i + 1, len(segs)):
            a, b = segs[i], segs[j]
            if a.t0 < b.t1 - 1e-9 and b.t0 < a.t1 - 1e-9:
                assert not (_unit_set(a) & _unit_set(b)), (a, b)


def _mouse(base, name, steps, units=1):
    return dataclasses.replace(base, name=name, steps=steps,
                               meta={**base.meta, "units": units})


US = next(j for j in ZOO if j.job_class == "US")
CI = next(j for j in ZOO if j.job_class == "CI")


# ------------------------------------------------- placement arithmetic

def test_aligned_offsets_buddy_alignment():
    assert aligned_offsets(1) == tuple(range(8))
    assert aligned_offsets(2) == (0, 2, 4, 6)
    assert aligned_offsets(4) == (0, 4)
    assert aligned_offsets(8) == (0,)


def test_find_offsets_disjoint_and_aligned():
    p = Partition((Slice(4, (1.0,)), Slice(2, (1.0,)), Slice(2, (1.0,))),
                  "test")
    starts = find_offsets(p, [True] * N_UNITS)
    assert starts is not None
    claimed = set()
    for st, s in zip(starts, p.slices):
        assert st % s.units == 0, "unaligned placement"
        rng = set(range(st, st + s.units))
        assert not (claimed & rng), "overlapping slices"
        claimed |= rng


def test_find_offsets_respects_free_mask_and_fails_cleanly():
    solo4 = solo_partition(4)
    # units 0-3 busy: the only aligned 4-range left starts at 4
    free = [False] * 4 + [True] * 4
    assert find_offsets(solo4, free) == (4,)
    # an aligned hole of 2+2 split across the boundary cannot host a 4-slice
    free = [False, False, True, True, True, True, False, False]
    assert find_offsets(solo4, free) is None
    assert find_offsets(solo_partition(2), free) == (2,)


def test_solo_partition_widths_and_labels():
    assert solo_partition().label == "[{1.0},1m]"     # table object, unchanged
    for u, lab in ((4, ".5m"), (2, ".25m"), (1, ".125m")):
        p = solo_partition(u)
        assert p.arity == 1 and p.total_units == u
        assert lab in p.label, p.label


def test_right_size_and_requested_units():
    assert US.right_size(1.05) == 1                    # faster on small slices
    assert CI.right_size(1.25) == N_UNITS              # scales, stays full-pod
    for tol in (1.05, 1.5, 2.0):
        w = US.right_size(tol)
        assert US.step_time(w) <= tol * US.step_time(N_UNITS)
    assert US.requested_units == N_UNITS               # no hint -> full pod
    assert _mouse(US, "m", 100).requested_units == 1
    bad = dataclasses.replace(US, meta={"units": 3})   # invalid width ignored
    assert bad.requested_units == N_UNITS


def test_to_placements_narrows_dedicated_slices_only():
    m = _mouse(US, "m@u1", 1000)
    sched = Schedule()
    sched.add([m], solo_partition())                         # dedicated slice
    sched.add([CI, CI], Partition((Slice(8, (0.5, 0.5)),), "mps"))  # shared
    pls = to_placements(sched)
    assert pls[0].partition.total_units == 1
    assert slice_label(pls[0].partition.slices) == pls[0].partition.label
    assert pls[1].partition is sched.partitions[1]     # MPS slice untouched
    # no hints anywhere -> identical partition objects (bit-compat path)
    sched2 = Schedule()
    sched2.add([CI], solo_partition())
    assert to_placements(sched2)[0].partition is sched2.partitions[0]


# ------------------------------------------------- occupancy invariants

@pytest.mark.parametrize("make_policy", [
    lambda: TimeSharingPolicy(),
    lambda: GreedyPackerPolicy(c_max=3),
    lambda: StaticPartitionPolicy("mig_only", c_max=3),
])
def test_concurrent_occupancy_invariants(make_policy):
    trace = fragmented_trace(ZOO, n=40, load=1.3, seed=2)
    res = ClusterSimulator(make_policy(), window=6).run(trace)
    assert len(res.jobs) == 40
    assert all(np.isfinite(j.finish) for j in res.jobs)
    _assert_no_overlap(res)
    # FREE reconciliation: per-unit busy from segments == slice_busy_s
    per_unit = [0.0] * N_UNITS
    for seg in res.timeline:
        for st, w in seg.slices:
            for u in range(st, st + w):
                per_unit[u] += seg.t1 - seg.t0
    assert np.allclose(per_unit, res.slice_busy_s)
    assert np.isclose(res.unit_busy_s, sum(res.slice_busy_s))
    # busy_time == union of segment intervals (pod busy when any unit is)
    ivs = sorted((s.t0, s.t1) for s in res.timeline)
    union, cur0, cur1 = 0.0, None, None
    for t0, t1 in ivs:
        if cur1 is None or t0 > cur1 + 1e-12:
            union += (cur1 - cur0) if cur1 is not None else 0.0
            cur0, cur1 = t0, t1
        else:
            cur1 = max(cur1, t1)
    union += (cur1 - cur0) if cur1 is not None else 0.0
    assert np.isclose(union, res.busy_time)
    assert 0.0 <= res.slice_utilization <= 1.0 + 1e-9
    assert np.isclose(res.idle_slice_frac, 1.0 - res.slice_utilization)


def test_concurrent_mode_actually_overlaps_on_fragmented_trace():
    trace = fragmented_trace(ZOO, n=40, load=1.3, seed=2)
    res = ClusterSimulator(TimeSharingPolicy(), window=6).run(trace)
    segs = res.timeline
    overlaps = sum(1 for i in range(len(segs)) for j in range(i + 1, len(segs))
                   if segs[i].t0 < segs[j].t1 - 1e-9
                   and segs[j].t0 < segs[i].t1 - 1e-9)
    assert overlaps > 0, "no concurrency on a width-mixed trace"
    assert res.throughput > ClusterSimulator(
        TimeSharingPolicy(), window=6, mode="blocking").run(trace).throughput


def test_simulator_concurrent_deterministic():
    trace = fragmented_trace(ZOO, n=30, seed=4)
    r1 = ClusterSimulator(TimeSharingPolicy(), window=5).run(trace)
    r2 = ClusterSimulator(TimeSharingPolicy(), window=5).run(trace)
    assert r1.summary() == r2.summary()
    assert [(j.dispatch, j.finish, j.units, j.backfilled) for j in r1.jobs] == \
           [(j.dispatch, j.finish, j.units, j.backfilled) for j in r2.jobs]


# --------------------------------------------------------- EASY backfill

def _crafted_window():
    """One coincident window: long 1-unit mouse, full-pod head, short
    1-unit mouse — the head blocks behind the long mouse and the short
    mouse is a textbook backfill candidate."""
    m_long = _mouse(US, "mouse-long", 40_000)
    big = dataclasses.replace(CI, name="big-head", meta=dict(CI.meta))
    m_short = _mouse(US, "mouse-short", 8_000)
    return [Arrival(t=10.0, binary=f"bin://{j.name}", profile=j)
            for j in (m_long, big, m_short)], (m_long, big, m_short)


def test_backfill_jumps_gap_without_delaying_head():
    trace, (m_long, big, m_short) = _crafted_window()
    dur_long = corun([m_long], solo_partition(1)).makespan
    on = ClusterSimulator(TimeSharingPolicy(), window=8).run(trace)
    off = ClusterSimulator(TimeSharingPolicy(), window=8,
                           backfill=False).run(trace)
    by = {r.name: r for r in on.jobs}
    by_off = {r.name: r for r in off.jobs}
    # the head's start is identical with and without backfill (EASY)
    assert np.isclose(by["big-head"].dispatch, 10.0 + dur_long)
    assert np.isclose(by["big-head"].dispatch, by_off["big-head"].dispatch)
    assert np.isclose(by["mouse-long"].dispatch, by_off["mouse-long"].dispatch)
    # the short mouse jumped the queue into the idle units...
    assert on.backfills == 1 and by["mouse-short"].backfilled
    assert np.isclose(by["mouse-short"].dispatch, 10.0)
    # ...and finished before the head's reserved start
    assert by["mouse-short"].finish <= by["big-head"].dispatch + 1e-9
    # without backfill it waited for FCFS order instead
    assert by_off["mouse-short"].dispatch > by_off["big-head"].dispatch - 1e-9
    assert off.backfills == 0 and not by_off["mouse-short"].backfilled


def test_lookahead_window_backfills_later_arrival():
    """A 1-unit job arriving while the head is blocked gets admitted
    through the bounded lookahead window and backfilled immediately."""
    m_long = _mouse(US, "mouse-long", 40_000)
    big = dataclasses.replace(CI, name="big-head", meta=dict(CI.meta))
    m_late = _mouse(US, "mouse-late", 8_000)
    trace = [Arrival(t=0.0, binary="bin://mouse-long", profile=m_long),
             Arrival(t=0.0, binary="bin://big-head", profile=big),
             Arrival(t=5.0, binary="bin://mouse-late", profile=m_late)]
    res = ClusterSimulator(TimeSharingPolicy(), window=2).run(trace)
    by = {r.name: r for r in res.jobs}
    dur_long = corun([m_long], solo_partition(1)).makespan
    assert res.backfills == 1 and by["mouse-late"].backfilled
    assert np.isclose(by["mouse-late"].dispatch, 5.0)
    assert np.isclose(by["big-head"].dispatch, dur_long)   # head undelayed
    assert res.dispatches == 2                             # lookahead window


# ------------------------------------------- blocking-mode compatibility

@pytest.mark.parametrize("window", [1, 4])
def test_concurrent_bit_compatible_with_blocking_on_full_pod_traces(window):
    """Without sub-pod width hints every placement is full-pod, so the
    slice-level engine must reproduce the PR-3 blocking results exactly
    (records bit-equal; busy time to float accumulation order)."""
    trace = poisson_trace(ZOO, n=25, seed=3)
    blk = ClusterSimulator(TimeSharingPolicy(), window=window,
                           mode="blocking").run(trace)
    con = ClusterSimulator(TimeSharingPolicy(), window=window).run(trace)
    assert [(j.dispatch, j.finish, j.group_size, j.partition)
            for j in blk.jobs] == \
           [(j.dispatch, j.finish, j.group_size, j.partition)
            for j in con.jobs]
    sb, sc = blk.summary(), con.summary()
    assert sb["mode"] == "blocking" and sc["mode"] == "concurrent"
    for k in sb:
        if k in ("mode", "busy_s", "utilization"):
            continue
        assert sb[k] == pytest.approx(sc[k]), k
    assert np.isclose(sb["busy_s"], sc["busy_s"])
    assert con.backfills == 0                      # nothing to backfill


def test_blocking_mode_segments_claim_full_pod():
    trace = poisson_trace(ZOO, n=10, seed=1)
    res = ClusterSimulator(TimeSharingPolicy(), window=4,
                           mode="blocking").run(trace)
    assert all(s.slices == ((0, N_UNITS),) for s in res.timeline)
    assert np.isclose(res.unit_busy_s, N_UNITS * res.busy_time)


# --------------------------------------------- dispatch-time context snapshot

class _RecordingPolicy(TimeSharingPolicy):
    """Time sharing that records the DispatchContext of every window."""

    def __init__(self):
        super().__init__()
        self.contexts = []

    def placements(self, submissions, context=None):
        self.contexts.append((context, [p for p, _ in submissions]))
        return super().placements(submissions, context=context)


def test_dispatch_context_matches_occupancy_and_ages():
    """The snapshot handed to the policy obeys the occupancy-map contract:
    every unit reported busy is covered by a claim segment spanning the
    dispatch instant, ages equal now - arrival for the window's
    submissions, and depth counts exactly the left-behind pending queue."""
    trace = fragmented_trace(ZOO, n=40, load=1.3, seed=2)
    pol = _RecordingPolicy()
    res = ClusterSimulator(pol, window=6).run(trace)
    assert pol.contexts and len(pol.contexts) == res.dispatches
    # windows pop the pending queue FCFS (lookahead included), so the
    # concatenated window submissions replay the time-sorted trace exactly
    order = sorted(trace, key=lambda a: a.t)
    k = 0
    partial = 0
    for ctx, bins in pol.contexts:
        assert ctx is not None and len(ctx.free_units) == N_UNITS
        busy = {u for u in range(N_UNITS) if not ctx.free_units[u]}
        covered = {u for seg in res.timeline
                   if seg.t0 <= ctx.now_s + 1e-9 and seg.t1 > ctx.now_s + 1e-9
                   for u in _unit_set(seg)}
        assert busy <= covered, (ctx.now_s, busy, covered)
        assert len(ctx.ages_s) == len(bins)
        for age, b in zip(ctx.ages_s, bins):
            assert b == order[k].binary
            assert age == pytest.approx(ctx.now_s - order[k].t)
            assert age >= -1e-9
            k += 1
        assert ctx.queue_depth >= 0
        if 0 < len(busy) < N_UNITS:
            partial += 1
    assert k == len(trace)
    # the fragmented family must exercise genuinely partial occupancies
    assert partial > 0


def test_blocking_dispatch_context_reports_idle_pod():
    trace = poisson_trace(ZOO, n=12, seed=1)

    class _Rec(TimeSharingPolicy):
        seen = []

        def dispatch(self, submissions, context=None):
            self.seen.append(context)
            return super().dispatch(submissions, context=context)

    pol = _Rec()
    ClusterSimulator(pol, window=4, mode="blocking").run(trace)
    assert pol.seen and all(all(c.free_units) for c in pol.seen)


# ------------------------------------------------------ fragmented trace

def test_fragmented_trace_mixes_slice_widths_coherently():
    trace = fragmented_trace(ZOO, n=120, seed=0)
    widths = {a.profile.requested_units for a in trace}
    assert 1 in widths and N_UNITS in widths, widths
    assert widths - {1, 2, 4, 8} == set()
    by_bin = {}
    for a in trace:
        # one profile object per (binary, width): repository keys coherent
        assert by_bin.setdefault(a.binary, a.profile) is a.profile
        if a.profile.requested_units < N_UNITS:
            assert a.profile.name.endswith(f"@u{a.profile.requested_units}")
            w = a.profile.requested_units
            assert a.profile.step_time(w) <= 1.65 * a.profile.step_time(N_UNITS)

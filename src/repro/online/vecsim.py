"""In-graph vectorized cluster simulator: the event heap as pytree arrays.

The Python :class:`~repro.online.simulator.ClusterSimulator` is a per-event
Python loop — exact, but one trace at a time.  This module applies the same
transformation PR 1 applied to the training loop (scalar Python loop ->
donated ``lax.scan``/``while_loop`` over fixed-shape pytree state) to the
*simulator* itself, so a whole batch of traces runs in one device call
under ``vmap`` (and across host devices under ``pmap`` — the
``--xla_force_host_platform_device_count`` idiom gives cheap CPU
parallelism in CI).

Event-table layout (the heap, flattened)
----------------------------------------
The heap's three event kinds become bounded array lanes with active masks;
"pop the heap" becomes an argmin:

* **ARRIVE** — the trace itself *is* the event table: arrival times are a
  sorted ``(capacity,)`` lane and two cursors replace the FCFS pending
  deque (``pend_lo``..``pend_hi`` index the admitted-but-undispatched
  span).  The next arrival event is ``t[pend_hi]``.
* **FREE** — outstanding slice claims live in ``N_UNITS`` fixed slots
  (each claim holds >= 1 of the 8 units, so 8 slots can never overflow):
  expiry time, claimed-unit mask, active flag.  The next free event is the
  masked min over expiries.
* **TICK** — not represented: re-training is a host-side callback, so the
  heap engine remains the only path with ``on_tick`` (documented below).

One event step takes ``now = min(next arrival, next expiry)``, drains
*every* event with ``t <= now`` (the heap's coincident-event drain), then
runs the same service fixpoint the Python ``_service`` loop runs: place
the FCFS head while it first-fits, admit one bounded lookahead window past
a blocked head, EASY-backfill later groups that provably finish before the
head's earliest feasible start (replayed claim expiries, in-graph).  The
per-unit occupancy map is an ``(N_UNITS,)`` mask and first-fit
aligned-buddy placement is a masked scan over the 8 candidate offsets.
A full trace is one ``lax.while_loop`` (each step retires >= 1 event, so
``2 * capacity + 4`` bounds it); ``vmap`` over a leading trace axis
evaluates hundreds of scenarios per call.

Scope and the plan seam
-----------------------
The engine executes two plan families through one dispatch machinery:

* **Solo-placement plans** (:class:`~repro.online.policies.\
TimeSharingPolicy` / ``policy=None``): every submission becomes its own
  single-slice group at its ``requested_units`` width, through the same
  first-sight protocol the heap runs (unprofiled binaries are scheduled
  ahead of the planned remainder of their window, and enter the in-graph
  profiled bitmap).  Group durations are *precomputed* per (job, width)
  by the float64 reference model (:func:`~repro.core.perfmodel_jax.\
solo_duration_table`, bit-equal to the heap's per-group ``corun``
  predictions for solo placements), so the two engines make identical
  discrete decisions and differ only by float32 rounding of the clock.
* **RL grouped plans** (:class:`~repro.online.policies.\
RLDispatchPolicy`): the agent's greedy co-scheduling episode runs
  in-graph at the same window-formation seam (``_build_run_rl``'s
  ``form_and_plan`` — the single place a plan is materialized into group
  slots).  The popped chunk is assembled into the ``CoScheduleEnv``
  observation layout (profile rows + status flags, plus the live
  ``ObsContext`` block under ``EnvConfig.obs_context``), scored by
  :func:`~repro.core.network.greedy_q_action` with the env's validity
  mask, and the closed groups pass through the heap's §IV-A fallback
  guard, pod-width refit, and dedicated-slice shrink before dispatching
  on the shared predicated place/backfill path.  Params are a
  closed-over pytree argument: ``hot_swap`` never recompiles, and
  ``sweep(param_sets=...)`` vmaps a population of agents.  Solo entries
  (first-sight and single-member groups) keep the exact f64 duration
  table; only true co-run groups carry the f32 in-graph model's
  clock-level drift.

Parity guarantee
----------------
For any concurrent-mode trace, :class:`VectorizedClusterSimulator` and the
Python heap produce matching :class:`~repro.online.simulator.SimResult`
job records: **identical decisions** (placement order, groups,
partitions, slice ranges, units, backfill flags, fallback/refit
outcomes, window/dispatch counts) and times equal up to float32
resolution of the clock (the heap is the float64 reference, exactly as
``train_agent_scalar`` is for the training engine).  Record attribution
for duplicate-tenant windows follows the heap's name-keyed FIFO.
``tests/test_vecsim.py`` pins the time-sharing side on randomized
traces; ``tests/test_parity_fuzz.py`` fuzzes the RL side (single-pod and
fleet) on shared ``tests/strategies.py`` generators.  Context-aware
agents (``obs_context=True``) see an f32 context block in-graph vs the
heap's f64 snapshot, so a near-tie action can legitimately flip;
profile-only agents are parity-exact at the decision level.

Capacity limits raise eagerly: a trace longer than ``capacity`` raises
``ValueError`` before the device call, and the engine carries an error
lane (ready-ring / event-step overflow) that the wrapper turns into
``RuntimeError`` — never silent truncation.
"""
from __future__ import annotations

from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.network import greedy_q_action
from repro.core.partition import (
    N_UNITS, Partition, Slice, enumerate_partitions, slice_label,
    solo_partition,
)
from repro.core.perfmodel import corun
from repro.core.perfmodel_jax import (
    UNIT_SIZES, JobTermsTable, QueueArrays, build_partition_table,
    group_metrics, job_terms_table, solo_duration_table,
)
from repro.online.policies import RLDispatchPolicy, TimeSharingPolicy
from repro.online.router import FleetView, PodView, make_router
from repro.online.simulator import (
    Arrival, JobRecord, Segment, SimConfig, SimResult,
)
from repro.online.telemetry import WAIT_BUCKETS_S

_WAIT_EDGES = jnp.asarray(np.array(WAIT_BUCKETS_S, np.float32))

_INF = jnp.float32(jnp.inf)
_BIG_SEQ = jnp.int32(2**30)
_UNIT_IDX = jnp.arange(N_UNITS, dtype=jnp.int32)

# constant aligned-buddy fit tensors, indexed by width-index into
# UNIT_SIZES: _COVERED[u, s, :] = units a width-u slice at offset s spans;
# _ALIGNED[u, s] = offset s is buddy-aligned and in range.  Precomputing
# these keeps the per-iteration fit query a gather + reduce instead of
# rebuilding an 8x8 mask from a traced width.
_COVERED = jnp.asarray(np.stack([
    (np.arange(N_UNITS)[None, :] >= np.arange(N_UNITS)[:, None])
    & (np.arange(N_UNITS)[None, :] < np.arange(N_UNITS)[:, None] + w)
    for w in UNIT_SIZES]))                    # (U, 8, 8) bool
_ALIGNED = jnp.asarray(np.stack([
    (np.arange(N_UNITS) % w == 0) & (np.arange(N_UNITS) + w <= N_UNITS)
    for w in UNIT_SIZES]))                    # (U, 8) bool

# error lanes (bitwise-OR'd): the wrapper raises RuntimeError on any
ERR_READY_OVERFLOW = 1          # ready ring out of slots (cannot happen at
                                # R = 2*window + 2; kept as an eager guard)
ERR_EVENT_OVERFLOW = 2          # while_loop exceeded 2*capacity+4 events
ERR_EPISODE = 4                 # RL co-schedule episode failed to terminate
                                # (cannot happen: 2*W steps bound any
                                # masked-greedy episode; eager guard)


class TraceArrays(NamedTuple):
    """One compiled trace: sorted arrival lanes, padded to ``capacity``."""

    t: jnp.ndarray               # (A,) f32 — sorted arrival times
    t_lo: jnp.ndarray            # (A,) f32 — float64 residual t - f32(t)
    job: jnp.ndarray             # (A,) i32 — row into the job table
    n: jnp.ndarray               # ()   i32 — live arrivals (rest padding)


class JobTable(NamedTuple):
    """Distinct-job lanes shared by every trace of a sweep."""

    width: jnp.ndarray           # (J,) i32 — requested slice width (units)
    widx: jnp.ndarray            # (J,) i32 — index into UNIT_SIZES
    dur: jnp.ndarray             # (J,) f32 — solo makespan at that width
                                 #           (float64 corun, cast once)
    solo8: jnp.ndarray           # (J,) f32 — full-pod solo time (throughput)


class _State(NamedTuple):
    """The whole simulation as fixed-shape lanes (A = capacity, R = ring)."""

    now: jnp.ndarray             # () f32
    now_lo: jnp.ndarray          # () f32 — clock residual (_clock_add)
    pend_lo: jnp.ndarray         # () i32 — first undispatched admitted arrival
    pend_hi: jnp.ndarray         # () i32 — first un-admitted arrival
    profiled: jnp.ndarray        # (J,) bool — repository bitmap (first sight)
    free: jnp.ndarray            # (N_UNITS,) bool — idle slice units
    # ready ring: dispatched groups waiting for units (FCFS by seq)
    r_active: jnp.ndarray        # (R,) bool
    r_seq: jnp.ndarray           # (R,) i32 — global FCFS order
    r_win: jnp.ndarray           # (R,) i32 — dispatch window id
    r_grp: jnp.ndarray           # (R,) i32 — row into the group log
    next_seq: jnp.ndarray        # () i32
    # claim table: outstanding FREE events
    c_active: jnp.ndarray        # (N_UNITS,) bool
    c_t1: jnp.ndarray            # (N_UNITS,) f32 — expiry
    c_lo: jnp.ndarray            # (N_UNITS,) f32 — expiry residual
    c_mask: jnp.ndarray          # (N_UNITS, N_UNITS) bool — claimed units
    # busy-span accounting (union over units, like the heap)
    n_busy: jnp.ndarray          # () i32
    busy_t0: jnp.ndarray         # () f32
    busy_time: jnp.ndarray       # () f32
    slice_busy: jnp.ndarray      # (N_UNITS,) f32
    # counters
    dispatches: jnp.ndarray      # () i32
    backfills: jnp.ndarray       # () i32
    n_groups: jnp.ndarray        # () i32
    place_seq: jnp.ndarray       # () i32 — placement order (timeline)
    steps: jnp.ndarray           # () i32 — event steps retired
    err: jnp.ndarray             # () i32 — ERR_* lanes
    # group log (one row per dispatched solo group; <= A rows).  Kept to
    # the minimum the host cannot rederive — width/duration live in the
    # job table via g_job, and placement seq/start/backfill pack into one
    # int lane — because every lane here is a (batch, A) while-loop carry.
    g_arr: jnp.ndarray           # (A,) i32 — arrival index (A = unused)
    g_job: jnp.ndarray           # (A,) i32 — row into the job table
    g_t0: jnp.ndarray            # (A,) f32 — placement time
    g_lo: jnp.ndarray            # (A,) f32 — placement time residual
    g_pack: jnp.ndarray          # (A,) i32 — (pseq << 4)|(start << 1)|bf


class MetricsState(NamedTuple):
    """In-graph streaming metrics, accumulated inside the ``while_loop``
    carry when the engine is built with ``telemetry=True`` — the pytree
    mirror of the heap :class:`~repro.online.telemetry.Telemetry`
    aggregates (same fixed ``WAIT_BUCKETS_S`` histogram layout, same
    event-gap integrals), so vmapped sweeps return per-lane metric
    tensors with zero extra device syncs."""

    wait_hist: jnp.ndarray       # (len(WAIT_BUCKETS_S)+1,) i32 counts
    wait_sum: jnp.ndarray        # () f32 — Σ wait at placement
    queue_depth_int: jnp.ndarray  # () f32 — ∫ pending-depth dt
    busy_unit_int: jnp.ndarray   # () f32 — ∫ claimed-units dt
    places: jnp.ndarray          # () i32 — groups placed


def _metrics_init() -> MetricsState:
    return MetricsState(
        wait_hist=jnp.zeros(len(WAIT_BUCKETS_S) + 1, jnp.int32),
        wait_sum=jnp.float32(0.0), queue_depth_int=jnp.float32(0.0),
        busy_unit_int=jnp.float32(0.0), places=jnp.int32(0))


class SweepSummary(NamedTuple):
    """Per-trace metrics of a vmapped sweep (leading batch axis)."""

    makespan: jnp.ndarray
    throughput: jnp.ndarray
    mean_wait: jnp.ndarray
    p50_wait: jnp.ndarray
    p99_wait: jnp.ndarray
    mean_turnaround: jnp.ndarray
    p95_turnaround: jnp.ndarray
    utilization: jnp.ndarray
    slice_utilization: jnp.ndarray
    backfills: jnp.ndarray
    dispatches: jnp.ndarray
    err: jnp.ndarray


# --------------------------------------------------------------- primitives

def _fit_table(free):
    """Per-width first-fit table on ``free``: ``(U, N_UNITS)`` bool.

    The masked mirror of :func:`~repro.core.partition.find_offsets` for a
    single slice (solo plans place exactly one): candidate starts are the
    8 unit offsets, valid iff buddy-aligned (``start % width == 0``) and
    every covered unit is idle.  Row ``u`` answers every fit query for
    width ``UNIT_SIZES[u]`` this iteration; first-fit = argmax.
    """
    return _ALIGNED & jnp.all(free[None, None, :] | ~_COVERED, axis=2)


def _claim_units(start, width):
    return (_UNIT_IDX >= start) & (_UNIT_IDX < start + width)


def _head(st: _State):
    """FCFS head of the ready ring: min seq among active slots."""
    seqs = jnp.where(st.r_active, st.r_seq, _BIG_SEQ)
    return jnp.argmin(seqs).astype(jnp.int32), jnp.any(st.r_active)


def _percentile(x, valid, q):
    """Masked ``np.percentile(x[valid], q)`` (linear interpolation)."""
    n = jnp.sum(valid)
    s = jnp.sort(jnp.where(valid, x, _INF))
    pos = jnp.float32(q / 100.0) * jnp.maximum(n - 1, 0).astype(jnp.float32)
    lo = jnp.floor(pos).astype(jnp.int32)
    hi = jnp.minimum(lo + 1, jnp.maximum(n - 1, 0))
    frac = pos - lo.astype(jnp.float32)
    lo = jnp.clip(lo, 0, x.shape[0] - 1)
    hi = jnp.clip(hi, 0, x.shape[0] - 1)
    out = s[lo] * (1.0 - frac) + s[hi] * frac
    return jnp.where(n > 0, out, jnp.float32(0.0))


# The clock is a float32 pair (value, residual).  A busy lane chains
# ``expiry = now + duration`` through thousands of events, and plain float32
# addition drops up to half an ulp at each link: on a 10^4-arrival fleet
# lane that drift reaches seconds by a 5e5-s horizon, far past float32
# resolution of the float64 heap clock.  The residual keeps what each add
# drops, so the value stays the float32 rounding of the float64 clock.
# Decisions compare values only; the residuals enter elapsed times
# (``_since``), event ties and the host-side float64 placement times.

def _clock_add(hi, lo, dt):
    """Compensated ``(hi + lo) + dt`` -> ``(value, residual)`` (two-sum)."""
    s = hi + dt
    bb = s - hi
    err = (hi - (s - bb)) + (dt - bb) + lo
    out = s + err
    return out, err - (out - s)


def _since(hi, lo, t, t_lo):
    """Elapsed ``(hi + lo) - (t + t_lo)`` between two clock pairs."""
    return (hi - t) + (lo - t_lo)


def _next_event(st, trace: TraceArrays):
    """The clock pair of the next event: the pending arrival or the
    earliest claim expiry, whichever comes first."""
    i = jnp.clip(st.pend_hi, 0, trace.t.shape[0] - 1)
    t_arr = jnp.where(st.pend_hi < trace.n, trace.t[i], _INF)
    exp = jnp.where(st.c_active, st.c_t1, _INF)
    k = jnp.argmin(exp)
    t_free, lo_arr, lo_free = exp[k], trace.t_lo[i], st.c_lo[k]
    from_arr = (t_arr < t_free) | ((t_arr == t_free) & (lo_arr < lo_free))
    return (jnp.where(from_arr, t_arr, t_free),
            jnp.where(from_arr, lo_arr, lo_free))


# ------------------------------------------------------------ state updates
#
# Every update below is *predicated* on a ``do`` flag instead of wrapped in
# ``lax.cond``: under ``vmap`` a batched cond lowers to a select that runs
# BOTH branches for the whole batch, so masked single-path updates (scatter
# to an out-of-bounds row with ``mode="drop"`` when ``do`` is False) are
# what keep the lockstep body small.

def _place(st: _State, jobs: JobTable, slot, start, backfilled, do) -> _State:
    """Claim the first-fit range for ready slot ``slot`` (heap ``_place``),
    iff ``do``."""
    g = st.r_grp[slot]
    j = st.g_job[g]
    w = jobs.width[j]
    dur = jobs.dur[j]
    mask = _claim_units(start, w) & do
    doi = jnp.where(do, jnp.int32(1), jnp.int32(0))
    A = st.g_arr.shape[0]
    gt = jnp.where(do, g, A)                 # drop target when masked off
    ct = jnp.where(do, jnp.argmin(st.c_active).astype(jnp.int32), N_UNITS)
    rt = jnp.where(do, slot, st.r_active.shape[0])
    pack = ((st.place_seq << 4) | (start << 1)
            | jnp.where(backfilled, jnp.int32(1), jnp.int32(0)))
    t1, t1_lo = _clock_add(st.now, st.now_lo, dur)
    return st._replace(
        free=st.free & ~mask,
        busy_t0=jnp.where(do & (st.n_busy == 0), st.now, st.busy_t0),
        n_busy=st.n_busy + doi * w,
        c_active=st.c_active.at[ct].set(True, mode="drop"),
        c_t1=st.c_t1.at[ct].set(t1, mode="drop"),
        c_lo=st.c_lo.at[ct].set(t1_lo, mode="drop"),
        c_mask=st.c_mask.at[ct].set(mask, mode="drop"),
        slice_busy=st.slice_busy + jnp.where(mask, dur, 0.0),
        g_t0=st.g_t0.at[gt].set(st.now, mode="drop"),
        g_lo=st.g_lo.at[gt].set(st.now_lo, mode="drop"),
        g_pack=st.g_pack.at[gt].set(pack, mode="drop"),
        place_seq=st.place_seq + doi,
        r_active=st.r_active.at[rt].set(False, mode="drop"),
        backfills=st.backfills + jnp.where(do & backfilled, jnp.int32(1),
                                           jnp.int32(0)),
    )


def _earliest_fit(st: _State, widx):
    """Earliest time a width-``UNIT_SIZES[widx]`` slice fits, replaying
    claim expiries — the in-graph mirror of the heap's ``_earliest_fit``
    reservation.  Candidate times are the claim expiries themselves;
    "first fit" is the min over fitting candidates, so no sort is needed
    (availability at time t depends only on which claims expired by t)."""
    # freed[i] = unit availability once every claim expiring by c_t1[i]
    # has released; fits[i] = the head width first-fits there
    rel = (st.c_active[None, :] & st.c_active[:, None]
           & (st.c_t1[None, :] <= st.c_t1[:, None]))
    freed = st.free[None, :] | jnp.any(rel[:, :, None] & st.c_mask[None],
                                       axis=1)
    fits = st.c_active & jnp.any(
        _ALIGNED[widx][None, :]
        & jnp.all(freed[:, None, :] | ~_COVERED[widx][None], axis=2), axis=1)
    first = jnp.min(jnp.where(fits, st.c_t1, _INF))
    last = jnp.max(jnp.where(st.c_active, st.c_t1, -_INF))
    return jnp.where(jnp.any(fits), first,
                     jnp.where(jnp.any(st.c_active), last, jnp.float32(0.0)))


def _make_form_window(trace: TraceArrays, jobs: JobTable, window: int):
    """Build the window-formation step (the plan seam): pop <= ``window``
    pending submissions, run the first-sight protocol over the profiled
    bitmap, and materialize the solo plan — first-sight groups ahead of
    the planned remainder, both in submission order, exactly the schedule
    order ``submission_protocol`` + ``to_placements`` produce."""

    def form_window(st: _State, do) -> _State:
        A = trace.t.shape[0]
        J = st.profiled.shape[0]
        k = jnp.where(do, jnp.minimum(jnp.int32(window),
                                      st.pend_hi - st.pend_lo), jnp.int32(0))
        i_w = jnp.arange(window, dtype=jnp.int32)
        on = i_w < k
        arr = jnp.clip(st.pend_lo + i_w, 0, A - 1)
        jrow = trace.job[arr]

        # first-sight marking, loop-free: a submission profiles iff its
        # binary is new to the repository AND it is the first occurrence
        # inside this window (duplicates see their predecessor's insert)
        earlier_same = ((jrow[None, :] == jrow[:, None])
                        & (i_w[None, :] < i_w[:, None]) & on[None, :])
        fs = on & ~jnp.any(earlier_same, axis=1) & ~st.profiled[jrow]
        profiled = st.profiled.at[jnp.where(on, jrow, J)].set(
            True, mode="drop")

        # placement order: first-sight solos first, then the planned
        # remainder — each in submission order (stable two-pass ranks)
        n_fs = jnp.sum(fs, dtype=jnp.int32)
        rank_fs = jnp.cumsum(fs, dtype=jnp.int32) - 1
        rank_pl = jnp.cumsum(~fs & on, dtype=jnp.int32) - 1
        pos = jnp.where(fs, rank_fs, n_fs + rank_pl)

        # group log rows n_groups .. n_groups+k-1, ordered by `pos`
        grow = jnp.where(on, st.n_groups + pos, A)

        # append k ready slots in group order: group q claims the q-th
        # inactive ring slot in index order (seq follows placement order)
        free_rank = jnp.cumsum(~st.r_active, dtype=jnp.int32) - 1
        q = jnp.where(~st.r_active & (free_rank < k), free_rank,
                      jnp.int32(-1))
        sel = q >= 0
        err = st.err | jnp.where(
            jnp.sum(~st.r_active, dtype=jnp.int32) < k,
            jnp.int32(ERR_READY_OVERFLOW), jnp.int32(0))

        return st._replace(
            profiled=profiled,
            g_arr=st.g_arr.at[grow].set(arr, mode="drop"),
            g_job=st.g_job.at[grow].set(jrow, mode="drop"),
            r_active=st.r_active | sel,
            r_seq=jnp.where(sel, st.next_seq + q, st.r_seq),
            r_win=jnp.where(sel, st.dispatches, st.r_win),
            r_grp=jnp.where(sel, st.n_groups + q, st.r_grp),
            err=err, next_seq=st.next_seq + k, n_groups=st.n_groups + k,
            pend_lo=st.pend_lo + k,
            dispatches=st.dispatches + jnp.where(do, jnp.int32(1),
                                                 jnp.int32(0)))

    return form_window


# -------------------------------------------------------------- trace runs

def _build_run(window: int, backfill: bool, capacity: int,
               telemetry: bool = False):
    """The jitted single-trace engine: ONE flat ``lax.while_loop``.

    Each iteration performs exactly one micro-action of the heap's
    event/service interleaving — place the FCFS head if it fits, else
    (blocked head) admit the bounded EASY lookahead window, place the
    lowest-seq eligible backfill candidate, form a window onto an idle
    pod, or (no service progress) advance the clock to the next event and
    drain everything coincident with it.  Flat-with-masked-updates is the
    shape ``vmap`` wants: a batched nested ``while_loop`` runs every level
    to the slowest lane's trip count (multiplicative lockstep), while a
    single loop pays only the max of per-lane totals.

    One-candidate-per-iteration backfill is *exactly* the heap's
    multi-placement scan: a claim added by a backfill placement expires by
    ``t_res`` and occupies units that were free when the scan started, so
    replaying expiries after it yields the same ``t_res``, and a candidate
    skipped for lack of space stays unplaceable once ``free`` shrinks —
    re-scanning from the lowest seq is the same sequence of placements.

    ``telemetry=True`` threads a :class:`MetricsState` alongside the
    engine state (``run`` then returns ``(state, metrics)``): the wait
    histogram fills at each placement, the queue-depth/busy-unit
    integrals advance at each clock step — all predicated updates on the
    existing flags, so the ``_State`` trajectory is **bit-identical**
    with the flag on or off, and with the flag off (the default) the
    compiled program is the exact pre-telemetry engine.
    """
    max_steps = 2 * capacity + 4

    def run(trace: TraceArrays, jobs: JobTable,
            width=jnp.int32(N_UNITS)):
        # `width` is the pod's slice width (traced, so a fleet can vmap a
        # pod axis over it): a narrower pod is the same engine with the
        # upper units born busy — they are never claimed, never freed, and
        # every fit query sees them occupied, mirroring the heap's _Pod.
        form_window = _make_form_window(trace, jobs, window)
        A = capacity
        R = 2 * window + 2
        J = jobs.width.shape[0]
        f32, i32 = jnp.float32, jnp.int32
        st = _State(
            now=f32(0.0), now_lo=f32(0.0), pend_lo=i32(0), pend_hi=i32(0),
            profiled=jnp.zeros(J, dtype=bool),
            free=_UNIT_IDX < width,
            r_active=jnp.zeros(R, dtype=bool),
            r_seq=jnp.zeros(R, i32), r_win=jnp.zeros(R, i32),
            r_grp=jnp.zeros(R, i32), next_seq=i32(0),
            c_active=jnp.zeros(N_UNITS, dtype=bool),
            c_t1=jnp.zeros(N_UNITS, f32), c_lo=jnp.zeros(N_UNITS, f32),
            c_mask=jnp.zeros((N_UNITS, N_UNITS), dtype=bool),
            n_busy=i32(0), busy_t0=f32(0.0), busy_time=f32(0.0),
            slice_busy=jnp.zeros(N_UNITS, f32),
            dispatches=i32(0), backfills=i32(0), n_groups=i32(0),
            place_seq=i32(0), steps=i32(0), err=i32(0),
            g_arr=jnp.full(A, A, i32), g_job=jnp.zeros(A, i32),
            g_t0=jnp.zeros(A, f32), g_lo=jnp.zeros(A, f32),
            g_pack=jnp.zeros(A, i32),
        )

        def live(st: _State):
            return ((st.pend_hi < trace.n) | jnp.any(st.c_active)
                    | (st.pend_lo < st.pend_hi) | jnp.any(st.r_active))

        def body(carry):
            if telemetry:
                st, ms = carry
            else:
                st, ms = carry, None
            # The four service rules are mutually exclusive by their gates
            # (rule 1 needs a fitting head; 2-3 a blocked head; 4 no head),
            # so one merged form_window and one merged _place execute
            # whichever rule fired — halving the per-iteration scatter
            # count vs. one call per rule.
            # --- rule 1: place the FCFS head if it first-fits
            head, head_exists = _head(st)
            hwidx = jobs.widx[st.g_job[st.r_grp[head]]]
            ftab = _fit_table(st.free)
            fh = ftab[hwidx]
            start = jnp.argmax(fh).astype(jnp.int32)
            place_head = head_exists & jnp.any(fh)
            blocked = head_exists & ~place_head
            pending = st.pend_hi > st.pend_lo
            anyfree = jnp.any(st.free)
            # rule 4 — the heap's `elif`: idle pod, no ready head
            can_form = ~head_exists & pending & anyfree
            slot, sstart, do_bf = head, start, jnp.bool_(False)
            if backfill:
                # rule 2 — bounded EASY lookahead: a blocked head admits at
                # most one window past its own (all ready share its window)
                max_win = jnp.max(jnp.where(st.r_active, st.r_win,
                                            jnp.int32(-1)))
                can_look = (blocked & pending & anyfree
                            & (max_win == st.r_win[head]))
            else:
                can_look = jnp.bool_(False)
            st = form_window(st, can_look | can_form)
            if backfill:
                # rule 3 — EASY backfill: lowest-seq non-head candidate
                # that fits now and drains by the head's reserved start
                # (free is untouched on the blocked path, so `ftab` holds)
                can_scan = blocked & (jnp.sum(st.r_active,
                                              dtype=jnp.int32) > 1)
                t_res = _earliest_fit(st, hwidx)
                jr = st.g_job[st.r_grp]
                fr = ftab[jobs.widx[jr]]                  # (R, N_UNITS)
                starts = jnp.argmax(fr, axis=1).astype(jnp.int32)
                oks = jnp.any(fr, axis=1)
                durs = jobs.dur[jr]
                elig = (st.r_active & oks
                        & (jnp.arange(R, dtype=jnp.int32) != head)
                        & (st.now + durs <= t_res + 1e-9) & can_scan)
                cand = jnp.argmin(jnp.where(elig, st.r_seq,
                                            _BIG_SEQ)).astype(jnp.int32)
                do_bf = can_scan & jnp.any(elig)
                slot = jnp.where(place_head, head, cand)
                sstart = jnp.where(place_head, start, starts[cand])
            do_place = place_head | do_bf
            if telemetry:
                # wait histogram at placement: the placed group's arrival
                # index lives in the (post-form_window) group log
                arr = jnp.clip(st.g_arr[st.r_grp[slot]], 0, A - 1)
                wait = _since(st.now, st.now_lo, trace.t[arr], trace.t_lo[arr])
                b = jnp.searchsorted(_WAIT_EDGES, wait,
                                     side="left").astype(jnp.int32)
                nb = ms.wait_hist.shape[0]
                ms = ms._replace(
                    wait_hist=ms.wait_hist.at[
                        jnp.where(do_place, b, nb)].add(1, mode="drop"),
                    wait_sum=ms.wait_sum + jnp.where(do_place, wait, 0.0),
                    places=ms.places + jnp.where(do_place, jnp.int32(1),
                                                 jnp.int32(0)))
            st = _place(st, jobs, slot, sstart, do_bf, do_place)
            progress = place_head | can_look | do_bf | can_form

            # --- no service progress: advance the clock one event batch
            adv = ~progress
            t_next, lo_next = _next_event(st, trace)
            now = jnp.where(adv, t_next, st.now)
            # drain every coincident event: admit all arrivals with t<=now.
            # The trace is sorted and everything <= the old clock is already
            # admitted, so the new cursor is just the count of t <= now
            # (padding lanes are +inf and never admit).
            pend_hi = jnp.where(
                adv, jnp.sum(trace.t <= now, dtype=jnp.int32), st.pend_hi)
            # ... and release every claim with t1 <= now
            rel = adv & st.c_active & (st.c_t1 <= now)
            freed = jnp.any(rel[:, None] & st.c_mask, axis=0)
            w_rel = jnp.sum(jnp.where(rel[:, None], st.c_mask, False),
                            dtype=jnp.int32)
            n_busy = st.n_busy - w_rel
            busy_time = st.busy_time + jnp.where(
                (n_busy == 0) & (w_rel > 0), now - st.busy_t0, 0.0)
            steps = st.steps + jnp.where(adv, jnp.int32(1), jnp.int32(0))
            if telemetry:
                # event-gap integrals: depth/busy constant over [st.now, now)
                dt = now - st.now
                ms = ms._replace(
                    queue_depth_int=ms.queue_depth_int
                    + (st.pend_hi - st.pend_lo).astype(jnp.float32) * dt,
                    busy_unit_int=ms.busy_unit_int
                    + st.n_busy.astype(jnp.float32) * dt)
            st = st._replace(
                now=now, now_lo=jnp.where(adv, lo_next, st.now_lo),
                pend_hi=pend_hi, free=st.free | freed,
                c_active=st.c_active & ~rel, n_busy=n_busy,
                busy_time=busy_time, steps=steps,
                err=st.err | jnp.where(steps > max_steps,
                                       jnp.int32(ERR_EVENT_OVERFLOW),
                                       jnp.int32(0)))
            return (st, ms) if telemetry else st

        if telemetry:
            return jax.lax.while_loop(
                lambda c: live(c[0]) & (c[0].err == 0), body,
                (st, _metrics_init()))
        return jax.lax.while_loop(lambda s: live(s) & (s.err == 0), body, st)

    return run


def _records(st: _State, trace: TraceArrays, jobs: JobTable):
    """Per-arrival dispatch/finish lanes scattered from the group log."""
    A = trace.t.shape[0]
    dur = jobs.dur[st.g_job]                  # junk on unused rows; dropped
    dispatch = jnp.zeros(A, jnp.float32).at[st.g_arr].set(
        st.g_t0, mode="drop")
    finish = jnp.zeros(A, jnp.float32).at[st.g_arr].set(
        st.g_t0 + dur, mode="drop")
    return dispatch, finish


def _summarize(st, trace: TraceArrays, dispatch, finish,
               solo8) -> SweepSummary:
    """Shared summary tail over per-arrival dispatch/finish lanes — ``st``
    is either engine's state (both carry the busy/backfill/err lanes)."""
    A = trace.t.shape[0]
    valid = jnp.arange(A) < trace.n
    wait = dispatch - trace.t
    turnaround = finish - trace.t
    makespan = jnp.max(jnp.where(valid, finish, 0.0))
    solo = jnp.sum(jnp.where(valid, solo8, 0.0))
    nz = makespan > 0
    n = jnp.maximum(jnp.sum(valid), 1)
    return SweepSummary(
        makespan=makespan,
        throughput=jnp.where(nz, solo / makespan, 0.0),
        mean_wait=jnp.sum(jnp.where(valid, wait, 0.0)) / n,
        p50_wait=_percentile(wait, valid, 50.0),
        p99_wait=_percentile(wait, valid, 99.0),
        mean_turnaround=jnp.sum(jnp.where(valid, turnaround, 0.0)) / n,
        p95_turnaround=_percentile(turnaround, valid, 95.0),
        utilization=jnp.where(nz, st.busy_time / makespan, 0.0),
        slice_utilization=jnp.where(
            nz, jnp.sum(st.slice_busy) / (N_UNITS * makespan), 0.0),
        backfills=st.backfills,
        dispatches=st.dispatches,
        err=st.err,
    )


def _summary(st: _State, trace: TraceArrays, jobs: JobTable) -> SweepSummary:
    dispatch, finish = _records(st, trace, jobs)
    return _summarize(st, trace, dispatch, finish, jobs.solo8[trace.job])


# ------------------------------------------------------------ host wrapper

def metrics_dict(ms: MetricsState) -> dict:
    """Host-side dict of one (or a pod-summed) :class:`MetricsState` —
    keyed like the heap registry (``docs/observability.md``) so parity
    tests and exporters read both engines uniformly."""
    counts = np.asarray(ms.wait_hist)
    return {
        "wait_s": {"edges": list(WAIT_BUCKETS_S),
                   "counts": counts.tolist(),
                   "sum": float(ms.wait_sum),
                   "count": int(counts.sum())},
        "queue_depth_integral_s": float(ms.queue_depth_int),
        "busy_unit_s": float(ms.busy_unit_int),
        "groups_placed": int(ms.places),
    }


def compile_trace(trace: list[Arrival], capacity: int,
                  names: dict[str, int] | None = None,
                  jobs: list | None = None) -> tuple[TraceArrays, list]:
    """Sort + pad one trace into :class:`TraceArrays`.

    ``names``/``jobs`` accumulate the distinct-job table across traces of a
    sweep (keyed by profile name, 1:1 with the repository's binary key), so
    a whole batch shares one :class:`JobTable`.  Returns the sorted
    arrival list alongside (the wrapper builds ``JobRecord``\\ s from it).
    """
    if len(trace) > capacity:
        raise ValueError(
            f"trace has {len(trace)} arrivals > capacity {capacity}; "
            f"the event table is fixed-size — raise `capacity`")
    order = sorted(trace, key=lambda a: a.t)
    names = {} if names is None else names
    jobs = [] if jobs is None else jobs
    rows = []
    for a in order:
        r = names.setdefault(a.profile.name, len(names))
        if r == len(jobs):
            jobs.append(a.profile)
        rows.append(r)
    t64 = np.array([a.t for a in order], np.float64)
    t = np.full(capacity, np.inf, np.float32)
    t[:len(order)] = t64
    t_lo = np.zeros(capacity, np.float32)
    t_lo[:len(order)] = t64 - t[:len(order)]
    job = np.zeros(capacity, np.int32)
    job[:len(rows)] = rows
    return TraceArrays(t=jnp.asarray(t), t_lo=jnp.asarray(t_lo),
                       job=jnp.asarray(job), n=jnp.int32(len(order))), order


def build_job_table(jobs: list) -> JobTable:
    """Float64 per-job solo durations at the requested width, cast once —
    the heap's per-group ``corun`` predictions for solo placements."""
    table = solo_duration_table(jobs)                 # (J, U) float64
    width = np.array([j.requested_units for j in jobs], np.int32)
    widx = np.searchsorted(np.asarray(UNIT_SIZES), width).astype(np.int32)
    dur = table[np.arange(len(jobs)), widx]
    solo8 = np.array([j.solo_time() for j in jobs], np.float64)
    return JobTable(width=jnp.asarray(width), widx=jnp.asarray(widx),
                    dur=jnp.asarray(dur, jnp.float32),
                    solo8=jnp.asarray(solo8, jnp.float32))


def _placement_times(st, g_n: int) -> np.ndarray:
    """Float64 placement times of a lane's first ``g_n`` groups: each
    clock pair summed on the host."""
    return (np.asarray(st.g_t0, np.float64)[:g_n]
            + np.asarray(st.g_lo, np.float64)[:g_n])


def _emit_lane(st: _State, jt: JobTable, records: list[JobRecord],
               pod: int = 0) -> list[Segment]:
    """Scatter one engine lane's group log into its (sorted-subtrace-
    indexed) ``JobRecord``\\ s and return the lane's :class:`Segment`\\ s
    in placement order — the reconstruction shared by the single-pod and
    fleet wrappers."""
    g_n = int(st.n_groups)
    g_arr = np.asarray(st.g_arr)[:g_n]
    g_t0 = _placement_times(st, g_n)
    g_job = np.asarray(st.g_job)[:g_n]
    g_dur = np.asarray(jt.dur, np.float64)[g_job]
    g_w = np.asarray(jt.width)[g_job]
    pack = np.asarray(st.g_pack)[:g_n]
    g_pseq, g_start, g_bf = pack >> 4, (pack >> 1) & 7, (pack & 1) == 1
    labels = {w: solo_partition(int(w)).label for w in set(g_w.tolist())}
    for g in range(g_n):
        rec = records[int(g_arr[g])]
        rec.dispatch = float(g_t0[g])
        rec.finish = float(g_t0[g] + g_dur[g])
        rec.group_size = 1
        rec.partition = labels[int(g_w[g])]
        rec.units = int(g_w[g])
        rec.backfilled = bool(g_bf[g])
        rec.pod = pod
    return [Segment(t0=float(g_t0[g]), t1=float(g_t0[g] + g_dur[g]), jobs=1,
                    partition=labels[int(g_w[g])],
                    slices=((int(g_start[g]), int(g_w[g])),),
                    backfilled=bool(g_bf[g]), pod=pod)
            for g in np.argsort(g_pseq)]


# ------------------------------------------------------------- RL serving
#
# The in-graph policy seam: the same engine skeleton, but the plan chosen
# at window formation comes from the DQN's greedy co-schedule episode
# (CoScheduleEnv, run as a lax.scan of masked dqn_apply forward passes)
# instead of the static solo plan.  The flat while_loop splits in two —
# an *inner* service/clock loop (cheap, every event) and an *outer*
# window loop whose body runs the episode (expensive, ~n/window times):
# under vmap a frozen lane skips neither, so hoisting the network out of
# the per-event loop is what makes batched RL serving fast.

class RLJobTable(NamedTuple):
    """Distinct-job lanes for the RL engine (row ``J`` = padding).

    The job list is padded to a power-of-two row count (repeating job 0)
    before the table is built, so sweeps over many randomized traces
    retrace the jitted engine at most ``log2`` times.
    """

    widx: jnp.ndarray            # (J+1,) i32 — requested width index
    dur_wu: jnp.ndarray          # (J+1, U) f32 — solo makespan per width
                                 #            (float64 corun, cast once)
    solo8: jnp.ndarray           # (J+1,) f32 — full-pod solo time
    terms: JobTermsTable         # (J+1, ...) — roofline terms + features


def build_rl_job_table(jobs: list) -> RLJobTable:
    J = max(8, 1 << max(0, len(jobs) - 1).bit_length())
    padded = list(jobs) + [jobs[0]] * (J - len(jobs))
    tab = solo_duration_table(padded)                 # (J, U) float64
    width = np.array([j.requested_units for j in padded], np.int32)
    widx = np.searchsorted(np.asarray(UNIT_SIZES), width).astype(np.int32)
    U = len(UNIT_SIZES)
    return RLJobTable(
        widx=jnp.asarray(np.concatenate([widx, [U - 1]]).astype(np.int32)),
        dur_wu=jnp.asarray(np.concatenate([tab, np.zeros((1, U))]),
                           jnp.float32),
        solo8=jnp.asarray(
            np.concatenate([[j.solo_time() for j in padded], [0.0]]),
            jnp.float32),
        terms=job_terms_table(padded))


class TrainRollout(NamedTuple):
    """Per-trace training logs emitted by the ``train=True`` RL engine.

    The window-formation seam is the decision surface: row ``w`` of the
    ``(A, T_EP, ...)`` lanes holds window ``w``'s episode — the exact
    observations the agent saw, the (ε-greedy) actions it took, the env
    validity masks, and a per-step ``valid`` flag (False once the episode
    is done or the window never formed).  ``w_wait`` / ``w_turn`` are the
    queueing outcome attributed back to the deciding window: at every
    placement the placed entry's member waits (``now - arrival``) and
    turnarounds (``now + finish_offset - arrival``) are scatter-added into
    the bucket of the window that *formed* the entry (``r_win``), so
    summing the buckets reproduces the serving engine's per-record
    wait/turnaround totals exactly (f32) — the invariant
    ``tests/test_queueing_reward.py`` fuzzes against the heap.
    """

    obs: jnp.ndarray             # (A, T_EP, D) f32 — episode observations
    act: jnp.ndarray             # (A, T_EP) i32 — actions taken
    mask: jnp.ndarray            # (A, T_EP, W+P) bool — validity masks
    valid: jnp.ndarray           # (A, T_EP) bool — real decision steps
    w_wait: jnp.ndarray          # (A,) f32 — Σ member waits per window
    w_turn: jnp.ndarray          # (A,) f32 — Σ member turnarounds per window


class _RLState(NamedTuple):
    """RL-engine lanes: the TS state plus the grouped-entry log.

    Entries (one ready-queue unit = one heap ``Placement``) carry up to
    ``C = c_max`` members; solo entries use partition row 0 (the full-pod
    solo — ``enumerate_partitions`` puts it first) with the fitted width
    in ``g_uidx``, so one layout covers first-sight runs, kept groups,
    and fallback/refit decompositions alike."""

    now: jnp.ndarray             # () f32
    now_lo: jnp.ndarray          # () f32
    pend_lo: jnp.ndarray         # () i32
    pend_hi: jnp.ndarray         # () i32
    profiled: jnp.ndarray        # (J,) bool
    free: jnp.ndarray            # (N_UNITS,) bool
    r_active: jnp.ndarray        # (R,) bool
    r_seq: jnp.ndarray           # (R,) i32
    r_win: jnp.ndarray           # (R,) i32
    r_grp: jnp.ndarray           # (R,) i32
    next_seq: jnp.ndarray        # () i32
    c_active: jnp.ndarray        # (N_UNITS,) bool
    c_t1: jnp.ndarray            # (N_UNITS,) f32
    c_lo: jnp.ndarray            # (N_UNITS,) f32
    c_mask: jnp.ndarray          # (N_UNITS, N_UNITS) bool
    n_busy: jnp.ndarray          # () i32
    busy_t0: jnp.ndarray         # () f32
    busy_time: jnp.ndarray       # () f32
    slice_busy: jnp.ndarray      # (N_UNITS,) f32
    dispatches: jnp.ndarray      # () i32
    backfills: jnp.ndarray       # () i32
    refits: jnp.ndarray          # () i32 — pod-width decompositions
    n_groups: jnp.ndarray        # () i32
    place_seq: jnp.ndarray       # () i32
    steps: jnp.ndarray           # () i32
    err: jnp.ndarray             # () i32
    # entry log (A rows; C = c_max member slots each)
    g_arr: jnp.ndarray           # (A, C) i32 — member arrival index
    g_job: jnp.ndarray           # (A, C) i32 — member job row
    g_size: jnp.ndarray         # (A,) i32 — member count
    g_pidx: jnp.ndarray          # (A,) i32 — planned partition row
    g_uidx: jnp.ndarray          # (A, C) i32 — fitted per-slot width index
    g_dur: jnp.ndarray           # (A,) f32 — claim horizon (makespan)
    g_ft: jnp.ndarray            # (A, C) f32 — per-slot finish offsets
    g_start: jnp.ndarray         # (A, C) i32 — per-slice start offsets
    g_t0: jnp.ndarray            # (A,) f32 — placement time
    g_lo: jnp.ndarray            # (A,) f32 — placement time residual
    g_pack: jnp.ndarray          # (A,) i32 — (pseq << 1) | backfilled


def _build_run_rl(window: int, backfill: bool, capacity: int,
                  telemetry: bool, env_cfg, train: bool = False):
    """The jitted RL single-trace engine.

    Two nested ``lax.while_loop``\\ s: the inner loop is the TS engine's
    service/clock body generalized to multi-slice entries, and *exits*
    (``want``) where the TS engine would form a window; the outer body
    then runs the window-formation seam — observation assembly, the
    greedy DQN episode, the §IV-A fallback guard, and pod-width fitting —
    once per window.  Scheduling semantics (formation gates, EASY
    backfill, claim replay) are unchanged from ``_build_run``; only the
    plan materialized at the seam differs.

    ``train=True`` adds the sim-in-the-loop training surface: ``run``
    takes a PRNG ``key`` and a *traced* exploration rate ``eps`` (so the
    ε schedule never recompiles), the episode acts ε-greedily over the
    same validity mask, and the returned :class:`TrainRollout` carries
    per-step (obs, act, mask, valid) logs plus per-window wait/turnaround
    buckets scatter-added at placement.  Step keys derive from
    ``fold_in(fold_in(key, window), step)`` so the stream is independent
    of vmap lockstep, and ``eps == 0`` reproduces the serving engine's
    greedy decisions bit-for-bit.
    """
    assert window <= env_cfg.window, (window, env_cfg.window)
    W = env_cfg.window
    C = env_cfg.c_max
    obs_ctx = env_cfg.obs_context
    parts = enumerate_partitions(C)
    P = len(parts)
    ptable = build_partition_table(parts, C)
    # static per-(partition, slot) masks: dedicated slice (single share ->
    # shrinks to the member's requested width) and first-slot-of-slice
    # (per-slice reductions over slot lanes)
    ded = np.zeros((P, C), bool)
    first = np.zeros((P, C), bool)
    for p_i, p in enumerate(parts):
        seen: set[int] = set()
        for s_i, (si, s, _b) in enumerate(p.slots):
            ded[p_i, s_i] = len(s.shares) == 1
            if si not in seen:
                first[p_i, s_i] = True
                seen.add(si)
    dedj = jnp.asarray(ded)
    firstj = jnp.asarray(first)
    units_arr = jnp.asarray(np.array(UNIT_SIZES, np.int32))
    U = len(UNIT_SIZES)
    A = capacity
    R = 2 * window + 2
    T_EP = 2 * W                 # selects + closes bound any episode
    max_steps = 2 * capacity + 4
    i32, f32 = jnp.int32, jnp.float32
    c_rng = jnp.arange(C, dtype=jnp.int32)
    w_rng = jnp.arange(W, dtype=jnp.int32)

    def slice_widths(p, uidx):
        """Per-slice (width index, validity) of partition row ``p`` under
        fitted per-slot widths ``uidx`` -> ((C,), (C,))."""
        eq = ((ptable.slot_slice[p][None, :] == c_rng[:, None])
              & ptable.slot_valid[p][None, :])
        svalid = jnp.any(eq, axis=1)
        svec = jnp.max(jnp.where(eq, uidx[None, :], -1), axis=1).astype(i32)
        return svec, svalid

    def fit_multi(free, svec, svalid):
        """In-graph ``find_offsets``: first-fit-decreasing placement of the
        partition's slices onto ``free``.  Python's stable sort breaks
        width ties by slice index; ``-units * C + index`` reproduces that
        order exactly.  Returns (all-fit, per-slice starts, claimed
        union mask)."""
        units = units_arr[jnp.clip(svec, 0, U - 1)]
        key = jnp.where(svalid, -units * C + c_rng, jnp.int32(2 ** 15))
        order = jnp.argsort(key)
        starts = jnp.zeros(C, i32)
        ok = jnp.bool_(True)
        cur = free
        union = jnp.zeros(N_UNITS, dtype=bool)
        for step in range(C):                  # static: C slices max
            sid = order[step]
            act = svalid[sid]
            w_i = jnp.clip(svec[sid], 0, U - 1)
            cand = _ALIGNED[w_i] & jnp.all(cur[None, :] | ~_COVERED[w_i],
                                           axis=1)
            has = jnp.any(cand)
            s0 = jnp.argmax(cand).astype(i32)
            ok = ok & (has | ~act)
            m = _claim_units(s0, units_arr[w_i]) & act & has
            cur = cur & ~m
            union = union | m
            starts = starts.at[sid].set(jnp.where(act, s0, 0))
        return ok, starts, union

    def earliest_fit_multi(st: _RLState, svec, svalid):
        """Multi-slice ``_earliest_fit``: replay claim expiries, earliest
        fitting one wins (same candidate argument as the TS engine)."""
        rel = (st.c_active[None, :] & st.c_active[:, None]
               & (st.c_t1[None, :] <= st.c_t1[:, None]))
        freed = st.free[None, :] | jnp.any(rel[:, :, None] & st.c_mask[None],
                                           axis=1)
        oks = jax.vmap(lambda f: fit_multi(f, svec, svalid)[0])(freed)
        fits = st.c_active & oks
        first_t = jnp.min(jnp.where(fits, st.c_t1, _INF))
        last = jnp.max(jnp.where(st.c_active, st.c_t1, -_INF))
        return jnp.where(jnp.any(fits), first_t,
                         jnp.where(jnp.any(st.c_active), last, f32(0.0)))

    def place_rl(st: _RLState, slot, starts, union, backfilled,
                 do) -> _RLState:
        g = st.r_grp[slot]
        dur = st.g_dur[g]
        mask = union & do
        w = jnp.sum(mask, dtype=i32)
        doi = jnp.where(do, i32(1), i32(0))
        gt = jnp.where(do, g, A)
        ct = jnp.where(do, jnp.argmin(st.c_active).astype(i32), N_UNITS)
        rt = jnp.where(do, slot, R)
        pack = (st.place_seq << 1) | jnp.where(backfilled, i32(1), i32(0))
        t1, t1_lo = _clock_add(st.now, st.now_lo, dur)
        return st._replace(
            free=st.free & ~mask,
            busy_t0=jnp.where(do & (st.n_busy == 0), st.now, st.busy_t0),
            n_busy=st.n_busy + w,
            c_active=st.c_active.at[ct].set(True, mode="drop"),
            c_t1=st.c_t1.at[ct].set(t1, mode="drop"),
            c_lo=st.c_lo.at[ct].set(t1_lo, mode="drop"),
            c_mask=st.c_mask.at[ct].set(mask, mode="drop"),
            slice_busy=st.slice_busy + jnp.where(mask, dur, 0.0),
            g_t0=st.g_t0.at[gt].set(st.now, mode="drop"),
            g_lo=st.g_lo.at[gt].set(st.now_lo, mode="drop"),
            g_start=st.g_start.at[gt].set(starts, mode="drop"),
            g_pack=st.g_pack.at[gt].set(pack, mode="drop"),
            place_seq=st.place_seq + doi,
            r_active=st.r_active.at[rt].set(False, mode="drop"),
            backfills=st.backfills + jnp.where(do & backfilled, i32(1),
                                               i32(0)))

    def run(trace: TraceArrays, rjt: RLJobTable, params,
            width=jnp.int32(N_UNITS), key=None, eps=None):
        Jp = rjt.widx.shape[0] - 1               # padding row index
        pod_widx = jnp.searchsorted(units_arr, width).astype(i32)
        tt = rjt.terms
        if train:
            n_feat = tt.features.shape[1]
            D = W * (n_feat + 5) + (N_UNITS + W + 1 if obs_ctx else 0)
            roll0 = TrainRollout(
                obs=jnp.zeros((A, T_EP, D), f32),
                act=jnp.zeros((A, T_EP), i32),
                mask=jnp.zeros((A, T_EP, W + P), dtype=bool),
                valid=jnp.zeros((A, T_EP), dtype=bool),
                w_wait=jnp.zeros(A, f32),
                w_turn=jnp.zeros(A, f32))
        else:
            roll0 = ()
        st0 = _RLState(
            now=f32(0.0), now_lo=f32(0.0), pend_lo=i32(0), pend_hi=i32(0),
            profiled=jnp.zeros(Jp, dtype=bool),
            free=_UNIT_IDX < width,
            r_active=jnp.zeros(R, dtype=bool),
            r_seq=jnp.zeros(R, i32), r_win=jnp.zeros(R, i32),
            r_grp=jnp.zeros(R, i32), next_seq=i32(0),
            c_active=jnp.zeros(N_UNITS, dtype=bool),
            c_t1=jnp.zeros(N_UNITS, f32), c_lo=jnp.zeros(N_UNITS, f32),
            c_mask=jnp.zeros((N_UNITS, N_UNITS), dtype=bool),
            n_busy=i32(0), busy_t0=f32(0.0), busy_time=f32(0.0),
            slice_busy=jnp.zeros(N_UNITS, f32),
            dispatches=i32(0), backfills=i32(0), refits=i32(0),
            n_groups=i32(0), place_seq=i32(0), steps=i32(0), err=i32(0),
            g_arr=jnp.full((A, C), A, i32), g_job=jnp.full((A, C), Jp, i32),
            g_size=jnp.zeros(A, i32), g_pidx=jnp.zeros(A, i32),
            g_uidx=jnp.zeros((A, C), i32), g_dur=jnp.zeros(A, f32),
            g_ft=jnp.zeros((A, C), f32), g_start=jnp.zeros((A, C), i32),
            g_t0=jnp.zeros(A, f32), g_lo=jnp.zeros(A, f32),
            g_pack=jnp.zeros(A, i32))

        def live(st):
            return ((st.pend_hi < trace.n) | jnp.any(st.c_active)
                    | (st.pend_lo < st.pend_hi) | jnp.any(st.r_active))

        def form_and_plan(st: _RLState, roll, do):
            if train:
                # one episode key per window; independent of how many
                # outer iterations frozen sibling lanes burn under vmap
                ep_key = jax.random.fold_in(key, st.dispatches)
            # ---- pop & first-sight protocol (same as _make_form_window)
            k = jnp.where(do, jnp.minimum(jnp.int32(window),
                                          st.pend_hi - st.pend_lo), i32(0))
            i_w = jnp.arange(window, dtype=jnp.int32)
            on = i_w < k
            arr = jnp.clip(st.pend_lo + i_w, 0, A - 1)
            jrow = trace.job[arr]
            earlier_same = ((jrow[None, :] == jrow[:, None])
                            & (i_w[None, :] < i_w[:, None]) & on[None, :])
            fs = on & ~jnp.any(earlier_same, axis=1) & ~st.profiled[jrow]
            profiled = st.profiled.at[jnp.where(on, jrow, Jp)].set(
                True, mode="drop")
            n_fs = jnp.sum(fs, dtype=i32)
            rank_fs = jnp.cumsum(fs, dtype=i32) - 1
            rank_pl = jnp.cumsum(~fs & on, dtype=i32) - 1
            n_pl = jnp.sum(~fs & on, dtype=i32)

            # ---- the profiled chunk as env-window queue rows (<= W)
            pt = jnp.where(~fs & on, rank_pl, W)
            pl_job = jnp.full(W, Jp, i32).at[pt].set(jrow, mode="drop")
            pl_arr = jnp.full(W, A, i32).at[pt].set(arr, mode="drop")
            pl_valid = w_rng < n_pl
            qa = QueueArrays(
                features=tt.features[pl_job], valid=pl_valid,
                comp=tt.comp[pl_job], mem=tt.mem[pl_job],
                collb=tt.collb[pl_job], colll=tt.colll[pl_job],
                fixedt=tt.fixedt[pl_job], steps=tt.steps[pl_job],
                solo=tt.solo[pl_job], cpct=tt.cpct[pl_job],
                mpct=tt.mpct[pl_job],
                mean_c=f32(1.0), mean_m=f32(1.0), mean_d=f32(1.0))
            if obs_ctx:
                # dispatch_obs_context in-graph: busy mask, per-slot ages,
                # pending depth left behind (float32 mirror of the heap's
                # float64 snapshot — context parity is approximate)
                busy_f = (~st.free).astype(jnp.float32)
                pl_i = jnp.clip(pl_arr, 0, A - 1)
                age = _since(st.now, st.now_lo, trace.t[pl_i],
                             trace.t_lo[pl_i])
                ages_f = jnp.where(
                    pl_valid,
                    jnp.log10(1.0 + jnp.maximum(age, 0.0)) / 6.0, 0.0)
                depth = jnp.minimum(
                    (st.pend_hi - st.pend_lo - k).astype(jnp.float32)
                    / (4.0 * W), 1.0)
                ctx_vec = jnp.concatenate(
                    [busy_f, ages_f.astype(jnp.float32), depth[None]])

            # ---- greedy co-schedule episode (CoScheduleEnv in-graph)
            def ep_step(carry, t):
                sched, gidx, gsize, pm, psize, ppidx, nplan = carry
                member = jnp.zeros(W, dtype=bool).at[
                    jnp.where(c_rng < gsize, gidx, W)].set(True, mode="drop")
                avail = pl_valid & ~sched & ~member
                prog = gsize.astype(jnp.float32) / jnp.float32(max(1, C))
                flags = jnp.stack([
                    jnp.where(avail, 1.0, 0.0),
                    jnp.where(member, 1.0, 0.0),
                    jnp.where(sched, 1.0, 0.0),
                    jnp.where(~pl_valid, 1.0, 0.0),
                    jnp.where(pl_valid, prog, 0.0)],
                    axis=1).astype(jnp.float32)
                obs = jnp.concatenate([qa.features, flags],
                                      axis=1).reshape(-1)
                if obs_ctx:
                    obs = jnp.concatenate([obs, ctx_vec])
                mask = jnp.concatenate([avail & (gsize < C),
                                        (gsize >= 1)
                                        & (ptable.arity == gsize)])
                done = jnp.all(sched | ~pl_valid) & (gsize == 0)
                act = greedy_q_action(params, obs, mask)
                if train:
                    # ε-greedy over the same mask (act_batch's idiom):
                    # uniform scores, invalid lanes at -1, argmax wins
                    ka, kb = jax.random.split(jax.random.fold_in(ep_key, t))
                    explore = jax.random.uniform(ka, ()) < eps
                    scores = jax.random.uniform(kb, mask.shape)
                    rand = jnp.argmax(
                        jnp.where(mask, scores, -1.0)).astype(i32)
                    act = jnp.where(explore, rand, act)
                do_sel = ~done & (act < W)
                do_close = ~done & (act >= W)
                row = jnp.where(do_close, nplan, W)
                pm = pm.at[row].set(gidx, mode="drop")
                psize = psize.at[row].set(gsize, mode="drop")
                ppidx = ppidx.at[row].set(jnp.clip(act - W, 0, P - 1),
                                          mode="drop")
                sched = sched | (member & do_close)
                gidx = gidx.at[jnp.where(do_sel, jnp.clip(gsize, 0, C - 1),
                                         C)].set(act, mode="drop")
                gidx = jnp.where(do_close, jnp.full(C, -1, i32), gidx)
                gsize = jnp.where(do_close, i32(0),
                                  gsize + jnp.where(do_sel, i32(1), i32(0)))
                nplan = nplan + jnp.where(do_close, i32(1), i32(0))
                ys = (obs, act, mask, ~done) if train else None
                return (sched, gidx, gsize, pm, psize, ppidx, nplan), ys

            init = (jnp.zeros(W, dtype=bool), jnp.full(C, -1, i32), i32(0),
                    jnp.full((W, C), -1, i32), jnp.zeros(W, i32),
                    jnp.zeros(W, i32), i32(0))
            (e_sched, _, e_gsize, pm, psize, ppidx, nplan), ep_ys = \
                jax.lax.scan(ep_step, init,
                             jnp.arange(T_EP, dtype=i32) if train else None,
                             length=T_EP)
            done_f = jnp.all(e_sched | ~pl_valid) & (e_gsize == 0)
            err_ep = jnp.where(do & ~done_f, i32(ERR_EPISODE), i32(0))
            if train:
                o_y, a_y, m_y, v_y = ep_ys
                wrow = jnp.where(do, st.dispatches, A)
                roll = roll._replace(
                    obs=roll.obs.at[wrow].set(o_y, mode="drop"),
                    act=roll.act.at[wrow].set(a_y, mode="drop"),
                    mask=roll.mask.at[wrow].set(m_y, mode="drop"),
                    valid=roll.valid.at[wrow].set(v_y, mode="drop"))

            # ---- §IV-A fallback + pod-width fitting, over planned rows
            row_on = w_rng < nplan
            mvalid = (c_rng[None, :] < psize[:, None]) & row_on[:, None]
            mslot = jnp.clip(pm, 0, W - 1)
            mjob = jnp.where(mvalid, pl_job[mslot], Jp)
            mwidx = rjt.widx[mjob]
            uplan = ptable.slot_units_idx[ppidx]
            uidx_fit = jnp.where(dedj[ppidx],
                                 jnp.minimum(uplan, mwidx), uplan)
            mk_plan, solo_sum, _ri = jax.vmap(
                lambda m, s, p: group_metrics(ptable, qa, m, s, p))(
                    pm, psize, ppidx)
            _mk, _s2, _r2, ft_fit = jax.vmap(
                lambda m, s, p, u: group_metrics(
                    ptable, qa, m, s, p, units_idx=u, with_finish=True))(
                    pm, psize, ppidx, uidx_fit)
            mk_fit = jnp.max(ft_fit, axis=1)
            fallback = row_on & (psize > 1) & (mk_plan > solo_sum)
            wfit = units_arr[uidx_fit]
            ftot = jnp.sum(jnp.where(firstj[ppidx] & ptable.slot_valid[ppidx],
                                     wfit, 0), axis=1)
            refit = row_on & ~fallback & (ftot > width)
            split = fallback | refit
            solo_widx = jnp.minimum(mwidx, pod_widx)
            solo_dur = rjt.dur_wu[mjob, solo_widx]
            fs_widx = jnp.minimum(rjt.widx[jrow], pod_widx)
            fs_dur = rjt.dur_wu[jrow, fs_widx]
            refits_add = (
                jnp.sum(jnp.where(refit, 1, 0))
                + jnp.sum(jnp.where(fallback[:, None] & mvalid
                                    & (mwidx > pod_widx), 1, 0))
                + jnp.sum(jnp.where(fs & (rjt.widx[jrow] > pod_widx), 1, 0)))

            # ---- entry expansion, in schedule order: first-sight solos,
            # then plan rows (split rows decompose to members in place)
            E = jnp.where(row_on, jnp.where(split, psize, 1), 0)
            off = n_fs + jnp.cumsum(E) - E
            n_ent = n_fs + jnp.sum(E)
            EN = window
            e_rng = jnp.arange(EN, dtype=jnp.int32)
            ent_job = jnp.full((EN, C), Jp, i32)
            ent_size = jnp.zeros(EN, i32)
            ent_pidx = jnp.zeros(EN, i32)      # row 0 = the full-pod solo
            ent_uidx = jnp.zeros((EN, C), i32)
            ent_dur = jnp.zeros(EN, f32)
            ent_ft = jnp.zeros((EN, C), f32)
            tfs = jnp.where(fs, rank_fs, EN)
            ent_job = ent_job.at[tfs, 0].set(jrow, mode="drop")
            ent_size = ent_size.at[tfs].set(1, mode="drop")
            ent_uidx = ent_uidx.at[tfs, 0].set(fs_widx, mode="drop")
            ent_dur = ent_dur.at[tfs].set(fs_dur, mode="drop")
            ent_ft = ent_ft.at[tfs, 0].set(fs_dur, mode="drop")
            # kept plan rows: single-member groups take the exact float64
            # solo duration (bit-equal to the heap's corun); true co-run
            # groups take the float32 in-graph model (clock-only drift)
            one = psize == 1
            grp_dur = jnp.where(one, rjt.dur_wu[mjob[:, 0], uidx_fit[:, 0]],
                                mk_fit)
            grp_ft = jnp.where(one[:, None],
                               jnp.where(c_rng[None, :] == 0,
                                         grp_dur[:, None], 0.0),
                               ft_fit)
            tg = jnp.where(row_on & ~split, off, EN)
            ent_job = ent_job.at[tg].set(mjob, mode="drop")
            ent_size = ent_size.at[tg].set(psize, mode="drop")
            ent_pidx = ent_pidx.at[tg].set(ppidx, mode="drop")
            ent_uidx = ent_uidx.at[tg].set(uidx_fit, mode="drop")
            ent_dur = ent_dur.at[tg].set(grp_dur, mode="drop")
            ent_ft = ent_ft.at[tg].set(grp_ft, mode="drop")
            # split rows: member solos, submission slots preserved in place
            tsp = jnp.where(split[:, None] & mvalid,
                            off[:, None] + c_rng[None, :], EN).reshape(-1)
            ent_job = ent_job.at[tsp, 0].set(mjob.reshape(-1), mode="drop")
            ent_size = ent_size.at[tsp].set(1, mode="drop")
            ent_pidx = ent_pidx.at[tsp].set(0, mode="drop")
            ent_uidx = ent_uidx.at[tsp, 0].set(solo_widx.reshape(-1),
                                               mode="drop")
            ent_dur = ent_dur.at[tsp].set(solo_dur.reshape(-1), mode="drop")
            ent_ft = ent_ft.at[tsp, 0].set(solo_dur.reshape(-1), mode="drop")
            # submission attribution is name-keyed FIFO in schedule-entry
            # order (the heap's _form_window by_name deques): when one
            # binary is popped twice into a window, the *entry* order — not
            # the agent's row choice — decides which arrival each entry
            # serves.  The o-th entry member of a job row takes the o-th
            # popped arrival of that row.
            flat_job = ent_job.reshape(-1)
            p_rng = jnp.arange(EN * C, dtype=i32)
            occ_ent = jnp.sum((flat_job[None, :] == flat_job[:, None])
                              & (p_rng[None, :] < p_rng[:, None]),
                              axis=1, dtype=i32)
            occ_pop = jnp.sum(earlier_same, axis=1, dtype=i32)
            amatch = ((jrow[None, :] == flat_job[:, None])
                      & (occ_pop[None, :] == occ_ent[:, None]) & on[None, :])
            ent_arr = jnp.where(
                jnp.any(amatch, axis=1),
                jnp.max(jnp.where(amatch, arr[None, :], 0), axis=1),
                A).reshape(EN, C).astype(i32)

            # ---- ring append (n_ent entries) + group-log scatter
            free_rank = jnp.cumsum(~st.r_active, dtype=i32) - 1
            q = jnp.where(~st.r_active & (free_rank < n_ent), free_rank,
                          i32(-1))
            sel = q >= 0
            err_ring = jnp.where(
                jnp.sum(~st.r_active, dtype=i32) < n_ent,
                i32(ERR_READY_OVERFLOW), i32(0))
            grow = jnp.where(e_rng < n_ent, st.n_groups + e_rng, A)
            st = st._replace(
                profiled=profiled,
                g_arr=st.g_arr.at[grow].set(ent_arr, mode="drop"),
                g_job=st.g_job.at[grow].set(ent_job, mode="drop"),
                g_size=st.g_size.at[grow].set(ent_size, mode="drop"),
                g_pidx=st.g_pidx.at[grow].set(ent_pidx, mode="drop"),
                g_uidx=st.g_uidx.at[grow].set(ent_uidx, mode="drop"),
                g_dur=st.g_dur.at[grow].set(ent_dur, mode="drop"),
                g_ft=st.g_ft.at[grow].set(ent_ft, mode="drop"),
                r_active=st.r_active | sel,
                r_seq=jnp.where(sel, st.next_seq + q, st.r_seq),
                r_win=jnp.where(sel, st.dispatches, st.r_win),
                r_grp=jnp.where(sel, st.n_groups + q, st.r_grp),
                next_seq=st.next_seq + n_ent,
                n_groups=st.n_groups + n_ent,
                pend_lo=st.pend_lo + k,
                refits=st.refits + refits_add,
                err=st.err | err_ep | err_ring,
                dispatches=st.dispatches + jnp.where(do, i32(1), i32(0)))
            return st, roll

        def inner_body(carry):
            st, ms, roll, _w = carry
            head, head_exists = _head(st)
            hg = st.r_grp[head]
            hsvec, hsvalid = slice_widths(st.g_pidx[hg], st.g_uidx[hg])
            ok_h, starts_h, union_h = fit_multi(st.free, hsvec, hsvalid)
            place_head = head_exists & ok_h
            blocked = head_exists & ~place_head
            pending = st.pend_hi > st.pend_lo
            anyfree = jnp.any(st.free)
            can_form = ~head_exists & pending & anyfree
            if backfill:
                max_win = jnp.max(jnp.where(st.r_active, st.r_win,
                                            jnp.int32(-1)))
                can_look = (blocked & pending & anyfree
                            & (max_win == st.r_win[head]))
            else:
                can_look = jnp.bool_(False)
            want = can_look | can_form       # exit: the outer body forms
            slot, sstarts, sunion = head, starts_h, union_h
            do_bf = jnp.bool_(False)
            if backfill:
                # the heap scans in the same pass it forms; here the scan
                # waits one iteration (~want) so it sees the formed ring
                can_scan = blocked & ~want & (jnp.sum(st.r_active,
                                                      dtype=i32) > 1)
                t_res = earliest_fit_multi(st, hsvec, hsvalid)
                svecs, svalids = jax.vmap(
                    lambda g: slice_widths(st.g_pidx[g], st.g_uidx[g]))(
                        st.r_grp)
                oks, starts_r, unions = jax.vmap(
                    lambda sv, sva: fit_multi(st.free, sv, sva))(
                        svecs, svalids)
                durs = st.g_dur[st.r_grp]
                elig = (st.r_active & oks
                        & (jnp.arange(R, dtype=i32) != head)
                        & (st.now + durs <= t_res + 1e-9) & can_scan)
                cand = jnp.argmin(jnp.where(elig, st.r_seq,
                                            _BIG_SEQ)).astype(i32)
                do_bf = can_scan & jnp.any(elig)
                slot = jnp.where(place_head, head, cand)
                sstarts = jnp.where(place_head, starts_h, starts_r[cand])
                sunion = jnp.where(place_head, union_h, unions[cand])
            do_place = place_head | do_bf
            if telemetry:
                g2 = st.r_grp[slot]
                arrm = jnp.clip(st.g_arr[g2], 0, A - 1)
                memv = c_rng < st.g_size[g2]
                waits = _since(st.now, st.now_lo, trace.t[arrm],
                               trace.t_lo[arrm])
                b = jnp.searchsorted(_WAIT_EDGES, waits,
                                     side="left").astype(i32)
                nb = ms.wait_hist.shape[0]
                ms = ms._replace(
                    wait_hist=ms.wait_hist.at[
                        jnp.where(do_place & memv, b, nb)].add(
                            1, mode="drop"),
                    wait_sum=ms.wait_sum + jnp.sum(
                        jnp.where(do_place & memv, waits, 0.0)),
                    places=ms.places + jnp.where(do_place, i32(1), i32(0)))
            if train:
                # queueing-reward attribution: the placed entry's member
                # waits/turnarounds land in the bucket of the window that
                # FORMED it (r_win), i.e. the decision that grouped these
                # jobs — not the wall-clock window of the placement
                gq = st.r_grp[slot]
                arrq = jnp.clip(st.g_arr[gq], 0, A - 1)
                memq = c_rng < st.g_size[gq]
                wq = _since(st.now, st.now_lo, trace.t[arrq],
                            trace.t_lo[arrq])
                tq = wq + st.g_ft[gq]
                brow = jnp.where(do_place, st.r_win[slot], A)
                roll = roll._replace(
                    w_wait=roll.w_wait.at[brow].add(
                        jnp.sum(jnp.where(memq, wq, 0.0)), mode="drop"),
                    w_turn=roll.w_turn.at[brow].add(
                        jnp.sum(jnp.where(memq, tq, 0.0)), mode="drop"))
            st = place_rl(st, slot, sstarts, sunion, do_bf, do_place)

            adv = ~do_place & ~want
            t_next, lo_next = _next_event(st, trace)
            now = jnp.where(adv, t_next, st.now)
            pend_hi = jnp.where(
                adv, jnp.sum(trace.t <= now, dtype=i32), st.pend_hi)
            rel = adv & st.c_active & (st.c_t1 <= now)
            freed = jnp.any(rel[:, None] & st.c_mask, axis=0)
            w_rel = jnp.sum(jnp.where(rel[:, None], st.c_mask, False),
                            dtype=i32)
            n_busy = st.n_busy - w_rel
            busy_time = st.busy_time + jnp.where(
                (n_busy == 0) & (w_rel > 0), now - st.busy_t0, 0.0)
            steps = st.steps + jnp.where(adv, i32(1), i32(0))
            if telemetry:
                dt = now - st.now
                ms = ms._replace(
                    queue_depth_int=ms.queue_depth_int
                    + (st.pend_hi - st.pend_lo).astype(jnp.float32) * dt,
                    busy_unit_int=ms.busy_unit_int
                    + st.n_busy.astype(jnp.float32) * dt)
            st = st._replace(
                now=now, now_lo=jnp.where(adv, lo_next, st.now_lo),
                pend_hi=pend_hi, free=st.free | freed,
                c_active=st.c_active & ~rel, n_busy=n_busy,
                busy_time=busy_time, steps=steps,
                err=st.err | jnp.where(steps > max_steps,
                                       i32(ERR_EVENT_OVERFLOW), i32(0)))
            return st, ms, roll, want

        def outer_body(carry):
            st, ms, roll = carry
            st, ms, roll, want = jax.lax.while_loop(
                lambda c: live(c[0]) & (c[0].err == 0) & ~c[3],
                inner_body, (st, ms, roll, jnp.bool_(False)))
            st, roll = form_and_plan(st, roll, want)
            return st, ms, roll

        st, ms, roll = jax.lax.while_loop(
            lambda c: live(c[0]) & (c[0].err == 0), outer_body,
            (st0, _metrics_init(), roll0))
        if train:
            return (st, ms, roll) if telemetry else (st, roll)
        return (st, ms) if telemetry else st

    return run


def _records_rl(st: _RLState, trace: TraceArrays):
    A = trace.t.shape[0]
    C = st.g_arr.shape[1]
    memv = jnp.arange(C)[None, :] < st.g_size[:, None]
    tgt = jnp.where(memv, st.g_arr, A).reshape(-1)
    dispatch = jnp.zeros(A, jnp.float32).at[tgt].set(
        jnp.broadcast_to(st.g_t0[:, None], st.g_arr.shape).reshape(-1),
        mode="drop")
    finish = jnp.zeros(A, jnp.float32).at[tgt].set(
        (st.g_t0[:, None] + st.g_ft).reshape(-1), mode="drop")
    return dispatch, finish


def _summary_rl(st: _RLState, trace: TraceArrays,
                rjt: RLJobTable) -> SweepSummary:
    dispatch, finish = _records_rl(st, trace)
    return _summarize(st, trace, dispatch, finish, rjt.solo8[trace.job])


def make_rollout_collector(env_cfg, window: int = 8, backfill: bool = True,
                           capacity: int = 256):
    """Jitted, vmapped sim-in-the-loop rollout collector.

    Returns ``collect(traces, rjt, params, keys, eps, widths)`` where
    ``traces`` is a stacked :class:`TraceArrays` batch (leading axis B),
    ``keys`` is a (B, 2) uint32 PRNG-key batch, ``eps`` a scalar traced
    exploration rate shared across the batch, and ``widths`` a (B,) i32
    pod-width lane.  Yields ``(SweepSummary, TrainRollout)`` pytrees with
    leading axis B — the summary carries the terminal makespan and the
    ``err`` lane (callers must check it), the rollout carries the
    transition logs and per-window queueing buckets that
    ``train_online``'s host-side stitcher turns into replay transitions.
    With ``eps=0`` the rollout's decisions are bit-identical to the
    serving engine's.
    """
    runf = _build_run_rl(window, backfill, capacity, False, env_cfg,
                         train=True)

    def _one(tr, rjt, params, k, eps, width):
        st, roll = runf(tr, rjt, params, width, k, eps)
        return _summary_rl(st, tr, rjt), roll

    return jax.jit(jax.vmap(_one, in_axes=(0, None, None, 0, None, 0)))


def _emit_lane_rl(st: _RLState, jobs: list, parts: list,
                  records: list[JobRecord], pod: int = 0) -> list[Segment]:
    """RL mirror of ``_emit_lane``: rebuild each entry's fitted partition
    from the logged per-slot widths (the exact ``to_placements`` shrink)
    and recompute its record times with the float64 ``corun`` the heap
    stores — so decisions AND label/units/grouping match the heap
    bit-for-bit, and only the placement clock carries float32 rounding."""
    g_n = int(st.n_groups)
    g_arr = np.asarray(st.g_arr)[:g_n]
    g_job = np.asarray(st.g_job)[:g_n]
    g_size = np.asarray(st.g_size)[:g_n]
    g_pidx = np.asarray(st.g_pidx)[:g_n]
    g_uidx = np.asarray(st.g_uidx)[:g_n]
    g_start = np.asarray(st.g_start)[:g_n]
    g_t0 = _placement_times(st, g_n)
    pack = np.asarray(st.g_pack)[:g_n]
    g_pseq, g_bf = pack >> 1, (pack & 1) == 1
    segs: list[tuple[int, Segment]] = []
    for g in range(g_n):
        size = int(g_size[g])
        group = [jobs[int(g_job[g, m])] for m in range(size)]
        planned = parts[int(g_pidx[g])]
        new_slices = list(planned.slices)
        changed = False
        for s_i, (si, s, _b) in enumerate(planned.slots):
            w = UNIT_SIZES[int(g_uidx[g, s_i])]
            if len(s.shares) == 1 and w < s.units:
                new_slices[si] = Slice(w, s.shares)
                changed = True
        part = (Partition(tuple(new_slices), slice_label(tuple(new_slices)))
                if changed else planned)
        pred = corun(group, part)
        t0 = float(g_t0[g])
        for m, (ft, (_si, s, _b)) in enumerate(zip(pred.finish_times,
                                                   part.slots)):
            rec = records[int(g_arr[g, m])]
            rec.dispatch = t0
            rec.finish = t0 + float(ft)
            rec.group_size = size
            rec.partition = part.label
            rec.units = s.units
            rec.backfilled = bool(g_bf[g])
            rec.pod = pod
        ranges = tuple((int(g_start[g, si]), s.units)
                       for si, s in enumerate(part.slices))
        segs.append((int(g_pseq[g]), Segment(
            t0=t0, t1=t0 + float(pred.makespan), jobs=size,
            partition=part.label, slices=ranges,
            backfilled=bool(g_bf[g]), pod=pod)))
    return [s for _, s in sorted(segs, key=lambda x: x[0])]


class VectorizedClusterSimulator:
    """Drop-in vectorized engine for time-sharing and RL dispatch plans.

    ``run(trace)`` returns a :class:`~repro.online.simulator.SimResult`
    built from the device lanes (records in sorted-trace order, timeline
    in placement order — the same shapes the heap produces), so every
    downstream consumer (summaries, percentiles, benchmarks) is shared.
    ``sweep(traces)`` evaluates a batch in one vmapped call (sharded over
    host devices via ``pmap`` when ``devices`` is given) and returns
    per-trace :class:`SweepSummary` lanes.

    ``policy`` is a :class:`~repro.online.policies.TimeSharingPolicy`
    (or ``None``, same semantics) or an :class:`~repro.online.policies.\
RLDispatchPolicy`, whose agent episodes then run in-graph at the
    window-formation seam (module docstring); ``hot_swap`` between calls
    never recompiles, and ``sweep(..., param_sets=[...])`` adds a
    leading params axis evaluating a population of agents in one call.
    Use :meth:`supports` to route other policies to the heap.  No
    ``on_tick``/re-training (host callbacks cannot run in-graph) and no
    ``mode="blocking"`` — the heap remains the only path for both.
    """

    def __init__(self, policy=None, window: int = 8, backfill: bool = True,
                 capacity: int = 256, telemetry: bool = False):
        if not self.supports(policy):
            raise ValueError(
                f"vectorized engine serves TimeSharingPolicy or "
                f"RLDispatchPolicy plans; got {type(policy).__name__}")
        assert window >= 1
        self.policy = policy if policy is not None else TimeSharingPolicy()
        self.window = window
        self.backfill = backfill
        self.capacity = capacity
        # `telemetry` is a *static* engine flag: False compiles the exact
        # pre-telemetry program; True threads a MetricsState through the
        # while_loop (run -> (state, metrics)) without touching the state
        # trajectory — see _build_run
        self.telemetry = telemetry
        self.last_metrics: dict | None = None
        self.last_sweep_metrics: MetricsState | None = None
        self._rl = isinstance(self.policy, RLDispatchPolicy)
        if self._rl:
            env_cfg = self.policy.scheduler.env_cfg
            if window > env_cfg.window:
                raise ValueError(
                    f"sim window {window} > agent window {env_cfg.window}: "
                    f"one formation would span several RL episodes "
                    f"(submission_protocol re-chunking); use a sim window "
                    f"<= EnvConfig.window")
            self._env_cfg = env_cfg
            self._parts = enumerate_partitions(env_cfg.c_max)
            runf = _build_run_rl(window, backfill, capacity, telemetry,
                                 env_cfg)
            if telemetry:
                def _one(tr, jt, params):
                    st, ms = runf(tr, jt, params)
                    return _summary_rl(st, tr, jt), ms
            else:
                def _one(tr, jt, params):
                    return _summary_rl(runf(tr, jt, params), tr, jt)
            self._sweepfn = jax.jit(jax.vmap(_one, in_axes=(0, None, None)))
            # population axis: outer vmap over stacked agent params — one
            # device call scores P agents x T traces on queueing reward
            self._sweep_pop = jax.jit(jax.vmap(
                jax.vmap(_one, in_axes=(0, None, None)),
                in_axes=(None, None, 0)))
        else:
            runf = _build_run(window, backfill, capacity, telemetry)
            if telemetry:
                def _one(tr, jt):
                    st, ms = runf(tr, jt)
                    return _summary(st, tr, jt), ms
            else:
                def _one(tr, jt):
                    return _summary(runf(tr, jt), tr, jt)
            self._sweepfn = jax.jit(jax.vmap(_one, in_axes=(0, None)))
        self._run1 = jax.jit(runf)

    @staticmethod
    def supports(policy) -> bool:
        """Policies this engine serves with decision-level heap parity."""
        return policy is None or isinstance(
            policy, (TimeSharingPolicy, RLDispatchPolicy))

    # ---------------------------------------------------------------- run

    def run(self, trace: list[Arrival]) -> SimResult:
        res = SimResult(policy=getattr(self.policy, "name", "time_sharing"),
                        window=self.window, jobs=[], mode="concurrent")
        if not trace:
            return res
        jobs: list = []
        tr, order = compile_trace(trace, self.capacity, jobs=jobs)
        if self._rl:
            jt = build_rl_job_table(jobs)
            out = jax.block_until_ready(
                self._run1(tr, jt, self.policy.agent.params))
        else:
            jt = build_job_table(jobs)
            out = jax.block_until_ready(self._run1(tr, jt))
        if self.telemetry:
            st, ms = out
            self.last_metrics = metrics_dict(ms)
        else:
            st = out
        self._check_err(int(st.err))

        records = [JobRecord(binary=a.binary, name=a.profile.name,
                             arrival=a.t, solo_time=a.profile.solo_time(),
                             idx=i, job_class=a.profile.job_class)
                   for i, a in enumerate(order)]
        res.jobs = records
        if self._rl:
            res.timeline = _emit_lane_rl(st, jobs, self._parts, records)
            res.refits = int(st.refits)
        else:
            res.timeline = _emit_lane(st, jt, records)
        res.busy_time = float(st.busy_time)
        res.dispatches = int(st.dispatches)
        res.backfills = int(st.backfills)
        res.slice_busy_s = [float(x) for x in np.asarray(st.slice_busy)]
        return res

    # -------------------------------------------------------------- sweep

    def sweep(self, traces: list[list[Arrival]],
              devices: list | None = None, with_metrics: bool = False,
              param_sets=None):
        """Evaluate ``traces`` in one device call (one compiled program).

        With ``devices`` (>= 2), the batch axis is sharded across host
        devices via ``pmap`` — the CPU-CI parallelism of
        ``XLA_FLAGS=--xla_force_host_platform_device_count=N``; a batch
        that does not divide across them raises ``ValueError``.

        With ``with_metrics=True`` (requires a ``telemetry=True`` engine)
        returns ``(SweepSummary, MetricsState)`` — the per-lane metric
        tensors accumulated in-graph, batch axis leading, at no extra
        device syncs.  A telemetry engine still records
        ``last_sweep_metrics`` when ``with_metrics`` is off.

        ``param_sets`` (RL engines only): a list of DQN param pytrees (or
        one pre-stacked pytree) adds a leading *population* axis — the
        returned :class:`SweepSummary` lanes are ``(n_params, n_traces)``,
        one vmap evaluating every agent of a population on queueing
        reward (mean/p99 wait and friends).  Exclusive of ``devices``
        sharding and ``with_metrics``.
        """
        if not traces:
            raise ValueError("empty sweep")
        if with_metrics and not self.telemetry:
            raise ValueError("with_metrics needs an engine built with "
                             "telemetry=True")
        if param_sets is not None and not self._rl:
            raise ValueError("param_sets needs an RLDispatchPolicy engine")
        if param_sets is not None and with_metrics:
            raise ValueError("param_sets and with_metrics are exclusive")
        names: dict[str, int] = {}
        jobs: list = []
        compiled = [compile_trace(t, self.capacity, names, jobs)[0]
                    for t in traces]
        batch = jax.tree.map(lambda *xs: jnp.stack(xs), *compiled)
        if self._rl:
            jt = build_rl_job_table(jobs)
            if param_sets is not None:
                stacked = (param_sets if isinstance(param_sets, dict)
                           else jax.tree.map(lambda *xs: jnp.stack(xs),
                                             *param_sets))
                out = jax.block_until_ready(
                    self._sweep_pop(batch, jt, stacked))
                self._check_err(int(np.max(np.asarray(out.err))))
                return out
            args = (jt, self.policy.agent.params)
        else:
            jt = build_job_table(jobs)
            args = (jt,)
        n_dev = len(devices) if devices else 1
        if n_dev > 1 and len(traces) % n_dev:
            raise ValueError(f"sweep of {len(traces)} traces does not divide "
                             f"across {n_dev} devices")
        if n_dev > 1:
            shard = jax.tree.map(
                lambda x: x.reshape((n_dev, len(traces) // n_dev)
                                    + x.shape[1:]), batch)
            pfn = jax.pmap(lambda tr: self._sweepfn(tr, *args),
                           devices=devices)
            out = jax.block_until_ready(pfn(shard))
            out = jax.tree.map(lambda x: x.reshape((-1,) + x.shape[2:]), out)
        else:
            out = jax.block_until_ready(self._sweepfn(batch, *args))
        if self.telemetry:
            summ, ms = out
            self.last_sweep_metrics = ms
        else:
            summ = out
        self._check_err(int(np.max(np.asarray(summ.err))))
        return (summ, ms) if with_metrics else summ

    @staticmethod
    def _check_err(err: int) -> None:
        if err & ERR_READY_OVERFLOW:
            raise RuntimeError("vectorized engine: ready ring overflow")
        if err & ERR_EVENT_OVERFLOW:
            raise RuntimeError("vectorized engine: event-step budget "
                               "exceeded (stuck trace?)")
        if err:
            raise RuntimeError(f"vectorized engine: error lanes {err:#x}")


def _quiescent_view(pods) -> FleetView:
    return FleetView(pods=tuple(
        PodView(idx=i, width=w, free=(True,) * w, pending=0, ready=0,
                queue_units=0, busy_units=0)
        for i, w in enumerate(pods)))


def hash_split_max(trace: list[Arrival], pods, seed: int = 0) -> int:
    """Largest per-pod sub-stream of ``trace`` under hash routing — the
    per-lane ``capacity`` a :class:`VectorizedFleetSimulator` needs."""
    router, view = make_router("hash", seed), _quiescent_view(pods)
    counts = np.bincount([router.route(a, view) for a in trace],
                         minlength=len(pods))
    return int(counts.max())


class VectorizedFleetSimulator:
    """Hash-routed fleet on the vectorized engine: a vmapped pod axis.

    The hash router is the one shipped policy computable from the trace
    alone — its assignment depends only on the binary path, the seed, and
    the *static* pod widths (eligibility), never on cluster state.  Routed
    sub-streams therefore never interact (claims are pod-local, windows
    are pod-local, a routed job never migrates), so the heap fleet under
    hash routing is **exactly** the merge of independent single-pod
    simulations of the routed subtraces.  This wrapper materializes that
    decomposition: split the trace with the same :class:`~repro.online.\\
    router.HashRouter` the heap uses, compile each pod's subtrace against
    one shared job table, and run all pods in ONE vmapped device call with
    a per-lane ``width`` (a narrow pod's upper units are born busy).
    Per-pod lanes are merged back into a single fleet
    :class:`~repro.online.simulator.SimResult` — records in sorted-trace
    order tagged with their pod, segments on the fleet-wide unit axis —
    matching the heap fleet's decisions exactly and its clock to float32.

    State-dependent routers (``least_loaded``/``frag``) couple the pods
    through the live :class:`FleetView` and stay heap-only, as do
    ``mode="blocking"``, ``on_tick`` re-training, and policies outside
    time-sharing/RL (:meth:`supports` mirrors
    :class:`VectorizedClusterSimulator`).  With an
    :class:`~repro.online.policies.RLDispatchPolicy` every pod lane runs
    the agent's episode in-graph; ``pod_params`` (a list of ``n_pods``
    params pytrees) optionally overrides the policy agent's params *per
    pod*, so heterogeneous fleets can serve per-pod-specialized agents
    in the same device call.  ``capacity``
    bounds the *per-pod* subtrace length; hash-splitting an
    ``n``-arrival trace needs roughly ``n / n_pods`` plus skew headroom.
    """

    def __init__(self, policy=None, config: SimConfig | None = None, *,
                 window: int = 8, backfill: bool = True,
                 capacity: int = 256,
                 pods: tuple[int, ...] | None = None,
                 router: str = "hash", router_seed: int = 0,
                 telemetry: bool = False, pod_params: list | None = None):
        if config is None:
            config = SimConfig(
                window=window, backfill=backfill,
                pods=tuple(pods) if pods is not None else (N_UNITS,),
                router=router, router_seed=router_seed)
        if not self.supports(policy):
            raise ValueError(
                f"vectorized fleet serves TimeSharingPolicy or "
                f"RLDispatchPolicy plans; got {type(policy).__name__}")
        if config.router != "hash":
            raise ValueError(
                f"vectorized fleet requires the state-free 'hash' router "
                f"(got {config.router!r}); state-dependent routers couple "
                f"pods and run on the heap ClusterSimulator")
        if config.mode != "concurrent" or config.tick_interval_s:
            raise ValueError("vectorized fleet is concurrent-mode only, "
                             "without ticks")
        self.config = config
        self.policy = policy if policy is not None else TimeSharingPolicy()
        self.capacity = capacity
        self.telemetry = telemetry
        self.last_metrics: dict | None = None
        self._router = make_router(config.router, config.router_seed)
        self._rl = isinstance(self.policy, RLDispatchPolicy)
        if pod_params is not None:
            if not self._rl:
                raise ValueError("pod_params needs an RLDispatchPolicy")
            if len(pod_params) != config.n_pods:
                raise ValueError(
                    f"pod_params has {len(pod_params)} entries for "
                    f"{config.n_pods} pods")
        self.pod_params = pod_params        # per-pod DQN params (None:
                                            # every pod runs policy.agent)
        if self._rl:
            env_cfg = self.policy.scheduler.env_cfg
            if config.window > env_cfg.window:
                raise ValueError(
                    f"sim window {config.window} > agent window "
                    f"{env_cfg.window}: use a sim window <= EnvConfig.window")
            self._env_cfg = env_cfg
            self._parts = enumerate_partitions(env_cfg.c_max)
            self._runp = jax.jit(jax.vmap(
                _build_run_rl(config.window, config.backfill, capacity,
                              telemetry, env_cfg),
                in_axes=(0, None, 0, 0)))
        else:
            self._runp = jax.jit(jax.vmap(
                _build_run(config.window, config.backfill, capacity,
                           telemetry),
                in_axes=(0, None, 0)))

    @staticmethod
    def supports(policy) -> bool:
        return VectorizedClusterSimulator.supports(policy)

    def run(self, trace: list[Arrival]) -> SimResult:
        cfg = self.config
        res = SimResult(policy=getattr(self.policy, "name", "time_sharing"),
                        window=cfg.window, jobs=[], mode="concurrent",
                        slice_busy_s=[0.0] * cfg.total_units,
                        pods=cfg.pods, router=cfg.router)
        if not trace:
            return res
        order = sorted(trace, key=lambda a: a.t)
        records = [JobRecord(binary=a.binary, name=a.profile.name,
                             arrival=a.t, solo_time=a.profile.solo_time(),
                             idx=i, job_class=a.profile.job_class)
                   for i, a in enumerate(order)]
        res.jobs = records

        # static pre-split: same router object the heap constructs, fed a
        # quiescent FleetView (hash ignores the dynamic fields) — so the
        # assignment is bit-identical to the heap's at-arrival routing
        view = _quiescent_view(cfg.pods)
        sub: list[list[Arrival]] = [[] for _ in cfg.pods]
        sub_rec: list[list[JobRecord]] = [[] for _ in cfg.pods]
        for a, rec in zip(order, records):
            p = 0 if cfg.n_pods == 1 else self._router.route(a, view)
            rec.pod = p
            sub[p].append(a)
            sub_rec[p].append(rec)

        names: dict[str, int] = {}
        jobs: list = []
        compiled = [compile_trace(s, self.capacity, names, jobs)[0]
                    for s in sub]
        batch = jax.tree.map(lambda *xs: jnp.stack(xs), *compiled)
        widths = jnp.asarray(np.array(cfg.pods, np.int32))
        if self._rl:
            jt = build_rl_job_table(jobs)
            plist = (self.pod_params if self.pod_params is not None
                     else [self.policy.agent.params] * cfg.n_pods)
            pstack = jax.tree.map(lambda *xs: jnp.stack(xs), *plist)
            out = jax.block_until_ready(
                self._runp(batch, jt, pstack, widths))
        else:
            jt = build_job_table(jobs)
            out = jax.block_until_ready(self._runp(batch, jt, widths))
        if self.telemetry:
            sts, mss = out
            # pod lanes are disjoint sub-streams: fleet metrics are the sum
            self.last_metrics = metrics_dict(
                jax.tree.map(lambda x: x.sum(0), mss))
        else:
            sts = out
        VectorizedClusterSimulator._check_err(
            int(np.max(np.asarray(sts.err))))

        offs = res.pod_offsets
        segs: list[Segment] = []
        for p, w in enumerate(cfg.pods):
            st = jax.tree.map(lambda x, p=p: x[p], sts)
            if self._rl:
                segs.extend(_emit_lane_rl(st, jobs, self._parts,
                                          sub_rec[p], pod=p))
                res.refits += int(st.refits)
            else:
                segs.extend(_emit_lane(st, jt, sub_rec[p], pod=p))
            res.busy_time += float(st.busy_time)
            res.dispatches += int(st.dispatches)
            res.backfills += int(st.backfills)
            sb = np.asarray(st.slice_busy)
            for u in range(w):
                res.slice_busy_s[offs[p] + u] = float(sb[u])
        # merge lanes chronologically; Python's stable sort keeps each
        # pod's placement order intact on ties
        segs.sort(key=lambda s: (s.t0, s.pod))
        res.timeline = segs
        return res

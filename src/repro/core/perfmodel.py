"""Co-run performance model: SoloRunTime / CoRunTime (paper Table I functions).

On real hardware these are measurements; in this CPU-only container they are
backed by a roofline contention model over the same per-job artifacts the
dry-run produces (DESIGN.md §5):

  * compute: a job with Level-2 share β gets β of the slice's MXU quanta
    -> compute term / β (static shares = MPS semantics; idle share is wasted
    when a co-resident finishes early, as on real MPS).
  * memory: co-residents on a slice share its HBM bandwidth. Water-filling
    allocation — each job demands its solo bandwidth utilization; low-demand
    jobs keep full speed (complementary CI+MI mixes co-locate well, paper
    Fig. 3), oversubscribed slices inflate everyone else.
  * collective: private per job (its own sub-ring), with the torus factor
    charged on split slices.
  * quantum-switch overhead: multiplicative (1 + sigma*(n_active-1)) — the
    VMEM/cache refill cost of time multiplexing (MPS context overhead
    analogue).

Jobs finish at different times; a phase simulation advances the group through
completion events, re-solving the bandwidth allocation after each (bandwidth
is physically freed; compute shares stay static).
"""
from __future__ import annotations

from dataclasses import dataclass

from repro.core.partition import Partition, Slice
from repro.core.profiles import JobProfile

SIGMA_QUANTUM = 0.03          # per-extra-co-resident switch overhead
KAPPA_INTERFERENCE = 0.35     # shared-slice HBM/ICI efficiency loss per unit
                              # of co-resident demand (stream mixing; the
                              # contention MIG-style isolation removes — paper Fig. 4)


@dataclass
class CoRunResult:
    makespan: float                      # CoRunTime(JS, R)
    finish_times: list[float]            # per job (CoRunAppTime)
    solo_times: list[float]              # per job (SoloRunAppTime)

    @property
    def solo_total(self) -> float:
        return sum(self.solo_times)

    @property
    def throughput_gain(self) -> float:
        return self.solo_total / self.makespan if self.makespan > 0 else 0.0


def water_fill(demands: list[float], capacity: float = 1.0) -> list[float]:
    """Allocate bandwidth fractions: min(demand, fair share), redistributing
    slack to the hungry (classic water-filling).

    Each round sates, in index order, every job whose demand fits the fair
    share of what is left; when none fits, the rest is split evenly.  A job
    still in the running holds nothing yet, so its unmet demand is its
    whole demand.  Two demands, the common case on a shared slice, take
    the same rounds written out."""
    n = len(demands)
    if n == 2 and capacity > 1e-12:
        d0, d1 = demands
        limit = capacity / 2 + 1e-15
        if d0 <= limit:
            if d1 <= limit:
                return [d0, d1]
            rest = capacity - d0
            return [d0, (d1 if d1 <= rest + 1e-15 else rest) if rest > 1e-12 else 0.0]
        if d1 <= limit:
            rest = capacity - d1
            return [(d0 if d0 <= rest + 1e-15 else rest) if rest > 1e-12 else 0.0, d1]
        return [capacity / 2, capacity / 2]
    alloc = [0.0] * n
    remaining = capacity
    active = range(n)
    while active and remaining > 1e-12:
        fair = remaining / len(active)
        limit = fair + 1e-15
        hungry = []
        for i in active:
            d = demands[i]
            if d <= limit:
                remaining -= d
                alloc[i] = d
            else:
                hungry.append(i)
        if len(hungry) == len(active):
            for i in hungry:
                alloc[i] = fair
            break
        active = hungry
    return alloc


def _slice_step_times(c: list[float], m: list[float], xb: list[float],
                      xl: list[float], fx: list[float],
                      shared_mem: bool) -> list[float]:
    """Current per-step time of each job running on one slice.

    The arguments are the running jobs' slice terms: compute over its
    share ``c``, memory ``m``, collective bytes ``xb`` and latency ``xl``,
    and the fixed per-step time ``fx``.  ``shared_mem``: the jobs share the
    slice's HBM (more than one runs on a multi-share slice).

    HBM bandwidth and ICI link bandwidth are physically shared: each job's
    bandwidth *utilization* (busy-time fraction) is water-filled against unit
    capacity, iterated to a fixed point (stretching a job's step lowers its
    utilization, freeing bandwidth). The latency component of the collective
    chain (tiny payloads) and the κ stream-mixing loss contend without
    consuming bandwidth. Compute is divided by the static β shares.
    """
    n = len(c)
    if n == 1:       # alone: full bandwidth, no interference, no switching
        return [max(c[0], m[0], xb[0] + xl[0]) + fx[0]]
    jobs = range(n)
    mem_t = list(m)                       # memory time under current bw grant
    coll_t = list(xb)                     # collective-bytes time, ditto
    for _ in range(30):
        mem_u = [0.0] * n                 # utilizations, each min(1, term / step)
        coll_u = [0.0] * n
        for i in jobs:
            t = max(c[i], mem_t[i], coll_t[i] + xl[i]) + fx[i]
            u = m[i] / t
            mem_u[i] = u if u < 1.0 else 1.0
            u = xb[i] / t
            coll_u[i] = u if u < 1.0 else 1.0
        ma = water_fill(mem_u) if shared_mem else mem_u
        ca = water_fill(coll_u)
        delta = 0.0
        for i in jobs:
            a_m, a_x = ma[i], ca[i]
            tgt_m = (m[i] / a_m if (shared_mem and a_m > 1e-12
                                    and mem_u[i] > a_m + 1e-12) else m[i])
            tgt_x = (xb[i] / a_x if (a_x > 1e-12 and coll_u[i] > a_x + 1e-12)
                     else xb[i])
            delta += abs(tgt_m - mem_t[i]) + abs(tgt_x - coll_t[i])
            mem_t[i] += 0.5 * (tgt_m - mem_t[i])      # damped toward equilibrium
            coll_t[i] += 0.5 * (tgt_x - coll_t[i])
        if delta < 1e-9:
            break

    mem_sum, coll_sum = sum(mem_u), sum(coll_u)
    switch = 1.0 + SIGMA_QUANTUM * (n - 1)
    out = []
    for i in jobs:
        km = 1.0 + KAPPA_INTERFERENCE * (mem_sum - mem_u[i]) if shared_mem else 1.0
        kx = 1.0 + KAPPA_INTERFERENCE * (coll_sum - coll_u[i])
        t = max(c[i], mem_t[i] * km, (coll_t[i] + xl[i]) * kx) + fx[i]
        out.append(t * switch)
    return out


def _simulate_slice(jobs: list[JobProfile], betas: list[float], s: Slice) -> list[float]:
    """Phase simulation of one slice; returns per-job finish times.

    Each job's slice terms are computed once; every phase re-solves the
    step times of the jobs still running."""
    n = len(jobs)
    units, tf = s.units, s.torus_factor
    c, m, xb, xl, fx = [], [], [], [], []
    for job, beta in zip(jobs, betas):
        cj, mj, xj = job.terms(units, tf)
        c.append(cj / beta)
        m.append(mj)
        xb.append(xj)
        xl.append(job.coll_latency(units))
        fx.append(job.fixed_latency(units) + job.serial_s)
    shared = s.shared_memory
    remaining = [float(j.steps) for j in jobs]
    finish = [0.0] * n
    live = list(range(n))
    t = 0.0
    for _ in range(n):  # at most n phases
        if not live:
            break
        st = _slice_step_times([c[j] for j in live], [m[j] for j in live],
                               [xb[j] for j in live], [xl[j] for j in live],
                               [fx[j] for j in live],
                               shared and len(live) > 1)
        # time to next completion
        dt = min(remaining[j] * st_j for j, st_j in zip(live, st))
        still = []
        for j, st_j in zip(live, st):
            remaining[j] -= dt / st_j
            if remaining[j] <= 1e-9:
                finish[j] = t + dt
            else:
                still.append(j)
        live = still
        t += dt
    return finish


def _finish_times(group: list[JobProfile], partition: Partition) -> list[float]:
    """Per-job finish times of ``group`` under ``partition`` (jobs -> slots
    in order); a slice's slots are consecutive, so each slice simulates
    its own run of positions."""
    assert len(group) == partition.arity, (len(group), partition.label)
    finish: list[float] = []
    for s in partition.slices:
        pos = len(finish)
        finish += _simulate_slice(group[pos:pos + len(s.shares)], s.shares, s)
    return finish


def corun(group: list[JobProfile], partition: Partition) -> CoRunResult:
    """CoRunTime for `group` under `partition` (jobs -> slots in order)."""
    finish = _finish_times(group, partition)
    solo = [j.solo_time() for j in group]
    return CoRunResult(makespan=max(finish), finish_times=finish, solo_times=solo)


def corun_time(group: list[JobProfile], partition: Partition) -> float:
    return max(_finish_times(group, partition))


def solo_run_time(group: list[JobProfile]) -> float:
    """Time-sharing: run one by one with the full pod."""
    return sum(j.solo_time() for j in group)


def best_assignment(group: list[JobProfile], partition: Partition) -> tuple[float, tuple[int, ...]]:
    """Min CoRunTime over job->slot orderings (paper's C! assignment space)."""
    import itertools

    best, best_perm = float("inf"), tuple(range(len(group)))
    for perm in itertools.permutations(range(len(group))):
        t = corun_time([group[i] for i in perm], partition)
        if t < best:
            best, best_perm = t, perm
    return best, best_perm

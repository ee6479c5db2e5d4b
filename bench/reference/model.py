"""Plain reference of the co-scheduler's job and co-run model.

A standalone restatement of what the serving path computes for one job
and one co-run group: the per-slice roofline step time of a job profile,
the curated partition table, aligned first-fit placement and the co-run
phase simulation.  Float64 Python throughout, no JAX, and nothing imported
from the program under test, so the benchmark's yardstick does not move
when the program does.  Each piece follows the program's documented
semantics (paper arXiv:2405.08754 §IV-A, Table VII analogue); the zoo
profiles it reads are the frozen snapshot in ``bench/data/zoo.json``.
"""
from __future__ import annotations

import math
from dataclasses import dataclass, field

N_UNITS = 8                      # slice units per pod
CHIPS_PER_UNIT = 32
VALID_WIDTHS = (1, 2, 4, 8)

PEAK_FLOPS = 197e12              # per-chip constants of the analytic profiles
HBM_BW = 819e9
ICI_BW = 2 * 50e9

LAUNCH_LATENCY_S = 75e-6
HOP_LATENCY_S = 1.2e-6
COLL_BASE_LAT_S = 6e-6
COLL_HOP_LAT_S = 1.0e-6

SIGMA_QUANTUM = 0.03             # co-run model constants
KAPPA_INTERFERENCE = 0.35


@dataclass(eq=False)
class Job:
    """One job profile: roofline terms per slice width."""

    name: str
    steps: int
    flops_total: float
    bytes_total: float
    coll_bytes_chip_pod: float
    n_coll_step: int
    serial_s: float
    meta: dict = field(default_factory=dict)

    def terms(self, units: int, torus_factor: float | None = None):
        chips = units * CHIPS_PER_UNIT
        tf = (1.0 if units == N_UNITS else 0.5) if torus_factor is None \
            else torus_factor
        return (self.flops_total / (chips * PEAK_FLOPS),
                self.bytes_total / (chips * HBM_BW),
                self.coll_bytes_chip_pod / (ICI_BW * tf))

    def fixed_latency(self, units: int) -> float:
        return LAUNCH_LATENCY_S + HOP_LATENCY_S * (units * 2 + 16)

    def coll_latency(self, units: int) -> float:
        return self.n_coll_step * (COLL_BASE_LAT_S
                                   + COLL_HOP_LAT_S * (2 * units + 16))

    def step_time(self, units: int) -> float:
        c, m, x = self.terms(units)
        return (max(c, m, x + self.coll_latency(units))
                + self.fixed_latency(units) + self.serial_s)

    def solo_time(self) -> float:
        return self.steps * self.step_time(N_UNITS)

    @property
    def requested_units(self) -> int:
        u = int(self.meta.get("units", N_UNITS))
        return u if u in VALID_WIDTHS else N_UNITS

    def features(self) -> list[float]:
        """The seven profile features of the agent's observation."""
        c, m, x = self.terms(N_UNITS)
        st = self.step_time(N_UNITS)
        return [c / st, m / st, (x + self.coll_latency(N_UNITS)) / st,
                self.step_time(1) / st / N_UNITS,
                math.log10(max(self.solo_time(), 1e-9)) / 6.0,
                math.log10(max(self.flops_total, 1.0)) / 20.0,
                self.serial_s / st]


def jobs_from_snapshot(rows: list[dict]) -> list[Job]:
    keys = ("name", "steps", "flops_total", "bytes_total",
            "coll_bytes_chip_pod", "n_coll_step", "serial_s", "meta")
    return [Job(**{k: r[k] for k in keys}) for r in rows]


# --------------------------------------------------------------- partitions

@dataclass(frozen=True)
class Slice:
    units: int
    shares: tuple[float, ...]


@dataclass(frozen=True)
class Partition:
    slices: tuple[Slice, ...]
    label: str

    @property
    def slots(self) -> list[tuple[int, Slice, float]]:
        return [(i, s, b) for i, s in enumerate(self.slices) for b in s.shares]

    @property
    def arity(self) -> int:
        return sum(len(s.shares) for s in self.slices)

    @property
    def total_units(self) -> int:
        return sum(s.units for s in self.slices)


def _width_label(units: int) -> str:
    return "1m" if units == N_UNITS else f"{units / N_UNITS:g}m".lstrip("0")


def slice_label(slices: tuple[Slice, ...]) -> str:
    parts = []
    for s in slices:
        w = _width_label(s.units)
        if len(s.shares) == 1:
            parts.append(f"[{{{s.shares[0]:g}}},{w}]")
        else:
            parts.append("[" + "+".join(f"({b:g})" for b in s.shares)
                         + f",{w}]")
    return "+".join(parts)


def _mps(label, *shares) -> Partition:
    return Partition((Slice(N_UNITS, tuple(shares)),), label)


def _p(label, *slices) -> Partition:
    return Partition(tuple(slices), label)


def partition_table(c_max: int) -> list[Partition]:
    """The curated partition table; the agent's close actions index it."""
    S = Slice
    table = [_p("[{1.0},1m]", S(8, (1.0,)))]
    table += [_mps(f"[({a:.1f})+({1 - a:.1f}),1m]", a, round(1 - a, 2))
              for a in (0.1, 0.2, 0.3, 0.4, 0.5)]
    table += [_p("[{.5},.5m]+[{.5},.5m]", S(4, (1.0,)), S(4, (1.0,)))]
    table += [
        _mps("[(.1)+(.1)+(.8),1m]", 0.1, 0.1, 0.8),
        _mps("[(.2)+(.2)+(.6),1m]", 0.2, 0.2, 0.6),
        _mps("[(.2)+(.3)+(.5),1m]", 0.2, 0.3, 0.5),
        _mps("[(.33)+(.33)+(.34),1m]", 0.33, 0.33, 0.34),
        _p("[{.5},.5m]+[(.5)+(.5),{.5},.5m]", S(4, (1.0,)), S(4, (0.5, 0.5))),
        _p("[{.5},.5m]+[(.25)+(.75),{.5},.5m]",
           S(4, (1.0,)), S(4, (0.25, 0.75))),
        _p("[{.5},.5m]+[{.25},.25m]+[{.25},.25m]",
           S(4, (1.0,)), S(2, (1.0,)), S(2, (1.0,))),
    ]
    table += [
        _mps("[(.1)+(.1)+(.1)+(.7),1m]", 0.1, 0.1, 0.1, 0.7),
        _mps("[(.25)x4,1m]", 0.25, 0.25, 0.25, 0.25),
        _mps("[(.1)+(.2)+(.3)+(.4),1m]", 0.1, 0.2, 0.3, 0.4),
        _p("[(.5)+(.5),{.5},.5m]x2", S(4, (0.5, 0.5)), S(4, (0.5, 0.5))),
        _p("[(.25)+(.75),{.5},.5m]x2", S(4, (0.25, 0.75)), S(4, (0.25, 0.75))),
        _p("[(.5)+(.5),{.5},.5m]+[{.25},.25m]x2",
           S(4, (0.5, 0.5)), S(2, (1.0,)), S(2, (1.0,))),
        _p("[{.25},.25m]x4", S(2, (1.0,)), S(2, (1.0,)), S(2, (1.0,)),
           S(2, (1.0,))),
    ]
    return [p for p in table if p.arity <= c_max]


def solo_partition(units: int = N_UNITS) -> Partition:
    if units == N_UNITS:
        return partition_table(1)[0]
    s = Slice(units, (1.0,))
    return Partition((s,), slice_label((s,)))


def find_offsets(partition: Partition, free) -> tuple[int, ...] | None:
    """Widest-first aligned first fit onto the free-unit mask."""
    avail = list(free)
    order = sorted(range(len(partition.slices)),
                   key=lambda i: -partition.slices[i].units)
    starts: list[int | None] = [None] * len(partition.slices)
    for i in order:
        w = partition.slices[i].units
        for off in range(0, N_UNITS, w):
            if all(avail[off:off + w]):
                starts[i] = off
                avail[off:off + w] = [False] * w
                break
        else:
            return None
    return tuple(starts)


# ------------------------------------------------------------ co-run model

def _water_fill(demands: list[float]) -> list[float]:
    n = len(demands)
    alloc = [0.0] * n
    remaining = 1.0
    active = list(range(n))
    while active and remaining > 1e-12:
        fair = remaining / len(active)
        sated = [i for i in active if demands[i] - alloc[i] <= fair + 1e-15]
        if sated:
            for i in sated:
                remaining -= demands[i] - alloc[i]
                alloc[i] = demands[i]
            active = [i for i in active if i not in sated]
        else:
            for i in active:
                alloc[i] += fair
            remaining = 0.0
    return alloc


def _slice_step_times(jobs, betas, s: Slice, active) -> list[float]:
    n_active = sum(active)
    idx = [j for j in range(len(jobs)) if active[j]]
    tf = 1.0 if s.units == N_UNITS else 0.5
    base = []
    for j in idx:
        c, m, x = jobs[j].terms(s.units, tf)
        base.append((c / betas[j], m, x, jobs[j].coll_latency(s.units),
                     jobs[j].fixed_latency(s.units) + jobs[j].serial_s))
    shared_mem = len(s.shares) > 1 and n_active > 1
    multi = n_active > 1
    mem_t = [b[1] for b in base]
    coll_t = [b[2] for b in base]
    mem_u = [0.0] * len(base)
    coll_u = [0.0] * len(base)
    for _ in range(30):
        st = [max(b[0], mt, ct + b[3]) + b[4]
              for b, mt, ct in zip(base, mem_t, coll_t)]
        mem_u = [min(1.0, b[1] / t) for b, t in zip(base, st)]
        coll_u = [min(1.0, b[2] / t) for b, t in zip(base, st)]
        ma = _water_fill(mem_u) if shared_mem else mem_u
        ca = _water_fill(coll_u) if multi else coll_u
        delta = 0.0
        for i, b in enumerate(base):
            u_m, a_m, u_x, a_x = mem_u[i], ma[i], coll_u[i], ca[i]
            tgt_m = (b[1] / a_m if shared_mem and a_m > 1e-12
                     and u_m > a_m + 1e-12 else b[1])
            tgt_x = (b[2] / a_x if multi and a_x > 1e-12
                     and u_x > a_x + 1e-12 else b[2])
            delta += abs(tgt_m - mem_t[i]) + abs(tgt_x - coll_t[i])
            mem_t[i] += 0.5 * (tgt_m - mem_t[i])
            coll_t[i] += 0.5 * (tgt_x - coll_t[i])
        if delta < 1e-9:
            break
    out = [math.inf] * len(jobs)
    for i, (b, mt, ct, j) in enumerate(zip(base, mem_t, coll_t, idx)):
        km = (1.0 + KAPPA_INTERFERENCE * (sum(mem_u) - mem_u[i])
              if shared_mem else 1.0)
        kx = (1.0 + KAPPA_INTERFERENCE * (sum(coll_u) - coll_u[i])
              if multi else 1.0)
        t = max(b[0], mt * km, (ct + b[3]) * kx) + b[4]
        if n_active > 1:
            t *= 1.0 + SIGMA_QUANTUM * (n_active - 1)
        out[j] = t
    return out


def _simulate_slice(jobs, betas, s: Slice) -> list[float]:
    n = len(jobs)
    remaining = [float(j.steps) for j in jobs]
    active = [True] * n
    finish = [0.0] * n
    t = 0.0
    for _ in range(n):
        if not any(active):
            break
        st = _slice_step_times(jobs, betas, s, active)
        dt = min(remaining[j] * st[j] for j in range(n) if active[j])
        for j in range(n):
            if active[j]:
                remaining[j] -= dt / st[j]
                if remaining[j] <= 1e-9:
                    active[j] = False
                    finish[j] = t + dt
        t += dt
    return finish


def corun(group: list[Job], partition: Partition) -> list[float]:
    """Per-job finish times of ``group`` co-run under ``partition`` (jobs
    take the partition's slots in order)."""
    by_slice: dict[int, tuple[list[int], list[float], Slice]] = {}
    for pos, (si, s, beta) in enumerate(partition.slots):
        bucket = by_slice.setdefault(si, ([], [], s))
        bucket[0].append(pos)
        bucket[1].append(beta)
    finish = [0.0] * len(group)
    for positions, betas, s in by_slice.values():
        for pos, ft in zip(positions,
                           _simulate_slice([group[p] for p in positions],
                                           betas, s)):
            finish[pos] = ft
    return finish

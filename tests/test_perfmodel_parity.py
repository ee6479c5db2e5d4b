"""The co-run model computes each slice's per-job terms once and runs its
fixed point on flat lists; it must give what the per-phase, per-dict model
gave, bit for bit.

The oracle below is that earlier model, kept verbatim.  Every case asserts
``==`` on the makespan and on each finish time: no tolerance.
"""
import itertools
import random

import pytest

from repro.core import make_zoo
from repro.core.partition import (
    Partition, Slice, enumerate_partitions, slice_label, solo_partition,
)
from repro.core.perfmodel import (
    KAPPA_INTERFERENCE, SIGMA_QUANTUM, corun, corun_time, water_fill,
)
from repro.core.profiles import JobProfile

ZOO = make_zoo()
TABLE = enumerate_partitions(4)


# ---------------------------------------------------------------------------
# the oracle: the model as it was, verbatim
# ---------------------------------------------------------------------------

def _water_fill(demands, capacity=1.0):
    n = len(demands)
    if n == 0:
        return []
    alloc = [0.0] * n
    remaining = capacity
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


def _slice_step_times(jobs, betas, s, active):
    n_active = sum(active)
    idx = [j for j in range(len(jobs)) if active[j]]
    base = []
    for j in idx:
        c, m, x = jobs[j].terms(s.units, s.torus_factor)
        base.append({
            "c": c / betas[j], "m": m, "xb": x,
            "xl": jobs[j].coll_latency(s.units),
            "fixed": jobs[j].fixed_latency(s.units) + jobs[j].serial_s,
        })
    shared_mem = s.shared_memory and n_active > 1
    multi = n_active > 1
    mem_t = [b["m"] for b in base]
    coll_t = [b["xb"] for b in base]
    mem_u = [0.0] * len(base)
    coll_u = [0.0] * len(base)

    for _ in range(30):
        st = [max(b["c"], mt, ct + b["xl"]) + b["fixed"]
              for b, mt, ct in zip(base, mem_t, coll_t)]
        mem_u = [min(1.0, b["m"] / t) for b, t in zip(base, st)]
        coll_u = [min(1.0, b["xb"] / t) for b, t in zip(base, st)]
        ma = _water_fill(mem_u) if shared_mem else mem_u
        ca = _water_fill(coll_u) if multi else coll_u
        delta = 0.0
        for i, (b, u_m, a_m, u_x, a_x) in enumerate(zip(base, mem_u, ma, coll_u, ca)):
            tgt_m = b["m"] / a_m if (shared_mem and a_m > 1e-12 and u_m > a_m + 1e-12) else b["m"]
            tgt_x = b["xb"] / a_x if (multi and a_x > 1e-12 and u_x > a_x + 1e-12) else b["xb"]
            delta += abs(tgt_m - mem_t[i]) + abs(tgt_x - coll_t[i])
            mem_t[i] += 0.5 * (tgt_m - mem_t[i])
            coll_t[i] += 0.5 * (tgt_x - coll_t[i])
        if delta < 1e-9:
            break

    out = [float("inf")] * len(jobs)
    for i, (b, mt, ct, j) in enumerate(zip(base, mem_t, coll_t, idx)):
        km = 1.0 + KAPPA_INTERFERENCE * (sum(mem_u) - mem_u[i]) if shared_mem else 1.0
        kx = 1.0 + KAPPA_INTERFERENCE * (sum(coll_u) - coll_u[i]) if multi else 1.0
        t = max(b["c"], mt * km, (ct + b["xl"]) * kx) + b["fixed"]
        if n_active > 1:
            t *= 1.0 + SIGMA_QUANTUM * (n_active - 1)
        out[j] = t
    return out


def _simulate_slice(jobs, betas, s):
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


def _corun(group, partition):
    slots = partition.slots
    assert len(group) == len(slots), (len(group), partition.label)
    by_slice = {}
    for pos, (si, s, beta) in enumerate(slots):
        bucket = by_slice.setdefault(si, ([], [], s))
        bucket[0].append(pos)
        bucket[1].append(beta)
    finish = [0.0] * len(group)
    for si, (positions, betas, s) in by_slice.items():
        fts = _simulate_slice([group[p] for p in positions], betas, s)
        for pos, ft in zip(positions, fts):
            finish[pos] = ft
    solo = [j.solo_time() for j in group]
    return max(finish), finish, solo


# ---------------------------------------------------------------------------
# cases
# ---------------------------------------------------------------------------

def _assert_bit_equal(group, partition):
    want_makespan, want_finish, want_solo = _corun(group, partition)
    got = corun(group, partition)
    assert got.makespan == want_makespan, partition.label
    assert got.finish_times == want_finish, partition.label
    assert all(type(f) is float for f in got.finish_times)
    assert got.solo_times == want_solo
    assert corun_time(group, partition) == want_makespan


def _random_profile(rng: random.Random, k: int) -> JobProfile:
    """Terms spread over the zoo's decades, so every bound (compute,
    memory, collective bytes, collective latency, fixed) leads somewhere."""
    return JobProfile(
        name=f"rand{k}", arch="rand", shape="rand",
        steps=rng.choice((1, 3, rng.randint(1, 200), rng.randint(1, 200_000))),
        flops_total=10 ** rng.uniform(12, 19),
        bytes_total=10 ** rng.uniform(9, 15),
        coll_bytes_chip_pod=rng.choice((0.0, 10 ** rng.uniform(3, 10))),
        n_coll_step=rng.choice((0, rng.randint(1, 400))),
        serial_s=rng.choice((0.0, 10 ** rng.uniform(-6, -2))),
    )


@pytest.mark.parametrize("partition", TABLE, ids=[p.label for p in TABLE])
def test_zoo_groups_every_slot_order(partition):
    """A few zoo groups of the partition's arity, in every slot order."""
    rng = random.Random(f"zoo:{partition.label}")
    k = partition.arity
    groups = [rng.sample(ZOO, k) for _ in range(3)]
    groups.append([ZOO[0]] * k)                     # one job object, k times
    for group in groups:
        for order in itertools.permutations(group):
            _assert_bit_equal(list(order), partition)


@pytest.mark.parametrize("units", [1, 2, 4, 8])
def test_zoo_alone_on_every_width(units):
    part = solo_partition(units)
    for job in ZOO:
        _assert_bit_equal([job], part)


def _narrowed(partition: Partition, units: int) -> Partition:
    """``partition`` with its dedicated slices cut to ``units``, as width
    fitting places right-sized jobs."""
    slices = tuple(Slice(min(units, s.units), s.shares) if len(s.shares) == 1
                   else s for s in partition.slices)
    return Partition(slices, slice_label(slices))


@pytest.mark.parametrize("seed", range(8))
def test_random_profiles_on_every_partition(seed):
    rng = random.Random(seed)
    fitted = [_narrowed(p, u) for p in TABLE for u in (1, 2)]
    for partition in TABLE + fitted:
        for _ in range(4):
            group = [_random_profile(rng, k) for k in range(partition.arity)]
            _assert_bit_equal(group, partition)


@pytest.mark.parametrize("n", [1, 2, 3, 4])
def test_water_fill_random_vectors(n):
    rng = random.Random(n)
    grid = (0.0, 0.25, 1 / 3, 0.5, 1.0)             # exact fair-share ties
    cases = [[rng.random() for _ in range(n)] for _ in range(500)]
    cases += [[rng.choice(grid) for _ in range(n)] for _ in range(200)]
    cases += [[rng.uniform(0.0, 2.0 / n) for _ in range(n)] for _ in range(300)]
    for demands in cases:
        for capacity in (1.0, rng.random()):
            assert water_fill(demands, capacity) == _water_fill(demands, capacity), \
                (demands, capacity)


@pytest.mark.parametrize("demands,capacity", [
    ([], 1.0),
    ([0.5, 0.5], 1.0), ([0.5 + 1e-15, 0.5], 1.0), ([0.6, 0.6], 1.0),
    ([0.2, 0.9], 1.0), ([0.9, 0.2], 1.0), ([0.2, 0.8], 1.0),
    ([1e-12, 3e-12], 2e-12), ([3e-12, 1e-12], 2e-12), ([0.3, 0.4], 1e-13),
    ([0.0, 0.0, 0.0], 1.0), ([1.0, 1.0, 1.0, 1.0], 1.0),
    ([0.1, 0.5, 0.5, 0.1], 1.0), ([1 / 3, 1 / 3, 1 / 3], 1.0),
])
def test_water_fill_edges(demands, capacity):
    assert water_fill(demands, capacity) == _water_fill(demands, capacity)

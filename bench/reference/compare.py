"""The comparison that decides a serving cell's ``correct``.

Both sides are given as plain dicts: ``records`` (one per arrival, in
sorted-trace order, with the decision fields and the clock), the run's
counters and each pod's placed segments in placement order.  Two numbers
come out:

* ``decisions_differing`` — records whose decision (pod, group size,
  partition, slice width, backfill flag, the jobs it shares its group
  with) differs, plus each differing run counter and segment.  Exact:
  its limit is 0.
* ``clock_gap`` — the largest gap between the two clocks over every
  record's dispatch and finish, each segment's ends and the busy time, as
  a share of the reference's value (at least one simulated second).
"""
from __future__ import annotations

import math

DECISION_FIELDS = ("name", "binary", "pod", "group_size", "partition",
                   "units", "backfilled")
COUNTERS = ("dispatches", "backfills", "refits")


UNMATCHED = 1e30          # the gap of a time one side lacks


def _gap(a: float, b: float) -> float:
    if not (math.isfinite(a) and math.isfinite(b)):
        return 0.0 if a == b or (math.isnan(a) and math.isnan(b)) \
            else UNMATCHED
    return abs(a - b) / max(abs(b), 1.0)


def group_ids(records: list[dict]) -> list[int]:
    """A group number per record: records dispatched together on one pod
    under one partition form one group.  (Every multi-job partition of the
    table spans the whole pod, so two such groups never start together on
    one pod.)"""
    ids: dict[tuple, int] = {}
    return [ids.setdefault((r["pod"], r["dispatch"], r["partition"],
                            r["group_size"], i if r["group_size"] == 1
                            else -1), len(ids))
            for i, r in enumerate(records)]


def _members(records: list[dict]) -> list[tuple[int, ...]]:
    groups: dict[int, list[int]] = {}
    ids = group_ids(records)
    for i, g in enumerate(ids):
        groups.setdefault(g, []).append(i)
    return [tuple(groups[g]) for g in ids]


def compare(program: dict, reference: dict) -> dict:
    differing = 0
    gap = 0.0
    ra, rb = program["records"], reference["records"]
    if len(ra) != len(rb):
        return {"decisions_differing": abs(len(ra) - len(rb)) + len(rb),
                "clock_gap": UNMATCHED, "examples": [
                    f"{len(ra)} records for {len(rb)} arrivals"]}
    examples = []
    ma, mb = _members(ra), _members(rb)
    for i, (a, b) in enumerate(zip(ra, rb)):
        bad = [f for f in DECISION_FIELDS if a[f] != b[f]]
        if ma[i] != mb[i]:
            bad.append("group")
        if bad:
            differing += 1
            if len(examples) < 5:
                examples.append(f"record {i}: " + ", ".join(
                    f"{f} {a.get(f, ma[i])!r} != {b.get(f, mb[i])!r}"
                    for f in bad))
        gap = max(gap, _gap(a["dispatch"], b["dispatch"]),
                  _gap(a["finish"], b["finish"]))
    for f in COUNTERS:
        if program[f] != reference[f]:
            differing += 1
            examples.append(f"{f} {program[f]} != {reference[f]}")
    for p, (sa, sb) in enumerate(zip(program["segments"],
                                     reference["segments"])):
        if len(sa) != len(sb):
            differing += abs(len(sa) - len(sb))
            examples.append(f"pod {p}: {len(sa)} segments != {len(sb)}")
            continue
        for x, y in zip(sa, sb):
            if x[2:] != y[2:]:
                differing += 1
            gap = max(gap, _gap(x[0], y[0]), _gap(x[1], y[1]))
    gap = max(gap, _gap(program["busy_time"], reference["busy_time"]))
    return {"decisions_differing": differing, "clock_gap": gap,
            "examples": examples[:5]}

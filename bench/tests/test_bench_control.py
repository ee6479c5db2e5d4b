"""The check that decides ``correct`` fails what it must fail.

* The control: the plain reference put in the program's place, computed
  one precision below what the configuration states (a float32 clock for
  the float64 one; bfloat16 operands for the agent's float32 ones), is
  judged not correct in every serving cell.
* Faults planted under a run whose chip check is skipped: an answer
  altered where it is produced, half of a trace's arrivals left out, and
  a unit that hands back the previous unit's result.  Each run is judged
  not correct; the same run without a fault is judged correct.

Run as a script, it prints the control's readings at the cells' own
sizes, one JSON line per seed:

    python bench/tests/test_bench_control.py <cell> <seed> [<seed> ...]
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
for _p in (ROOT, ROOT / "src"):
    if str(_p) not in sys.path:
        sys.path.insert(0, str(_p))

from bench import run as bench_run  # noqa: E402
from bench.lib.serving import Inputs, follow_keys, load_json  # noqa: E402
from bench.reference.compare import compare  # noqa: E402

SEED = 2**31 + 101
# every serving cell the harness can drive: the benchmark's, and the two
# fleet cells kept out of BENCHMARK.json until the vectorized engine admits
# arrivals on its full clock (see PERF.md)
CELLS = {"pod_decide_rl": ("paper_pod", "poisson_pod"),
         "fleet_rl_poisson": ("fleet_4pod_hash", "poisson_fleet"),
         "fleet_ts_poisson": ("fleet_4pod_hash_ts", "poisson_fleet")}


def _entry(cell: str) -> dict:
    config, traffic = CELLS[cell]
    return {"name": cell, "config": config, "traffic": traffic, "chips": 1}


def control_numbers(cell: str, seed: int, arrivals: int | None = None,
                    pool: int | None = None, clock: str = "float32",
                    operands: str = "bfloat16") -> dict:
    """The compared numbers of the reference computed at ``clock`` and
    ``operands`` in the program's place — by default the control — over
    the cell's trace pool (or a smaller one), worst over the traces, as
    the check computes them."""
    entry = _entry(cell)
    config = load_json(ROOT / "bench" / "configs" / f"{entry['config']}.json")
    traffic = load_json(ROOT / "bench" / "traffic" / f"{entry['traffic']}.json")
    traffic = dict(traffic, arrivals=arrivals or traffic["arrivals"],
                   pool=pool or traffic["pool"])
    tie_tol = load_json(ROOT / "bench" / "workloads"
                        / f"{cell}.json")["check"]["tie_tol"]
    inputs = Inputs(config, traffic, seed)
    worst = {"decisions_differing": 0, "clock_gap": 0.0, "tie_gap": 0.0}
    for k in range(len(inputs.pool)):
        trace = inputs.reference_trace(k)
        ctl = inputs.reference(clock, operands, tie_tol).run(trace)
        ref = inputs.reference("float64", "float32", tie_tol).run(
            trace, follow=follow_keys(ctl))
        cmp = compare(ctl, ref)
        worst["decisions_differing"] += cmp["decisions_differing"]
        worst["clock_gap"] = max(worst["clock_gap"], cmp["clock_gap"])
        worst["tie_gap"] = max(worst["tie_gap"], ref["tie_gap"])
    if config["policy"] != "rl":
        worst.pop("tie_gap")
    return worst


def _fails(cell: str, numbers: dict) -> bool:
    limits = load_json(ROOT / "bench" / "workloads"
                       / f"{cell}.json")["check"]["limits"]
    return any(v > limits[k] for k, v in numbers.items())


@pytest.mark.parametrize("cell,arrivals", [
    ("fleet_rl_poisson", 2000),
    ("fleet_ts_poisson", 10000),
    ("pod_decide_rl", 2000),
])
def test_control_is_not_correct(cell, arrivals):
    assert _fails(cell, control_numbers(cell, SEED, arrivals, pool=1))


# ------------------------------------------------------------------ faults

def _ctx(cell: str, arrivals: int):
    entry, bench = _entry(cell), load_json(ROOT / "BENCHMARK.json")
    args = argparse.Namespace(workload=cell, seed=SEED, seconds=0.0,
                              trace=0)
    ctx = bench_run.Context(args, entry,
                            {"platform": "cpu", "kind": "cpu", "count": 1})
    ctx.traffic = dict(ctx.traffic, arrivals=arrivals, pool=2)
    if "lane_capacity" in ctx.spec:
        ctx.spec = dict(ctx.spec, lane_capacity=arrivals)
    return ctx, bench


def _alter(res):
    res.jobs[len(res.jobs) // 2] = dataclasses.replace(
        res.jobs[len(res.jobs) // 2], units=4, partition="[{.5},.5m]")
    return res


def _halve(res):
    res.jobs = res.jobs[: len(res.jobs) // 2]
    return res


def _planted(cls, fault):
    run, last = cls.run, []

    def broken(self, trace):
        res = run(self, trace)
        if fault == "stale":       # the previous call's result again
            last.append(res)
            return last[-2] if len(last) > 1 else res
        return {"alter": _alter, "halve": _halve}[fault](res)

    return broken


@pytest.mark.parametrize("cell,arrivals,cls", [
    ("fleet_ts_poisson", 600, "VectorizedFleetSimulator"),
    ("pod_decide_rl", 120, "ClusterSimulator"),
])
@pytest.mark.parametrize("fault", [None, "alter", "halve", "stale"])
def test_planted_fault_is_not_correct(monkeypatch, cell, arrivals, cls,
                                      fault):
    import repro.online as online

    ctx, bench = _ctx(cell, arrivals)
    if fault is not None:
        monkeypatch.setattr(getattr(online, cls), "run",
                            _planted(getattr(online, cls), fault))
    line = bench_run.run_cell(ctx, bench)["line"]
    assert line["correct"] is (fault is None), line["check"]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description="control readings")
    ap.add_argument("cell")
    ap.add_argument("seeds", type=int, nargs="+")
    a = ap.parse_args(argv)
    for s in a.seeds:
        print(json.dumps({"cell": a.cell, "seed": s,
                          **control_numbers(a.cell, s)}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())

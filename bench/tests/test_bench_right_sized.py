"""The right-sized, arrival-aware pod (cell ``pod_right_sized_rl``).

* The arrival process ``right_sized`` gives what the program's
  ``fragmented_trace`` gives for the same load, mix, seed and tolerances:
  times, zoo entries and requested widths, bit for bit.
* Its traces served by the heap engine with the frozen context-trained
  agent are judged correct, and the control (the reference one precision
  below) and planted faults are judged not correct.
* The context block feeds the agent's decisions, and the reference's
  context block is the program's.
* The driver ``decide_spans`` reads the program's spans under
  ``--trace 1`` and reads nothing where the program has none.
"""
from __future__ import annotations

import argparse
import dataclasses
import sys
from pathlib import Path

import numpy as np
import pytest

ROOT = Path(__file__).resolve().parents[2]
for _p in (ROOT, ROOT / "src"):
    if str(_p) not in sys.path:
        sys.path.insert(0, str(_p))

import test_bench_control as control  # noqa: E402

from bench import run as bench_run  # noqa: E402
from bench.drivers import decide, decide_spans  # noqa: E402
from bench.lib import traffic  # noqa: E402
from bench.lib.serving import Inputs, judge, load_json, plain  # noqa: E402
from bench.reference import context_planner  # noqa: E402
from bench.reference.model import jobs_from_snapshot  # noqa: E402
from bench.reference.serve import View  # noqa: E402

CELL = "pod_right_sized_rl"
SEED = 2**31 + 4099
CONFIG = load_json(ROOT / "bench" / "configs" / "right_sized_pod.json")
TRAFFIC = load_json(ROOT / "bench" / "traffic" / "right_sized_poisson.json")
CHECK = load_json(ROOT / "bench" / "workloads" / f"{CELL}.json")["check"]


@pytest.fixture(autouse=True)
def _recorder_off():
    from repro import spans

    spans.disable()
    spans.reset()
    yield
    spans.disable()
    spans.reset()


def _inputs(arrivals: int, load: float = TRAFFIC["load"], seed: int = SEED,
            config: dict = CONFIG) -> Inputs:
    return Inputs(config, dict(TRAFFIC, arrivals=arrivals, load=load,
                               pool=1), seed)


def _serve(inputs: Inputs, policy=None):
    from repro.online import ClusterSimulator

    policy = policy or inputs.program_policy()
    return ClusterSimulator(policy, inputs.sim_config()).run(
        inputs.program_pool()[0])


# ------------------------------------------------------------- arrivals

@pytest.mark.parametrize("seed", [7, 11, 2**31 + 17])
def test_right_sized_is_the_programs_fragmented_trace(seed):
    from repro.core.workloads import make_zoo
    from repro.online.traces import fragmented_trace

    zoo = traffic.load_zoo()
    times, picks, units = traffic.load_process("right_sized").trace(
        TRAFFIC, zoo, seed, 1.0)
    prog = fragmented_trace(make_zoo(), TRAFFIC["arrivals"],
                            load=TRAFFIC["load"], mix=TRAFFIC["mix"],
                            seed=seed, tols=tuple(TRAFFIC["tols"]))
    assert len(prog) == len(times) == TRAFFIC["arrivals"]
    assert [a.t for a in prog] == times.tolist()
    assert [a.profile.name.split("@u")[0] for a in prog] == \
        [zoo[p]["name"] for p in picks]
    assert [a.profile.requested_units for a in prog] == units.tolist()


def test_right_sized_widths_at_2000_arrivals():
    times, picks, units = traffic.load_process("right_sized").trace(
        TRAFFIC, traffic.load_zoo(), 7, 1.0)
    counts = dict(zip(*np.unique(units, return_counts=True)))
    assert {int(k): int(v) for k, v in counts.items()} == \
        {1: 699, 2: 65, 4: 180, 8: 1056}
    assert np.all(np.diff(times) > 0)


def test_right_sizing_restates_the_programs_at_every_tolerance():
    from repro.core.workloads import make_zoo

    ref = traffic.load_process("right_sized")
    snap = {r["name"]: r for r in traffic.load_zoo()}
    for prof in make_zoo():
        (job,) = jobs_from_snapshot([snap[prof.name]])
        for tol in TRAFFIC["tols"]:
            assert ref.right_size(job, tol) == prof.right_size(tol)


# ----------------------------------------------------------- the agent

def test_frozen_agent_sees_the_context_block():
    inputs = _inputs(8)
    cfg = inputs.env_config()
    assert cfg.obs_context and (cfg.window, cfg.c_max) == (8, 4)
    from repro.core.env import CoScheduleEnv, context_dim

    w0 = inputs.params["w0"]
    assert w0.shape[0] == CoScheduleEnv(cfg).state_dim == 96 + 17
    assert context_dim(cfg) == 17
    assert np.abs(w0[96:]).max() > 0.1
    assert [inputs.params[f"w{i}"].shape[1] for i in range(3)] == \
        CONFIG["agent"]["hidden"]


def test_reference_context_block_is_the_programs():
    from repro.core.env import DispatchContext, context_block

    free = (True, False, False, True, True, True, False, True)
    ages = (0.0, 3.5, 120.25, 7e4)
    for depth in (0, 5, 40):
        view = View(free, ages, depth)
        want = context_block(DispatchContext(free, ages, depth), 8)
        got = context_planner.context_block(view, ages, 8)
        assert got.dtype == want.dtype and np.array_equal(got, want)


def test_zeroed_context_changes_decisions():
    """The same agent with the context rows of its input layer zeroed,
    which is the agent shown a zero context block, decides otherwise."""
    inputs = _inputs(400, load=1.5, seed=11)
    trained = _serve(inputs)
    policy = inputs.program_policy()
    agent = policy.agent
    agent.params = dict(agent.params,
                        w0=agent.params["w0"].at[96:].set(0.0))
    zeroed = _serve(inputs, policy)
    differ = sum(dataclasses.astuple(a) != dataclasses.astuple(b)
                 for a, b in zip(trained.jobs, zeroed.jobs))
    assert differ > 0


# ------------------------------------------------------------ the check

@pytest.mark.parametrize("load", [1.25, 1.5])
def test_served_right_sized_traces_are_correct(load, monkeypatch):
    inputs = _inputs(300, load=load)
    res = _serve(inputs)
    assert {r.units for r in res.jobs} >= {1, 4, 8}
    assert res.backfills > 0
    mixed = []
    found = context_planner.planned_ages

    def recorded(view, n_planned):
        mixed.append(n_planned < len(view.ages_s))
        return found(view, n_planned)

    monkeypatch.setattr(context_planner, "planned_ages", recorded)
    verdict = judge(inputs, {0: plain(res)}, CHECK)
    # windows that hold first sights beside profiled jobs were planned
    assert any(mixed) and not all(mixed)
    n = verdict["numbers"]
    assert n["decisions_differing"] == 0, verdict["examples"]
    assert n["clock_gap"] <= 1e-9
    assert n["tie_gap"] <= 1e-4


def test_control_is_not_correct(monkeypatch):
    monkeypatch.setitem(control.CELLS, CELL,
                        ("right_sized_pod", "right_sized_poisson"))
    numbers = control.control_numbers(CELL, SEED, 2000, pool=1)
    assert control._fails(CELL, numbers), numbers


def _ctx(arrivals: int, trace: bool):
    entry = {"name": CELL, "config": "right_sized_pod",
             "traffic": "right_sized_poisson", "chips": 1}
    args = argparse.Namespace(workload=CELL, seed=SEED, seconds=0.0,
                              trace=0)
    ctx = bench_run.Context(args, entry,
                            {"platform": "cpu", "kind": "cpu", "count": 1})
    ctx.traffic = dict(ctx.traffic, arrivals=arrivals, pool=2)
    ctx.trace = trace               # spans on, with no profiler
    return ctx


@pytest.mark.parametrize("fault", [None, "alter", "halve", "stale"])
def test_planted_fault_is_not_correct(monkeypatch, fault):
    import repro.online as online

    ctx = _ctx(150, trace=False)
    if fault is not None:
        monkeypatch.setattr(online.ClusterSimulator, "run", control._planted(
            online.ClusterSimulator, fault))
    line = bench_run.run_cell(ctx, load_json(ROOT / "BENCHMARK.json"))
    assert line["line"]["correct"] is (fault is None), line["line"]["check"]


# ----------------------------------------------------------- the driver

def test_untraced_run_is_the_decide_driver(monkeypatch):
    calls = []
    monkeypatch.setattr(decide, "run", lambda ctx: calls.append(ctx) or {})
    ctx = _ctx(10, trace=False)
    assert decide_spans.run(ctx) == {} and calls == [ctx]


def test_traced_run_reads_the_programs_spans():
    from repro import spans

    ctx = _ctx(200, trace=True)
    out = decide_spans.run(ctx)
    c = out["counters"]
    assert out["numbers"]["decisions_differing"] == 0
    # the recorder was reset where the window starts: the warm-up's calls
    # are not counted
    assert c["pack_calls"] == c["episode_calls"] > 0
    assert c["fit_calls"] == c["decide_calls"]
    assert c["shrunk_slices"] > 0 and c["backfilled_groups"] > 0
    assert c["backfill_scans"] >= 1
    assert c["pack_p50_us"] > 0 and c["fit_p50_us"] > 0
    run = {"counters": c, "breakdown": None, "device": ctx.device}
    assert bench_run.read_metric("decide.pack_us", run) == c["pack_p50_us"]
    assert bench_run.read_metric("decide.fit_us", run) == c["fit_p50_us"]
    assert not spans._on and spans.records() == []


def test_a_program_without_the_spans_reads_nothing():
    c = decide_spans.span_counters({"repro.sched.episode": {
        "count": 3, "median_us": 9.0}}, {"repro.sched.steps": [4, 5, 6]})
    assert c == {"pack_calls": 0, "pack_p50_us": None, "fit_calls": 0,
                 "fit_p50_us": None, "shrunk_slices": 0,
                 "backfill_scans": 0, "backfilled_groups": 0}
    run = {"counters": c}
    assert bench_run.read_metric("decide.pack_us", run) is None
    assert bench_run.read_metric("decide.fit_us", run) is None

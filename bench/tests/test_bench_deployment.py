"""A deployment enters the benchmark as new files: an arrival process that
gives each arrival a slice width, the agent's settings from the
configuration, and a reference planner that sees the pod.

* Requested widths reach both sides alike, and a trace of 1/2/4/8-unit
  requests served by the heap engine with the frozen ``paper_pod`` agent
  is judged correct.
* A planner named by the configuration is handed, at every window it
  plans, the same free-unit mask, head ages and pending depth as the
  program's ``DispatchContext``.
* ``obs_context`` comes from the configuration's agent block.
"""
from __future__ import annotations

import sys
import types
from pathlib import Path

import numpy as np
import pytest

ROOT = Path(__file__).resolve().parents[2]
for _p in (ROOT, ROOT / "src"):
    if str(_p) not in sys.path:
        sys.path.insert(0, str(_p))

from bench.lib import serving, traffic  # noqa: E402
from bench.lib.serving import Inputs, judge, load_json, plain  # noqa: E402
from bench.reference.serve import RLPlanner  # noqa: E402

SEED = 2**31 + 7919
CONFIG = load_json(ROOT / "bench" / "configs" / "paper_pod.json")
CHECK = load_json(ROOT / "bench" / "workloads" / "pod_decide_rl.json")["check"]
WIDTHS = (1, 2, 4, 8)


def _widths_trace(spec: dict, zoo: list[dict], seed: int, capacity: float):
    """Poisson arrivals, each asking for a width drawn from ``WIDTHS``."""
    poisson = traffic.load_process("poisson").poisson
    times, picks = poisson(zoo, spec["arrivals"], spec["load"], spec["mix"],
                           seed, capacity)
    rng = np.random.default_rng(seed + 1)
    return times, picks, rng.choice(WIDTHS, size=len(picks))


@pytest.fixture
def widths_process(monkeypatch):
    """The process ``widths`` is found by name like a file's would be."""
    found = traffic.load_process

    def load_process(name):
        if name == "widths":
            return types.SimpleNamespace(trace=_widths_trace)
        return found(name)

    monkeypatch.setattr(traffic, "load_process", load_process)
    return {"process": "widths", "load": 1.25, "mix": "balanced",
            "arrivals": 200, "pool": 1}


def _serve(inputs: Inputs, k: int = 0, on_decide=None):
    from repro.online import ClusterSimulator

    policy = inputs.program_policy()
    if on_decide is not None:
        decide = policy.decide

        def recorded(subs, context=None):
            on_decide(subs, context)
            return decide(subs, context=context)

        policy.decide = recorded
    return ClusterSimulator(policy, inputs.sim_config()).run(
        inputs.program_pool()[k])


def test_widths_reach_program_and_reference_alike(widths_process):
    inputs = Inputs(CONFIG, widths_process, SEED)
    units = inputs.pool[0].units
    assert set(units.tolist()) == set(WIDTHS)
    prog = inputs.program_pool()[0]
    ref = inputs.reference_trace(0)
    assert len(prog) == len(ref) == 200
    for a, (t, binary, job), w, p in zip(prog, ref, units,
                                         inputs.pool[0].picks):
        name = inputs.zoo[p]["name"]
        want = name if w == 8 else f"{name}@u{w}"
        assert a.t == t
        assert a.binary == binary == f"bin://{want}"
        assert a.profile.name == job.name == want
        assert a.profile.requested_units == job.requested_units == w
        assert a.profile.meta.get("units") == job.meta.get("units") == (
            None if w == 8 else w)


def test_narrow_requests_served_by_the_heap_are_correct(widths_process):
    inputs = Inputs(CONFIG, widths_process, SEED)
    res = _serve(inputs)
    assert {r.units for r in res.jobs} >= {1, 2, 4}
    verdict = judge(inputs, {0: plain(res)}, CHECK)
    n = verdict["numbers"]
    assert n["decisions_differing"] == 0, verdict["examples"]
    assert n["clock_gap"] <= 1e-9
    assert n["tie_gap"] <= 1e-4


def test_named_planner_sees_the_programs_dispatch_context(
        widths_process, monkeypatch):
    views = []

    class Recording(RLPlanner):
        def plan(self, queue, accept, view=None):
            views.append(view)
            return super().plan(queue, accept, view)

    monkeypatch.setitem(sys.modules, "bench.reference.recording",
                        types.SimpleNamespace(Planner=Recording))
    config = dict(CONFIG, agent=dict(CONFIG["agent"], planner="recording"))
    inputs = Inputs(config, widths_process, SEED)
    assert serving.load_planner("recording") is Recording

    contexts, seen = [], set()

    def on_decide(subs, context):
        # the reference plans a window that holds a profiled job
        planned = False
        for binary, _ in subs:
            planned |= binary in seen
            seen.add(binary)
        if planned:
            contexts.append(context)

    res = _serve(inputs, on_decide=on_decide)
    verdict = judge(inputs, {0: plain(res)}, CHECK)
    assert verdict["numbers"]["decisions_differing"] == 0
    assert len(contexts) > 20
    assert len(views) == len(contexts)
    narrow = 0
    for v, c in zip(views, contexts):
        assert v.free_units == c.free_units
        assert v.ages_s == c.ages_s
        assert v.queue_depth == c.queue_depth
        narrow += not all(v.free_units)
    assert narrow > 0


def test_agent_settings_come_from_the_configuration():
    from repro.core import EnvConfig
    from repro.core.env import CoScheduleEnv

    traffic_spec = load_json(ROOT / "bench" / "traffic" / "poisson_pod.json")
    inputs = Inputs(CONFIG, traffic_spec, SEED)
    cfg = inputs.env_config()
    assert cfg == EnvConfig(window=8, c_max=4)
    assert cfg.obs_context is False
    dim = CoScheduleEnv(cfg).state_dim
    assert dim == inputs.params["w0"].shape[0]
    policy = inputs.program_policy()
    assert policy.scheduler.env_cfg == cfg

    aware = dict(CONFIG, agent=dict(CONFIG["agent"], obs_context=True))
    cfg_aware = Inputs(aware, traffic_spec, SEED).env_config()
    assert cfg_aware.obs_context is True
    W = CONFIG["agent"]["window"]
    assert CoScheduleEnv(cfg_aware).state_dim == dim + 8 + W + 1

"""What the serving drivers share: the cell's inputs on both sides of the
comparison and the comparison itself.

The program gets its own objects (``JobProfile``, ``Arrival``, a
``DQNAgent`` holding the frozen params); the reference gets plain ones
built from the same frozen files.  Nothing the program computes reaches
the reference except the decisions it is judged on.
"""
from __future__ import annotations

import importlib
import json
from pathlib import Path

import ml_dtypes
import numpy as np

from bench.lib import traffic
from bench.reference.compare import compare, group_ids
from bench.reference.model import N_UNITS, jobs_from_snapshot
from bench.reference.serve import (
    RLPlanner, ReferenceAgent, ReferenceFleet, f32_clock, f64_clock,
)

BENCH = Path(__file__).resolve().parents[1]

# operand precision of the agent's matrix products: what the configuration
# states, and the next lower one for the control
OPERANDS = {"float32": np.float32, "bfloat16": ml_dtypes.bfloat16}
CLOCKS = {"float64": f64_clock, "float32": f32_clock}


def load_planner(name: str):
    """The reference planner class ``Planner`` of ``bench/reference/<name>.py``
    (constructed as :class:`RLPlanner` is)."""
    return importlib.import_module(f"bench.reference.{name}").Planner


class Inputs:
    """A cell's frozen inputs and its trace pool for one ``--seed``."""

    def __init__(self, config: dict, traffic_spec: dict, seed: int):
        self.config = config
        self.zoo = traffic.load_zoo()
        capacity = sum(config["pods"]) / N_UNITS
        self.pool = traffic.make_pool(traffic_spec, self.zoo, seed, capacity)
        agent = config.get("agent")
        self.params = (dict(np.load(BENCH / agent["params"]))
                       if agent else None)
        self._ref_jobs: dict[tuple[int, int], object] = {}

    def _arrivals(self, k: int):
        """Trace ``k`` as ``(time, zoo row as submitted, (pick, width))``."""
        times, picks, units = self.pool[k]
        return [(float(t), traffic.variant(self.zoo[p], int(w)),
                 (int(p), int(w)))
                for t, p, w in zip(times, picks, units)]

    # ------------------------------------------------------------ program

    def program_pool(self):
        """The pool as the program's ``Arrival`` lists; one ``JobProfile``
        object per zoo entry and requested width, shared by its arrivals."""
        from repro.core.profiles import JobProfile
        from repro.online.simulator import Arrival

        keys = ("name", "arch", "shape", "steps", "flops_total",
                "bytes_total", "coll_bytes_chip_pod", "n_coll_step",
                "serial_s")
        profs: dict[tuple[int, int], JobProfile] = {}

        def profile(row, key):
            if key not in profs:
                profs[key] = JobProfile(**{k: row[k] for k in keys},
                                        meta=dict(row["meta"]))
            return profs[key]

        return [[Arrival(t=t, binary=traffic.binary(row),
                         profile=profile(row, key))
                 for t, row, key in self._arrivals(k)]
                for k in range(len(self.pool))]

    def env_config(self):
        """The agent's ``EnvConfig``: ``window``, ``c_max`` and
        ``obs_context`` (default false) of the configuration's agent."""
        from repro.core import EnvConfig

        a = self.config["agent"]
        return EnvConfig(window=a["window"], c_max=a["c_max"],
                         obs_context=a.get("obs_context", False))

    def program_policy(self):
        """The configured policy, built from the program's classes."""
        from repro.online import RLDispatchPolicy, TimeSharingPolicy

        if self.config["policy"] == "time_sharing":
            return TimeSharingPolicy()
        import jax.numpy as jnp
        from repro.core.agent import DQNAgent
        from repro.core.env import CoScheduleEnv

        env_cfg = self.env_config()
        env = CoScheduleEnv(env_cfg)
        agent = DQNAgent(env.state_dim, env.n_actions, seed=0)
        agent.params = {k: jnp.asarray(v, jnp.float32)
                        for k, v in self.params.items()}
        return RLDispatchPolicy(agent, env_cfg)

    def sim_config(self):
        from repro.online import SimConfig

        c = self.config
        return SimConfig(window=c["window"], backfill=c["backfill"],
                         pods=tuple(c["pods"]), router=c["router"])

    # ---------------------------------------------------------- reference

    def reference_trace(self, k: int):
        """Trace ``k`` as the reference's ``(t, binary, Job)`` triples; one
        ``Job`` per zoo entry and requested width."""
        out = []
        for t, row, key in self._arrivals(k):
            if key not in self._ref_jobs:
                self._ref_jobs[key] = jobs_from_snapshot([row])[0]
            out.append((t, traffic.binary(row), self._ref_jobs[key]))
        return out

    def reference(self, clock: str, operands: str, tie_tol: float):
        """The reference fleet; an RL policy plans with the configuration's
        agent ``planner`` module, or :class:`RLPlanner` where none is
        named."""
        c = self.config
        planner = None
        if c["policy"] == "rl":
            a = c["agent"]
            cls = load_planner(a["planner"]) if "planner" in a else RLPlanner
            planner = cls(ReferenceAgent(self.params, OPERANDS[operands]),
                          a["window"], a["c_max"], tie_tol)
        return ReferenceFleet(tuple(c["pods"]), c["window"], planner,
                              CLOCKS[clock])


def plain(res) -> dict:
    """A program ``SimResult`` as the comparison's plain dict."""
    segs = [[] for _ in res.pods]
    for s in res.timeline:
        segs[s.pod].append((s.t0, s.t1, s.jobs, s.partition,
                            tuple(tuple(r) for r in s.slices), s.backfilled))
    for p in segs:
        p.sort(key=lambda s: s[0])
    return {"records": [{"name": r.name, "binary": r.binary, "pod": r.pod,
                         "dispatch": r.dispatch, "finish": r.finish,
                         "group_size": r.group_size,
                         "partition": r.partition, "units": r.units,
                         "backfilled": r.backfilled} for r in res.jobs],
            "dispatches": res.dispatches, "backfills": res.backfills,
            "refits": res.refits, "busy_time": res.busy_time,
            "segments": segs}


def follow_keys(result: dict) -> list[tuple]:
    """Per record: group size, partition, slice width, group, co-run time,
    dispatch and backfill flag — what resolves the agent's near-ties in
    the reference."""
    recs = result["records"]
    return [(r["group_size"], r["partition"], r["units"], g,
             r["finish"] - r["dispatch"], r["dispatch"], r["backfilled"])
            for r, g in zip(recs, group_ids(recs))]


def judge(inputs: Inputs, served: dict[int, dict], check: dict) -> dict:
    """Run the reference over each served trace of ``served`` (pool index
    to plain program result) and compare.  Returns the compared numbers,
    each the worst over the traces, with examples of what differed."""
    worst = {"decisions_differing": 0, "clock_gap": 0.0}
    rl = inputs.config["policy"] == "rl"
    if rl:
        worst["tie_gap"] = 0.0
    examples: list[str] = []
    for k, prog in sorted(served.items()):
        ref = inputs.reference("float64", "float32", check["tie_tol"])
        trace = inputs.reference_trace(k)
        follow = (follow_keys(prog) if len(prog["records"]) == len(trace)
                  else None)
        out = ref.run(trace, follow=follow)
        cmp = compare(prog, out)
        worst["decisions_differing"] += cmp["decisions_differing"]
        worst["clock_gap"] = max(worst["clock_gap"], cmp["clock_gap"])
        if rl:
            worst["tie_gap"] = max(worst["tie_gap"], out["tie_gap"])
        examples += [f"trace {k}: {e}" for e in cmp["examples"]]
    return {"numbers": worst, "examples": examples[:5]}


def unserved(prog: dict) -> int:
    return sum(1 for r in prog["records"]
               if not (np.isfinite(r["dispatch"]) and np.isfinite(r["finish"])
                       and r["dispatch"] <= r["finish"]))


def load_json(path: Path) -> dict:
    return json.loads(path.read_text())

#!/usr/bin/env python3
"""Bring-up run of the co-scheduler's device programs on one TPU chip.

    python chip_smoke.py

One process, six phases in order, each through the library entry points
the benchmarks use:

1. device       — refuse to run anywhere but a TPU;
2. train        — ``train_agent`` at the paper's Table VI widths
                  (512/256/128 hidden, 100k replay ring, 16 envs);
3. fleet        — the trained agent serving a 10^4-arrival Poisson trace on
                  a hash-routed (8,8,8,8) fleet, then time sharing at
                  10^5 arrivals (``VectorizedFleetSimulator``);
4. reference    — the heap ``ClusterSimulator`` on the 10^4 trace (time
                  sharing) and a 2,000-arrival RL trace, compared decision
                  by decision with the vectorized engine;
5. sweeps       — 64-trace RL ``sweep`` and a 4-agent population sweep;
6. train_online — two rounds of sim-in-the-loop training, population 2.

Each phase prints one line with its compile seconds (JAX's own trace,
lowering and backend-compile events, persistent-cache loads included), the
rest of its wall time, and its persistent compile-cache hits and misses.
These are bring-up timings of one run, not benchmark results.  Any failed
check makes the run exit non-zero; only a run where every check passed
prints, as its last line, ``{"ok": true, "device": {...}}``.
"""
from __future__ import annotations

import contextlib
import json
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

import jax  # noqa: E402
import numpy as np  # noqa: E402

from repro.compile_cache import enable_compile_cache  # noqa: E402
from repro.core import (  # noqa: E402
    CoScheduleEnv, DQNAgent, EnvConfig, TrainConfig, make_zoo, train_agent,
)
from repro.core.partition import N_UNITS  # noqa: E402
from repro.core.train import TrainOnlineConfig, train_online  # noqa: E402
from repro.online import (  # noqa: E402
    ClusterSimulator, RLDispatchPolicy, SimConfig, TRACE_FAMILIES,
    TimeSharingPolicy, VectorizedClusterSimulator, VectorizedFleetSimulator,
)
from repro.online.simulator import decision_diffs  # noqa: E402
from repro.online.vecsim import hash_split_max  # noqa: E402

PODS = (8, 8, 8, 8)
LOAD = 0.85                 # the fleet_scale benchmark's load
N_RL, N_TS, N_REF_RL = 10_000, 100_000, 2_000
SWEEP_BATCH, SWEEP_N, POPULATION = 64, 120, 4

_COMPILE_EVENTS = frozenset({
    "/jax/core/compile/jaxpr_trace_duration",
    "/jax/core/compile/jaxpr_to_mlir_module_duration",
    "/jax/core/compile/backend_compile_duration",
})
_CACHE_HIT = "/jax/compilation_cache/cache_hits"
_CACHE_MISS = "/jax/compilation_cache/cache_misses"


def _union_s(spans) -> float:
    """Seconds covered by ``spans``: a jit traced inside another's trace
    reports a span nested in the outer one, counted once."""
    total, reach = 0.0, float("-inf")
    for s, e in sorted(spans):
        if e > reach:
            total += e - max(s, reach)
            reach = e
    return total


class Run:
    """Failed checks, plus the compile spans and persistent-cache lookups
    JAX reports through ``jax.monitoring`` while the run is live."""

    def __init__(self):
        self.failures: list[str] = []
        self.spans: list[tuple[float, float]] = []
        self.cache = {_CACHE_HIT: 0, _CACHE_MISS: 0}
        jax.monitoring.register_event_time_span_listener(self._on_span)
        jax.monitoring.register_event_listener(self._on_event)

    def _on_span(self, event: str, start: float, end: float, **_) -> None:
        if event in _COMPILE_EVENTS:
            self.spans.append((start, end))

    def _on_event(self, event: str, **_) -> None:
        if event in self.cache:
            self.cache[event] += 1

    @contextlib.contextmanager
    def phase(self, name: str):
        info: dict = {}
        n0, t0 = len(self.spans), time.perf_counter()
        hits0, miss0 = self.cache[_CACHE_HIT], self.cache[_CACHE_MISS]
        yield info
        wall = time.perf_counter() - t0
        comp = _union_s(self.spans[n0:])
        extra = " ".join(f"{k}={v}" for k, v in info.items())
        print(f"phase {name}: compile_s={comp:.3f} "
              f"steady_s={wall - comp:.3f} "
              f"cache_hits={self.cache[_CACHE_HIT] - hits0} "
              f"cache_misses={self.cache[_CACHE_MISS] - miss0} {extra}",
              flush=True)

    def check(self, ok: bool, what: str) -> None:
        if not ok:
            self.failures.append(what)
            print(f"FAIL {what}", flush=True)

    def check_served(self, res, n: int, what: str) -> None:
        """Every arrival served once, with arrival <= dispatch <= finish on
        the engine's float32 clock (the device lanes hold f32 arrival
        times).  A run whose device lanes flagged an error never gets here:
        the engine raises on any nonzero ``err``."""
        self.check(len(res.jobs) == n,
                   f"{what}: {len(res.jobs)} records for {n}")
        arr = np.float32([j.arrival for j in res.jobs])
        disp = np.float32([j.dispatch for j in res.jobs])
        fin = np.float32([j.finish for j in res.jobs])
        self.check(bool(np.isfinite(disp).all() and np.isfinite(fin).all()),
                   f"{what}: unserved arrivals")
        self.check(bool((arr <= disp).all() and (disp <= fin).all()),
                   f"{what}: arrival <= dispatch <= finish violated")


def finite_tree(tree) -> bool:
    return all(bool(np.isfinite(np.asarray(x)).all())
               for x in jax.tree.leaves(tree))


def main() -> int:
    cache_dir = enable_compile_cache()
    run = Run()
    check, phase = run.check, run.phase

    with phase("device") as info:
        devs = jax.devices()
        dev = devs[0]
        info.update(platform=dev.platform, kind=repr(dev.device_kind),
                    count=len(devs), jax=jax.__version__, cache=cache_dir)
    if dev.platform != "tpu":
        print(f"no TPU: JAX found {dev.platform}; not running on it",
              file=sys.stderr)
        return 1

    zoo = make_zoo()
    env_cfg = EnvConfig(window=8, c_max=4)
    with phase("train") as info:
        agent, hist = train_agent(zoo, env_cfg,
                                  TrainConfig(episodes=400, seed=0))
        tp = hist[-1]["eval_throughput"]
        info.update(episodes=hist[-1]["episode"], eval_throughput=tp,
                    env_steps=agent.env_steps, updates=agent.updates)
        check(finite_tree(agent.params), "train: non-finite params")
        check(0.0 < tp <= 2.0, f"train: eval throughput {tp} not in (0, 2]")

    cfg = SimConfig(window=8, pods=PODS, router="hash")
    fleet_cap = sum(PODS) / N_UNITS
    trace_rl = TRACE_FAMILIES["poisson"](zoo, n=N_RL, load=LOAD, seed=0,
                                         capacity=fleet_cap)
    trace_ts = TRACE_FAMILIES["poisson"](zoo, n=N_TS, load=LOAD, seed=0,
                                         capacity=fleet_cap)
    # one engine per program, sized for its largest trace, so the smaller
    # reference traces of phase 4 reuse the compiled program
    vec_rl = VectorizedFleetSimulator(
        RLDispatchPolicy(agent, env_cfg), cfg,
        capacity=hash_split_max(trace_rl, PODS))
    vec_ts = VectorizedFleetSimulator(
        TimeSharingPolicy(), cfg, capacity=hash_split_max(trace_ts, PODS))
    with phase("fleet") as info:
        res_rl = vec_rl.run(trace_rl)
        run.check_served(res_rl, N_RL, "fleet rl")
        res_ts = vec_ts.run(trace_ts)
        run.check_served(res_ts, N_TS, "fleet time_sharing")
        info.update(rl_lane_capacity=vec_rl.capacity,
                    ts_lane_capacity=vec_ts.capacity,
                    rl_p99_wait_s=res_rl.p99_wait,
                    ts_p99_wait_s=res_ts.p99_wait)

    with phase("reference") as info:
        ts_trace = trace_rl            # the same 10^4 trace, time sharing
        ts_diffs = decision_diffs(
            ClusterSimulator(TimeSharingPolicy(), cfg).run(ts_trace),
            vec_ts.run(ts_trace))
        ref_trace = TRACE_FAMILIES["poisson"](zoo, n=N_REF_RL, load=LOAD,
                                              seed=1, capacity=fleet_cap)
        vec_ref = vec_rl.run(ref_trace)
        run.check_served(vec_ref, N_REF_RL, "reference rl")
        rl_diffs = decision_diffs(
            ClusterSimulator(RLDispatchPolicy(agent, env_cfg),
                             cfg).run(ref_trace),
            vec_ref)
        info.update(ts_differing=len(ts_diffs), rl_differing=len(rl_diffs))
        for name, diffs in (("time_sharing", ts_diffs), ("rl", rl_diffs)):
            for d in diffs[:5]:
                print(f"  {name} differs: {d}", flush=True)
            check(not diffs, f"reference: {len(diffs)} {name} decisions "
                             f"differ from the heap")

    with phase("sweeps") as info:
        traces = [TRACE_FAMILIES["poisson"](zoo, n=SWEEP_N, load=1.25,
                                            seed=s)
                  for s in range(SWEEP_BATCH)]
        vec = VectorizedClusterSimulator(RLDispatchPolicy(agent, env_cfg),
                                         window=8, capacity=128)
        summ = vec.sweep(traces)
        env = CoScheduleEnv(env_cfg)
        pop = [agent.params] + [
            DQNAgent(env.state_dim, env.n_actions, seed=1 + k).params
            for k in range(POPULATION - 1)]
        psumm = vec.sweep(traces, param_sets=pop)
        mk, pmk = np.asarray(summ.makespan), np.asarray(psumm.makespan)
        check(pmk.shape == (POPULATION, SWEEP_BATCH),
              f"sweeps: population lanes {pmk.shape}")
        check(bool(np.isfinite(pmk).all() and (mk > 0).all()),
              "sweeps: non-finite or empty makespans")
        check(bool(np.array_equal(np.asarray(summ.dispatches),
                                  np.asarray(psumm.dispatches)[0])
                   and np.allclose(mk, pmk[0], rtol=1e-5)),
              "sweeps: population row 0 differs from the plain sweep")
        info.update(mean_makespan_s=float(mk.mean()),
                    population_mean_makespan_s=[float(x)
                                                for x in pmk.mean(axis=1)])

    with phase("train_online") as info:
        online, ohist = train_online(
            zoo, env_cfg, TrainOnlineConfig(rounds=2, population=2, seed=0),
            warm_start=agent)
        check(finite_tree(online.params), "train_online: non-finite params")
        check(len(ohist) == 2, f"train_online: {len(ohist)} rounds")
        info.update(transitions=ohist[-1]["transitions"],
                    selected=ohist[-1]["selected"])

    if run.failures:
        print(f"{len(run.failures)} check(s) failed", file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devs)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())

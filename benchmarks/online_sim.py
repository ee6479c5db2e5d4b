"""Online cluster benchmark: policies under multi-tenant arrival traces.

Serves identical arrival traces (Poisson / bursty MMPP / diurnal /
heavy-tailed job scales / fragmentation-stressing right-sized widths)
through the event-driven cluster simulator with each dispatch policy, and
writes ``BENCH_online.json`` — the online-phase trajectory future PRs
regress against.  The headline figures are makespan-derived throughput
ratios vs the time-sharing baseline (the paper's Fig. 8 metric, streamed:
up to 1.87x in the paper's queues); the RL policy runs twice, once frozen
and once with MISO-style periodic re-training against the live profile
repository.

Every trace family is additionally served under both dispatch modes —
slice-level **concurrent + backfill** (the default) vs the PR-3
**blocking-window** pod — with the same frozen policies, and the
``concurrent_vs_blocking`` throughput ratios land in the
``dispatch_comparison`` section: 1.0 on full-pod-only families (the modes
are bit-compatible there) and strictly above 1.0 on the fragmented family,
where right-sized jobs pack disjoint slices and small groups backfill idle
gaps.

The ``arrival_aware`` section is the observation-mode comparison: a
**context-trained** agent (profiles + live cluster state — busy-unit mask,
queue ages, pending depth; see ``docs/observation.md``) vs the
**profile-only** agent vs time sharing, frozen, on every trace family.
The context agent is warm-started from the profile-only agent through
``widen_dqn_params`` (identical Q-function at zero context), so the
comparison isolates what the arrival-aware features add; the fragmented
family is the headline — the agent should recover dispatch-layer packing
gains from state alone.  ``benchmarks.bench_gate`` pins the committed
``rl_context_vs_profile_only`` ratio there.

The ``vectorized_sim`` section is the engine comparison: the in-graph
vectorized simulator (``repro.online.vecsim``, one jitted
``lax.while_loop`` per trace, ``vmap`` over a leading trace axis) vs the
Python event heap on identical solo-placement traces — single-trace wall
time both ways plus vmapped-sweep throughput (traces/sec at batch >= 64),
whose ``speedup_vs_heap`` is floored by ``benchmarks.bench_gate``.
``vectorized_rl`` is the same comparison for **RL serving**: the trained
agent's episodes run in-graph at the window-formation seam (observation
assembly + fit-masked greedy argmax inside the jitted episode) vs the
heap replaying the identical agent, plus the ``sweep(param_sets=...)``
population mode — P agents x batch traces in one device call.  The
``sim_wall`` block mirrors every policy×family cell's ``sim_wall_s`` so
the Python-vs-vectorized trend stays visible in the committed trajectory,
and ``--engine vectorized`` routes supported cells (solo-placement
policies, concurrent mode, no retrainer) through the vectorized engine —
each cell records which ``engine`` served it.

    PYTHONPATH=src python -m benchmarks.online_sim [--fast] [--profile] \
        [--out BENCH_online.json] [--engine {heap,vectorized}]
    PYTHONPATH=src python -m benchmarks.online_sim --section arrival_aware

``--profile`` records a per-phase wall-time breakdown in every heap cell
(``profile``: sim / policy / retrain seconds, plus per-family
``trace_gen_s``) so future perf PRs have a phase-level baseline.  The
``retrain_trigger`` section is the clock-vs-drift re-training A/B
(``OnlineRetrainer(trigger="drift")`` gated by the telemetry layer's
``DriftMonitor``); ``telemetry_overhead`` records the telemetry-on/off
sim-wall ratio for both engines, gated at ``TELEMETRY_OVERHEAD_MAX`` by
``benchmarks.bench_gate``.  In smoke mode ``--telemetry-artifacts DIR``
additionally serves one telemetry-enabled fleet cell and writes its
Chrome trace + events/metrics JSONL there for CI artifact upload,
cross-checking the metric aggregates against ``summary()``.

The ``queueing_reward`` section is the reward-source A/B: ``train_online``
(sim-in-the-loop training inside the vectorized engine — reward is the
engine-accumulated per-window wait/turnaround plus a makespan terminal,
with population-based training over scenario x exploration) refines the
committed proxy-trained agent, and both serve identical held-out traces
of every family; the gate requires the queueing-trained agent's p99 wait
to win on at least ``QUEUEING_WIN_FAMILIES_MIN`` of the five families.

``--section <name>`` recomputes only that section (for ``arrival_aware``,
re-training both agents deterministically from the committed run's
settings; ``vectorized_sim`` re-measures both engines; ``sim_wall``
derives from the committed ``traces`` cells) and merges it into the
committed ``BENCH_online.json`` — the incremental path for
observation-layer and engine changes.

``--smoke`` is the CI guard (< 60 s): a tiny agent, short traces, RL with
re-training vs time sharing, plus the dispatch-mode comparison and a
context-agent serve check; fails (exit 1) if the RL policy's throughput
drops below ``--ratio-floor`` x time sharing on the Poisson trace, if
concurrent dispatch falls below blocking on any smoke family, if it fails
to *beat* blocking by ``--frag-margin`` on the fragmented family, if the
context-trained agent cannot serve the fragmented smoke trace, or if the
committed ``BENCH_online.json`` is missing required keys.  Smoke mode does
not overwrite the committed trajectory unless ``--out`` is given.
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import statistics
import sys
import time

from benchmarks.bench_gate import (
    ARRIVAL_FLOOR, CONC_BLK_FLOOR, FLEET_P99_FLOOR, FRAG_MARGIN,
    QUEUEING_WIN_FAMILIES_MIN, TELEMETRY_OVERHEAD_MAX, VECRL_SPEEDUP_FLOOR,
    VECSIM_SPEEDUP_FLOOR,
)
from benchmarks.common import emit, missing_keys
from repro import spans
from repro.compile_cache import enable_compile_cache
from repro.core import (
    CoScheduleEnv, DQNAgent, EnvConfig, TrainConfig, make_zoo, train_agent,
    widen_dqn_params,
)
from repro.core.agent import DQNConfig
from repro.core.env import context_dim
from repro.core.partition import N_UNITS
from repro.online import (
    ClusterSimulator, GreedyPackerPolicy, OnlineRetrainer, RLDispatchPolicy,
    SimConfig, StaticPartitionPolicy, TRACE_FAMILIES, Telemetry,
    TimeSharingPolicy, VectorizedClusterSimulator, VectorizedFleetSimulator,
    default_retrain_train_config,
)
from repro.online.vecsim import hash_split_max

REQUIRED_KEYS = ("window", "n_arrivals", "traces", "rl_vs_time_sharing",
                 "dispatch_comparison", "arrival_aware", "sim_wall",
                 "vectorized_sim", "vectorized_rl", "fleet_scale",
                 "queueing_reward", "note")

# fleet-scale grid: trace family -> pod widths (heterogeneous 4/8 fleets
# stress width eligibility and the frag router; uniform 8s isolate pure
# load balancing).  Arrival rates are capacity-scaled so `load` keeps its
# single-pod meaning across fleet shapes.
FLEET_FAMILIES = {"poisson": (8, 8, 8, 8), "fragmented": (8, 8, 4, 4)}
FLEET_ROUTERS = ("hash", "least_loaded", "frag")
FLEET_LOAD = 0.85

FLEET_NOTE = (
    "routers x {time_sharing, rl(frozen profile-only agent)} on capacity-"
    "scaled traces (load keeps its single-pod meaning: 1.0 saturates the "
    "whole fleet); headline metric is p50/p99 wait — tail latency, not "
    "makespan, is what routing moves at fleet scale; *_vs_hash_p99 > 1 "
    "means the router beats tenant-affine hashing (hash is lumpy over a "
    "small tenant pool, so load-aware routers win big at high load); "
    "vectorized_100k serves 10^5 arrivals through the vmapped pod-axis "
    "engine (hash routing is trace-computable, so the fleet splits into "
    "independent per-pod lanes); single_pod_parity re-runs each committed "
    "traces family under SimConfig(pods=(8,)) and requires key-by-key "
    "exact equality with the committed single-pod cells — the fleet "
    "refactor must not move the legacy numbers")


ARRIVAL_NOTE = (
    "frozen-agent observation-mode comparison on identical traces: "
    "rl_context observes profiles + live cluster state (busy-unit mask, "
    "queue ages, pending depth — docs/observation.md) and was warm-started "
    "from rl_profile_only via widen_dqn_params (identical Q at zero "
    "context) then trained with per-episode sampled contexts and the "
    "fit-shaping term; ratios are makespan-derived throughput as "
    "everywhere else; ctx_seed seeds only the refresh's context draws and "
    "exploration (the warm start pins the starting Q-function); the "
    "fragmented family is gated by benchmarks.bench_gate "
    "(rl_context >= ARRIVAL_FLOOR x rl_profile_only)")


def _simulate(policy, trace, window, retrainer=None, mode="concurrent",
              engine="heap", profile=False):
    # the vectorized engine serves solo-placement plans in concurrent mode
    # with no periodic tick; everything else stays on the Python heap
    use_vec = (engine == "vectorized" and retrainer is None
               and mode == "concurrent"
               and VectorizedClusterSimulator.supports(policy))
    # --profile: the span recorder splits each heap cell's sim_wall_s into
    # sim / policy (repro.policy.decide) / retrain (repro.sim.tick) phases
    # (heap cells only; the vectorized engine's policy work is compiled
    # into the graph)
    profiling = profile and not use_vec
    if profiling:
        spans.reset()
        spans.enable()
    t0 = time.perf_counter()
    try:
        if use_vec:
            res = VectorizedClusterSimulator(
                policy, window=window,
                capacity=max(128, 2 * len(trace))).run(trace)
        else:
            sim = ClusterSimulator(
                policy, window=window, mode=mode,
                tick_interval_s=retrainer.interval_s if retrainer else None,
                on_tick=retrainer)
            res = sim.run(trace)
    finally:
        if profiling:
            spans.disable()
    out = res.summary()
    out["sim_wall_s"] = time.perf_counter() - t0
    out["engine"] = "vectorized" if use_vec else "heap"
    if profiling:
        totals = spans.summary()
        phases = {k: round(totals.get(name, {}).get("total_s", 0.0), 6)
                  for k, name in (("policy_s", "repro.policy.decide"),
                                  ("retrain_s", "repro.sim.tick"))}
        phases["sim_s"] = max(
            0.0, out["sim_wall_s"] - phases["policy_s"] - phases["retrain_s"])
        out["profile"] = phases
        spans.reset()
    if retrainer is not None:
        out["retrains"] = len(retrainer.history)
        out["retrain_history"] = retrainer.history
    return out


def _sim_wall_block(traces: dict) -> dict:
    """Per policy×family ``sim_wall_s`` lifted out of the traces section."""
    return {fam: {pol: cell["sim_wall_s"]
                  for pol, cell in fam_out.items()
                  if isinstance(cell, dict) and "sim_wall_s" in cell}
            for fam, fam_out in traces.items()}


def _fleet_cell(policy, trace, window, pods, router, seed=0):
    t0 = time.perf_counter()
    cfg = SimConfig(window=window, pods=pods, router=router,
                    router_seed=seed)
    res = ClusterSimulator(policy, cfg).run(trace)
    out = res.summary()
    out["sim_wall_s"] = time.perf_counter() - t0
    out["engine"] = "heap"
    return out


def _fleet_scale(zoo, agent, env_cfg, window, n, seed,
                 load=FLEET_LOAD, n_vec=100_000):
    """The fleet-scale grid: routers x policies per family, the 10^5
    vectorized cell, and per-family p99 ratios vs hash routing."""
    families: dict = {}
    for i, (fam, pods) in enumerate(FLEET_FAMILIES.items()):
        cap = sum(pods) / N_UNITS
        trace = TRACE_FAMILIES[fam](zoo, n=n, load=load, seed=seed + i,
                                    capacity=cap)
        cells: dict = {}
        for router in FLEET_ROUTERS:
            cells[router] = {
                "time_sharing": _fleet_cell(TimeSharingPolicy(), trace,
                                            window, pods, router, seed),
                "rl": _fleet_cell(RLDispatchPolicy(agent, env_cfg), trace,
                                  window, pods, router, seed),
            }
            emit(f"fleet_{fam}_{router}",
                 cells[router]["rl"]["sim_wall_s"] * 1e6 / n,
                 f"ts_p99={cells[router]['time_sharing']['p99_wait_s']:.0f}s")
        ratios = {
            f"{r}_vs_hash_p99": {
                pol: (cells["hash"][pol]["p99_wait_s"]
                      / max(cells[r][pol]["p99_wait_s"], 1e-9))
                for pol in ("time_sharing", "rl")}
            for r in FLEET_ROUTERS if r != "hash"}
        families[fam] = {"pods": list(pods), "cells": cells,
                         "ratios": ratios}
    vec_cell = None
    if n_vec:
        pods = FLEET_FAMILIES["poisson"]
        cap = sum(pods) / N_UNITS
        trace = TRACE_FAMILIES["poisson"](zoo, n=n_vec, load=load,
                                          seed=seed, capacity=cap)
        capacity = int(1.02 * hash_split_max(trace, pods, seed)) + 8
        t0 = time.perf_counter()
        vec = VectorizedFleetSimulator(
            TimeSharingPolicy(),
            SimConfig(window=window, pods=pods, router="hash",
                      router_seed=seed),
            capacity=capacity)
        vec_cell = vec.run(trace).summary()
        vec_cell["sim_wall_s"] = time.perf_counter() - t0
        vec_cell["engine"] = "vectorized"
        vec_cell["n_arrivals"] = n_vec
        vec_cell["family"] = "poisson"
        vec_cell["lane_capacity"] = capacity
        emit("fleet_vectorized_100k", vec_cell["sim_wall_s"] * 1e6 / n_vec,
             f"p99={vec_cell['p99_wait_s']:.0f}s")
    return {
        "n_arrivals": n, "load": load, "seed": seed, "window": window,
        "routers": list(FLEET_ROUTERS),
        "families": families,
        "vectorized_100k": vec_cell,
        "note": FLEET_NOTE,
    }


def _single_pod_parity(zoo, bench) -> dict:
    """Re-run each committed traces family on a ``pods=(8,)`` fleet and
    require exact key-by-key equality with the committed single-pod
    ``time_sharing`` cells (floats through JSON round-trip exactly)."""
    out: dict = {}
    n, load = bench["n_arrivals"], bench["load"]
    seed, window = bench["seed"], bench["window"]
    skip = {"sim_wall_s", "engine", "schema", "n_pods", "pods", "router",
            "refits", "p50_wait_s", "p99_wait_s"}
    for i, fam in enumerate(bench["traces"]):
        cell = bench["traces"][fam].get("time_sharing")
        if not isinstance(cell, dict):
            continue
        trace = TRACE_FAMILIES[fam](zoo, n=n, load=load, seed=seed + i)
        fresh = ClusterSimulator(
            TimeSharingPolicy(),
            SimConfig(window=window, pods=(N_UNITS,))).run(trace).summary()
        keys = [k for k in cell if k not in skip]
        out[fam] = all(fresh.get(k) == cell[k] for k in keys)
    return out


def _vectorized_sim(zoo, window, n, load, seed, batch=64, capacity=128):
    """Engine comparison: heap vs vectorized, single trace + vmapped sweep.

    Same solo-placement workload both ways (time sharing, concurrent mode,
    ``batch`` seed-varied Poisson traces).  The heap's traces/sec comes
    from serving the first few traces one at a time; the vectorized
    engine's from one warm vmapped ``sweep`` call over the whole batch
    (compile time reported separately — it amortizes across sweeps).
    """
    traces = [TRACE_FAMILIES["poisson"](zoo, n=n, load=load, seed=seed + i)
              for i in range(batch)]
    n_heap = min(8, batch)
    t0 = time.perf_counter()
    heap_res = [ClusterSimulator(TimeSharingPolicy(), window=window).run(tr)
                for tr in traces[:n_heap]]
    heap_per_trace = (time.perf_counter() - t0) / n_heap
    vec = VectorizedClusterSimulator(TimeSharingPolicy(), window=window,
                                     capacity=capacity)
    t0 = time.perf_counter()
    vec_res = vec.run(traces[0])
    vec_compile_s = time.perf_counter() - t0
    t0 = time.perf_counter()
    vec.run(traces[0])
    vec_per_trace = time.perf_counter() - t0
    t0 = time.perf_counter()
    vec.sweep(traces)
    sweep_compile_s = time.perf_counter() - t0
    t0 = time.perf_counter()
    summ = vec.sweep(traces)
    sweep_wall = time.perf_counter() - t0
    traces_per_s = batch / sweep_wall
    heap_traces_per_s = 1.0 / heap_per_trace
    # parity spot check rides along so the committed numbers carry proof
    # the two engines measured the same system
    h0, v0 = heap_res[0], vec_res
    section = {
        "family": "poisson", "window": window, "n_arrivals": n,
        "load": load, "seed": seed, "capacity": capacity,
        "single_trace": {
            "heap_wall_s": heap_per_trace,
            "vectorized_wall_s": vec_per_trace,
            "vectorized_compile_s": vec_compile_s,
        },
        "sweep": {
            "batch": batch,
            "wall_s": sweep_wall,
            "compile_s": sweep_compile_s,
            "traces_per_s": traces_per_s,
            "heap_traces_per_s": heap_traces_per_s,
            "speedup_vs_heap": traces_per_s / heap_traces_per_s,
        },
        "parity": {
            "heap_makespan_s": h0.makespan,
            "vectorized_makespan_s": v0.makespan,
            "heap_p99_wait_s": h0.p99_wait,
            "vectorized_p99_wait_s": v0.p99_wait,
            "sweep_mean_makespan_s": float(summ.makespan.mean()),
        },
        "note": ("heap_traces_per_s serves traces one at a time on the "
                 "Python event heap; traces_per_s is one warm vmapped "
                 "sweep call over the whole batch (compile_s amortizes "
                 "across sweeps and is excluded, matching how the engine "
                 "is used for fleet-scale evaluation); speedup_vs_heap is "
                 "their ratio, floored by benchmarks.bench_gate; parity "
                 "keys show both engines measured the same system "
                 "(decision-level equality is asserted in "
                 "tests/test_vecsim.py)"),
    }
    emit("vectorized_sim", sweep_wall * 1e6 / batch,
         f"speedup={section['sweep']['speedup_vs_heap']:.2f}x")
    return section


def _vectorized_rl(zoo, agent, env_cfg, window, n, load, seed,
                   batch=64, capacity=128, population=4):
    """Engine comparison for RL serving: in-graph agent episodes vs heap.

    The same trained agent both ways.  The heap replays it through
    :class:`RLDispatchPolicy` one trace at a time (a fresh policy per
    trace: the profile repository fills as jobs complete, and the
    vectorized engine's profiled lane also starts empty every run, so
    fresh-per-trace is the matched condition); the vectorized engine
    runs the DQN forward pass at the window-formation seam *inside* the
    jitted episode and sweeps the whole batch in one vmapped call.
    ``population`` extra param sets ride ``sweep(param_sets=...)``'s
    leading axis — one device call evaluates P agents x batch traces,
    the population-evaluation mode the axis exists for.
    """
    traces = [TRACE_FAMILIES["poisson"](zoo, n=n, load=load, seed=seed + i)
              for i in range(batch)]
    n_heap = min(8, batch)
    t0 = time.perf_counter()
    heap_res = [ClusterSimulator(RLDispatchPolicy(agent, env_cfg),
                                 window=window).run(tr)
                for tr in traces[:n_heap]]
    heap_per_trace = (time.perf_counter() - t0) / n_heap
    vec = VectorizedClusterSimulator(RLDispatchPolicy(agent, env_cfg),
                                     window=window, capacity=capacity)
    t0 = time.perf_counter()
    vec_res = vec.run(traces[0])
    vec_compile_s = time.perf_counter() - t0
    t0 = time.perf_counter()
    vec.run(traces[0])
    vec_per_trace = time.perf_counter() - t0
    t0 = time.perf_counter()
    vec.sweep(traces)
    sweep_compile_s = time.perf_counter() - t0
    t0 = time.perf_counter()
    summ = vec.sweep(traces)
    sweep_wall = time.perf_counter() - t0
    traces_per_s = batch / sweep_wall
    heap_traces_per_s = 1.0 / heap_per_trace
    # population axis: the trained params plus seed-varied random inits
    env = CoScheduleEnv(env_cfg)
    param_sets = [agent.params] + [
        DQNAgent(env.state_dim, env.n_actions, seed=seed + 1 + k).params
        for k in range(population - 1)]
    t0 = time.perf_counter()
    vec.sweep(traces, param_sets=param_sets)
    pop_compile_s = time.perf_counter() - t0
    t0 = time.perf_counter()
    psumm = vec.sweep(traces, param_sets=param_sets)
    pop_wall = time.perf_counter() - t0
    h0, v0 = heap_res[0], vec_res
    section = {
        "family": "poisson", "window": window, "n_arrivals": n,
        "load": load, "seed": seed, "capacity": capacity,
        "single_trace": {
            "heap_wall_s": heap_per_trace,
            "vectorized_wall_s": vec_per_trace,
            "vectorized_compile_s": vec_compile_s,
        },
        "sweep": {
            "batch": batch,
            "wall_s": sweep_wall,
            "compile_s": sweep_compile_s,
            "traces_per_s": traces_per_s,
            "heap_traces_per_s": heap_traces_per_s,
            "speedup_vs_heap": traces_per_s / heap_traces_per_s,
        },
        "population": {
            "params_sets": len(param_sets),
            "wall_s": pop_wall,
            "compile_s": pop_compile_s,
            "episodes_per_s": len(param_sets) * batch / pop_wall,
            "mean_makespan_s_per_params": [
                float(m) for m in psumm.makespan.mean(axis=1)],
        },
        "parity": {
            "heap_makespan_s": h0.makespan,
            "vectorized_makespan_s": v0.makespan,
            "heap_p99_wait_s": h0.p99_wait,
            "vectorized_p99_wait_s": v0.p99_wait,
            "sweep_mean_makespan_s": float(summ.makespan.mean()),
        },
        "note": ("heap_traces_per_s replays the trained agent through "
                 "RLDispatchPolicy on the Python event heap one trace at "
                 "a time (fresh policy per trace: both engines start with "
                 "an empty profile repository); traces_per_s is one warm "
                 "vmapped sweep call with the DQN forward pass running "
                 "in-graph at the window-formation seam (compile_s "
                 "amortizes and is excluded); speedup_vs_heap is their "
                 "ratio, floored by benchmarks.bench_gate; population is "
                 "the sweep(param_sets=...) mode — params_sets x batch "
                 "agent episodes in ONE device call (row 0 is the trained "
                 "agent, the rest seed-varied random inits); decision-"
                 "level RL parity is asserted in tests/test_parity_fuzz.py"),
    }
    emit("vectorized_rl", sweep_wall * 1e6 / batch,
         f"speedup={section['sweep']['speedup_vs_heap']:.2f}x "
         f"pop={len(param_sets)}x{batch}")
    return section


def _retrain_trigger(zoo, agent, env_cfg, window, n, load, seed,
                     interval_min, retrain_episodes):
    """Clock vs drift re-training A/B on a drift-prone trace.

    The MMPP family's regime switches move the arrival mix over time —
    exactly what the :class:`~repro.online.telemetry.DriftMonitor` watches
    (class/width-mix entropy, idle-fraction rise).  Both arms serve the
    identical trace with the same frozen starting agent and the same tick
    cadence; the clock arm retrains every tick, the drift arm only on a
    drift verdict.  The committed cell records throughput and retrain
    counts — the gate (``benchmarks.bench_gate``) requires drift to hold
    throughput within ``DRIFT_RETRAIN_FLOOR`` of clock while never
    retraining more often.
    """
    trace = TRACE_FAMILIES["mmpp"](zoo, n=n, load=load, seed=seed)
    out: dict = {"family": "mmpp", "n_arrivals": n, "load": load,
                 "seed": seed, "interval_min": interval_min,
                 "retrain_episodes": retrain_episodes}
    for trig in ("clock", "drift"):
        pol = RLDispatchPolicy(agent, env_cfg)
        rt = OnlineRetrainer(
            policy=pol, train_cfg=default_retrain_train_config(
                retrain_episodes),
            interval_s=interval_min * 60.0, min_jobs=4, trigger=trig)
        cell = _simulate(pol, trace, window, retrainer=rt)
        if trig == "drift":
            cell["drift_observations"] = len(rt.monitor.history)
            cell["drift_verdicts"] = sum(
                1 for h in rt.monitor.history if h["drift"])
        out[trig] = cell
        emit(f"retrain_trigger_{trig}", cell["sim_wall_s"] * 1e6 / n,
             f"retrains={cell['retrains']} tp={cell['throughput']:.3f}")
    out["drift_vs_clock_throughput"] = (out["drift"]["throughput"]
                                        / out["clock"]["throughput"])
    out["retrains_saved"] = (out["clock"]["retrains"]
                             - out["drift"]["retrains"])
    out["note"] = (
        "identical mmpp trace, identical frozen starting agent, identical "
        "tick cadence; clock retrains every tick with enough repository "
        "jobs, drift only when the DriftMonitor fires on the interval's "
        "class/width-mix entropy or idle-fraction shift (then rebases); "
        "drift_vs_clock_throughput near 1.0 with retrains_saved > 0 means "
        "the drift signals buy back retraining compute without giving up "
        "serving quality")
    return out


def _queueing_reward(zoo, agent, env_cfg, window, n, load, seed):
    """Sim-in-the-loop refinement A/B: queueing-trained vs proxy-trained.

    ``train_online`` rolls the job zoo as serving traces through the
    vectorized training engine and optimizes the engine-accumulated
    queueing reward (negative per-window wait/turnaround + makespan
    terminal), warm-started from the committed run's proxy-trained agent.
    Both agents — the frozen proxy incumbent and the refined result — then
    serve identical held-out traces of every family on the event heap,
    and the committed cell records per-family p99 wait both ways.  A
    family is a ``win`` when the queueing-trained agent's p99 wait is at
    or below the proxy-trained agent's; the gate
    (``benchmarks.bench_gate``) requires wins on at least
    ``QUEUEING_WIN_FAMILIES_MIN`` of the five families.  The elitism
    guard inside ``train_online`` makes the refinement safe by
    construction: a refresh that does not beat the incumbent on training
    eval returns the incumbent's weights unchanged.
    """
    from repro.core.train import TrainOnlineConfig, train_online

    # train on the serving distribution: all five families at the bench's
    # arrival count and load, so the refinement optimizes the traffic the
    # A/B serves rather than a shrunken proxy of it
    cfg = TrainOnlineConfig(
        window=min(8, window), seed=seed, n_arrivals=n,
        capacity=max(128, 2 * n),
        scenarios=tuple((fam, load) for fam in sorted(TRACE_FAMILIES)),
        eval_traces=2 * len(TRACE_FAMILIES))
    t0 = time.perf_counter()
    refined, hist = train_online(zoo, env_cfg, cfg, warm_start=agent)
    train_wall = time.perf_counter() - t0
    emit("queueing_reward_train", train_wall * 1e6 / max(1, cfg.rounds),
         f"rounds={hist[-1]['round']} sel={hist[-1]['selected']}")
    families: dict = {}
    for i, fam in enumerate(sorted(TRACE_FAMILIES)):
        trace = TRACE_FAMILIES[fam](zoo, n=n, load=load, seed=seed + 500 + i)
        px = _simulate(RLDispatchPolicy(agent, env_cfg), trace, window)
        qx = _simulate(RLDispatchPolicy(refined, env_cfg), trace, window)
        ratio = (qx["p99_wait_s"] / px["p99_wait_s"]
                 if px["p99_wait_s"] > 0.0 else 1.0)
        families[fam] = {
            "proxy_p99_wait_s": px["p99_wait_s"],
            "queueing_p99_wait_s": qx["p99_wait_s"],
            "proxy_mean_wait_s": px["mean_wait_s"],
            "queueing_mean_wait_s": qx["mean_wait_s"],
            "proxy_throughput": px["throughput"],
            "queueing_throughput": qx["throughput"],
            "queueing_vs_proxy_p99": ratio,
            "win": qx["p99_wait_s"] <= px["p99_wait_s"],
        }
        emit(f"queueing_reward_{fam}", qx["sim_wall_s"] * 1e6,
             f"q/p p99={ratio:.3f} win={families[fam]['win']}")
    wins = sum(1 for f in families.values() if f["win"])
    return {
        "n_arrivals": n, "load": load, "seed": seed,
        "train": {"rounds": hist[-1]["round"],
                  "population": cfg.population,
                  "transitions": hist[-1]["transitions"],
                  "selected": hist[-1]["selected"],
                  "train_eval_p99_wait": min(hist[-1]["final_scores"]),
                  "wall_s": train_wall},
        "families": families,
        "families_won": wins,
        "note": (
            "p99 wait of the queueing-trained agent (train_online "
            "warm-started from the committed proxy agent: PBT over "
            "scenario x exploration, reward = engine-accumulated "
            "wait/turnaround + makespan terminal) vs the frozen "
            "proxy-trained agent on identical held-out traces; win "
            "means queueing p99 <= proxy p99, and the elitism guard "
            "returns the incumbent unchanged when no trained member "
            "beats it on training eval — training on the real queueing "
            "outcome never loses to the throughput proxy"),
    }


def _telemetry_overhead(zoo, window, n, load, seed, repeats=21):
    """Telemetry-enabled vs disabled sim wall time, both engines.

    Same machine, same run, ``repeats`` alternating off/on pairs — the
    committed ``overhead_ratio`` is the median of per-pair ratios, which
    cancels slow machine drift that a best-of or median-of-each-side
    comparison picks up as phantom overhead.  The heap side pays
    per-event hook calls; the vectorized side carries the
    ``MetricsState`` through its ``lax.while_loop`` (compile time
    excluded both ways — it amortizes).  Gated at
    ``TELEMETRY_OVERHEAD_MAX`` by ``benchmarks.bench_gate``.
    """
    trace = TRACE_FAMILIES["poisson"](zoo, n=n, load=load, seed=seed)

    def heap_wall(tel_on: bool) -> float:
        tel = Telemetry() if tel_on else None
        sim = ClusterSimulator(TimeSharingPolicy(), window=window,
                               telemetry=tel)
        t0 = time.perf_counter()
        sim.run(trace)
        return time.perf_counter() - t0

    def paired(wall) -> tuple[float, float, float]:
        wall(False), wall(True)                  # warm outside timing
        pairs = [(wall(False), wall(True)) for _ in range(repeats)]
        return (statistics.median(b for b, _ in pairs),
                statistics.median(t for _, t in pairs),
                statistics.median(t / b for b, t in pairs))

    heap_base, heap_tel, heap_ratio = paired(heap_wall)

    cap = max(128, 2 * len(trace))
    engines = {
        False: VectorizedClusterSimulator(TimeSharingPolicy(), window=window,
                                          capacity=cap),
        True: VectorizedClusterSimulator(TimeSharingPolicy(), window=window,
                                         capacity=cap, telemetry=True),
    }
    for eng in engines.values():
        eng.run(trace)                       # compile outside the timed region

    def vec_wall(tel_on: bool) -> float:
        t0 = time.perf_counter()
        engines[tel_on].run(trace)
        return time.perf_counter() - t0

    vec_base, vec_tel, vec_ratio = paired(vec_wall)
    section = {
        "family": "poisson", "n_arrivals": n, "load": load, "seed": seed,
        "window": window, "repeats": repeats,
        "heap": {"base_wall_s": heap_base, "telemetry_wall_s": heap_tel,
                 "overhead_ratio": heap_ratio},
        "vectorized": {"base_wall_s": vec_base, "telemetry_wall_s": vec_tel,
                       "overhead_ratio": vec_ratio},
        "max_allowed_ratio": TELEMETRY_OVERHEAD_MAX,
        "note": ("median per-pair off/on wall ratios on one machine in one "
                 "process — cross-machine absolute times never enter the "
                 "gate; vectorized walls are warm (compile excluded, as "
                 "the engine is used); heap telemetry includes full event "
                 "recording + metrics hooks, vectorized carries "
                 "MetricsState in-graph"),
    }
    emit("telemetry_overhead_heap", heap_tel * 1e6 / n,
         f"ratio={heap_ratio:.3f}x")
    emit("telemetry_overhead_vec", vec_tel * 1e6 / n,
         f"ratio={vec_ratio:.3f}x")
    return section


def _context_agent(zoo, env_cfg, base_agent, episodes, seed=0):
    """Train the arrival-aware agent, warm-started from the profile-only one.

    ``widen_dqn_params`` zero-pads the input layer (params, target, Adam
    moments), so training starts from the exact profile-only Q-function and
    only has to learn how the context block modulates it; exploration
    restarts on a reduced ε schedule sized for adaptation, not rediscovery.
    """
    ctx_cfg = dataclasses.replace(env_cfg, obs_context=True)
    extra = context_dim(ctx_cfg)
    probe = CoScheduleEnv(ctx_cfg)
    warm = DQNAgent(probe.state_dim, probe.n_actions, base_agent.cfg, seed=seed)
    warm.params = widen_dqn_params(base_agent.params, extra)
    warm.target_params = widen_dqn_params(base_agent.target_params, extra)
    warm.opt = {"m": widen_dqn_params(base_agent.opt["m"], extra),
                "v": widen_dqn_params(base_agent.opt["v"], extra),
                "t": base_agent.opt["t"]}
    t0 = time.perf_counter()
    agent, hist = train_agent(
        zoo, ctx_cfg,
        TrainConfig(episodes=episodes, eval_every=max(50, episodes // 4),
                    obs_context=True, seed=seed,
                    dqn=DQNConfig(eps_start=0.5,
                                  eps_decay_steps=episodes * 6)),
        warm_start=warm)
    emit("arrival_aware_train", (time.perf_counter() - t0) * 1e6 / episodes,
         f"tp={hist[-1]['eval_throughput']:.3f}")
    return agent, ctx_cfg


def _arrival_aware(zoo, env_cfg, ctx_cfg, agent, ctx_agent, families,
                   n, load, seed, window, engine="heap"):
    """Frozen observation-mode comparison, one entry per trace family."""
    out: dict = {}
    for i, fam in enumerate(families):
        trace = TRACE_FAMILIES[fam](zoo, n=n, load=load, seed=seed + i)
        ts = _simulate(TimeSharingPolicy(), trace, window, engine=engine)
        rl = _simulate(RLDispatchPolicy(agent, env_cfg), trace, window)
        rlc = _simulate(RLDispatchPolicy(ctx_agent, ctx_cfg), trace, window)
        out[fam] = {
            "rl_profile_only": rl,
            "rl_context": rlc,
            "time_sharing_throughput": ts["throughput"],
            "rl_context_vs_profile_only": rlc["throughput"] / rl["throughput"],
            "rl_context_vs_time_sharing": rlc["throughput"] / ts["throughput"],
            "rl_profile_only_vs_time_sharing": rl["throughput"] / ts["throughput"],
        }
        emit(f"arrival_aware_{fam}", rlc["sim_wall_s"] * 1e6,
             f"ctx/prof={out[fam]['rl_context_vs_profile_only']:.3f}")
    out["note"] = ARRIVAL_NOTE
    return out


def _bench_trace(tname, trace, agent, env_cfg, window, retrain_cfg,
                 baselines: bool, engine="heap", profile=False,
                 trace_gen_s=None):
    """All policies on one trace; fresh repositories so profiling restarts."""
    out: dict = {"arrivals": len(trace), "span_s": trace[-1].t}
    if trace_gen_s is not None:
        out["trace_gen_s"] = trace_gen_s
    out["time_sharing"] = _simulate(TimeSharingPolicy(), trace, window,
                                    engine=engine, profile=profile)
    # dispatch-mode comparison: same frozen policies, blocking pod
    out["time_sharing_blocking"] = _simulate(TimeSharingPolicy(), trace,
                                             window, mode="blocking",
                                             profile=profile)
    if baselines:
        out["greedy_packer"] = _simulate(GreedyPackerPolicy(), trace, window,
                                         engine=engine, profile=profile)
        out["mig_mps_default"] = _simulate(
            StaticPartitionPolicy("mig_mps_default"), trace, window,
            engine=engine, profile=profile)
        out["rl"] = _simulate(RLDispatchPolicy(agent, env_cfg), trace, window,
                              engine=engine, profile=profile)
        out["rl_blocking"] = _simulate(RLDispatchPolicy(agent, env_cfg),
                                       trace, window, mode="blocking",
                                       profile=profile)
    pol = RLDispatchPolicy(agent, env_cfg)
    rt = OnlineRetrainer(policy=pol, **retrain_cfg)
    out["rl_retrain"] = _simulate(pol, trace, window, retrainer=rt,
                                  profile=profile)
    ts_tp = out["time_sharing"]["throughput"]
    for name in ("greedy_packer", "mig_mps_default", "rl", "rl_retrain"):
        if name in out:
            out[f"{name}_vs_time_sharing"] = out[name]["throughput"] / ts_tp
    cvb = {"time_sharing": (out["time_sharing"]["throughput"]
                            / out["time_sharing_blocking"]["throughput"])}
    if "rl_blocking" in out:
        cvb["rl"] = out["rl"]["throughput"] / out["rl_blocking"]["throughput"]
    out["concurrent_vs_blocking"] = cvb
    emit(f"online_{tname}", out["rl_retrain"]["sim_wall_s"] * 1e6,
         f"rl_rt/ts={out['rl_retrain_vs_time_sharing']:.3f} "
         f"conc/blk={cvb['time_sharing']:.3f}")
    return out


def main() -> None:
    enable_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--fast", action="store_true", help="shrink the full run")
    ap.add_argument("--smoke", action="store_true",
                    help="CI guard: tiny counts, ratio floors + key check")
    ap.add_argument("--ratio-floor", type=float, default=0.98,
                    help="min rl_retrain/time_sharing throughput in --smoke")
    ap.add_argument("--frag-margin", type=float, default=FRAG_MARGIN,
                    help="min concurrent/blocking throughput on the "
                         "fragmented family in --smoke (shared with "
                         "benchmarks.bench_gate)")
    ap.add_argument("--window", type=int, default=None)
    ap.add_argument("--arrivals", type=int, default=None)
    ap.add_argument("--episodes", type=int, default=None)
    ap.add_argument("--ctx-episodes", type=int, default=None,
                    help="training budget for the context agent "
                         "(default: same as --episodes)")
    ap.add_argument("--ctx-seed", type=int, default=2,
                    help="training seed for the context agent's refresh "
                         "(its own knob: the warm start pins the starting "
                         "Q-function, so this only seeds context draws and "
                         "exploration)")
    ap.add_argument("--load", type=float, default=1.25)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--retrain-interval-min", type=float, default=None)
    ap.add_argument("--engine", choices=("heap", "vectorized"),
                    default="heap",
                    help="simulator engine for policy×family cells; "
                         "'vectorized' routes supported cells "
                         "(solo-placement, concurrent, no retrainer) "
                         "through repro.online.vecsim and leaves the rest "
                         "on the heap — each cell records which engine "
                         "served it")
    ap.add_argument("--sweep-batch", type=int, default=64,
                    help="vmapped batch size for the vectorized_sim sweep")
    ap.add_argument("--section",
                    choices=("arrival_aware", "vectorized_sim",
                             "vectorized_rl", "sim_wall",
                             "fleet_scale", "retrain_trigger",
                             "telemetry_overhead", "queueing_reward"),
                    default=None,
                    help="recompute one section and merge it into the "
                         "committed --bench-json instead of a full run")
    ap.add_argument("--profile", action="store_true",
                    help="record a per-phase wall-time breakdown (trace "
                         "gen / sim / policy / retrain) in each heap cell")
    ap.add_argument("--telemetry-artifacts", default=None, metavar="DIR",
                    help="(smoke) write a telemetry-enabled fleet cell's "
                         "Chrome trace + events/metrics JSONL into DIR "
                         "for CI artifact upload")
    ap.add_argument("--bench-json", default="BENCH_online.json",
                    help="committed trajectory checked for keys in --smoke")
    ap.add_argument("--out", default=None,
                    help="where to write results (default BENCH_online.json; "
                         "smoke mode writes nothing unless given)")
    args, _ = ap.parse_known_args()

    if args.section == "sim_wall":
        # pure derivation from the committed traces cells — no simulation
        with open(args.bench_json) as f:
            bench = json.load(f)
        bench["sim_wall"] = _sim_wall_block(bench["traces"])
        out = args.out or args.bench_json
        with open(out, "w") as f:
            json.dump(bench, f, indent=1)
        cells = sum(len(v) for v in bench["sim_wall"].values())
        print(f"merged sim_wall into {out}: {cells} policy×family cells")
        return

    if args.section == "fleet_scale":
        with open(args.bench_json) as f:
            bench = json.load(f)
        window = args.window or bench["window"]
        n = args.arrivals or 10_000
        seed = bench.get("seed", args.seed)
        episodes = args.episodes or bench["train_episodes"]
        zoo = make_zoo()
        env_cfg = EnvConfig(window=window, c_max=4)
        print("name,us_per_call,derived")
        # deterministic replication of the committed run's profile-only
        # agent (same replication path as --section arrival_aware)
        agent, _ = train_agent(
            zoo, env_cfg,
            TrainConfig(episodes=episodes, eval_every=max(50, episodes // 4),
                        seed=seed,
                        dqn=DQNConfig(eps_decay_steps=episodes * 6)))
        section = _fleet_scale(zoo, agent, env_cfg, window, n, seed)
        section["single_pod_parity"] = _single_pod_parity(zoo, bench)
        bench["fleet_scale"] = section
        frag = section["families"]["fragmented"]["ratios"]
        best = max(frag[k]["time_sharing"] for k in frag)
        acc = bench.setdefault("acceptance", {})
        acc["fleet_best_router_beats_hash_on_fragmented"] = (
            best >= FLEET_P99_FLOOR)
        acc["fleet_single_pod_parity"] = all(
            section["single_pod_parity"].values())
        out = args.out or args.bench_json
        with open(out, "w") as f:
            json.dump(bench, f, indent=1)
        print(f"merged fleet_scale into {out}: best/hash p99 on fragmented "
              f"= {best:.2f}x (floor {FLEET_P99_FLOOR:.1f}), parity "
              f"{section['single_pod_parity']}")
        return

    if args.section == "telemetry_overhead":
        with open(args.bench_json) as f:
            bench = json.load(f)
        window = args.window or bench["window"]
        n = args.arrivals or max(400, bench["n_arrivals"])
        load = bench.get("load", args.load)
        seed = bench.get("seed", args.seed)
        zoo = make_zoo()
        print("name,us_per_call,derived")
        section = _telemetry_overhead(zoo, window, n, load, seed)
        bench["telemetry_overhead"] = section
        worst = max(section["heap"]["overhead_ratio"],
                    section["vectorized"]["overhead_ratio"])
        bench.setdefault("acceptance", {})[
            "telemetry_overhead_within_max"] = worst <= TELEMETRY_OVERHEAD_MAX
        out = args.out or args.bench_json
        with open(out, "w") as f:
            json.dump(bench, f, indent=1)
        print(f"merged telemetry_overhead into {out}: heap "
              f"{section['heap']['overhead_ratio']:.3f}x, vectorized "
              f"{section['vectorized']['overhead_ratio']:.3f}x "
              f"(max {TELEMETRY_OVERHEAD_MAX:.2f}x)")
        return

    if args.section == "retrain_trigger":
        with open(args.bench_json) as f:
            bench = json.load(f)
        window = args.window or bench["window"]
        n = args.arrivals or bench["n_arrivals"]
        load = bench.get("load", args.load)
        seed = bench.get("seed", args.seed)
        episodes = args.episodes or bench["train_episodes"]
        interval_min = (args.retrain_interval_min
                        or bench.get("retrain", {}).get("interval_min", 30.0))
        retrain_episodes = bench.get("retrain", {}).get("episodes", 240)
        zoo = make_zoo()
        env_cfg = EnvConfig(window=window, c_max=4)
        print("name,us_per_call,derived")
        # deterministic replication of the committed run's profile-only agent
        agent, _ = train_agent(
            zoo, env_cfg,
            TrainConfig(episodes=episodes, eval_every=max(50, episodes // 4),
                        seed=seed,
                        dqn=DQNConfig(eps_decay_steps=episodes * 6)))
        section = _retrain_trigger(zoo, agent, env_cfg, window, n, load,
                                   seed, interval_min, retrain_episodes)
        bench["retrain_trigger"] = section
        bench.setdefault("acceptance", {})[
            "drift_trigger_holds_throughput_with_fewer_retrains"] = (
            section["drift_vs_clock_throughput"] >= 0.97
            and section["retrains_saved"] >= 0)
        out = args.out or args.bench_json
        with open(out, "w") as f:
            json.dump(bench, f, indent=1)
        print(f"merged retrain_trigger into {out}: drift/clock throughput "
              f"{section['drift_vs_clock_throughput']:.3f}, retrains "
              f"{section['clock']['retrains']} -> "
              f"{section['drift']['retrains']}")
        return

    if args.section == "vectorized_sim":
        with open(args.bench_json) as f:
            bench = json.load(f)
        window = args.window or bench["window"]
        n = args.arrivals or bench["n_arrivals"]
        load = bench.get("load", args.load)
        seed = bench.get("seed", args.seed)
        zoo = make_zoo()
        print("name,us_per_call,derived")
        section = _vectorized_sim(zoo, window, n, load, seed,
                                  batch=args.sweep_batch)
        bench["vectorized_sim"] = section
        bench.setdefault("acceptance", {})[
            "vectorized_sweep_speedup_ge_floor"] = (
            section["sweep"]["speedup_vs_heap"] >= VECSIM_SPEEDUP_FLOOR)
        out = args.out or args.bench_json
        with open(out, "w") as f:
            json.dump(bench, f, indent=1)
        print(f"merged vectorized_sim into {out}: "
              f"{section['sweep']['speedup_vs_heap']:.2f}x over heap at "
              f"batch {section['sweep']['batch']} "
              f"({section['sweep']['traces_per_s']:.0f} traces/s, floor "
              f"{VECSIM_SPEEDUP_FLOOR:.1f}x)")
        return

    if args.section == "vectorized_rl":
        with open(args.bench_json) as f:
            bench = json.load(f)
        window = args.window or bench["window"]
        n = args.arrivals or bench["n_arrivals"]
        load = bench.get("load", args.load)
        seed = bench.get("seed", args.seed)
        episodes = args.episodes or bench["train_episodes"]
        zoo = make_zoo()
        env_cfg = EnvConfig(window=window, c_max=4)
        print("name,us_per_call,derived")
        # deterministic replication of the committed run's profile-only agent
        agent, _ = train_agent(
            zoo, env_cfg,
            TrainConfig(episodes=episodes, eval_every=max(50, episodes // 4),
                        seed=seed,
                        dqn=DQNConfig(eps_decay_steps=episodes * 6)))
        section = _vectorized_rl(zoo, agent, env_cfg, window, n, load, seed,
                                 batch=args.sweep_batch)
        bench["vectorized_rl"] = section
        bench.setdefault("acceptance", {})[
            "vectorized_rl_sweep_speedup_ge_floor"] = (
            section["sweep"]["speedup_vs_heap"] >= VECRL_SPEEDUP_FLOOR)
        out = args.out or args.bench_json
        with open(out, "w") as f:
            json.dump(bench, f, indent=1)
        print(f"merged vectorized_rl into {out}: "
              f"{section['sweep']['speedup_vs_heap']:.2f}x over heap RL at "
              f"batch {section['sweep']['batch']} "
              f"({section['sweep']['traces_per_s']:.0f} traces/s, floor "
              f"{VECRL_SPEEDUP_FLOOR:.1f}x); population "
              f"{section['population']['params_sets']}x"
              f"{section['sweep']['batch']} episodes in "
              f"{section['population']['wall_s']:.3f}s")
        return

    if args.section == "queueing_reward":
        with open(args.bench_json) as f:
            bench = json.load(f)
        window = args.window or bench["window"]
        n = args.arrivals or bench["n_arrivals"]
        load = bench.get("load", args.load)
        seed = bench.get("seed", args.seed)
        episodes = args.episodes or bench["train_episodes"]
        zoo = make_zoo()
        env_cfg = EnvConfig(window=window, c_max=4)
        print("name,us_per_call,derived")
        # deterministic replication of the committed run's profile-only agent
        agent, _ = train_agent(
            zoo, env_cfg,
            TrainConfig(episodes=episodes, eval_every=max(50, episodes // 4),
                        seed=seed,
                        dqn=DQNConfig(eps_decay_steps=episodes * 6)))
        section = _queueing_reward(zoo, agent, env_cfg, window, n, load, seed)
        bench["queueing_reward"] = section
        bench.setdefault("acceptance", {})[
            "queueing_trained_wins_majority_families"] = (
            len(section["families"]) == len(TRACE_FAMILIES)
            and section["families_won"] >= QUEUEING_WIN_FAMILIES_MIN)
        out = args.out or args.bench_json
        with open(out, "w") as f:
            json.dump(bench, f, indent=1)
        print(f"merged queueing_reward into {out}: wins "
              f"{section['families_won']}/{len(section['families'])} "
              f"(floor {QUEUEING_WIN_FAMILIES_MIN}), selected "
              f"{section['train']['selected']}, "
              + ", ".join(
                  f"{t}={section['families'][t]['queueing_vs_proxy_p99']:.3f}"
                  for t in sorted(section["families"])))
        return

    if args.section == "arrival_aware":
        with open(args.bench_json) as f:
            bench = json.load(f)
        window = args.window or bench["window"]
        n = args.arrivals or bench["n_arrivals"]
        load = bench.get("load", args.load)
        seed = bench.get("seed", args.seed)
        episodes = args.episodes or bench["train_episodes"]
        zoo = make_zoo()
        env_cfg = EnvConfig(window=window, c_max=4)
        print("name,us_per_call,derived")
        # deterministic replication of the committed run's profile-only agent
        agent, _ = train_agent(
            zoo, env_cfg,
            TrainConfig(episodes=episodes, eval_every=max(50, episodes // 4),
                        seed=seed,
                        dqn=DQNConfig(eps_decay_steps=episodes * 6)))
        ctx_agent, ctx_cfg = _context_agent(
            zoo, env_cfg, agent, args.ctx_episodes or episodes,
            seed=args.ctx_seed)
        section = _arrival_aware(zoo, env_cfg, ctx_cfg, agent, ctx_agent,
                                 tuple(TRACE_FAMILIES), n, load, seed, window)
        section["ctx_seed"] = args.ctx_seed
        bench["arrival_aware"] = section
        bench.setdefault("acceptance", {})[
            "arrival_aware_fragmented_ctx_ge_profile_only"] = (
            section["fragmented"]["rl_context_vs_profile_only"]
            >= ARRIVAL_FLOOR)
        out = args.out or args.bench_json
        with open(out, "w") as f:
            json.dump(bench, f, indent=1)
        print(f"merged arrival_aware into {out}: ctx/profile-only " +
              ", ".join(f"{t}={section[t]['rl_context_vs_profile_only']:.3f}"
                        for t in TRACE_FAMILIES))
        return

    if args.smoke:
        window = args.window or 6
        episodes = args.episodes or 120
        n = args.arrivals or 32
        families = ("poisson", "fragmented", "mmpp")
        interval_min = args.retrain_interval_min or 40.0
        retrain_episodes = 80
    else:
        window = args.window or 8
        episodes = args.episodes or (600 if args.fast else 1500)
        n = args.arrivals or (60 if args.fast else 120)
        families = tuple(TRACE_FAMILIES)
        interval_min = args.retrain_interval_min or 30.0
        retrain_episodes = 240

    zoo = make_zoo()
    env_cfg = EnvConfig(window=window, c_max=4)
    print("name,us_per_call,derived")
    t0 = time.perf_counter()
    # seed threaded so --section arrival_aware can replicate this agent
    # bit-exactly from the committed run's recorded seed
    agent, hist = train_agent(
        zoo, env_cfg,
        TrainConfig(episodes=episodes, eval_every=max(50, episodes // 4),
                    seed=args.seed,
                    dqn=DQNConfig(eps_decay_steps=episodes * 6)))
    emit("online_train_agent", (time.perf_counter() - t0) * 1e6 / episodes,
         f"tp={hist[-1]['eval_throughput']:.3f}")
    retrain_cfg = {
        "train_cfg": default_retrain_train_config(retrain_episodes),
        "interval_s": interval_min * 60.0,
        "min_jobs": 4,
    }

    traces = {}
    for i, fam in enumerate(families):
        t_gen = time.perf_counter()
        trace = TRACE_FAMILIES[fam](zoo, n=n, load=args.load,
                                    seed=args.seed + i)
        t_gen = time.perf_counter() - t_gen
        traces[fam] = _bench_trace(fam, trace, agent, env_cfg, window,
                                   retrain_cfg, baselines=not args.smoke,
                                   engine=args.engine, profile=args.profile,
                                   trace_gen_s=t_gen if args.profile else None)

    # observation-mode comparison: context-trained vs profile-only, frozen
    ctx_episodes = args.ctx_episodes or (100 if args.smoke else episodes)
    ctx_agent, ctx_cfg = _context_agent(zoo, env_cfg, agent, ctx_episodes,
                                        seed=args.ctx_seed)
    arrival = None
    ctx_smoke_tp = None
    fleet_smoke = None
    if args.smoke:
        # plumbing guard only: the context agent must serve the
        # fragmentation-stressing trace end to end (committed performance
        # floors live in benchmarks.bench_gate)
        i_frag = families.index("fragmented")
        frag_trace = TRACE_FAMILIES["fragmented"](zoo, n=n, load=args.load,
                                                  seed=args.seed + i_frag)
        ctx_smoke_tp = _simulate(RLDispatchPolicy(ctx_agent, ctx_cfg),
                                 frag_trace, window)["throughput"]
        emit("arrival_aware_smoke", 0.0, f"ctx_tp={ctx_smoke_tp:.3f}")
        # fleet plumbing guard: every router serves a heterogeneous
        # (8, 4) fleet end to end with pod-local claims, and the
        # vectorized fleet engine matches the heap on the hash cell
        fleet_trace = TRACE_FAMILIES["fragmented"](
            zoo, n=n, load=args.load, seed=args.seed, capacity=1.5)
        pods = (N_UNITS, 4)
        served, p99 = True, {}
        for router_name in FLEET_ROUTERS:
            fres = ClusterSimulator(
                TimeSharingPolicy(),
                SimConfig(window=window, pods=pods,
                          router=router_name)).run(fleet_trace)
            served &= all(s + w <= fres.pods[seg.pod]
                          for seg in fres.timeline for s, w in seg.slices)
            served &= all(r.finish == r.finish for r in fres.jobs)  # no NaN
            p99[router_name] = fres.p99_wait
        vres = VectorizedFleetSimulator(
            TimeSharingPolicy(),
            SimConfig(window=window, pods=pods, router="hash"),
            capacity=max(64, 2 * n)).run(fleet_trace)
        tol = max(1e-3 * max(p99["hash"], 1.0), 1e-2)
        fleet_smoke = {
            "pods": list(pods), "p99_wait_s": p99, "served": served,
            "vec_heap_p99_gap_s": abs(vres.p99_wait - p99["hash"]),
            "vec_parity": abs(vres.p99_wait - p99["hash"]) <= tol,
        }
        emit("fleet_smoke", 0.0,
             f"p99_hash={p99['hash']:.1f}s "
             f"gap={fleet_smoke['vec_heap_p99_gap_s']:.4f}s")
        if args.telemetry_artifacts:
            # telemetry-enabled fleet cell: Chrome trace + events/metrics
            # JSONL for CI artifact upload, with the metrics aggregates
            # cross-checked against summary() (the acceptance invariant)
            import os
            os.makedirs(args.telemetry_artifacts, exist_ok=True)
            tel = Telemetry()
            tres = ClusterSimulator(
                TimeSharingPolicy(),
                SimConfig(window=window, pods=pods, router="hash"),
                telemetry=tel).run(fleet_trace)
            summ = tres.summary()
            d = args.telemetry_artifacts
            tel.recorder.write_chrome_trace(f"{d}/smoke_trace.json", pods)
            tel.recorder.write_jsonl(f"{d}/smoke_events.jsonl")
            tel.metrics.write_jsonl(f"{d}/smoke_metrics.jsonl")
            mm = {m["name"]: m for m in tel.metrics.to_dicts()}
            busy = sum(tres.slice_busy_s)
            fleet_smoke["telemetry_matches_summary"] = (
                mm["jobs_arrived"]["value"] == summ["jobs"]
                and mm["backfills"]["value"] == summ["backfills"]
                and mm["refits"]["value"] == summ["refits"]
                and mm["windows_formed"]["value"] == summ["dispatches"]
                and mm["groups_placed"]["value"] == summ["groups"]
                and abs(mm["busy_unit_s"]["value"] - busy)
                <= 1e-6 * max(busy, 1.0))
            emit("telemetry_artifacts", 0.0,
                 f"events={len(tel.recorder.events)} "
                 f"match={fleet_smoke['telemetry_matches_summary']}")
    else:
        arrival = _arrival_aware(zoo, env_cfg, ctx_cfg, agent, ctx_agent,
                                 families, n, args.load, args.seed, window,
                                 engine=args.engine)

    # engine comparison rides the full run (smoke keeps its <60 s budget;
    # CI exercises the sweep path via tests/test_vecsim.py instead)
    vec_section = None if args.smoke else _vectorized_sim(
        zoo, window, n, args.load, args.seed, batch=args.sweep_batch)
    vecrl_section = None if args.smoke else _vectorized_rl(
        zoo, agent, env_cfg, window, n, args.load, args.seed,
        batch=args.sweep_batch)

    # fleet-scale grid rides the full run too (frozen profile-only agent)
    fleet = None if args.smoke else _fleet_scale(
        zoo, agent, env_cfg, window,
        2_000 if args.fast else 10_000, args.seed,
        n_vec=0 if args.fast else 100_000)

    rl_vs_ts = {t: traces[t]["rl_retrain_vs_time_sharing"] for t in traces}
    dispatch_cmp = {t: traces[t]["concurrent_vs_blocking"] for t in traces}
    frag = traces.get("fragmented", {})
    result = {
        "window": window,
        "n_arrivals": n,
        "load": args.load,
        "seed": args.seed,
        "train_episodes": episodes,
        "engine": args.engine,
        "retrain": {"interval_min": interval_min,
                    "episodes": retrain_episodes},
        "traces": traces,
        "rl_vs_time_sharing": rl_vs_ts,
        "dispatch_comparison": dispatch_cmp,
        "arrival_aware": arrival,
        "sim_wall": _sim_wall_block(traces),
        "vectorized_sim": vec_section,
        "vectorized_rl": vecrl_section,
        "fleet_scale": fleet,
        "acceptance": {
            "arrival_aware_fragmented_ctx_ge_profile_only": (
                arrival is not None
                and arrival["fragmented"]["rl_context_vs_profile_only"]
                >= ARRIVAL_FLOOR),
            "poisson_arrivals": traces.get("poisson", {}).get("arrivals", 0),
            "rl_retrain_beats_time_sharing_on_poisson":
                rl_vs_ts.get("poisson", 0.0) > 1.0,
            "concurrent_ge_blocking_all_families":
                all(min(r.values()) >= CONC_BLK_FLOOR
                    for r in dispatch_cmp.values()),
            "concurrent_strictly_beats_blocking_on_fragmented":
                frag.get("concurrent_vs_blocking",
                         {}).get("time_sharing", 0.0) > 1.0,
            "fragmented_backfills":
                frag.get("time_sharing", {}).get("backfills", 0),
            "vectorized_sweep_speedup_ge_floor": (
                vec_section is not None
                and vec_section["sweep"]["speedup_vs_heap"]
                >= VECSIM_SPEEDUP_FLOOR),
            "vectorized_rl_sweep_speedup_ge_floor": (
                vecrl_section is not None
                and vecrl_section["sweep"]["speedup_vs_heap"]
                >= VECRL_SPEEDUP_FLOOR),
        },
        "note": ("throughput = total solo work / makespan (time sharing ~1.0 "
                 "on a saturated pod); *_vs_time_sharing are ratios of that "
                 "metric on identical traces; rl_retrain re-trains the agent "
                 "on the live profile repository every interval_min simulated "
                 "minutes, warm-started from current params, and hot-swaps "
                 "it; all policies pay the same first-sight profiling cost "
                 "(unprofiled jobs run solo); dispatch_comparison = "
                 "concurrent-dispatch/blocking-window throughput per policy "
                 "on identical traces — 1.0 where placements are full-pod "
                 "(bit-compatible modes), >1.0 on the fragmented family "
                 "where right-sized jobs pack disjoint slices and backfill "
                 "idle gaps; slice_utilization/idle_slice_frac in each "
                 "summary are claimed-unit-seconds over N_UNITS x makespan"),
    }

    if fleet is not None:
        fleet["single_pod_parity"] = _single_pod_parity(zoo, result)
        frag_r = fleet["families"]["fragmented"]["ratios"]
        best = max(frag_r[k]["time_sharing"] for k in frag_r)
        result["acceptance"]["fleet_best_router_beats_hash_on_fragmented"] = (
            best >= FLEET_P99_FLOOR)
        result["acceptance"]["fleet_single_pod_parity"] = all(
            fleet["single_pod_parity"].values())

    if args.smoke:
        failures = []
        ratio = rl_vs_ts.get("poisson", 0.0)
        if ratio < args.ratio_floor:
            failures.append(f"rl_retrain/time_sharing {ratio:.3f} below "
                            f"floor {args.ratio_floor:.2f}")
        for fam, cmp_ in dispatch_cmp.items():
            worst = min(cmp_.values())
            if worst < CONC_BLK_FLOOR:
                failures.append(f"concurrent below blocking on {fam}: "
                                f"{worst:.3f}")
        frag_ratio = dispatch_cmp.get("fragmented", {}).get("time_sharing", 0.0)
        if frag_ratio < args.frag_margin:
            failures.append(f"fragmented concurrent/blocking {frag_ratio:.3f} "
                            f"below margin {args.frag_margin:.2f}")
        if not (ctx_smoke_tp and ctx_smoke_tp > 0):
            failures.append(f"context agent failed to serve the fragmented "
                            f"smoke trace (tp={ctx_smoke_tp})")
        if fleet_smoke is not None:
            if not fleet_smoke["served"]:
                failures.append("fleet smoke: a router produced cross-pod "
                                "or unserved work on the (8, 4) fleet")
            if not fleet_smoke["vec_parity"]:
                failures.append(
                    f"fleet smoke: vectorized fleet p99 diverges from heap "
                    f"by {fleet_smoke['vec_heap_p99_gap_s']:.4f}s on the "
                    f"hash cell")
            if not fleet_smoke.get("telemetry_matches_summary", True):
                failures.append("fleet smoke: telemetry metrics diverge "
                                "from summary() on the telemetry-enabled "
                                "cell")
        missing = missing_keys(args.bench_json, REQUIRED_KEYS)
        if missing:
            failures.append(f"{args.bench_json} missing keys: {missing}")
        if args.out:
            with open(args.out, "w") as f:
                json.dump({"smoke": True, **result}, f, indent=1)
        if failures:
            print("SMOKE FAIL: " + "; ".join(failures))
            sys.exit(1)
        print(f"smoke ok: rl_retrain/ts {ratio:.3f} on poisson "
              f"(floor {args.ratio_floor:.2f}), fragmented conc/blk "
              f"{frag_ratio:.3f} (margin {args.frag_margin:.2f}), "
              f"context agent serves fragmented (tp={ctx_smoke_tp:.3f}), "
              f"fleet (8,4) served by all routers (vec/heap p99 gap "
              f"{fleet_smoke['vec_heap_p99_gap_s']:.4f}s), "
              f"{args.bench_json} keys present")
        return

    out = args.out or "BENCH_online.json"
    with open(out, "w") as f:
        json.dump(result, f, indent=1)
    print(f"wrote {out}: rl_retrain/ts " +
          ", ".join(f"{t}={r:.3f}" for t, r in rl_vs_ts.items()) +
          "; conc/blk " +
          ", ".join(f"{t}={r['time_sharing']:.3f}"
                    for t, r in dispatch_cmp.items()) +
          "; ctx/prof " +
          ", ".join(f"{t}={arrival[t]['rl_context_vs_profile_only']:.3f}"
                    for t in families))


if __name__ == "__main__":
    main()

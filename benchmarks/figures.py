"""One benchmark function per paper figure (Figs. 3-5 observations,
Figs. 8-12 evaluation). Each prints `name,us_per_call,derived` CSV rows and
returns a dict for EXPERIMENTS.md."""
from __future__ import annotations

import time

import numpy as np

from benchmarks.common import emit, rl_scheduler
from repro.core import POLICIES, Schedule, corun_time, make_zoo, solo_run_time, paper_queues
from repro.core.metrics import avg_app_slowdown, fairness, relative_throughput
from repro.core.partition import Partition, Slice, enumerate_partitions
from repro.core.workloads import zoo_by_class


def _pair_pool(zoo):
    by = zoo_by_class(zoo)
    return {
        "CI+MI": (by["CI"][0], by["MI"][0]),
        "CI+CI": (by["CI"][0], by["CI"][1]),
        "MI+MI": (by["MI"][0], by["MI"][1]),
        "CI+US": (by["CI"][0], by["US"][0]),
    }


# ---------------------------------------------------------------------------
# Fig. 3: co-run throughput vs MPS compute-share sweep
# ---------------------------------------------------------------------------

def fig3_share_sweep(fast=False):
    zoo = make_zoo()
    out = {}
    t0 = time.time()
    n = 0
    for mix, (a, b) in _pair_pool(zoo).items():
        for share in (0.1, 0.2, 0.3, 0.4, 0.5, 0.6, 0.7, 0.8, 0.9):
            p = Partition((Slice(8, (round(share, 2), round(1 - share, 2))),), f"mps{share}")
            tp = solo_run_time([a, b]) / corun_time([a, b], p)
            out[(mix, share)] = tp
            emit(f"fig3/{mix}/share={share:.1f}", (time.time() - t0) * 1e6 / max(1, n := n + 1), f"{tp:.4f}")
    return out


# ---------------------------------------------------------------------------
# Fig. 4: shared vs private bandwidth at equal compute allocation
# ---------------------------------------------------------------------------

def fig4_bw_partitioning(fast=False):
    zoo = make_zoo()
    out = {}
    t0 = time.time()
    n = 0
    shared_half = Partition((Slice(8, (0.5, 0.5)),), "shared")          # one domain
    private_half = Partition((Slice(4, (1.0,)), Slice(4, (1.0,))), "private")
    for mix, (a, b) in _pair_pool(zoo).items():
        for label, p in (("shared", shared_half), ("private", private_half)):
            tp = solo_run_time([a, b]) / corun_time([a, b], p)
            out[(mix, label)] = tp
            emit(f"fig4/{mix}/{label}", (time.time() - t0) * 1e6 / max(1, n := n + 1), f"{tp:.4f}")
    return out


# ---------------------------------------------------------------------------
# Fig. 5: partitioning-variant comparison for a 4-job mix
# ---------------------------------------------------------------------------

def fig5_variants(fast=False):
    # mix with scale-heterogeneous jobs (the hierarchical option's home turf:
    # right-sizing slices for US jobs while big jobs share the rest)
    zoo = make_zoo()
    by = zoo_by_class(zoo)
    jobs = [by["CI"][0], by["MI"][0], by["US"][0], by["US"][-1]]
    styles = {"mps": [], "mig": [], "hier": []}
    for p in enumerate_partitions(4):
        if p.style in styles:
            styles[p.style].append(p)
    out = {}
    t0 = time.time()
    n = 0
    for style, parts in styles.items():
        best = 0.0
        for p in parts:
            from repro.core.baselines import exhaustive_schedule

            sched = exhaustive_schedule(jobs, 4, parts)
            best = max(best, relative_throughput(sched))
            break  # exhaustive_schedule already optimizes within the style
        out[style] = best
        emit(f"fig5/{style}", (time.time() - t0) * 1e6 / max(1, n := n + 1), f"{best:.4f}")
    return out


# ---------------------------------------------------------------------------
# Fig. 8: throughput, 5 methods x 12 queues
# ---------------------------------------------------------------------------

METHODS = ("time_sharing", "mig_only", "mps_only", "mig_mps_default", "rl", "oracle")


def _method_schedules(queues, zoo, window, c_max, fast):
    sched_rl, env_cfg = rl_scheduler(zoo, window, c_max, fast)
    all_scheds: dict[str, dict[str, Schedule]] = {m: {} for m in METHODS}
    for qname, queue in queues.items():
        for m in METHODS:
            if m == "rl":
                all_scheds[m][qname] = sched_rl.schedule(queue)
            else:
                all_scheds[m][qname] = POLICIES[m](queue, c_max)
    return all_scheds


def fig8_throughput(fast=False, window=12, c_max=4):
    zoo = make_zoo()
    queues = paper_queues(zoo, window=window, per_kind=3)
    t0 = time.time()
    scheds = _method_schedules(queues, zoo, window, c_max, fast)
    out = {}
    for m in METHODS:
        tps = [relative_throughput(s) for s in scheds[m].values()]
        out[m] = {"per_queue": tps, "am": float(np.mean(tps)), "max": float(np.max(tps))}
        emit(f"fig8/{m}/AM", (time.time() - t0) * 1e6 / len(queues), f"{out[m]['am']:.4f}")
        emit(f"fig8/{m}/max", 0.0, f"{out[m]['max']:.4f}")
    return out, scheds, queues


# ---------------------------------------------------------------------------
# Fig. 9 / Fig. 10: window and Cmax scaling
# ---------------------------------------------------------------------------

def fig9_window(fast=False):
    zoo = make_zoo()
    out = {}
    t0 = time.time()
    for w in ((4, 8, 12) if fast else (4, 8, 12, 16)):
        queues = paper_queues(zoo, window=w, per_kind=1)
        sched_rl, _ = rl_scheduler(zoo, w, 4, fast, episodes=800)
        tps = [relative_throughput(sched_rl.schedule(q)) for q in queues.values()]
        out[w] = float(np.mean(tps))
        emit(f"fig9/W={w}", (time.time() - t0) * 1e6, f"{out[w]:.4f}")
    return out


def fig10_cmax(fast=False):
    zoo = make_zoo()
    out = {}
    t0 = time.time()
    for c in (2, 3, 4):
        queues = paper_queues(zoo, window=12, per_kind=1)
        sched_rl, _ = rl_scheduler(zoo, 12, c, fast, episodes=800)
        tps = [relative_throughput(sched_rl.schedule(q)) for q in queues.values()]
        out[c] = float(np.mean(tps))
        emit(f"fig10/Cmax={c}", (time.time() - t0) * 1e6, f"{out[c]:.4f}")
    return out


# ---------------------------------------------------------------------------
# Fig. 11 / Fig. 12: slowdown and fairness (reuse fig8 schedules)
# ---------------------------------------------------------------------------

def fig11_12_slowdown_fairness(scheds=None, queues=None, fast=False):
    if scheds is None:
        _, scheds, queues = fig8_throughput(fast=fast)
    out = {}
    for m in METHODS:
        slows = [avg_app_slowdown(s) for s in scheds[m].values()]
        fairs = [fairness(s) for s in scheds[m].values()]
        out[m] = {"avg_slowdown": float(np.mean(slows)), "best_slowdown": float(np.min(slows)),
                  "fairness": float(np.mean(fairs))}
        emit(f"fig11/{m}/avg_slowdown", 0.0, f"{out[m]['avg_slowdown']:.4f}")
        emit(f"fig12/{m}/fairness", 0.0, f"{out[m]['fairness']:.4f}")
    return out

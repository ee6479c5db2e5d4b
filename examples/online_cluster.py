"""Online cluster serving, end to end (paper §IV-B under live traffic):

  1. offline-train the co-scheduling agent on the job zoo,
  2. generate a multi-tenant arrival trace (Poisson / bursty / diurnal /
     heavy-tailed job scales),
  3. serve the same trace with time sharing, the greedy packer, and the RL
     scheduler — the RL run periodically re-trains against the live profile
     repository (MISO-style) and hot-swaps the refreshed agent,
  4. compare makespan-derived throughput, waits, turnaround, and slice-level
     packing (slice utilization, backfills), and show the slice-occupancy
     timeline of the first RL dispatches.

Groups run concurrently on disjoint slice ranges (EASY backfill included);
pick ``--trace fragmented`` to see right-sized 1-unit mice pack around
full-pod jobs.
``--context`` trains the agent on the arrival-aware observation (profiles
+ busy-unit mask + queue ages + pending depth — docs/observation.md) and
the simulator then feeds it the real cluster snapshot at every dispatch
window.  ``--pods 8,8,4,4 --router frag`` serves the trace on a
heterogeneous four-pod fleet instead of one pod — each arrival is routed
to a pod at its arrival instant, then dispatched by the unchanged
per-pod path (``--pods 8`` is the single-pod default, bit-compatible
with earlier PRs).

The RL run records the full telemetry event stream
(docs/observability.md): the dispatch timeline printed at the end is
read back from its ``place`` events, and ``--trace-out trace.json``
writes the same stream as a Chrome-trace file — load it in
https://ui.perfetto.dev to scrub the per-pod, per-slice-unit occupancy
tracks interactively.

    PYTHONPATH=src python examples/online_cluster.py [--trace fragmented]
"""
import argparse
import time

from repro.core import EnvConfig, TrainConfig, make_zoo, train_agent
from repro.core.agent import DQNConfig
from repro.online import (
    ClusterSimulator, GreedyPackerPolicy, OnlineRetrainer, RLDispatchPolicy,
    ROUTERS, SimConfig, TRACE_FAMILIES, Telemetry, TimeSharingPolicy,
    default_retrain_train_config,
)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--episodes", type=int, default=800)
    ap.add_argument("--window", type=int, default=8)
    ap.add_argument("--arrivals", type=int, default=80)
    ap.add_argument("--trace", choices=sorted(TRACE_FAMILIES), default="poisson")
    ap.add_argument("--load", type=float, default=1.25)
    ap.add_argument("--retrain-interval-min", type=float, default=30.0)
    ap.add_argument("--context", action="store_true",
                    help="arrival-aware observation: train with sampled "
                         "cluster-state contexts and serve with the "
                         "simulator's real dispatch snapshots")
    ap.add_argument("--pods", default="8",
                    help="comma-separated slice widths, one per pod "
                         "(e.g. 8,8,4,4); the default single 8 is the "
                         "classic one-pod cluster")
    ap.add_argument("--router", choices=sorted(ROUTERS), default="hash",
                    help="fleet router assigning each arrival a pod")
    ap.add_argument("--trace-out", metavar="PATH", default=None,
                    help="write the RL run's lifecycle events as a "
                         "Chrome-trace JSON (open in Perfetto / "
                         "chrome://tracing): one track per pod x slice "
                         "unit")
    args = ap.parse_args()
    pods = tuple(int(w) for w in args.pods.split(","))

    zoo = make_zoo()
    env_cfg = EnvConfig(window=args.window, c_max=4, obs_context=args.context)
    feats = "profiles + cluster state" if args.context else "profiles only"
    print(f"zoo: {len(zoo)} jobs — offline training ({args.episodes} episodes, "
          f"observing {feats})")
    t0 = time.time()
    agent, hist = train_agent(
        zoo, env_cfg,
        TrainConfig(episodes=args.episodes, eval_every=args.episodes // 2,
                    dqn=DQNConfig(eps_decay_steps=args.episodes * 6)))
    print(f"trained in {time.time()-t0:.0f}s: train_tp="
          f"{hist[-1]['eval_throughput']:.3f} "
          f"heldout_tp={hist[-1]['heldout_throughput']:.3f}")

    fleet_cap = sum(pods) / max(pods)       # full-pod equivalents
    trace = TRACE_FAMILIES[args.trace](zoo, n=args.arrivals, load=args.load,
                                       seed=0, capacity=fleet_cap)
    print(f"\ntrace '{args.trace}': {len(trace)} arrivals over "
          f"{trace[-1].t/3600:.2f} simulated hours (load {args.load}, "
          f"fleet {pods} via '{args.router}' router)")

    def cfg(tick=None):
        return SimConfig(window=args.window, pods=pods,
                         router=args.router, tick_interval_s=tick)

    results = {}
    results["time_sharing"] = ClusterSimulator(
        TimeSharingPolicy(), cfg()).run(trace)
    results["greedy_packer"] = ClusterSimulator(
        GreedyPackerPolicy(), cfg()).run(trace)
    pol = RLDispatchPolicy(agent, env_cfg)
    retrainer = OnlineRetrainer(
        policy=pol, train_cfg=default_retrain_train_config(240),
        interval_s=args.retrain_interval_min * 60.0)
    tel = Telemetry()
    results["rl+retrain"] = ClusterSimulator(
        pol, cfg(tick=retrainer.interval_s), on_tick=retrainer,
        telemetry=tel).run(trace)

    ts = results["time_sharing"].throughput
    print(f"\n{'policy':14s} {'throughput':>10s} {'vs_ts':>6s} "
          f"{'makespan_h':>10s} {'mean_wait_m':>11s} {'p99_wait_m':>10s} "
          f"{'slice_util':>10s} {'backfills':>9s}")
    for name, r in results.items():
        print(f"{name:14s} {r.throughput:10.3f} {r.throughput/ts:6.3f} "
              f"{r.makespan/3600:10.2f} {r.mean_wait/60:11.1f} "
              f"{r.p99_wait/60:10.1f} {r.slice_utilization:10.3f} "
              f"{r.backfills:9d}")

    print(f"\nre-training cycles: {len(retrainer.history)}")
    for h in retrainer.history:
        print(f"  t={h['t_s']/60:6.0f}min repo={h['repository_jobs']:3d} jobs "
              f"{h['class_counts']} train_tp={h['train_eval_throughput']:.3f}")

    # the slice-occupancy timeline now comes from the telemetry event
    # stream — the same "place" events a --trace-out file visualizes
    print("\nfirst RL dispatches (slice occupancy, from telemetry events):")
    for e in sorted(tel.recorder.by_kind("place"),
                    key=lambda e: (e["t_s"], e["pod"], e["slices"]))[:10]:
        units = ",".join(f"{st}-{st + w - 1}" for st, w in e["slices"])
        where = f"pod{e['pod']} units {units:9s}" if len(pods) > 1 \
            else f"units {units:9s}"
        bf = " (backfilled)" if e["backfilled"] else ""
        print(f"  [{e['t_s']:8.0f}s -> {e['t1_s']:8.0f}s] {where} "
              f"{len(e['jobs'])} job(s) on {e['partition']}{bf}")

    if args.trace_out:
        tel.recorder.write_chrome_trace(args.trace_out, pods=pods)
        print(f"\nwrote {len(tel.recorder)} lifecycle events to "
              f"{args.trace_out} (load in https://ui.perfetto.dev or "
              f"chrome://tracing)")
    print("online_cluster OK")


if __name__ == "__main__":
    main()

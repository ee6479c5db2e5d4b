"""Co-scheduler launcher (the paper's online phase as a CLI):

    PYTHONPATH=src python -m repro.launch.schedule --episodes 2000 --window 12

Trains the DQN agent over the job zoo, schedules the Q1..Q12 queues, and
prints the five-method comparison (paper Fig. 8).
"""
from __future__ import annotations

import argparse

import numpy as np


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--episodes", type=int, default=2000)
    ap.add_argument("--window", type=int, default=12)
    ap.add_argument("--c-max", type=int, default=4)
    ap.add_argument("--per-kind", type=int, default=3)
    args = ap.parse_args()

    from repro.core import (
        POLICIES, DQNConfig, EnvConfig, RLScheduler, TrainConfig, make_zoo,
        paper_queues, summarize, train_agent, validate_schedule,
    )

    zoo = make_zoo()
    env_cfg = EnvConfig(window=args.window, c_max=args.c_max)
    agent, _ = train_agent(
        zoo, env_cfg,
        TrainConfig(episodes=args.episodes,
                    eval_every=max(100, args.episodes // 4),
                    dqn=DQNConfig(eps_decay_steps=max(1500, args.episodes * 7))))
    sched = RLScheduler(agent, env_cfg)
    queues = paper_queues(zoo, window=args.window, per_kind=args.per_kind)

    methods = ["time_sharing", "mig_only", "mps_only", "mig_mps_default", "rl", "oracle"]
    table = {m: [] for m in methods}
    for qname, queue in queues.items():
        for m in methods:
            s = sched.schedule(queue) if m == "rl" else POLICIES[m](queue, args.c_max)
            if m == "rl":
                validate_schedule(queue, s, args.c_max)
            table[m].append(summarize(s)["throughput"])
    print(f"{'method':18s} " + " ".join(f"{q:>6s}" for q in queues) + "    AM   max")
    for m in methods:
        row = table[m]
        print(f"{m:18s} " + " ".join(f"{v:6.3f}" for v in row) +
              f" {np.mean(row):6.3f} {np.max(row):5.3f}")


if __name__ == "__main__":
    main()

"""Driver of the live dispatch cells: the heap ``ClusterSimulator``
serving through ``RLDispatchPolicy.decide``.

One unit serves one trace of the pool on a fresh cluster (empty profile
repository).  Every ``decide()`` call and every ``agent.greedy_episode``
call (the window's one device round trip, where the window holds a
profiled job) is timed on the host clock; ``decide_p95_ms`` is the 95th
percentile of all ``decide()`` calls of the window.  The loop is closed:
the heap advances simulated time between decisions, so a call's time is
its service time.
"""
from __future__ import annotations

import time

import numpy as np

from bench.lib.serving import Inputs, judge, plain, unserved


class _Timed:
    """Wraps a bound method, keeping each call's wall seconds."""

    def __init__(self, fn, spans, name: str):
        self.fn, self.spans, self.name = fn, spans, name
        self.samples: list[float] = []

    def __call__(self, *args, **kwargs):
        with self.spans(self.name):
            t = time.perf_counter()
            out = self.fn(*args, **kwargs)
            self.samples.append(time.perf_counter() - t)
        return out


def run(ctx) -> dict:
    from repro.online import ClusterSimulator, RLDispatchPolicy

    spans = ctx.spans
    with spans("bench.setup"):
        inputs = Inputs(ctx.config, ctx.traffic, ctx.seed)
        pool = inputs.program_pool()
        base = inputs.program_policy()
        agent, env_cfg, cfg = base.agent, base.scheduler.env_cfg, \
            inputs.sim_config()
        episode = _Timed(agent.greedy_episode, spans, "bench.episode")
        agent.greedy_episode = episode
        decide_s: list[float] = []

        def serve(trace):
            policy = RLDispatchPolicy(agent, env_cfg)
            timed = _Timed(policy.decide, spans, "bench.decide")
            policy.decide = timed
            res = ClusterSimulator(policy, cfg).run(trace)
            decide_s.extend(timed.samples)
            return res

        serve(pool[0][:64])
        decide_s.clear()
        episode.samples.clear()

    served: dict[int, object] = {}
    arrivals = units = 0
    mark = ctx.watch.mark()
    ctx.window_start()
    t0 = time.perf_counter()
    while True:
        k = units % len(pool)
        with spans("bench.unit"):
            served[k] = serve(pool[k])
        arrivals += len(pool[k])
        units += 1
        if units == 1:
            ctx.first_unit_done()
        if time.perf_counter() - t0 >= ctx.seconds:
            break
    ctx.window_end(ctx.watch.since(mark))

    served = {k: plain(r) for k, r in served.items()}
    failed = sum(unserved(r) for r in served.values())
    verdict = judge(inputs, served, ctx.spec["check"])
    ms = np.asarray(decide_s) * 1e3
    return {"metrics": {"decide_p95_ms": float(np.percentile(ms, 95))},
            "counters": {"units": units, "arrivals": arrivals,
                         "decide_calls": len(ms),
                         "decide_p50_ms": float(np.median(ms)),
                         "episode_calls": len(episode.samples),
                         "episode_p50_us": (
                             float(np.median(episode.samples)) * 1e6
                             if episode.samples else None),
                         "compared_traces": sorted(served)},
            "attempted": arrivals, "failed": failed, **verdict}

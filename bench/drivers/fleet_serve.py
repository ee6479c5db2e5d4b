"""Driver of the vectorized fleet cells.

One unit is one ``VectorizedFleetSimulator.run(trace)`` over a trace of
the pool: host pre-split, the device call and the merge of the pod lanes
into one result.  Units run back to back, cycling the pool, until the
window's seconds have passed; ``sim_arrivals_per_s`` is every arrival of
those units over their total wall time.
"""
from __future__ import annotations

import time

from bench.lib.serving import Inputs, judge, plain, unserved


def _warm_trace(trace, picks) -> list:
    """The shortest prefix of ``trace`` that holds every job of the trace,
    so the warm-up compiles the job table's full shape."""
    need = len(set(picks.tolist()))
    seen = set()
    for n, p in enumerate(picks.tolist(), 1):
        seen.add(p)
        if len(seen) == need:
            return trace[:max(n, 64)]
    return trace


def run(ctx) -> dict:
    from repro.online import VectorizedFleetSimulator

    spans = ctx.spans
    with spans("bench.setup"):
        inputs = Inputs(ctx.config, ctx.traffic, ctx.seed)
        pool = inputs.program_pool()
        sim = VectorizedFleetSimulator(inputs.program_policy(),
                                       inputs.sim_config(),
                                       capacity=ctx.spec["lane_capacity"])
        sim.run(_warm_trace(pool[0], inputs.pool[0].picks))

    served: dict[int, object] = {}
    arrivals = units = 0
    traced_arrivals = 0
    mark = ctx.watch.mark()
    ctx.window_start()
    t0 = time.perf_counter()
    while True:
        k = units % len(pool)
        with spans("bench.unit"):
            served[k] = sim.run(pool[k])
        arrivals += len(pool[k])
        units += 1
        if units == 1:
            traced_arrivals = arrivals
            ctx.first_unit_done()
        if time.perf_counter() - t0 >= ctx.seconds:
            break
    wall = time.perf_counter() - t0
    ctx.window_end(ctx.watch.since(mark))

    served = {k: plain(r) for k, r in served.items()}
    failed = sum(unserved(r) for r in served.values())
    verdict = judge(inputs, served, ctx.spec["check"])
    return {"metrics": {"sim_arrivals_per_s": arrivals / wall},
            "counters": {"units": units, "arrivals": arrivals,
                         "traced_arrivals": traced_arrivals,
                         "compared_traces": sorted(served)},
            "attempted": arrivals, "failed": failed, **verdict}

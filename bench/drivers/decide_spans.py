"""Driver of a live dispatch cell whose traced run also reads the
program's own spans.

It serves nothing of its own: the window is ``bench.drivers.decide``'s,
unchanged.  With ``--trace 0`` that is all, so the end-to-end numbers come
from the code that times every ``decide`` cell.  With ``--trace 1`` the
program's span recorder (``repro.spans``) is on for the whole run and is
reset where the window starts, and the line's counters gain, over every
call of the window:

* ``pack_calls``, ``pack_p50_us``: ``repro.sched.pack``, the window
  packed for the episode, the context block included;
* ``fit_calls``, ``fit_p50_us``: ``repro.policy.fit``, the plan's
  dedicated slices shrunk to the requested widths;
* ``shrunk_slices``: the sum of the counter ``repro.policy.shrunk``;
* ``backfill_scans``: ``repro.sim.backfill``, EASY backfill scans;
* ``backfilled_groups``: the sum of the counter ``repro.sim.backfilled``.

A program without a span or counter reads 0 calls (and no median) there.
"""
from __future__ import annotations

from bench.drivers import decide


def span_counters(summary: dict, counters: dict) -> dict:
    """The counters the line gains from ``repro.spans``' readings."""
    def calls(name):
        return summary.get(name, {}).get("count", 0)

    def p50_us(name):
        return summary[name]["median_us"] if calls(name) else None

    return {"pack_calls": calls("repro.sched.pack"),
            "pack_p50_us": p50_us("repro.sched.pack"),
            "fit_calls": calls("repro.policy.fit"),
            "fit_p50_us": p50_us("repro.policy.fit"),
            "shrunk_slices": sum(counters.get("repro.policy.shrunk", [])),
            "backfill_scans": calls("repro.sim.backfill"),
            "backfilled_groups": sum(counters.get("repro.sim.backfilled",
                                                  []))}


def run(ctx) -> dict:
    if not ctx.trace:
        return decide.run(ctx)
    from repro import spans

    start = ctx.window_start

    def window_start():
        spans.reset()
        start()

    ctx.window_start = window_start
    spans.reset()
    spans.enable()
    try:
        out = decide.run(ctx)
    finally:
        spans.disable()
    out["counters"].update(span_counters(spans.summary(), spans.counters()))
    spans.reset()
    return out

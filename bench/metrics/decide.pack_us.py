"""Median host wall time of one ``repro.sched.pack`` span (the window's
features, validity and context block packed for the episode), in
microseconds, over every call of the window; none where the program has
no such span."""


def read(run: dict):
    c = run["counters"]
    return c["pack_p50_us"] if c.get("pack_calls") else None

"""Median host wall time of one ``repro.policy.fit`` span (the plan's
dedicated slices shrunk to their jobs' requested widths), in
microseconds, over every call of the window; none where the program has
no such span."""


def read(run: dict):
    c = run["counters"]
    return c["fit_p50_us"] if c.get("fit_calls") else None

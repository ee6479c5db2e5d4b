"""Median host wall time of one ``RLDispatchPolicy.decide`` call, in
milliseconds, over every call of the window."""


def read(run: dict):
    c = run["counters"]
    return c["decide_p50_ms"] if c.get("decide_calls") else None

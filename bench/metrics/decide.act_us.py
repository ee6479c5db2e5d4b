"""Median host wall time of one ``DQNAgent.act`` call on the dispatch
path (observation to the device, forward pass, action back), in
microseconds, over every call of the window."""


def read(run: dict):
    c = run["counters"]
    return c["act_p50_us"] if c.get("act_calls") else None

"""Median host wall time of one ``DQNAgent.greedy_episode`` call on the
dispatch path (the packed window to the device, the episode's scan, the
actions back), in microseconds, over every call of the window."""


def read(run: dict):
    c = run["counters"]
    return c["episode_p50_us"] if c.get("episode_calls") else None

"""Poisson arrivals of full-pod requests: a frozen copy of the program's
Poisson generator over the zoo.

Traffic keys: ``load`` (offered solo work per full-pod equivalent of the
fleet), ``mix`` (class mix) and ``arrivals`` per trace.
"""
from __future__ import annotations

import numpy as np

from bench.lib.traffic import job_probs, rate
from bench.reference.model import N_UNITS


def poisson(zoo: list[dict], n: int, load: float, mix: str, seed: int,
            capacity: float) -> tuple[np.ndarray, np.ndarray]:
    """Arrival times (s) and zoo indices of one Poisson trace."""
    rng = np.random.default_rng(seed)
    times = np.cumsum(rng.exponential(1.0 / rate(zoo, load, capacity),
                                      size=n))
    picks = rng.choice(len(zoo), size=n, p=job_probs(zoo, mix))
    return times, picks


def trace(traffic: dict, zoo: list[dict], seed: int, capacity: float):
    times, picks = poisson(zoo, traffic["arrivals"], traffic["load"],
                           traffic["mix"], seed, capacity)
    return times, picks, np.full(len(picks), N_UNITS, np.int64)

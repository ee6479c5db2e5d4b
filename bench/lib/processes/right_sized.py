"""Poisson arrivals of MISO-style right-sized slice requests: a frozen
restatement of the program's fragmented-trace generator over the zoo.

Each arrival draws a job by the class mix, then a tolerance from
``tols``, and asks for the narrowest slice width of 1, 2 or 4 units whose
solo step time stays within that tolerance of the full-pod step time
(else the full pod).  Draw order: the picks, then one tolerance index per
arrival, then the exponential gaps.

Traffic keys: ``load`` (offered solo work per full-pod equivalent of the
fleet), ``mix`` (class mix), ``arrivals`` per trace and ``tols``.
"""
from __future__ import annotations

import numpy as np

from bench.lib.traffic import job_probs, rate
from bench.reference.model import N_UNITS, jobs_from_snapshot

NARROW = (1, 2, 4)


def right_size(job, tol: float) -> int:
    """Narrowest width whose solo step time is within ``tol`` of the
    full pod's."""
    full = job.step_time(N_UNITS)
    for u in NARROW:
        if job.step_time(u) <= tol * full:
            return u
    return N_UNITS


def right_sized(zoo: list[dict], n: int, load: float, mix: str,
                tols: tuple[float, ...], seed: int, capacity: float
                ) -> tuple[np.ndarray, np.ndarray, np.ndarray]:
    """Arrival times (s), zoo indices and requested widths of one trace."""
    rng = np.random.default_rng(seed)
    picks = rng.choice(len(zoo), size=n, p=job_probs(zoo, mix))
    tol_idx = rng.integers(0, len(tols), size=n)
    times = np.cumsum(rng.exponential(1.0 / rate(zoo, load, capacity),
                                      size=n))
    jobs = jobs_from_snapshot(zoo)
    width = {(p, t): right_size(jobs[p], tols[t])
             for p in range(len(zoo)) for t in range(len(tols))}
    units = np.asarray([width[p, t] for p, t in zip(picks.tolist(),
                                                      tol_idx.tolist())],
                       np.int64)
    return times, picks, units


def trace(traffic: dict, zoo: list[dict], seed: int, capacity: float):
    return right_sized(zoo, traffic["arrivals"], traffic["load"],
                       traffic["mix"], tuple(traffic["tols"]), seed,
                       capacity)

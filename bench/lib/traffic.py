"""The benchmark's traffic generator: Poisson arrivals over the frozen zoo.

A frozen copy of the Poisson arrival process and the paper's §V-A2 class
mixes ("balanced": CI/MI/US equally likely; "ci"/"mi"/"us": 50% the named
class, 25% each other), so a traffic mix keeps its arrivals when the
program's own generators change.  Job solo times and classes come from
the zoo snapshot (``bench/data/zoo.json``), not from the program's
performance model.

A traffic file (``bench/traffic/<name>.json``) gives ``process``
("poisson"), ``load`` (offered solo work per full-pod equivalent of the
fleet), ``mix``, ``arrivals`` per trace and ``pool`` (traces made per
run, served in turn).
"""
from __future__ import annotations

import json
from pathlib import Path

import numpy as np

ZOO = Path(__file__).resolve().parents[1] / "data" / "zoo.json"
_CLASS_ORDER = ("CI", "MI", "US")


def load_zoo() -> list[dict]:
    return json.loads(ZOO.read_text())["jobs"]


def _class_weights(mix: str) -> dict[str, float]:
    if mix == "balanced":
        return {c: 1 / 3 for c in _CLASS_ORDER}
    dom = mix.upper()
    if dom not in _CLASS_ORDER:
        raise ValueError(f"unknown class mix {mix!r}")
    return {c: 0.5 if c == dom else 0.25 for c in _CLASS_ORDER}


def job_probs(zoo: list[dict], mix: str) -> np.ndarray:
    """Class weight split evenly over the class's jobs; absent classes
    give their weight to the others in proportion."""
    w = _class_weights(mix)
    by_cls = {c: 0 for c in _CLASS_ORDER}
    for j in zoo:
        by_cls[j["job_class"]] += 1
    p = np.array([w[j["job_class"]] / by_cls[j["job_class"]] for j in zoo])
    return p / p.sum()


def rate(zoo: list[dict], load: float, capacity: float) -> float:
    """Arrivals/s submitting ``load * capacity`` pods' worth of solo work."""
    return capacity * load / float(np.mean([j["solo_time_s"] for j in zoo]))


def poisson(zoo: list[dict], n: int, load: float, mix: str, seed: int,
            capacity: float) -> tuple[np.ndarray, np.ndarray]:
    """Arrival times (s) and zoo indices of one Poisson trace."""
    rng = np.random.default_rng(seed)
    times = np.cumsum(rng.exponential(1.0 / rate(zoo, load, capacity),
                                      size=n))
    picks = rng.choice(len(zoo), size=n, p=job_probs(zoo, mix))
    return times, picks


def binary(row: dict) -> str:
    return f"bin://{row['name']}"


def trace_seeds(seed: int, pool: int) -> list[int]:
    """The pool's trace seeds, drawn from the run's ``--seed``."""
    ss = np.random.SeedSequence(seed % 2**64)
    return [int(s) for s in ss.generate_state(pool, np.uint32)]


def make_pool(traffic: dict, zoo: list[dict], seed: int,
              capacity: float) -> list[tuple[np.ndarray, np.ndarray]]:
    if traffic["process"] != "poisson":
        raise ValueError(f"unknown arrival process {traffic['process']!r}")
    return [poisson(zoo, traffic["arrivals"], traffic["load"],
                    traffic["mix"], s, capacity)
            for s in trace_seeds(seed, traffic["pool"])]

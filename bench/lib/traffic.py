"""The benchmark's traffic: arrival processes over the frozen zoo.

A traffic file (``bench/traffic/<name>.json``) names its arrival
``process``; the process is the module ``bench/lib/processes/<process>.py``,
found by name, whose ``trace(traffic, zoo, seed, capacity)`` turns the
file's parameters, the zoo, one trace seed and the fleet's capacity (in
full-pod equivalents) into one :class:`Trace`.  Every traffic file also
gives ``arrivals`` per trace and ``pool`` (traces made per run, served in
turn); the rest of its keys are the process's own.

Job solo times and classes come from the zoo snapshot
(``bench/data/zoo.json``), not from the program's performance model, and
the class mixes are frozen copies of the paper's §V-A2 recipes
("balanced": CI/MI/US equally likely; "ci"/"mi"/"us": 50% the named
class, 25% each other), so a traffic mix keeps its arrivals when the
program's own generators change.
"""
from __future__ import annotations

import importlib.util
import json
from pathlib import Path
from typing import NamedTuple

import numpy as np

from bench.reference.model import N_UNITS

BENCH = Path(__file__).resolve().parents[1]
ZOO = BENCH / "data" / "zoo.json"
PROCESSES = BENCH / "lib" / "processes"
_CLASS_ORDER = ("CI", "MI", "US")


class Trace(NamedTuple):
    """One trace: arrival times (float64 s), zoo indices, and the slice
    width each arrival requests (``N_UNITS`` for a full-pod request)."""

    times: np.ndarray
    picks: np.ndarray
    units: np.ndarray


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


def variant(row: dict, units: int) -> dict:
    """The zoo row as it is submitted at ``units`` slice units: the row
    itself at full width; narrower, a variant named ``<name>@u<units>``
    with ``meta["units"]``, so the profile repository keeps each width as
    its own application."""
    if units >= N_UNITS:
        return row
    return dict(row, name=f"{row['name']}@u{units}",
                meta={**row["meta"], "units": int(units)})


def binary(row: dict) -> str:
    return f"bin://{row['name']}"


def trace_seeds(seed: int, pool: int) -> list[int]:
    """The pool's trace seeds, drawn from the run's ``--seed``."""
    ss = np.random.SeedSequence(seed % 2**64)
    return [int(s) for s in ss.generate_state(pool, np.uint32)]


def load_process(name: str):
    """The arrival process module ``bench/lib/processes/<name>.py``."""
    path = PROCESSES / f"{name}.py"
    if not path.is_file():
        raise ValueError(f"unknown arrival process {name!r}")
    spec = importlib.util.spec_from_file_location(f"bench_process_{name}",
                                                  path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def make_pool(traffic: dict, zoo: list[dict], seed: int,
              capacity: float) -> list[Trace]:
    process = load_process(traffic["process"])
    return [Trace(*process.trace(traffic, zoo, s, capacity))
            for s in trace_seeds(seed, traffic["pool"])]

"""The benchmark's frozen traffic generator reproduces the program's
Poisson generator as it stood when the benchmark was defined: the
checked-in fixture was made by ``repro.online.traces.poisson_trace`` over
``make_zoo()``, and this test reads only the fixture, not the program.
Arrival processes are found by name, and the pool of ``pod_decide_rl``
stays what it was when the Poisson generator moved into its process
file."""
from __future__ import annotations

import hashlib
import json
from pathlib import Path

import numpy as np
import pytest

from bench.lib import traffic

HERE = Path(__file__).resolve().parent
FIXTURE = json.loads((HERE / "poisson_fixture.json").read_text())
BIG_SEED = 2**31 + 271828
# sha256 over each trace's float64 times then int64 zoo indices, the
# pool of pod_decide_rl (traffic poisson_pod, one pod) at BIG_SEED, as the
# generator made it before it moved into bench/lib/processes/poisson.py
POD_POOL_SHA256 = ("3cab2a2d2aa250179014aca77ce3586e"
                   "14df89b68f2c0f66cceb56132c75be78")


def test_poisson_copy_reproduces_the_fixture():
    zoo = traffic.load_zoo()
    poisson = traffic.load_process("poisson").poisson
    times, picks = poisson(zoo, FIXTURE["n"], FIXTURE["load"],
                           FIXTURE["mix"], FIXTURE["seed"],
                           FIXTURE["capacity"])
    want_t = [a[0] for a in FIXTURE["arrivals"]]
    want_b = [a[1] for a in FIXTURE["arrivals"]]
    assert times.tolist() == want_t
    assert [traffic.binary(zoo[p]) for p in picks] == want_b


def test_pool_is_fixed_by_the_seed_and_differs_across_seeds():
    zoo = traffic.load_zoo()
    spec = {"process": "poisson", "load": 0.85, "mix": "balanced",
            "arrivals": 50, "pool": 3}
    big = 2**31 + 12345
    a = traffic.make_pool(spec, zoo, big, 4.0)
    b = traffic.make_pool(spec, zoo, big, 4.0)
    c = traffic.make_pool(spec, zoo, big + 1, 4.0)
    assert len(a) == 3
    for ta, tb in zip(a, b):
        assert all(np.array_equal(x, y) for x, y in zip(ta, tb))
    assert not np.array_equal(a[0].times, c[0].times)


def test_class_mix_weights():
    zoo = traffic.load_zoo()
    p = traffic.job_probs(zoo, "ci")
    share = {c: sum(pi for pi, j in zip(p, zoo) if j["job_class"] == c)
             for c in ("CI", "MI", "US")}
    assert abs(share["CI"] - 0.5) < 1e-12
    assert abs(share["MI"] - 0.25) < 1e-12


def test_poisson_pool_through_the_lookup_is_unchanged():
    zoo = traffic.load_zoo()
    spec = {"process": "poisson", "load": FIXTURE["load"],
            "mix": FIXTURE["mix"], "arrivals": FIXTURE["n"], "pool": 1}
    tr = traffic.Trace(*traffic.load_process(spec["process"]).trace(
        spec, zoo, FIXTURE["seed"], FIXTURE["capacity"]))
    assert tr.times.tolist() == [a[0] for a in FIXTURE["arrivals"]]
    assert ([traffic.binary(zoo[p]) for p in tr.picks]
            == [a[1] for a in FIXTURE["arrivals"]])
    assert (tr.units == 8).all()

    spec = json.loads((HERE.parent / "traffic" / "poisson_pod.json")
                      .read_text())
    pool = traffic.make_pool(spec, zoo, BIG_SEED, 1.0)
    h = hashlib.sha256()
    for tr in pool:
        assert tr.times.dtype == np.float64 and (tr.units == 8).all()
        h.update(np.asarray(tr.times, np.float64).tobytes())
        h.update(np.asarray(tr.picks, np.int64).tobytes())
    assert h.hexdigest() == POD_POOL_SHA256


def test_unknown_process_is_refused():
    with pytest.raises(ValueError, match="unknown arrival process"):
        traffic.make_pool({"process": "no_such_process", "pool": 1},
                          traffic.load_zoo(), 1, 1.0)

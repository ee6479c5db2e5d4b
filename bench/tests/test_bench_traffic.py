"""The benchmark's frozen traffic generator reproduces the program's
Poisson generator as it stood when the benchmark was defined: the
checked-in fixture was made by ``repro.online.traces.poisson_trace`` over
``make_zoo()``, and this test reads only the fixture, not the program."""
from __future__ import annotations

import json
from pathlib import Path

import numpy as np

from bench.lib import traffic

FIXTURE = json.loads((Path(__file__).resolve().parent
                      / "poisson_fixture.json").read_text())


def test_poisson_copy_reproduces_the_fixture():
    zoo = traffic.load_zoo()
    times, picks = traffic.poisson(zoo, FIXTURE["n"], FIXTURE["load"],
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
    for (ta, pa), (tb, pb) in zip(a, b):
        assert np.array_equal(ta, tb) and np.array_equal(pa, pb)
    assert not np.array_equal(a[0][0], c[0][0])


def test_class_mix_weights():
    zoo = traffic.load_zoo()
    p = traffic.job_probs(zoo, "ci")
    share = {c: sum(pi for pi, j in zip(p, zoo) if j["job_class"] == c)
             for c in ("CI", "MI", "US")}
    assert abs(share["CI"] - 0.5) < 1e-12
    assert abs(share["MI"] - 0.25) < 1e-12

"""Telemetry layer invariants (docs/observability.md).

Four guarantees:

* **span chains** — on a drained trace every arrived job's lifecycle
  chain ``arrive -> window -> place`` completes in order and its claim
  reaches ``free`` at the predicted end;
* **aggregate fidelity** — the streaming registry's counters and the
  vectorized engine's in-graph ``MetricsState`` agree with each other
  (heap-vs-vec parity) and with the post-hoc ``SimResult.summary()``;
  the bucketed histogram matches the numpy reference;
* **observes, never steers** — enabling telemetry changes no decision:
  heap ``SimResult``\\ s and vectorized summaries are bit-identical with
  the flag on and off, and the scanned training engine's parameter
  trajectory is exactly unchanged under ``TrainConfig(telemetry=True)``;
* **drift signals** — the EMA monitor seeds, fires on mix-entropy /
  idle-fraction shifts, respects ``min_arrivals``, and rebases.
"""
import json

import jax
import numpy as np
import pytest

from repro.core import EnvConfig, TrainConfig, make_zoo, train_agent
from repro.core.agent import DQNConfig
from repro.online import (
    ClusterSimulator, DriftMonitor, GreedyPackerPolicy, OnlineRetrainer,
    RLDispatchPolicy, SimConfig, TRACE_FAMILIES, Telemetry,
    TimeSharingPolicy, VectorizedClusterSimulator, WAIT_BUCKETS_S,
)
from repro.online.telemetry import Histogram, entropy_bits
from repro.online.vecsim import metrics_dict

ZOO = make_zoo()

_ENGINES: dict = {}


def _vec_engine(window=8, capacity=96, telemetry=False):
    key = (window, capacity, telemetry)
    if key not in _ENGINES:
        _ENGINES[key] = VectorizedClusterSimulator(
            TimeSharingPolicy(), window=window, capacity=capacity,
            telemetry=telemetry)
    return _ENGINES[key]


def _trace(family="poisson", n=40, seed=3, **kw):
    return TRACE_FAMILIES[family](ZOO, n=n, load=1.3, seed=seed, **kw)


# ---------------------------------------------------------------------------
# Span chains
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("pods", [(8,), (8, 4)])
def test_span_chain_completes_for_every_job(pods):
    tel = Telemetry()
    cfg = SimConfig(window=8, pods=pods, router="hash")
    res = ClusterSimulator(GreedyPackerPolicy(), cfg, telemetry=tel).run(
        _trace(n=40))
    spans = tel.recorder.job_spans()
    assert len(spans) == len(res.jobs) == 40
    for rec in res.jobs:
        s = spans[rec.idx]
        assert s["arrive"] == rec.arrival
        assert s["window"] is not None and s["window"] >= s["arrive"]
        assert s["place"] is not None and s["place"] >= s["window"]
        assert s["run_end"] is not None and s["run_end"] > s["place"]
        # concurrent mode: the claim's FREE lands exactly at run_end
        assert s["free"] == pytest.approx(s["run_end"])
        assert s["pod"] == rec.pod
        assert s["backfilled"] == rec.backfilled


def test_span_events_are_ordered_and_jsonable(tmp_path):
    tel = Telemetry()
    ClusterSimulator(TimeSharingPolicy(), window=8, telemetry=tel).run(
        _trace(family="fragmented", n=30))
    ts = [e["t_s"] for e in tel.recorder.events]
    assert ts == sorted(ts)
    p = tmp_path / "events.jsonl"
    tel.recorder.write_jsonl(str(p))
    lines = [json.loads(line) for line in p.read_text().splitlines()]
    assert len(lines) == len(tel.recorder)
    assert {line["kind"] for line in lines} >= {"arrive", "window",
                                                "place", "free"}


def test_chrome_trace_is_valid_trace_event_json(tmp_path):
    tel = Telemetry()
    cfg = SimConfig(window=8, pods=(8, 4), router="hash")
    ClusterSimulator(GreedyPackerPolicy(), cfg, telemetry=tel).run(
        _trace(n=30))
    p = tmp_path / "trace.json"
    tel.recorder.write_chrome_trace(str(p), pods=(8, 4))
    doc = json.loads(p.read_text())
    evs = doc["traceEvents"]
    assert {e["ph"] for e in evs} <= {"M", "X", "i"}
    xs = [e for e in evs if e["ph"] == "X"]
    # one complete event per claimed unit per placement
    claimed_units = sum(sum(w for _, w in e["slices"])
                       for e in tel.recorder.by_kind("place"))
    assert len(xs) == claimed_units
    for e in xs:
        assert e["dur"] >= 0 and e["pid"] in (0, 1)


# ---------------------------------------------------------------------------
# Aggregate fidelity
# ---------------------------------------------------------------------------


def test_histogram_matches_numpy_reference():
    rng = np.random.default_rng(0)
    xs = rng.lognormal(mean=4.0, sigma=2.0, size=500)
    h = Histogram("wait_s", WAIT_BUCKETS_S)
    for x in xs:
        h.observe(float(x))
    edges = np.asarray(WAIT_BUCKETS_S)
    ref = np.array([np.count_nonzero(
        (xs <= edges[i]) & ((xs > edges[i - 1]) if i else True))
        for i in range(len(edges))] + [np.count_nonzero(xs > edges[-1])])
    assert h.counts == ref.tolist()
    assert h.count == 500 and h.sum == pytest.approx(xs.sum())
    assert h.mean == pytest.approx(xs.mean())
    # bucket-interpolated percentile lands within one bucket of the truth
    for q in (50, 95, 99):
        est, true = h.percentile(q), float(np.percentile(xs, q))
        idx = int(np.searchsorted(edges, true, side="left"))
        lo = 0.0 if idx == 0 else float(edges[idx - 1])
        hi = float(edges[idx]) if idx < len(edges) else true
        assert lo <= est <= max(hi, est)


def test_registry_counters_match_summary():
    tel = Telemetry()
    cfg = SimConfig(window=8, pods=(8, 4), router="hash")
    res = ClusterSimulator(GreedyPackerPolicy(), cfg, telemetry=tel).run(
        _trace(family="fragmented", n=40))
    summ = res.summary()
    m = {d["name"]: d for d in tel.metrics.to_dicts()}
    assert m["jobs_arrived"]["value"] == summ["jobs"]
    assert m["windows_formed"]["value"] == summ["dispatches"]
    assert m["groups_placed"]["value"] == summ["groups"]
    assert m["backfills"]["value"] == summ["backfills"]
    assert m["refits"]["value"] == summ["refits"]
    assert m["busy_unit_s"]["value"] == pytest.approx(
        sum(res.slice_busy_s), rel=1e-9)
    assert m["wait_s"]["count"] == summ["jobs"]
    assert m["wait_s"]["sum"] == pytest.approx(
        sum(r.wait for r in res.jobs), rel=1e-9)


@pytest.mark.parametrize("seed", [1, 5, 11])
def test_heap_vs_vectorized_metric_parity(seed):
    trace = _trace(n=40, seed=seed)
    tel = Telemetry()
    ClusterSimulator(TimeSharingPolicy(), window=8, telemetry=tel).run(trace)
    eng = _vec_engine(telemetry=True)
    eng.run(trace)
    vm = eng.last_metrics
    hh = tel.metrics.histogram("wait_s")
    assert vm["wait_s"]["counts"] == hh.counts
    assert vm["wait_s"]["count"] == hh.count
    assert vm["groups_placed"] == tel.metrics.counter("groups_placed").value
    assert vm["wait_s"]["sum"] == pytest.approx(hh.sum, rel=1e-3, abs=0.5)
    assert vm["queue_depth_integral_s"] == pytest.approx(
        tel.metrics.counter("queue_depth_integral_s").value,
        rel=1e-3, abs=1.0)
    assert vm["busy_unit_s"] == pytest.approx(
        tel.metrics.counter("busy_unit_s").value, rel=1e-3, abs=1.0)


def test_rl_vectorized_metrics_match_summary():
    """The in-graph RL serving path feeds the same MetricsState lanes the
    time-sharing path does: its streaming counters must agree with the
    post-hoc ``SimResult.summary()`` exactly like the heap path's
    registry does."""
    from repro.core import CoScheduleEnv
    from repro.core.agent import DQNAgent

    env_cfg = EnvConfig()
    env = CoScheduleEnv(env_cfg)
    policy = RLDispatchPolicy(
        DQNAgent(env.state_dim, env.n_actions, seed=0), env_cfg)
    eng = VectorizedClusterSimulator(policy, window=8, capacity=96,
                                     telemetry=True)
    res = eng.run(_trace(n=40, seed=5))
    summ = res.summary()
    vm = eng.last_metrics
    assert vm["wait_s"]["count"] == summ["jobs"]
    assert vm["wait_s"]["sum"] == pytest.approx(
        sum(r.wait for r in res.jobs), rel=1e-3, abs=0.5)
    assert vm["groups_placed"] == summ["groups"]
    assert vm["busy_unit_s"] == pytest.approx(
        sum(res.slice_busy_s), rel=1e-3, abs=1.0)
    # streaming histogram == numpy reference over the same records
    ref = Histogram("wait_s", WAIT_BUCKETS_S)
    for r in res.jobs:
        ref.observe(r.wait)
    assert vm["wait_s"]["counts"] == ref.counts


def test_sweep_with_metrics_returns_lane_tensors():
    traces = [_trace(n=30, seed=s) for s in (0, 1, 2)]
    eng = _vec_engine(telemetry=True)
    summ, ms = eng.sweep(traces, with_metrics=True)
    assert ms.wait_hist.shape == (3, len(WAIT_BUCKETS_S) + 1)
    for i in range(3):
        lane = metrics_dict(jax.tree.map(lambda x: x[i], ms))
        assert lane["wait_s"]["count"] == 30
    with pytest.raises(ValueError):
        _vec_engine(telemetry=False).sweep(traces, with_metrics=True)


# ---------------------------------------------------------------------------
# Observes, never steers
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("family,pods", [("poisson", (8,)),
                                         ("fragmented", (8, 4))])
def test_heap_disabled_vs_enabled_results_identical(family, pods):
    trace = _trace(family=family, n=40)
    cfg = SimConfig(window=8, pods=pods, router="hash")
    r0 = ClusterSimulator(GreedyPackerPolicy(), cfg).run(trace)
    r1 = ClusterSimulator(GreedyPackerPolicy(), cfg,
                          telemetry=Telemetry()).run(trace)
    assert r0.summary() == r1.summary()
    for a, b in zip(r0.jobs, r1.jobs):
        assert (a.name, a.wait, a.turnaround, a.pod, a.units,
                a.backfilled) == (b.name, b.wait, b.turnaround, b.pod,
                                  b.units, b.backfilled)


def test_vectorized_disabled_vs_enabled_summaries_identical():
    trace = _trace(n=40)
    s0 = _vec_engine(telemetry=False).run(trace).summary()
    s1 = _vec_engine(telemetry=True).run(trace).summary()
    assert s0 == s1


def test_training_telemetry_keeps_parameter_trajectory():
    env_cfg = EnvConfig(window=6, c_max=3)
    dqn = DQNConfig(eps_decay_steps=200)
    mk = lambda tele: TrainConfig(episodes=40, eval_every=20, seed=7,  # noqa: E731
                                  dqn=dqn, telemetry=tele)
    a0, h0 = train_agent(ZOO, env_cfg, mk(False))
    a1, h1 = train_agent(ZOO, env_cfg, mk(True))
    for x, y in zip(jax.tree.leaves(a0.params), jax.tree.leaves(a1.params)):
        np.testing.assert_array_equal(np.asarray(x), np.asarray(y))
    for r0, r1 in zip(h0, h1):
        assert r0["eval_throughput"] == r1["eval_throughput"]
        assert r0["episode"] == r1["episode"]
    # telemetry-only fields exist and are finite
    assert all(np.isfinite(r["loss"]) and np.isfinite(r["grad_norm"])
               for r in h1)
    assert all("loss" not in r for r in h0)


# ---------------------------------------------------------------------------
# Drift signals
# ---------------------------------------------------------------------------


def test_entropy_bits():
    assert entropy_bits({"a": 8}) == 0.0
    assert entropy_bits({"a": 4, "b": 4}) == pytest.approx(1.0)
    assert entropy_bits({}) == 0.0


def test_drift_monitor_seeds_then_fires_on_mix_shift():
    mon = DriftMonitor()
    flat = {"CI": 4, "MI": 4, "US": 4}
    widths = {8: 6, 1: 6}
    assert not mon.observe(flat, widths, 0.2)["drift"]       # seeds
    assert not mon.observe(flat, widths, 0.2)["drift"]       # same regime
    v = mon.observe({"US": 12}, {1: 12}, 0.2)                # mix collapses
    assert v["drift"]
    assert set(v["reasons"]) >= {"class_entropy", "width_entropy"}


def test_drift_monitor_idle_rise_and_min_arrivals():
    mon = DriftMonitor()
    mon.observe({"CI": 8}, {8: 8}, 0.1)
    v = mon.observe({"CI": 8}, {8: 8}, 0.1 + mon.idle_threshold + 0.05)
    assert v["drift"] and v["reasons"] == ["idle_slice_frac"]
    thin = DriftMonitor()
    thin.observe({"CI": 8}, {8: 8}, 0.1)
    assert not thin.observe({"US": 2}, {1: 2}, 0.9)["drift"]  # < min_arrivals


def test_drift_monitor_rebase_resets_baseline():
    mon = DriftMonitor()
    mon.observe({"CI": 4, "MI": 4}, {8: 4, 1: 4}, 0.1)
    assert mon.observe({"US": 8}, {1: 8}, 0.1)["drift"]
    mon.rebase()
    assert not mon.observe({"US": 8}, {1: 8}, 0.1)["drift"]   # new normal
    assert not mon.observe({"US": 8}, {1: 8}, 0.1)["drift"]


def test_retrainer_rejects_unknown_trigger():
    pol = RLDispatchPolicy.__new__(RLDispatchPolicy)  # no agent needed
    with pytest.raises(ValueError):
        OnlineRetrainer(policy=pol, train_cfg=TrainConfig(episodes=1),
                        interval_s=60.0, trigger="sometimes")

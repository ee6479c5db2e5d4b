"""Heap-vs-vectorized engine parity on randomized traces.

The vectorized engine (``repro.online.vecsim``) must be a drop-in for the
Python event heap on everything it claims to serve: randomized
concurrent-mode traces produce matching per-job records (wait /
turnaround / slice range / backfill flag), matching dispatch/backfill
counts, and a matching placement-ordered timeline.  Decisions are
compared exactly; times to f32 resolution (the device engine carries f32
lanes, the heap is the f64 reference).  Capacity overflow must raise
eagerly — a silently dropped arrival would corrupt every downstream
metric.
"""
import jax
import numpy as np
import pytest

try:
    from hypothesis import given, settings, strategies as st
except ImportError:
    from _hypothesis_compat import given, settings, st

from strategies import ZOO, assert_parity, close, make_trace, trace_specs

from repro.online import (
    Arrival, ClusterSimulator, GreedyPackerPolicy, TRACE_FAMILIES,
    TimeSharingPolicy, VectorizedClusterSimulator,
)

# engines cached per configuration: each instance owns its jitted program,
# so reuse across examples keeps the suite's compile count bounded
_ENGINES: dict = {}


def _vec_engine(window=8, backfill=True, capacity=96):
    key = (window, backfill, capacity)
    if key not in _ENGINES:
        _ENGINES[key] = VectorizedClusterSimulator(
            TimeSharingPolicy(), window=window, backfill=backfill,
            capacity=capacity)
    return _ENGINES[key]


def _heap(trace, window=8, backfill=True):
    return ClusterSimulator(TimeSharingPolicy(), window=window,
                            backfill=backfill).run(trace)


# parity helpers shared with test_fleet / test_parity_fuzz
_close = close
_assert_parity = assert_parity


@settings(max_examples=20, deadline=None, derandomize=True)
@given(spec=trace_specs())
def test_parity_randomized_traces(spec):
    trace = make_trace(*spec)
    _assert_parity(_heap(trace), _vec_engine().run(trace))


def test_parity_backfill_heavy():
    """Overloaded fragmented traces exercise the EASY-backfill scan; the
    engines must agree on which groups jump the blocked head."""
    total = 0
    for seed in range(4):
        trace = TRACE_FAMILIES["fragmented"](ZOO, n=40, load=1.6, seed=seed)
        h = _heap(trace)
        _assert_parity(h, _vec_engine().run(trace))
        total += h.backfills
    assert total > 0  # the property must actually be exercised


@pytest.mark.parametrize("window", [2, 4])
def test_parity_small_windows(window):
    trace = TRACE_FAMILIES["mmpp"](ZOO, n=30, load=1.3, seed=7)
    _assert_parity(_heap(trace, window=window),
                   _vec_engine(window=window).run(trace))


def test_parity_backfill_disabled():
    trace = TRACE_FAMILIES["fragmented"](ZOO, n=40, load=1.6, seed=1)
    _assert_parity(_heap(trace, backfill=False),
                   _vec_engine(backfill=False).run(trace))


def test_coincident_arrivals_share_one_dispatch_window():
    trace = [Arrival(t=10.0, binary=f"bin://co{i}", profile=ZOO[i])
             for i in range(4)]
    v = _vec_engine(window=4).run(trace)
    _assert_parity(_heap(trace, window=4), v)
    assert v.dispatches == 1


def test_percentile_fields_populated_by_both_engines():
    """Satellite metric: p50/p99 wait in summary(), equal to numpy's
    percentile of the per-job waits, from either engine."""
    trace = TRACE_FAMILIES["poisson"](ZOO, n=40, load=1.4, seed=9)
    for res in (_heap(trace), _vec_engine().run(trace)):
        s = res.summary()
        waits = [j.wait for j in res.jobs]
        assert _close(s["p50_wait_s"], float(np.percentile(waits, 50)))
        assert _close(s["p99_wait_s"], float(np.percentile(waits, 99)))
        assert s["p50_wait_s"] <= s["p99_wait_s"]


def test_sweep_rows_match_single_trace_runs():
    """Each row of the vmapped sweep equals the corresponding single-trace
    run — vmap must not change the program, only batch it."""
    eng = _vec_engine(capacity=64)
    traces = [TRACE_FAMILIES["poisson"](ZOO, n=24, load=1.2, seed=s)
              for s in range(4)]
    summ = eng.sweep(traces)
    for i, trace in enumerate(traces):
        res = eng.run(trace)
        s = res.summary()
        np.testing.assert_allclose(float(summ.makespan[i]), s["makespan_s"],
                                   rtol=1e-4)
        np.testing.assert_allclose(float(summ.mean_wait[i]), s["mean_wait_s"],
                                   rtol=1e-3, atol=1e-3)
        np.testing.assert_allclose(float(summ.p99_wait[i]), s["p99_wait_s"],
                                   rtol=1e-3, atol=1e-3)
        np.testing.assert_allclose(float(summ.throughput[i]), s["throughput"],
                                   rtol=1e-4)
        assert int(summ.dispatches[i]) == s["dispatches"]
        assert int(summ.backfills[i]) == res.backfills


def test_sweep_sharded_matches_unsharded():
    """``devices=jax.devices()`` shards the batch via pmap when the CI job
    forces 8 host devices (XLA_FLAGS=--xla_force_host_platform_device_count);
    on a single device it falls back to vmap.  Results must be identical."""
    eng = _vec_engine(capacity=64)
    traces = [TRACE_FAMILIES["diurnal"](ZOO, n=24, load=1.2, seed=s)
              for s in range(8)]
    base = eng.sweep(traces)
    shard = eng.sweep(traces, devices=jax.devices())
    for a, b in zip(base, shard):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-6, atol=1e-6)


def test_sweep_indivisible_across_devices_raises():
    """A batch that cannot be split evenly over ``devices`` is refused
    before any device call, never run quietly on one device."""
    traces = [TRACE_FAMILIES["poisson"](ZOO, n=8, load=1.2, seed=s)
              for s in range(3)]
    with pytest.raises(ValueError, match="does not divide"):
        _vec_engine(capacity=64).sweep(traces, devices=jax.devices()[:1] * 2)


def test_capacity_overflow_raises_eagerly():
    """A trace longer than the event table must raise before the device
    program runs — never silently drop arrivals."""
    trace = TRACE_FAMILIES["poisson"](ZOO, n=20, load=1.0, seed=0)
    eng = VectorizedClusterSimulator(TimeSharingPolicy(), capacity=16)
    with pytest.raises(ValueError, match="capacity"):
        eng.run(trace)
    with pytest.raises(ValueError, match="capacity"):
        eng.sweep([trace])


def test_error_lanes_raise():
    check = VectorizedClusterSimulator._check_err
    with pytest.raises(RuntimeError, match="ready ring"):
        check(1)
    with pytest.raises(RuntimeError, match="budget"):
        check(2)
    check(0)  # clean run is silent


def test_unsupported_policy_rejected():
    with pytest.raises(ValueError, match="TimeSharingPolicy or "
                                         "RLDispatchPolicy"):
        VectorizedClusterSimulator(GreedyPackerPolicy())


def test_empty_trace_and_empty_sweep():
    res = _vec_engine().run([])
    assert res.jobs == [] and res.makespan == 0.0
    with pytest.raises(ValueError, match="empty"):
        _vec_engine().sweep([])

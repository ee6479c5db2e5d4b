"""The trace reduction: busy time, idle share, operations and labelled
gaps, on a small profiler trace recorded on the CPU (checked in as
``data/cpu_trace.xplane.pb``: two jitted calls inside ``bench.unit`` spans
with a 20 ms ``bench.host_wait`` between them, all in ``bench.window``).

The CPU has no device plane, so the XLA client thread's operations stand
in for a device's ``XLA Ops`` line here."""
from __future__ import annotations

from pathlib import Path

import pytest

from bench.lib import trace as T

FIXTURE = Path(__file__).resolve().parent / "data" / "cpu_trace.xplane.pb"
_NOT_OPS = ("ThunkExecutor", "SlinkyThreadPool", "Threadpool", "end:")


@pytest.fixture(scope="module")
def events():
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(str(FIXTURE))
    ops, host = [], []
    for plane in pd.planes:
        for line in plane.lines:
            evs = [(e.name, e.start_ns, e.start_ns + e.duration_ns)
                   for e in line.events]
            if line.name.startswith("tf_XLAPjRtCpuClient"):
                ops += [e for e in evs if not e[0].startswith(_NOT_OPS)]
            host += [e for e in evs if e[0].startswith("bench.")]
    assert ops and host
    return {"/device:TPU:0": ops}, host


def test_busy_time_is_the_union_of_operations(events):
    devices, host = events
    out = T.reduce(devices, host)
    lo, hi = next((s, e) for n, s, e in host if n == T.WINDOW)
    # sweep of +1/-1 edges: time with at least one operation running
    edges = sorted([(max(s, lo), 1) for _, s, e in devices["/device:TPU:0"]
                    if min(e, hi) > max(s, lo)]
                   + [(min(e, hi), -1) for _, s, e in devices["/device:TPU:0"]
                      if min(e, hi) > max(s, lo)])
    busy_ns, depth, prev = 0.0, 0, lo
    for t, d in edges:
        if depth > 0:
            busy_ns += t - prev
        depth, prev = depth + d, t
    assert out["window_s"] == pytest.approx((hi - lo) / 1e9)
    assert out["busy_s"] == pytest.approx(busy_ns / 1e9, rel=1e-12)
    assert 0.0 < out["busy_s"] < out["window_s"]


def test_longest_gap_is_labelled_by_the_host_span(events):
    out = T.reduce(*events)
    name, seconds = out["idle_gaps"][0]
    assert name == "bench.host_wait"
    assert 0.02 <= seconds < 0.03
    assert len(out["idle_gaps"]) <= 10 and len(out["device_ops"]) <= 10
    ops = dict(out["device_ops"])
    assert "dot_general.1" in ops
    assert sum(ops.values()) >= out["busy_s"] - 1e-9


def test_merge_gaps_and_labels():
    busy = T.merge([(5, 7), (0, 2), (1, 3), (9, 20)], 0, 10)
    assert busy == [(0, 3), (5, 7), (9, 10)]
    assert T.gaps(busy, 0, 12) == [(3, 5), (7, 9), (10, 12)]
    spans = [("bench.unit", 0, 12), ("bench.act", 6, 10)]
    assert T.label((7, 9), spans) == "bench.act"
    assert T.label((3, 5), spans) == "bench.unit"
    assert T.label((20, 30), spans) == "host outside any span"


def test_reduce_refuses_a_trace_without_window_or_device(events):
    devices, host = events
    with pytest.raises(ValueError):
        T.reduce(devices, [h for h in host if h[0] != T.WINDOW])
    with pytest.raises(ValueError):
        T.reduce({}, host)

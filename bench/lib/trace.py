"""Reduction of one profiler trace to device busy time, idle gaps and the
device operations that took most time.

The trace is the ``.xplane.pb`` JAX's profiler writes.  Device planes are
``/device:TPU:<n>``; their operations are the events of the ``XLA Ops``
line.  Host spans are the harness's own ``bench.*`` annotations on the
host plane.  All times share the trace's clock, and the window is the
harness's ``bench.window`` span.
"""
from __future__ import annotations

import re
from collections import defaultdict

_DEVICE = re.compile(r"^/device:TPU:\d+$")
OPS_LINE = "XLA Ops"
WINDOW = "bench.window"


def _events(line):
    for e in line.events:
        yield e.name, e.start_ns, e.start_ns + e.duration_ns


def read(path) -> tuple[dict[str, list], list]:
    """``({device plane: [(op, start_ns, end_ns)]}, [(span, start, end)])``."""
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(str(path))
    devices: dict[str, list] = {}
    host: list = []
    for plane in pd.planes:
        if _DEVICE.match(plane.name):
            ops = []
            for line in plane.lines:
                if line.name == OPS_LINE:
                    ops += list(_events(line))
            devices[plane.name] = ops
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                host += [ev for ev in _events(line)
                         if ev[0].startswith("bench.")]
    return devices, host


def merge(intervals, lo: float, hi: float) -> list[tuple[float, float]]:
    """Union of ``(start, end)`` intervals clipped to ``[lo, hi]``."""
    out: list[list[float]] = []
    for s, e in sorted((max(s, lo), min(e, hi)) for s, e in intervals):
        if e <= s:
            continue
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def gaps(busy: list[tuple[float, float]], lo: float, hi: float):
    """Idle intervals of ``[lo, hi]`` between merged busy intervals."""
    out, t = [], lo
    for s, e in busy:
        if s > t:
            out.append((t, s))
        t = max(t, e)
    if hi > t:
        out.append((t, hi))
    return out


def label(gap, spans) -> str:
    """The innermost host span covering the gap's midpoint."""
    mid = 0.5 * (gap[0] + gap[1])
    best, width = "host outside any span", float("inf")
    for name, s, e in spans:
        if s <= mid <= e and e - s < width:
            best, width = name, e - s
    return best


def reduce(devices: dict[str, list], host: list, top: int = 10) -> dict:
    """Busy and window seconds (busy averaged over the device planes), the
    operations with the most device time and the longest labelled gaps,
    all within the ``bench.window`` span."""
    windows = [(s, e) for name, s, e in host if name == WINDOW]
    if not windows:
        raise ValueError(f"no {WINDOW} span in the trace")
    lo, hi = windows[0]
    if not devices:
        raise ValueError("no TPU device plane in the trace")
    busy_ns, op_ns, all_gaps = 0.0, defaultdict(float), []
    spans = [h for h in host if h[0] != WINDOW]
    for ops in devices.values():
        busy = merge([(s, e) for _, s, e in ops], lo, hi)
        busy_ns += sum(e - s for s, e in busy)
        for name, s, e in ops:
            op_ns[name] += max(0.0, min(e, hi) - max(s, lo))
        all_gaps += gaps(busy, lo, hi)
    all_gaps.sort(key=lambda g: g[0] - g[1])
    return {
        "busy_s": busy_ns / len(devices) / 1e9,
        "window_s": (hi - lo) / 1e9,
        "device_ops": [[n, t / 1e9] for n, t in
                       sorted(op_ns.items(), key=lambda kv: -kv[1])[:top]],
        "idle_gaps": [[label(g, spans), (g[1] - g[0]) / 1e9]
                      for g in all_gaps[:top]],
    }

"""Pieces every driver shares: the device check, compile accounting, host
spans, the profiler window and memory readings."""
from __future__ import annotations

import contextlib
import json
import shutil
import tempfile
from pathlib import Path

import jax

PEAKS = Path(__file__).resolve().parents[1] / "peaks.json"

_COMPILE_EVENTS = frozenset({
    "/jax/core/compile/jaxpr_trace_duration",
    "/jax/core/compile/jaxpr_to_mlir_module_duration",
    "/jax/core/compile/backend_compile_duration",
})
_BACKEND_COMPILE = "/jax/core/compile/backend_compile_duration"
_CACHE_HIT = "/jax/compilation_cache/cache_hits"
_CACHE_MISS = "/jax/compilation_cache/cache_misses"


class NoChip(RuntimeError):
    """The process found no TPU, or fewer chips than the cell needs."""


def device_info(chips: int) -> dict:
    """Platform, kind and count of the devices JAX found.  Anything but
    enough TPU chips of a kind in ``peaks.json`` is refused: the benchmark
    never measures the CPU, nor a chip whose peaks it does not know."""
    devs = jax.devices()
    platform = devs[0].platform
    if platform != "tpu":
        raise NoChip(f"JAX found {platform}, not a TPU")
    if len(devs) < chips:
        raise NoChip(f"{len(devs)} chips found, the cell needs {chips}")
    kind = devs[0].device_kind
    peaks = json.loads(PEAKS.read_text())["devices"]
    if kind not in peaks:
        raise NoChip(f"device kind {kind!r} is not in {PEAKS.name}")
    return {"platform": platform, "kind": kind, "count": chips}


def memory_peak_bytes(count: int) -> int:
    """Peak bytes in use on the fullest of the first ``count`` devices."""
    peak = 0
    for d in jax.devices()[:count]:
        stats = d.memory_stats() or {}
        peak = max(peak, int(stats.get("peak_bytes_in_use", 0)))
    return peak


def union_s(spans) -> float:
    """Seconds covered by ``(start, end)`` spans, overlaps counted once."""
    total, reach = 0.0, float("-inf")
    for s, e in sorted(spans):
        if e > reach:
            total += e - max(s, reach)
            reach = e
    return total


class CompileWatch:
    """Compile spans, backend compiles and persistent-cache lookups that
    JAX reports through ``jax.monitoring`` while the process runs."""

    def __init__(self):
        self.spans: list[tuple[float, float]] = []
        self.backend_compiles = 0
        self.cache = {_CACHE_HIT: 0, _CACHE_MISS: 0}
        jax.monitoring.register_event_time_span_listener(self._on_span)
        jax.monitoring.register_event_listener(self._on_event)

    def _on_span(self, event: str, start: float, end: float, **_) -> None:
        if event in _COMPILE_EVENTS:
            self.spans.append((start, end))
        if event == _BACKEND_COMPILE:
            self.backend_compiles += 1

    def _on_event(self, event: str, **_) -> None:
        if event in self.cache:
            self.cache[event] += 1

    def mark(self) -> tuple[int, int]:
        return len(self.spans), self.backend_compiles

    def since(self, mark: tuple[int, int]) -> dict:
        n, b = mark
        return {"compile_s": union_s(self.spans[n:]),
                "traces": len(self.spans) - n,
                "backend_compiles": self.backend_compiles - b,
                "cache_hits": self.cache[_CACHE_HIT],
                "cache_misses": self.cache[_CACHE_MISS]}


class Spans:
    """Host spans of the harness, written into the profiler's trace when
    one is recording (``jax.profiler.TraceAnnotation``) and free of cost
    otherwise."""

    def __init__(self, on: bool):
        self.on = on

    def __call__(self, name: str):
        if self.on:
            return jax.profiler.TraceAnnotation(name)
        return contextlib.nullcontext()


class Profiler:
    """One profiler window; the trace is read back and deleted at stop."""

    def __init__(self):
        self.dir = tempfile.mkdtemp(prefix="bench_trace_")

    def start(self) -> None:
        # no Python tracer: it records every Python call of the host loop,
        # which the spans and the device planes do not need
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 1
        jax.profiler.start_trace(self.dir, profiler_options=opts)

    def stop(self) -> Path:
        jax.profiler.stop_trace()
        found = sorted(Path(self.dir).rglob("*.xplane.pb"))
        if not found:
            raise RuntimeError("the profiler wrote no trace")
        return found[-1]

    def close(self) -> None:
        shutil.rmtree(self.dir, ignore_errors=True)

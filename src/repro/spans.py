"""Wall-clock spans at the layer boundaries of the live dispatch path.

The heap serving loop marks where its time goes with ``span(name)``:
``ClusterSimulator.run`` -> ``_form_window`` -> ``DispatchPolicy.decide``
-> ``RLScheduler.schedule`` (the episode, the co-run guard) ->
``DQNAgent.greedy_episode`` (the window to the device, launch, actions
back).  The scalar training loop's ``DQNAgent.act`` marks its round trip
the same way.  The names are listed in ``docs/observability.md``; all
start with ``repro.``.

The recorder is off by default.  Off, :func:`span` returns one shared
no-op context manager: no clock read, no allocation, no JAX call.
:func:`enable` switches it on for the whole process.  On, every span keeps
its name, start and end (``time.perf_counter_ns``), the id of its parent
span and the id of its root, the outermost span open (one ``run()``, or
one ``decide()`` called on its own).  Each span also enters a
``jax.profiler.TraceAnnotation`` of the same name, so a ``jax.profiler``
trace shows it on the host plane, on the clock of the device's operations.

    from repro import spans

    spans.enable()
    ClusterSimulator(policy, cfg).run(trace)
    spans.summary()["repro.agent.episode"]   # count, total_s, self_s, median_us
    spans.reset()

Spans are kept in memory until :func:`reset`, and are recorded from the
one thread that serves (the nesting is a single stack).  Spans observe and
never steer: a run makes the same decisions with the recorder on or off.
"""
from __future__ import annotations

import itertools
import statistics
import time
from collections import defaultdict
from typing import NamedTuple


class Span(NamedTuple):
    """One finished span; ``parent`` is ``None`` for a root."""

    name: str
    start_ns: int
    end_ns: int
    id: int
    parent: int | None
    root: int


class _Off:
    __slots__ = ()

    def __enter__(self):
        return None

    def __exit__(self, *exc):
        return False


NOOP = _Off()

_on = False
_annotation = None          # jax.profiler.TraceAnnotation, bound by enable()
_ids = itertools.count()
_open: list = []            # the spans entered and not yet left, outermost first
_done: list[Span] = []
_counts: dict[str, list] = defaultdict(list)


class _Live:
    __slots__ = ("name", "id", "parent", "root", "ann", "start")

    def __init__(self, name: str):
        self.name = name

    def __enter__(self):
        top = _open[-1] if _open else None
        self.id = next(_ids)
        self.parent = None if top is None else top.id
        self.root = self.id if top is None else top.root
        _open.append(self)
        self.ann = _annotation(self.name)
        self.ann.__enter__()
        self.start = time.perf_counter_ns()
        return self

    def __exit__(self, *exc):
        end = time.perf_counter_ns()
        self.ann.__exit__(*exc)
        _open.pop()
        _done.append(Span(self.name, self.start, end, self.id, self.parent,
                          self.root))
        return False


def span(name: str):
    """A context manager timing ``name``; the shared no-op when off."""
    return _Live(name) if _on else NOOP


def count(name: str, value) -> None:
    """Record one sample of the counter ``name`` (nothing when off)."""
    if _on:
        _counts[name].append(value)


def enable() -> None:
    """Record spans and counters from now on, and annotate the profiler."""
    global _on, _annotation
    from jax.profiler import TraceAnnotation

    _annotation = TraceAnnotation
    _on = True


def disable() -> None:
    """Stop recording; what was recorded stays until :func:`reset`."""
    global _on
    _on = False


def reset() -> None:
    """Drop every recorded span and counter sample."""
    _done.clear()
    _counts.clear()


def records() -> list[Span]:
    """The finished spans, in the order they ended."""
    return list(_done)


def counters() -> dict[str, list]:
    """Every counter's samples, in the order they were recorded."""
    return {k: list(v) for k, v in _counts.items()}


def summary() -> dict[str, dict]:
    """Per span name: ``count``, ``total_s``, ``self_s`` (duration less the
    part under child spans) and ``median_us`` of one span."""
    child_ns: dict[int, int] = defaultdict(int)
    for s in _done:
        if s.parent is not None:
            child_ns[s.parent] += s.end_ns - s.start_ns
    by_name: dict[str, list[Span]] = defaultdict(list)
    for s in _done:
        by_name[s.name].append(s)
    out = {}
    for name, group in sorted(by_name.items()):
        durs = [s.end_ns - s.start_ns for s in group]
        total = sum(durs)
        covered = sum(child_ns.get(s.id, 0) for s in group)
        out[name] = {"count": len(group), "total_s": total / 1e9,
                     "self_s": (total - covered) / 1e9,
                     "median_us": statistics.median(durs) / 1e3}
    return out

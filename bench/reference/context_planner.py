"""Reference planner of an arrival-aware agent: :class:`RLPlanner` whose
observation carries the context block.

The agent's input is the window's profile rows and flags, as
:class:`RLPlanner` builds them, followed by ``N_UNITS + W + 1`` float32
values built from the pod as the planner is handed it (``view``):

* the busy mask, one per slice unit, 1 where the unit is claimed;
* ``log10(1 + age) / 6`` for each planned slot, in window order, where the
  ages are those of the window's profiled submissions only (a first sight
  runs solo and takes no slot), zero past the last;
* ``min(depth / (4 W), 1)`` of the pending depth behind the window.  The
  fleet's window is never wider than the agent's, so a window is planned
  as one chunk and no later chunk adds to the depth.

Near-ties are followed and the co-run guard applied as in
:class:`RLPlanner`.  Nothing here imports the program.
"""
from __future__ import annotations

import math
import sys

import numpy as np

from bench.reference.model import N_UNITS
from bench.reference.serve import ReferenceFleet, RLPlanner, View


def age_feature(age_s: float) -> float:
    return math.log10(1.0 + max(age_s, 0.0)) / 6.0


def depth_feature(depth: int, window: int) -> float:
    return min(depth / (4.0 * window), 1.0)


def planned_ages(view: View, n_planned: int) -> tuple[float, ...]:
    """The ages of the window's planned (profiled) submissions.

    ``view`` ages every submission of the window.  Where some are first
    sights, which of them were is read from the window the calling
    :class:`ReferenceFleet` is deciding: its head, its trace and the solo
    groups it gave the first sights (``View`` carries no such mask)."""
    if n_planned == len(view.ages_s):
        return view.ages_s
    frame = sys._getframe(1)
    while frame is not None \
            and frame.f_code is not ReferenceFleet._decide.__code__:
        frame = frame.f_back
    if frame is None:
        raise RuntimeError("a window with first sights is planned only "
                           "inside ReferenceFleet.run")
    head, order, solo = (frame.f_locals[k] for k in ("head", "order", "out"))
    fresh = {id(g[0]) for g, _ in solo}
    ages = []
    for age, i in zip(view.ages_s, head):
        job = order[i][2]
        if id(job) in fresh:       # the binary's first sight, run solo
            fresh.discard(id(job))
        else:
            ages.append(age)
    assert len(ages) == n_planned, (len(ages), n_planned)
    return tuple(ages)


def context_block(view: View, ages: tuple[float, ...],
                  window: int) -> np.ndarray:
    """``(N_UNITS + window + 1,)`` float32: busy mask, ages, depth."""
    out = np.zeros((N_UNITS + window + 1,), np.float32)
    assert len(view.free_units) == N_UNITS, view.free_units
    out[:N_UNITS] = [0.0 if f else 1.0 for f in view.free_units]
    for i, a in enumerate(ages[:window]):
        out[N_UNITS + i] = age_feature(a)
    out[-1] = depth_feature(view.queue_depth, window)
    return out


class Planner(RLPlanner):
    """:class:`RLPlanner` with the context block appended to every
    observation of the episode."""

    def _obs(self, feats, ep) -> np.ndarray:
        return np.concatenate([super()._obs(feats, ep), self._ctx])

    def plan(self, queue, accept, view: View | None = None):
        assert view is not None and len(queue) <= self.W
        self._ctx = context_block(view, planned_ages(view, len(queue)),
                                  self.W)
        return super().plan(queue, accept, view)

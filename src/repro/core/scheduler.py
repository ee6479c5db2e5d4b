"""Online phase (paper §IV-B): trained agent -> (L_JS, L_R) for a queue.

The agent runs greedily (ε = 0), the whole episode as one device call
whose steps mirror the stateful reference env (``CoScheduleEnv``) —
greedy episodes do not advance the agent's ε-decay schedule, so
scheduling/evaluation frequency never perturbs training exploration.
The §IV-A constraint ``CoRunTime <= SoloRunTime`` is then *enforced by
construction*: any group whose predicted co-run loses to time sharing is
split back into solo runs (the paper's constraint-1 guard).  Jobs without
a profile in the repository are excluded from co-scheduling and executed
solo while being profiled (paper's online protocol).

Two shared pieces sit between any planner and the cluster simulator:

* :func:`submission_protocol` — the single first-sight implementation
  (unprofiled binary -> solo run + repository insert) every dispatcher
  wraps, so the profiling cost is identical across policies by
  construction.  It also carries the dispatch-time
  :class:`~repro.core.env.DispatchContext` (free-unit mask, per-submission
  ages, pending depth) down to context-aware planners, re-chunked so each
  planning window sees exactly its own submissions' ages.
* :func:`to_placements` — width-fits a planned :class:`Schedule` into
  :class:`Placement`\\ s: dedicated (single-share) slices shrink to their
  job's ``requested_units`` hint so right-sized jobs occupy only the slice
  range they can use, which is what lets the simulator run independent
  groups concurrently on disjoint slices and backfill small jobs into idle
  gaps.  MPS-shared slices keep their planned width (the share semantics
  assume the planned slice), and a job without a hint keeps the full
  width — offline schedules are bit-identical through this function.
"""
from __future__ import annotations

from dataclasses import dataclass

import numpy as np

from repro import spans
from repro.core.agent import DQNAgent
from repro.core.env import DispatchContext, EnvConfig, context_block, context_dim
from repro.core.partition import (
    Partition, Slice, enumerate_partitions, slice_label, solo_partition,
)
from repro.core.perfmodel import corun_time, solo_run_time
from repro.core.problem import Schedule
from repro.core.profiles import FEATURES, JobProfile, ProfileRepository


@dataclass
class SchedulerStats:
    fallback_groups: int = 0
    unprofiled_jobs: int = 0
    windows: int = 0                 # RL scheduling windows run by submissions


def submission_protocol(repository: ProfileRepository,
                        submissions: list[tuple[str, JobProfile | None]],
                        plan, window: int | None = None,
                        on_unprofiled=None, on_window=None,
                        context: DispatchContext | None = None) -> Schedule:
    """The §IV-B online submission protocol, shared by every dispatcher.

    Submissions are ``(binary_path, maybe-fresh-profile)`` pairs.  A binary
    the repository has never seen runs **solo** on the full pod (profiled as
    it runs) and its fresh measurement enters the repository — a first
    sight with no measurement is reported via ``on_unprofiled`` but cannot
    be scheduled.  The profiled remainder is chunked into ``window``-sized
    batches (``None``: one batch) and handed to ``plan(queue) -> Schedule``.
    ``RLScheduler.schedule_submissions`` and the online package's
    ``DispatchPolicy.dispatch`` are both thin wrappers over this function,
    so the first-sight cost is identical across policies by construction.

    ``context`` is the dispatcher's cluster-state snapshot: its ``ages_s``
    align positionally with ``submissions``.  When given, each chunk's
    planner is called as ``plan(queue, context)`` with the ages filtered to
    that chunk's profiled jobs and ``queue_depth`` grown by the profiled
    submissions still waiting in later chunks of this same window (they
    queue behind this plan exactly like pending arrivals do).  ``None``
    preserves the historical ``plan(queue)`` call unchanged.
    """
    solo = solo_partition()
    sched = Schedule()
    profiled: list[JobProfile] = []
    ages: list[float] = []
    for k, (path, fresh) in enumerate(submissions):
        prof = repository.lookup(path)
        if prof is None:
            if on_unprofiled is not None:
                on_unprofiled(path, fresh)
            if fresh is not None:       # measured during this solo run
                repository.insert(path, fresh)
                sched.add([fresh], solo)
            continue
        profiled.append(prof)
        if context is not None:
            ages.append(context.ages_s[k] if k < len(context.ages_s) else 0.0)
    W = window or max(1, len(profiled))
    for lo in range(0, len(profiled), W):
        chunk = profiled[lo:lo + W]
        if on_window is not None:
            on_window(chunk)
        if context is None:
            inner = plan(chunk)
        else:
            later = len(profiled) - (lo + len(chunk))
            inner = plan(chunk, DispatchContext(
                free_units=context.free_units,
                ages_s=tuple(ages[lo:lo + len(chunk)]),
                queue_depth=context.queue_depth + later,
                now_s=context.now_s))
        for g, p in zip(inner.groups, inner.partitions):
            sched.add(g, p)
    return sched


@dataclass
class Placement:
    """One co-run group bound to the (possibly sub-pod) partition it will
    occupy.  The *which slice units* decision is the simulator's (its
    occupancy map first-fits the partition's slices onto free ranges);
    the placement fixes *how wide* each slice is."""

    group: list[JobProfile]
    partition: Partition


@dataclass(frozen=True)
class DispatchDecision:
    """The single result of one dispatch window — what
    ``DispatchPolicy.decide`` returns.

    ``schedule`` is the planned :class:`Schedule`, ``placements`` its
    width-fitted form (:func:`to_placements`) that the slice-level
    simulator consumes, and ``first_sight`` / ``planned`` count this
    window's submissions on each side of the profiling protocol."""

    schedule: Schedule
    placements: tuple[Placement, ...]
    first_sight: int = 0
    planned: int = 0


def to_placements(sched: Schedule) -> list[Placement]:
    """Width-fit a planned Schedule into slice-level placements.

    Dedicated (single-share) slices shrink to their job's
    ``requested_units`` placement hint — never grow, and MPS-shared slices
    are untouched.  Groups and slot order are preserved, so per-job finish
    times still come from :func:`~repro.core.perfmodel.corun` on the fitted
    partition.  Schedules over jobs without width hints pass through
    unchanged (identical objects), which keeps full-pod dispatch
    bit-compatible.  Spanned as ``repro.policy.fit``; the counter
    ``repro.policy.shrunk`` takes the slices shrunk by one call."""
    with spans.span("repro.policy.fit"):
        out: list[Placement] = []
        shrunk = 0
        for g, p in zip(sched.groups, sched.partitions):
            new_slices = list(p.slices)
            changed = False
            for pos, (si, s, _beta) in enumerate(p.slots):
                if len(s.shares) != 1:
                    continue
                req = g[pos].requested_units
                if req < s.units:
                    new_slices[si] = Slice(req, s.shares)
                    changed = True
                    shrunk += 1
            part = (Partition(tuple(new_slices),
                              slice_label(tuple(new_slices)))
                    if changed else p)
            out.append(Placement(list(g), part))
        spans.count("repro.policy.shrunk", shrunk)
        return out


class RLScheduler:
    def __init__(self, agent: DQNAgent, env_cfg: EnvConfig | None = None,
                 repository: ProfileRepository | None = None):
        self.agent = agent
        self.env_cfg = env_cfg or EnvConfig()
        # `or` would discard an *empty* repository (len 0 is falsy) and
        # silently sever the caller's handle to the shared profile store
        self.repository = repository if repository is not None else ProfileRepository()
        self.stats = SchedulerStats()
        self._partitions = enumerate_partitions(self.env_cfg.c_max)

    def schedule(self, queue: list[JobProfile],
                 context: DispatchContext | None = None) -> Schedule:
        """Greedy episode over ``queue``; ``context`` is the dispatch-time
        cluster snapshot an ``obs_context`` environment folds into the
        observation (ignored — zero block — otherwise).

        The episode runs on the device in one call
        (:meth:`DQNAgent.greedy_episode`); the host packs the window's
        features and replays the returned actions into the Schedule."""
        cfg = self.env_cfg
        W = cfg.window
        with spans.span("repro.sched.episode"):
            out = self.agent.greedy_episode(self._pack(queue, context), W,
                                            cfg.c_max, cfg.obs_context)
            n = int(out[-2])
            if not out[-1]:
                raise RuntimeError("scheduler failed to terminate")
            spans.count("repro.sched.steps", n)
            sched = Schedule()
            group: list[JobProfile] = []
            for a in out[:n].tolist():
                if a < W:
                    group.append(queue[a])
                else:
                    sched.add(group, self._partitions[a - W])
                    group = []
            return self._enforce_constraints(sched)

    def _pack(self, queue: list[JobProfile],
              context: DispatchContext | None) -> np.ndarray:
        """The episode's host input, flat f32: ``(W, F)`` features (zero
        rows pad), ``(W,)`` validity, then the context block under
        ``obs_context`` (zero without a snapshot) — what
        ``CoScheduleEnv`` would observe at reset.  Spanned as
        ``repro.sched.pack``, the context block as ``repro.sched.context``."""
        with spans.span("repro.sched.pack"):
            cfg = self.env_cfg
            W, F = cfg.window, len(FEATURES)
            assert len(queue) <= W, (len(queue), W)
            out = np.zeros((W * F + W + context_dim(cfg),), np.float32)
            out[:len(queue) * F] = np.asarray(
                [j.features() for j in queue], np.float32).reshape(-1)
            out[W * F:W * F + len(queue)] = 1.0
            if cfg.obs_context and context is not None:
                assert len(context.ages_s) == len(queue), \
                    (len(context.ages_s), len(queue))
                with spans.span("repro.sched.context"):
                    out[W * F + W:] = context_block(context, W)
            return out

    def schedule_submissions(self, submissions: list[tuple[str, JobProfile | None]],
                             context: DispatchContext | None = None) -> Schedule:
        """:func:`submission_protocol` with the agent as planner.

        Unprofiled jobs run solo (full pod) and enter the repository; the
        profiled remainder is co-scheduled by the agent.  More profiled jobs
        than the agent's window are chunked into successive window-sized RL
        episodes (each counted in ``stats.windows``) — the event-driven
        cluster simulator hands over whatever is pending, which can exceed W.
        ``context`` (the simulator's dispatch snapshot) reaches each episode
        re-chunked by :func:`submission_protocol`.
        """
        def on_unprofiled(path, fresh):
            self.stats.unprofiled_jobs += 1

        def on_window(chunk):
            self.stats.windows += 1

        return submission_protocol(self.repository, submissions,
                                   self.schedule, window=self.env_cfg.window,
                                   on_unprofiled=on_unprofiled,
                                   on_window=on_window, context=context)

    def _enforce_constraints(self, sched: Schedule) -> Schedule:
        """The §IV-A guard: a multi-job group whose predicted co-run loses
        to time sharing is split into solo runs.  Spanned as
        ``repro.sched.guard``; the counters ``repro.sched.guard_groups`` and
        ``repro.sched.fallback`` take the multi-job groups one call
        evaluates and the groups it splits."""
        with spans.span("repro.sched.guard"):
            solo = solo_partition()
            out = Schedule()
            evaluated = split = 0
            for g, p in zip(sched.groups, sched.partitions):
                if len(g) > 1:
                    evaluated += 1
                    if corun_time(g, p) > solo_run_time(g):
                        split += 1
                        for j in g:
                            out.add([j], solo)
                        continue
                out.add(g, p)
            self.stats.fallback_groups += split
            spans.count("repro.sched.guard_groups", evaluated)
            spans.count("repro.sched.fallback", split)
            return out

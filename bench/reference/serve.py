"""Plain reference of the co-scheduler's serving semantics.

An event-heap simulation of a hash-routed fleet of pods serving a trace:
arrivals join their pod's FCFS queue, a window of up to ``window``
submissions is handed to the policy once the pod's dispatched groups have
drained (plus one lookahead window past a blocked head), groups are placed
first-fit onto aligned free slice units, later groups backfill only when
they finish before the blocked head's reserved start, and a job seen for
the first time runs solo on the full pod while it is profiled.  The
policy is time sharing (every job solo) or the DQN agent's greedy episode
(select jobs into a group, close it with a partition), with groups whose
co-run loses to time sharing split back into solo runs.

Nothing here imports the program.  ``ReferenceAgent`` computes the
agent's forward pass in NumPy with the operand precision the
configuration states (float32 operands, exact accumulation) or, for the
control, a lower one.  Where two of the agent's actions are nearer than
the tie tolerance, the reference takes the one whose window schedule the
program's records show, and reports how far that action lay below its
best (``tie_gap``): the rounding of a sound forward pass may pick either,
and a larger gap is a decision the reference would not make.

A planner is called as ``plan(queue, accept, view)`` once a window forms
with at least one profiled job; ``view`` is the pod as the reference sees
it then (:class:`View`), for a planner whose agent observes more than the
window's profiles.  :class:`RLPlanner` ignores it.
"""
from __future__ import annotations

import heapq
import zlib
from collections import defaultdict, deque
from typing import NamedTuple

import numpy as np

from bench.reference.model import (
    N_UNITS, Job, Partition, Slice, corun, find_offsets, partition_table,
    slice_label, solo_partition,
)

_ARRIVE, _FREE = 0, 2
N_FLAGS = 5


def hash_pod(binary: str, n_pods: int, seed: int = 0) -> int:
    """CRC-32 tenant hashing over full-width pods."""
    h = zlib.crc32(binary.encode("utf-8"))
    h ^= (seed * 0x9E3779B1) & 0xFFFFFFFF
    return h % n_pods


def f32_clock(x: float) -> float:
    return float(np.float32(x))


def f64_clock(x: float) -> float:
    return x


class View(NamedTuple):
    """The pod when a window forms: its free-unit mask, the age of each
    head submission (seconds since arrival, in window order) and the
    number of submissions still pending behind the window."""

    free_units: tuple[bool, ...]
    ages_s: tuple[float, ...]
    queue_depth: int


# ------------------------------------------------------------------- agent

def _round_to(x: np.ndarray, dtype) -> np.ndarray:
    return np.asarray(x, np.float32).astype(dtype).astype(np.float64)


class ReferenceAgent:
    """Dueling DQN forward pass (hidden layers with ReLU, then V + A -
    mean(A)) with every matrix product's operands rounded to
    ``operand_dtype`` and accumulated in float64."""

    def __init__(self, params: dict, operand_dtype):
        self.dtype = operand_dtype
        self.layers = []
        i = 0
        while f"w{i}" in params:
            self.layers.append((_round_to(params[f"w{i}"], operand_dtype),
                                np.asarray(params[f"b{i}"], np.float64)))
            i += 1
        self.wv = _round_to(params["wV"], operand_dtype)
        self.bv = np.asarray(params["bV"], np.float64)
        self.wa = _round_to(params["wA"], operand_dtype)
        self.ba = np.asarray(params["bA"], np.float64)

    def q(self, obs: np.ndarray) -> np.ndarray:
        h = obs
        for w, b in self.layers:
            h = np.maximum(_round_to(h, self.dtype) @ w + b, 0.0)
        h = _round_to(h, self.dtype)
        a = h @ self.wa + self.ba
        return h @ self.wv + self.bv + a - a.mean()


class _Episode:
    """State of one greedy scheduling episode over a window queue."""

    __slots__ = ("scheduled", "in_group", "groups")

    def __init__(self, n: int):
        self.scheduled = [False] * n
        self.in_group: list[int] = []
        self.groups: list[tuple[list[int], Partition]] = []

    def copy(self) -> "_Episode":
        e = _Episode(0)
        e.scheduled = list(self.scheduled)
        e.in_group = list(self.in_group)
        e.groups = list(self.groups)
        return e


class RLPlanner:
    """The agent's greedy episode, following the program at near-ties."""

    def __init__(self, agent: ReferenceAgent, window: int, c_max: int,
                 tie_tol: float, max_expansions: int = 4000):
        self.agent = agent
        self.W = window
        self.c_max = c_max
        self.parts = partition_table(c_max)
        self.tie_tol = tie_tol
        self.max_expansions = max_expansions
        self.n_actions = window + len(self.parts)

    def _obs(self, feats, ep: _Episode) -> np.ndarray:
        W, nf = self.W, len(feats[0]) if feats else 7
        out = np.zeros((W, nf + N_FLAGS), np.float32)
        progress = len(ep.in_group) / max(1, self.c_max)
        for i in range(W):
            if i >= len(feats):
                out[i, nf + 3] = 1.0
                continue
            out[i, :nf] = feats[i]
            out[i, nf] = float(not ep.scheduled[i] and i not in ep.in_group)
            out[i, nf + 1] = float(i in ep.in_group)
            out[i, nf + 2] = float(ep.scheduled[i])
            out[i, nf + 4] = progress
        return out.reshape(-1)

    def _mask(self, n: int, ep: _Episode) -> np.ndarray:
        m = np.zeros(self.n_actions, bool)
        if len(ep.in_group) < self.c_max:
            for i in range(n):
                m[i] = not ep.scheduled[i] and i not in ep.in_group
        if ep.in_group:
            for k, p in enumerate(self.parts):
                m[self.W + k] = p.arity == len(ep.in_group)
        return m

    def _step(self, ep: _Episode, action: int) -> _Episode:
        ep = ep.copy()
        if action < self.W:
            ep.in_group.append(action)
        else:
            ep.groups.append((ep.in_group, self.parts[action - self.W]))
            for i in ep.in_group:
                ep.scheduled[i] = True
            ep.in_group = []
        return ep

    def plan(self, queue: list[Job], accept,
             view: View | None = None) -> tuple[list, float, bool]:
        """Groups of ``queue`` as ``[(jobs, partition)]`` after the
        co-run-versus-time-sharing guard, the gap of the path taken and
        whether ``accept`` took it.  ``view`` is not used: this agent
        observes the window's profiles only.

        Paths are expanded in order of their largest gap below the best
        action, so the greedy path comes first; among near-ties, the first
        complete schedule that ``accept`` takes is returned.  When none is
        taken within the expansion budget, the greedy schedule is."""
        feats = [j.features() for j in queue]
        n = len(queue)
        heap = [(0.0, 0, 0, _Episode(n))]
        tick = 1
        greedy = None
        for _ in range(self.max_expansions):
            if not heap:
                break
            gap, _neg_depth, _, ep = heapq.heappop(heap)
            if all(ep.scheduled) and not ep.in_group:
                sched = self._guard(queue, ep.groups)
                if greedy is None:
                    greedy = sched
                if accept(sched):
                    return sched, gap, True
                continue
            mask = self._mask(n, ep)
            q = self.agent.q(self._obs(feats, ep))
            valid = np.flatnonzero(mask)
            best = q[valid].max()
            scale = max(float(np.abs(q[valid]).max()), 1e-9)
            for a in valid:
                g = (best - q[a]) / scale
                if g <= self.tie_tol:
                    heapq.heappush(heap, (max(gap, float(g)), _neg_depth - 1,
                                          tick, self._step(ep, int(a))))
                    tick += 1
        if greedy is None:         # budget spent before any leaf: go greedy
            ep = _Episode(n)
            while not (all(ep.scheduled) and not ep.in_group):
                mask = self._mask(n, ep)
                q = np.where(mask, self.agent.q(self._obs(feats, ep)), -np.inf)
                ep = self._step(ep, int(np.argmax(q)))
            greedy = self._guard(queue, ep.groups)
        return greedy, 0.0, False

    @staticmethod
    def _guard(queue, groups):
        solo = solo_partition()
        out = []
        for idx, p in groups:
            g = [queue[i] for i in idx]
            if len(g) > 1 and max(corun(g, p)) > sum(j.solo_time() for j in g):
                out += [([j], solo) for j in g]
            else:
                out.append((g, p))
        return out


# -------------------------------------------------------------- simulation

class _Pod:
    def __init__(self, idx: int, width: int):
        self.idx, self.width = idx, width
        self.pending: deque[int] = deque()
        self.ready: deque = deque()
        self.free = [u < width for u in range(N_UNITS)]
        self.claims: dict[int, tuple] = {}
        self.cid = 0
        self.n_busy = N_UNITS - width
        self.busy_t0 = 0.0


class _Run:
    __slots__ = ("group", "partition", "recs", "finish", "makespan", "wid")

    def __init__(self, group, partition, recs, wid, finish):
        self.group, self.partition, self.recs, self.wid = (
            group, partition, recs, wid)
        self.finish = finish
        self.makespan = max(finish)


def _width_fit(group: list[Job], p: Partition) -> Partition:
    """Dedicated slices shrink to their job's requested width."""
    new = list(p.slices)
    changed = False
    for pos, (si, s, _b) in enumerate(p.slots):
        if len(s.shares) == 1 and group[pos].requested_units < s.units:
            new[si] = Slice(group[pos].requested_units, s.shares)
            changed = True
    return Partition(tuple(new), slice_label(tuple(new))) if changed else p


class ReferenceFleet:
    """Event-driven reference of one hash-routed fleet and policy.

    ``planner`` is None for time sharing, else an :class:`RLPlanner` or a
    planner that takes its constructor and its ``plan``.
    ``clock`` rounds every simulated time the reference computes: float64
    is the configuration's clock, float32 the control's."""

    def __init__(self, pods: tuple[int, ...], window: int, planner=None,
                 clock=f64_clock, router_seed: int = 0):
        assert all(w == N_UNITS for w in pods), "full-width pods only"
        self.pods_cfg = tuple(pods)
        self.window = window
        self.planner = planner
        self.clock = clock
        self.router_seed = router_seed
        if planner is not None:
            assert window <= planner.W

    def run(self, trace: list[tuple[float, str, Job]], follow=None) -> dict:
        """Serve ``trace`` (``(t, binary, job)`` triples).  ``follow`` is
        the program's per-job ``(group size, partition, slice width, group,
        co-run time, dispatch, backfilled)`` in sorted-trace order, used
        only to resolve the agent's near-ties."""
        clk = self.clock
        order = sorted(trace, key=lambda a: a[0])
        times = [clk(t) for t, _, _ in order]
        recs = [{"name": j.name, "binary": b, "pod": 0, "dispatch": np.nan,
                 "finish": np.nan, "group_size": 0, "partition": "",
                 "units": N_UNITS, "backfilled": False}
                for _, b, j in order]
        self.repo: dict[str, Job] = {}
        self.res = {"dispatches": 0, "backfills": 0, "refits": 0,
                    "busy_time": 0.0, "tie_gap": 0.0,
                    "segments": [[] for _ in self.pods_cfg]}
        pods = [_Pod(i, w) for i, w in enumerate(self.pods_cfg)]
        heap: list = []
        seq = 0

        def push(t, kind, payload):
            nonlocal seq
            heapq.heappush(heap, (t, kind, seq, payload))
            seq += 1

        for i in range(len(order)):
            push(times[i], _ARRIVE, i)

        def handle(now, kind, payload):
            if kind == _ARRIVE:
                p = hash_pod(order[payload][1], len(pods), self.router_seed)
                recs[payload]["pod"] = p
                pods[p].pending.append(payload)
            else:
                pidx, cid = payload
                self._release(now, pods[pidx], cid)

        self.follow = follow
        self._coruns: dict = {}
        if follow is not None:
            self.group_size = defaultdict(int)
            for f in follow:
                self.group_size[f[3]] += 1
        ctx = (order, recs, push)
        while heap:
            now, kind, _, payload = heapq.heappop(heap)
            handle(now, kind, payload)
            while heap and heap[0][0] == now:
                _, k2, _, p2 = heapq.heappop(heap)
                handle(now, k2, p2)
            for pod in pods:
                self._service(now, pod, ctx)
        self.res["records"] = recs
        return self.res

    # ----------------------------------------------------------- dispatch

    def _service(self, now, pod: _Pod, ctx) -> None:
        while True:
            progress = False
            while pod.ready:
                starts = find_offsets(pod.ready[0].partition, pod.free)
                if starts is None:
                    break
                self._place(now, pod, pod.ready.popleft(), starts, ctx)
                progress = True
            if pod.ready:
                if (pod.pending and any(pod.free)
                        and pod.ready[-1].wid == pod.ready[0].wid):
                    self._form_window(now, pod, ctx)
                    progress = True
                if len(pod.ready) > 1:
                    progress |= self._backfill(now, pod, ctx)
            elif pod.pending and any(pod.free):
                self._form_window(now, pod, ctx)
                progress = True
            if not progress:
                return

    def _decide(self, head, order, accept, view: View):
        """First sight: solo on the full pod while profiled; the rest is
        planned as one window, the planner given ``view``.  Returns
        ``[(jobs, partition)]``."""
        out, queue = [], []
        for i in head:
            _, binary, job = order[i]
            known = self.repo.get(binary)
            if known is None:
                self.repo[binary] = job
                out.append(([job], solo_partition()))
            else:
                queue.append(known)
        if not queue:
            return out
        if self.planner is None:
            return out + [([j], solo_partition()) for j in queue]
        groups, gap, taken = self.planner.plan(
            queue, lambda g: accept(out + g), view)
        self.res["tie_gap"] = max(self.res["tie_gap"], gap)
        if not taken:
            # the program left the reference's path: the records differ
            # from here on, and searching near-ties can no longer help
            self.follow = None
        return out + groups

    def _placements(self, pod: _Pod, groups):
        fitted = []
        for g, p in groups:
            p = _width_fit(g, p)
            if p.total_units <= pod.width:
                fitted.append((g, p))
            else:
                self.res["refits"] += 1
                fitted += [([j], solo_partition(min(j.requested_units,
                                                    pod.width))) for j in g]
        return fitted

    def _attribute(self, head, order, fitted):
        """Records of ``head`` per fitted group, first in first out by
        job name."""
        by_name: dict[str, deque] = defaultdict(deque)
        for i in head:
            by_name[order[i][2].name].append(i)
        out = [[by_name[j.name].popleft() for j in g] for g, _ in fitted]
        assert not any(by_name.values()), "policy dropped submissions"
        return out

    def _form_window(self, now, pod: _Pod, ctx) -> None:
        order = ctx[0]
        head = [pod.pending.popleft()
                for _ in range(min(self.window, len(pod.pending)))]
        view = View(tuple(pod.free),
                    tuple(now - self.clock(order[i][0]) for i in head),
                    len(pod.pending))

        def accept(groups):
            """Whether the program's records show this window schedule:
            each job's group, partition, slice width and co-run time, and
            the order in which the groups that were not backfilled
            started."""
            if self.follow is None:
                return True
            fitted = self._placements_dry(pod, groups)
            starts = []
            for (g, p), idx in zip(fitted, self._attribute(head, order,
                                                           fitted)):
                f = [self.follow[i] for i in idx]
                if len({x[3] for x in f}) != 1 \
                        or self.group_size[f[0][3]] != len(idx):
                    return False
                for x, ft, (_si, s, _b) in zip(f, self._corun(g, p),
                                               p.slots):
                    if x[:3] != (len(g), p.label, s.units) \
                            or abs(x[4] - ft) > 1e-4 * max(ft, 1.0):
                        return False
                if not f[0][6]:
                    starts.append(f[0][5])
            return starts == sorted(starts)

        fitted = self._placements(pod, self._decide(head, order, accept,
                                                    view))
        for (g, p), idx in zip(fitted, self._attribute(head, order, fitted)):
            pod.ready.append(_Run(g, p, idx, self.res["dispatches"],
                                  self._corun(g, p)))
        self.res["dispatches"] += 1

    def _corun(self, g: list[Job], p: Partition) -> list[float]:
        key = (tuple(j.name for j in g), p)
        if key not in self._coruns:
            self._coruns[key] = corun(g, p)
        return self._coruns[key]

    def _placements_dry(self, pod, groups):
        refits = self.res["refits"]
        fitted = self._placements(pod, groups)
        self.res["refits"] = refits
        return fitted

    def _backfill(self, now, pod: _Pod, ctx) -> bool:
        t_res = self._earliest_fit(pod, pod.ready[0].partition)
        placed = False
        for run in list(pod.ready)[1:]:
            starts = find_offsets(run.partition, pod.free)
            if starts is None:
                continue
            if now + run.makespan <= t_res + 1e-9:
                pod.ready.remove(run)
                self._place(now, pod, run, starts, ctx, backfilled=True)
                self.res["backfills"] += 1
                placed = True
        return placed

    @staticmethod
    def _earliest_fit(pod: _Pod, partition) -> float:
        expiries = sorted({t1 for _, t1 in pod.claims.values()})
        free = list(pod.free)
        for t in expiries:
            for ranges, t1 in pod.claims.values():
                if t1 <= t:
                    for st, w in ranges:
                        free[st:st + w] = [True] * w
            if find_offsets(partition, free) is not None:
                return t
        return expiries[-1] if expiries else 0.0

    def _place(self, now, pod: _Pod, run: _Run, starts, ctx,
               backfilled: bool = False) -> None:
        _order, recs, push = ctx
        clk = self.clock
        ranges = tuple((st, s.units)
                       for st, s in zip(starts, run.partition.slices))
        for st, w in ranges:
            pod.free[st:st + w] = [False] * w
        if pod.n_busy == N_UNITS - pod.width:
            pod.busy_t0 = now
        pod.n_busy += sum(w for _, w in ranges)
        t1 = clk(now + run.makespan)
        for i, ft, (_si, s, _b) in zip(run.recs, run.finish,
                                       run.partition.slots):
            recs[i].update(dispatch=now, finish=clk(now + ft),
                           group_size=len(run.group),
                           partition=run.partition.label, units=s.units,
                           backfilled=backfilled)
        self.res["segments"][pod.idx].append(
            (now, t1, len(run.group), run.partition.label, ranges,
             backfilled))
        pod.claims[pod.cid] = (ranges, t1)
        push(t1, _FREE, (pod.idx, pod.cid))
        pod.cid += 1

    def _release(self, now, pod: _Pod, cid) -> None:
        ranges, _ = pod.claims.pop(cid)
        for st, w in ranges:
            pod.free[st:st + w] = [True] * w
            pod.n_busy -= w
        if pod.n_busy == N_UNITS - pod.width:
            self.res["busy_time"] += now - pod.busy_t0

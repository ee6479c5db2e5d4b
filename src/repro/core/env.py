"""RL environment for co-scheduling + hierarchical partitioning (paper §IV-C).

State: W slots x (f profile features + 5 status flags), flattened — exactly
the paper's input layer ``W x (f+5)``.  With ``EnvConfig.obs_context=True``
an **arrival-aware context block** is appended (see ``docs/observation.md``
for the full spec): the pod's busy-unit occupancy mask (``N_UNITS``), the
per-slot queueing age of each window job (``W``), and the normalized depth
of the pending queue beyond this window (1) — the live cluster state the
online dispatch layer observes at each window, so the policy can *learn*
backfill-like behavior instead of inheriting it from the dispatcher.  A
zeroed context (empty pod, fresh queue) makes the prefix bit-identical to
the profile-only observation, and ``obs_context=False`` (default) changes
nothing at all.
Actions: W *select-job-i into the current group* + N_p *close the group with
partition p* (the paper's A = W + N_p decomposition; assignment to partition
slots follows selection order, covering the C! orderings).
Rewards (paper Table VI):
    on close:  Σ_j r_i(j)  +  r_f = (SoloRunTime/CoRunTime - 1) x 100
    r_i = (SmAllocRatio*ComputeRatio + MemoryAllocRatio*MemoryRatio) * DurationRatio^2
Under ``obs_context`` a close is additionally shaped by ``-ctx_fit_weight``
when the chosen partition cannot first-fit the observed free units (the
precomputed :func:`~repro.core.perfmodel_jax.build_fit_table` gather) —
the signal that ties the context features to packing-aware decisions; it
is exactly zero at zero context, preserving regression parity.
Episode: schedule the whole window; terminal when all W jobs are grouped.

The environment has two implementations:

  * **Functional core** — an immutable :class:`EnvState` pytree with pure
    ``reset``/``step`` transition functions whose reward math runs on
    precomputed JAX arrays (:mod:`repro.core.perfmodel_jax`).  Everything is
    jit-able and vmap-able, so the training engine fuses B parallel episodes
    and the DQN update into a single ``lax.scan`` (see ``train.py``).
    :class:`VecCoScheduleEnv` owns the compiled entry points.
  * **Stateful reference wrapper** — :class:`CoScheduleEnv` keeps the
    original mutable gym-style API (used by ``RLScheduler``, the baselines,
    and examples) and computes rewards with the float64 Python perfmodel.
    The parity test pins the functional core to this wrapper step-for-step.
"""
from __future__ import annotations

import math
from dataclasses import dataclass
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.partition import (
    N_UNITS, Partition, aligned_offsets, enumerate_partitions, find_offsets,
)
from repro.core.perfmodel import corun_time, solo_run_time
from repro.core.perfmodel_jax import (
    PartitionTable, QueueArrays, build_fit_table, build_partition_table,
    group_metrics, group_reward, queue_arrays, stack_queues,
)
from repro.core.problem import Schedule
from repro.core.profiles import FEATURES, JobProfile

N_FLAGS = 5  # available, in-group, scheduled, padding, group-progress


@dataclass
class EnvConfig:
    window: int = 12                     # W
    c_max: int = 4                       # Cmax
    r_f_scale: float = 100.0             # paper: x100
    r_i_weight: float = 0.2              # r_f carries the true objective
    invalid_penalty: float = -10.0       # masked anyway; safety net
    obs_context: bool = False            # append the arrival-aware block
    ctx_fit_weight: float = 10.0         # close-shaping when the partition
                                         # can't fit the observed free units
                                         # (active only under obs_context)

    def key(self) -> tuple:
        """Hashable identity (EnvConfig is mutable; used for engine caches).
        Derived from the declared fields so it can never go stale."""
        import dataclasses

        return tuple(getattr(self, f.name) for f in dataclasses.fields(self))


def context_dim(cfg: EnvConfig) -> int:
    """Width of the appended context block: busy mask + per-slot ages + depth."""
    return (N_UNITS + cfg.window + 1) if cfg.obs_context else 0


def age_feature(age_s: float) -> float:
    """Queueing age -> feature: log10 compression on the same 1e6-second
    scale as the profile features' ``log_duration`` (docs/observation.md)."""
    return math.log10(1.0 + max(age_s, 0.0)) / 6.0


def depth_feature(depth: int, window: int) -> float:
    """Pending-queue depth -> feature: saturating at 4 windows' worth."""
    return min(depth / (4.0 * window), 1.0)


@dataclass(frozen=True)
class DispatchContext:
    """Cluster-state snapshot the online dispatch layer hands the planner.

    Built by :class:`~repro.online.simulator.ClusterSimulator` at every
    dispatch window and threaded through ``submission_protocol`` down to
    ``RLScheduler.schedule``; the environment normalizes it into the
    observation's context block (:func:`dispatch_obs_context`).
    """

    free_units: tuple[bool, ...]         # (N_UNITS,) True = idle slice unit
    ages_s: tuple[float, ...]            # per-submission wait so far, seconds
    queue_depth: int = 0                 # pending submissions beyond this window
    now_s: float = 0.0                   # simulated dispatch instant


class ObsContext(NamedTuple):
    """Normalized context block appended to the observation (f32 pytree).

    The zero context — empty pod, no queued work, fresh arrivals — is the
    parity anchor: with ``ObsContext`` all-zero the observation prefix
    bit-matches the profile-only layout and the fit shaping is exactly 0.
    ``busy_units`` is therefore stored busy-high (1 = claimed), so "all
    zeros" means "everything free" rather than the pathological opposite.
    """

    busy_units: jnp.ndarray              # (N_UNITS,) f32 — 1 = unit claimed
    ages: jnp.ndarray                    # (W,) f32 — age_feature per slot
    queue_depth: jnp.ndarray             # () f32 — depth_feature


def zero_context(window: int) -> ObsContext:
    """The neutral (empty-cluster) context — the offline/parity default."""
    return ObsContext(
        busy_units=jnp.zeros((N_UNITS,), jnp.float32),
        ages=jnp.zeros((window,), jnp.float32),
        queue_depth=jnp.zeros((), jnp.float32),
    )


def context_block(ctx: DispatchContext, window: int) -> np.ndarray:
    """A simulator snapshot as the observation's flat context block, on the
    host: ``(N_UNITS + window + 1,)`` f32 — busy mask, per-slot ages,
    depth, in that order."""
    out = np.zeros((N_UNITS + window + 1,), np.float32)
    busy = [0.0 if f else 1.0 for f in ctx.free_units]
    assert len(busy) == N_UNITS, ctx.free_units
    out[:N_UNITS] = busy
    for i, a in enumerate(ctx.ages_s[:window]):
        out[N_UNITS + i] = age_feature(a)
    out[-1] = depth_feature(ctx.queue_depth, window)
    return out


def dispatch_obs_context(ctx: DispatchContext, window: int) -> ObsContext:
    """Normalize a simulator snapshot into the observation's context block."""
    block = context_block(ctx, window)
    return ObsContext(
        busy_units=jnp.asarray(block[:N_UNITS]),
        ages=jnp.asarray(block[N_UNITS:-1]),
        queue_depth=jnp.float32(block[-1]),
    )


_N_CTX_MASKS = 64


def _context_mask_table(n_masks: int = _N_CTX_MASKS, seed: int = 0) -> jnp.ndarray:
    """(K, N_UNITS) f32 — plausible busy masks for training-time sampling.

    Each row is a union of buddy-aligned block claims (the only shapes the
    slice-level dispatcher ever produces) at a uniformly drawn fill target,
    so offline training sees the occupancy distribution serve time will.
    Row 0 is the all-free pod, anchoring the zero-context regime in the
    training data.  Fixed seed: the table is part of the engine's
    deterministic identity.
    """
    rng = np.random.default_rng(seed)
    out = np.zeros((n_masks, N_UNITS), np.float32)
    for i in range(1, n_masks):
        target = rng.uniform()
        busy = np.zeros(N_UNITS, bool)
        for _ in range(16):
            if busy.mean() >= target:
                break
            w = int(rng.choice((1, 2, 4, 8), p=(0.4, 0.3, 0.2, 0.1)))
            off = int(rng.choice(aligned_offsets(w)))
            if not busy[off:off + w].any():
                busy[off:off + w] = True
        out[i] = busy
    return jnp.asarray(out)


class EnvState(NamedTuple):
    """Immutable episode state; ``queue`` is constant through the episode.

    ``ctx`` is the arrival-aware context the episode was reset with; it is
    carried (and tree-mapped) even when ``obs_context=False``, where it is
    all-zero and never read — one pytree shape for both modes."""

    queue: QueueArrays                   # per-queue precomputed job arrays
    scheduled: jnp.ndarray               # (W,) bool
    group_idx: jnp.ndarray               # (c_max,) i32, selection order, -1 pad
    group_size: jnp.ndarray              # () i32
    ctx: ObsContext                      # arrival-aware context block


class VecCoScheduleEnv:
    """Functional env: pure jitted ``reset``/``step`` + vmapped batch forms.

    ``reset(queue_arrays)`` and ``step(state, action)`` are pure functions of
    their inputs — all mutation is in the returned :class:`EnvState`.  The
    batch variants (``reset_batch``/``step_batch``) vmap over a leading env
    axis; ``queue_batch`` builds the stacked :class:`QueueArrays` input.
    """

    def __init__(self, cfg: EnvConfig | None = None):
        self.cfg = cfg or EnvConfig()
        self.partitions: list[Partition] = enumerate_partitions(self.cfg.c_max)
        self.table: PartitionTable = build_partition_table(
            self.partitions, self.cfg.c_max)
        self.n_features = len(FEATURES)
        self.context_dim = context_dim(self.cfg)
        self.state_dim = (self.cfg.window * (self.n_features + N_FLAGS)
                          + self.context_dim)
        self.n_actions = self.cfg.window + len(self.partitions)
        if self.cfg.obs_context:
            # partition-vs-busy-mask fit table (close shaping) + the sampled
            # occupancy distribution offline training draws contexts from
            self._fit_table = build_fit_table(self.partitions)
            self._ctx_masks = _context_mask_table()
            self._pow2 = jnp.asarray(2 ** np.arange(N_UNITS), jnp.int32)
        self._obs_b = jax.vmap(self._obs)
        self.reset = jax.jit(self._reset_zero)
        self.reset_ctx = jax.jit(self._reset)
        self.step = jax.jit(self._step)
        self.reset_batch = jax.jit(jax.vmap(self._reset_zero))
        self.reset_batch_ctx = jax.jit(jax.vmap(self._reset))
        self.step_batch = jax.jit(jax.vmap(self._step))
        self.obs_batch = jax.jit(self._obs_b)
        self.close_metrics_batch = jax.jit(jax.vmap(self._close_metrics))

    # ----------------------------------------------------------- queue prep
    def queue_arrays(self, queue: list[JobProfile]) -> QueueArrays:
        return queue_arrays(queue, self.cfg.window)

    def queue_batch(self, queues: list[list[JobProfile]]) -> QueueArrays:
        return stack_queues([self.queue_arrays(q) for q in queues])

    # ------------------------------------------------------- pure functions
    def _reset(self, qa: QueueArrays,
               ctx: ObsContext) -> tuple[EnvState, jnp.ndarray, jnp.ndarray]:
        state = EnvState(
            queue=qa,
            scheduled=jnp.zeros((self.cfg.window,), bool),
            group_idx=jnp.full((self.cfg.c_max,), -1, jnp.int32),
            group_size=jnp.int32(0),
            ctx=ctx,
        )
        return state, self._obs(state), self._mask(state)

    def _reset_zero(self, qa: QueueArrays):
        """Reset with the neutral zero context — the profile-only default."""
        return self._reset(qa, zero_context(self.cfg.window))

    def sample_context(self, key: jax.Array, mean_d: jnp.ndarray,
                       valid: jnp.ndarray) -> ObsContext:
        """Batched training-time context draw (requires ``obs_context``).

        ``mean_d`` (B,) is each queue's mean solo duration — the natural
        scale for queueing-age draws — and ``valid`` (B, W) masks padding
        slots to zero age.  Busy masks come from the precomputed aligned-
        claim table, ages from an exponential wait model, and queue depth
        from an exponential with mean one window — mirrors of the
        normalizations in :func:`dispatch_obs_context` (the jnp forms of
        :func:`age_feature` / :func:`depth_feature`), so offline training
        and online serving read the same feature distributions.  Pure and
        trace-friendly: the scanned engine resamples at episode auto-reset.
        """
        B, _ = valid.shape
        k_m, k_a, k_d = jax.random.split(key, 3)
        idx = jax.random.randint(k_m, (B,), 0, self._ctx_masks.shape[0])
        # dtype pinned: under JAX_ENABLE_X64 the default draw would be f64
        # and silently promote the whole observation out of f32
        raw = (jax.random.exponential(k_a, valid.shape, dtype=jnp.float32)
               * mean_d[:, None])
        return ObsContext(
            busy_units=self._ctx_masks[idx],
            ages=jnp.where(valid, jnp.log10(1.0 + raw) / 6.0,
                           jnp.float32(0.0)),
            queue_depth=jnp.minimum(
                jax.random.exponential(k_d, (B,), dtype=jnp.float32) / 4.0,
                1.0),
        )

    def _member(self, state: EnvState) -> jnp.ndarray:
        """(W,) bool — job i currently selected into the open group."""
        live = jnp.arange(self.cfg.c_max) < state.group_size
        hits = state.group_idx[None, :] == jnp.arange(self.cfg.window)[:, None]
        return jnp.any(hits & live[None, :], axis=1)

    def _obs(self, state: EnvState) -> jnp.ndarray:
        member = self._member(state)
        valid = state.queue.valid
        progress = state.group_size.astype(jnp.float32) / max(1, self.cfg.c_max)
        flags = jnp.stack([
            (valid & ~state.scheduled & ~member).astype(jnp.float32),
            member.astype(jnp.float32),
            (state.scheduled & valid).astype(jnp.float32),
            (~valid).astype(jnp.float32),
            jnp.where(valid, progress, 0.0),
        ], axis=1)
        flat = jnp.concatenate([state.queue.features, flags], axis=1).reshape(-1)
        if not self.cfg.obs_context:
            return flat
        return jnp.concatenate([flat, state.ctx.busy_units, state.ctx.ages,
                                state.ctx.queue_depth[None]])

    def _mask(self, state: EnvState) -> jnp.ndarray:
        member = self._member(state)
        can_select = (state.queue.valid & ~state.scheduled & ~member
                      & (state.group_size < self.cfg.c_max))
        can_close = (state.group_size >= 1) & (self.table.arity == state.group_size)
        return jnp.concatenate([can_select, can_close])

    def _done(self, state: EnvState) -> jnp.ndarray:
        return (jnp.all(state.scheduled | ~state.queue.valid)
                & (state.group_size == 0))

    def _step(self, state: EnvState, action: jnp.ndarray):
        """Pure transition -> (state', obs', reward, done, mask')."""
        W = self.cfg.window
        mask = self._mask(state)
        valid = mask[action]
        is_select = action < W
        # select branch: append to the open group (selection order preserved)
        sel_state = state._replace(
            group_idx=state.group_idx.at[state.group_size].set(
                action.astype(jnp.int32)),
            group_size=state.group_size + 1,
        )
        # close branch: score the group under partition p, mark scheduled
        p_idx = jnp.clip(action - W, 0, len(self.partitions) - 1)
        r_close = group_reward(self.table, state.queue, state.group_idx,
                               state.group_size, p_idx,
                               self.cfg.r_i_weight, self.cfg.r_f_scale)
        if self.cfg.obs_context and self.cfg.ctx_fit_weight > 0:
            # arrival-aware shaping: closing onto a partition that cannot
            # first-fit the observed free units costs ctx_fit_weight — the
            # learned analogue of "don't plan a placement that must block".
            # At zero context every partition fits, so this subtracts an
            # exact 0.0 and the profile-only rewards are bit-preserved.
            m_idx = jnp.sum(jnp.where(state.ctx.busy_units > 0.5,
                                      self._pow2, 0), dtype=jnp.int32)
            r_close = r_close - self.cfg.ctx_fit_weight * (
                1.0 - self._fit_table[p_idx, m_idx])
        close_state = state._replace(
            scheduled=state.scheduled | self._member(state),
            group_idx=jnp.full((self.cfg.c_max,), -1, jnp.int32),
            group_size=jnp.int32(0),
        )
        branch = jax.tree.map(lambda a, b: jnp.where(is_select, a, b),
                              sel_state, close_state)
        new_state = jax.tree.map(lambda a, b: jnp.where(valid, a, b),
                                 branch, state)
        reward = jnp.where(
            valid,
            jnp.where(is_select, 0.0, r_close),
            jnp.float32(self.cfg.invalid_penalty),
        )
        return (new_state, self._obs(new_state), reward,
                self._done(new_state), self._mask(new_state))

    def _close_metrics(self, state: EnvState, action: jnp.ndarray):
        """(co-run time, solo time, multi-job?) the close `action` realizes.

        Zeros when `action` is not a valid close, so an evaluation scan can
        unconditionally accumulate these alongside ``step``/``step_batch`` —
        the relative-throughput bookkeeping of the greedy rollout stays
        entirely on device (no Python perfmodel in the eval hot path).
        """
        W = self.cfg.window
        ok = self._mask(state)[action] & (action >= W)
        p_idx = jnp.clip(action - W, 0, len(self.partitions) - 1)
        mk, so, _ = group_metrics(self.table, state.queue, state.group_idx,
                                  state.group_size, p_idx)
        zero = jnp.float32(0.0)
        return (jnp.where(ok, mk, zero), jnp.where(ok, so, zero),
                ok & (state.group_size > 1))


class CoScheduleEnv:
    """Gym-style (reset/step) reference wrapper, dependency-free.

    Thin stateful shell over the same action/observation contract as the
    functional core, kept for the scheduler/baselines API.  Rewards use the
    float64 Python perfmodel, making this the ground truth the vectorized
    engine is parity-tested against; it also materializes the
    :class:`Schedule` object the online phase consumes.
    """

    def __init__(self, cfg: EnvConfig | None = None):
        self.cfg = cfg or EnvConfig()
        self.partitions: list[Partition] = enumerate_partitions(self.cfg.c_max)
        self.n_features = len(FEATURES)
        self.context_dim = context_dim(self.cfg)
        self.state_dim = (self.cfg.window * (self.n_features + N_FLAGS)
                          + self.context_dim)
        self.n_actions = self.cfg.window + len(self.partitions)
        self._queue: list[JobProfile] = []
        self._ctx: DispatchContext | None = None

    # ------------------------------------------------------------------ API
    def reset(self, queue: list[JobProfile],
              context: DispatchContext | None = None) -> tuple[np.ndarray, np.ndarray]:
        """``context`` is the dispatch-time cluster snapshot (ignored unless
        ``cfg.obs_context``); ``None`` is the neutral zero context."""
        assert len(queue) <= self.cfg.window
        if context is not None and self.cfg.obs_context:
            assert len(context.ages_s) == len(queue), \
                (len(context.ages_s), len(queue))
        self._queue = list(queue)
        self._ctx = context
        self._scheduled = [False] * len(queue)
        self._in_group: list[int] = []           # selection-ordered indices
        self.schedule = Schedule()
        return self._state(), self.action_mask()

    def step(self, action: int):
        W = self.cfg.window
        reward = 0.0
        if not self._valid(action):
            return self._state(), self.cfg.invalid_penalty, self.done, self.action_mask(), {}
        if action < W:
            self._in_group.append(action)
        else:
            partition = self.partitions[action - W]
            group = [self._queue[i] for i in self._in_group]
            reward = self._close_reward(group, partition)
            self.schedule.add(group, partition)
            for i in self._in_group:
                self._scheduled[i] = True
            self._in_group = []
        return self._state(), reward, self.done, self.action_mask(), {}

    @property
    def done(self) -> bool:
        return all(self._scheduled) and not self._in_group

    # ------------------------------------------------------------- internals
    def _valid(self, action: int) -> bool:
        W = self.cfg.window
        if action < W:
            return (action < len(self._queue)
                    and not self._scheduled[action]
                    and action not in self._in_group
                    and len(self._in_group) < self.cfg.c_max)
        p = self.partitions[action - W]
        return len(self._in_group) >= 1 and p.arity == len(self._in_group)

    def action_mask(self) -> np.ndarray:
        return np.array([self._valid(a) for a in range(self.n_actions)], dtype=bool)

    def _state(self) -> np.ndarray:
        W = self.cfg.window
        out = np.zeros((W, self.n_features + N_FLAGS), np.float32)
        progress = len(self._in_group) / max(1, self.cfg.c_max)
        for i in range(W):
            if i >= len(self._queue):
                out[i, self.n_features + 3] = 1.0       # padding
                continue
            out[i, : self.n_features] = self._queue[i].features()
            out[i, self.n_features + 0] = float(not self._scheduled[i] and i not in self._in_group)
            out[i, self.n_features + 1] = float(i in self._in_group)
            out[i, self.n_features + 2] = float(self._scheduled[i])
            out[i, self.n_features + 4] = progress
        flat = out.reshape(-1)
        if not self.cfg.obs_context:
            return flat
        if self._ctx is None:
            return np.concatenate([flat, np.zeros((self.context_dim,),
                                                  np.float32)])
        # one normalization implementation: the same conversion the
        # vectorized serve path uses (busy, ages, depth — in that order)
        return np.concatenate([flat, context_block(self._ctx, W)])

    # ------------------------------------------------------------- rewards
    def _close_reward(self, group: list[JobProfile], partition: Partition) -> float:
        means = self._window_means()
        ri = sum(
            self._r_i(job, beta, s.units, means)
            for job, (_, s, beta) in zip(group, partition.slots)
        )
        ct = corun_time(group, partition)
        st = solo_run_time(group)
        rf = (st / ct - 1.0) * self.cfg.r_f_scale if ct > 0 else 0.0
        reward = self.cfg.r_i_weight * ri + rf
        if (self.cfg.obs_context and self.cfg.ctx_fit_weight > 0
                and self._ctx is not None
                and find_offsets(partition, list(self._ctx.free_units)) is None):
            # mirror of the functional env's fit shaping (exact: same
            # first-fit predicate the fit table was built from)
            reward -= self.cfg.ctx_fit_weight
        return reward

    def _window_means(self) -> dict:
        jobs = self._queue
        return {
            "compute": float(np.mean([j.compute_pct for j in jobs])) or 1e-9,
            "memory": float(np.mean([j.memory_pct for j in jobs])) or 1e-9,
            "duration": float(np.mean([j.solo_time() for j in jobs])) or 1e-9,
        }

    def _r_i(self, job: JobProfile, beta: float, units: int, means: dict) -> float:
        """Paper Table VI intermediate reward, TPU-mapped:
        SmAllocRatio = chips fraction x β; MemoryAllocRatio = slice bandwidth
        fraction (co-residents all access the slice's bandwidth, like the
        GI's αm)."""
        sm_alloc = (units / N_UNITS) * beta
        mem_alloc = units / N_UNITS
        compute_ratio = job.compute_pct / max(means["compute"], 1e-9)
        memory_ratio = job.memory_pct / max(means["memory"], 1e-9)
        duration_ratio = job.solo_time() / max(means["duration"], 1e-9)
        return (sm_alloc * compute_ratio + mem_alloc * memory_ratio) * duration_ratio ** 2

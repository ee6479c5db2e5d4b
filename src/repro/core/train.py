"""Offline RL training (paper §IV-B) on a vectorized pure-functional engine.

``train_agent`` drives B parallel environments through a single jitted
``lax.scan``: vmapped ε-greedy action selection, batched ``EnvState.step``
transitions, pushes into the on-device replay ring, and interleaved
double-DQN updates all live in one compiled program — no per-step Python
dispatch.  With ``cfg.per_alpha > 0`` the ring is a sum-tree prioritized
buffer (``repro.core.replay``): stratified proportional sampling, IS
weights (β annealed alongside ε) inside the loss, and |TD|-driven priority
refresh, all threaded through the scan carry.  Episodes auto-reset inside
the scan; the driver peels off segments of ~``eval_every`` episodes, runs
the greedy evaluation rollout — itself a jitted ``step_batch`` scan over
*every* train queue at once, with co-run/solo times accumulated from the
in-graph perfmodel, so a training run never leaves device between
segments — and emits history records with the same keys as the original
loop.  Record semantics are segment-granular: ``episode`` is the cumulative
completed-episode count when the record was taken (it can overshoot
``cfg.episodes`` by up to one segment), ``ep_reward`` is the mean return
of the episodes completed in that segment, and ``eval_throughput`` is the
mean relative throughput over the train queues (previously: queue 0 only,
via the scalar reference env).  Each record also carries
``heldout_throughput`` — the same greedy metric over a second stacked batch
of queues drawn *only* from the held-out (unseen) jobs, the paper's
generalization test — evaluated in the same jitted ``step_batch`` rollout
(the two batches are concatenated along the queue axis).  When no held-out
jobs exist (e.g. re-training on a live profile repository with
``heldout=set()``) the field is ``None``.

``train_agent(..., warm_start=agent)`` seeds the engine from an existing
agent's online/target params and optimizer state instead of a fresh
initialization — the MISO-style periodic re-training entry point
(``repro.online.retrain``): exploration (``env_steps``) restarts at zero so
``cfg.dqn``'s ε schedule governs the refresh, but the Q-function continues
from where the previous cycle left off.

**Scan-carry layout.**  One ``_Carry`` NamedTuple threads the entire
training state through ``lax.scan`` (and is *donated* to the jitted
segment, so the ~100 MB replay ring is updated in place rather than
copied):

    env / obs / mask             — live B-batched episode state: EnvState
                                   pytree, (B, D) observations, (B, A)
                                   action masks;
    reset_env / reset_obs /      — per-env episode-start snapshots; when
    reset_mask                     env ``b`` reports done, ``_bsel``
                                   tree-selects row ``b`` back to its
                                   reset copy inside the scan (episode
                                   auto-reset without leaving the graph);
    params / target / opt        — online Q-network, target network, and
                                   optimizer state pytrees, updated by the
                                   gated double-DQN step;
    replay                       — ``ReplayState`` or (``per_alpha > 0``)
                                   ``PrioritizedReplayState``; the static
                                   choice selects the uniform or PER
                                   engine at trace time;
    key                          — PRNG key, split per scan step for
                                   action noise and replay sampling;
    env_steps / updates          — () i32 counters driving the ε/β
                                   schedules and the target-sync cadence;
    ep_ret                       — (B,) running episode returns, emitted
                                   (masked by done) as the scan's per-step
                                   output for history records.

Because every mutable quantity lives in the carry, a segment is a pure
function ``(carry, n_steps) -> (carry, (dones, returns))`` — the driver
owns nothing but the Python-side history bookkeeping, and identical
carries replay identically (the determinism test pins this).

``train_agent_scalar`` preserves the seed per-step Python loop verbatim —
it is the semantic reference for the parity test.

Random queues over the zoo, ε-greedy exploration, dueling double-DQN
updates; held-out jobs excluded (paper's unseen-application generalization
test).

**Deliberate default-cadence change:** the scalar seed loop ran 1 DQN
update per env transition (128 gradient samples per transition — far above
the classic DQN ratio).  The vectorized default is 1 update per
``update_every`` (16) transitions = 8 samples/transition, the
DeepMind-classic cadence; with the target network synced on a fixed
*transition* cadence this trains schedulers whose throughput clears the
seed acceptance bar across seeds.  Set ``update_every=1`` to recover the
seed's update work exactly (at that cadence the DQN updates, not the
rollout, dominate the work).
"""
from __future__ import annotations

import dataclasses
import math
from dataclasses import dataclass, field
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.agent import (
    DQNAgent, DQNConfig, _dqn_update, _dqn_update_aux, _dqn_update_per,
    _dqn_update_per_aux, act_batch, beta_at, epsilon_at,
)
from repro.core.env import CoScheduleEnv, EnvConfig, EnvState, VecCoScheduleEnv
from repro.core.metrics import relative_throughput
from repro.core.network import dqn_apply, masked_argmax
from repro.core.perfmodel_jax import stack_queues
from repro.core.profiles import JobProfile
from repro.core.replay import (
    PrioritizedReplayState, ReplayState, per_init, per_push, per_sample,
    per_update, replay_init, replay_push, replay_sample,
)
from repro.core.scheduler import RLScheduler
from repro.core.workloads import QUEUE_KINDS, make_queue


@dataclass
class TrainConfig:
    episodes: int = 3000
    updates_per_step: int = 1
    n_train_queues: int = 20            # paper: 20 random queues for training
    n_heldout_queues: int = 8           # unseen-job queues per eval record
    strict_classes: bool = True         # demand CI+MI+US in the train pool
    seed: int = 0
    eval_every: int = 100
    batch_envs: int = 16                # B parallel envs in the scanned engine
    update_every: int = 16              # env transitions per DQN update
    per_alpha: float = 0.0              # PER priority exponent; 0 = uniform
    per_beta0: float = 0.4              # initial IS-correction exponent
    per_eps: float = 1e-3               # priority floor added to |TD|
    obs_context: bool = False           # arrival-aware context features:
                                        # promotes env_cfg.obs_context and
                                        # samples per-episode contexts in-scan
    telemetry: bool = False             # per-record loss/TD/grad-norm series
                                        # extracted from the scan carry
    dqn: DQNConfig = field(default_factory=DQNConfig)


def heldout_split(jobs: list[JobProfile], frac: float = 0.33, seed: int = 7):
    """Paper: mark ~1/3 of programs as unseen (*) — excluded from training."""
    rng = np.random.default_rng(seed)
    by_cls: dict[str, list[JobProfile]] = {}
    for j in jobs:
        by_cls.setdefault(j.job_class, []).append(j)
    held: set[str] = set()
    for cls, pool in by_cls.items():
        k = max(1, int(len(pool) * frac)) if len(pool) > 1 else 0
        idx = rng.permutation(len(pool))[:k]
        held.update(pool[i].name for i in idx)
    return held


def _train_queues(jobs, env_cfg, cfg, heldout, rng):
    """20 fixed training queues, all classes represented (paper §V-A2).

    ``cfg.strict_classes=False`` lets recipes remap missing classes onto
    the ones present — required when training on a live profile repository
    mid-growth (the online retrainer sets it); offline callers keep the
    historical 'zoo has no X jobs' validation by default."""
    return [
        make_queue(jobs, QUEUE_KINDS[i % len(QUEUE_KINDS)], env_cfg.window, rng,
                   exclude=heldout, strict=cfg.strict_classes)
        for i in range(cfg.n_train_queues)
    ]


def _heldout_queues(jobs, env_cfg, cfg, heldout, rng):
    """Queues drawn only from held-out jobs — the generalization eval batch.

    Empty when there are no held-out jobs (then the per-record
    ``heldout_throughput`` is ``None``).  Uses its own RNG so the training
    stream (queue composition, per-segment env assignment) is untouched.
    """
    pool = [j for j in jobs if j.name in heldout]
    if not pool or cfg.n_heldout_queues <= 0:
        return []
    return [
        make_queue(pool, QUEUE_KINDS[i % len(QUEUE_KINDS)], env_cfg.window, rng,
                   strict=False)
        for i in range(cfg.n_heldout_queues)
    ]


# ---------------------------------------------------------------------------
# Scanned rollout+update engine
# ---------------------------------------------------------------------------

class _Carry(NamedTuple):
    env: EnvState                        # B-batched episode states
    obs: jnp.ndarray                     # (B, D)
    mask: jnp.ndarray                    # (B, A)
    reset_env: EnvState                  # per-env episode-start states
    reset_obs: jnp.ndarray
    reset_mask: jnp.ndarray
    params: dict
    target: dict
    opt: dict
    replay: ReplayState | PrioritizedReplayState
    key: jax.Array
    env_steps: jnp.ndarray               # () i32
    updates: jnp.ndarray                 # () i32
    ep_ret: jnp.ndarray                  # (B,) running episode returns


def _bsel(pred, a, b):
    """Per-env tree select: pred (B,) broadcast over each leaf's trailing dims."""
    def sel(x, y):
        p = pred.reshape(pred.shape + (1,) * (x.ndim - 1))
        return jnp.where(p, x, y)
    return jax.tree.map(sel, a, b)


def _build_engine(venv: VecCoScheduleEnv, dqn_cfg: DQNConfig,
                  batch_envs: int, updates_per_scan: int,
                  update_period: int, target_sync_updates: int,
                  per: tuple[float, float, float] | None = None,
                  telemetry: bool = False):
    """One scan step = B env transitions + gated DQN updates.

    ``updates_per_scan`` updates run every ``update_period``-th scan step —
    the two together honor ``update_every`` whether B is larger or smaller
    than it.  ``target_sync_updates`` is the sync period in *updates*,
    pre-scaled by the driver so the target network refreshes on the same
    env-transition cadence as the scalar loop (whose 1:1 update ratio made
    ``DQNConfig.target_sync`` updates == transitions).

    ``per = (alpha, beta0, eps)`` statically selects the prioritized-replay
    engine: the carry holds a :class:`PrioritizedReplayState`, each update
    draws a stratified proportional sample, applies IS weights (β annealed
    alongside ε) inside the loss, and writes the new |TD|-derived priorities
    back into the sum-tree before the next update of the same scan step.
    ``per=None`` is the uniform engine, unchanged.

    With ``venv.cfg.obs_context`` (a static trace-time branch) every episode
    auto-reset draws a **fresh arrival-aware context** for that env — busy
    mask from the aligned-claim table, ages/depth from the wait model
    (``VecCoScheduleEnv.sample_context``) — so offline training sees the
    occupancy distribution serve time will, one context per episode, all
    inside the scanned rollout.  The reset observation is recomputed from
    the re-contexted state; masks are context-independent.  Without the
    flag the key stream and compiled program are byte-identical to PR 4.

    ``telemetry`` (static) swaps the update steps for their ``_aux``
    variants and emits per-scan-step ``(loss, |td|, grad_norm, updated)``
    alongside the episode outputs — same forward pass, same gradients,
    bit-identical parameter trajectory (the aux outputs are reads of
    quantities the update computes anyway).
    """
    B = batch_envs
    ctx_mode = venv.cfg.obs_context

    def body(c: _Carry, _):
        if ctx_mode:
            key, k_act, k_upd, k_ctx = jax.random.split(c.key, 4)
        else:
            key, k_act, k_upd = jax.random.split(c.key, 3)
        env_steps = c.env_steps + B
        eps = epsilon_at(dqn_cfg, env_steps)
        a = act_batch(c.params, k_act, c.obs, c.mask, eps)
        env2, obs2, r, done, mask2 = venv.step_batch(c.env, a)
        push = replay_push if per is None else per_push
        replay = push(c.replay, {
            "s": c.obs, "a": a, "r": r, "s2": obs2,
            "done": done.astype(jnp.float32), "mask2": mask2})
        scan_t = env_steps // B                       # 1-based scan step index
        can = (replay.size >= dqn_cfg.batch_size) & (scan_t % update_period == 0)

        # (loss, |td|, grad_norm) of the scan step's last update — zeros on
        # steps with no update; `can` tells the consumer which is which
        tl = (jnp.float32(0.0), jnp.float32(0.0), jnp.float32(0.0))
        if per is None:
            def upd(_, uc):
                if telemetry:
                    params, target, opt, updates, k, _ = uc
                else:
                    params, target, opt, updates, k = uc
                k, k_s = jax.random.split(k)
                batch = replay_sample(replay, k_s, dqn_cfg.batch_size)
                if telemetry:
                    params, opt, loss, td, gn = _dqn_update_aux(
                        params, target, opt, batch, dqn_cfg)
                else:
                    params, opt, _ = _dqn_update(params, target, opt, batch,
                                                 dqn_cfg)
                updates = updates + 1
                sync = updates % target_sync_updates == 0
                target = jax.tree.map(lambda p, t: jnp.where(sync, p, t),
                                      params, target)
                if telemetry:
                    return params, target, opt, updates, k, (loss, td, gn)
                return params, target, opt, updates, k

            uc0 = (c.params, c.target, c.opt, c.updates, k_upd)
            if telemetry:
                uc0 = uc0 + (tl,)
            # `can` is a scalar (the body is not vmapped), so cond really
            # skips the untaken branch — no tree-wide where copies, and
            # warmup steps before the buffer fills pay nothing
            out = jax.lax.cond(
                can,
                lambda uc: jax.lax.fori_loop(0, updates_per_scan, upd, uc),
                lambda uc: uc,
                uc0)
            if telemetry:
                params, target, opt, updates, _, tl = out
            else:
                params, target, opt, updates, _ = out
        else:
            alpha, beta0, per_eps = per
            beta = beta_at(beta0, env_steps, dqn_cfg.eps_decay_steps)

            def upd(_, uc):
                if telemetry:
                    params, target, opt, updates, rep, k, _ = uc
                else:
                    params, target, opt, updates, rep, k = uc
                k, k_s = jax.random.split(k)
                batch, idx, w = per_sample(rep, k_s, dqn_cfg.batch_size,
                                           alpha, beta)
                if telemetry:
                    params, opt, loss, td, gn = _dqn_update_per_aux(
                        params, target, opt, batch, w, dqn_cfg)
                else:
                    params, opt, _, td = _dqn_update_per(params, target, opt,
                                                         batch, w, dqn_cfg)
                if alpha > 0:          # alpha == 0: priorities never read
                    rep = per_update(rep, idx, td, alpha, per_eps)
                updates = updates + 1
                sync = updates % target_sync_updates == 0
                target = jax.tree.map(lambda p, t: jnp.where(sync, p, t),
                                      params, target)
                if telemetry:
                    return (params, target, opt, updates, rep, k,
                            (loss, jnp.mean(td), gn))
                return params, target, opt, updates, rep, k

            uc0 = (c.params, c.target, c.opt, c.updates, replay, k_upd)
            if telemetry:
                uc0 = uc0 + (tl,)
            # the replay joins the update carry here: priority writes must
            # be visible to the next update drawn in the same scan step
            out = jax.lax.cond(
                can,
                lambda uc: jax.lax.fori_loop(0, updates_per_scan, upd, uc),
                lambda uc: uc,
                uc0)
            if telemetry:
                params, target, opt, updates, replay, _, tl = out
            else:
                params, target, opt, updates, replay, _ = out
        ep_all = c.ep_ret + r
        if ctx_mode:
            # per-episode context refresh: envs that finished an episode
            # restart on a freshly sampled cluster state (the snapshot in
            # reset_env keeps its zero/segment context; only the live row
            # is re-contexted, so the carry layout is unchanged).  The
            # profile prefix of a reset observation is context-independent,
            # so splice the fresh context tail onto the cached prefix
            # instead of rebuilding the whole observation every step.
            fresh = venv.sample_context(k_ctx, c.reset_env.queue.mean_d,
                                        c.reset_env.queue.valid)
            r_env = c.reset_env._replace(ctx=fresh)
            d0 = venv.state_dim - venv.context_dim
            r_obs = jnp.concatenate(
                [c.reset_obs[:, :d0], fresh.busy_units, fresh.ages,
                 fresh.queue_depth[:, None]], axis=1)
        else:
            r_env, r_obs = c.reset_env, c.reset_obs
        carry = _Carry(
            env=_bsel(done, r_env, env2),
            obs=jnp.where(done[:, None], r_obs, obs2),
            mask=jnp.where(done[:, None], c.reset_mask, mask2),
            reset_env=c.reset_env, reset_obs=c.reset_obs, reset_mask=c.reset_mask,
            params=params, target=target, opt=opt, replay=replay, key=key,
            env_steps=env_steps, updates=updates,
            ep_ret=jnp.where(done, 0.0, ep_all),
        )
        ret = jnp.where(done, ep_all, 0.0)
        if telemetry:
            return carry, (done, ret, tl[0], tl[1], tl[2], can)
        return carry, (done, ret)

    def run_segment(carry: _Carry, n_steps: int):
        return jax.lax.scan(body, carry, None, length=n_steps)

    # donate the carry: the replay ring is ~100 MB and re-enters every
    # segment — without donation each call copies it across the jit boundary
    return jax.jit(run_segment, static_argnums=1, donate_argnums=0)


def _build_eval(venv: VecCoScheduleEnv):
    """Jitted greedy evaluation: many queues per record, fully on device.

    Greedy rollout over a batch of eval queues via ``step_batch`` (2W scan
    steps — the episode-length upper bound: W selects + at most W closes),
    accumulating each closed group's co-run/solo time from the in-graph
    perfmodel.  Mirrors ``RLScheduler._enforce_constraints``: a multi-job
    group whose co-run loses to time sharing is counted at its solo time
    (the §IV-A constraint-1 fallback).  Returns per-queue relative
    throughput — no scalar ``CoScheduleEnv`` anywhere in the eval hot path.
    """
    two_w = 2 * venv.cfg.window

    def run(params, env, obs, mask):
        def body(carry, _):
            env, obs, mask, cot, sol = carry
            a = masked_argmax(dqn_apply(params, obs), mask)
            mk, so, multi = venv.close_metrics_batch(env, a)
            env2, obs2, _, _, mask2 = venv.step_batch(env, a)
            cot = cot + jnp.where(multi & (mk > so), so, mk)
            sol = sol + so
            return (env2, obs2, mask2, cot, sol), None

        zeros = jnp.zeros(mask.shape[:1], jnp.float32)
        (_, _, _, cot, sol), _ = jax.lax.scan(
            body, (env, obs, mask, zeros, zeros), None, length=two_w)
        return jnp.where(cot > 0, sol / jnp.maximum(cot, 1e-30), 0.0)

    return jax.jit(run)


_ENGINE_CACHE: dict = {}


def _engine_for(env_cfg: EnvConfig, dqn_cfg: DQNConfig,
                batch_envs: int, updates_per_scan: int,
                update_period: int, target_sync_updates: int,
                per: tuple[float, float, float] | None,
                telemetry: bool = False):
    key = (env_cfg.key(), dqn_cfg, batch_envs, updates_per_scan,
           update_period, target_sync_updates, per, telemetry)
    if key not in _ENGINE_CACHE:
        venv = VecCoScheduleEnv(env_cfg)
        _ENGINE_CACHE[key] = (venv, _build_engine(venv, dqn_cfg, batch_envs,
                                                  updates_per_scan,
                                                  update_period,
                                                  target_sync_updates, per,
                                                  telemetry),
                              _build_eval(venv))
        while len(_ENGINE_CACHE) > 8:      # bound compiled-engine retention
            _ENGINE_CACHE.pop(next(iter(_ENGINE_CACHE)))
    return _ENGINE_CACHE[key]


def train_agent(jobs: list[JobProfile], env_cfg: EnvConfig | None = None,
                cfg: TrainConfig | None = None, heldout: set[str] | None = None,
                verbose: bool = False, warm_start: DQNAgent | None = None,
                _force_per: bool = False) -> tuple[DQNAgent, list[dict]]:
    """Train on the scanned vectorized engine; same signature/records as ever.

    ``cfg.per_alpha > 0`` switches the engine to prioritized replay.
    ``warm_start`` seeds params/target/opt from an existing agent (shapes
    must match this ``env_cfg``); exploration restarts at step 0 under
    ``cfg.dqn``'s ε schedule — the periodic re-training entry point.
    ``_force_per`` routes ``per_alpha == 0`` through the PER machinery
    anyway (uniform indices, unit weights) — the regression parity test
    uses it to pin that path bit-exactly to the uniform engine.
    ``cfg.obs_context`` (or ``env_cfg.obs_context``) widens observations
    with the arrival-aware context block and samples a fresh cluster-state
    context per episode inside the scan; evaluation rollouts stay at the
    neutral zero context, so ``eval_throughput`` remains comparable across
    the two observation modes.
    ``cfg.telemetry`` adds ``loss``/``td_abs``/``grad_norm``/``beta``/
    ``updates`` to each history record (means of the scan's per-step
    update samples since the previous record) while keeping the parameter
    trajectory bit-identical — see ``docs/observability.md``.
    """
    cfg = cfg or TrainConfig()
    env_cfg = env_cfg or EnvConfig()
    if cfg.obs_context and not env_cfg.obs_context:
        env_cfg = dataclasses.replace(env_cfg, obs_context=True)
    use_ctx = env_cfg.obs_context
    B = cfg.batch_envs
    use_per = cfg.per_alpha > 0 or _force_per
    per = (cfg.per_alpha, cfg.per_beta0, cfg.per_eps) if use_per else None
    # honor the configured updates-per-transition ratio on both sides of
    # B vs update_every: several updates per scan step when B is larger,
    # one update every few scan steps when B is smaller
    ratio = B * cfg.updates_per_step / max(1, cfg.update_every)
    if ratio >= 1.0:
        updates_per_scan, update_period = max(1, round(ratio)), 1
    else:
        updates_per_scan, update_period = 1, max(1, round(1.0 / ratio))
    # keep the target-refresh cadence fixed in env transitions (the scalar
    # loop's 1:1 ratio made target_sync updates == transitions)
    sync_updates = max(1, round(cfg.dqn.target_sync * updates_per_scan
                                / (B * update_period)))
    venv, engine, eval_fn = _engine_for(env_cfg, cfg.dqn, B, updates_per_scan,
                                        update_period, sync_updates, per,
                                        cfg.telemetry)
    agent = DQNAgent(venv.state_dim, venv.n_actions, cfg.dqn, seed=cfg.seed,
                     per_alpha=cfg.per_alpha, per_beta0=cfg.per_beta0,
                     per_eps=cfg.per_eps)
    if warm_start is not None:
        # copy (not alias): the jitted segment donates its carry, and donated
        # buffers are invalidated — the caller's agent must stay usable
        src, dst = jax.tree.leaves(warm_start.params), jax.tree.leaves(agent.params)
        assert len(src) == len(dst) and all(a.shape == b.shape
                                            for a, b in zip(src, dst)), \
            "warm_start agent shape mismatch with this EnvConfig/DQNConfig"
        agent.params = jax.tree.map(jnp.copy, warm_start.params)
        agent.target_params = jax.tree.map(jnp.copy, warm_start.target_params)
        agent.opt = jax.tree.map(jnp.copy, warm_start.opt)
    rng = np.random.default_rng(cfg.seed)
    heldout = heldout if heldout is not None else heldout_split(jobs)
    train_queues = _train_queues(jobs, env_cfg, cfg, heldout, rng)
    held_queues = _heldout_queues(jobs, env_cfg, cfg, heldout,
                                  np.random.default_rng(cfg.seed + 0x9E37))
    qa = [venv.queue_arrays(q) for q in train_queues]
    n_tr = len(train_queues)
    # one stacked eval batch: train queues first, held-out queues after —
    # a single jitted rollout yields both metrics per record
    qa_eval = stack_queues(qa + [venv.queue_arrays(q) for q in held_queues])

    # segment length targeting ~eval_every completed episodes per scan;
    # never below one worst-case episode (2W steps: all-solo groups) —
    # env state resets at segment boundaries, so a shorter segment would
    # complete zero episodes and the driver loop could never terminate
    ep_len = env_cfg.window + math.ceil(env_cfg.window / env_cfg.c_max)
    seg_eps = max(1, min(cfg.eval_every, cfg.episodes))
    seg_steps = max(2 * env_cfg.window, math.ceil(seg_eps * ep_len / B))

    params, target, opt = agent.params, agent.target_params, agent.opt
    # round capacity up to a multiple of B: ring writes stay block-aligned
    capacity = -(-cfg.dqn.buffer_size // B) * B
    init = per_init if use_per else replay_init
    replay = init(capacity, venv.state_dim, venv.n_actions)
    key = jax.random.PRNGKey(cfg.seed)
    # segment-start context draws use their own key rather than consuming
    # from the main stream.  Note the *in-scan* streams still differ from a
    # profile-only run: context mode splits the carry key 4 ways instead of
    # 3, so per-step action/replay randomness is not comparable across the
    # two observation modes under one seed (the compiled programs differ
    # anyway — wider obs, extra sampling).
    ctx_key = jax.random.PRNGKey(cfg.seed + 0x51C3) if use_ctx else None
    env_steps = jnp.int32(0)
    updates = jnp.int32(0)
    eval_every = max(1, cfg.eval_every)
    episodes_done, next_eval = 0, eval_every
    history: list[dict] = []
    # telemetry accumulators flushed into each history record: sums of the
    # per-scan-step (loss, |td|, grad_norm) samples over steps that ran an
    # update since the last record
    tel = {"loss": 0.0, "td_abs": 0.0, "grad_norm": 0.0, "n": 0}

    while episodes_done < cfg.episodes:
        # each env runs one of the 20 fixed queues for this segment
        env_q = rng.integers(0, len(train_queues), size=B)
        qa_batch = stack_queues([qa[i] for i in env_q])
        r_env, r_obs, r_mask = venv.reset_batch(qa_batch)
        if use_ctx:
            # segment-start contexts; later episodes resample at auto-reset
            ctx_key, k0 = jax.random.split(ctx_key)
            r_env = r_env._replace(ctx=venv.sample_context(
                k0, r_env.queue.mean_d, r_env.queue.valid))
            r_obs = venv.obs_batch(r_env)
        # distinct buffers for the live-env side: the jitted segment donates
        # its carry, and XLA rejects the same buffer donated twice
        live_env = jax.tree.map(jnp.copy, r_env)
        carry = _Carry(env=live_env, obs=jnp.copy(r_obs), mask=jnp.copy(r_mask),
                       reset_env=r_env, reset_obs=r_obs, reset_mask=r_mask,
                       params=params, target=target, opt=opt, replay=replay,
                       key=key, env_steps=env_steps, updates=updates,
                       ep_ret=jnp.zeros((B,), jnp.float32))
        carry, outs = engine(carry, seg_steps)
        if cfg.telemetry:
            dones, rets, losses, tds, gnorms, cans = outs
            m = np.asarray(cans)
            if m.any():
                tel["loss"] += float(np.asarray(losses)[m].sum())
                tel["td_abs"] += float(np.asarray(tds)[m].sum())
                tel["grad_norm"] += float(np.asarray(gnorms)[m].sum())
                tel["n"] += int(m.sum())
        else:
            dones, rets = outs
        params, target, opt, replay, key = (carry.params, carry.target, carry.opt,
                                            carry.replay, carry.key)
        env_steps, updates = carry.env_steps, carry.updates
        n_done = int(np.asarray(dones).sum())
        episodes_done += n_done
        if episodes_done >= next_eval or episodes_done >= cfg.episodes:
            agent.params, agent.target_params, agent.opt = params, target, opt
            agent.env_steps, agent.updates = int(env_steps), int(updates)
            # device-resident greedy eval: every train queue in one jitted
            # batch rollout; record the mean relative throughput
            e_env, e_obs, e_mask = venv.reset_batch(qa_eval)
            tp = np.asarray(eval_fn(params, e_env, e_obs, e_mask))
            ep_reward = float(np.asarray(rets).sum() / max(1, n_done))
            rec = {"episode": episodes_done, "eps": agent.epsilon,
                   "ep_reward": ep_reward,
                   "eval_throughput": float(tp[:n_tr].mean()),
                   "heldout_throughput": (float(tp[n_tr:].mean())
                                          if held_queues else None)}
            if cfg.telemetry:
                n = tel["n"]
                rec["loss"] = tel["loss"] / n if n else None
                rec["td_abs"] = tel["td_abs"] / n if n else None
                rec["grad_norm"] = tel["grad_norm"] / n if n else None
                rec["beta"] = (float(beta_at(cfg.per_beta0, int(env_steps),
                                             cfg.dqn.eps_decay_steps))
                               if use_per else None)
                rec["updates"] = int(updates)
                tel = {"loss": 0.0, "td_abs": 0.0, "grad_norm": 0.0, "n": 0}
            history.append(rec)
            next_eval = (episodes_done // eval_every + 1) * eval_every
            if verbose:
                held = rec["heldout_throughput"]
                print(f"ep {rec['episode']:5d} eps={rec['eps']:.3f} "
                      f"reward={rec['ep_reward']:8.1f} "
                      f"eval_tp={rec['eval_throughput']:.3f} "
                      f"held_tp={held if held is None else f'{held:.3f}'}")

    agent.params, agent.target_params, agent.opt = params, target, opt
    agent.env_steps, agent.updates = int(env_steps), int(updates)
    return agent, history


# ---------------------------------------------------------------------------
# Seed-equivalent scalar loop (reference + throughput baseline)
# ---------------------------------------------------------------------------

def train_agent_scalar(jobs: list[JobProfile], env_cfg: EnvConfig | None = None,
                       cfg: TrainConfig | None = None,
                       heldout: set[str] | None = None,
                       verbose: bool = False) -> tuple[DQNAgent, list[dict]]:
    """The original per-step Python training loop, preserved verbatim."""
    cfg = cfg or TrainConfig()
    env_cfg = env_cfg or EnvConfig()
    env = CoScheduleEnv(env_cfg)
    agent = DQNAgent(env.state_dim, env.n_actions, cfg.dqn, seed=cfg.seed,
                     per_alpha=cfg.per_alpha, per_beta0=cfg.per_beta0,
                     per_eps=cfg.per_eps)
    rng = np.random.default_rng(cfg.seed)
    heldout = heldout if heldout is not None else heldout_split(jobs)
    train_queues = _train_queues(jobs, env_cfg, cfg, heldout, rng)

    history: list[dict] = []
    for ep in range(cfg.episodes):
        queue = train_queues[int(rng.integers(0, len(train_queues)))]
        state, mask = env.reset(queue)
        ep_reward = 0.0
        while not env.done:
            action = agent.act(state, mask)
            s2, r, done, mask2, _ = env.step(action)
            agent.observe(state, action, r, s2, done, mask2)
            state, mask = s2, mask2
            ep_reward += r
            for _ in range(cfg.updates_per_step):
                agent.update()
        if (ep + 1) % max(1, cfg.eval_every) == 0 or ep == cfg.episodes - 1:
            sched = RLScheduler(agent, env_cfg).schedule(train_queues[0])
            rec = {"episode": ep + 1, "eps": agent.epsilon, "ep_reward": ep_reward,
                   "eval_throughput": relative_throughput(sched)}
            history.append(rec)
            if verbose:
                print(f"ep {ep+1:5d} eps={agent.epsilon:.3f} "
                      f"reward={ep_reward:8.1f} eval_tp={rec['eval_throughput']:.3f}")
    return agent, history


# ---------------------------------------------------------------------------
# Sim-in-the-loop training on queueing reward (+ population-based training)
# ---------------------------------------------------------------------------

@dataclass
class TrainOnlineConfig:
    """Config for :func:`train_online` — the environment is the vectorized
    serving simulator itself, so the reward is the real queueing outcome
    (negative per-window wait/turnaround, makespan terminal) rather than
    the offline per-window throughput proxy."""

    rounds: int = 30                    # collect -> update -> eval cycles
    traces_per_round: int = 6           # fresh serving traces per member
    n_arrivals: int = 48                # arrivals per trace
    window: int = 8                     # serve window (<= env_cfg.window)
    backfill: bool = True
    capacity: int = 128                 # engine trace capacity
    scenarios: tuple = (("poisson", 1.25), ("mmpp", 1.25),
                        ("heavy_tailed", 1.1), ("diurnal", 1.0))
    seed: int = 0
    eps_start: float = 0.5              # round-schedule ε (not cfg.dqn's)
    eps_end: float = 0.05
    eps_decay_rounds: int = 20
    updates_per_round: int = 48         # DQN updates after each collect
    target_sync_updates: int = 32       # target refresh cadence, in updates
    push_block: int = 32                # replay ring block-push size
    population: int = 4                 # PBT members
    pbt_interval: int = 5               # rounds between exploit/explore
    pbt_quantile: float = 0.25          # copy bottom q from top q
    eval_traces: int = 6                # shared eval set, one sweep/round
    wait_weight: float = 1.0            # reward mix (per arrival)
    turnaround_weight: float = 0.0
    makespan_weight: float = 1.0
    per_alpha: float = 0.0              # PER exponent; 0 = uniform ring
    per_beta0: float = 0.4
    per_eps: float = 1e-3
    dqn: DQNConfig = field(default_factory=lambda: DQNConfig(
        buffer_size=20_000))


def _stitch_transitions(roll, n_windows: int, makespan: float,
                        cfg: TrainOnlineConfig):
    """Host-side transition stitcher for one trace rollout.

    Chains every valid decision step (window-major, step order) into one
    serving episode.  Window ``w``'s queueing bucket (member waits +
    turnarounds, normalized per arrival) lands as negative reward on the
    *last* decision of window ``w`` — the close that committed the plan;
    windows with no decisions (all first-sight solos) fold into the most
    recent earlier decision (or the first, for a leading window).  The
    final transition adds the makespan terminal and sets ``done``; its
    ``mask2`` is all-False, which the TD target treats as terminal.
    Returns ``None`` when the trace produced no decisions at all.
    """
    valid = np.asarray(roll.valid)[:n_windows]
    if not valid.any():
        return None
    idx = np.argwhere(valid)                      # row-major: window, step
    m = len(idx)
    obs = np.asarray(roll.obs)[:n_windows]
    act = np.asarray(roll.act)[:n_windows]
    mask = np.asarray(roll.mask)[:n_windows]
    s = obs[idx[:, 0], idx[:, 1]]
    a = act[idx[:, 0], idx[:, 1]]
    mk = mask[idx[:, 0], idx[:, 1]]
    s2 = np.concatenate([s[1:], np.zeros_like(s[:1])])
    mask2 = np.concatenate([mk[1:], np.zeros_like(mk[:1])])
    done = np.zeros(m, np.float32)
    done[-1] = 1.0
    norm = 1.0 / max(1, cfg.n_arrivals)
    bucket = -(cfg.wait_weight * np.asarray(roll.w_wait, np.float64)
               + cfg.turnaround_weight
               * np.asarray(roll.w_turn, np.float64))[:n_windows] * norm
    r = np.zeros(m, np.float64)
    # last decision with window <= w; leading no-decision windows fold
    # forward into the first decision
    tx = np.maximum(np.searchsorted(idx[:, 0], np.arange(n_windows),
                                    side="right") - 1, 0)
    np.add.at(r, tx, bucket)
    r[-1] += -cfg.makespan_weight * float(makespan) * norm
    return {"s": s.astype(np.float32), "a": a.astype(np.int32),
            "r": r.astype(np.float32), "s2": s2.astype(np.float32),
            "done": done, "mask2": mask2.astype(bool)}


_UPDATER_CACHE: dict = {}


def _online_updater(dqn_cfg: DQNConfig, n_updates: int, sync_updates: int,
                    per):
    """Jitted K-update loop over a replay ring: sample -> double-DQN step
    -> priority refresh (PER) -> cadenced target sync.  ``per`` is None
    for the uniform ring or ``(alpha, per_eps)`` for the sum-tree."""
    key_t = (dqn_cfg, n_updates, sync_updates, per)
    if key_t in _UPDATER_CACHE:
        return _UPDATER_CACHE[key_t]

    def run(params, target, opt, replay, key, updates, beta):
        def upd(_, carry):
            params, target, opt, replay, key, updates = carry
            key, ks = jax.random.split(key)
            if per is None:
                batch = replay_sample(replay, ks, dqn_cfg.batch_size)
                params, opt, _ = _dqn_update(params, target, opt, batch,
                                             dqn_cfg)
            else:
                alpha, p_eps = per
                batch, idx, w = per_sample(replay, ks, dqn_cfg.batch_size,
                                           alpha, beta)
                params, opt, _, td = _dqn_update_per(params, target, opt,
                                                     batch, w, dqn_cfg)
                if alpha > 0.0:
                    replay = per_update(replay, idx, td, alpha, p_eps)
            updates = updates + 1
            sync = updates % sync_updates == 0
            target = jax.tree.map(lambda p, t: jnp.where(sync, p, t),
                                  params, target)
            return params, target, opt, replay, key, updates
        return jax.lax.fori_loop(
            0, n_updates, upd, (params, target, opt, replay, key, updates))

    fn = jax.jit(run)
    if len(_UPDATER_CACHE) >= 8:
        _UPDATER_CACHE.pop(next(iter(_UPDATER_CACHE)))
    _UPDATER_CACHE[key_t] = fn
    return fn


_COLLECTOR_CACHE: dict = {}


def _collector_for(env_cfg: EnvConfig, cfg: TrainOnlineConfig):
    from repro.online.vecsim import make_rollout_collector
    key_t = (env_cfg.key(), cfg.window, cfg.backfill, cfg.capacity)
    if key_t not in _COLLECTOR_CACHE:
        if len(_COLLECTOR_CACHE) >= 8:
            _COLLECTOR_CACHE.pop(next(iter(_COLLECTOR_CACHE)))
        _COLLECTOR_CACHE[key_t] = make_rollout_collector(
            env_cfg, window=cfg.window, backfill=cfg.backfill,
            capacity=cfg.capacity)
    return _COLLECTOR_CACHE[key_t]


def train_online(jobs: list[JobProfile], env_cfg: EnvConfig | None = None,
                 cfg: TrainOnlineConfig | None = None,
                 warm_start: DQNAgent | None = None,
                 verbose: bool = False) -> tuple[DQNAgent, list[dict]]:
    """Sim-in-the-loop training: the vectorized serving simulator is the
    environment, queueing outcome is the reward.

    Each round, every population member rolls ``traces_per_round`` fresh
    traces of its (family, load) scenario through the ε-greedy training
    engine (`vecsim` ``train=True``), the host stitches the logged
    window-seam decisions into replay transitions whose rewards are the
    engine-accumulated per-window wait/turnaround (plus a terminal
    makespan term), and ``updates_per_round`` double-DQN updates run on
    the member's ring.  All members are then scored in ONE
    ``sweep(param_sets=...)`` call on a shared eval-trace set (mean p99
    wait — lower is better); every ``pbt_interval`` rounds the bottom
    ``pbt_quantile`` of members copy the top performers' weights and
    re-draw their exploration scale and scenario (exploit/explore over
    agents AND trace families).  Returns the best member as a
    :class:`DQNAgent` plus per-round history.  With ``warm_start`` the
    population starts from the given agent's weights, and the unchanged
    warm-start params are scored in the final eval as an elitism guard —
    if no trained member beats them, the original agent's weights are
    returned (``history[-1]["selected"] == "warm_start"``).
    """
    from repro.online import TRACE_FAMILIES
    from repro.online.policies import RLDispatchPolicy
    from repro.online.vecsim import (
        VectorizedClusterSimulator, build_rl_job_table, compile_trace,
    )
    from repro.core.partition import N_UNITS

    cfg = cfg or TrainOnlineConfig()
    env_cfg = env_cfg or EnvConfig()
    if cfg.window > env_cfg.window:
        raise ValueError(f"serve window {cfg.window} > agent window "
                         f"{env_cfg.window}")
    for fam, _ld in cfg.scenarios:
        if fam not in TRACE_FAMILIES:
            raise ValueError(f"unknown trace family {fam!r}")
    env = CoScheduleEnv(env_cfg)
    state_dim, n_actions = env.state_dim, env.n_actions
    pop = max(1, cfg.population)
    rng = np.random.default_rng(cfg.seed)
    base_key = jax.random.PRNGKey(cfg.seed)
    collect = _collector_for(env_cfg, cfg)
    use_per = cfg.per_alpha > 0.0
    per_t = (cfg.per_alpha, cfg.per_eps) if use_per else None
    updater = _online_updater(cfg.dqn, cfg.updates_per_round,
                              max(1, cfg.target_sync_updates), per_t)
    blk = cfg.push_block
    ring_cap = -(-cfg.dqn.buffer_size // blk) * blk

    def _fresh_member(m: int) -> dict:
        seed_agent = DQNAgent(state_dim, n_actions, cfg.dqn,
                              seed=cfg.seed + m)
        if warm_start is not None:
            params = jax.tree.map(jnp.copy, warm_start.params)
            target = jax.tree.map(jnp.copy, warm_start.target_params)
            opt = jax.tree.map(jnp.copy, warm_start.opt)
        else:
            params = seed_agent.params
            target = seed_agent.target_params
            opt = seed_agent.opt
        ring = (per_init(ring_cap, state_dim, n_actions) if use_per
                else replay_init(ring_cap, state_dim, n_actions))
        return {"params": params, "target": target, "opt": opt,
                "replay": ring, "updates": jnp.int32(0),
                "stage": {f: [] for f in
                          ("s", "a", "r", "s2", "done", "mask2")},
                "staged": 0, "env_steps": 0,
                "eps_scale": 1.0, "scenario": m % len(cfg.scenarios),
                "score": float("inf")}

    members = [_fresh_member(m) for m in range(pop)]

    # shared eval traces, round-robin over the scenario axis
    eval_traces = [
        TRACE_FAMILIES[cfg.scenarios[t % len(cfg.scenarios)][0]](
            jobs, n=cfg.n_arrivals,
            load=cfg.scenarios[t % len(cfg.scenarios)][1],
            seed=cfg.seed + 9000 + t)
        for t in range(max(1, cfg.eval_traces))]
    eval_agent = DQNAgent(state_dim, n_actions, cfg.dqn, seed=cfg.seed)
    vec = VectorizedClusterSimulator(
        RLDispatchPolicy(eval_agent, env_cfg), window=cfg.window,
        backfill=cfg.backfill, capacity=cfg.capacity)

    def _eval_scores(param_list) -> np.ndarray:
        summ = vec.sweep(eval_traces, param_sets=param_list)
        return np.asarray(summ.p99_wait, np.float64).mean(axis=1)

    widths = jnp.full((cfg.traces_per_round,), N_UNITS, jnp.int32)
    history: list[dict] = []
    total_tx = 0
    for rnd in range(cfg.rounds):
        frac = min(1.0, rnd / max(1, cfg.eps_decay_rounds))
        eps_round = cfg.eps_start + (cfg.eps_end - cfg.eps_start) * frac
        for m, mem in enumerate(members):
            fam, load = cfg.scenarios[mem["scenario"]]
            traces = [TRACE_FAMILIES[fam](
                jobs, n=cfg.n_arrivals, load=load,
                seed=cfg.seed + 1 + rnd * 131 + m * 17 + t)
                for t in range(cfg.traces_per_round)]
            names: dict[str, int] = {}
            tjobs: list = []
            compiled = [compile_trace(t, cfg.capacity, names, tjobs)[0]
                        for t in traces]
            batch = jax.tree.map(lambda *xs: jnp.stack(xs), *compiled)
            rjt = build_rl_job_table(tjobs)
            keys = jax.random.split(
                jax.random.fold_in(base_key, rnd * pop + m),
                cfg.traces_per_round)
            eps = jnp.float32(min(1.0, eps_round * mem["eps_scale"]))
            summ, roll = collect(batch, rjt, mem["params"], keys, eps,
                                 widths)
            VectorizedClusterSimulator._check_err(
                int(np.max(np.asarray(summ.err))))
            n_win = np.asarray(summ.dispatches, np.int64)
            mks = np.asarray(summ.makespan, np.float64)
            for t in range(cfg.traces_per_round):
                one = jax.tree.map(lambda x: x[t], roll)
                tx = _stitch_transitions(one, int(n_win[t]),
                                         float(mks[t]), cfg)
                if tx is None:
                    continue
                for f in mem["stage"]:
                    mem["stage"][f].append(tx[f])
                mem["staged"] += len(tx["a"])
                mem["env_steps"] += len(tx["a"])
                total_tx += len(tx["a"])
            # block-aligned ring pushes; remainder stays staged
            if mem["staged"] >= blk:
                full = {f: np.concatenate(v) for f, v in
                        mem["stage"].items()}
                n_push = (mem["staged"] // blk) * blk
                for lo in range(0, n_push, blk):
                    chunk = {f: jnp.asarray(v[lo:lo + blk])
                             for f, v in full.items()}
                    mem["replay"] = (per_push(mem["replay"], chunk)
                                     if use_per
                                     else replay_push(mem["replay"], chunk))
                for f in mem["stage"]:
                    mem["stage"][f] = [full[f][n_push:]]
                mem["staged"] -= n_push
            size = int(mem["replay"].ring.size if use_per
                       else mem["replay"].size)
            if size >= cfg.dqn.batch_size:
                beta = jnp.float32(beta_at(cfg.per_beta0,
                                           mem["env_steps"],
                                           cfg.dqn.eps_decay_steps))
                (mem["params"], mem["target"], mem["opt"], mem["replay"],
                 _, mem["updates"]) = updater(
                    mem["params"], mem["target"], mem["opt"],
                    mem["replay"],
                    jax.random.fold_in(base_key, 70_000 + rnd * pop + m),
                    mem["updates"], beta)

        scores = _eval_scores([mem["params"] for mem in members])
        for mem, sc in zip(members, scores):
            mem["score"] = float(sc)
        order = np.argsort(scores)
        rec = {"round": rnd + 1, "eps": float(eps_round),
               "scores": [float(s) for s in scores],
               "best_member": int(order[0]),
               "best_p99": float(scores[order[0]]),
               "transitions": total_tx}
        if pop > 1 and cfg.pbt_interval > 0 and rnd < cfg.rounds - 1 \
                and (rnd + 1) % cfg.pbt_interval == 0:
            n_q = max(1, int(pop * cfg.pbt_quantile))
            swaps = []
            for dst, src in zip(order[-n_q:], order[:n_q]):
                lo, hi = members[dst], members[src]
                lo["params"] = jax.tree.map(jnp.copy, hi["params"])
                lo["target"] = jax.tree.map(jnp.copy, hi["target"])
                lo["opt"] = jax.tree.map(jnp.copy, hi["opt"])
                lo["eps_scale"] = float(np.clip(
                    hi["eps_scale"] * rng.choice([0.8, 1.25]), 0.25, 2.0))
                lo["scenario"] = int(rng.integers(len(cfg.scenarios)))
                swaps.append((int(dst), int(src)))
            rec["pbt"] = swaps
        history.append(rec)
        if verbose:
            print(f"round {rnd + 1:3d} eps={eps_round:.3f} "
                  f"best_p99={rec['best_p99']:.2f} tx={total_tx}")

    # final selection (+ warm-start elitism guard: a refresh must beat the
    # incumbent strictly on eval, else the incumbent's weights are kept)
    finals = [mem["params"] for mem in members]
    labels: list = list(range(pop))
    if warm_start is not None:
        finals.append(warm_start.params)
        labels.append("warm_start")
    scores = _eval_scores(finals)
    best = int(np.argmin(scores[:pop]))
    if warm_start is not None and scores[pop] <= scores[best]:
        best = pop
    selected = labels[best]
    agent = DQNAgent(state_dim, n_actions, cfg.dqn, seed=cfg.seed,
                     per_alpha=cfg.per_alpha, per_beta0=cfg.per_beta0,
                     per_eps=cfg.per_eps)
    if selected == "warm_start":
        agent.params = jax.tree.map(jnp.copy, warm_start.params)
        agent.target_params = jax.tree.map(jnp.copy,
                                           warm_start.target_params)
        agent.opt = jax.tree.map(jnp.copy, warm_start.opt)
    else:
        mem = members[selected]
        agent.params, agent.target_params = mem["params"], mem["target"]
        agent.opt = mem["opt"]
        agent.env_steps = int(mem["env_steps"])
        agent.updates = int(mem["updates"])
    if history:
        history[-1]["selected"] = ("warm_start"
                                   if selected == "warm_start"
                                   else int(selected))
        history[-1]["final_scores"] = [float(s) for s in scores]
    return agent, history

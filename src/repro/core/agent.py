"""DQN agent: masked ε-greedy action selection + jit'd double-DQN updates.

Two call surfaces share the same parameters and update rule:

  * ``DQNAgent`` — the stateful single-env agent used by ``RLScheduler``
    (``greedy_episode``: a whole greedy episode in one device call) and
    the scalar training loop (``act``: one step a call).  Greedy
    (evaluation) calls do **not** advance ``env_steps``, so evaluation
    frequency cannot perturb the ε schedule.
  * ``act_batch`` / ``epsilon_at`` — pure functions over (params, key,
    obs, mask) used by the vectorized engine: vmapped ε-greedy selection
    with ``jax.random`` keys and the linear ε schedule computed in-graph,
    so the whole rollout lives inside one ``lax.scan``.
"""
from __future__ import annotations

import functools
from dataclasses import dataclass

import jax
import jax.numpy as jnp
import numpy as np

from repro import spans
from repro.core.network import (
    dqn_apply, greedy_q_action, init_dqn, masked_argmax,
)
from repro.core.partition import enumerate_partitions
from repro.core.profiles import FEATURES
from repro.core.replay import PrioritizedReplayBuffer, ReplayBuffer


@dataclass(frozen=True)
class DQNConfig:
    gamma: float = 0.99
    lr: float = 5e-4
    batch_size: int = 128
    buffer_size: int = 100_000
    target_sync: int = 500           # updates between target-network syncs
    eps_start: float = 1.0
    eps_end: float = 0.01
    eps_decay_steps: int = 15_000    # env steps for linear ε decay
    huber_delta: float = 1.0
    reward_scale: float = 0.01       # rewards are O(100); keep TD targets O(1)


def _adam_init(params):
    z = lambda p: jnp.zeros_like(p)
    return {"m": jax.tree.map(z, params), "v": jax.tree.map(z, params),
            "t": jnp.zeros((), jnp.int32)}


def _td_and_huber(p, target_params, batch, cfg: DQNConfig):
    """Per-sample double-DQN TD error and its Huber transform."""
    q = dqn_apply(p, batch["s"])                                       # (B, A)
    q_sa = jnp.take_along_axis(q, batch["a"][:, None], axis=1)[:, 0]
    # double DQN: online argmax (masked), target value
    q2_online = dqn_apply(p, batch["s2"])
    a2 = masked_argmax(q2_online, batch["mask2"])
    q2_target = dqn_apply(target_params, batch["s2"])
    v2 = jnp.take_along_axis(q2_target, a2[:, None], axis=1)[:, 0]
    v2 = jnp.where(batch["mask2"].any(axis=1), v2, 0.0)               # terminal: no actions
    y = batch["r"] * cfg.reward_scale + cfg.gamma * (1.0 - batch["done"]) * v2
    y = jax.lax.stop_gradient(y)
    err = q_sa - y
    huber = jnp.where(jnp.abs(err) <= cfg.huber_delta,
                      0.5 * err ** 2,
                      cfg.huber_delta * (jnp.abs(err) - 0.5 * cfg.huber_delta))
    return err, huber


def _adam_step(params, grads, opt, lr: float):
    t = opt["t"] + 1
    b1, b2, eps = 0.9, 0.999, 1e-8
    m = jax.tree.map(lambda m_, g: b1 * m_ + (1 - b1) * g, opt["m"], grads)
    v = jax.tree.map(lambda v_, g: b2 * v_ + (1 - b2) * g * g, opt["v"], grads)
    tf = t.astype(jnp.float32)
    lr_t = lr * jnp.sqrt(1 - b2 ** tf) / (1 - b1 ** tf)
    params = jax.tree.map(lambda p, m_, v_: p - lr_t * m_ / (jnp.sqrt(v_) + eps),
                          params, m, v)
    return params, {"m": m, "v": v, "t": t}


@functools.partial(jax.jit, static_argnames=("cfg",))
def _dqn_update(params, target_params, opt, batch, cfg: DQNConfig):
    def loss_fn(p):
        _, huber = _td_and_huber(p, target_params, batch, cfg)
        return jnp.mean(huber)

    loss, grads = jax.value_and_grad(loss_fn)(params)
    params, opt = _adam_step(params, grads, opt, cfg.lr)
    return params, opt, loss


def _grad_norm(grads):
    """Global L2 norm over all gradient leaves (training telemetry)."""
    return jnp.sqrt(sum(jnp.sum(g * g) for g in jax.tree.leaves(grads)))


@functools.partial(jax.jit, static_argnames=("cfg",))
def _dqn_update_aux(params, target_params, opt, batch, cfg: DQNConfig):
    """``_dqn_update`` + telemetry aux -> (params, opt, loss, |td|, gnorm).

    The aux outputs ride ``has_aux`` on the same forward pass, and the
    grad norm is read off the gradients the Adam step consumes anyway —
    the parameter trajectory is bit-identical to ``_dqn_update``
    (pinned by the training-telemetry parity test).
    """
    def loss_fn(p):
        err, huber = _td_and_huber(p, target_params, batch, cfg)
        return jnp.mean(huber), jnp.mean(jnp.abs(err))

    (loss, td), grads = jax.value_and_grad(loss_fn, has_aux=True)(params)
    gnorm = _grad_norm(grads)
    params, opt = _adam_step(params, grads, opt, cfg.lr)
    return params, opt, loss, td, gnorm


@functools.partial(jax.jit, static_argnames=("cfg",))
def _dqn_update_per(params, target_params, opt, batch, w, cfg: DQNConfig):
    """Importance-weighted double-DQN update -> (params, opt, loss, |td|).

    ``w`` are per-sample IS weights from the prioritized sampler (applied
    inside the loss); the returned absolute TD errors feed the sum-tree
    priority refresh.  With ``w == 1`` this is bit-identical to
    ``_dqn_update`` — multiplying the Huber terms by exact ones changes no
    float — which is what keeps ``per_alpha = 0`` a true uniform engine.
    """
    def loss_fn(p):
        err, huber = _td_and_huber(p, target_params, batch, cfg)
        return jnp.mean(w * huber), jnp.abs(err)

    (loss, td), grads = jax.value_and_grad(loss_fn, has_aux=True)(params)
    params, opt = _adam_step(params, grads, opt, cfg.lr)
    return params, opt, loss, td


@functools.partial(jax.jit, static_argnames=("cfg",))
def _dqn_update_per_aux(params, target_params, opt, batch, w, cfg: DQNConfig):
    """``_dqn_update_per`` + grad-norm aux -> (params, opt, loss, td, gnorm)."""
    def loss_fn(p):
        err, huber = _td_and_huber(p, target_params, batch, cfg)
        return jnp.mean(w * huber), jnp.abs(err)

    (loss, td), grads = jax.value_and_grad(loss_fn, has_aux=True)(params)
    gnorm = _grad_norm(grads)
    params, opt = _adam_step(params, grads, opt, cfg.lr)
    return params, opt, loss, td, gnorm


@jax.jit
def _greedy_action(params, obs, mask):
    return greedy_q_action(params, obs, mask)


@functools.partial(jax.jit, static_argnames=("window", "c_max", "obs_context"))
def _greedy_episode(params, packed, window: int, c_max: int,
                    obs_context: bool):
    """One whole greedy co-scheduling episode on the device.

    ``packed`` is the window on the host's side, flat f32: the ``(W, F)``
    profile features, the ``(W,)`` slot validity and, under
    ``obs_context``, the context block.  Each of the ``2 W`` scan steps
    (selects plus closes bound any episode) builds the observation and
    mask as ``VecCoScheduleEnv._obs``/``_mask`` do, picks the action with
    ``greedy_q_action`` and applies it; steps after done, or on an invalid
    action, change nothing.  Returns ``(2 W + 2,)`` i32: the actions taken
    (``-1`` where none), their count and the done flag.
    """
    W, C, F = window, c_max, len(FEATURES)
    i32, f32 = jnp.int32, jnp.float32
    arity = jnp.asarray([p.arity for p in enumerate_partitions(C)], i32)
    features = packed[:W * F].reshape(W, F)
    valid = packed[W * F:W * F + W] > 0.5
    ctx = packed[W * F + W:]
    w_rng, c_rng = jnp.arange(W, dtype=i32), jnp.arange(C, dtype=i32)

    def step(carry, _):
        sched, gidx, gsize = carry
        member = jnp.any((gidx[None, :] == w_rng[:, None])
                         & (c_rng < gsize)[None, :], axis=1)
        avail = valid & ~sched & ~member
        progress = gsize.astype(f32) / max(1, C)
        flags = jnp.stack([avail.astype(f32), member.astype(f32),
                           (sched & valid).astype(f32), (~valid).astype(f32),
                           jnp.where(valid, progress, 0.0)], axis=1)
        obs = jnp.concatenate([features, flags], axis=1).reshape(-1)
        if obs_context:
            obs = jnp.concatenate([obs, ctx])
        mask = jnp.concatenate([avail & (gsize < C),
                                (gsize >= 1) & (arity == gsize)])
        done = jnp.all(sched | ~valid) & (gsize == 0)
        act = greedy_q_action(params, obs, mask)
        ok = ~done & mask[act]
        do_sel, do_close = ok & (act < W), ok & (act >= W)
        sched = sched | (member & do_close)
        gidx = gidx.at[jnp.where(do_sel, gsize, C)].set(act, mode="drop")
        gidx = jnp.where(do_close, jnp.full(C, -1, i32), gidx)
        gsize = jnp.where(do_close, 0, gsize + do_sel.astype(i32))
        return (sched, gidx, gsize), jnp.where(ok, act, -1)

    init = (jnp.zeros(W, bool), jnp.full(C, -1, i32), jnp.int32(0))
    (sched, _, gsize), acts = jax.lax.scan(step, init, None, length=2 * W)
    done = jnp.all(sched | ~valid) & (gsize == 0)
    return jnp.concatenate([acts, jnp.sum(acts >= 0, dtype=i32)[None],
                            done.astype(i32)[None]])


def epsilon_at(cfg: DQNConfig, env_steps):
    """Linear ε schedule as a pure function of the env-step count.

    Accepts a plain int (scalar agent hot path — no jnp dispatch) or a
    traced array (inside the scanned engine)."""
    if isinstance(env_steps, (int, float)):
        frac = min(1.0, env_steps / max(1, cfg.eps_decay_steps))
    else:
        frac = jnp.clip(env_steps / max(1, cfg.eps_decay_steps), 0.0, 1.0)
    return cfg.eps_start + (cfg.eps_end - cfg.eps_start) * frac


def beta_at(beta0: float, env_steps, decay_steps: int):
    """Linear IS-exponent anneal β0 -> 1 over the ε-decay horizon.

    Prioritized replay's bias correction should be complete (β = 1) by the
    time exploration has settled, so β shares ``eps_decay_steps``.  Accepts
    a plain int (scalar loop) or a traced array (scanned engine), like
    ``epsilon_at``.
    """
    if isinstance(env_steps, (int, float)):
        frac = min(1.0, env_steps / max(1, decay_steps))
    else:
        frac = jnp.clip(env_steps / max(1, decay_steps), 0.0, 1.0)
    return beta0 + (1.0 - beta0) * frac


@jax.jit
def act_batch(params, key, obs, mask, eps):
    """Vmapped masked ε-greedy: one action per env row.

    obs (B, D), mask (B, A) -> (B,) i32.  Exploration draws a uniformly
    random *valid* action (argmax of uniform scores over the mask).
    """
    greedy = masked_argmax(dqn_apply(params, obs), mask)
    k_bern, k_choice = jax.random.split(key)
    explore = jax.random.uniform(k_bern, greedy.shape) < eps
    scores = jax.random.uniform(k_choice, mask.shape)
    rand = jnp.argmax(jnp.where(mask, scores, -1.0), axis=-1)
    return jnp.where(explore, rand, greedy).astype(jnp.int32)


class DQNAgent:
    def __init__(self, state_dim: int, n_actions: int, cfg: DQNConfig | None = None,
                 seed: int = 0, per_alpha: float = 0.0, per_beta0: float = 0.4,
                 per_eps: float = 1e-3):
        self.cfg = cfg or DQNConfig()
        key = jax.random.PRNGKey(seed)
        self.params = init_dqn(key, state_dim, n_actions)
        self.target_params = jax.tree.map(jnp.copy, self.params)
        self.opt = _adam_init(self.params)
        self._replay: ReplayBuffer | None = None   # lazy: ~100 MB at defaults
        self._replay_shape = (state_dim, n_actions, seed)
        self.per_alpha = per_alpha                 # 0 -> uniform replay
        self.per_beta0 = per_beta0
        self.per_eps = per_eps
        self.rng = np.random.default_rng(seed)
        self.env_steps = 0
        self.updates = 0

    @property
    def replay(self) -> ReplayBuffer:
        """Numpy replay for the scalar loop; the vectorized engine keeps its
        own on-device ring, so allocation waits for first use."""
        if self._replay is None:
            d, a, seed = self._replay_shape
            if self.per_alpha > 0:
                self._replay = PrioritizedReplayBuffer(
                    self.cfg.buffer_size, d, a, seed,
                    alpha=self.per_alpha, eps=self.per_eps)
            else:
                self._replay = ReplayBuffer(self.cfg.buffer_size, d, a, seed)
        return self._replay

    # ----------------------------------------------------------------- act
    @property
    def epsilon(self) -> float:
        return epsilon_at(self.cfg, self.env_steps)

    def act(self, state: np.ndarray, mask: np.ndarray, greedy: bool = False) -> int:
        if not greedy:
            # only exploration steps advance the ε-decay schedule; greedy
            # (evaluation) calls must not change exploration behaviour
            self.env_steps += 1
            if self.rng.random() < self.epsilon:
                return int(self.rng.choice(np.flatnonzero(mask)))
        # greedy selection routes through the same jitted kernel the
        # vectorized engine closes over in-graph (see network.greedy_q_action).
        # One round trip: put the observation on the device, launch the
        # forward pass (returns once enqueued), fetch the action back
        span = spans.span
        with span("repro.agent.act"):
            with span("repro.agent.act.put"):
                obs, valid = jnp.asarray(state), jnp.asarray(mask)
            with span("repro.agent.act.launch"):
                out = _greedy_action(self.params, obs, valid)
            with span("repro.agent.act.fetch"):
                return int(out)

    # -------------------------------------------------------------- learn
    def observe(self, s, a, r, s2, done, mask2) -> None:
        self.replay.push(s, a, r, s2, done, mask2)

    def update(self) -> float | None:
        if len(self.replay) < self.cfg.batch_size:
            return None
        if self.per_alpha > 0:
            beta = beta_at(self.per_beta0, self.env_steps, self.cfg.eps_decay_steps)
            batch, idx, w = self.replay.sample(self.cfg.batch_size, beta)
            batch = {k: jnp.asarray(v) for k, v in batch.items()}
            self.params, self.opt, loss, td = _dqn_update_per(
                self.params, self.target_params, self.opt, batch,
                jnp.asarray(w), self.cfg)
            self.replay.update_priorities(idx, np.asarray(td))
        else:
            batch = self.replay.sample(self.cfg.batch_size)
            batch = {k: jnp.asarray(v) for k, v in batch.items()}
            self.params, self.opt, loss = _dqn_update(
                self.params, self.target_params, self.opt, batch, self.cfg)
        self.updates += 1
        if self.updates % self.cfg.target_sync == 0:
            self.target_params = jax.tree.map(jnp.copy, self.params)
        return float(loss)

    def greedy_episode(self, packed: np.ndarray, window: int, c_max: int,
                       obs_context: bool) -> np.ndarray:
        """A whole greedy episode in one round trip (see
        :func:`_greedy_episode` for ``packed`` and the result): one
        transfer in, one launch, one fetch.  The parameters are an argument
        of the compiled program, so a swap to same-shaped ones reuses it."""
        span = spans.span
        with span("repro.agent.episode"):
            with span("repro.agent.episode.put"):
                x = jax.device_put(packed)
            with span("repro.agent.episode.launch"):
                out = _greedy_episode(self.params, x, window=window,
                                      c_max=c_max, obs_context=obs_context)
            with span("repro.agent.episode.fetch"):
                return np.asarray(out)

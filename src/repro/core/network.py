"""Dueling double deep Q-network in pure JAX (paper §IV-D / Table VI).

Architecture (paper Table VI): input W x (f+5) — widened by the
arrival-aware context block (busy-unit mask + per-slot ages + queue depth,
see docs/observation.md) when the environment runs with
``EnvConfig.obs_context``; 3 fully-connected hidden layers 512/256/128,
ReLU; dueling heads V (1) and A (n_actions); Q = V + A - mean(A)
[Wang et al. 2016]. Double-DQN targets use the online network's argmax
with the target network's value [van Hasselt et al. 2016].

``widen_dqn_params`` is the bridge between the two input widths: it
zero-pads the input layer for the appended features, so a profile-only
agent warm-starts a context-aware run while computing the identical
Q-function at zero context.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

HIDDEN = (512, 256, 128)


def init_dqn(key, in_dim: int, n_actions: int, hidden=HIDDEN) -> dict:
    params = {}
    dims = (in_dim, *hidden)
    keys = jax.random.split(key, len(hidden) + 2)
    for i in range(len(hidden)):
        params[f"w{i}"] = jax.random.normal(keys[i], (dims[i], dims[i + 1])) * (2.0 / dims[i]) ** 0.5
        params[f"b{i}"] = jnp.zeros((dims[i + 1],))
    params["wV"] = jax.random.normal(keys[-2], (hidden[-1], 1)) * (1.0 / hidden[-1]) ** 0.5
    params["bV"] = jnp.zeros((1,))
    params["wA"] = jax.random.normal(keys[-1], (hidden[-1], n_actions)) * (1.0 / hidden[-1]) ** 0.5
    params["bA"] = jnp.zeros((n_actions,))
    return params


def dqn_apply(params: dict, x: jnp.ndarray) -> jnp.ndarray:
    """x: (..., in_dim) -> Q (..., n_actions)."""
    h = x
    i = 0
    while f"w{i}" in params:
        h = jax.nn.relu(h @ params[f"w{i}"] + params[f"b{i}"])
        i += 1
    v = h @ params["wV"] + params["bV"]                    # (..., 1)
    a = h @ params["wA"] + params["bA"]                    # (..., n_actions)
    return v + a - jnp.mean(a, axis=-1, keepdims=True)


def masked_argmax(q: jnp.ndarray, mask: jnp.ndarray) -> jnp.ndarray:
    return jnp.argmax(jnp.where(mask, q, -jnp.inf), axis=-1)


def greedy_q_action(params: dict, obs: jnp.ndarray, mask: jnp.ndarray) -> jnp.ndarray:
    """Greedy fit-masked action for one observation: () i32.

    The single action-selection implementation shared by
    ``DQNAgent.act(greedy=True)``, the heap serving path's in-graph
    episode (``DQNAgent.greedy_episode``) and the vectorized engine's
    in-graph policy seam — ties break to the first
    maximal index on both, so the two paths pick identical actions on
    identical observations (the property the parity fuzzer pins).
    """
    q = dqn_apply(params, obs[None])[0]
    return masked_argmax(q, mask).astype(jnp.int32)


def widen_dqn_params(params: dict, extra_in: int) -> dict:
    """Zero-pad the input layer for ``extra_in`` *appended* observation dims.

    New observation features are appended at the end of the flat state
    vector (the context block's contract), so the matching new rows of
    ``w0`` go at the end of its input axis and are zero — the widened
    network computes the same Q-values whenever the appended features are
    zero.  This is the warm-start path from a profile-only agent into an
    arrival-aware one: at zero context the two agents are the same
    function, and training only has to learn how context should *modulate*
    an already-competent policy.  Works on any params-shaped tree whose
    only input-anchored leaf is ``w0`` (online/target params and the Adam
    moment trees alike).
    """
    assert extra_in >= 0, extra_in
    out = dict(params)
    w0 = params["w0"]
    pad = jnp.zeros((extra_in, w0.shape[1]), w0.dtype)
    out["w0"] = jnp.concatenate([w0, pad], axis=0)
    return out

"""Shared benchmark utilities: cached agent training, CSV emission."""
from __future__ import annotations

import os
import time


from repro import checkpoint as ck
from repro.core import DQNAgent, EnvConfig, RLScheduler, TrainConfig, train_agent
from repro.core.agent import DQNConfig
from repro.core.env import CoScheduleEnv

AGENT_DIR = "experiments/agents"
DRYRUN_DIR = "experiments/dryrun"


def emit(name: str, us_per_call: float, derived) -> None:
    print(f"{name},{us_per_call:.1f},{derived}")


def missing_keys(path: str, required) -> list[str]:
    """Keys absent from a committed BENCH json (all of them if no file) —
    the shared --smoke guard check."""
    import json

    if not os.path.exists(path):
        return list(required)
    with open(path) as f:
        data = json.load(f)
    return [k for k in required if k not in data]


def trained_agent(zoo, window: int = 12, c_max: int = 4, episodes: int = 2000,
                  fast: bool = False, tag: str = "") -> tuple[DQNAgent, EnvConfig]:
    """Train (or load cached) DQN agent for a (window, c_max) setting."""
    if fast:
        episodes = min(episodes, 400)
    env_cfg = EnvConfig(window=window, c_max=c_max)
    env = CoScheduleEnv(env_cfg)
    cache = os.path.join(AGENT_DIR, f"w{window}_c{c_max}_e{episodes}{tag}")
    agent = DQNAgent(env.state_dim, env.n_actions, DQNConfig(), seed=0)
    try:
        tree, extra, _ = ck.restore(cache)
        import jax.numpy as jnp

        agent.params = {k: jnp.asarray(v) for k, v in tree["params"].items()}
        agent.target_params = agent.params
        agent.env_steps = int(extra.get("env_steps", 10**9))
        return agent, env_cfg
    except FileNotFoundError:
        pass
    t0 = time.time()
    agent, _ = train_agent(
        zoo, env_cfg,
        TrainConfig(episodes=episodes,
                    eval_every=max(100, episodes // 4),
                    dqn=DQNConfig(eps_decay_steps=max(1500, episodes * 7))),
    )
    ck.save(cache, episodes, {"params": agent.params}, extra={"env_steps": agent.env_steps},
            keep_last=1)
    emit(f"train_agent_w{window}", (time.time() - t0) * 1e6 / max(1, episodes), "cached")
    return agent, env_cfg


def rl_scheduler(zoo, window=12, c_max=4, fast=False, episodes=3000) -> tuple[RLScheduler, EnvConfig]:
    agent, env_cfg = trained_agent(zoo, window, c_max, episodes=episodes, fast=fast)
    return RLScheduler(agent, env_cfg), env_cfg

"""End-to-end behaviour tests for the paper's system.

The headline claim (paper Fig. 8): the RL co-scheduler produces valid
schedules whose throughput beats time sharing and approaches the exhaustive
oracle; plus a real end-to-end train loop with checkpoint/restart.
"""
import numpy as np
import jax
import jax.numpy as jnp
import pytest

from repro.core import (
    EnvConfig,
    POLICIES,
    RLScheduler,
    TrainConfig,
    make_zoo,
    paper_queues,
    summarize,
    train_agent,
    validate_schedule,
)
from repro.core.agent import DQNConfig


@pytest.fixture(scope="module")
def trained():
    zoo = make_zoo()
    env_cfg = EnvConfig(window=6, c_max=4)
    agent, hist = train_agent(
        zoo, env_cfg,
        TrainConfig(episodes=400, eval_every=200, n_train_queues=8,
                    dqn=DQNConfig(eps_decay_steps=2500)),
    )
    return zoo, env_cfg, agent


def test_rl_beats_time_sharing_and_respects_constraints(trained):
    zoo, env_cfg, agent = trained
    sched = RLScheduler(agent, env_cfg)
    queues = paper_queues(zoo, window=6, per_kind=1)
    tps = []
    for queue in queues.values():
        s = sched.schedule(queue)
        validate_schedule(queue, s, env_cfg.c_max)
        tps.append(summarize(s)["throughput"])
    assert float(np.mean(tps)) > 1.1, tps   # clearly better than time sharing


def test_rl_within_oracle_envelope(trained):
    zoo, env_cfg, agent = trained
    sched = RLScheduler(agent, env_cfg)
    queues = paper_queues(zoo, window=6, per_kind=1)
    for queue in queues.values():
        tp_rl = summarize(sched.schedule(queue))["throughput"]
        tp_or = summarize(POLICIES["oracle"](queue, env_cfg.c_max))["throughput"]
        assert tp_rl <= tp_or + 1e-6        # oracle is the upper bound


def test_training_improves_over_untrained(trained):
    zoo, env_cfg, agent = trained
    from repro.core import DQNAgent
    from repro.core.env import CoScheduleEnv

    env = CoScheduleEnv(env_cfg)
    fresh = DQNAgent(env.state_dim, env.n_actions, DQNConfig(), seed=123)
    queues = paper_queues(zoo, window=6, per_kind=1)
    tp_trained, tp_fresh = [], []
    for queue in queues.values():
        tp_trained.append(summarize(RLScheduler(agent, env_cfg).schedule(queue))["throughput"])
        tp_fresh.append(summarize(RLScheduler(fresh, env_cfg).schedule(queue))["throughput"])
    assert np.mean(tp_trained) >= np.mean(tp_fresh) - 0.05


def test_end_to_end_tiny_training_loop(tmp_path):
    """Real model + optimizer + data + checkpoint: loss decreases, resume works."""
    from repro.configs import get_smoke_config
    from repro.data import DataPipeline
    from repro.models.model import init_params, loss_fn
    from repro.optim import OptConfig, adamw_update, init_opt_state
    from repro import checkpoint as ck

    cfg = get_smoke_config("llama3-8b")
    pipe = DataPipeline(cfg.vocab_size, 32, 16, seed=0, mode="markov")
    params = init_params(cfg, jax.random.PRNGKey(0))
    opt = init_opt_state(params)
    opt_cfg = OptConfig(lr=5e-3, warmup_steps=5, decay_steps=300, clip_norm=1.0)

    @jax.jit
    def step(params, opt, batch):
        (total, metrics), grads = jax.value_and_grad(loss_fn, has_aux=True)(params, batch, cfg)
        params, opt, om = adamw_update(params, grads, opt, opt_cfg)
        return params, opt, metrics["loss"]

    losses = []
    for s in range(45):
        batch = {k: jnp.asarray(v) for k, v in pipe.batch(s).items()}
        params, opt, loss = step(params, opt, batch)
        losses.append(float(loss))
        if s == 19:
            ck.save(str(tmp_path), s, {"params": params}, extra={"data_step": s})
    assert min(losses[-5:]) < losses[0] - 0.25, losses[:3] + losses[-5:]

    # restart path: restore and continue deterministically
    tree, extra, s0 = ck.restore(str(tmp_path))
    assert s0 == 19 and extra["data_step"] == 19

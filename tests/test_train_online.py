"""Sim-in-the-loop training (`train_online`): stitching, PBT, updaters.

The fuzz suite (``test_queueing_reward``) pins the engine-side invariant
— buckets equal serving totals; this file covers the host-side machinery
built on top of it: transition stitching (reward attribution, terminal
handling, no-decision-window folding), the jitted update loop's target
sync, population-based training exploit/explore, the warm-start elitism
guard, config validation, and the retrainer's ``reward="queueing"``
branch.
"""
import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro.core import EnvConfig, make_zoo
from repro.core.agent import DQNAgent, DQNConfig
from repro.core.env import CoScheduleEnv
from repro.core.replay import replay_init, replay_push
from repro.core.train import (
    TrainOnlineConfig, _online_updater, _stitch_transitions, train_online,
)
from repro.online import (
    ClusterSimulator, OnlineRetrainer, TrainRollout, poisson_trace,
)
from repro.online.policies import RLDispatchPolicy
from repro.online.retrain import default_retrain_online_config

ZOO = make_zoo()
ENV_CFG = EnvConfig(window=4)
_ENV = CoScheduleEnv(ENV_CFG)


def _tiny_cfg(**kw):
    base = dict(rounds=2, traces_per_round=2, n_arrivals=16, capacity=64,
                window=4, population=1, eval_traces=2, updates_per_round=8,
                eps_decay_rounds=2, scenarios=(("poisson", 1.2),),
                dqn=DQNConfig(buffer_size=2048, batch_size=32,
                              eps_decay_steps=500))
    base.update(kw)
    return TrainOnlineConfig(**base)


# ------------------------------------------------------------- stitching

def _mk_roll(valid, w_wait, w_turn, n_act=4, d=3):
    a_cap, t_ep = valid.shape
    rng = np.random.default_rng(0)
    return TrainRollout(
        obs=rng.standard_normal((a_cap, t_ep, d)).astype(np.float32),
        act=rng.integers(0, n_act, (a_cap, t_ep)).astype(np.int32),
        mask=np.ones((a_cap, t_ep, n_act), bool),
        valid=valid, w_wait=np.asarray(w_wait, np.float32),
        w_turn=np.asarray(w_turn, np.float32))


def test_stitch_rewards_fold_and_terminate():
    valid = np.array([[1, 0], [0, 0], [1, 1], [1, 1]], bool)  # win3 unused
    roll = _mk_roll(valid, [10.0, 20.0, 30.0, 99.0], [0.0] * 4)
    cfg = TrainOnlineConfig(n_arrivals=10, wait_weight=1.0,
                            turnaround_weight=0.0, makespan_weight=1.0)
    tx = _stitch_transitions(roll, n_windows=3, makespan=50.0, cfg=cfg)
    assert len(tx["a"]) == 3
    # window 1 had no decisions: its bucket folds into window 0's last
    # decision; window 2's bucket + terminal makespan land on the close
    np.testing.assert_allclose(tx["r"], [-3.0, 0.0, -8.0], atol=1e-6)
    np.testing.assert_array_equal(tx["done"], [0.0, 0.0, 1.0])
    # s2 chains decisions across windows; the terminal row is zeros with
    # an all-False mask (the TD target's terminal encoding)
    np.testing.assert_array_equal(tx["s2"][0], tx["s"][1])
    assert not tx["s2"][-1].any() and not tx["mask2"][-1].any()
    np.testing.assert_array_equal(tx["s"][0], roll.obs[0, 0])
    np.testing.assert_array_equal(tx["s"][2], roll.obs[2, 1])
    assert tx["a"][1] == roll.act[2, 0]


def test_stitch_leading_windows_fold_forward():
    valid = np.array([[0, 0], [1, 0]], bool)
    roll = _mk_roll(valid, [5.0, 7.0], [1.0, 1.0])
    cfg = TrainOnlineConfig(n_arrivals=1, wait_weight=1.0,
                            turnaround_weight=2.0, makespan_weight=0.0)
    tx = _stitch_transitions(roll, n_windows=2, makespan=9.0, cfg=cfg)
    assert len(tx["a"]) == 1
    np.testing.assert_allclose(tx["r"], [-(5 + 7) - 2.0 * (1 + 1)],
                               atol=1e-5)


def test_stitch_no_decisions_returns_none():
    roll = _mk_roll(np.zeros((2, 2), bool), [1.0, 2.0], [0.0, 0.0])
    assert _stitch_transitions(roll, 2, 3.0, TrainOnlineConfig()) is None


# ---------------------------------------------------------- update engine

def test_online_updater_steps_and_syncs_target():
    d, n_act = 6, 3
    agent = DQNAgent(d, n_act, DQNConfig(batch_size=8, buffer_size=64),
                     seed=0)
    ring = replay_init(64, d, n_act)
    rng = np.random.default_rng(1)
    batch = {"s": jnp.asarray(rng.standard_normal((32, d)), jnp.float32),
             "a": jnp.asarray(rng.integers(0, n_act, 32), jnp.int32),
             "r": jnp.asarray(rng.standard_normal(32), jnp.float32),
             "s2": jnp.asarray(rng.standard_normal((32, d)), jnp.float32),
             "done": jnp.zeros(32, jnp.float32),
             "mask2": jnp.ones((32, n_act), bool)}
    ring = replay_push(ring, batch)
    upd = _online_updater(agent.cfg, n_updates=4, sync_updates=1, per=None)
    params, target, opt, ring2, _, updates = upd(
        agent.params, agent.target_params, agent.opt, ring,
        jax.random.PRNGKey(0), jnp.int32(0), jnp.float32(0.4))
    assert int(updates) == 4
    # params moved, and with sync every update the target tracks them
    assert any(not np.array_equal(np.asarray(a), np.asarray(b)) for a, b in
               zip(jax.tree.leaves(agent.params), jax.tree.leaves(params)))
    for a, b in zip(jax.tree.leaves(params), jax.tree.leaves(target)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


# ------------------------------------------------------------ train_online

def test_train_online_population_pbt_and_history():
    cfg = _tiny_cfg(rounds=4, population=3, pbt_interval=2,
                    scenarios=(("poisson", 1.2), ("mmpp", 1.3)))
    agent, hist = train_online(ZOO, ENV_CFG, cfg)
    assert len(hist) == 4
    assert all(len(r["scores"]) == 3 for r in hist)
    assert any("pbt" in r for r in hist)            # exploit/explore fired
    assert "selected" in hist[-1] and "final_scores" in hist[-1]
    assert all(np.isfinite(np.asarray(x)).all()
               for x in jax.tree.leaves(agent.params))


def test_train_online_deterministic():
    cfg = _tiny_cfg()
    a0, h0 = train_online(ZOO, ENV_CFG, cfg)
    a1, h1 = train_online(ZOO, ENV_CFG, cfg)
    for x, y in zip(jax.tree.leaves(a0.params), jax.tree.leaves(a1.params)):
        np.testing.assert_array_equal(np.asarray(x), np.asarray(y))
    assert [r["scores"] for r in h0] == [r["scores"] for r in h1]


def test_train_online_per_path():
    agent, hist = train_online(ZOO, ENV_CFG, _tiny_cfg(per_alpha=0.6))
    assert hist and np.isfinite(hist[-1]["best_p99"])


def test_train_online_warm_start_elitism_guard():
    warm = DQNAgent(_ENV.state_dim, _ENV.n_actions, seed=5)
    before = [np.asarray(x).copy() for x in jax.tree.leaves(warm.params)]
    cfg = _tiny_cfg(rounds=1, updates_per_round=2)
    agent, hist = train_online(ZOO, ENV_CFG, cfg, warm_start=warm)
    sel = hist[-1]["selected"]
    assert sel == "warm_start" or isinstance(sel, int)
    if sel == "warm_start":
        for x, y in zip(before, jax.tree.leaves(agent.params)):
            np.testing.assert_array_equal(x, np.asarray(y))
    # warm start copied, never donated
    for x, y in zip(before, jax.tree.leaves(warm.params)):
        np.testing.assert_array_equal(x, np.asarray(y))


def test_train_online_validates_config():
    with pytest.raises(ValueError, match="serve window"):
        train_online(ZOO, EnvConfig(window=4), _tiny_cfg(window=8))
    with pytest.raises(ValueError, match="unknown trace family"):
        train_online(ZOO, ENV_CFG,
                     _tiny_cfg(scenarios=(("nope", 1.0),)))


# --------------------------------------------------------------- retrainer

def test_retrainer_queueing_reward_refresh():
    trace = poisson_trace(ZOO, n=24, load=1.3, seed=7)
    agent = DQNAgent(_ENV.state_dim, _ENV.n_actions, seed=0)
    pol = RLDispatchPolicy(agent, ENV_CFG)
    ocfg = _tiny_cfg(rounds=1, updates_per_round=4)
    rt = OnlineRetrainer(policy=pol, reward="queueing", online_cfg=ocfg,
                         interval_s=trace[-1].t / 2.0, min_jobs=3)
    res = ClusterSimulator(pol, window=4, tick_interval_s=rt.interval_s,
                           on_tick=rt).run(trace)
    assert res.ticks >= 1 and len(rt.history) >= 1
    for h in rt.history:
        assert h["rounds"] >= 1
        assert np.isfinite(h["train_eval_p99_wait"])
        assert "train_eval_throughput" not in h


def test_retrainer_rejects_unknown_reward():
    pol = RLDispatchPolicy(
        DQNAgent(_ENV.state_dim, _ENV.n_actions, seed=0), ENV_CFG)
    with pytest.raises(ValueError, match="unknown reward"):
        OnlineRetrainer(policy=pol, reward="bogus")


def test_default_retrain_online_config_shape():
    cfg = default_retrain_online_config(rounds=5)
    assert cfg.rounds == 5 and cfg.population == 1
    assert cfg.eps_decay_rounds >= 1

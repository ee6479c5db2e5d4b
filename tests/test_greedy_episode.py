"""The heap serving path's greedy episode runs on the device in one call.

Contract: ``RLScheduler.schedule`` plans exactly what the scalar reference
loop (``CoScheduleEnv`` stepped with ``DQNAgent.act(greedy=True)``) plans,
group for group and partition for partition, with and without the
arrival-aware context; a hot swap to same-shaped parameters follows the new
parameters without compiling again; and an episode that does not finish
raises.
"""
import numpy as np
import pytest

from repro import spans
from repro.core import DQNAgent, DQNConfig, EnvConfig, RLScheduler, make_zoo
from repro.core import agent as agent_mod
from repro.core.env import CoScheduleEnv, DispatchContext
from repro.core.partition import N_UNITS
from repro.online import RLDispatchPolicy

ZOO = make_zoo()


def _agent(cfg: EnvConfig, seed: int) -> DQNAgent:
    env = CoScheduleEnv(cfg)
    return DQNAgent(env.state_dim, env.n_actions, DQNConfig(), seed=seed)


def _reference(agent: DQNAgent, cfg: EnvConfig, queue, context):
    """The old serving loop: the scalar env, one ``act`` round trip a step.
    Returns (schedule before the guard, steps)."""
    env = CoScheduleEnv(cfg)
    state, mask = env.reset(queue, context)
    steps = 0
    while not env.done:
        state, _, _, mask, _ = env.step(agent.act(state, mask, greedy=True))
        steps += 1
        assert steps < 10 * cfg.window
    return env.schedule, steps


def _key(sched):
    return ([[id(j) for j in g] for g in sched.groups],
            [p.label for p in sched.partitions])


def _context(rng, n: int) -> DispatchContext:
    return DispatchContext(
        free_units=tuple(bool(b) for b in rng.integers(0, 2, N_UNITS)),
        ages_s=tuple(float(a) for a in rng.exponential(300.0, n)),
        queue_depth=int(rng.integers(0, 40)),
        now_s=float(rng.uniform(0, 1e4)))


@pytest.fixture
def no_guard(monkeypatch):
    """Compare plans before the §IV-A guard, which could hide a difference
    by splitting both sides' groups into the same solos."""
    monkeypatch.setattr(RLScheduler, "_enforce_constraints",
                        lambda self, sched: sched)


@pytest.mark.parametrize("window,c_max,obs_context,seed", [
    (8, 4, False, 0), (8, 4, False, 1), (8, 4, False, 2), (8, 4, False, 3),
    (8, 4, True, 0), (8, 4, True, 1), (8, 4, True, 2), (8, 4, True, 3),
    (5, 3, False, 4), (5, 3, True, 5), (4, 2, False, 6), (3, 1, True, 7),
])
def test_device_episode_plans_what_the_scalar_loop_plans(
        no_guard, window, c_max, obs_context, seed):
    cfg = EnvConfig(window=window, c_max=c_max, obs_context=obs_context)
    agent = _agent(cfg, seed)
    sched = RLScheduler(agent, cfg)
    rng = np.random.default_rng(seed)
    spans.enable()
    try:
        for n in [*range(1, window + 1), window, window]:
            queue = [ZOO[i] for i in rng.integers(0, len(ZOO), n)]
            for context in ((None, _context(rng, n)) if obs_context
                            else (None,)):
                want, steps = _reference(agent, cfg, queue, context)
                spans.reset()
                got = sched.schedule(queue, context)
                assert _key(got) == _key(want), (n, context)
                assert spans.counters()["repro.sched.steps"] == [steps]
    finally:
        spans.disable()
        spans.reset()


def test_hot_swap_follows_new_params_without_compiling(no_guard):
    cfg = EnvConfig(window=8, c_max=4)
    old, new = _agent(cfg, 11), _agent(cfg, 12)
    policy = RLDispatchPolicy(old, cfg)
    rng = np.random.default_rng(5)
    queues = [[ZOO[i] for i in rng.integers(0, len(ZOO), 8)]
              for _ in range(6)]
    before = [_key(policy.scheduler.schedule(q)) for q in queues]
    assert before == [_key(_reference(old, cfg, q, None)[0]) for q in queues]
    compiled = agent_mod._greedy_episode._cache_size()
    policy.hot_swap(new)
    after = [_key(policy.scheduler.schedule(q)) for q in queues]
    assert agent_mod._greedy_episode._cache_size() == compiled
    assert after == [_key(_reference(new, cfg, q, None)[0]) for q in queues]
    assert after != before


def test_an_episode_that_does_not_finish_raises(monkeypatch):
    cfg = EnvConfig(window=4, c_max=3)
    sched = RLScheduler(_agent(cfg, 0), cfg)
    real = agent_mod._greedy_episode

    def unfinished(*args, **kwargs):
        return real(*args, **kwargs).at[-1].set(0)

    monkeypatch.setattr(agent_mod, "_greedy_episode", unfinished)
    with pytest.raises(RuntimeError, match="failed to terminate"):
        sched.schedule(list(ZOO[:4]))

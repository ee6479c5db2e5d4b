"""RL environment + agent invariants."""
import numpy as np
import jax.numpy as jnp

try:
    from hypothesis import given, settings, strategies as st
except ImportError:
    from _hypothesis_compat import given, settings, st

from repro.core import EnvConfig, make_zoo, validate_schedule
from repro.core.agent import DQNAgent, DQNConfig, _dqn_update
from repro.core.env import CoScheduleEnv
from repro.core.network import dqn_apply, init_dqn, masked_argmax

ZOO = make_zoo()


def _queue(n=6, seed=0):
    rng = np.random.default_rng(seed)
    idx = rng.choice(len(ZOO), size=n, replace=False)
    return [ZOO[i] for i in idx]


@given(seed=st.integers(0, 2**30))
@settings(max_examples=15)
def test_env_random_episode_is_valid(seed):
    """Any mask-respecting action sequence terminates in a valid schedule."""
    env_cfg = EnvConfig(window=6, c_max=4)
    env = CoScheduleEnv(env_cfg)
    queue = _queue(6, seed)
    state, mask = env.reset(queue)
    rng = np.random.default_rng(seed)
    steps = 0
    while not env.done:
        assert mask.any(), "valid action must always exist"
        a = int(rng.choice(np.flatnonzero(mask)))
        state, r, done, mask, _ = env.step(a)
        assert np.isfinite(r)
        steps += 1
        assert steps < 100
    assert state.shape == (env.state_dim,)
    validate_schedule(queue, env.schedule, 4, enforce_solo_constraint=False)


def test_env_state_layout():
    env_cfg = EnvConfig(window=6, c_max=4)
    env = CoScheduleEnv(env_cfg)
    state, mask = env.reset(_queue(4))  # 2 padding slots
    s = state.reshape(6, -1)
    assert np.all(s[4:, env.n_features + 3] == 1.0)  # padding flag
    assert np.all(s[:4, env.n_features + 0] == 1.0)  # available flag
    # padded slots are never selectable
    assert not mask[4] and not mask[5]


def test_mask_forbids_oversized_groups():
    env_cfg = EnvConfig(window=6, c_max=2)
    env = CoScheduleEnv(env_cfg)
    _, mask = env.reset(_queue(6))
    env.step(0)
    _, _, _, mask, _ = env.step(1)
    # group is at c_max=2: no more job selections allowed
    assert not mask[: env.cfg.window].any()
    # only arity-2 partitions closable
    for i, p in enumerate(env.partitions):
        assert mask[env.cfg.window + i] == (p.arity == 2)


def test_masked_argmax():
    q = jnp.array([[1.0, 5.0, 3.0]])
    mask = jnp.array([[True, False, True]])
    assert int(masked_argmax(q, mask)[0]) == 2


def test_masked_argmax_tie_takes_lowest_valid_index():
    """Exact Q ties must resolve to the first valid action, deterministically."""
    q = jnp.array([[2.0, 7.0, 7.0, 7.0]])
    mask = jnp.array([[True, False, True, True]])
    assert int(masked_argmax(q, mask)[0]) == 2
    # all-equal rows: the first *valid* index wins
    q0 = jnp.zeros((1, 4))
    assert int(masked_argmax(q0, mask)[0]) == 0
    assert int(masked_argmax(q0, jnp.array([[False, False, True, True]]))[0]) == 2
    assert int(masked_argmax(q0, jnp.ones((1, 4), bool))[0]) == 0


def test_dqn_shapes_and_dueling():
    import jax

    params = init_dqn(jax.random.PRNGKey(0), 20, 7)
    q = dqn_apply(params, jnp.zeros((3, 20)))
    assert q.shape == (3, 7)
    # dueling head: mean-advantage subtraction -> adding a constant to A
    # leaves Q invariant; check V contributes uniformly
    q1 = dqn_apply(params, jnp.ones((1, 20)))
    assert bool(jnp.isfinite(q1).all())


def test_dqn_update_reduces_td_loss():
    cfg = DQNConfig(lr=1e-2)
    agent = DQNAgent(10, 4, cfg, seed=0)
    rng = np.random.default_rng(0)
    batch = {
        "s": jnp.asarray(rng.normal(size=(64, 10)), jnp.float32),
        "a": jnp.asarray(rng.integers(0, 4, 64), jnp.int32),
        "r": jnp.asarray(rng.normal(size=64), jnp.float32),
        "s2": jnp.asarray(rng.normal(size=(64, 10)), jnp.float32),
        "done": jnp.ones((64,), jnp.float32),   # terminal: y = r (fixed target)
        "mask2": jnp.ones((64, 4), bool),
    }
    params, opt = agent.params, agent.opt
    losses = []
    for _ in range(60):
        params, opt, loss = _dqn_update(params, agent.target_params, opt, batch, cfg)
        losses.append(float(loss))
    assert losses[-1] < 0.3 * losses[0], (losses[0], losses[-1])


def test_agent_act_respects_mask():
    agent = DQNAgent(10, 5, DQNConfig(eps_start=0.0, eps_end=0.0), seed=0)
    mask = np.array([False, True, False, True, False])
    for _ in range(10):
        a = agent.act(np.zeros(10, np.float32), mask)
        assert mask[a]


def test_greedy_act_does_not_advance_epsilon_schedule():
    """Evaluation (greedy) calls must not consume ε-decay env steps."""
    agent = DQNAgent(10, 5, DQNConfig(eps_decay_steps=100), seed=0)
    mask = np.ones(5, bool)
    eps0 = agent.epsilon
    for _ in range(20):
        agent.act(np.zeros(10, np.float32), mask, greedy=True)
    assert agent.env_steps == 0 and agent.epsilon == eps0
    agent.act(np.zeros(10, np.float32), mask)          # exploration step
    assert agent.env_steps == 1 and agent.epsilon < eps0


def test_replay_cycles():
    from repro.core.replay import ReplayBuffer

    rb = ReplayBuffer(8, 3, 2, seed=0)
    for i in range(20):
        rb.push(np.full(3, i, np.float32), 0, 1.0, np.zeros(3), False, np.ones(2, bool))
    assert len(rb) == 8
    batch = rb.sample(4)
    assert batch["s"].shape == (4, 3)
    assert batch["s"].max() >= 12  # only recent entries survive

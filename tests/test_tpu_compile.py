"""Compile-only checks of the device programs for a described TPU v5e.

The TPU compiler is installed alongside JAX, so each jitted program of the
main path can be lowered and compiled for a ``v5e:2x2`` topology that is
*described*, not attached: no chip is touched and nothing runs.  What this
catches is what the CPU backend and Pallas interpret mode cannot — tiling
and VMEM refusals in a kernel, a program that does not fit the device.

The topology is described inside a module fixture (never at import, in a
``skipif`` or in ``conftest.py``): only one process may load the TPU
library, so under several pytest workers only the worker that runs this
file loads it, and every worker still collects the same tests.  Keep all
such tests in this one file.  The persistent compile cache is switched off
around them, since an entry compiled for a described chip cannot be read
back without one.
"""
import os

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import SingleDeviceSharding

from repro.core import CoScheduleEnv, DQNAgent, EnvConfig, TrainConfig, make_zoo
from repro.core.partition import N_UNITS
from repro.core.perfmodel_jax import stack_queues
from repro.core.replay import replay_init
from repro.core.train import _Carry, _engine_for
from repro.core.workloads import QUEUE_KINDS, make_queue
from repro.online import (
    RLDispatchPolicy, SimConfig, TRACE_FAMILIES, TimeSharingPolicy,
    VectorizedClusterSimulator, VectorizedFleetSimulator,
)
from repro.online.vecsim import (
    build_job_table, build_rl_job_table, compile_trace, hash_split_max,
)

ZOO = make_zoo()
ENV_CFG = EnvConfig(window=8, c_max=4)


@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache as cc
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler / library lock held elsewhere
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)
    cc.reset_cache()


def _sds(tree, sharding):
    """Abstract arguments on the described chip (shapes + dtypes only)."""
    return jax.tree.map(
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=sharding),
        tree)


def _stacked(traces, capacity):
    names, jobs = {}, []
    compiled = [compile_trace(t, capacity, names, jobs)[0] for t in traces]
    return jax.tree.map(lambda *xs: jnp.stack(xs), *compiled), jobs


def _poisson(n_traces, n):
    return [TRACE_FAMILIES["poisson"](ZOO, n=n, load=1.25, seed=i)
            for i in range(n_traces)]


def _agent():
    env = CoScheduleEnv(ENV_CFG)
    return DQNAgent(env.state_dim, env.n_actions, seed=0)


def _compiled_ok(compiled):
    mem = compiled.memory_analysis()
    assert mem.temp_size_in_bytes + mem.argument_size_in_bytes < 16 * 2**30
    return compiled


def test_time_sharing_sweep_compiles(one_chip):
    batch, jobs = _stacked(_poisson(64, 120), 128)
    vec = VectorizedClusterSimulator(TimeSharingPolicy(), window=8,
                                     capacity=128)
    _compiled_ok(vec._sweepfn.lower(
        *_sds((batch, build_job_table(jobs)), one_chip)).compile())


def test_rl_sweep_compiles(one_chip):
    batch, jobs = _stacked(_poisson(64, 120), 128)
    vec = VectorizedClusterSimulator(RLDispatchPolicy(_agent(), ENV_CFG),
                                     window=8, capacity=128)
    args = (batch, build_rl_job_table(jobs), vec.policy.agent.params)
    _compiled_ok(vec._sweepfn.lower(*_sds(args, one_chip)).compile())


def test_rl_fleet_compiles(one_chip):
    # lane capacity of chip_smoke.py's fleet phase: the largest hash-routed
    # pod share of a 10^4-arrival Poisson trace at load 0.85
    pods = (8, 8, 8, 8)
    capacity = hash_split_max(TRACE_FAMILIES["poisson"](
        ZOO, n=10_000, load=0.85, seed=0, capacity=sum(pods) / N_UNITS), pods)
    batch, jobs = _stacked(_poisson(len(pods), 64), capacity)
    fleet = VectorizedFleetSimulator(
        RLDispatchPolicy(_agent(), ENV_CFG),
        SimConfig(window=8, pods=pods, router="hash"), capacity=capacity)
    params = jax.tree.map(lambda x: jnp.stack([x] * len(pods)),
                          fleet.policy.agent.params)
    args = (batch, build_rl_job_table(jobs), params,
            jnp.asarray(np.array(pods, np.int32)))
    _compiled_ok(fleet._runp.lower(*_sds(args, one_chip)).compile())


@pytest.mark.parametrize("obs_context", [False, True])
def test_heap_episode_compiles_to_f32_vector_products(one_chip, obs_context):
    """The heap serving path's episode program: its single-observation
    products lower to f32 vector code like ``_greedy_action``'s, never to
    MXU convolutions, which at default precision take bfloat16 passes."""
    from repro.core.agent import _greedy_episode
    from repro.core.env import context_dim
    from repro.core.profiles import FEATURES

    cfg = EnvConfig(window=8, c_max=4, obs_context=obs_context)
    env = CoScheduleEnv(cfg)
    agent = DQNAgent(env.state_dim, env.n_actions, seed=0)
    W = cfg.window
    packed = np.zeros((W * len(FEATURES) + W + context_dim(cfg),), np.float32)
    compiled = _compiled_ok(_greedy_episode.lower(
        *_sds((agent.params, packed), one_chip), window=W, c_max=cfg.c_max,
        obs_context=obs_context).compile())
    assert "convolution" not in compiled.as_text()


def test_train_agent_segment_compiles(one_chip):
    # the default TrainConfig's cadence, as train_agent derives it: 16 envs
    # and one update per 16 transitions -> one update per scan step
    cfg = TrainConfig()
    B = cfg.batch_envs
    assert B * cfg.updates_per_step == cfg.update_every
    sync = max(1, round(cfg.dqn.target_sync / B))
    venv, engine, _ = _engine_for(ENV_CFG, cfg.dqn, B, 1, 1, sync, None)
    rng = np.random.default_rng(0)
    qa = stack_queues([venv.queue_arrays(make_queue(
        ZOO, QUEUE_KINDS[i % len(QUEUE_KINDS)], ENV_CFG.window, rng))
        for i in range(B)])
    env, obs, mask = venv.reset_batch(qa)
    agent = DQNAgent(venv.state_dim, venv.n_actions, cfg.dqn)
    capacity = -(-cfg.dqn.buffer_size // B) * B
    replay = jax.eval_shape(
        lambda: replay_init(capacity, venv.state_dim, venv.n_actions))
    carry = _Carry(env=env, obs=obs, mask=mask, reset_env=env,
                   reset_obs=obs, reset_mask=mask, params=agent.params,
                   target=agent.target_params, opt=agent.opt, replay=replay,
                   key=jax.random.PRNGKey(0), env_steps=jnp.int32(0),
                   updates=jnp.int32(0), ep_ret=jnp.zeros((B,), jnp.float32))
    _compiled_ok(engine.lower(_sds(carry, one_chip), 64).compile())


# one zoo width per kernel: llama3-8b (32 query heads, 8 kv heads, d_head
# 128, d_model 4096) at its train_4k / decode_32k shapes
def _flash(S):
    from repro.kernels.flash_attention.ops import flash_attention
    return flash_attention.lower(
        S((1, 4096, 32, 128)), S((1, 4096, 8, 128)), S((1, 4096, 8, 128)),
        impl="kernel", interpret=False)


def _decode(S):
    from repro.kernels.decode_attention.ops import decode_attention
    return decode_attention.lower(
        S((8, 32, 128)), S((8, 32768, 8, 128)), S((8, 32768, 8, 128)),
        S((8,), jnp.int32), impl="kernel", interpret=False)


def _rmsnorm(S):
    from repro.kernels.rmsnorm.ops import rmsnorm
    return rmsnorm.lower(S((4, 4096, 4096)), S((4096,)), impl="kernel",
                         interpret=False)


@pytest.mark.parametrize("lower", [_flash, _decode, _rmsnorm],
                         ids=["flash_attention", "decode_attention",
                              "rmsnorm"])
def test_pallas_kernel_compiles(one_chip, lower):
    def S(shape, dtype=jnp.bfloat16):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)
    compiled = _compiled_ok(lower(S).compile())
    assert "tpu_custom_call" in compiled.as_text()

"""Regression: proxy-reward training is bit-unchanged by the queueing path.

The sim-in-the-loop additions (``train_online``, the ``train=True`` engine
mode, the retrainer's ``reward="queueing"`` branch) must be invisible to
the classic offline path: ``train_agent`` with the new code merely
*imported* has to produce bit-identical parameter trajectories to the
pre-PR code (the same pattern as the telemetry-off identity test — a
static flag that is off compiles the exact old program).

Two layers:

* an always-on determinism check — two fresh runs in this process agree
  bit-for-bit, and a run made *after* exercising ``train_online`` still
  agrees (no hidden global state leaks from the new machinery);
* a golden-checkpoint check against ``tests/golden/`` — params/targets
  captured from the pre-PR tree under a pinned tiny config.  Bit-exact
  float reproducibility only holds on the recorded jax version, backend,
  and x64 mode, so mismatching environments skip with a message rather
  than fail (CI pins all three).
"""
import json
import pathlib

import numpy as np
import pytest

import jax

from repro.core import EnvConfig, TrainConfig, make_zoo, train_agent
from repro.core.agent import DQNConfig
from repro.core.train import TrainOnlineConfig, train_online

ZOO = make_zoo()
GOLDEN = pathlib.Path(__file__).parent / "golden" / "train_agent_proxy_v1.npz"


def _pinned_cfg():
    env_cfg = EnvConfig(window=4)
    cfg = TrainConfig(episodes=24, eval_every=12, seed=7, batch_envs=4,
                      update_every=4, n_train_queues=4, n_heldout_queues=2,
                      dqn=DQNConfig(eps_decay_steps=200, buffer_size=2048,
                                    batch_size=32, target_sync=100))
    return env_cfg, cfg


def _leaves(agent):
    return ([np.asarray(x) for x in jax.tree.leaves(agent.params)],
            [np.asarray(x) for x in jax.tree.leaves(agent.target_params)])


def test_train_agent_deterministic_and_unaffected_by_train_online():
    env_cfg, cfg = _pinned_cfg()
    a0, h0 = train_agent(ZOO, env_cfg, cfg)
    # exercise the new path in between: it must not perturb a rerun
    ocfg = TrainOnlineConfig(rounds=1, traces_per_round=2, n_arrivals=12,
                             capacity=64, population=1, eval_traces=2,
                             updates_per_round=4, window=4,
                             scenarios=(("poisson", 1.2),))
    train_online(ZOO, EnvConfig(window=4), ocfg)
    a1, h1 = train_agent(ZOO, env_cfg, cfg)
    for x, y in zip(*map(lambda a: sum(_leaves(a), []), (a0, a1))):
        np.testing.assert_array_equal(x, y)
    for r0, r1 in zip(h0, h1):
        assert r0["eval_throughput"] == r1["eval_throughput"]
        assert r0["ep_reward"] == r1["ep_reward"]


def test_train_agent_matches_pre_pr_golden_checkpoint():
    with np.load(GOLDEN, allow_pickle=False) as z:
        meta = json.loads(str(z["meta"]))
        golden = {k: z[k] for k in z.files if k != "meta"}
    env = {"jax": jax.__version__, "backend": jax.default_backend(),
           "x64": bool(jax.config.jax_enable_x64)}
    pinned = {k: meta[k] for k in env}
    if env != pinned:
        pytest.skip(f"golden pinned to {pinned}, running {env}: bit-exact "
                    f"float reproducibility is only defined on the "
                    f"recorded stack")
    env_cfg, cfg = _pinned_cfg()
    agent, hist = train_agent(ZOO, env_cfg, cfg)
    params, targets = _leaves(agent)
    for i, x in enumerate(params):
        np.testing.assert_array_equal(x, golden[f"param_{i}"], err_msg=(
            f"param leaf {i} drifted from the pre-PR checkpoint — the "
            f"proxy-reward path is no longer bit-unchanged"))
    for i, x in enumerate(targets):
        np.testing.assert_array_equal(x, golden[f"target_{i}"],
                                      err_msg=f"target leaf {i} drifted")
    assert [h["eval_throughput"] for h in hist] == meta["eval_throughput"]
    assert [h["ep_reward"] for h in hist] == meta["ep_reward"]
    assert [h["heldout_throughput"] for h in hist] \
        == meta["heldout_throughput"]


def _write_golden(path=GOLDEN):
    """Record the pinned run's params/targets/history on the current stack."""
    env_cfg, cfg = _pinned_cfg()
    agent, hist = train_agent(ZOO, env_cfg, cfg)
    params, targets = _leaves(agent)
    meta = {"jax": jax.__version__, "backend": jax.default_backend(),
            "x64": bool(jax.config.jax_enable_x64),
            "machine": __import__("platform").machine(),
            **{k: [h[k] for h in hist]
               for k in ("eval_throughput", "heldout_throughput", "ep_reward")}}
    arrays = {f"param_{i}": x for i, x in enumerate(params)}
    arrays.update({f"target_{i}": x for i, x in enumerate(targets)})
    np.savez(path, meta=json.dumps(meta), **arrays)
    print(f"wrote {path}: {meta}")


if __name__ == "__main__":
    # Regenerate from a tree whose training path is known-good, on the CPU:
    #   JAX_PLATFORMS=cpu PYTHONPATH=src python tests/test_train_regression.py
    _write_golden()

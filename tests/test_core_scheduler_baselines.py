"""Scheduler + baseline policy invariants."""
import numpy as np
import pytest

from repro.core import (
    DQNAgent,
    DQNConfig,
    EnvConfig,
    POLICIES,
    RLScheduler,
    make_zoo,
    summarize,
    validate_schedule,
)
from repro.core.env import CoScheduleEnv
from repro.core.profiles import ProfileRepository
from repro.core.workloads import make_queue

ZOO = make_zoo()
RNG = np.random.default_rng(0)
QUEUE = make_queue(ZOO, "balanced", 6, RNG)


def _fresh_agent(env_cfg):
    env = CoScheduleEnv(env_cfg)
    return DQNAgent(env.state_dim, env.n_actions, DQNConfig(), seed=0)


def test_time_sharing_is_identity():
    sched = POLICIES["time_sharing"](QUEUE, 4)
    s = summarize(sched)
    assert abs(s["throughput"] - 1.0) < 1e-9
    assert abs(s["avg_slowdown"] - 1.0) < 1e-9
    assert abs(s["fairness"] - 1.0) < 1e-9


@pytest.mark.parametrize("policy", ["mig_only", "mps_only", "mig_mps_default", "oracle"])
def test_baselines_valid_and_no_worse_than_time_sharing(policy):
    sched = POLICIES[policy](QUEUE, 4)
    validate_schedule(QUEUE, sched, 4)
    assert summarize(sched)["throughput"] >= 1.0 - 1e-9


def test_oracle_dominates_restricted_policies():
    tp = {p: summarize(POLICIES[p](QUEUE, 4))["throughput"]
          for p in ("mig_only", "mps_only", "mig_mps_default", "oracle")}
    for p in ("mig_only", "mps_only", "mig_mps_default"):
        assert tp["oracle"] >= tp[p] - 1e-9, tp


def test_untrained_rl_scheduler_still_valid():
    """Even an untrained agent must emit constraint-respecting schedules
    (the constraint guard enforces CoRunTime <= SoloRunTime)."""
    env_cfg = EnvConfig(window=6, c_max=4)
    sched = RLScheduler(_fresh_agent(env_cfg), env_cfg).schedule(QUEUE)
    validate_schedule(QUEUE, sched, 4)


def test_scheduler_online_protocol_unprofiled_jobs_run_solo():
    env_cfg = EnvConfig(window=6, c_max=4)
    repo = ProfileRepository()
    repo.insert("/bin/jobA", QUEUE[0])
    repo.insert("/bin/jobB", QUEUE[1])
    sched_obj = RLScheduler(_fresh_agent(env_cfg), env_cfg, repo)
    subs = [("/bin/jobA", None), ("/bin/jobB", None), ("/bin/new", QUEUE[2])]
    sched = sched_obj.schedule_submissions(subs)
    # the unknown job ran solo and entered the repository
    assert sched_obj.stats.unprofiled_jobs == 1
    assert repo.lookup("/bin/new") is not None
    names = [j.name for g in sched.groups for j in g]
    assert QUEUE[2].name in names


def test_schedule_submissions_fresh_none_is_skipped_but_counted():
    """An unprofiled job with no fresh measurement cannot run (nothing to
    schedule) — it is counted, not silently dropped into the schedule."""
    env_cfg = EnvConfig(window=6, c_max=4)
    sched_obj = RLScheduler(_fresh_agent(env_cfg), env_cfg)
    sched = sched_obj.schedule_submissions([("/bin/ghost", None)])
    assert sched.groups == []
    assert sched_obj.stats.unprofiled_jobs == 1
    assert len(sched_obj.repository) == 0


def test_schedule_submissions_unprofiled_runs_solo_full_pod():
    env_cfg = EnvConfig(window=6, c_max=4)
    sched_obj = RLScheduler(_fresh_agent(env_cfg), env_cfg)
    sched = sched_obj.schedule_submissions([("/bin/new", QUEUE[0])])
    assert len(sched.groups) == 1 and len(sched.groups[0]) == 1
    p = sched.partitions[0]
    assert p.arity == 1 and p.slices[0].units == 8     # full pod, solo
    assert sched_obj.repository.lookup("/bin/new") is QUEUE[0]


def test_schedule_submissions_chunks_oversized_windows():
    """More profiled jobs than W run as successive RL windows, all covered."""
    env_cfg = EnvConfig(window=4, c_max=3)
    repo = ProfileRepository()
    subs = []
    for i in range(10):
        repo.insert(f"/bin/j{i}", QUEUE[i % len(QUEUE)])
        subs.append((f"/bin/j{i}", None))
    sched_obj = RLScheduler(_fresh_agent(env_cfg), env_cfg, repo)
    sched = sched_obj.schedule_submissions(subs)
    assert sched_obj.stats.windows == 3                # ceil(10 / 4)
    assert sched_obj.stats.unprofiled_jobs == 0
    assert sum(len(g) for g in sched.groups) == 10
    for g, p in zip(sched.groups, sched.partitions):
        assert len(g) == p.arity <= 3


def test_scheduler_shares_caller_repository_even_when_empty():
    """Regression: an empty repository is falsy — `or` used to replace it,
    severing the caller's handle to the shared profile store."""
    env_cfg = EnvConfig(window=6, c_max=4)
    repo = ProfileRepository()
    sched_obj = RLScheduler(_fresh_agent(env_cfg), env_cfg, repo)
    assert sched_obj.repository is repo
    sched_obj.schedule_submissions([("/bin/a", QUEUE[0])])
    assert "/bin/a" in repo


def test_enforce_constraints_counts_fallback_groups():
    """A group whose co-run loses to time sharing is split back into solo
    runs and tallied in stats.fallback_groups."""
    from repro.core.partition import enumerate_partitions
    from repro.core.perfmodel import corun_time, solo_run_time
    from repro.core.problem import Schedule

    bad = None
    for p in (q for q in enumerate_partitions(4) if q.arity == 2):
        for i in range(len(ZOO)):
            for j in range(i, len(ZOO)):
                g = [ZOO[i], ZOO[j]]
                if corun_time(g, p) > solo_run_time(g):
                    bad = (g, p)
                    break
            if bad:
                break
        if bad:
            break
    assert bad is not None, "zoo has no losing co-run pair to test with"
    env_cfg = EnvConfig(window=6, c_max=4)
    sched_obj = RLScheduler(_fresh_agent(env_cfg), env_cfg)
    raw = Schedule()
    raw.add(*bad)
    out = sched_obj._enforce_constraints(raw)
    assert sched_obj.stats.fallback_groups == 1
    assert [len(g) for g in out.groups] == [1, 1]
    assert all(p.arity == 1 for p in out.partitions)


def test_best_for_group_defaults_to_full_permutation_sweep():
    """The oracle's per-group search must cover all C! slot orderings —
    a truncated sweep (the old max_perms=8) is not an upper bound."""
    import itertools

    from repro.core.baselines import _best_for_group
    from repro.core.partition import enumerate_partitions
    from repro.core.perfmodel import corun_time

    group = [ZOO[i] for i in (0, 12, 20, 25)]     # mixed CI/MI/US 4-group
    parts = [p for p in enumerate_partitions(4) if p.arity == 4]
    t_default, p_default, _ = _best_for_group(group, parts)
    brute = min(
        corun_time([group[i] for i in perm], p)
        for p in parts
        for perm in itertools.permutations(range(4))
    )
    assert t_default == brute
    # a truncated sweep can only be worse or equal
    t_trunc, _, _ = _best_for_group(group, parts, max_perms=1)
    assert t_default <= t_trunc


def test_window_scaling_monotone_for_oracle():
    """Paper Fig. 9: more window -> no less throughput (oracle)."""
    rng = np.random.default_rng(1)
    q4 = make_queue(ZOO, "balanced", 4, rng)
    q8 = q4 + make_queue(ZOO, "balanced", 4, rng)
    tp4 = summarize(POLICIES["oracle"](q4, 4))["throughput"]
    tp8 = summarize(POLICIES["oracle"](q8, 4))["throughput"]
    assert tp8 >= tp4 * 0.9  # larger window has at least comparable headroom

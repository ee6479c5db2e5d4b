"""Online cluster serving: event-driven multi-tenant arrivals + re-training.

This package turns the offline queue solver into a system that serves
traffic over simulated time — the paper's §IV-B online phase under
MISO-style multi-tenant dynamics.

Event model
-----------
:class:`~repro.online.simulator.ClusterSimulator` advances a single event
heap of ``ARRIVE`` / ``TICK`` / ``FREE`` events.  Submissions queue FCFS;
whenever slice units are idle and the dispatched-group queue has drained,
the head window (up to W submissions) is handed to a
:class:`~repro.online.policies.DispatchPolicy` as ``(binary, profile)``
pairs.  First-sight binaries run solo while being profiled and enter the
:class:`~repro.core.profiles.ProfileRepository`; profiled jobs are
co-scheduled into hierarchically partitioned groups.  The policy's
width-fitted :class:`~repro.core.scheduler.Placement`\\ s are first-fitted
onto disjoint aligned slice-unit ranges, so independent groups run
**concurrently**; a blocked head reserves its earliest feasible start and
an EASY-backfill scan lets small later groups jump into idle gaps without
delaying it.  Each group's FREE event is keyed by its claimed slice
ranges.  Per-job wait/turnaround, cluster makespan/throughput/utilization,
and slice-level fragmentation metrics (idle-slice fraction, per-slice
utilization timeline) land in a
:class:`~repro.online.simulator.SimResult`.  Everything is deterministic
given the trace seed.

Fleet serving
-------------
:class:`~repro.online.simulator.SimConfig` scales the same event model to
an N-pod fleet with heterogeneous slice widths: a
:class:`~repro.online.router.Router` (hash / least-loaded /
fragmentation-scored) assigns each arrival a pod at its arrival instant,
and the whole dispatch path above runs per pod — claims never span pods.
``SimConfig(pods=(8,),...)`` (the default) is the single-pod cluster of
earlier PRs, bit-compatible with it.  The hash-routed fleet also runs on
the vectorized engine
(:class:`~repro.online.vecsim.VectorizedFleetSimulator`) as one vmapped
pod axis — hash routing is trace-computable, so the fleet decomposes into
independent per-pod lanes.  Both vectorized engines serve time-sharing
*and* RL plans: an :class:`~repro.online.policies.RLDispatchPolicy`'s
agent episodes run in-graph at the window-formation seam (observation
assembly + fit-masked greedy argmax, ``docs/architecture.md``), and
``sweep(param_sets=...)`` evaluates a population of agents in one device
call.

Traces ↔ paper workload mix
---------------------------
:mod:`repro.online.traces` generates arrival processes (Poisson, bursty
MMPP, diurnal, heavy-tailed job scales, fragmentation-stressing
right-sized slice requests) whose per-arrival job draw follows the paper's
§V-A2 queue recipes: ``mix="ci"|"mi"|"us"`` weights the dominant class at
50% (the CI/MI/US-dominant queue categories of Table V),
``mix="balanced"`` draws classes uniformly.  A trace is therefore the
streaming analogue of the paper's static queue families.

Arrival-aware observations
--------------------------
Every dispatch window hands the policy a
:class:`~repro.core.env.DispatchContext` — free-unit mask, per-submission
ages, pending depth at the dispatch instant.  An RL policy whose
environment has ``EnvConfig.obs_context`` set folds that snapshot into the
agent's observation (the context block of ``docs/observation.md``), so the
policy plans from *profiles + live cluster state*; all other policies, and
context-blind agents, ignore it bit-compatibly.

Re-training
-----------
:class:`~repro.online.retrain.OnlineRetrainer` hangs off the simulator's
periodic tick: every K simulated minutes it re-trains the agent on the live
repository (warm-started from current params via ``train_agent(...,
warm_start=...)``) and hot-swaps the refreshed agent into the RL dispatch
policy.  With ``trigger="drift"`` a
:class:`~repro.online.telemetry.DriftMonitor` gates each tick on
arrival-mix entropy and idle-fraction shifts instead of retraining
unconditionally.

Telemetry
---------
:mod:`repro.online.telemetry` is the observability layer
(``docs/observability.md``): pass ``telemetry=Telemetry()`` to
:class:`~repro.online.simulator.ClusterSimulator` (or ``telemetry=True``
to the vectorized engines) for lifecycle event traces (JSONL /
Perfetto-loadable Chrome trace), a streaming metrics registry, and
windowed time series via
:meth:`~repro.online.simulator.SimResult.timeseries`.  Telemetry observes
and never steers: disabled runs are bit-identical, enabled runs change no
decision.
"""
from repro.online.policies import (
    DispatchPolicy, GreedyPackerPolicy, PolicyStats, RLDispatchPolicy,
    StaticPartitionPolicy, TimeSharingPolicy,
)
from repro.online.retrain import (
    OnlineRetrainer, default_retrain_online_config,
    default_retrain_train_config,
)
from repro.online.router import (
    FleetView, FragRouter, HashRouter, LeastLoadedRouter, PodView, ROUTERS,
    Router, make_router,
)
from repro.online.simulator import (
    Arrival, ClusterSimulator, JobRecord, Segment, SimConfig, SimResult,
)
from repro.online.telemetry import (
    DriftMonitor, MetricsRegistry, Telemetry, TraceRecorder, WAIT_BUCKETS_S,
    spans,
)
from repro.online.traces import (
    TRACE_FAMILIES, diurnal_trace, fragmented_trace, heavy_tailed_trace,
    mmpp_trace, poisson_trace,
)
from repro.online.vecsim import (
    SweepSummary, TrainRollout, VectorizedClusterSimulator,
    VectorizedFleetSimulator, make_rollout_collector,
)

__all__ = [
    "Arrival", "ClusterSimulator", "DispatchPolicy", "DriftMonitor",
    "FleetView", "FragRouter", "GreedyPackerPolicy", "HashRouter",
    "JobRecord", "LeastLoadedRouter", "MetricsRegistry", "OnlineRetrainer",
    "PodView", "PolicyStats", "ROUTERS", "RLDispatchPolicy",
    "Router", "Segment", "SimConfig", "SimResult", "StaticPartitionPolicy",
    "SweepSummary", "TRACE_FAMILIES", "Telemetry", "TimeSharingPolicy",
    "TraceRecorder", "TrainRollout", "VectorizedClusterSimulator",
    "VectorizedFleetSimulator", "WAIT_BUCKETS_S",
    "default_retrain_online_config", "default_retrain_train_config",
    "diurnal_trace", "fragmented_trace",
    "heavy_tailed_trace", "make_rollout_collector", "make_router",
    "mmpp_trace", "poisson_trace", "spans",
]

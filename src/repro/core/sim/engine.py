"""Event-driven cluster simulation engine.

The engine owns time: the event heap, the simulation clock, GPU failure /
repair injection, job accounting and metric collection.  Every *scheduling*
decision — queue discipline, placement, phase transitions after a timer,
reaction to completions — is delegated to the :class:`~repro.core.sim
.policies.Policy` named by ``SimConfig.policy`` (see
``repro/core/sim/policies/`` for the built-ins and how to add one).  The
*placement* choice within a policy's feasible GPUs is a further pluggable
layer: the :class:`~repro.core.sim.placement.Placer` named by
``SimConfig.placer`` (default ``least-loaded``, the paper's rule).

Fleets may be heterogeneous: pass ``fleet=`` (a list of
:class:`~repro.core.fleet.GPUSpec`, e.g. from ``fleet.parse_fleet
("a100:4+h100:4")``) and every GPU carries its own partition space,
performance model and estimator.  The legacy ``(space, pm, estimator)``
arguments build a homogeneous fleet and stay bit-identical to the
pre-fleet simulator; ``sim.space`` / ``sim.pm`` / ``sim.estimator`` remain
as the first spec's objects for homogeneous callers.

The *goal* of every partition decision is the third pluggable layer: the
:class:`~repro.core.sim.objectives.Objective` named by
``SimConfig.objective`` (``throughput`` — the paper's, bit-identical
default — / ``energy`` / ``edp``).  Each GPU integrates its wall power
(per-kind :class:`~repro.core.fleet.PowerModel`) into ``GPU.energy_j``;
the run's total lands in ``TraceMetrics.energy_j``.

Fault tolerance: optional Poisson GPU failures re-queue affected jobs with
progress rolled back to the last checkpoint *of the current placement*
(periodic ones every ``ckpt_interval_s`` of progressing time, plus any CKPT
phase the GPU actually executed); the destroyed work is speed-weighted, not
wall-clock.  The failed GPU is out for ``repair_s``.  The policy's normal
arrival path handles re-admission — job-level fault tolerance is the
scheduler itself.  ``rack_size`` / ``rack_mtbf_s`` add *correlated*
failures on top: whole racks of consecutive GPU ids go down in one event
(the power/network failure domain per-GPU Poisson faults cannot express).
"""
from __future__ import annotations

import dataclasses
import heapq
import itertools
import time
from dataclasses import dataclass
from typing import Dict, List, Optional, Sequence, Tuple

import numpy as np

from repro.core.estimators import OracleEstimator
from repro.core.fleet import GPUSpec, homogeneous_fleet
from repro.core.jobs import Job
from repro.core.metrics import TraceMetrics, compute_metrics
from repro.core.partitions import PartitionSpace
from repro.core.perfmodel import PerfModel
from repro.core.sim.faults import FaultInjector, get_fault_injector
from repro.core.sim.gpu import (CKPT, DEGRADED, GPU, HEALTHY, IDLE, MIG_RUN,
                                MPS_PROF, QUARANTINED)
from repro.core.sim.index import FleetIndex, WorkAggregate
from repro.core.sim.prof import new_prof
from repro.core.sim.soa import FleetState
from repro.core.sim.policies import get_policy


@dataclass
class SimConfig:
    n_gpus: int = 8
    policy: str = "miso"             # any name in policies.available_policies()
    placer: str = "least-loaded"     # any name in placement.available_placers()
    objective: str = "throughput"    # any name in objectives.available_objectives()
    static_partition: Tuple[int, ...] = (4, 2, 1)   # optsta only
    mps_level_time_s: float = 10.0   # per MPS level (paper: 10s x 3 levels)
    mig_reconfig_s: float = 4.0      # GPU reset (paper §3)
    ckpt_base_s: float = 2.0
    ckpt_bw_gbps: float = 4.0        # job state of mem_gb -> save+restore time
    overhead_scale: float = 1.0      # Fig 17 sensitivity knob
    mps_only_level: float = 0.33
    mps_only_max_jobs: int = 3
    max_sim_s: float = 10_000_000.0
    # fault injection
    gpu_mtbf_s: float = 0.0          # 0 = no failures
    repair_s: float = 600.0
    ckpt_interval_s: float = 600.0   # periodic checkpoint for fault rollback
    # correlated (rack-level) failures: racks of `rack_size` consecutive
    # GPU ids fail together at Poisson rate 1/rack_mtbf_s (both must be > 0)
    rack_size: int = 0
    rack_mtbf_s: float = 0.0
    # pluggable fault injectors (core/sim/faults.py) by registry name; the
    # default () enables nothing — no fault events exist, no fault RNG is
    # drawn, golden traces stay bit-identical (zero-overhead guarantee)
    faults: Tuple[str, ...] = ()
    mps_crash_mtbf_s: float = 0.0    # mps_blast: mean s between crash shocks
    reconfig_fail_p: float = 0.0     # flaky_reconfig: P(repartition op fails)
    reconfig_retry_s: float = 20.0   # base retry backoff, doubled per attempt
    reconfig_max_retries: int = 3    # exhausted retries = hard GPU fault
    straggler_mtbf_s: float = 0.0    # straggler: mean s between onsets
    straggler_factor: float = 0.5    # degraded speed multiplier while struck
    straggler_recover_s: float = 1800.0  # degradation clears after this
    estimator_fault_p: float = 0.0   # estimator_garbage: P(garbage window)
    # GPU health state machine (healthy -> degraded -> quarantined ->
    # repaired): `quarantine_faults` soft faults within `quarantine_window_s`
    # quarantine the GPU for `quarantine_repair_s`, migrating its residents
    # off via the checkpoint/rollback primitive.  0 = never quarantine.
    quarantine_faults: int = 0
    quarantine_window_s: float = 3600.0
    quarantine_repair_s: float = 1800.0
    seed: int = 0
    # profiling measurement noise (paper Fig 14): sigma of the relative error
    # on each MPS-matrix entry; drawn from the simulator RNG per window
    mps_noise_sigma: float = 0.0
    # collect per-component wall-clock (placement / Algorithm-1 / estimator /
    # event loop / BatchSim stages) into ClusterSim.prof (core/sim/prof.py);
    # surfaced by `launch/sweep --profile`
    profile: bool = False


class ClusterSim:
    def __init__(self, jobs: Sequence[Job], cfg: SimConfig,
                 space: Optional[PartitionSpace] = None,
                 pm: Optional[PerfModel] = None, estimator=None,
                 fleet: Optional[Sequence[GPUSpec]] = None):
        if fleet is None:
            if space is None or pm is None:
                raise TypeError("ClusterSim needs either (space, pm) or fleet=")
            fleet = homogeneous_fleet(space, pm,
                                      estimator or OracleEstimator(pm),
                                      cfg.n_gpus)
        else:
            fleet = list(fleet)
            if cfg.n_gpus != len(fleet):
                # the fleet defines the cluster size; keep the caller's
                # config object untouched
                cfg = dataclasses.replace(cfg, n_gpus=len(fleet))
        self.cfg = cfg
        self.fleet: List[GPUSpec] = list(fleet)
        # homogeneous-compat defaults (first spec); per-GPU code must use
        # g.space / g.pm / g.estimator
        self.space = self.fleet[0].space
        self.pm = self.fleet[0].pm
        self.estimator = self.fleet[0].estimator
        self.jobs = {j.jid: j for j in jobs}
        self.queue: List[int] = []
        self.gpus = [GPU(i, self, spec) for i, spec in enumerate(self.fleet)]
        self.events: List[tuple] = []
        self.t = 0.0
        self.rng = np.random.default_rng(cfg.seed)
        # separate stream for profiling measurement noise: common random
        # numbers across sensitivity arms — varying mps_noise_sigma must not
        # perturb the failure-injection schedule drawn from self.rng
        self.noise_rng = np.random.default_rng((cfg.seed, 0xA100))
        # third dedicated stream, for the pluggable fault injectors
        # (core/sim/faults.py): enabling or tuning chaos must not perturb
        # the Poisson failure schedule in self.rng or the measurement noise
        # in self.noise_rng — and vice versa (CONTRIBUTING, determinism
        # contract)
        self.fault_rng = np.random.default_rng((cfg.seed, 0xFA17))
        self.profile_cache: Dict[tuple, Dict[int, float]] = {}  # (mi_group, space)
        self.completed: List[int] = []
        self._counter = itertools.count()
        # per-component wall-clock buckets (core/sim/prof.py; None =
        # profiling off, the hot paths check `prof is not None` and pay
        # nothing)
        self.prof: Optional[Dict[str, float]] = (
            new_prof() if cfg.profile else None)
        # -- placement hot-path structures (see repro.core.sim.index):
        # in-system remaining-work aggregate (hetero-speed split point) ...
        self.work_agg = WorkAggregate()
        self._resident_count = 0
        # ... cached up-set, invalidated on failure / repair promotion; the
        # down-heap drives promotions lazily as the clock passes down_until
        self._up_cache: Optional[List[GPU]] = None
        self._down_heap: List[Tuple[float, int]] = []
        # ... and the per-kind (count, max-addable-slice) fleet index; built
        # before the policy so its placer can bind to it
        self.index = FleetIndex(self)
        for g in self.gpus:
            self._refresh_feas(g)
            self.index.add(g)
        # fleet-wide SoA staging buffers for vectorized batch settles
        # (end-of-run, rollout snapshots); per-event paths never touch it
        self.fleet_state = FleetState(self.gpus)
        self.policy = get_policy(cfg.policy)(self)
        # -- robustness accounting (all zero when nothing ever faults):
        # destroyed work and recovery waits are Kahan-summed like the
        # in-system work aggregate; counters are plain ints
        self.fstats: Dict[str, float] = {
            "n_faults": 0, "n_blasts": 0, "blast_jobs": 0,
            "blast_radius_max": 0, "n_quarantines": 0, "n_migrations": 0,
            "n_reconfig_retries": 0, "n_estimator_faults": 0,
            "quarantine_gpu_s": 0.0,
        }
        self.lost_agg = WorkAggregate()    # work-seconds destroyed by faults
        self.recover_agg = WorkAggregate()  # fault-eviction -> re-place waits
        self._evict_t: Dict[int, float] = {}  # jid -> last fault-evict time
        # -- fault injectors: engine-side hooks are collected once so runs
        # without them pay a single empty-list check per hook point
        self.fault_injectors: Dict[str, FaultInjector] = {}
        self._reconfig_hooks: List[FaultInjector] = []
        self._est_hooks: List[FaultInjector] = []
        for name in cfg.faults:
            inj = get_fault_injector(name)(self)
            self.fault_injectors[name] = inj
            if type(inj).on_reconfig_end is not FaultInjector.on_reconfig_end:
                self._reconfig_hooks.append(inj)
            if type(inj).filter_estimates is not FaultInjector.filter_estimates:
                self._est_hooks.append(inj)

        for j in jobs:
            self._push(j.arrival, "arrival", j.jid)
        if cfg.gpu_mtbf_s > 0:
            for g in self.gpus:
                self._push(float(self.rng.exponential(cfg.gpu_mtbf_s)),
                           "failure", g.gid)
        if cfg.rack_mtbf_s > 0 and cfg.rack_size > 0:
            n_racks = (len(self.gpus) + cfg.rack_size - 1) // cfg.rack_size
            for r in range(n_racks):
                self._push(float(self.rng.exponential(cfg.rack_mtbf_s)),
                           "rack_failure", r)
        for inj in self.fault_injectors.values():
            inj.schedule_initial()

    # ---------------------------------------------------------- event glue

    def _push(self, t, kind, payload, stamp=0):
        heapq.heappush(self.events, (t, next(self._counter), kind, payload, stamp))

    def _schedule_gpu_events(self, g: GPU):
        g.stamp += 1
        phase = g.phase
        if phase == CKPT or phase == MPS_PROF:
            heapq.heappush(self.events, (g.phase_end, next(self._counter),
                                         "gpu_timer", g.gid, g.stamp))
        nc = g.next_completion()
        if nc:
            heapq.heappush(self.events, (nc[0], next(self._counter),
                                         "completion", (g.gid, nc[1]),
                                         g.stamp))

    # ---------------------------------------------------------- run loop

    def run(self) -> TraceMetrics:
        n_target = len(self.jobs)
        prof = self.prof
        t_run0 = time.perf_counter() if prof is not None else 0.0
        # hot-loop locals: the heap, the completion list and the clock cap
        # are bound once (none is ever rebound after __init__)
        events = self.events
        completed = self.completed
        gpus = self.gpus
        heappop = heapq.heappop
        max_sim_s = self.cfg.max_sim_s
        while events and len(completed) < n_target:
            t, _, kind, payload, stamp = heappop(events)
            if t > max_sim_s:
                break
            self.t = t
            if prof is not None:
                prof["events"] += 1.0
            # dispatch ordered by event frequency: stale-stamped timer /
            # completion entries dominate the heap traffic at scale
            if kind == "gpu_timer":
                g = gpus[payload]
                if stamp != g.stamp or t < g.phase_end - 1e-9:
                    continue
                self._dispatch_timer(t, g)
            elif kind == "completion":
                gid, jid = payload
                g = gpus[gid]
                if stamp != g.stamp:
                    continue
                g.advance(t)
                rj = g.jobs.get(jid)
                if rj is None or rj.job.remaining > 1e-6:
                    self._schedule_gpu_events(g)
                    continue
                self._dispatch_completion(t, g, rj.job)
            elif kind == "arrival":
                self._dispatch_arrival(t, payload)
            elif kind == "failure":
                self._on_failure(self.gpus[payload])
            elif kind == "rack_failure":
                self._on_rack_failure(payload)
            elif kind == "fault":
                # pluggable chaos (core/sim/faults.py): payload routes to
                # the owning injector, which handles and usually re-arms it
                name, data = payload
                self.fault_injectors[name].on_event(data)
            elif kind == "repair":
                self.policy.admit()
        # settle every GPU's accounting (and energy integral) to the final
        # clock; completed-job metrics are already fixed, so this only
        # extends idle/energy windows.  One masked vector update covers the
        # eligible rows (bit-identical to the scalar advance — see
        # core/sim/soa.py); the rest keep scalar operation order.
        self.fleet_state.settle_all(self.t)
        if prof is not None:
            prof["total_s"] += time.perf_counter() - t_run0
        return self.finish(settle=False)

    # ------------------------------------------------- stepping / batching
    # The same event bodies run() inlines, exposed one tick at a time so
    # BatchSim (core/sim/batch.py) can advance many replicas in lockstep.
    # run() stays the hot scalar path: the dispatchers below are only
    # called on *valid* timer/completion/arrival events, whose policy work
    # dwarfs one extra method call; stale-stamp traffic never leaves the
    # inline loop.

    def _dispatch_timer(self, t: float, g: GPU, collect: bool = False):
        """Process a valid gpu_timer event (plus its same-tick batch).

        ``collect=True`` (BatchSim) returns a :class:`PendingPhaseEnd`
        holding the policy's estimator work instead of finishing the tick,
        or True when the policy has no batchable work (processed inline)."""
        batch = self._drain_same_tick_timers(t, g)
        if collect:
            gs = [g] if batch is None else batch
            pend = self._collect_phase_end(gs)
            return True if pend is None else pend
        if batch is None:
            self.end_phase(g)
        else:
            self.end_phase_batch(batch)
        return True

    def _dispatch_completion(self, t: float, g: GPU, job: Job,
                             collect: bool = False):
        """Process a valid completion event (plus its same-tick batch);
        ``collect=True`` may return a :class:`PendingCompletion`."""
        batch = self._drain_same_tick_completions(t, g, job)
        if collect:
            items = [(g, job)] if batch is None else batch
            pend = self._collect_completions(items)
            return True if pend is None else pend
        if batch is None:
            self._on_completion(g, job)
        else:
            self._on_completion_batch(batch)
        return True

    def _dispatch_arrival(self, t: float, jid: int) -> None:
        # drain every further arrival stamped exactly t so the FCFS
        # admit runs once over the whole burst (trace replays carry
        # integer timestamps with heavy same-second bursts); for
        # FCFS this is literally the same placement sequence, and
        # queue-scanning disciplines (SRPT) see the full burst at
        # once — their intended semantics
        events = self.events
        prof = self.prof
        self._enqueue(self.jobs[jid])
        while events and events[0][0] == t and events[0][2] == "arrival":
            _, _, _, jid2, _ = heapq.heappop(events)
            if prof is not None:
                prof["events"] += 1.0
            self._enqueue(self.jobs[jid2])
        self.policy.admit()

    def step_event(self, collect: bool = False):
        """Advance the simulation by one *processed* event tick.

        Pops events exactly as :meth:`run` does (stale-stamped entries are
        skipped without returning) and processes the first valid one.
        Returns:

        * ``True`` — a tick was fully processed, more work may remain;
        * ``False`` — terminal: heap empty, all jobs completed, or the
          clock cap was passed (matching run()'s loop conditions);
        * a pending object (``collect=True`` only) — the tick's policy
          decisions were *collected* but not applied: the caller owns the
          estimate -> partition -> apply pipeline (see
          :class:`PendingPhaseEnd` / :class:`PendingCompletion`), which
          lets BatchSim fuse this work across replicas.
        """
        events = self.events
        gpus = self.gpus
        prof = self.prof
        n_target = len(self.jobs)
        max_sim_s = self.cfg.max_sim_s
        while events and len(self.completed) < n_target:
            t, _, kind, payload, stamp = heapq.heappop(events)
            if t > max_sim_s:
                return False
            self.t = t
            if prof is not None:
                prof["events"] += 1.0
            if kind == "gpu_timer":
                g = gpus[payload]
                if stamp != g.stamp or t < g.phase_end - 1e-9:
                    continue
                return self._dispatch_timer(t, g, collect)
            elif kind == "completion":
                gid, jid = payload
                g = gpus[gid]
                if stamp != g.stamp:
                    continue
                g.advance(t)
                rj = g.jobs.get(jid)
                if rj is None or rj.job.remaining > 1e-6:
                    self._schedule_gpu_events(g)
                    continue
                return self._dispatch_completion(t, g, rj.job, collect)
            elif kind == "arrival":
                self._dispatch_arrival(t, payload)
                return True
            elif kind == "failure":
                self._on_failure(gpus[payload])
                return True
            elif kind == "rack_failure":
                self._on_rack_failure(payload)
                return True
            elif kind == "fault":
                name, data = payload
                self.fault_injectors[name].on_event(data)
                return True
            elif kind == "repair":
                self.policy.admit()
                return True
        return False

    def run_until_collect(self):
        """Drain events inline — :meth:`run`'s hoisted hot loop — until a
        tick yields a pending collect batch, and return it.  Ticks whose
        policy has no batchable work are processed inline exactly as
        ``step_event(collect=True)`` would; returns None when the replica
        is terminal (heap empty, all jobs completed, or clock cap passed).

        This is BatchSim's per-round frontier: every live replica
        surrenders exactly one pending per round, so the cross-replica
        fusion batch is as wide as the batch itself while the per-event
        overhead stays at run()-loop level (no per-event method call).
        A replica whose policy never collects (no fusable hooks) runs to
        completion in one call — bit-identical to its scalar run."""
        events = self.events
        completed = self.completed
        gpus = self.gpus
        heappop = heapq.heappop
        prof = self.prof
        n_target = len(self.jobs)
        max_sim_s = self.cfg.max_sim_s
        while events and len(completed) < n_target:
            t, _, kind, payload, stamp = heappop(events)
            if t > max_sim_s:
                return None
            self.t = t
            if prof is not None:
                prof["events"] += 1.0
            if kind == "gpu_timer":
                g = gpus[payload]
                if stamp != g.stamp or t < g.phase_end - 1e-9:
                    continue
                r = self._dispatch_timer(t, g, collect=True)
                if r is not True:
                    return r
            elif kind == "completion":
                gid, jid = payload
                g = gpus[gid]
                if stamp != g.stamp:
                    continue
                g.advance(t)
                rj = g.jobs.get(jid)
                if rj is None or rj.job.remaining > 1e-6:
                    self._schedule_gpu_events(g)
                    continue
                r = self._dispatch_completion(t, g, rj.job, collect=True)
                if r is not True:
                    return r
            elif kind == "arrival":
                self._dispatch_arrival(t, payload)
            elif kind == "failure":
                self._on_failure(gpus[payload])
            elif kind == "rack_failure":
                self._on_rack_failure(payload)
            elif kind == "fault":
                name, data = payload
                self.fault_injectors[name].on_event(data)
            elif kind == "repair":
                self.policy.admit()
        return None

    def _collect_phase_end(self, gs: List[GPU]):
        """Collect-mode twin of :meth:`end_phase_batch`: same reconfig
        filter and pre-phase accounting, but the policy's estimator windows
        are *collected* for cross-replica fusion instead of being estimated
        here.  Returns None when the policy has nothing to fuse (the whole
        tick was processed inline, exactly as end_phase_batch would)."""
        if self._reconfig_hooks:
            gs = [g for g in gs
                  if not (g.phase == CKPT and self._reconfig_failed(g))]
            if not gs:
                return None
        for g in gs:
            self._pre_phase_end(g)
        work = self.policy.collect_phase_end(gs)
        if work is None:
            # policy has no batchable estimator work this tick (or does not
            # support collection): fall through to the scalar batch path
            self.policy.on_phase_end_batch(gs)
            for g in gs:
                self.finalize(g)
            return None
        return PendingPhaseEnd(self, gs, work)

    def _collect_completions(self, items: List[Tuple[GPU, Job]]):
        """Collect-mode twin of :meth:`_on_completion_batch`: completion
        accounting runs now; the policy's repartition decisions are
        collected for cross-replica fusion.  Returns None when the policy
        does not support collection (tick processed inline) or had no
        decisions to make (finalize/admit run inline)."""
        for g, job in items:
            self._finish(g, job)
        decisions = self.policy.collect_completion(items)
        if decisions is None:
            self.policy.on_completion_batch(items)
            for g, _ in items:
                self.finalize(g)
            self.policy.admit()
            return None
        if not decisions:
            for g, _ in items:
                self.finalize(g)
            self.policy.admit()
            return None
        return PendingCompletion(self, items, decisions)

    def finish(self, settle: bool = True) -> TraceMetrics:
        """Final accounting + metric collection (end of run()/BatchSim).
        ``settle=False`` is for callers that already settled the fleet to
        ``self.t`` (run()'s tail, BatchSim's batched settle)."""
        if settle:
            self.fleet_state.settle_all(self.t)
        fs = self.fstats
        if fs["n_quarantines"]:
            # a quarantine still open at the final clock only occupied the
            # fleet up to that clock, not its whole repair window
            fs["quarantine_gpu_s"] -= sum(
                g.down_until - self.t for g in self.gpus
                if g.health == QUARANTINED and g.down_until > self.t)
        return compute_metrics([self.jobs[i] for i in self.completed],
                               self.cfg.n_gpus,
                               energy_j=float(sum(g.energy_j
                                                  for g in self.gpus)),
                               energy_span_s=self.t,
                               fault_stats={
                                   **fs,
                                   "work_lost_s": self.lost_agg.total,
                                   "recover_s_total": self.recover_agg.total,
                                   "n_recovered": self.recover_agg.count})

    # ----------------------------------------------- placement constraints
    # Shared feasibility checks usable by any policy's pick_gpu; all are
    # evaluated against the candidate GPU's own space / perf model.

    def _sync_up(self):
        """Promote repaired GPUs back into the in-service structures once
        the clock passes their ``down_until``.  Entries whose GPU failed
        again while down (``down_until`` extended, a fresh entry pushed) or
        was already promoted are stale and skipped."""
        heap = self._down_heap
        t = self.t
        while heap and heap[0][0] <= t:
            _, gid = heapq.heappop(heap)
            g = self.gpus[gid]
            if g._in_index or t < g.down_until:
                continue
            if g.health != HEALTHY:
                # repairs are full repairs: a quarantined (or degraded-then-
                # failed) GPU comes back healthy
                g.health = HEALTHY
            self._refresh_feas(g)
            self.index.add(g)
            self._up_cache = None

    def up_gpus(self):
        """GPUs currently in service (not failed / under repair).  Cached:
        the up-set only changes at failure events and ``down_until``
        boundaries, both of which invalidate it — not on every call."""
        self._sync_up()
        if self._up_cache is None:
            self._up_cache = [g for g in self.gpus if self.t >= g.down_until]
        return self._up_cache

    def _refresh_feas(self, g: GPU):
        """Recompute ``g._max_add``: the largest menu slice a new job could
        still require with ``g``'s residents feasibly re-partitioned around
        it (0 = nothing fits).  ``PartitionSpace.placeable`` is monotone in
        the added requirement, so for memory-monotone menus
        ``spare_slice_ok(g, job) == (min_required_slice(job) <= _max_add)``
        — which is what lets the fleet index prune whole buckets instead of
        running ``feasible_exact`` per GPU.  Non-monotone menus (no shipped
        space) get ``None``: never pruned, always exact-checked."""
        space = g.space
        if not space._mem_monotone:
            g._max_add = None
            return
        if len(g.jobs) >= space.max_jobs:
            g._max_add = 0
            return
        reqs = []
        for rj in g.jobs.values():
            r = space.job_required_slice(rj.job)
            if r is None:                # unplaceable resident (forced state):
                g._max_add = 0           # nothing more fits for sure
                return
            reqs.append(r)
        key = tuple(sorted(reqs))
        cached = space._max_add_cache.get(key)
        if cached is not None:
            g._max_add = cached
            return
        best = 0
        for s in space.sizes:            # sizes are stored descending
            if space.placeable(reqs + [s]):
                best = s
                break
        if len(space._max_add_cache) >= 65536:
            space._max_add_cache.pop(next(iter(space._max_add_cache)))
        space._max_add_cache[key] = best
        g._max_add = best

    def _resident_changed(self, g: GPU):
        """Re-bucket ``g`` after its resident set changed (in-service GPUs
        only; failed ones re-enter via the repair promotion)."""
        if g._in_index:
            self._refresh_feas(g)
            self.index.update(g)

    def remove_resident(self, g: GPU, jid: int):
        """Remove one resident from ``g`` keeping the placement index and
        resident accounting consistent.  Policies must route evictions
        through this instead of ``del g.jobs[jid]``."""
        rj = g._pop_resident(jid)
        if rj.job.phases:
            g._n_phased -= 1
        g._spd_dirty = True
        self._resident_count -= 1
        self._resident_changed(g)

    def mem_ok(self, g: GPU, job: Job, exclude: Optional[int] = None) -> bool:
        if exclude is None:
            # resident memory sum cached on the speed-key identity chain: a
            # changed resident set always re-keys refresh_speeds before the
            # next placement scan, and the recompute below runs in dict
            # order — bit-identical to summing fresh on every call
            if g._mem_key is g._spd_key:
                total = g._mem_total
            else:
                total = sum(rj.job.profile.mem_gb
                            for rj in g.jobs.values())
                g._mem_total = total
                g._mem_key = g._spd_key
        else:
            total = sum(rj.job.profile.mem_gb for jid, rj in g.jobs.items()
                        if jid != exclude)
        return total + job.profile.mem_gb <= g.pm.hw.mem_gb

    def spare_slice_ok(self, g: GPU, job: Job,
                       exclude: Optional[int] = None) -> bool:
        """'Maximum spare slice' check (paper §4.3): after adding the job,
        some valid partition must give every job a memory- AND QoS-feasible
        slice.  ``exclude`` ignores one resident jid (what-if for
        preemption).

        The check is *exact* and vectorized: each job's (memory, QoS) pair
        collapses to one scalar slice requirement
        (:meth:`PartitionSpace.min_required_slice`), compared in one pass
        against the space's precomputed per-length sorted-size matrix.  The
        historical biggest-memory-first greedy missed feasible placements
        when a small-memory job carried a large QoS floor (e.g. mem=1 GB
        qos_min_slice=4 next to mem=10 GB qos=0 on partition (4, 2))."""
        space = g.space
        mems = [max(job.profile.mem_gb, job.min_mem_gb)]
        qoss = [job.qos_min_slice]
        for jid, rj in g.jobs.items():
            if jid != exclude:
                mems.append(max(rj.job.profile.mem_gb, rj.job.min_mem_gb))
                qoss.append(rj.job.qos_min_slice)
        return space.feasible_exact(mems, qoss)

    # ------------------------------------------------------ job lifecycle

    def _enqueue(self, job: Job):
        job.queue_since = self.t
        self.queue.append(job.jid)
        self.work_agg.add(job.remaining)

    def _on_arrival(self, job: Job):
        # multi-instance clones are expanded by traces.expand_multi_instance;
        # clones share an mi_group so the MPS profile is measured only once.
        self._enqueue(job)
        self.policy.admit()

    def place(self, g: GPU, job: Job):
        """Land ``job`` on ``g`` (accounting + policy phase setup)."""
        g.advance(self.t)
        if job.start_time is None:
            job.start_time = self.t
        job.t_queue += max(0.0, self.t - job.queue_since)
        if self._evict_t:
            # time-to-recover: the wait between a fault eviction (failure /
            # blast / migration) and this re-placement
            t0 = self._evict_t.pop(job.jid, None)
            if t0 is not None:
                self.recover_agg.add(self.t - t0)
        g._add_resident(job)
        if job.phases:
            g._n_phased += 1
        g._spd_dirty = True
        self._resident_count += 1
        self._resident_changed(g)
        self.policy.on_place(g, job)
        self.finalize(g)

    def _drain_same_tick_timers(self, t: float, first: GPU):
        """Pop every further *valid* gpu_timer event stamped exactly ``t``
        off the heap so their phase ends process as one batch (the fused
        estimator service feeds all same-tick MPS windows through a single
        predictor forward).  Safe because a GPU's phase end never touches
        another GPU's state: validity checked at drain time equals validity
        checked after processing the earlier timers, and at most one timer
        per GPU can carry its current stamp.  Returns None when ``first`` is
        alone at this tick."""
        batch = None
        events = self.events
        while events and events[0][0] == t and events[0][2] == "gpu_timer":
            _, _, _, payload, stamp = heapq.heappop(events)
            g2 = self.gpus[payload]
            if stamp != g2.stamp or t < g2.phase_end - 1e-9:
                continue
            if batch is None:
                batch = [first]
            batch.append(g2)
        return batch

    def end_phase(self, g: GPU, schedule: bool = True):
        """A phase window on ``g`` expired; let the policy transition the
        state machine.  ``schedule=False`` suppresses event scheduling for
        callers that finalize the GPU themselves right after (e.g. the
        zero-dead-time checkpoint in MISO's ``begin_profiling`` — such
        instant transitions are not reconfigure ops and skip the fault
        hook)."""
        if schedule and self._reconfig_hooks and g.phase == CKPT \
                and self._reconfig_failed(g):
            return
        self._pre_phase_end(g)
        self.policy.on_phase_end(g)
        self.finalize(g, schedule=schedule)

    def end_phase_batch(self, gs: Sequence[GPU]):
        """Process several same-tick phase ends as one policy batch.  The
        accounting before and the finalize after bracket the policy hook per
        GPU exactly as back-to-back :meth:`end_phase` calls would (phase
        ends are cross-GPU independent; event counters are consumed only by
        the finalize loop, in the same order)."""
        if self._reconfig_hooks:
            gs = [g for g in gs
                  if not (g.phase == CKPT and self._reconfig_failed(g))]
            if not gs:
                return
        for g in gs:
            self._pre_phase_end(g)
        self.policy.on_phase_end_batch(gs)
        for g in gs:
            self.finalize(g)

    def _reconfig_failed(self, g: GPU) -> bool:
        """Give enabled injectors a shot at failing the reconfigure op that
        ends a CKPT window (transient MIG-reconfiguration faults).  True
        means the op failed: the injector already rescheduled the retry (or
        escalated to a hard fault) and the phase end must not proceed — in
        particular the in-flight checkpoint is NOT durable (no since_ckpt
        reset), matching the mid-save failure semantics in ``GPU.advance``."""
        for inj in self._reconfig_hooks:
            if inj.on_reconfig_end(g):
                return True
        return False

    def _pre_phase_end(self, g: GPU):
        g.advance(self.t)
        if g.phase == CKPT:
            # the checkpoint window ran to completion: the save is durable,
            # so resident jobs have nothing left at risk
            g.reset_ckpt_marks()

    def _drain_same_tick_completions(self, t: float, first: GPU,
                                     first_job: Job):
        """Pop every further *valid* completion event stamped exactly ``t``
        so the policies' completion reactions batch (MISO re-optimizes every
        affected GPU through one Algorithm-1 pass) and the queue is admitted
        once for the whole tick.  Only contiguous completion events are
        taken — interleaved other-kind events keep their heap order — and at
        most one completion per GPU can be valid (``next_completion``
        schedules only the earliest; a same-tick follow-up is rescheduled by
        the finalize and drains on the next loop iteration).  Returns None
        when ``first`` is alone at this tick."""
        batch = None
        events = self.events
        prof = self.prof
        while events and events[0][0] == t and events[0][2] == "completion":
            _, _, _, (gid, jid), stamp = heapq.heappop(events)
            if prof is not None:
                prof["events"] += 1.0
            g2 = self.gpus[gid]
            if stamp != g2.stamp:
                continue
            g2.advance(t)
            rj = g2.jobs.get(jid)
            if rj is None or rj.job.remaining > 1e-6:
                self._schedule_gpu_events(g2)
                continue
            if batch is None:
                batch = [(first, first_job)]
            batch.append((g2, rj.job))
        return batch

    def _finish(self, g: GPU, job: Job):
        """Shared completion accounting (single and batched paths)."""
        job.finish_time = self.t
        self.work_agg.discard(job.remaining)
        job.remaining = 0.0
        self.remove_resident(g, job.jid)
        g.estimates.pop(job.jid, None)
        self.completed.append(job.jid)

    def _on_completion(self, g: GPU, job: Job):
        self._finish(g, job)
        self.policy.on_completion(g, job)
        self.finalize(g)
        self.policy.admit()

    def _on_completion_batch(self, items: Sequence[Tuple[GPU, Job]]):
        """Several same-tick completions on distinct GPUs: account them all,
        let the policy react once (batched Algorithm-1 across the affected
        GPUs), then finalize each GPU and admit the queue once."""
        for g, job in items:
            self._finish(g, job)
        self.policy.on_completion_batch(items)
        for g, _ in items:
            self.finalize(g)
        self.policy.admit()

    # ------------------------------------------- failures, faults & health

    def _on_failure(self, g: GPU):
        # an independent failure landing on a GPU already down (rack outage
        # or an earlier fault) is absorbed: it must not restart the repair
        # clock, re-evacuate an empty GPU or push a second live heap entry —
        # the same guard the rack path applies.  The next-failure draw still
        # happens, so the Poisson schedule is unchanged either way.
        if self.t >= g.down_until:
            self.record_fault(g, hard=True)
            self._fail_gpu(g)
        if self.cfg.gpu_mtbf_s > 0:
            self._push(self.t + float(self.rng.exponential(self.cfg.gpu_mtbf_s)),
                       "failure", g.gid)

    def _on_rack_failure(self, rack: int):
        """Correlated failure: every in-service GPU of ``rack`` (a block of
        ``cfg.rack_size`` consecutive ids) goes down at once — the
        rack-level power/network event per-GPU Poisson faults cannot model.
        Already-down members stay on their existing repair clock."""
        lo = rack * self.cfg.rack_size
        for g in self.gpus[lo:lo + self.cfg.rack_size]:
            if self.t >= g.down_until:
                self.record_fault(g, hard=True)
                self._fail_gpu(g)
        self._push(self.t + float(self.rng.exponential(self.cfg.rack_mtbf_s)),
                   "rack_failure", rack)

    def record_fault(self, g: GPU, hard: bool = False) -> bool:
        """Account one fault on ``g`` and drive the health state machine
        (healthy -> degraded -> quarantined).  ``hard`` faults — outright
        GPU/rack failures, which already pay a full repair window — are
        counted but do not feed the quarantine tracker.  Returns True when
        this fault tipped ``g`` into quarantine (its residents are already
        migrated off and the GPU is down)."""
        self.fstats["n_faults"] += 1
        if hard:
            return False
        cfg = self.cfg
        g.fault_times.append(self.t)
        lo = self.t - cfg.quarantine_window_s
        while g.fault_times and g.fault_times[0] < lo:
            g.fault_times.pop(0)
        if (cfg.quarantine_faults > 0
                and len(g.fault_times) >= cfg.quarantine_faults
                and self.t >= g.down_until):
            self._quarantine(g)
            return True
        if g.health == HEALTHY:
            g.health = DEGRADED
        return False

    def _quarantine(self, g: GPU):
        """Too many faults inside the window: migrate every resident off
        ``g`` (checkpoint/rollback primitive) and take it out of service
        for ``cfg.quarantine_repair_s`` through the same down machinery
        plain failures use; the repair promotion restores it to healthy."""
        fs = self.fstats
        fs["n_quarantines"] += 1
        fs["quarantine_gpu_s"] += self.cfg.quarantine_repair_s
        self.migrate_residents(g)
        g.fault_times = []
        self._take_down(g, self.cfg.quarantine_repair_s)
        g.health = QUARANTINED
        # unlike plain failures (whose victims wait for the next admit),
        # evacuation is a deliberate scheduling action: re-place now
        self.policy.admit()

    def migrate_residents(self, g: GPU) -> int:
        """Migration primitive (quarantine evacuation today; live migration
        / defragmentation tomorrow): checkpoint-roll every resident of
        ``g`` back and requeue it at the head in placement order.  A
        migrating job pays exactly its since-last-checkpoint work — the
        same price a failure charges — and its re-placement wait lands in
        the time-to-recover metric.  Returns the number migrated; ``g``
        stays in service (callers that also fail the GPU take it down
        themselves)."""
        n = len(g.jobs)
        if n:
            self.fstats["n_migrations"] += n
            self._evacuate_residents(g)
            g.phase = IDLE
            g.partition = ()
            self._resident_changed(g)
            self.finalize(g)
        return n

    def crash_jobs(self, g: GPU, jids: Sequence[int]):
        """Fault-kill specific residents of ``g`` (MPS blast radius / MIG
        slice containment): each victim rolls back to its last placement
        checkpoint and requeues at the head in placement order; ``g`` stays
        in service and the policy reshapes the survivors
        (``Policy.on_fault_evict``)."""
        g.advance(self.t)
        victims = set(jids)
        requeued = []
        for jid in list(g.jobs):
            if jid not in victims:
                continue
            rj = g.jobs[jid]
            job = rj.job
            rolled = min(job.work, job.remaining + rj.since_ckpt_work)
            self.work_agg.shift(rolled - job.remaining)
            self.lost_agg.shift(rolled - job.remaining)
            job.remaining = rolled
            job.queue_since = self.t
            self._evict_t[jid] = self.t
            requeued.append(jid)
            if job.phases:
                g._n_phased -= 1
            g._pop_resident(jid)
            g.estimates.pop(jid, None)
        self.queue[:0] = requeued
        self._resident_count -= len(requeued)
        g._spd_dirty = True
        self._resident_changed(g)
        self.policy.on_fault_evict(g)
        self.finalize(g)
        self.policy.admit()

    def _evacuate_residents(self, g: GPU):
        """Checkpoint-rollback eviction shared by failures, quarantine and
        migration: every resident loses its since-last-checkpoint work
        (speed-weighted, never wall-clock seconds and never cumulative
        t_run across earlier placements), is requeued at the head without
        reversing placement order, and starts a time-to-recover clock."""
        g.advance(self.t)
        if not g.jobs:
            return
        requeued = []
        for rj in g.jobs.values():
            job = rj.job
            rolled = min(job.work, job.remaining + rj.since_ckpt_work)
            self.work_agg.shift(rolled - job.remaining)
            self.lost_agg.shift(rolled - job.remaining)
            job.remaining = rolled
            job.queue_since = self.t
            self._evict_t[job.jid] = self.t
            requeued.append(job.jid)
        self.queue[:0] = requeued
        self._resident_count -= len(g.jobs)
        g._clear_residents()
        g._n_phased = 0
        g._spd_dirty = True
        g.estimates.clear()

    def _take_down(self, g: GPU, repair_s: float):
        """Out of service for ``repair_s``, shared by failures and
        quarantine.  Repairs are full repairs: straggler degradation and
        any in-flight reconfig retry clear with the hardware swap."""
        g.phase = IDLE
        g.partition = ()
        g.speed_fault = 1.0
        g.sched_ok = True
        g.reconfig_tries = 0
        # mutations here bypass refresh_speeds: break the speed/watts/memory
        # validity chains so the next refresh and advance recompute
        g._spd_dirty = True
        g._spd_key = object()
        g.down_until = self.t + repair_s
        g.stamp += 1
        # out of service: drop from the fleet index and the up-set cache;
        # _sync_up promotes it back once the clock passes down_until (a
        # re-failure while down just leaves a stale, skipped heap entry)
        self.index.remove(g)
        self._up_cache = None
        heapq.heappush(self._down_heap, (g.down_until, g.gid))
        self._push(g.down_until, "repair", g.gid, g.stamp)

    def _fail_gpu(self, g: GPU):
        """Take ``g`` down now: roll resident jobs back to their last
        placement checkpoint, requeue them at the head, schedule the
        repair.  Shared by independent failures, rack outages and
        exhausted reconfig retries."""
        self._evacuate_residents(g)
        self._take_down(g, self.cfg.repair_s)

    def filter_estimates(self, g: GPU, jids: Sequence[int], ests):
        """Give enabled estimator-fault injectors a chance to corrupt the
        freshly-produced slice-speed estimates (no-op list when no injector
        hooks the point — the zero-overhead path)."""
        for inj in self._est_hooks:
            ests = inj.filter_estimates(g, jids, ests)
        return ests

    # ---------------------------------------------------------- common

    def finalize(self, g: GPU, schedule: bool = True):
        g.refresh_speeds()
        if schedule:
            self._schedule_gpu_events(g)


class PendingPhaseEnd:
    """A collected same-tick phase-end batch awaiting its estimator pass.

    Produced by ``step_event(collect=True)`` when the tick's policy has MPS
    windows to estimate.  The owner (BatchSim) runs the pipeline in stages
    so the expensive middle fuses across replicas:

    1. ``work`` — :class:`~repro.core.sim.policies.base.EstimateWork` items
       whose ``ests`` the owner fills via one fused ``estimate_batch`` per
       estimator object (stage A);
    2. :meth:`apply` — store the estimates / run the non-MPS transitions in
       scalar order and collect
       :class:`~repro.core.sim.policies.base.RepartDecision` items, whose
       ``choice`` the owner fills via fused ``optimize_partition_batch``
       calls (stages B/C);
    3. :meth:`finish` — apply the solved choices and finalize the GPUs,
       completing the tick exactly as ``end_phase_batch`` would (stage D).
    """

    __slots__ = ("sim", "gs", "work", "decisions")
    kind = "phase_end"

    def __init__(self, sim: "ClusterSim", gs: List[GPU], work: list):
        self.sim = sim
        self.gs = gs
        self.work = work
        self.decisions: list = []

    def apply(self) -> list:
        self.decisions = self.sim.policy.apply_phase_end(self.gs, self.work)
        return self.decisions

    def finish(self) -> None:
        sim = self.sim
        pol = sim.policy
        for d in self.decisions:
            pol.apply_decision(d)
        for g in self.gs:
            sim.finalize(g)


class PendingCompletion:
    """A collected same-tick completion batch awaiting its partition pass.

    Completion accounting and the policy's non-repartition side effects
    already ran; ``decisions`` (non-empty) awaits fused Algorithm-1 solves.
    :meth:`finish` applies the solved choices, finalizes the affected GPUs
    and admits the queue once — the tail of ``_on_completion_batch``."""

    __slots__ = ("sim", "items", "decisions")
    kind = "completion"

    def __init__(self, sim: "ClusterSim", items: List[Tuple[GPU, Job]],
                 decisions: list):
        self.sim = sim
        self.items = items
        self.decisions = decisions

    def apply(self) -> list:
        return self.decisions

    def finish(self) -> None:
        sim = self.sim
        pol = sim.policy
        for d in self.decisions:
            pol.apply_decision(d)
        for g, _ in self.items:
            sim.finalize(g)
        pol.admit()


def simulate(jobs, cfg: SimConfig, space: Optional[PartitionSpace] = None,
             pm: Optional[PerfModel] = None, estimator=None,
             fleet: Optional[Sequence[GPUSpec]] = None) -> TraceMetrics:
    import copy
    jobs = copy.deepcopy(list(jobs))
    return ClusterSim(jobs, cfg, space, pm, estimator, fleet=fleet).run()

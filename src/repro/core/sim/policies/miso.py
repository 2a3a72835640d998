"""MISO: MPS-probe -> predict -> MIG-repartition (the paper's contribution).

Every placement triggers the full pipeline with all its overheads
(conservative reporting, paper §5 "Competing Techniques"):

  checkpoint -> MPS profiling sweep (3 levels) -> estimator -> Algorithm 1
  -> checkpoint + reconfigure -> MIG run

Multi-instance clones reuse their group's cached MPS profile and skip the
sweep (paper §4.3: spawned instances are not re-profiled).
"""
from __future__ import annotations

import time

from repro.core.jobs import Job
from repro.core.sim.gpu import CKPT, GPU, IDLE, MIG_RUN, MPS_PROF
from repro.core.sim.policies.base import (EstimateWork, Policy,
                                          register_policy)
from repro.core.sim.prof import timed


@register_policy
class MisoPolicy(Policy):
    name = "miso"

    # placement: the inherited candidates (shared-MIG admission) ranked by
    # the configured placer — least-loaded by default (paper §4)

    def on_place(self, g: GPU, job: Job):
        # profiles are space-specific: a clone landing on a different
        # accelerator kind must not reuse another kind's slice estimates
        cached = (self.sim.profile_cache.get((job.mi_group, g.space.name))
                  if job.mi_group is not None else None)
        if cached is not None:
            # multi-instance clone: skip MPS, straight to optimizer
            g.estimates[job.jid] = cached
            self.repartition(g, overhead=True)
        else:
            self.begin_profiling(g)

    def on_phase_end(self, g: GPU):
        cfg = self.sim.cfg
        if g.phase == CKPT and g.needs_profile:
            g.phase = MPS_PROF
            g.phase_end = self.sim.t + 3 * cfg.mps_level_time_s \
                * cfg.overhead_scale
            g.needs_profile = False
        elif g.phase == MPS_PROF:
            self.measure_and_partition(g)
        elif g.phase == CKPT:
            g.phase = MIG_RUN if g.jobs else IDLE

    def on_phase_end_batch(self, gs):
        """Fused estimator service: every MPS window ending at this tick is
        measured in event order (one noise draw each, same stream as
        sequential processing), estimated through a single batched predictor
        forward per estimator, and repartitioned through one batched
        Algorithm-1 pass per space.  Non-profiling phase ends in the batch
        keep their sequential semantics."""
        prof_gs = [g for g in gs if g.phase == MPS_PROF]
        if len(prof_gs) < 2:
            for g in gs:
                self.on_phase_end(g)
            return
        mixes = {g.gid: self._mix(g) for g in prof_gs}
        prof = self.sim.prof
        t0 = time.perf_counter() if prof is not None else 0.0
        mats = {g.gid: self._measure(g, mixes[g.gid][1]) for g in prof_gs}
        by_est = {}
        for g in prof_gs:
            by_est.setdefault(id(g.estimator), []).append(g)
        ests = {}
        for group in by_est.values():
            requests = [(mixes[g.gid][1], mats[g.gid], mixes[g.gid][2])
                        for g in group]
            for g, est in zip(group, group[0].estimator.estimate_batch(
                    requests, prof=prof)):
                ests[g.gid] = est
        if prof is not None:
            prof["estimator_s"] += time.perf_counter() - t0
        for g in gs:
            if g.phase == MPS_PROF:
                self._store_estimates(g, mixes[g.gid][0], ests[g.gid])
            else:
                self.on_phase_end(g)
        self.repartition_many(prof_gs, overhead=True)

    # ------------------------------------------- collect/apply (BatchSim)

    def collect_phase_end(self, gs):
        """Collect every MPS window ending at this tick: the mix and its
        (noise-drawing) measurement happen NOW, in event order — exactly
        where :meth:`on_phase_end_batch` draws them — so the dedicated
        noise stream sees the same sequence; only the estimator forward is
        deferred for cross-replica fusion."""
        prof_gs = [g for g in gs if g.phase == MPS_PROF]
        if not prof_gs:
            return None
        work = []
        for g in prof_gs:
            jids, profs, qos = self._mix(g)
            work.append(EstimateWork(g, jids, profs, qos,
                                     self._measure(g, profs), self.sim.prof))
        return work

    def apply_phase_end(self, gs, work):
        """Stage B: estimates are in — store them / run the non-profiling
        transitions in scalar hook order, and hand back the repartition
        decisions for the fused Algorithm-1 pass."""
        by_gid = {w.g.gid: w for w in work}
        prof_gs = []
        for g in gs:
            if g.phase == MPS_PROF:
                w = by_gid[g.gid]
                self._store_estimates(g, w.jids, w.ests)
                prof_gs.append(g)
            else:
                self.on_phase_end(g)
        return self.collect_repartitions(prof_gs, overhead=True)

    def collect_completion(self, items):
        """Collect-mode twin of :meth:`on_completion_batch`: emptied GPUs
        go IDLE now; re-optimizations of GPUs that keep running jobs are
        returned as pending decisions for the fused solve."""
        repart = [g for g, _ in items if g.jobs and g.phase == MIG_RUN]
        for g, _ in items:
            if not g.jobs:
                g.phase = IDLE
                g.partition = ()
        return self.collect_repartitions(repart, overhead=True)

    def on_completion(self, g: GPU, job: Job):
        # re-optimize with known profiles (no new MPS sweep needed)
        if g.jobs and g.phase == MIG_RUN:
            self.repartition(g, overhead=True)
        elif not g.jobs:
            g.phase = IDLE
            g.partition = ()

    def on_completion_batch(self, items):
        """Same-tick completions: one batched Algorithm-1 pass re-optimizes
        every affected GPU that keeps running jobs (equivalent to the
        per-GPU :meth:`on_completion` reactions — completions in a batch
        land on distinct GPUs, so the reactions are independent)."""
        repart = [g for g, _ in items if g.jobs and g.phase == MIG_RUN]
        for g, _ in items:
            if not g.jobs:
                g.phase = IDLE
                g.partition = ()
        if repart:
            self.repartition_many(repart, overhead=True)

    def on_fault_evict(self, g: GPU):
        """A fault killed some residents mid-flight: re-optimize the
        surviving slice layout exactly like a completion does (survivors
        already have profiles; no new MPS sweep).  A GPU caught outside its
        MIG run (checkpointing / profiling) keeps its in-flight phase — the
        pipeline re-converges on its own."""
        if g.jobs and g.phase == MIG_RUN:
            self.repartition(g, overhead=True)
        elif not g.jobs:
            g.phase = IDLE
            g.partition = ()

    # ------------------------------------------------------------ profiling

    def begin_profiling(self, g: GPU):
        """Checkpoint whatever is running, then open the MPS window.  A
        freshly-started GPU (no job had a slice yet) has zero dead time and
        transitions straight to MPS_PROF."""
        sim = self.sim
        g.advance(sim.t)
        dead = g.ckpt_duration() if any(
            rj.slice_size for rj in g.jobs.values()) else 0.0
        g.phase = CKPT
        g.phase_end = sim.t + dead
        g.needs_profile = True
        for rj in g.jobs.values():
            rj.slice_size = None
        g._spd_dirty = True
        if dead == 0.0:
            # the caller finalizes the GPU once afterwards; suppress the
            # redundant event scheduling here
            sim.end_phase(g, schedule=False)

    def measure_and_partition(self, g: GPU):
        jids, profs, qos = self._mix(g)
        prof = self.sim.prof
        t0 = time.perf_counter() if prof is not None else 0.0
        mps_mat = self._measure(g, profs)
        ests = g.estimator.estimate(profs, mps_mat, qos=qos, prof=prof)
        if prof is not None:
            prof["estimator_s"] += time.perf_counter() - t0
        self._store_estimates(g, jids, ests)
        self.repartition(g, overhead=True)

    def _mix(self, g: GPU):
        """The co-location group on ``g``: (jids, progress profiles, QoS)."""
        sim = self.sim
        jids = list(g.jobs)
        profs = [rj.job.profile_at(1.0 - rj.job.remaining / rj.job.work)
                 for rj in g.jobs.values()]
        qos = [sim.jobs[j].qos_min_slice for j in jids]
        return jids, profs, qos

    def _measure(self, g: GPU, profs):
        """The MPS measurement for ``g``'s window (None for estimators that
        do not consume one).  Draws measurement noise from the simulator's
        dedicated stream — call in event order."""
        sim = self.sim
        if not getattr(g.estimator, "needs_mps", False):
            return None
        # thread the simulator's noise stream so every profiling window
        # draws fresh measurement noise (Fig 14 sensitivity) without
        # disturbing the main RNG's failure-injection schedule
        prof = sim.prof
        if prof is None:
            return g.estimator.measure_mps(
                profs, noise_sigma=sim.cfg.mps_noise_sigma, rng=sim.noise_rng)
        with timed(prof, "mps_measure_s", "mps.measure"):
            return g.estimator.measure_mps(
                profs, noise_sigma=sim.cfg.mps_noise_sigma, rng=sim.noise_rng)

    def _store_estimates(self, g: GPU, jids, ests):
        """Record the estimator's output on the GPU (and the shared
        multi-instance profile cache).  Subclasses hook here to keep their
        own profile bookkeeping, so the fused batch path sees it too."""
        sim = self.sim
        if sim._est_hooks:
            # estimator-fault corruption point + graceful degradation: the
            # sanitizer runs before anything is cached, so last-known-good
            # lookups see the previous window's estimates
            ests = [self.sanitize_estimate(g, jid, est)
                    for jid, est in zip(jids,
                                        sim.filter_estimates(g, jids, ests))]
        for jid, est in zip(jids, ests):
            g.estimates[jid] = est
            grp = sim.jobs[jid].mi_group
            if grp is not None:
                sim.profile_cache[(grp, g.space.name)] = est

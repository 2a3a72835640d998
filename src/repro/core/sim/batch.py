"""Replica-batched engine: lockstep execution of many sim replicas.

A parameter sweep, a seed ensemble or an RL rollout wants B *independent*
simulations — same fleet shape, different seeds / policies / knobs.  Run
serially, each replica pays the per-event Python overhead alone and every
estimator forward / Algorithm-1 solve ships one request.  :class:`BatchSim`
advances all B replicas in lockstep rounds instead:

* each replica's event heap is drained through
  ``ClusterSim.run_until_collect()`` — the scalar engine's own hoisted
  hot loop, run to the replica's next *collectible* tick, so each round's
  frontier is one pending decision batch per live replica (finished
  replicas are masked out) and non-decision events cost exactly what the
  scalar engine pays for them;
* ticks whose policy work is fusable come back as pending objects
  (:class:`~repro.core.sim.engine.PendingPhaseEnd` /
  :class:`~repro.core.sim.engine.PendingCompletion`) instead of being
  processed inline, and the round funnels the work of ALL replicas through
  the fused services:

  - **stage A** — every collected MPS window, grouped by estimator object,
    gets its noise-free MPS matrix from one stacked fixed-point pass
    (``PerfModel.mps_matrices``; the noise was drawn at collect time) and
    goes through one ``estimate_batch`` call: a single stacked
    ``(sum B_i, levels, jobs)`` predictor forward for the whole round;
  - **stage B** — each pending resumes its tick (store estimates, run
    non-profiling transitions) and surrenders its repartition decisions;
  - **stage C** — decisions grouped by (partition space, power model,
    objective) solve through one stacked-DP ``optimize_partition_batch``
    per group, with the scalar ``optimize_partition`` fallback per
    infeasible element and the policy's own ``choose_partition`` for
    policies that override it;
  - **stage D** — each pending applies its solved choices and finalizes,
    completing the tick exactly as the scalar engine would.

Bit-identity: replicas share nothing mutable but deterministic pure caches
(optimizer memo, space feasibility caches, the perf model's MPS memo)
whose values are order-independent, every noise draw happens at collect
time inside its own replica in event order, and the fused services are
element-exact twins of their scalar counterparts — so each replica's
metrics are bit-identical to running it alone through
``ClusterSim.run()``.  ``tests/test_batch.py`` holds that property over
the golden traces.

Cross-replica fusion needs shared spec objects: build replicas against the
same ``GPUSpec`` list (the sweep runner's fleet cache already does this)
or grouping keys degenerate to one group per replica — still correct,
just unfused.

The :meth:`BatchSim.step` / :meth:`BatchSim.observe` pair is the
vectorized-environment surface for learned scheduling (a future GPUJobEnv):
step advances every live replica to its next decision point (the natural
environment granularity — between decisions there is nothing to act on),
observe exports replica-major ``(B, G)`` fleet scalars and ``(B, G, S)``
resident columns.
"""
from __future__ import annotations

from typing import TYPE_CHECKING, Dict, List, Optional, Sequence

import numpy as np

from repro.core.metrics import TraceMetrics
from repro.core.optimizer import optimize_partition, optimize_partition_batch
from repro.core.sim.policies.base import EstimateWork, Policy, RepartDecision
from repro.core.sim.prof import new_prof, split, timed
from repro.core.sim.soa import settle_rows

if TYPE_CHECKING:  # pragma: no cover - import cycle guard
    from repro.core.sim.engine import ClusterSim
    from repro.core.sim.gpu import GPU


class BatchFleetState:
    """Replica-major view over B fleets: rows are ``(B, G)`` flattened.

    Per-GPU state stays canonical on the :class:`GPU` objects (exactly the
    single-replica SoA contract in ``core/sim/soa.py``); this class owns
    the cross-replica batch barriers: the per-replica-clock settle and the
    ``(B, G)`` / ``(B, G, S)`` array exports for vectorized consumers.
    """

    __slots__ = ("sims", "gpus", "b", "g", "idle_w")

    def __init__(self, sims: Sequence["ClusterSim"]):
        if not sims:
            raise ValueError("BatchFleetState needs at least one replica")
        self.sims = list(sims)
        g0 = len(self.sims[0].gpus)
        for s in self.sims[1:]:
            if len(s.gpus) != g0:
                raise ValueError(
                    f"replica fleet shapes differ: {len(s.gpus)} vs {g0} "
                    f"GPUs (BatchSim requires one shape across the batch)")
        self.b = len(self.sims)
        self.g = g0
        # replica-major flatten: row b*G + g is replica b's GPU g
        self.gpus: List["GPU"] = [g for s in self.sims for g in s.gpus]
        self.idle_w = np.array([g._idle_w for g in self.gpus])

    def settle_all(self,
                   free_min: Optional[int] = None,
                   occ_min: Optional[int] = None) -> None:
        """Settle every replica's fleet to that replica's clock in one
        ``settle_rows`` pass over all ``B*G`` rows (per-row target times).
        Work-aggregate shifts land on each row's own replica, in gid order
        within it — bit-identical to per-replica ``settle_all`` calls."""
        ts: List[float] = []
        for s in self.sims:
            ts.extend([s.t] * len(s.gpus))
        settle_rows(self.gpus, ts, idle_w=self.idle_w,
                    free_min=free_min, occ_min=occ_min)

    def scalars(self) -> Dict[str, np.ndarray]:
        """Snapshot the per-GPU fleet scalars as ``(B, G)`` arrays."""
        n = len(self.gpus)
        shape = (self.b, self.g)
        out = {}
        for name in ("last_update", "down_until", "energy_j"):
            out[name] = np.fromiter(
                (getattr(g, name) for g in self.gpus),
                dtype=np.float64, count=n).reshape(shape)
        return out

    def resident_matrix(self) -> Dict[str, np.ndarray]:
        """Export the per-resident SoA columns as replica-major
        ``(B, G, S)`` arrays (``S`` = widest resident count anywhere in the
        batch; ``mask`` marks occupied slots).  Read-only bridge for
        vectorized consumers — never feeds back into simulation state."""
        gpus = self.gpus
        s = max((len(g._rjobs) for g in gpus), default=0)
        shape = (self.b, self.g, max(s, 1))
        speed = np.zeros(shape)
        ck_t = np.zeros(shape)
        ck_w = np.zeros(shape)
        remaining = np.zeros(shape)
        mask = np.zeros(shape, dtype=bool)
        for i, g in enumerate(gpus):
            k = len(g._rjobs)
            if not k:
                continue
            b, gg = divmod(i, self.g)
            speed[b, gg, :k] = g._spd
            ck_t[b, gg, :k] = g._ckt
            ck_w[b, gg, :k] = g._ckw
            # replica-major gather — MS110 recognizes this subscript-store
            # pattern in batch.py; <=7 slots per row (the soa.py bound)
            remaining[b, gg, :k] = [rj.job.remaining for rj in g._rjobs]
            mask[b, gg, :k] = True
        return {"speed": speed, "since_ckpt_t": ck_t,
                "since_ckpt_work": ck_w, "remaining": remaining,
                "mask": mask}


class BatchSim:
    """Advance B independent :class:`ClusterSim` replicas in lockstep.

    Replicas must share one fleet shape (GPU count); seeds, policies,
    placers, objectives and workloads may differ per replica.  Callers own
    job-list isolation (each replica needs its own ``Job`` objects, as
    ``simulate`` guarantees via deepcopy).
    """

    def __init__(self, sims: Sequence["ClusterSim"]):
        self.sims: List["ClusterSim"] = list(sims)
        self.fleet_state = BatchFleetState(self.sims)
        self.done: List[bool] = [False] * len(self.sims)
        self.rounds = 0

    @property
    def b(self) -> int:
        return len(self.sims)

    # ------------------------------------------------------------ stepping

    def step(self) -> bool:
        """One lockstep round: every live replica drains its event heap to
        its next collectible tick (``ClusterSim.run_until_collect`` — the
        scalar hot loop, so non-decision events cost exactly what they cost
        the scalar engine) and surrenders one pending batch; the fusable
        work of all of them then runs through the staged services.  A
        replica with no pending left is done.  Returns True while any
        replica remains live."""
        pendings = []
        for i, sim in enumerate(self.sims):
            if self.done[i]:
                continue
            prof = sim.prof
            if prof is None:
                r = sim.run_until_collect()
            else:
                with timed(prof, "collect_s", "sim.collect"):
                    r = sim.run_until_collect()
            if r is None:
                self.done[i] = True
            else:
                pendings.append(r)
        if pendings:
            # stage A: one stacked predictor forward per estimator object
            self._fuse_estimates(
                [w for p in pendings if p.kind == "phase_end"
                 for w in p.work])
            # stage B: resume each tick, collect its pending decisions
            decisions: List[RepartDecision] = []
            for p in pendings:
                prof = p.sim.prof
                if prof is None:
                    decisions.extend(p.apply())
                else:
                    with timed(prof, "round_apply_s", "batch.apply"):
                        decisions.extend(p.apply())
            # stage C: fused Algorithm-1 solves across replicas
            self._solve_decisions(decisions)
            # stage D: apply + finalize, completing each replica's tick
            for p in pendings:
                prof = p.sim.prof
                if prof is None:
                    p.finish()
                else:
                    with timed(prof, "round_apply_s", "batch.apply"):
                        p.finish()
        self.rounds += 1
        return not all(self.done)

    def run(self) -> List[TraceMetrics]:
        """Drive every replica to completion; per-replica metrics in input
        order, each bit-identical to ``ClusterSim.run()`` on that replica
        alone."""
        while self.step():
            pass
        self.settle()
        return [sim.finish(settle=False) for sim in self.sims]

    def settle(self) -> None:
        """Settle every replica's fleet accounting to its current clock
        (cheap, idempotent at a fixed clock; call before reading
        :meth:`observe` progress or computing metrics).  Note extra
        mid-flight settles split energy-integration intervals and so can
        move ``energy_j`` by float rounding relative to an unobserved run;
        :meth:`run` settles only once, at the end, like the scalar engine."""
        self.fleet_state.settle_all()

    # ----------------------------------------------------- fused services

    @staticmethod
    def _fuse_estimates(works: List[EstimateWork]) -> None:
        """Stage A: fill ``w.mat`` and ``w.ests`` for every collected MPS
        window, per estimator object one stacked MPS fixed-point pass and
        one ``estimate_batch`` call.  The noise draws already happened at
        collect time inside each replica, in event order; the fixed point
        and the forward are pure, so cross-replica fusion is exact.

        Profiled replicas get the stage's time, the estimator's buckets
        and the stage's counters in proportion to the windows each sent."""
        if not works:
            return
        owners = [w.prof for w in works]
        if not any(owners):
            _estimate_groups(works, None)
            return
        scratch = new_prof()
        with timed(scratch, "fused_estimator_s", "batch.stage_a"):
            _estimate_groups(works, scratch)
        split(scratch, owners)

    @staticmethod
    def _solve_decisions(decisions: List[RepartDecision]) -> None:
        """Stage C: fill ``d.choice`` for every pending repartition.

        Decisions are grouped by (partition space, power model, objective
        identity) — the complete input signature of the stacked DP — so one
        ``optimize_partition_batch`` serves each group across replicas,
        with the scalar ``optimize_partition`` fallback for elements whose
        feasible-first pass returns None: exactly
        ``Policy.choose_partition_batch``, element for element.  A policy
        class that overrides ``choose_partition`` keeps its own per-decision
        logic (same guard the scalar batch path applies).

        Profiled replicas get the stage's time and counters in proportion
        to the decisions each sent."""
        if not decisions:
            return
        owners = [d.policy.sim.prof for d in decisions]
        if not any(owners):
            _solve_groups(decisions, None)
            return
        scratch = new_prof()
        with timed(scratch, "fused_alg1_s", "batch.stage_c"):
            _solve_groups(decisions, scratch)
        split(scratch, owners)

    # --------------------------------------------------------- observation

    def observe(self) -> Dict[str, np.ndarray]:
        """Replica-major snapshot for vectorized consumers (the GPUJobEnv
        surface): per-replica scalars (clock, queue depth, completions,
        done mask), ``(B, G)`` fleet scalars and ``(B, G, S)`` resident
        columns.  Pure read — call :meth:`settle` first when progress must
        be current to each replica's clock."""
        out: Dict[str, np.ndarray] = {
            "t": np.array([s.t for s in self.sims]),
            "queue_len": np.array([len(s.queue) for s in self.sims]),
            "completed": np.array([len(s.completed) for s in self.sims]),
            "done": np.array(self.done, dtype=bool),
        }
        out.update(self.fleet_state.scalars())
        out.update(self.fleet_state.resident_matrix())
        return out


def _estimate_groups(works: List[EstimateWork], prof) -> None:
    by_est: Dict[int, List[EstimateWork]] = {}
    for w in works:
        by_est.setdefault(id(w.g.estimator), []).append(w)
    for group in by_est.values():
        est = group[0].g.estimator
        todo = [w for w in group if w.mat is None]
        if todo and getattr(est, "needs_mps", False):
            _measure_group(est, todo, prof)
        requests = [(w.profs, w.mat, w.qos) for w in group]
        for w, ests in zip(group, est.estimate_batch(requests, prof=prof)):
            w.ests = ests
    if prof is not None:
        prof["stage_a_runs"] += 1.0
        prof["stage_a_forwards"] += len(by_est)


def _measure_group(est, works: List[EstimateWork], prof) -> None:
    """The MPS probe of an estimator's windows that have no matrix yet,
    less the noise drawn at collect time: every noise-free matrix through
    one stacked fixed-point pass (``UNetEstimator.measure_mps_many``)."""
    mixes, noises = [w.profs for w in works], [w.noise for w in works]
    if prof is None:
        mats = est.measure_mps_many(mixes, noises)
    else:
        with timed(prof, "mps_measure_s", "mps.measure"):
            mats = est.measure_mps_many(mixes, noises, prof)
    for w, m in zip(works, mats):
        w.mat = m


def _solve_groups(decisions: List[RepartDecision], prof) -> None:
    groups: Dict[tuple, List[RepartDecision]] = {}
    for d in decisions:
        pol = d.policy
        if type(pol).choose_partition is not Policy.choose_partition:
            d.choice = pol.choose_partition(d.speeds, space=d.g.space,
                                            power=d.g.power)
            continue
        key = (id(d.g.space), id(d.g.power), pol.objective.memo_key())
        groups.setdefault(key, []).append(d)
    for group in groups.values():
        d0 = group[0]
        space, power = d0.g.space, d0.g.power
        objective = d0.policy.objective
        first = optimize_partition_batch(
            space, [d.speeds for d in group], require_feasible=True,
            objective=objective, power=power)
        for d, c in zip(group, first):
            d.choice = c if c is not None else optimize_partition(
                space, d.speeds, objective=objective, power=power)
    if prof is not None:
        prof["stage_c_runs"] += 1.0
        prof["stage_c_solves"] += len(groups)

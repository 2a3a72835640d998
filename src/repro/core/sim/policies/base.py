"""Scheduling-policy plugin layer.

A :class:`Policy` owns every scheduling decision the cluster makes; the
engine (``repro.core.sim.engine``) owns time, events and accounting.  The
hooks mirror the lifecycle of a job:

* ``admit``          — queue discipline (default FCFS; override for e.g. SRPT)
* ``placement_candidates`` — feasibility: the GPUs a queued job *may* land on
  under this policy's co-location rules (default: the engine's shared
  job-count / memory / spare-slice checks)
* ``pick_gpu``       — placement: delegates the choice among those
  candidates to the pluggable :class:`~repro.core.sim.placement.Placer`
  named by ``SimConfig.placer`` (``least-loaded`` by default)
* ``on_place``       — set the GPU's phase/partition after a job lands
* ``on_phase_end``   — a CKPT/MPS_PROF timer expired; advance the state machine
* ``on_phase_end_batch`` — several timers expired at one tick (the engine
  coalesces them); default replays ``on_phase_end`` sequentially
* ``on_completion``  — a job finished; reshape what is left on the GPU
* ``mps_phase_speeds`` — how co-located jobs progress during an MPS phase

New policies subclass :class:`Policy`, set ``name``, and decorate with
:func:`register_policy`; they are then reachable from ``SimConfig.policy``,
``repro.launch.cluster --policy`` and the benchmark harness with no engine
changes.  See ``miso_frag.py`` / ``srpt.py`` for ~30-line examples.

The *goal* of the partition search is a third pluggable layer: the
:class:`~repro.core.sim.objectives.Objective` named by
``SimConfig.objective`` (default ``throughput``, the paper's Eq. 2–4 and
bit-identical to the historical optimizer; ``energy`` / ``edp`` trade
throughput for watts).  ``choose_partition`` threads it — plus the target
GPU's per-kind power model — into every Algorithm-1 call.
"""
from __future__ import annotations

import time
from abc import ABC, abstractmethod
from typing import TYPE_CHECKING, Dict, List, Optional, Sequence, Tuple, Type

from repro.core.estimators import OracleEstimator
from repro.core.jobs import Job, JobProfile
from repro.core.optimizer import optimize_partition, optimize_partition_batch
from repro.core.perfmodel import MPS_LEVELS
from repro.core.sim.gpu import CKPT, GPU, IDLE, MIG_RUN
from repro.core.sim.objectives import get_objective
from repro.core.sim.placement import get_placer

if TYPE_CHECKING:  # pragma: no cover - import cycle guard
    from repro.core.sim.engine import ClusterSim

_REGISTRY: Dict[str, Type["Policy"]] = {}


def register_policy(cls: Type["Policy"]) -> Type["Policy"]:
    """Class decorator: make ``cls`` reachable as ``SimConfig.policy=name``."""
    if not getattr(cls, "name", None):
        raise ValueError(f"{cls.__name__} must define a non-empty `name`")
    if cls.name in _REGISTRY:
        raise ValueError(f"duplicate policy name {cls.name!r} "
                         f"({_REGISTRY[cls.name].__name__} vs {cls.__name__})")
    _REGISTRY[cls.name] = cls
    return cls


def get_policy(name: str) -> Type["Policy"]:
    try:
        return _REGISTRY[name]
    except KeyError:
        raise ValueError(
            f"unknown scheduling policy {name!r}; "
            f"available: {', '.join(available_policies())}") from None


def available_policies() -> List[str]:
    return sorted(_REGISTRY)


class EstimateWork:
    """One MPS profiling window collected for a fused estimator pass.

    Produced by :meth:`Policy.collect_phase_end`; the owner (BatchSim)
    groups items by estimator object, measures the MPS matrix of every
    window of an estimator that consumes one (one stacked fixed-point pass
    per group) and fills ``ests`` with one ``estimate_batch`` call per
    group — one stacked predictor forward for every same-tick window
    across every replica."""

    __slots__ = ("g", "jids", "profs", "qos", "mat", "ests", "prof",
                 "noise")

    def __init__(self, g: GPU, jids, profs, qos, mat, prof=None,
                 noise=None):
        self.g = g
        self.jids = jids
        self.profs = profs
        self.qos = qos
        self.mat = mat          # measured MPS matrix (None: estimator-only,
                                # or until the owner measures it in stage A)
        self.ests: Optional[list] = None   # filled by the owner (stage A)
        self.prof = prof        # the replica's profile buckets (None: off)
        self.noise = noise      # measurement noise drawn at collect time
                                # for ``mat`` (None: none)


class RepartDecision:
    """One pending Algorithm-1 decision collected for a fused solve.

    Produced by :meth:`Policy.collect_repartitions`; the owner groups
    decisions by (space, power, objective) and fills ``choice`` through
    ``optimize_partition_batch`` —  exactly the stacked DP
    ``choose_partition_batch`` runs, so the solved choice is bit-identical
    to the scalar ``repartition`` path.  ``Policy.apply_decision`` then
    applies it."""

    __slots__ = ("policy", "g", "jids", "speeds", "overhead", "choice")

    def __init__(self, policy: "Policy", g: GPU, jids, speeds,
                 overhead: bool):
        self.policy = policy
        self.g = g
        self.jids = jids
        self.speeds = speeds
        self.overhead = overhead
        self.choice = None      # filled by the owner (stage C)


class Policy(ABC):
    """Base class for scheduling policies (one instance per simulation)."""

    name: str = ""

    def __init__(self, sim: "ClusterSim"):
        self.sim = sim
        self.placer = get_placer(sim.cfg.placer)(sim)
        self.objective = get_objective(sim.cfg.objective)()
        self.indexable = self._index_exact()
        # blocked-head cache: (head jid, index version) when the last admit
        # stalled — feasibility depends only on resident sets and the
        # up-set, both versioned by the fleet index, so an unchanged pair
        # means the head still fits nowhere and the queue scan is skipped
        self._blocked: Optional[Tuple[int, int]] = None
        # 3-level MPS mean memo: profiles are immutable and drawn from a
        # bounded pool, so the mean speed list for a (perf model, profile
        # mix) pair never changes; the profile tuple is pinned in the value
        # so the id key cannot be recycled.  Callers never mutate the list.
        self._mps_mean_cache: Dict[tuple, tuple] = {}

    def _index_exact(self) -> bool:
        """Whether ``placement_candidates`` is faithfully described by the
        (``admit_ok``, ``admit_caps``) fleet-index contract: the class
        providing ``placement_candidates`` must itself provide ``admit_ok``
        (declaring the pair in sync).  A subclass that overrides the
        candidate rule alone falls back to the materialized scan instead of
        silently getting the base contract's candidates."""
        for klass in type(self).__mro__:
            if "placement_candidates" in vars(klass):
                return klass is Policy or "admit_ok" in vars(klass)
        return True

    # ------------------------------------------------------ queue discipline

    def admit(self):
        """FCFS: place queue-head jobs until the head does not fit.  A head
        recorded as blocked stays blocked until the fleet index version
        moves (placement, completion, eviction, failure, repair) — FCFS
        never looks past it, so the whole call short-circuits."""
        sim = self.sim
        sim._sync_up()                   # repair promotions bump the version
        if self._blocked is not None and sim.queue \
                and self._blocked == (sim.queue[0], sim.index.version):
            return
        while sim.queue:
            job = sim.jobs[sim.queue[0]]
            g = self.pick_gpu(job)
            if g is None:
                self._blocked = (job.jid, sim.index.version)
                return
            sim.queue.pop(0)
            sim.place(g, job)
        self._blocked = None

    # ---------------------------------------------------------- placement

    def placement_candidates(self, job: Job) -> List[GPU]:
        """GPUs ``job`` may land on under this policy's co-location rules.
        Default: the shared-MIG admission every partitioning policy uses —
        in-service, under the space's job cap, memory-feasible and passing
        the exact spare-slice check.  Policies with different co-location
        semantics (NoPart, MPS-only, OptSta) override *this* — together
        with the (``admit_ok``, ``admit_caps``) index contract below — so
        every placer composes with them."""
        sim = self.sim
        return [g for g in sim.up_gpus()
                if g.sched_ok and len(g.jobs) < g.space.max_jobs
                and sim.mem_ok(g, job) and sim.spare_slice_ok(g, job)]

    # The same admission as a fleet-index query, so placers can enumerate
    # feasible GPUs from the index instead of scanning the fleet: the index
    # applies ``admit_caps`` (resident-count cap; prune=True additionally
    # skips buckets whose max addable slice cannot cover the job — exactly
    # the spare-slice check for memory-monotone menus) and ``admit_ok``
    # settles whatever the buckets cannot.

    def admit_ok(self, g: GPU, job: Job) -> bool:
        """Per-GPU residue of ``placement_candidates`` once the index has
        applied this policy's caps.  Default: the memory check, plus the
        exact spare-slice check only where bucket pruning could not prove
        it (non-monotone menus, ``g._max_add is None``)."""
        sim = self.sim
        return sim.mem_ok(g, job) and (g._max_add is not None
                                       or sim.spare_slice_ok(g, job))

    def admit_caps(self, job: Job) -> Tuple[Optional[int], bool]:
        """(max resident count, prune by slice-requirement level) for the
        index query.  None = each kind's ``space.max_jobs - 1``."""
        return None, True

    def pick_gpu(self, job: Job) -> Optional[GPU]:
        """Choose a GPU for ``job`` (None leaves it queued): the pluggable
        placer ranks this policy's feasible candidates (straight off the
        fleet index wherever the policy's rule is index-expressible)."""
        prof = self.sim.prof
        if prof is None:
            return self.placer.pick_for(job, self)
        t0 = time.perf_counter()
        g = self.placer.pick_for(job, self)
        prof["placement_s"] += time.perf_counter() - t0
        return g

    # ------------------------------------------------------------ lifecycle

    @abstractmethod
    def on_place(self, g: GPU, job: Job):
        """``job`` was just added to ``g.jobs``; set phase / slices."""

    def on_phase_end(self, g: GPU):
        """A CKPT or MPS_PROF window on ``g`` ended (no-op by default —
        only profiling policies drive multi-step phase chains)."""

    def on_phase_end_batch(self, gs: Sequence[GPU]):
        """Several GPUs' windows ended at the same simulation tick (the
        engine drains the heap for same-tick timers).  Default: process
        sequentially in event order — results are identical because phase
        ends are cross-GPU independent.  Profiling policies override this to
        fuse the per-GPU estimator forwards into one batched inference."""
        for g in gs:
            self.on_phase_end(g)

    @abstractmethod
    def on_completion(self, g: GPU, job: Job):
        """``job`` finished and was removed from ``g.jobs``."""

    def on_completion_batch(self, items: Sequence[tuple]):
        """Several jobs finished at the same simulation tick, on distinct
        GPUs (``items`` is (gpu, job) pairs in event order; the engine
        drains the heap for same-tick completions).  Default: sequential —
        correct for policies whose completion reaction is local to the
        affected GPU.  MISO-family policies override this to fuse the
        re-optimizations into one batched Algorithm-1 pass."""
        for g, job in items:
            self.on_completion(g, job)

    # ------------------------------------------- collect/apply (BatchSim)
    # The staged twin of the batch hooks above, used by the replica-batched
    # engine (core/sim/batch.py): instead of estimating and solving inside
    # the hook, a policy *collects* its estimator windows and Algorithm-1
    # decisions so the owner can fuse them across replicas.  Contract: a
    # ``collect_*`` hook either returns None having touched NOTHING (the
    # engine falls back to the scalar batch hook), or performs all of its
    # non-fusable side effects and returns the collected work — never both.
    # The default implementations return None: a policy that doesn't opt in
    # simply runs its scalar hooks inside the batched engine, which keeps
    # the bit-identity contract trivially.

    def collect_phase_end(self, gs: Sequence[GPU]
                          ) -> Optional[List[EstimateWork]]:
        """Collect this tick's estimator windows instead of running them.
        None (default) = no fusable work: the engine processes the tick via
        ``on_phase_end_batch``.  A non-None return must be non-empty; the
        engine will call :meth:`apply_phase_end` with the estimated work."""
        return None

    def apply_phase_end(self, gs: Sequence[GPU],
                        work: Sequence[EstimateWork]
                        ) -> List[RepartDecision]:
        """Resume the phase-end tick once ``work[i].ests`` are filled:
        store estimates / run non-profiling transitions in scalar hook
        order, and return the repartition decisions still to be solved."""
        raise NotImplementedError(
            f"{type(self).__name__}.collect_phase_end returned work but "
            f"apply_phase_end is not implemented")

    def collect_completion(self, items: Sequence[tuple]
                           ) -> Optional[List[RepartDecision]]:
        """Collect this tick's completion-triggered repartitions.  None
        (default) = not supported: the engine falls back to
        ``on_completion_batch``.  A supporting policy performs its
        non-repartition side effects and returns the (possibly empty)
        decision list."""
        return None

    def collect_repartitions(self, gs: Sequence[GPU], overhead: bool = False
                             ) -> List[RepartDecision]:
        """Collect-mode twin of :meth:`repartition_many`: emptied GPUs go
        IDLE immediately (no optimizer run, exactly as the scalar path);
        the rest become pending decisions carrying their slice-speed
        estimates.  The solved choices are applied by
        :meth:`apply_decision` in collection order — cross-GPU independent,
        so any order is bit-identical to the scalar loop."""
        out: List[RepartDecision] = []
        for g in gs:
            jids = list(g.jobs)
            if not jids:
                g.phase = IDLE
                g.partition = ()
                continue
            out.append(RepartDecision(self, g, jids,
                                      self.partition_speeds(g, jids),
                                      overhead))
        return out

    def apply_decision(self, d: RepartDecision) -> None:
        """Apply one solved repartition decision (stage D)."""
        self._apply_choice(d.g, d.jids, d.choice, d.overhead)

    def on_fault_evict(self, g: GPU):
        """Fault injection just killed *some* residents of ``g``
        (``ClusterSim.crash_jobs``); the GPU itself stays in service.
        Reshape what is left.  Default: only reset an emptied GPU —
        partitioning policies override to re-optimize the survivors."""
        if not g.jobs:
            g.phase = IDLE
            g.partition = ()

    # ------------------------------------------------------------ MPS model

    def mps_phase_speeds(self, profs: Sequence[JobProfile],
                         g: Optional[GPU] = None):
        """Per-job progress rates while ``g`` is in an MPS phase.  The
        profiling sweep runs 3 levels back-to-back, so use the mean
        (accumulated in level order, matching np.mean's axis-0 reduction
        bit-for-bit).  ``g=None`` falls back to the homogeneous default
        perf model."""
        pm = g.pm if g is not None else self.sim.pm
        key = (id(pm),) + tuple(id(p) for p in profs)
        hit = self._mps_mean_cache.get(key)
        if hit is not None and all(a is b for a, b in zip(hit[0], profs)):
            return hit[1]
        m0, m1, m2 = (pm.mps_speeds(profs, lv) for lv in MPS_LEVELS)
        out = [((a + b) + c) / 3.0 for a, b, c in zip(m0, m1, m2)]
        if len(self._mps_mean_cache) >= 65536:
            self._mps_mean_cache.pop(next(iter(self._mps_mean_cache)))
        self._mps_mean_cache[key] = (tuple(profs), out)
        return out

    # -------------------------------------------------- partition machinery
    # Shared by every MIG-partitioning policy (miso / oracle / variants).

    def partition_speeds(self, g: GPU, jids: Sequence[int]) -> List[Dict[int, float]]:
        """Per-job slice-speed estimates used by the optimizer; the default
        reads the estimates cached on the GPU at profiling time."""
        return [g.estimates.get(j, {g.space.full_size: 1.0})
                for j in jids]

    def sanitize_estimate(self, g: GPU, jid: int,
                          est: Dict[int, float]) -> Dict[int, float]:
        """Graceful degradation for estimator faults: a slice-speed map is
        valid iff every value is a finite fraction in [0, 1.5] (slight
        super-linearity tolerated) and at least one slice shows progress.
        Garbage degrades to the job's last-known-good estimate when one is
        cached, else to the oracle's ground-truth slice speeds — Algorithm 1
        never sees NaNs, negatives or an all-zero map."""
        ok = True
        mx = 0.0
        for v in est.values():
            if not (0.0 <= v <= 1.5):        # NaN fails this comparison too
                ok = False
                break
            if v > mx:
                mx = v
        if ok and mx > 0.0:
            return est
        prev = g.estimates.get(jid)
        if prev is not None:
            return prev
        job = self.sim.jobs[jid]
        prof = job.profile_at(1.0 - job.remaining / job.work)
        return OracleEstimator(g.pm).estimate(
            [prof], qos=[job.qos_min_slice])[0]

    def choose_partition(self, speeds: Sequence[Dict[int, float]],
                         space=None, power=None):
        """Algorithm 1 under the configured objective: feasible-first, fall
        back to best-effort.  ``space`` is the target GPU's partition space
        (defaults to the homogeneous one); ``power`` its per-kind
        :class:`~repro.core.fleet.PowerModel`, consumed by energy-aware
        objectives (``None`` = reference a100)."""
        space = space if space is not None else self.sim.space
        return optimize_partition(space, speeds, require_feasible=True,
                                  objective=self.objective, power=power) \
            or optimize_partition(space, speeds,
                                  objective=self.objective, power=power)

    def choose_partition_batch(self, speeds_list, space=None, power=None):
        """Algorithm 1 for several decisions against one space at once,
        via the stacked DP (``optimize_partition_batch``) — element i equals
        ``choose_partition(speeds_list[i], space)`` exactly.  Policies that
        override ``choose_partition`` fall back to their per-decision logic
        automatically."""
        space = space if space is not None else self.sim.space
        if type(self).choose_partition is not Policy.choose_partition:
            return [self.choose_partition(sp, space=space, power=power)
                    for sp in speeds_list]
        first = optimize_partition_batch(space, speeds_list,
                                         require_feasible=True,
                                         objective=self.objective, power=power)
        return [c if c is not None else
                optimize_partition(space, sp,
                                   objective=self.objective, power=power)
                for c, sp in zip(first, speeds_list)]

    def repartition(self, g: GPU, overhead: bool = False):
        """Run the optimizer with current estimates and apply the partition;
        ``overhead=True`` charges a checkpoint+reconfigure window when the
        partition actually changes."""
        jids = list(g.jobs)
        if not jids:
            g.phase = IDLE
            g.partition = ()
            return
        prof = self.sim.prof
        if prof is None:
            choice = self.choose_partition(self.partition_speeds(g, jids),
                                           space=g.space, power=g.power)
        else:
            t0 = time.perf_counter()
            speeds = self.partition_speeds(g, jids)
            t1 = time.perf_counter()
            choice = self.choose_partition(speeds, space=g.space,
                                           power=g.power)
            t2 = time.perf_counter()
            prof["estimator_s"] += t1 - t0   # oracle runs its estimator here
            prof["alg1_s"] += t2 - t1
        self._apply_choice(g, jids, choice, overhead)

    def repartition_many(self, gs: Sequence[GPU], overhead: bool = False):
        """Repartition several GPUs in one batched Algorithm-1 pass (grouped
        by partition space + power model — one shared spec per kind, so the
        group key is stable).  Equivalent to calling :meth:`repartition` per
        GPU in order — used by the same-tick phase-end batch."""
        per_space: Dict[tuple, List] = {}
        for g in gs:
            jids = list(g.jobs)
            if not jids:
                g.phase = IDLE
                g.partition = ()
                continue
            per_space.setdefault((id(g.space), id(g.power)),
                                 []).append((g, jids))
        prof = self.sim.prof
        for items in per_space.values():
            g0 = items[0][0]
            if prof is None:
                choices = self.choose_partition_batch(
                    [self.partition_speeds(g, jids) for g, jids in items],
                    space=g0.space, power=g0.power)
            else:
                t0 = time.perf_counter()
                speeds = [self.partition_speeds(g, jids)
                          for g, jids in items]
                t1 = time.perf_counter()
                choices = self.choose_partition_batch(
                    speeds, space=g0.space, power=g0.power)
                t2 = time.perf_counter()
                # the prof clocks are metrics-only (sweep --profile) and
                # never feed simulation state, hence the MS107 suppression
                prof["estimator_s"] += t1 - t0  # misolint: disable=MS107 -- prof clock bucket, metrics-only
                prof["alg1_s"] += t2 - t1
            for (g, jids), choice in zip(items, choices):
                self._apply_choice(g, jids, choice, overhead)

    def _apply_choice(self, g: GPU, jids, choice, overhead: bool):
        old = tuple(rj.slice_size for rj in g.jobs.values())
        for jid, size in zip(jids, choice.partition):
            g.jobs[jid].slice_size = size
        g._spd_dirty = True
        g.partition = tuple(sorted(choice.partition, reverse=True))
        if overhead and old != tuple(choice.partition):
            g.phase = CKPT
            g.phase_end = self.sim.t + g.ckpt_duration()
            g.needs_profile = False
        else:
            g.phase = MIG_RUN

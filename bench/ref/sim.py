"""One replica of the cluster as a plain per-event loop.

It states the ``miso`` policy, ``least-loaded`` placement and the
``throughput`` objective (:data:`STATES`), and nothing else.  Each GPU has
its group's menu, memory and speed model, estimator and U-Net weights
(``ref.fleet``).  Work is in seconds of the reference GPU, so a GPU of
speed scale ``s`` progresses ``s`` times as fast as its own speeds say, in
the MPS window and on its slices alike.

Jobs queue first come, first served; the head goes to the GPU with
fewest jobs (lowest id on a tie, whatever its speed) among those that
can take it: fewer jobs than the largest partition of its menu holds,
the sum of footprints within the GPU's memory, and some partition of its
menu that gives every job a slice it fits.  A placement checkpoints the
GPU's jobs if they were running on slices, then opens an MPS window of
one level time per level, during which the jobs progress at their mean
MPS speed.  At its end the probe's matrix goes through the estimator and
Algorithm 1 picks a partition and assignment; a change of layout costs a
reconfiguration plus the largest job's checkpoint before the jobs run on
their slices at their true speeds.  A completion re-runs Algorithm 1 on
the estimates the GPU holds when it is running on slices, or leaves the
GPU idle when it is empty.

Two answers of the program are taken rather than made, because a float32
forward and a near-tie in Algorithm 1 may each go either way and a single
flip changes every later event of a replica:

* each MPS window is answered with the output the program's U-Net gave at
  that point, which is measured against this module's float64 forward of
  the reference's own probe matrix (``unet_gap``);
* each Algorithm-1 decision follows the program's partition, once it is
  checked to be a valid partition that scores as well as the best the
  enumeration finds, feasible first (``alg1_gap``).

A point at which the program did nothing, or acted on another set of
jobs, reads 1 in the gap of its kind.  Everything else (placement, phase
times, progress and completion) is this module's own.
"""
from __future__ import annotations

import collections
import heapq
import itertools
from typing import Dict, List, NamedTuple, Optional, Sequence, Tuple

import numpy as np

from ref.testbed import Profile, Testbed
from ref.unet import Estimator, forward

IDLE, CKPT, MPS, MIG = "idle", "ckpt", "mps", "mig"
ARRIVAL, TIMER, DONE = 0, 1, 2
#: the configuration's policy, placer and objective this module states
STATES = {"policy": "miso", "placer": "least-loaded",
          "objective": "throughput"}


class Job:
    __slots__ = ("jid", "prof", "arrival", "work", "remaining", "finish")

    def __init__(self, jid: int, prof: Profile, arrival: float, work: float):
        self.jid, self.prof = jid, prof
        self.arrival, self.work = arrival, work
        self.remaining = work
        self.finish: Optional[float] = None


class Kind(NamedTuple):
    """What the GPUs of one group run with."""
    tb: Testbed
    est: Estimator
    params: dict
    scale: float


class Gpu:
    def __init__(self, gid: int, kind: Kind):
        self.gid, self.kind = gid, kind
        self.phase = IDLE
        self.phase_end = 0.0
        self.reprobe = False          # the CKPT window leads into MPS
        self.jobs: List[Job] = []     # placement order
        self.slice: Dict[int, Optional[int]] = {}
        self.speed: Dict[int, float] = {}
        self.estimates: Dict[int, Dict[int, float]] = {}
        self.clock = 0.0
        self.stamp = 0


class ProgramRecord:
    """What the program did in one replica, by GPU and in order: the U-Net
    output of each MPS window and the partition of each decision."""

    def __init__(self, windows: Sequence[tuple], decisions: Sequence[tuple]):
        self.windows = collections.defaultdict(collections.deque)
        for gid, jids, mat, out in windows:
            self.windows[gid].append((tuple(jids), mat, out))
        self.decisions = collections.defaultdict(collections.deque)
        for gid, jids, part in decisions:
            self.decisions[gid].append((tuple(jids), tuple(part)))


class Replica:
    def __init__(self, kinds: Sequence[Kind], sim: dict,
                 jobs: Sequence[Job], record: ProgramRecord):
        """``kinds``: each GPU's, by GPU id."""
        self.kinds, self.cfg = list(kinds), sim
        self.jobs = {j.jid: j for j in jobs}
        self.record = record
        self.gpus: List[Gpu] = []
        self.queue: List[int] = []
        self.heap: list = []
        self.seq = itertools.count()
        self.t = 0.0
        self.done = 0
        self.unet_gap = 0.0
        self.alg1_gap = 0.0
        self.windows = 0
        self.decisions = 0

    # ------------------------------------------------------------- loop

    def run(self) -> Dict[int, float]:
        """Finish time of every job the replica completes."""
        self.gpus = [Gpu(i, k) for i, k in enumerate(self.kinds)]
        for j in self.jobs.values():
            self._push(j.arrival, ARRIVAL, j.jid, 0)
        while self.heap and self.done < len(self.jobs):
            t, _, kind, x, stamp = heapq.heappop(self.heap)
            self.t = t
            if kind == ARRIVAL:
                self.queue.append(x)
                self._admit()
            elif kind == TIMER:
                g = self.gpus[x]
                if stamp != g.stamp or t < g.phase_end - 1e-9:
                    continue
                self._advance(g)
                self._phase_end(g)
                self._settle(g)
            else:
                g, job = self.gpus[x[0]], self.jobs[x[1]]
                if stamp != g.stamp:
                    continue
                self._advance(g)
                if job not in g.jobs or job.remaining > 1e-6:
                    self._schedule(g)
                    continue
                self._finish(g, job)
                if g.jobs and g.phase == MIG:
                    self._repartition(g)
                elif not g.jobs:
                    g.phase = IDLE
                self._settle(g)
                self._admit()
        if any(self.record.windows.values()):
            self.unet_gap = 1.0        # the program probed where this did not
        if any(self.record.decisions.values()):
            self.alg1_gap = 1.0
        return {j.jid: j.finish for j in self.jobs.values()
                if j.finish is not None}

    def _push(self, t, kind, x, stamp):
        heapq.heappush(self.heap, (t, next(self.seq), kind, x, stamp))

    # --------------------------------------------------------- placement

    def _admit(self):
        while self.queue:
            job = self.jobs[self.queue[0]]
            free = [g for g in self.gpus
                    if len(g.jobs) < g.kind.tb.max_jobs
                    and sum(j.prof.mem_gb for j in g.jobs)
                    + job.prof.mem_gb <= g.kind.tb.hw["mem_gb"]
                    and g.kind.tb.fits([j.prof.mem_gb for j in g.jobs]
                                       + [job.prof.mem_gb])]
            if not free:
                return
            g = min(free, key=lambda g: (len(g.jobs), g.gid))
            self.queue.pop(0)
            self._advance(g)
            g.jobs.append(job)
            g.slice[job.jid] = None
            dead = self._ckpt_s(g) if any(g.slice.values()) else 0.0
            g.phase, g.phase_end, g.reprobe = CKPT, self.t + dead, True
            for jid in g.slice:
                g.slice[jid] = None
            if dead == 0.0:
                self._phase_end(g)
            self._settle(g)

    # ------------------------------------------------------------ phases

    def _ckpt_s(self, g: Gpu) -> float:
        c = self.cfg
        if not g.jobs:
            return c["mig_reconfig_s"] * c["overhead_scale"]
        save = max(c["ckpt_base_s"] + j.prof.mem_gb / c["ckpt_bw_gbps"]
                   for j in g.jobs)
        return (c["mig_reconfig_s"] + save) * c["overhead_scale"]

    def _phase_end(self, g: Gpu):
        c = self.cfg
        if g.phase == CKPT and g.reprobe:
            g.phase = MPS
            g.phase_end = self.t + (len(g.kind.tb.levels)
                                    * c["mps_level_time_s"]
                                    * c["overhead_scale"])
            g.reprobe = False
        elif g.phase == MPS:
            self._probe(g)
            self._repartition(g)
        elif g.phase == CKPT:
            g.phase = MIG if g.jobs else IDLE

    def _probe(self, g: Gpu):
        est, params = g.kind.est, g.kind.params
        profs = [j.prof for j in g.jobs]
        mat = est.measure(profs)
        jids = tuple(j.jid for j in g.jobs)
        self.windows += 1
        queue = self.record.windows.get(g.gid)
        if queue and queue[0][0] == jids:
            _, _, out = queue.popleft()
            out = np.asarray(out)
            gap = float(np.abs(out - forward(params, mat[None])[0]).max())
            self.unet_gap = max(self.unet_gap,
                                gap if np.isfinite(gap) else 1.0)
        else:
            self.unet_gap = 1.0
            out = forward(params, mat[None])[0]
        for j, e in zip(g.jobs, est.estimate(profs, out)):
            g.estimates[j.jid] = e

    def _repartition(self, g: Gpu):
        jids = tuple(j.jid for j in g.jobs)
        speeds = [g.estimates.get(jid, {g.kind.tb.full: 1.0}) for jid in jids]
        part = self._decide(g, jids, speeds)
        old = tuple(g.slice[jid] for jid in jids)
        for jid, s in zip(jids, part):
            g.slice[jid] = s
        if old != part:
            g.phase, g.phase_end = CKPT, self.t + self._ckpt_s(g)
            g.reprobe = False
        else:
            g.phase = MIG

    def _decide(self, g: Gpu, jids: Tuple[int, ...],
                speeds: Sequence[Dict[int, float]]) -> Tuple[int, ...]:
        """Algorithm 1 by enumeration over ``g``'s menu; returns the
        program's partition when it is a valid one that scores the best,
        else its gap counts."""
        by_len = g.kind.tb.by_len
        best, feasible_best, first = -1.0, -1.0, None
        m = len(jids)
        for part in by_len.get(m, ()):
            row_best, row_feasible = -1.0, False
            for perm in sorted(set(itertools.permutations(part))):
                vals = [speeds[j].get(perm[j], 0.0) for j in range(m)]
                obj = sum(vals)
                if obj > row_best + 1e-12:
                    row_best, row_feasible = obj, all(v > 0.0 for v in vals)
                elif obj >= row_best - 1e-12 and all(v > 0.0 for v in vals):
                    row_feasible = True
                if obj > best:
                    best, first = obj, perm
            if row_feasible:
                feasible_best = max(feasible_best, row_best)
        target = feasible_best if feasible_best >= 0.0 else best
        self.decisions += 1
        queue = self.record.decisions.get(g.gid)
        if not queue or queue[0][0] != jids:
            self.alg1_gap = 1.0
            return tuple(first)
        _, part = queue.popleft()
        valid = (len(part) == m and tuple(sorted(part, reverse=True))
                 in by_len.get(m, ()))
        vals = [speeds[j].get(part[j], 0.0) for j in range(m)] if valid else []
        if not valid or (feasible_best >= 0.0 and min(vals) <= 0.0):
            self.alg1_gap = 1.0
        else:
            self.alg1_gap = max(self.alg1_gap, target - sum(vals))
        return part

    # ---------------------------------------------------------- progress

    def _advance(self, g: Gpu):
        dt = self.t - g.clock
        if dt > 0 and g.phase in (MPS, MIG):
            for j in g.jobs:
                j.remaining -= g.speed[j.jid] * dt
        g.clock = self.t

    def _settle(self, g: Gpu):
        """New speeds after a change on ``g``, in reference-GPU seconds of
        work a second, and its next events."""
        tb, scale = g.kind.tb, g.kind.scale
        if g.phase == MIG:
            g.speed = {j.jid: (scale * tb.slice_speed(j.prof, g.slice[j.jid])
                               if g.slice[j.jid] else 0.0) for j in g.jobs}
        elif g.phase == MPS and g.jobs:
            g.speed = {j.jid: scale * v for j, v in
                       zip(g.jobs, tb.mps_run_speeds([j.prof
                                                      for j in g.jobs]))}
        else:
            g.speed = {j.jid: 0.0 for j in g.jobs}
        self._schedule(g)

    def _schedule(self, g: Gpu):
        g.stamp += 1
        if g.phase in (CKPT, MPS):
            self._push(g.phase_end, TIMER, g.gid, g.stamp)
        best = None
        for j in g.jobs:
            s = g.speed[j.jid]
            if s > 1e-12:
                tf = g.clock + max(j.remaining, 0.0) / s
                if best is None or tf < best[0]:
                    best = (tf, j.jid)
        if best is not None:
            self._push(best[0], DONE, (g.gid, best[1]), g.stamp)

    def _finish(self, g: Gpu, job: Job):
        job.finish, job.remaining = self.t, 0.0
        g.jobs.remove(job)
        del g.slice[job.jid]
        g.speed.pop(job.jid, None)
        g.estimates.pop(job.jid, None)
        self.done += 1

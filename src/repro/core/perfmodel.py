"""Ground-truth performance model: job speeds on MIG slices and under MPS.

No A100s (or TPUs) exist in this container, so measured speeds are replaced
by a roofline-analytic model (DESIGN.md §2, "what changed"):

* **MIG slice** (interference-free): the slice provides ``compute_frac`` of
  peak FLOP/s, ``mem_bw_frac`` of HBM bandwidth and ``cache_frac`` of shared
  L2.  Losing cache inflates a job's HBM bytes by its ``cache_sens``.
  ``t = max(t_compute, t_memory)``; speed = 1/t.

* **MPS level** (interference-prone): every co-located job is capped at
  ``level`` of the SMs; total compute is time-multiplexed when oversubscribed,
  HBM bandwidth is contended proportionally to demand (fixed-point
  iteration), and co-runners add cache pressure that inflates bytes.

The U-Net predictor is trained purely on (MPS-matrix -> MIG-matrix) pairs
from this model — it never sees these internals, mirroring how the paper
trains on measured pairs.
"""
from __future__ import annotations

import itertools
from dataclasses import dataclass
from typing import Dict, List, Optional, Sequence

import numpy as np

from repro.core.jobs import JobProfile
from repro.core.partitions import PartitionSpace

MPS_LEVELS = (1.00, 0.50, 0.14)       # paper §4.1: 100 / 50 / 14 %
MPS_ITERS = 12                        # blended fixed-point iterations


@dataclass(frozen=True)
class Hardware:
    name: str
    peak_flops: float                 # per accelerator (full)
    hbm_bw: float
    mem_gb: float
    cache_mps_kappa: float = 1.50     # byte inflation per unit cache pressure (MPS)
    cache_mig_kappa: float = 0.45     # byte inflation for reduced-cache slices
    mps_mux_overhead: float = 0.12    # per-co-runner time-multiplexing cost (MPS)
    mps_bw_loss: float = 0.15         # achievable-HBM-bandwidth loss per co-runner
    sched_overhead_s: float = 1e-3    # fixed per-step latency floor


A100 = Hardware("a100-40gb", peak_flops=312e12, hbm_bw=1.555e12, mem_gb=40.0)
H100 = Hardware("h100-80gb", peak_flops=989e12, hbm_bw=3.35e12, mem_gb=80.0,
                mps_bw_loss=0.12)
# one v5e pod as "one accelerator": 256 chips
TPU_V5E_POD = Hardware("tpu-v5e-pod", peak_flops=256 * 197e12,
                       hbm_bw=256 * 819e9, mem_gb=256 * 16.0,
                       cache_mps_kappa=0.15, cache_mig_kappa=0.0)


_CACHE_MAX = 65536   # FIFO bound: profiles are deepcopied per job, so keys
                     # accumulate across long sweeps without one

#: ``mps_matrices`` solves the memo misses of one mix length in one
#: stacked numpy pass from this many (mix, level) rows up, and one by one
#: through ``mps_speeds`` below it.  The stacked pass costs nearly the
#: same at 1 row as at 20 (its cost is numpy's per-call overhead); a row
#: solved one by one costs its own loop.  Where they cross, on the host
#: CPU of a TPU v5e machine (testbed mixes padded to 7 jobs, medians of
#: 150 fresh draws): stacked 423 us at 5 rows and 426 at 6, one by one
#: 412 and 488.
MPS_STACK_MIN_ROWS = 6

_MPS_FIELDS = ("cache_sens", "bytes_per_step", "sm_util", "flops_per_step",
               "compute_eff")


def pysum(x: np.ndarray) -> np.ndarray:
    """``sum()`` down the first axis of ``x``, bit for bit as CPython 3.12
    sums Python floats from its start of 0: the running sum, with each
    step's rounding error accumulated apart (Neumaier) and added back at
    the end where it is finite.  CPython takes each error by a branch on
    the magnitudes (Fast2Sum); TwoSum here gives the same double, as both
    are the exact error of the step.  ``+ 0.0`` maps a running sum of -0.0
    to 0.0, as CPython's int start does, so a zero error adds nothing."""
    if len(x) == 0:
        return np.zeros(x.shape[1:])
    f = np.add.accumulate(x)
    t = f[-1] + 0.0
    if len(x) == 1:
        return t
    a, s = f[:-1], f[1:]
    bb = s - a
    c = np.add.accumulate((a - (s - bb)) + (x[1:] - bb))[-1]
    if not np.isfinite(c).all():
        c = np.where(np.isfinite(c), c, 0.0)
    return t + c


class PerfModel:
    """Ground-truth speeds.  All entry points are memoized on the profile
    *object* — a job's profile is piecewise constant in progress (and, since
    :class:`JobProfile` is an immutable value object that survives trace
    deep-copies, shared across simulations in one process), so the same
    vectors are asked for over and over.  Keys are ``id(profile)`` with the
    profile held in the cache entry, which pins the id for the entry's
    lifetime; this skips re-hashing nine dataclass fields per lookup.  The
    cached dicts are shared objects: callers must treat them as read-only
    (every in-repo consumer copies before mutating)."""

    def __init__(self, space: PartitionSpace, hw: Hardware = A100):
        self.space = space
        self.hw = hw
        self._time_cache: dict = {}
        self._speed_cache: dict = {}
        self._vec_cache: dict = {}
        self._mps_cache: dict = {}

    def _bound(self, cache: dict) -> None:
        if len(cache) >= _CACHE_MAX:
            cache.pop(next(iter(cache)))

    # ----------------------------------------------------------- MIG side

    def slice_time(self, prof: JobProfile, size: int) -> float:
        """Seconds per step on slice ``size`` (inf if OOM)."""
        key = (id(prof), size)
        hit = self._time_cache.get(key)
        if hit is not None:
            return hit[1]
        self._bound(self._time_cache)
        t = self._slice_time(prof, size)
        self._time_cache[key] = (prof, t)
        return t

    def _slice_time(self, prof: JobProfile, size: int) -> float:
        st = self.space.slices[size]
        if prof.mem_gb > st.memory_gb:
            return float("inf")
        # the job can only keep `sm_util` of the full GPU's SMs busy; a slice
        # smaller than that clips it (paper Takeaway 1: small jobs lose little
        # on small slices)
        usable = min(self.space.compute_frac(size), prof.sm_util)
        t_comp = prof.flops_per_step / (
            self.hw.peak_flops * usable * prof.compute_eff)
        bytes_eff = prof.bytes_per_step * (
            1.0 + self.hw.cache_mig_kappa * prof.cache_sens
            * (1.0 - self.space.cache_frac(size)))
        t_mem = bytes_eff / (self.hw.hbm_bw * self.space.mem_bw_frac(size))
        return max(t_comp, t_mem) + self.hw.sched_overhead_s

    def slice_speed(self, prof: JobProfile, size: int) -> float:
        """Execution speed on a slice normalized by full-slice speed: (0,1]."""
        key = (id(prof), size)
        hit = self._speed_cache.get(key)
        if hit is not None:
            return hit[1]
        t_full = self.slice_time(prof, self.space.full_size)
        t = self.slice_time(prof, size)
        v = 0.0 if t == float("inf") else t_full / t
        self._bound(self._speed_cache)
        self._speed_cache[key] = (prof, v)
        return v

    def speed_vector(self, prof: JobProfile) -> dict:
        hit = self._vec_cache.get(id(prof))
        if hit is not None:
            return hit[1]
        self._bound(self._vec_cache)
        sv = {s: self.slice_speed(prof, s) for s in self.space.sizes}
        self._vec_cache[id(prof)] = (prof, sv)
        return sv

    # ----------------------------------------------------------- MPS side

    def mps_speeds(self, profs: Sequence[JobProfile], level: float,
                   iters: int = MPS_ITERS) -> list:
        """Normalized speeds (vs. solo full-GPU) for jobs co-located in MPS at
        ``level`` active-thread fraction each.  The fixed point is memoized
        on the (profiles, level) mix — a GPU's MPS window asks for the same
        mix at every event inside it — and loop-invariant terms are hoisted
        out of the iteration; the arithmetic (and therefore every float bit)
        is unchanged from the historical per-call loop."""
        m = len(profs)
        if m == 0:
            return []
        key = (tuple(id(p) for p in profs), level, iters)
        hit = self._mps_cache.get(key)
        if hit is not None:
            return list(hit[1])
        # cache pressure from co-runners (shared L2 in MPS)
        pressures = []
        for i, p in enumerate(profs):
            others = sum(q.cache_sens for j, q in enumerate(profs) if j != i)
            pressures.append(min(2.0, others / 2.0))
        bytes_eff = [p.bytes_per_step *
                     (1.0 + self.hw.cache_mps_kappa * p.cache_sens * pr)
                     for p, pr in zip(profs, pressures)]

        # compute shares: each job is capped at min(level, its own achievable
        # occupancy); oversubscription time-multiplexes proportionally
        caps = [min(level, p.sm_util) for p in profs]
        total_cap = sum(caps)
        shares = [c / max(1.0, total_cap) for c in caps]

        # contended DRAM loses efficiency (row-buffer conflicts etc.)
        bw_total = self.hw.hbm_bw * max(0.4, 1.0 - self.hw.mps_bw_loss * (m - 1))
        solo = [1.0 / self.slice_time(p, self.space.full_size) for p in profs]
        # per-job compute time and the multiplexing factor are invariant
        # across fixed-point iterations
        t_comps = [p.flops_per_step / (self.hw.peak_flops * shares[i]
                                       * p.compute_eff)
                   for i, p in enumerate(profs)]
        mux = 1.0 + self.hw.mps_mux_overhead * (m - 1)
        overhead = self.hw.sched_overhead_s
        rates = list(solo)
        for _ in range(iters):
            demand = [r * b for r, b in zip(rates, bytes_eff)]
            total_d = sum(demand)
            new_rates = []
            for i in range(m):
                if total_d > bw_total and total_d > 0:
                    bw_i = bw_total * demand[i] / total_d
                else:
                    bw_i = bw_total
                t_mem = bytes_eff[i] / max(bw_i, 1e-6)
                new_rates.append(1.0 / (max(t_comps[i], t_mem) * mux
                                        + overhead))
            rates = [0.5 * a + 0.5 * b for a, b in zip(rates, new_rates)]

        out = [r / s for r, s in zip(rates, solo)]
        self._bound(self._mps_cache)
        self._mps_cache[key] = (tuple(profs), out)
        return list(out)

    def mps_matrix(self, profs: Sequence[JobProfile]) -> list:
        """3 x m matrix of MPS speeds (rows = MPS_LEVELS)."""
        return [self.mps_speeds(profs, lv) for lv in MPS_LEVELS]

    def mps_matrices(self, mixes: Sequence[Sequence[JobProfile]],
                     prof: Optional[Dict[str, float]] = None
                     ) -> List[List[list]]:
        """``mps_matrix`` of every mix: the same values, in rows shared
        with the memo (read-only).  The memo answers what it holds; the
        rows it misses are solved as rows of one array per mix length by
        :meth:`_mps_speeds_stacked` where that length has
        ``MPS_STACK_MIN_ROWS`` of them, else one by one by
        :meth:`mps_speeds`.  Solved rows enter the memo as ``mps_speeds``
        enters them, so later scalar calls hit.  ``prof`` (profile
        buckets, ``core/sim/prof.py``) counts the rows asked for, those
        the memo answered (a row asked for twice is answered the second
        time) and those solved stacked."""
        cache = self._mps_cache
        found: Dict[tuple, list] = {}      # memo key -> row
        todo: Dict[tuple, tuple] = {}      # memo key -> (profs, level)
        keys_of = []
        for profs in mixes:
            ids = tuple(map(id, profs))
            keys = [(ids, lv, MPS_ITERS) for lv in MPS_LEVELS]
            for key, lv in zip(keys, MPS_LEVELS):
                if key in found or key in todo:
                    continue
                hit = cache.get(key)
                if hit is not None:
                    found[key] = hit[1]
                else:
                    todo[key] = (profs, lv)
            keys_of.append(keys)
        stacked = 0
        by_len: Dict[int, list] = {}
        for key, (profs, _) in todo.items():
            by_len.setdefault(len(profs), []).append(key)
        for keys in by_len.values():
            if len(keys) < MPS_STACK_MIN_ROWS:
                continue
            rates = self._mps_speeds_stacked([todo[k][0] for k in keys],
                                             [todo[k][1] for k in keys])
            if rates is None:
                continue
            stacked += len(keys)
            for k, row in zip(keys, rates.tolist()):
                self._bound(cache)
                cache[k] = (tuple(todo[k][0]), row)
                found[k] = row
        for key, (profs, lv) in todo.items():
            if key not in found:
                found[key] = self.mps_speeds(profs, lv)
        if prof is not None:
            asked = len(MPS_LEVELS) * len(mixes)
            prof["mps_rows"] += asked
            prof["mps_rows_hit"] += asked - len(todo)
            prof["mps_rows_stacked"] += stacked
        return [[found[k] for k in keys] for keys in keys_of]

    def _mps_speeds_stacked(self, mixes: Sequence[Sequence[JobProfile]],
                            levels: Sequence[float]) -> Optional[np.ndarray]:
        """``mps_speeds`` of many (mix, level) rows whose mixes share one
        length m, as an (rows, m) float64 array equal to it bit for bit:
        each of its float operations runs here on a whole column, in its
        order, and its ``sum()``s are :func:`pysum`.  None where that does
        not hold: an empty mix, a profile field that is not a Python float
        (``sum()`` adds ints and float subclasses differently), or a
        profile with no full-GPU speed (``mps_speeds`` raises there)."""
        hw = self.hw
        m = len(mixes[0])
        # each mix once (its levels are rows that share it), each profile
        # once; the arrays run (job, row)
        at = {id(ps): ps for ps in mixes}
        objs = list({id(p): p for ps in at.values() for p in ps}.values())
        vals = [[getattr(p, f) for p in objs] for f in _MPS_FIELDS]
        if set(map(type, itertools.chain.from_iterable(vals))) != {float}:
            return None
        col = {id(p): k for k, p in enumerate(objs)}
        cols = {i: [col[id(p)] for p in ps] for i, ps in at.items()}
        full = self.space.full_size
        cs, byt, sm, fl, eff, t_solo = np.array(
            vals + [[self.slice_time(p, full) for p in objs]])[
                :, np.array([cols[id(ps)] for ps in mixes]).T]
        solo = 1.0 / t_solo
        if not solo.all():
            return None
        # cache pressure from co-runners: job i sums the others in order
        others = pysum(cs[np.array(
            [[j for j in range(m) if j != i] for i in range(m)],
            dtype=np.intp).reshape(m, m - 1).T])
        pressures = np.minimum(2.0, others / 2.0)
        bytes_eff = byt * (1.0 + hw.cache_mps_kappa * cs * pressures)
        caps = np.minimum(np.array(levels), sm)
        shares = caps / np.maximum(1.0, pysum(caps))
        bw_total = hw.hbm_bw * max(0.4, 1.0 - hw.mps_bw_loss * (m - 1))
        t_comps = fl / (hw.peak_flops * shares * eff)
        mux = 1.0 + hw.mps_mux_overhead * (m - 1)
        overhead = hw.sched_overhead_s
        # ``total_d > bw_total and total_d > 0`` in one comparison
        bw_floor = max(bw_total, 0.0)
        rates = solo
        for _ in range(MPS_ITERS):
            demand = rates * bytes_eff
            total_d = pysum(demand)
            bw_i = np.where(total_d > bw_floor, bw_total * demand / total_d,
                            bw_total)
            t_mem = bytes_eff / np.maximum(bw_i, 1e-6)
            new_rates = 1.0 / (np.maximum(t_comps, t_mem) * mux + overhead)
            rates = 0.5 * rates + 0.5 * new_rates
        return (rates / solo).T


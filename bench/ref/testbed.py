"""A GPU of one kind as its configuration's group states it: the MIG menu
and the speeds a job runs at on a slice or under MPS.

Speeds are normalized to the job alone on the whole GPU.  On a MIG slice
a job gets the slice's share of the SMs (no more than it can use), of the
memory bandwidth and of the L2, and losing L2 inflates its memory traffic
by its cache sensitivity.  Under MPS every co-runner is capped at the
level's share of the SMs, SMs are time-shared when the caps add up to
more than the GPU, co-runners inflate each other's memory traffic, and
the bandwidth left after contention is split by demand (a damped
fixed-point iteration).
"""
from __future__ import annotations

import itertools
import math
from typing import Dict, List, NamedTuple, Sequence, Tuple


#: the constants of ``hardware`` that the speed model reads
HARDWARE = ("peak_flops", "hbm_bw", "mem_gb", "cache_mps_kappa",
            "cache_mig_kappa", "mps_mux_overhead", "mps_bw_loss",
            "sched_overhead_s")


class Profile(NamedTuple):
    name: str
    flops_per_step: float
    bytes_per_step: float
    mem_gb: float
    compute_eff: float
    cache_sens: float
    sm_util: float


def profile(row: dict) -> Profile:
    return Profile(**{k: row[k] for k in Profile._fields})


class Testbed:
    """The GPUs of one group (``ref.fleet``), probed at MPS ``levels``."""

    def __init__(self, group: dict, levels: Sequence[float]):
        hw, mig = group["hardware"], group["mig"]
        self.hw = {k: hw[k] for k in HARDWARE}
        self.levels = tuple(levels)
        self.compute_slots = mig["compute_slots"]
        self.memory_slots = mig["memory_slots"]
        self.slices: Dict[int, dict] = {s["size"]: s for s in mig["slices"]}
        self.full = max(self.slices)
        self.exclusions = [frozenset(e) for e in mig["exclusions"]]
        self.partitions = self._partitions()
        self.max_jobs = max(len(p) for p in self.partitions)
        self.by_len: Dict[int, List[Tuple[int, ...]]] = {}
        for p in self.partitions:
            self.by_len.setdefault(len(p), []).append(p)

    # ---------------------------------------------------------- the menu

    def _partitions(self) -> List[Tuple[int, ...]]:
        """Every multiset of slices that fits the GPU's compute and memory
        slots, within each slice's count and the exclusions."""
        sizes = sorted(self.slices, reverse=True)
        out = []
        for counts in itertools.product(
                *(range(self.slices[s]["max_count"] + 1) for s in sizes)):
            part = tuple(s for s, n in zip(sizes, counts) for _ in range(n))
            if not part:
                continue
            compute = sum(self.slices[s]["compute_slots"] for s in part)
            memory = sum(self.slices[s]["memory_slots"] for s in part)
            if compute > self.compute_slots or memory > self.memory_slots:
                continue
            if any(e <= set(part) for e in self.exclusions):
                continue
            out.append(part)
        return out

    def fits(self, mems: Sequence[float]) -> bool:
        """Whether some partition gives each of these jobs a slice with
        enough memory: the largest job on the slice with most memory, the
        next on the next, and so on."""
        need = sorted(mems, reverse=True)
        for part in self.by_len.get(len(need), ()):
            have = sorted((self.slices[s]["memory_gb"] for s in part),
                          reverse=True)
            if all(h >= n for h, n in zip(have, need)):
                return True
        return False

    # ----------------------------------------------------------- speeds

    def slice_time(self, p: Profile, size: int) -> float:
        """Seconds per step on slice ``size``; infinite when it does not
        fit the slice's memory."""
        s, hw = self.slices[size], self.hw
        if p.mem_gb > s["memory_gb"]:
            return math.inf
        sms = min(s["compute_slots"] / self.compute_slots, p.sm_util)
        t_compute = p.flops_per_step / (hw["peak_flops"] * sms
                                        * p.compute_eff)
        traffic = p.bytes_per_step * (
            1.0 + hw["cache_mig_kappa"] * p.cache_sens
            * (1.0 - s["cache_frac"]))
        t_memory = traffic / (hw["hbm_bw"]
                              * (s["memory_slots"] / self.memory_slots))
        return max(t_compute, t_memory) + hw["sched_overhead_s"]

    def slice_speed(self, p: Profile, size: int) -> float:
        t = self.slice_time(p, size)
        return 0.0 if math.isinf(t) else self.slice_time(p, self.full) / t

    def mps_speeds(self, profs: Sequence[Profile], level: float,
                   iters: int = 12) -> List[float]:
        """Each co-runner's speed at MPS level ``level``."""
        hw, m = self.hw, len(profs)
        traffic = []
        for i, p in enumerate(profs):
            others = sum(q.cache_sens for j, q in enumerate(profs) if j != i)
            pressure = min(2.0, others / 2.0)
            traffic.append(p.bytes_per_step * (
                1.0 + hw["cache_mps_kappa"] * p.cache_sens * pressure))
        caps = [min(level, p.sm_util) for p in profs]
        share = [c / max(1.0, sum(caps)) for c in caps]
        bandwidth = hw["hbm_bw"] * max(0.4, 1.0 - hw["mps_bw_loss"] * (m - 1))
        alone = [1.0 / self.slice_time(p, self.full) for p in profs]
        t_compute = [p.flops_per_step / (hw["peak_flops"] * share[i]
                                         * p.compute_eff)
                     for i, p in enumerate(profs)]
        mux = 1.0 + hw["mps_mux_overhead"] * (m - 1)
        rates = list(alone)
        for _ in range(iters):
            demand = [r * b for r, b in zip(rates, traffic)]
            total = sum(demand)
            new = []
            for i in range(m):
                bw = (bandwidth * demand[i] / total
                      if total > bandwidth and total > 0 else bandwidth)
                t_memory = traffic[i] / max(bw, 1e-6)
                new.append(1.0 / (max(t_compute[i], t_memory) * mux
                                  + hw["sched_overhead_s"]))
            rates = [0.5 * a + 0.5 * b for a, b in zip(rates, new)]
        return [r / a for r, a in zip(rates, alone)]

    def mps_run_speeds(self, profs: Sequence[Profile]) -> List[float]:
        """Speeds while the probe sweeps the levels one after another: the
        mean over the levels."""
        per_level = [self.mps_speeds(profs, lv) for lv in self.levels]
        return [sum(col) / len(self.levels) for col in zip(*per_level)]

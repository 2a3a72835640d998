"""The benchmark's one traffic generator.

A traffic mix (``bench/traffic/<mix>.json``) states a job trace the way
MISO's evaluation draws one (paper §5): ``jobs`` jobs, each a uniform pick
from the configuration's workload pool, with Poisson arrivals
``mean_gap_s`` apart on average and a lognormal amount of work (``work_s``:
median, shape, and the bounds it is clipped to).  That trace is drawn once,
from ``draw_seed``; a run's ``--seed`` then deals it out as ``traces``
traces, each the same jobs and the same gaps in an order of its own.  So
every seed offers the same work, and two seeds differ only in the order in
which it arrives.

A trace is plain data: per job its pool index, arrival time and work.
"""
from __future__ import annotations

import math
from typing import Dict, List

import numpy as np

_MASK = (1 << 64) - 1


def draw(traffic: dict, pool_size: int) -> Dict[str, np.ndarray]:
    """The mix's one draw of jobs and gaps."""
    n = int(traffic["jobs"])
    w = traffic["work_s"]
    rng = np.random.default_rng(int(traffic["draw_seed"]))
    gaps = rng.exponential(float(traffic["mean_gap_s"]), size=n)
    pick = rng.integers(0, pool_size, size=n)
    work = np.clip(rng.lognormal(math.log(float(w["median"])),
                                 float(w["sigma"]), size=n),
                   float(w["min"]), float(w["max"]))
    return {"pick": pick, "work": work, "gaps": gaps}


def traces(traffic: dict, pool_size: int, seed: int
           ) -> List[Dict[str, np.ndarray]]:
    """The run's ``traces`` traces, dealt from ``seed``: trace ``k`` orders
    the jobs and the gaps by two permutations of its own."""
    base = draw(traffic, pool_size)
    n = len(base["gaps"])
    out = []
    for k in range(int(traffic["traces"])):
        rng = np.random.default_rng([int(seed) & _MASK, k])
        jobs, gaps = rng.permutation(n), rng.permutation(n)
        out.append({"pick": base["pick"][jobs],
                    "work": base["work"][jobs],
                    "arrival": np.cumsum(base["gaps"][gaps])})
    return out

"""The comparison that decides ``correct``.

The reference is ``ref/``: the cluster's semantics written out plainly
from the configuration file, with Algorithm 1 as a literal enumeration and
the U-Net as a NumPy float64 forward, on the benchmark's own copy of the
weights.  Each GPU is its group's (``ref.fleet``): its menu, speed model,
estimator, weights and speed scale.  Three numbers are compared, each with
its limit:

* ``unet_gap``: over every MPS window of the run, the largest absolute
  difference between the program's U-Net output and the float64 forward
  of the same matrix through the weights of the window's GPU; in the
  replicas the reference re-runs, of the matrix the reference measured
  itself.  A window whose row cannot be told apart reads 1.
* ``alg1_gap``: over every Algorithm-1 decision of the replicas the
  reference re-runs, how far the program's partition scores below the
  best the enumeration finds on the same estimates over the menu of the
  decision's GPU (feasible first).  A partition that is not on the menu,
  or infeasible where a feasible one exists, or a decision the reference
  does not come to, reads 1.
* ``jct_gap``: over every job of those replicas, the gap between the
  program's and the reference's completion time, relative to the job's
  completion time less its arrival; a job that either side leaves
  unfinished reads 1.

Which replicas the reference re-runs is drawn from the seed once the
window has closed: ``SAMPLE`` of them, the one with most decisions among
them.  ``PERF.md`` gives the readings each limit was set from.
"""
from __future__ import annotations

import math
import os
import sys
import time
from typing import Dict, List, Sequence

import numpy as np

BENCH = os.path.dirname(os.path.abspath(__file__))
if BENCH not in sys.path:
    sys.path.insert(0, BENCH)

from ref.fleet import gpu_groups, groups  # noqa: E402
from ref.sim import Job, Kind, ProgramRecord, Replica  # noqa: E402
from ref.testbed import Testbed, profile  # noqa: E402
from ref.unet import Estimator, forward, load_weights  # noqa: E402

LIMITS = {"unet_gap": 2e-5, "alg1_gap": 1e-9, "jct_gap": 1e-9}
#: replicas of the window the reference re-runs
SAMPLE = 8


def unet_gap(windows: Dict[tuple, list], params: Sequence[dict],
             group_of: Sequence[int], strays: int) -> float:
    """Largest |program - float64 forward| over every recorded window,
    each through ``params[group_of[gid]]``, the weights of its GPU."""
    if strays:
        return 1.0
    rows = [(gid, mat, out) for ws in windows.values()
            for gid, _, mat, out in ws]
    if any(not 0 <= gid < len(group_of) for gid, _, _ in rows):
        return 1.0
    gap = 0.0
    for k, p in enumerate(params):
        mine = [(mat, out) for gid, mat, out in rows if group_of[gid] == k]
        if not mine:
            continue
        mats = np.stack([np.asarray(m, np.float32) for m, _ in mine])
        outs = np.stack([o for _, o in mine]).astype(np.float64)
        uniq, inv = np.unique(mats, axis=0, return_inverse=True)
        ref = np.concatenate([forward(p, uniq[i:i + 4096])
                              for i in range(0, len(uniq), 4096)])
        g = float(np.abs(outs - ref[inv.reshape(-1)]).max())
        if not math.isfinite(g):
            return 1.0
        gap = max(gap, g)
    return gap


def sample(keys: Sequence[tuple], decisions: Dict[tuple, list],
           seed: int, n: int = SAMPLE) -> List[tuple]:
    """``n`` replicas drawn from ``seed``, led by the one with most
    decisions."""
    keys = sorted(keys)
    if len(keys) <= n:
        return keys
    longest = max(keys, key=lambda k: (len(decisions.get(k, ())), k))
    rng = np.random.default_rng([int(seed) & ((1 << 64) - 1), 0xC4EC])
    rest = [k for k in keys if k != longest]
    picks = rng.choice(len(rest), size=n - 1, replace=False)
    return [longest] + [rest[i] for i in sorted(picks)]


def compare(config: dict, traces: list, trace_of: Dict[tuple, int],
            finished: Dict[tuple, np.ndarray], windows, decisions,
            strays: int, seed: int, root: str) -> dict:
    """The three numbers, and what the reference did to read them.
    ``finished`` holds each replica's completion times by job (NaN where
    the program left a job unfinished), ``trace_of`` its trace."""
    t0 = time.perf_counter()
    fleet = groups(config)
    group_of = gpu_groups(fleet)
    kinds = []
    for g in fleet:
        params, heads = load_weights(os.path.join(
            root, g["predictor"]["weights"]))
        tb = Testbed(g, config["mps_levels"])
        est = Estimator(tb, g["predictor"], config["pad_profile"], heads)
        kinds.append(Kind(tb, est, params, float(g["speed_scale"])))
    pool = [profile(r) for r in config["workloads"]]
    out = {"unet_gap": unet_gap(windows, [k.params for k in kinds],
                                group_of, strays),
           "alg1_gap": 0.0, "jct_gap": 0.0}
    checked = sample(list(finished), decisions, seed)
    n_windows = n_decisions = 0
    for key in checked:
        tr = traces[trace_of[key]]
        jobs = [Job(i, pool[int(p)], float(a), float(w))
                for i, (p, a, w) in enumerate(zip(tr["pick"], tr["arrival"],
                                                  tr["work"]))]
        rep = Replica([kinds[k] for k in group_of], config["sim"], jobs,
                      ProgramRecord(windows.get(key, ()),
                                    decisions.get(key, ())))
        done = rep.run()
        n_windows += rep.windows
        n_decisions += rep.decisions
        out["unet_gap"] = max(out["unet_gap"], rep.unet_gap)
        out["alg1_gap"] = max(out["alg1_gap"], rep.alg1_gap)
        got = finished[key]
        for j in jobs:
            a, b = got[j.jid], done.get(j.jid)
            if b is None or not math.isfinite(a):
                gap = 1.0
            else:
                gap = float(abs(a - b) / max(b - j.arrival, 1e-9))
            out["jct_gap"] = max(out["jct_gap"], gap)
    return {"numbers": out, "replicas": len(checked), "windows": n_windows,
            "decisions": n_decisions, "seconds": time.perf_counter() - t0}

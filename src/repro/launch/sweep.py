"""Parallel policy x placer x objective x scenario x seed sweep engine.

Fans a grid of cluster simulations across worker *processes* (each cell is
an independent event-driven run, so the sweep is embarrassingly parallel)
and emits one schema-stable JSON report consumed by ``benchmarks/`` for
trajectory tracking (``BENCH_*.json``).

  PYTHONPATH=src python -m repro.launch.sweep \\
      --policies miso,srpt --scenarios bursty,diurnal,heavy_tail --seeds 3
  PYTHONPATH=src python -m repro.launch.sweep --scenarios smoke --seeds 2
  PYTHONPATH=src python -m repro.launch.sweep --scenarios hetero_smoke \\
      --placers least-loaded,hetero-speed --seeds 2
  PYTHONPATH=src python -m repro.launch.sweep --scenarios hetero_smoke \\
      --policies miso --objectives throughput,energy,edp --seeds 2
  PYTHONPATH=src python -m repro.launch.sweep --fleet a100:8 --serial

Scenarios come from :mod:`repro.core.scenarios` (each carries a default
heterogeneous fleet spec, placer, objective and optional SimConfig
overrides; override with ``--fleet`` / ``--placers`` / ``--objectives``);
policies are any registered scheduling policy, placers any registered
placement layer (:mod:`repro.core.sim.placement`) and objectives any
registered Algorithm-1 goal (:mod:`repro.core.sim.objectives`).  The JSON
schema is versioned: bump ``SCHEMA_VERSION`` on any breaking change to the
result shape (v2 added the placer axis; v3 added the objective axis and the
energy columns; v4 adds the robustness columns — ``goodput`` /
``gross_stp`` / ``work_lost_s`` / blast, recovery and quarantine counters
in every result, ``goodput_mean`` / ``work_lost_s_mean`` in the summary —
plus a top-level ``errors`` list of cells that crashed or timed out).

Two execution engines share the cell-build path (``--engine``):

* ``pool`` (default) — one process per cell on the persistent warm pool
  below;
* ``batched`` — cells sharing a resolved fleet spec coalesce into one
  in-process lockstep replica batch (``core/sim/batch.py``): estimator
  forwards and Algorithm-1 solves fuse across cells, metrics stay
  bit-identical per cell, and ``config.batched_cells`` records how many
  cells actually ran batched; a group that fails to build or run raises.

One process owns an accelerator: the pool starts only on a CPU backend, and
on any other backend a sweep runs ``--engine batched`` or ``--serial`` in
the process that holds the device.

Warm-pool execution (the driver loop that makes cheap rollouts cheap):

* The worker pool is a **process-lifetime singleton**, not a per-sweep
  throwaway: the first parallel :func:`run_sweep` spawns it (spawn
  context — forking a jax-initialized parent deadlocks in XLA's inherited
  thread-pool locks) and every later sweep in the same driver process
  reuses the already-warm workers, so the spawn + import + jit-warm cost
  (~seconds per worker) is paid once per process instead of once per
  sweep.  ``shutdown_pool()`` tears it down explicitly; an ``atexit`` hook
  does so at interpreter exit, and a worker crash (``BrokenProcessPool``)
  rebuilds the pool once and retries the batch.
* Job traces are served from a **content-addressed scenario/trace cache**:
  in-process memo keyed (scenario, effective seed, trace length) — seeds
  collapse for ``seed_sensitive=False`` replay scenarios — plus an
  optional on-disk pickle tier (``--trace-cache DIR``, atomic writes keyed
  by the sha256 of the cell key) shared across driver processes.  The
  engine deep-copies its job list (`simulate()` contract), so cached
  pristine traces are reused bit-identically; repeated cells across
  sweeps, ``--resume`` re-runs and warm-pool rollout loops all skip job
  generation.  ``--profile`` attaches per-cell ``gen_s`` / ``setup_s`` /
  ``overhead_s`` buckets so the saving is measurable, not asserted.

Hardening (chaos sweeps run long and can die mid-grid): every cell runs
under a per-cell wall-clock budget (``--cell-timeout``, SIGALRM) with
bounded retry (``--retries``); a cell that still fails is recorded in
``report["errors"]`` instead of sinking the whole sweep (the CLI then exits
non-zero once the report is written), and ``--resume
partial.json`` skips cells already present in an earlier report of the
same schema version (error cells are always re-run).  POSIX reserves
signal delivery for the main thread: when the runner is embedded off the
main thread (test harnesses, GUI drivers) or the platform has no SIGALRM
(Windows), the timeout degrades to a documented no-op — the cell runs
unbounded — instead of dying on ``signal.signal``'s ValueError.
"""
from __future__ import annotations

import argparse
import atexit
import hashlib
import json
import os
import pickle
import signal
import sys
import threading
import time
from concurrent.futures import ProcessPoolExecutor
from concurrent.futures.process import BrokenProcessPool
from typing import Dict, List, Optional, Sequence, Tuple

SCHEMA_VERSION = 4

# grids whose total simulated jobs fall under this run in-process: worker
# startup (fork + pool plumbing, ~hundreds of ms) dwarfs such cells
_AUTO_SERIAL_JOBS = 64

#: bump when trace generation changes in a way that invalidates cached
#: pickles (new Job fields, different attribute streams); part of every
#: cache key, so stale on-disk entries simply stop being addressed
TRACE_CACHE_VERSION = 1

# in-process trace memo: key -> pristine job list (never simulated on
# directly — the engine deep-copies; see _get_jobs)
_TRACE_CACHE: Dict[tuple, list] = {}
_TRACE_CACHE_MAX = 32                 # traces can be 100K jobs; FIFO-bound
_FLEET_CACHE: Dict[str, list] = {}    # fleet spec string -> GPUSpec list

_WARMED = False


def _warm_runtime() -> None:
    """Pay one-time lazy costs before simulating: numpy's random-module
    machinery (~40 ms on first Generator construction) and — when per-kind
    predictor artifacts exist, i.e. sweeps will run U-Net estimators — the
    shared jitted U-Net apply for the standard shapes.  Runs in the parent
    for serial sweeps and as the pool initializer in every worker; the
    persistent pool means each worker pays it exactly once per driver
    process, not once per sweep."""
    global _WARMED
    if _WARMED:
        return
    _WARMED = True
    import glob
    import os

    import numpy as np
    np.random.default_rng(0)
    art_dir = os.path.join(os.path.dirname(__file__), "..", "..", "..",
                           "artifacts")
    if glob.glob(os.path.join(art_dir, "predictor*.npz")):
        from repro.core.predictor.unet import warm_jit_cache
        warm_jit_cache()


# ------------------------------------------------------------ warm pool

_POOL: Optional[ProcessPoolExecutor] = None
_POOL_WORKERS = 0


def _get_pool(workers: Optional[int]) -> ProcessPoolExecutor:
    """The process-lifetime worker pool.  ``workers=None`` reuses whatever
    pool exists (or sizes a new one to the CPU count); an explicit size
    that differs from the live pool recycles it."""
    global _POOL, _POOL_WORKERS
    import jax
    if jax.default_backend() != "cpu":
        # every worker would load the accelerator runtime, which only one
        # process may hold; the one that holds it is this one
        raise RuntimeError(
            f"the sweep worker pool runs only on a CPU backend (this process "
            f"holds {jax.default_backend()!r}); use --engine batched or "
            f"--serial")
    want = workers or _POOL_WORKERS or (os.cpu_count() or 1)
    if _POOL is not None and want != _POOL_WORKERS:
        shutdown_pool()
    if _POOL is None:
        import multiprocessing
        # spawn, not fork: workers run jitted U-Net inference (per-kind
        # predictor artifacts), and forking a jax-initialized parent
        # deadlocks in XLA's inherited thread-pool locks
        _POOL = ProcessPoolExecutor(
            max_workers=want,
            mp_context=multiprocessing.get_context("spawn"),
            initializer=_warm_runtime)
        _POOL_WORKERS = want
    return _POOL


def shutdown_pool() -> None:
    """Tear down the persistent worker pool (no-op when none is live).
    Registered at exit; call explicitly to reclaim the workers early."""
    global _POOL, _POOL_WORKERS
    if _POOL is not None:
        _POOL.shutdown(wait=True)
        _POOL = None
        _POOL_WORKERS = 0


atexit.register(shutdown_pool)


# ---------------------------------------------------------- trace cache

def _trace_key(task: Dict, sc) -> tuple:
    """Content address of a cell's job trace.  Replay scenarios
    (``seed_sensitive=False``) generate the identical workload for every
    seed, so their seeds collapse to one entry."""
    return (TRACE_CACHE_VERSION, task["scenario"],
            task["seed"] if sc.seed_sensitive else 0,
            task.get("n_jobs") or sc.n_jobs)


def _get_jobs(task: Dict, sc) -> Tuple[list, float, str]:
    """The cell's pristine job list, its load cost in seconds, and where
    it came from (``"memo"`` / ``"disk"`` / ``"fresh"``).  Callers must
    not mutate the returned list or its jobs — every simulation runs on a
    deep copy (the ``simulate()`` contract), which is what makes sharing
    one trace across cells bit-identical to regenerating it."""
    t0 = time.perf_counter()
    key = _trace_key(task, sc)
    jobs = _TRACE_CACHE.get(key)
    if jobs is not None:
        return jobs, time.perf_counter() - t0, "memo"
    src = "fresh"
    path = None
    cache_dir = task.get("trace_cache")
    if cache_dir:
        os.makedirs(cache_dir, exist_ok=True)
        h = hashlib.sha256(repr(key).encode()).hexdigest()[:24]
        path = os.path.join(cache_dir, f"trace_{h}.pkl")
        if os.path.exists(path):
            try:
                with open(path, "rb") as f:
                    jobs = pickle.load(f)
                src = "disk"
            except Exception:
                jobs = None          # corrupt/partial entry: regenerate
    if jobs is None:
        jobs = sc.make_jobs(task["seed"], task.get("n_jobs"))
        if path is not None:
            # atomic publish: concurrent workers race benignly (same key
            # -> same bytes), readers never see a torn file
            tmp = f"{path}.tmp.{os.getpid()}"
            with open(tmp, "wb") as f:
                pickle.dump(jobs, f, protocol=pickle.HIGHEST_PROTOCOL)
            os.replace(tmp, path)
    while len(_TRACE_CACHE) >= _TRACE_CACHE_MAX:
        _TRACE_CACHE.pop(next(iter(_TRACE_CACHE)))
    _TRACE_CACHE[key] = jobs
    return jobs, time.perf_counter() - t0, src


def _get_fleet(spec: str) -> list:
    fleet = _FLEET_CACHE.get(spec)
    if fleet is None:
        from repro.core.fleet import parse_fleet
        fleet = _FLEET_CACHE[spec] = parse_fleet(spec)
    return fleet


def _build_cell(task: Dict, profile: bool = False):
    """Resolve one cell's (scenario, fleet, config) and construct its
    ready-to-run ``ClusterSim`` on a deep copy of the (possibly cached)
    pristine trace.  Shared by the per-process scalar path and the
    in-process batched engine, so a cell is built identically either way.
    Returns ``(sim, meta)`` where ``meta`` carries everything
    :func:`_cell_result` needs to describe the cell."""
    import copy

    from repro.core.scenarios import get_scenario
    from repro.core.simulator import ClusterSim, SimConfig

    sc = get_scenario(task["scenario"])
    jobs, gen_s, trace_src = _get_jobs(task, sc)
    fleet = _get_fleet(task.get("fleet") or sc.fleet)
    placer = task.get("placer") or sc.placer
    objective = task.get("objective") or sc.objective
    cfg_kwargs = dict(sc.sim_kwargs)     # scenario-bundled SimConfig knobs
    if task.get("mtbf") is not None:     # explicit --mtbf wins, 0 included
        cfg_kwargs["gpu_mtbf_s"] = task["mtbf"]
    cfg = SimConfig(n_gpus=len(fleet), policy=task["policy"],
                    placer=placer, objective=objective, seed=task["seed"],
                    profile=profile, **cfg_kwargs)
    t_set0 = time.perf_counter()
    sim = ClusterSim(copy.deepcopy(list(jobs)), cfg, fleet=fleet)
    setup_s = time.perf_counter() - t_set0
    meta = {"task": task, "placer": placer, "objective": objective,
            "fleet": fleet, "n_jobs": len(jobs), "gen_s": gen_s,
            "setup_s": setup_s, "trace_src": trace_src}
    return sim, meta


def _cell_result(meta: Dict, m, wall_s: float) -> Dict:
    """The schema-stable result record for one finished cell."""
    from repro.core.fleet import describe_fleet

    task = meta["task"]
    return {
        "policy": task["policy"],
        "placer": meta["placer"],
        "objective": meta["objective"],
        "scenario": task["scenario"],
        "seed": task["seed"],
        "fleet": describe_fleet(meta["fleet"]),
        "n_jobs": meta["n_jobs"],
        "n_completed": len(m.jcts),
        "metrics": {
            "avg_jct_s": m.avg_jct,
            "p50_jct_s": m.p50_jct,
            "p90_jct_s": m.p90_jct,
            "makespan_s": m.makespan,
            "stp": m.stp,
            "energy_j": m.energy_j,
            "avg_power_w": m.avg_power_w,
            "energy_per_job_j": m.energy_per_job_j,
            "jct_per_joule": m.jct_per_joule,
            "breakdown_s": dict(m.breakdown),
            # v4 robustness columns (all zero when no fault model ran)
            "goodput": m.goodput,
            "gross_stp": m.gross_stp,
            "work_lost_s": m.work_lost_s,
            "n_fault_events": m.n_fault_events,
            "blast_jobs": m.blast_jobs,
            "blast_radius_max": m.blast_radius_max,
            "mean_recover_s": m.mean_recover_s,
            "quarantine_occupancy": m.quarantine_occupancy,
            "n_quarantines": m.n_quarantines,
            "n_migrations": m.n_migrations,
        },
        "wall_s": wall_s,
    }


def run_task(task: Dict) -> Dict:
    """One sweep cell: simulate (policy, placer, objective, scenario, seed)
    on a fleet.

    Module-level and dict-in/dict-out so it pickles cleanly into worker
    processes.
    """
    t0 = time.time()
    profile = bool(task.get("profile"))
    sim, meta = _build_cell(task, profile)
    t_run0 = time.perf_counter()
    m = sim.run()
    run_s = time.perf_counter() - t_run0
    out = _cell_result(meta, m, time.time() - t0)
    if profile:
        out["profile"] = _profile_record(sim.prof, meta)
        # everything that is not the simulation itself
        out["profile"]["overhead_s"] = max(0.0, out["wall_s"] - run_s)
    return out


def _profile_record(p: Dict[str, float], meta: Dict) -> Dict:
    """A profiled cell's ``profile`` entry from its ``ClusterSim.prof``
    (``core/sim/prof.py``).  A scalar cell's time is its ``run()``
    (``total_s``); a batched cell's is its replica's rounds: its own event
    loop (``collect_s``), stages B and D (``round_apply_s``) and its shares
    of the fused stages A and C.  One formula covers both, since each
    engine leaves the other's buckets at 0.  Every bucket is copied;
    ``alg1_s`` and ``estimator_s`` become the whole layer, inline work plus
    the fused stage's share."""
    total = (p["total_s"] + p["collect_s"] + p["round_apply_s"]
             + p["fused_estimator_s"] + p["fused_alg1_s"])
    estimator = p["estimator_s"] + p["fused_estimator_s"]
    alg1 = p["alg1_s"] + p["fused_alg1_s"]
    rec = dict(p)
    rec.update({
        "alg1_s": alg1,
        "estimator_s": estimator,
        # everything else the run loop did: heap churn, accounting,
        # phase bookkeeping
        "event_loop_s": max(0.0, total - p["placement_s"] - alg1
                            - estimator),
        "total_s": total,
        "events": int(p["events"]),
        # per-cell set-up buckets; trace_src says whether job generation
        # was skipped by the content-addressed cache
        "gen_s": meta["gen_s"],
        "setup_s": meta["setup_s"],
        "trace_src": meta["trace_src"],
    })
    return rec


def _run_batched(tasks: List[Dict]) -> List[Dict]:
    """Run sweep cells through the in-process replica-batched engine.

    Cells coalesce by resolved fleet spec: one spec string means one fleet
    shape *and* (via the fleet cache) shared ``GPUSpec`` objects, so every
    replica in a group fuses its estimator forwards and Algorithm-1 solves
    with the others (``core/sim/batch.py``).  Each group runs as one
    lockstep ``BatchSim``; per-replica metrics are bit-identical to the
    scalar engine, and ``wall_s`` is the group's wall-clock amortized over
    its members (lockstep execution has no per-cell attribution).

    A group whose build or run raises propagates the error.  Per-cell
    SIGALRM budgets cannot interrupt a lockstep round, so ``retries`` and
    ``cell_timeout`` do not apply to batched cells.
    """
    from repro.core.scenarios import get_scenario
    from repro.core.sim.batch import BatchSim

    _warm_runtime()
    groups: Dict[str, List[Dict]] = {}
    for task in tasks:
        sc = get_scenario(task["scenario"])
        groups.setdefault(task.get("fleet") or sc.fleet, []).append(task)
    results: List[Dict] = []
    for members in groups.values():
        t0 = time.time()
        built = [_build_cell(t, bool(t.get("profile"))) for t in members]
        ms = BatchSim([sim for sim, _ in built]).run()
        wall = (time.time() - t0) / len(members)
        for (sim, meta), m in zip(built, ms):
            out = _cell_result(meta, m, wall)
            if sim.prof is not None:
                out["profile"] = _profile_record(sim.prof, meta)
            results.append(out)
    return results


class CellTimeout(Exception):
    """A sweep cell exceeded its per-cell wall-clock budget."""


def _on_alarm(signum, frame):
    raise CellTimeout("cell exceeded its wall-clock budget")


def run_task_safe(task: Dict) -> Dict:
    """Crash-isolated :func:`run_task`: per-cell wall-clock budget
    (``task["cell_timeout"]`` seconds, SIGALRM) and bounded retry
    (``task["retries"]`` attempts).  The alarm is armed only when the
    platform has SIGALRM *and* we are on the main thread — CPython rejects
    ``signal.signal`` anywhere else — so off-main-thread or Windows runs
    degrade to a documented no-op (the cell runs unbounded) instead of
    crashing the grid.  A cell that exhausts its attempts returns an
    *error record* (same identity keys, an ``"error"`` string, no
    ``"metrics"``) instead of raising, so one diverging simulation cannot
    sink an hours-long grid."""
    timeout = task.get("cell_timeout")
    attempts = max(1, int(task.get("retries") or 1))
    use_alarm = (bool(timeout) and hasattr(signal, "SIGALRM")
                 and threading.current_thread() is threading.main_thread())
    err: Optional[BaseException] = None
    for _ in range(attempts):
        try:
            if use_alarm:
                old = signal.signal(signal.SIGALRM, _on_alarm)
                signal.setitimer(signal.ITIMER_REAL, float(timeout))
            try:
                return run_task(task)
            finally:
                if use_alarm:
                    signal.setitimer(signal.ITIMER_REAL, 0.0)
                    signal.signal(signal.SIGALRM, old)
        except Exception as e:
            err = e                      # recorded below, never swallowed
    from repro.core.scenarios import get_scenario
    sc = get_scenario(task["scenario"])
    return {
        "policy": task["policy"],
        "placer": task.get("placer") or sc.placer,
        "objective": task.get("objective") or sc.objective,
        "scenario": task["scenario"],
        "seed": task["seed"],
        "error": f"{type(err).__name__}: {err}",
        "attempts": attempts,
    }


def _task_key(task: Dict) -> Tuple[str, str, str, str, int]:
    """The identity of a cell inside a report, with the scenario's default
    placer / objective resolved exactly as :func:`run_task` resolves it."""
    from repro.core.scenarios import get_scenario
    sc = get_scenario(task["scenario"])
    return (task["scenario"], task["policy"],
            task.get("placer") or sc.placer,
            task.get("objective") or sc.objective, task["seed"])


def _load_resume_cells(path: str) -> Dict[Tuple, Dict]:
    """Successful cells of a partial report, keyed by cell identity.
    Cells recorded in ``report["errors"]`` — and any defensive error
    record that leaked into ``results`` — are *not* loaded, so a resumed
    sweep always re-runs them; a report from a different schema version
    resumes nothing — its metric columns would not line up with the cells
    this sweep produces."""
    with open(path) as f:
        rep = json.load(f)
    if rep.get("kind") != "miso-sweep":
        raise ValueError(f"{path} is not a miso-sweep report")
    if rep.get("schema_version") != SCHEMA_VERSION:
        return {}
    return {(r["scenario"], r["policy"], r["placer"], r["objective"],
             r["seed"]): r for r in rep.get("results", [])
            if "error" not in r and "metrics" in r}


def run_sweep(policies: Sequence[str], scenarios: Sequence[str],
              seeds: Sequence[int], placers: Optional[Sequence[str]] = None,
              objectives: Optional[Sequence[str]] = None,
              fleet: Optional[str] = None,
              n_jobs: Optional[int] = None, mtbf: Optional[float] = None,
              workers: Optional[int] = None, serial: bool = False,
              profile: bool = False, retries: int = 1,
              cell_timeout: Optional[float] = None,
              resume: Optional[str] = None,
              trace_cache: Optional[str] = None,
              engine: str = "pool") -> Dict:
    """Run the full grid and return the JSON-ready report dict.

    ``placers=None`` / ``objectives=None`` run each scenario's own default;
    an explicit list crosses it with every (policy, scenario, seed) cell.
    ``profile=True`` attaches per-component wall-clock (placement /
    Algorithm-1 / estimator / event loop) plus per-cell overhead buckets
    (generation / setup / total non-simulation time) to every result.
    ``retries`` / ``cell_timeout`` bound each cell (exhausted cells land in
    ``report["errors"]``); ``resume`` is the path of a partial report whose
    successful same-schema cells are carried over instead of re-run (its
    error cells are re-run).  ``trace_cache`` names a directory for the
    on-disk tier of the content-addressed trace cache (None = in-process
    memo only).  Parallel grids run on the persistent warm pool — see the
    module docstring; it refuses to start on a non-CPU backend.

    ``engine="batched"`` routes cells through the in-process
    replica-batched engine: cells sharing a resolved fleet spec run in
    lockstep with fused estimator / Algorithm-1 services and bit-identical
    per-cell metrics (coalesce rules: :func:`_run_batched`); a profiled
    batched cell reports its replica's share of each lockstep round."""
    tasks = [{"policy": p, "placer": pl, "objective": ob, "scenario": sc,
              "seed": s, "fleet": fleet, "n_jobs": n_jobs, "mtbf": mtbf,
              "profile": profile, "retries": retries,
              "cell_timeout": cell_timeout, "trace_cache": trace_cache}
             for sc in scenarios for p in policies
             for pl in (placers or [None])
             for ob in (objectives or [None]) for s in seeds]
    resumed: List[Dict] = []
    if resume is not None:
        done = _load_resume_cells(resume)
        if done:
            fresh = []
            for t in tasks:
                prev = done.get(_task_key(t))
                if prev is not None:
                    resumed.append(prev)
                else:
                    fresh.append(t)
            tasks = fresh
    t0 = time.time()
    batched_results: List[Dict] = []
    if engine == "batched" and tasks:
        batched_results, tasks = _run_batched(tasks), []
    if workers is None and not serial:
        # tiny grids (e.g. the CI smoke sweep) finish faster in-process than
        # a pool takes to start; an explicit --workers always gets the pool
        from repro.core.scenarios import get_scenario
        total_jobs = sum(t["n_jobs"] or get_scenario(t["scenario"]).n_jobs
                         for t in tasks)
        serial = total_jobs <= _AUTO_SERIAL_JOBS
    if not tasks:          # fully resumed or fully batched: nothing pooled
        results = []
        workers_used = 1
    elif serial or len(tasks) == 1:
        _warm_runtime()
        results = [run_task_safe(t) for t in tasks]
        workers_used = 1
    else:
        pool = _get_pool(workers)
        workers_used = _POOL_WORKERS
        try:
            results = list(pool.map(run_task_safe, tasks))
        except BrokenProcessPool:
            # a worker died hard (OOM, segfault in native code): rebuild
            # the warm pool once and retry the whole batch — cells are
            # idempotent, so a clean second pass is safe
            shutdown_pool()
            pool = _get_pool(workers)
            workers_used = _POOL_WORKERS
            results = list(pool.map(run_task_safe, tasks))
    errors = [r for r in results if "error" in r]
    results = [r for r in results if "error" not in r] + batched_results \
        + resumed
    sort_key = lambda r: (r["scenario"], r["policy"], r["placer"],
                          r["objective"], r["seed"])
    results.sort(key=sort_key)
    errors.sort(key=sort_key)

    # summary: scenario -> policy -> placer -> objective -> seed-mean
    # aggregates (the leaf levels are what let diff_sweeps compare placement
    # layers and optimization objectives)
    cells: Dict[tuple, List[Dict]] = {}
    for r in results:
        cells.setdefault((r["scenario"], r["policy"], r["placer"],
                          r["objective"]), []).append(r)
    summary: Dict[str, Dict] = {}
    for (sc, p, pl, ob), cell in cells.items():
        mean = lambda key: (sum(r["metrics"][key] for r in cell)
                            / len(cell))
        summary.setdefault(sc, {}).setdefault(p, {}).setdefault(pl, {})[ob] = {
            "avg_jct_s_mean": mean("avg_jct_s"),
            "p90_jct_s_mean": mean("p90_jct_s"),
            "stp_mean": mean("stp"),
            "makespan_s_mean": mean("makespan_s"),
            "energy_j_mean": mean("energy_j"),
            "energy_per_job_j_mean": mean("energy_per_job_j"),
            "goodput_mean": mean("goodput"),
            "work_lost_s_mean": mean("work_lost_s"),
        }

    report = {
        "schema_version": SCHEMA_VERSION,
        "kind": "miso-sweep",
        "config": {
            "policies": list(policies),
            "placers": list(placers) if placers else None,
            "objectives": list(objectives) if objectives else None,
            "scenarios": list(scenarios),
            "seeds": list(seeds),
            "fleet": fleet,          # null = each scenario's default fleet
            "n_jobs": n_jobs,        # null = each scenario's default length
            "mtbf_s": mtbf,
            "workers": workers_used,
            "serial": bool(serial or len(tasks) <= 1),
            "retries": retries,
            "cell_timeout_s": cell_timeout,
            "resumed_cells": len(resumed),
            "trace_cache": trace_cache,
            "engine": engine,
            # cells the batched engine ran
            "batched_cells": len(batched_results),
        },
        "wall_s_total": time.time() - t0,
        "results": results,
        "errors": errors,
        "summary": summary,
    }
    if profile:
        # stamp which determinism contract produced these numbers: the
        # misolint rule-set hash ties a benchmark JSON to the exact lint
        # rules the tree was clean under (see README "Static analysis")
        try:
            from misolint import ruleset_hash
            report["lint_version"] = ruleset_hash()
        except ImportError:     # lint tooling not on sys.path: stamp absent
            report["lint_version"] = None
    return report


def _print_summary(report: Dict) -> None:
    print(f"[sweep] {len(report['results'])} runs on "
          f"{report['config']['workers']} worker(s) in "
          f"{report['wall_s_total']:.1f}s")
    if report["config"]["resumed_cells"]:
        print(f"[sweep] resumed {report['config']['resumed_cells']} "
              f"cell(s) from a partial report")
    for e in report.get("errors", ()):
        print(f"[sweep] ERROR {e['scenario']}/{e['policy']}/{e['placer']}/"
              f"{e['objective']} seed={e['seed']}: {e['error']} "
              f"({e['attempts']} attempt(s))")
    w = max((len(s) for s in report["summary"]), default=8)
    for sc, by_policy in report["summary"].items():
        for p, by_placer in by_policy.items():
            for pl, by_obj in by_placer.items():
                for ob, agg in by_obj.items():
                    print(f"  {sc:<{w}}  {p:<10} {pl:<15} {ob:<11}"
                          f" avg_jct {agg['avg_jct_s_mean']:>9,.0f}s"
                          f"  p90 {agg['p90_jct_s_mean']:>9,.0f}s"
                          f"  stp {agg['stp_mean']:.3f}"
                          f"  energy {agg['energy_j_mean'] / 1e6:>7.2f}MJ")
    profiled = [r for r in report["results"] if r.get("profile")]
    if profiled:
        tot = {k: sum(r["profile"][k] for r in profiled)
               for k in ("placement_s", "alg1_s", "estimator_s",
                         "event_loop_s", "total_s")}
        n_ev = sum(r["profile"]["events"] for r in profiled)
        print(f"[sweep] profile: total {tot['total_s']:.2f}s over "
              f"{n_ev:,} events — placement {tot['placement_s']:.2f}s, "
              f"Algorithm-1 {tot['alg1_s']:.2f}s, estimator "
              f"{tot['estimator_s']:.2f}s, event loop "
              f"{tot['event_loop_s']:.2f}s")
        ov = [r["profile"] for r in profiled
              if "overhead_s" in r["profile"]]
        if ov:
            n = len(ov)
            mean_ms = lambda k: sum(o[k] for o in ov) / n * 1e3
            hits = sum(1 for o in ov if o.get("trace_src") != "fresh")
            print(f"[sweep] per-cell overhead: mean "
                  f"{mean_ms('overhead_s'):.1f} ms "
                  f"(gen {mean_ms('gen_s'):.1f} ms, "
                  f"setup {mean_ms('setup_s'):.1f} ms; "
                  f"trace cache {hits}/{n} hits)")
        # per-cell wall-clock spread: mean alone hides a grid whose tail
        # cell dominates the sweep; name the slowest cell so it can be
        # bounded (--cell-timeout) or investigated directly
        walls = sorted(r["wall_s"] for r in profiled)
        pct = lambda q: walls[min(len(walls) - 1,
                                  int(round(q * (len(walls) - 1))))]
        slow = max(profiled, key=lambda r: r["wall_s"])
        print(f"[sweep] per-cell wall: p50 {pct(0.50):.2f}s "
              f"p95 {pct(0.95):.2f}s; slowest {slow['scenario']}/"
              f"{slow['policy']}/{slow['placer']}/{slow['objective']} "
              f"seed={slow['seed']} at {slow['wall_s']:.2f}s")


def build_parser() -> argparse.ArgumentParser:
    ap = argparse.ArgumentParser(
        description="parallel policy x placer x objective x scenario x seed "
                    "simulation sweep")
    ap.add_argument("--policies", default="miso,srpt",
                    help="comma-separated policy names")
    ap.add_argument("--placers", default=None,
                    help="comma-separated placer names to cross with every "
                         "cell (see repro.core.sim.placement; default: each "
                         "scenario's own placer)")
    ap.add_argument("--objectives", default=None,
                    help="comma-separated objective names to cross with "
                         "every cell (see repro.core.sim.objectives; "
                         "default: each scenario's own objective)")
    ap.add_argument("--scenarios", default="bursty,diurnal,heavy_tail",
                    help="comma-separated scenario names "
                         "(see repro.core.scenarios)")
    ap.add_argument("--seeds", type=int, default=3,
                    help="number of seeds (0..N-1) per cell")
    ap.add_argument("--fleet", default=None,
                    help="fleet spec like a100:4+h100:4 "
                         "(default: each scenario's own fleet)")
    ap.add_argument("--jobs", type=int, default=None,
                    help="override each scenario's trace length")
    ap.add_argument("--mtbf", type=float, default=None,
                    help="accelerator MTBF seconds (fault injection); "
                         "overrides any scenario-bundled value, 0 disables "
                         "faults even for fault scenarios (default: each "
                         "scenario's own setting)")
    ap.add_argument("--workers", type=int, default=None,
                    help="worker processes (default: reuse the live warm "
                         "pool, else one per CPU)")
    ap.add_argument("--serial", action="store_true",
                    help="run in-process, no worker pool")
    ap.add_argument("--profile", action="store_true",
                    help="attach per-component wall-clock (placement, "
                         "Algorithm-1, estimator, event loop) and per-cell "
                         "overhead buckets (gen/setup/total) to every "
                         "result and print the totals")
    ap.add_argument("--retries", type=int, default=1,
                    help="attempts per cell before recording it as an "
                         "error cell (default 1: no retry)")
    ap.add_argument("--cell-timeout", type=float, default=None,
                    help="per-cell wall-clock budget in seconds (SIGALRM; "
                         "a timed-out attempt counts against --retries; "
                         "no-op off the main thread or without SIGALRM)")
    ap.add_argument("--resume", default=None,
                    help="partial report JSON whose successful same-schema "
                         "cells are carried over instead of re-run "
                         "(error cells are retried)")
    ap.add_argument("--trace-cache", default=None,
                    help="directory for the on-disk tier of the "
                         "content-addressed trace cache (default: "
                         "in-process memo only)")
    ap.add_argument("--engine", choices=("pool", "batched"),
                    default="pool",
                    help="cell execution engine: 'pool' runs one process "
                         "per cell on the warm worker pool; 'batched' "
                         "coalesces cells that share a fleet spec into "
                         "one in-process lockstep replica batch with "
                         "fused estimator/Algorithm-1 services "
                         "(bit-identical metrics). On a non-CPU backend "
                         "the pool refuses to start: use 'batched' or "
                         "--serial")
    ap.add_argument("--out", default="BENCH_sweep.json",
                    help="JSON report path")
    return ap


def main(argv=None) -> int:
    args = build_parser().parse_args(argv)
    from repro.core.scenarios import available_scenarios, get_scenario
    from repro.core.sim.objectives import get_objective
    from repro.core.sim.placement import get_placer
    from repro.core.sim.policies import available_policies, get_policy

    policies = [p.strip() for p in args.policies.split(",") if p.strip()]
    scenarios = [s.strip() for s in args.scenarios.split(",") if s.strip()]
    placers = ([p.strip() for p in args.placers.split(",") if p.strip()]
               if args.placers else None)
    objectives = ([o.strip() for o in args.objectives.split(",") if o.strip()]
                  if args.objectives else None)
    for p in policies:
        get_policy(p)                    # fail fast with the full list
    for s in scenarios:
        get_scenario(s)
    for pl in placers or ():
        get_placer(pl)
    for ob in objectives or ():
        get_objective(ob)

    from repro.launch import compile_cache
    compile_cache.enable()
    report = run_sweep(policies, scenarios, seeds=list(range(args.seeds)),
                       placers=placers, objectives=objectives,
                       fleet=args.fleet, n_jobs=args.jobs,
                       mtbf=args.mtbf, workers=args.workers,
                       serial=args.serial, profile=args.profile,
                       retries=args.retries, cell_timeout=args.cell_timeout,
                       resume=args.resume, trace_cache=args.trace_cache,
                       engine=args.engine)
    with open(args.out, "w") as f:
        json.dump(report, f, indent=1, sort_keys=False)
        f.write("\n")
    _print_summary(report)
    print(f"[sweep] report -> {args.out}")
    return 1 if report["errors"] else 0


if __name__ == "__main__":
    sys.exit(main())

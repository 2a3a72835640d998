"""Component benchmarks: predictor accuracy (paper §4.1), Algorithm-1
latency (paper §4.2/§8), kernel microbenches, TPU-pod adaptation."""
from __future__ import annotations

import copy
import random
import time

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks.common import (ARTIFACT, ORACLE_EST, PM, SPACE,
                               miso_estimator, row, run_policies,
                               testbed_trace)
from repro.core.optimizer import (_assign_dp, clear_memo, memo_stats,
                                  optimize_partition,
                                  optimize_partition_batch,
                                  optimize_partition_bruteforce)


def predictor_accuracy(fast=True):
    """Validation MAE (paper: 0.017) + linreg R^2 (paper: 0.96) + accuracy
    on completely fresh mixes."""
    import os
    if not os.path.exists(ARTIFACT):
        return [row("predictor_skipped", 0.0, "artifact missing")]
    t0 = time.time()
    from repro.core.predictor import dataset as ds
    from repro.core.predictor import unet
    from repro.core.predictor.train import load_artifact
    params, heads, hist = load_artifact(ARTIFACT)
    net = unet.UNet(params)
    fresh = ds.generate_dataset(PM, mixes_per_count=20 if fast else 100,
                                seed=31337)
    pred = np.asarray(net(jnp.asarray(fresh["val_x"])))
    mae = float(np.abs(pred - fresh["val_y"]).mean())
    return [row("predictor_accuracy", time.time() - t0,
                f"val_mae={hist['val_mae'][-1]:.4f};fresh_mix_mae={mae:.4f};"
                f"linreg_r2_2g={heads['r2'][0]:.3f};"
                f"linreg_r2_1g={heads['r2'][1]:.3f}")]


def _legacy_scan(space, speeds):
    """The pre-vectorization optimize_partition inner loop (dict DP per
    multiset, first-strict-max scan) — the un-memoized comparison baseline;
    ``_assign_dp`` is kept in-tree as the tie-break oracle."""
    best = None
    m = len(speeds)
    for part in space.partitions_of_len(m):
        obj, perm = _assign_dp(part, speeds)
        feasible = all(speeds[j].get(perm[j], 0.0) > 0.0 for j in range(m))
        if best is None or obj > best[0]:
            best = (obj, perm, feasible)
    return best


def _best_of(fn, reps, repeats=5):
    best = float("inf")
    for _ in range(repeats):
        t0 = time.perf_counter()
        for _ in range(reps):
            fn()
        best = min(best, (time.perf_counter() - t0) / reps)
    return best


def optimizer_latency(fast=True):
    """Algorithm 1 latency (paper: <=0.5ms; 80ms at 10x combinations).

    Reports, per co-location count m: the legacy scalar scan (dict DP per
    multiset — the pre-vectorization implementation), the vectorized
    single-decision pass, the batched per-decision cost when B same-tick
    decisions solve in one stacked DP (what the engine's same-tick
    coalescing exercises), and the memo cache's speedup on repeated
    repartitions.  The acceptance metric is the un-memoized batched
    speedup aggregated over the m grid (``optimizer_unmemoized_speedup``).
    """
    rng = random.Random(0)
    rows = []
    hits = misses = 0
    B = 16
    legacy_sum = vec_sum = batch_sum = 0.0
    reps = 30 if fast else 200
    for m in (3, 5, 7):
        speeds = []
        for _ in range(m):
            sv = {7: 1.0}
            for s in (4, 3, 2, 1):
                sv[s] = rng.uniform(0.1, 1.0)
            speeds.append(sv)
        mixes = [[{s: (v if s == 7 else rng.uniform(0.1, 1.0))
                   for s, v in sv.items()} for sv in speeds]
                 for _ in range(B)]
        legacy = _best_of(lambda: _legacy_scan(SPACE, speeds), reps)
        vec = _best_of(lambda: optimize_partition(SPACE, speeds, memo=False),
                       reps)
        batch = _best_of(lambda: optimize_partition_batch(SPACE, mixes,
                                                          memo=False),
                         max(reps // 4, 5)) / B
        bf = _best_of(lambda: optimize_partition_bruteforce(SPACE, speeds),
                      max(reps // 10, 5))
        # memoized repeated repartition: first call fills, the rest hit
        clear_memo()
        t0 = time.perf_counter()
        for _ in range(reps):
            optimize_partition(SPACE, speeds)
        memo = (time.perf_counter() - t0) / reps
        stats = memo_stats()
        hits += stats["hits"]
        misses += stats["misses"]
        legacy_sum += legacy
        vec_sum += vec
        batch_sum += batch
        rows.append(row(
            f"optimizer_m{m}", vec,
            f"legacy_ms={legacy*1e3:.3f};vec_ms={vec*1e3:.3f};"
            f"batch{B}_ms_per_decision={batch*1e3:.3f};"
            f"bruteforce_ms={bf*1e3:.3f};memo_ms={memo*1e3:.3f};"
            f"vec_speedup={legacy/max(vec, 1e-12):.1f}x;"
            f"batch_speedup={legacy/max(batch, 1e-12):.1f}x;"
            f"memo_speedup={legacy/max(memo, 1e-12):.1f}x"))
    rows.append(row(
        "optimizer_unmemoized_speedup", 0.0,
        f"single={legacy_sum/max(vec_sum, 1e-12):.1f}x;"
        f"batched_B{B}={legacy_sum/max(batch_sum, 1e-12):.1f}x;"
        f"legacy_total_ms={legacy_sum*1e3:.3f};"
        f"vec_total_ms={vec_sum*1e3:.3f};"
        f"batch_total_ms={batch_sum*1e3:.3f}"))
    rows.append(row("optimizer_memo_stats", 0.0,
                    f"hits={hits};misses={misses}"))
    return rows


def scheduling_policies(fast=True):
    """All registered policies head-to-head on one trace (the policy layer's
    reachability check: legacy five + miso-frag + srpt)."""
    from repro.core.simulator import available_policies
    jobs = testbed_trace(40 if fast else 100, lam=30.0, seed=13,
                         max_duration_s=1800)
    res = run_policies(jobs, available_policies(), n_gpus=4,
                       estimator=miso_estimator())
    n, _ = res["nopart"]
    rows = []
    for pol in available_policies():
        m, t = res[pol]
        rows.append(row(f"policy_{pol}", t,
                        f"jct_gain_vs_nopart={1 - m.avg_jct / n.avg_jct:+.3f};"
                        f"stp={m.stp:.3f};completed={len(m.jcts)}"))
    return rows


def kernel_bench(fast=True):
    """Pure-JAX flash vs naive attention on CPU (wall time + peak-residual
    note); Pallas kernels run in interpret mode for correctness, so their
    timing is not meaningful off-TPU — FLOPs parity is reported instead."""
    from repro.models import flash, modules
    rows = []
    B, S, H, D = 2, 1024, 4, 64
    ks = jax.random.split(jax.random.PRNGKey(0), 3)
    q = jax.random.normal(ks[0], (B, S, H, D))
    k = jax.random.normal(ks[1], (B, S, H, D))
    v = jax.random.normal(ks[2], (B, S, H, D))
    pos = jnp.arange(S, dtype=jnp.int32)

    def loss_flash(q, k, v):
        return flash.flash_attention(q, k, v, q_positions=pos,
                                     kv_positions=pos, causal=True,
                                     block_q=128, block_kv=128).sum()

    def loss_naive(q, k, v):
        return modules.naive_attention(q, k, v, q_positions=pos,
                                       kv_positions=pos, causal=True).sum()

    for name, fn in (("flash", loss_flash), ("naive", loss_naive)):
        g = jax.jit(jax.grad(fn, argnums=(0, 1, 2)))
        g(q, k, v)[0].block_until_ready()  # compile
        reps = 3 if fast else 10
        t0 = time.time()
        for _ in range(reps):
            g(q, k, v)[0].block_until_ready()
        rows.append(row(f"attn_bwd_{name}_S{S}", (time.time() - t0) / reps,
                        "custom-vjp flash vs naive, CPU wall time"))
    return rows


def trace_scaling(fast=True):
    """Engine scalability: replay synthetic Alibaba-distribution traces at
    growing fleet sizes (the indexed-hot-path acceptance curve).

    Each cell drives one miso run over a homogeneous a100 fleet of ``n``
    GPUs with ``min(20*n, 100_000)`` jobs; the arrival rate scales with the
    fleet (``load_scale = n/16``) so per-GPU utilization stays roughly
    constant and wall time isolates the engine's per-event cost.  The full
    grid ends at the 5,000-GPU / 100K-job cell, whose wall time must stay
    under 5 minutes single-process."""
    from repro.core.fleet import homogeneous_fleet
    from repro.core.simulator import ClusterSim, SimConfig
    from repro.core.traces_alibaba import synthesize_alibaba_trace
    sizes = (8, 64, 512) if fast else (8, 64, 512, 2048, 5000)
    fleet_proto = homogeneous_fleet(SPACE, PM, ORACLE_EST, 1)[0]
    rows = []
    # the us/event rows feed CI's regression gate (diff_sweeps.py components
    # mode), so take the min over a few identical replays: the sim is
    # deterministic, only the wall clock is noisy, and min-of-N is the
    # standard noise floor estimator for a deterministic workload
    reps = 5 if fast else 1
    for n in sizes:
        n_jobs = min(20 * n, 100_000)
        jobs = synthesize_alibaba_trace(n_jobs, seed=7, load_scale=n / 16.0,
                                        max_duration_s=7200.0)
        cfg = SimConfig(n_gpus=n, policy="miso", profile=True)
        wall = float("inf")
        for _ in range(reps):
            sim = ClusterSim(copy.deepcopy(jobs), cfg,
                             fleet=[fleet_proto] * n)
            t0 = time.perf_counter()
            m = sim.run()
            wall = min(wall, time.perf_counter() - t0)
        p = sim.prof
        rows.append(row(
            f"trace_scaling_n{n}", wall / max(p["events"], 1.0),
            f"gpus={n};jobs={len(jobs)};wall_s={wall:.2f};"
            f"events={int(p['events'])};completed={len(m.jcts)};"
            f"jobs_per_s={len(m.jcts) / max(wall, 1e-9):.0f};"
            f"placement_s={p['placement_s']:.2f};"
            f"alg1_s={p['alg1_s']:.2f};estimator_s={p['estimator_s']:.2f}"))
    return rows


def batch_rollout(fast=True):
    """Replica-batched engine vs the warm-pool path on a B=16 smoke grid.

    The rollout this measures is the sweep driver's: B independent cells,
    same fleet shape, different (policy, seed).  The warm-pool side
    dispatches each cell to its worker pool; the batched side runs all B
    cells in one lockstep ``BatchSim`` in-process.  Event counts come from
    one profiled serial pass (the sim is deterministic, so every engine
    replays the identical event stream).

    Two baselines, because the pool path's cost depends on who is asking:

    * ``pool_wall_s`` — what ``--engine pool`` costs a *fresh driver
      process* (one CLI sweep): worker spawn + import + jit-warm
      initializer + the cells.  This is the cost the in-process batched
      engine eliminates outright, and the >=4x acceptance target is
      measured against it (measured once — it is cold by definition).
    * ``pool_warm_wall_s`` — the amortized per-sweep cost inside a
      long-lived driver that reuses the warm pool (min-of-reps after a
      warm-up sweep).  Recorded so nobody mistakes the headline for the
      amortized regime: against this baseline the batched engine wins
      only the fused-dispatch margin (~2x here), because both engines
      pay the same per-event scalar machinery and the bit-identity
      contract forbids approximating it away.

    The gated column is the batched engine's aggregate us/event (walls
    are min-of-reps); derived records both baselines' events/sec and both
    speedups against the >=4x target.  The pool runs only on a CPU backend
    (one process owns an accelerator), so on any other backend both pool
    baselines and the speedups read "not measured"."""
    from repro.launch.sweep import run_sweep, shutdown_pool

    B = 16
    kw = dict(policies=["miso", "srpt"], scenarios=["smoke"],
              seeds=list(range(B // 2)))
    # one profiled serial pass for the denominators (not timed)
    prof = run_sweep(serial=True, profile=True, **kw)
    events = sum(r["profile"]["events"] for r in prof["results"])
    reps = 3 if fast else 10
    batched_wall = float("inf")
    rep = None
    for _ in range(reps):
        t0 = time.perf_counter()
        rep = run_sweep(serial=True, engine="batched", **kw)
        batched_wall = min(batched_wall, time.perf_counter() - t0)
    assert rep["config"]["batched_cells"] == B, "batched engine skipped cells"
    derived = (f"B={B};events={events};"
               f"batched_wall_s={batched_wall:.3f};"
               f"batched_events_per_s={events / max(batched_wall, 1e-9):.0f};")
    if jax.default_backend() != "cpu":
        derived += (f"pool_wall_s=not measured ({jax.default_backend()} "
                    f"backend);speedup=not measured;target=4.00x")
        return [row("batch_rollout", batched_wall / max(events, 1), derived)]
    shutdown_pool()                            # cold-driver baseline
    t0 = time.perf_counter()
    run_sweep(workers=1, **kw)
    pool_wall = time.perf_counter() - t0
    pool_warm = float("inf")                   # amortized baseline
    for _ in range(reps):
        t0 = time.perf_counter()
        run_sweep(workers=1, **kw)
        pool_warm = min(pool_warm, time.perf_counter() - t0)
    shutdown_pool()
    return [row(
        "batch_rollout", batched_wall / max(events, 1),
        derived
        + f"pool_wall_s={pool_wall:.3f};pool_warm_wall_s={pool_warm:.3f};"
        f"pool_events_per_s={events / max(pool_wall, 1e-9):.0f};"
        f"pool_warm_events_per_s={events / max(pool_warm, 1e-9):.0f};"
        f"speedup={pool_wall / batched_wall:.2f}x;"
        f"speedup_warm={pool_warm / batched_wall:.2f}x;target=4.00x")]


def tpu_cluster(fast=True):
    """MISO over TPU-pod sub-slices (the DESIGN.md adaptation)."""
    from repro.core.estimators import OracleEstimator
    from repro.core.partitions import tpu_pod_space
    from repro.core.perfmodel import PerfModel, TPU_V5E_POD
    from repro.core.simulator import SimConfig, simulate
    from repro.core.traces import generate_trace
    t0 = time.time()
    space = tpu_pod_space()
    pm = PerfModel(space, TPU_V5E_POD)
    jobs = generate_trace(60 if fast else 200, lam_s=20.0, seed=77)
    est = OracleEstimator(pm)
    m = simulate(jobs, SimConfig(n_gpus=4, policy="miso"), space, pm, est)
    n = simulate(jobs, SimConfig(n_gpus=4, policy="nopart"), space, pm, est)
    return [row("tpu_pod_miso", time.time() - t0,
                f"jct_gain={1 - m.avg_jct / n.avg_jct:+.3f};"
                f"slices=2x16..16x16;pods=4")]


# --------------------------------------------------------------- reporting


def write_report(path: str, fast: bool = True) -> dict:
    """Write the component-latency JSON report (``BENCH_components.json``,
    schema v1) consumed by CI for perf-trajectory tracking.  Rows mirror the
    CSV harness: (name, us_per_call, derived key=value pairs)."""
    import json
    report = {
        "schema_version": 1,
        "kind": "miso-components",
        "rows": [{"name": n, "us_per_call": float(us), "derived": d}
                 for n, us, d in (optimizer_latency(fast=fast)
                                  + scheduling_policies(fast=fast)
                                  + trace_scaling(fast=fast)
                                  + batch_rollout(fast=fast))],
    }
    with open(path, "w") as f:
        json.dump(report, f, indent=1)
        f.write("\n")
    return report


def main(argv=None) -> int:
    import argparse
    ap = argparse.ArgumentParser(
        description="component benchmarks -> BENCH_components.json")
    ap.add_argument("--out", default="BENCH_components.json")
    ap.add_argument("--full", action="store_true")
    args = ap.parse_args(argv)
    rep = write_report(args.out, fast=not args.full)
    for r in rep["rows"]:
        print(f"{r['name']},{r['us_per_call']},{r['derived']}")
    print(f"[components] report -> {args.out}")
    return 0


if __name__ == "__main__":
    import sys
    sys.exit(main())

"""MISO cluster controller driver: the paper's Fig 6 pipeline end-to-end.

Central controller + per-accelerator server API over a job trace:
FCFS queue -> least-loaded placement -> MPS profiling (interference-prone
co-run) -> U-Net MPS->MIG translation -> Algorithm 1 -> dynamic partitions.
The execution backend is the event simulator (DESIGN.md §2): the
accelerators are simulated, and only the U-Net forward runs on the device
JAX holds.  With ``--space tpu`` the accelerators are v5e pods
partitioned into contiguous sub-mesh slices and each slice maps onto a
JAX mesh of its ``mesh_shape`` with axes ``launch.mesh.SLICE_AXES``
(printed per slice with ``--show-meshes``, without building it on devices).

``--policy`` accepts any registered scheduling policy
(``repro/core/sim/policies/``):

* ``nopart``    — exclusive whole-GPU execution (paper baseline)
* ``optsta``    — best static MIG partition, never reconfigured
* ``mpsonly``   — MPS co-location at a fixed level, no partitioning
* ``miso``      — the paper's policy: MPS probe -> predict -> repartition
* ``oracle``    — perfect knowledge, zero overhead (upper bound)
* ``miso-frag`` — MISO preferring partitions that keep large contiguous
                  slices free (fragmentation-aware)
* ``srpt``      — MISO with a preemptive shortest-remaining-work queue

``--placer`` accepts any registered placement layer
(``repro/core/sim/placement.py``): ``least-loaded`` (paper default),
``hetero-speed`` (long jobs to fast GPUs on mixed fleets), ``frag-aware``
(keep large contiguous slices free), ``best-fit-slice`` (tightest feasible
partition wins).

``--objective`` accepts any registered Algorithm-1 goal
(``repro/core/sim/objectives.py``): ``throughput`` (the paper's Eq. 2–4,
bit-identical default), ``energy`` (min joules per unit work subject to a
QoS floor), ``edp`` (energy-delay product).  Every run reports the
fleet-integrated energy alongside JCT/STP.

  PYTHONPATH=src python -m repro.launch.cluster --policy miso --jobs 60
  PYTHONPATH=src python -m repro.launch.cluster --policy srpt --lam 20
  PYTHONPATH=src python -m repro.launch.cluster --space tpu --show-meshes
  PYTHONPATH=src python -m repro.launch.cluster --fleet a100:4+h100:4
  PYTHONPATH=src python -m repro.launch.cluster --fleet a100:4+h100:4 \\
      --placer hetero-speed

``--fleet`` runs a heterogeneous cluster (per-GPU slice menus / perf models,
see ``repro.core.fleet``); scenario x policy grids over fleets are driven in
parallel by ``python -m repro.launch.sweep``.
"""
from __future__ import annotations

import argparse
import math
import sys

from repro.core.estimators import NoisyEstimator, OracleEstimator, UNetEstimator
from repro.core.partitions import a100_mig_space, tpu_pod_space
from repro.core.perfmodel import A100, TPU_V5E_POD, PerfModel
from repro.core.simulator import (SimConfig, available_objectives,
                                  available_placers, available_policies,
                                  simulate)
from repro.core.traces import generate_trace

def _a100_artifact():
    """The committed a100 predictor artifact (per-kind name, with the
    legacy un-suffixed predictor.npz accepted), or None."""
    from repro.core.fleet import default_artifact_path
    return default_artifact_path("a100")


def build_parser() -> argparse.ArgumentParser:
    ap = argparse.ArgumentParser()
    ap.add_argument("--space", choices=["a100", "tpu"], default="a100")
    ap.add_argument("--fleet", default=None,
                    help="heterogeneous fleet spec, e.g. a100:4+h100:4 "
                         "(overrides --space/--accelerators/--estimator)")
    ap.add_argument("--policy", default="miso", choices=available_policies())
    ap.add_argument("--placer", default="least-loaded",
                    choices=available_placers(),
                    help="placement layer: which feasible GPU a queued job "
                         "lands on (least-loaded = paper default)")
    ap.add_argument("--objective", default="throughput",
                    choices=available_objectives(),
                    help="Algorithm-1 goal: what the partition search "
                         "optimizes (throughput = paper default; energy/edp "
                         "trade JCT for joules)")
    ap.add_argument("--estimator", default="auto",
                    choices=["auto", "unet", "oracle", "noisy"])
    ap.add_argument("--sigma", type=float, default=0.05)
    ap.add_argument("--accelerators", type=int, default=8)
    ap.add_argument("--jobs", type=int, default=100)
    ap.add_argument("--lam", type=float, default=60.0,
                    help="mean inter-arrival time in seconds (1/rate, "
                         "not the Poisson rate itself)")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--mtbf", type=float, default=0.0,
                    help="accelerator MTBF seconds (fault injection)")
    from repro.core.simulator import available_fault_injectors
    ap.add_argument("--faults", default=None,
                    help="comma-separated fault injectors to enable with "
                         "demo chaos knobs (available: "
                         f"{', '.join(available_fault_injectors())}); "
                         "repeated faults quarantine the GPU and migrate "
                         "its residents off")
    ap.add_argument("--show-meshes", action="store_true")
    return ap


# demo knobs applied per enabled injector by --faults (the flaky_fleet
# scenario's settings); sweeps wanting full control use scenario sim_kwargs
_FAULT_DEMO_KNOBS = {
    "mps_blast": {"mps_crash_mtbf_s": 1500.0},
    "flaky_reconfig": {"reconfig_fail_p": 0.15, "reconfig_retry_s": 15.0,
                       "reconfig_max_retries": 2},
    "straggler": {"straggler_mtbf_s": 700.0, "straggler_factor": 0.25,
                  "straggler_recover_s": 100000.0},
    "estimator_garbage": {"estimator_fault_p": 0.2},
}


def _fault_kwargs(spec: str | None) -> dict:
    """SimConfig overrides for a ``--faults`` spec (empty dict when off)."""
    if not spec:
        return {}
    from repro.core.simulator import get_fault_injector
    names = tuple(s.strip() for s in spec.split(",") if s.strip())
    for n in names:
        get_fault_injector(n)            # fail fast with the full list
    kw: dict = {"faults": names, "ckpt_interval_s": 240.0,
                "quarantine_faults": 2, "quarantine_window_s": 3600.0,
                "quarantine_repair_s": 480.0}
    for n in names:
        kw.update(_FAULT_DEMO_KNOBS.get(n, {}))
    return kw


def _print_robustness(metrics) -> None:
    print(f"  goodput   : {metrics.goodput:.3f} committed work-seconds/s/"
          f"accelerator (gross {metrics.gross_stp:.3f}, "
          f"{metrics.work_lost_s:,.0f} work-s destroyed)")
    print(f"  faults    : {metrics.n_fault_events} events | "
          f"{metrics.blast_jobs} blast kills (max radius "
          f"{metrics.blast_radius_max}) | {metrics.n_quarantines} "
          f"quarantines | {metrics.n_migrations} migrations | "
          f"quarantine occupancy {metrics.quarantine_occupancy:.1%}")


def main(argv=None):
    args = build_parser().parse_args(argv)
    from repro.launch import compile_cache
    compile_cache.enable()

    if args.fleet:
        from repro.core.fleet import describe_fleet, parse_fleet
        fleet = parse_fleet(args.fleet)
        jobs = generate_trace(args.jobs, lam_s=args.lam, seed=args.seed)
        cfg = SimConfig(n_gpus=len(fleet), policy=args.policy,
                        placer=args.placer, objective=args.objective,
                        gpu_mtbf_s=args.mtbf, seed=args.seed,
                        **_fault_kwargs(args.faults))
        metrics = simulate(jobs, cfg, fleet=fleet)
        b = metrics.breakdown
        by_kind = {s.kind: type(s.estimator).__name__ for s in fleet}
        ests = ", ".join(f"{k}={v}" for k, v in by_kind.items())
        print(f"[cluster] {args.policy} (placer {args.placer}, objective "
              f"{args.objective}) on fleet {describe_fleet(fleet)}: "
              f"{len(metrics.jcts)} jobs (per-kind estimators: {ests})")
        print(f"  avg JCT   : {metrics.avg_jct:,.0f} s "
              f"(p50 {metrics.p50_jct:,.0f}, p90 {metrics.p90_jct:,.0f})")
        print(f"  makespan  : {metrics.makespan:,.0f} s")
        print(f"  STP       : {metrics.stp:.3f} work-seconds/s/accelerator")
        print(f"  energy    : {metrics.energy_j / 3.6e6:,.2f} kWh "
              f"({metrics.avg_power_w:,.0f} W cluster avg, "
              f"{metrics.energy_per_job_j / 3.6e6:,.3f} kWh/job)")
        print(f"  breakdown : queue {b['queue']:,.0f}s | mps {b['mps']:,.0f}s"
              f" | ckpt {b['ckpt']:,.0f}s | run {b['run']:,.0f}s")
        if args.faults:
            _print_robustness(metrics)
        return 0

    if args.space == "tpu":
        space, hw = tpu_pod_space(), TPU_V5E_POD
    else:
        space, hw = a100_mig_space(), A100
    pm = PerfModel(space, hw)

    artifact = _a100_artifact() if args.space == "a100" else None
    if args.estimator == "oracle" or args.policy == "oracle":
        est = OracleEstimator(pm)
    elif args.estimator == "noisy":
        est = NoisyEstimator(pm, sigma=args.sigma, seed=args.seed)
    elif args.estimator == "unet" or (args.estimator == "auto"
                                      and artifact is not None):
        if args.space != "a100":
            raise SystemExit(
                "[cluster] --estimator unet: no U-Net predictor exists for "
                f"the {args.space} space (its slice menu does not match the "
                "net's 7g/4g/3g output rows); use --estimator oracle")
        if artifact is None:
            raise SystemExit(
                "[cluster] --estimator unet: no trained a100 artifact found; "
                "train one with  PYTHONPATH=src python -m "
                "repro.core.predictor.train --kinds a100")
        est = UNetEstimator.from_artifact(pm, artifact)
        print("[cluster] estimator: trained U-Net + linreg heads")
    else:
        est = OracleEstimator(pm)
        print("[cluster] estimator: oracle (no artifact / tpu space)")

    jobs = generate_trace(args.jobs, lam_s=args.lam, seed=args.seed)
    cfg = SimConfig(n_gpus=args.accelerators, policy=args.policy,
                    placer=args.placer, objective=args.objective,
                    gpu_mtbf_s=args.mtbf, seed=args.seed,
                    **_fault_kwargs(args.faults))
    metrics = simulate(jobs, cfg, space, pm, est)

    if args.show_meshes and args.space == "tpu":
        from repro.launch.mesh import SLICE_AXES
        print("[cluster] slice -> JAX mesh mapping:")
        for size in sorted(space.slices):
            st = space.slices[size]
            if st.mesh_shape:
                print(f"  {st.name}: mesh {st.mesh_shape} axes "
                      f"{SLICE_AXES} = {math.prod(st.mesh_shape)} devices")

    b = metrics.breakdown
    print(f"[cluster] {args.policy} on {args.accelerators} x {args.space}: "
          f"{len(metrics.jcts)} jobs")
    print(f"  avg JCT   : {metrics.avg_jct:,.0f} s (p50 {metrics.p50_jct:,.0f},"
          f" p90 {metrics.p90_jct:,.0f})")
    print(f"  makespan  : {metrics.makespan:,.0f} s")
    print(f"  STP       : {metrics.stp:.3f} work-seconds/s/accelerator")
    print(f"  energy    : {metrics.energy_j / 3.6e6:,.2f} kWh "
          f"({metrics.avg_power_w:,.0f} W cluster avg)")
    print(f"  breakdown : queue {b['queue']:,.0f}s | mps {b['mps']:,.0f}s | "
          f"ckpt {b['ckpt']:,.0f}s | run {b['run']:,.0f}s")
    if args.faults:
        _print_robustness(metrics)
    return 0


if __name__ == "__main__":
    sys.exit(main())

#!/usr/bin/env python3
"""Run one cell of the on-chip benchmark and print its result.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Everything is found by name from ``BENCHMARK.json`` at the root of the
checkout: the cell names a configuration (``bench/configs/<config>.json``)
and a traffic mix (``bench/traffic/<traffic>.json``), and each metric of the
cell is read by ``bench/metrics/<metric>.py``.  Nothing here branches on a
cell.

Before any work, the configuration is checked against what the reference
states (``bench/ref/``): its policy, placer and objective, and for each
group of its fleet the program's spec of that kind (the MIG menu, the
speed model's constants and the speed scale).  A mismatch exits non-zero
and names the key.

Set-up (``setup_s``) runs from the start of this script to the first timed
replay: JAX and the chip, the program's fleet with each group's GPUs on an
estimator with the benchmark's copy of that group's predictor weights, the
run's traces dealt from ``--seed`` and the program's jobs made from them,
and each group's U-Net programs at every batch size the mix sends, taken
from the persistent compilation cache in ``.jax_cache/`` of the
checkout.  The window then runs whole replays back to
back, a closed loop: replay ``r`` builds ``replicas`` fresh ``ClusterSim``
replicas, replica ``i`` from a deep copy of trace ``(r * replicas + i) mod
traces``, and drives them through ``BatchSim.run()``; the window ends at the
first replay boundary after ``--seconds``.  After it, the device's peak
memory is read and ``check.py`` compares the run with the plain reference.
``--trace 1`` adds the program's profile counters, the benchmark's spans and
a profiler trace of the window's first seconds, and reports the per-layer
metrics instead of the end-to-end ones.

Without a TPU, or with fewer chips than the cell asks for, it exits
non-zero before any work and prints no result.  The last line of standard
output is the result as JSON; the numbers compared are the last lines of
standard error.
"""
from __future__ import annotations

import time

_T0 = time.perf_counter()

import argparse  # noqa: E402
import copy  # noqa: E402
import dataclasses  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
import traceback  # noqa: E402
import types  # noqa: E402

BENCH = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH)
#: seconds of the window that the profiler traces in a ``--trace 1`` run
TRACE_SECONDS = 4.0


def log(msg: str) -> None:
    print(f"[bench] {msg}", file=sys.stderr, flush=True)


def load_cell(root: str, name: str, trace: bool) -> dict:
    """The cell ``name`` of ``<root>/BENCHMARK.json`` with its
    configuration, traffic mix and the metrics it reports (per-layer ones
    with ``trace``), each metric with its reader loaded from
    ``<root>/bench/metrics/<metric>.py``."""
    with open(os.path.join(root, "BENCHMARK.json")) as fh:
        spec = json.load(fh)
    cells = {w["name"]: w for w in spec["workloads"]}
    if name not in cells:
        raise SystemExit(f"unknown workload {name!r}; known: "
                         f"{', '.join(sorted(cells))}")
    cell = dict(cells[name])
    with open(os.path.join(root, "bench", "configs",
                           cell["config"] + ".json")) as fh:
        cell["config_data"] = json.load(fh)
    with open(os.path.join(root, "bench", "traffic",
                           cell["traffic"] + ".json")) as fh:
        cell["traffic_data"] = json.load(fh)
    metrics = []
    for m in spec["per_layer" if trace else "end_to_end"]:
        if name not in m.get("workloads", [name]):
            continue
        path = os.path.join(root, "bench", "metrics", m["name"] + ".py")
        mod_spec = importlib.util.spec_from_file_location(
            "bench_metric_" + m["name"].replace(".", "_").replace("-", "_"),
            path)
        if mod_spec is None:
            raise SystemExit(f"no reader for metric {m['name']!r} at {path}")
        mod = importlib.util.module_from_spec(mod_spec)
        mod_spec.loader.exec_module(mod)
        metrics.append((m, mod.read))
    cell["metrics"] = metrics
    return cell


def require_chips(n: int):
    """The first device, or exit: no other backend stands in for a TPU."""
    import jax

    devs = jax.devices()
    if devs[0].platform != "tpu" or len(devs) < n:
        sys.exit(f"bench: needs {n} TPU chip(s); JAX has {len(devs)} "
                 f"{devs[0].platform!r} device(s); nothing was run")
    return devs[0]


def program_spec(kind: str):
    """The program's spec of ``kind`` as its factory in ``FLEET_KINDS``
    builds it, with no predictor artifact: loading one would start JAX."""
    from repro.core import fleet

    make = fleet.FLEET_KINDS[kind]
    make = getattr(make, "__wrapped__", make)   # past the factory's memo
    artifact_path = fleet.default_artifact_path
    fleet.default_artifact_path = lambda kind: None
    try:
        return make()
    finally:
        fleet.default_artifact_path = artifact_path


def spec_gaps(group: dict, spec):
    """``(key, stated, program's)`` for each key of ``group`` that the
    reference reads and the program's ``spec`` holds otherwise: the speed
    scale, the MIG menu and the hardware constants."""
    from ref.testbed import HARDWARE

    mig, space, hw = group["mig"], spec.space, spec.pm.hw
    yield "speed_scale", group["speed_scale"], spec.speed_scale
    yield "mig.compute_slots", mig["compute_slots"], space.total_compute
    yield "mig.memory_slots", mig["memory_slots"], space.total_mem
    yield ("mig.exclusions", sorted(sorted(e) for e in mig["exclusions"]),
           sorted(sorted(e) for e in space.exclusions))
    stated = {s["size"]: s for s in mig["slices"]}
    yield "mig.slices (sizes)", sorted(stated), sorted(space.sizes)
    for size, st in sorted(space.slices.items()):
        s = stated[size]
        for key, theirs in (("name", st.name),
                            ("compute_slots", st.compute_slots),
                            ("memory_slots", st.mem_slots),
                            ("memory_gb", st.memory_gb),
                            ("max_count", st.max_count),
                            ("cache_frac", st.cache_frac)):
            yield f"mig.slices[{size}].{key}", s.get(key), theirs
    for key in HARDWARE:
        yield f"hardware.{key}", group["hardware"].get(key), getattr(hw, key)


def check_config(config: dict) -> list:
    """The fleet's groups with the program's spec of each, or exit before
    any work where the configuration asks for what the reference does not
    state or states a group otherwise than the program's spec."""
    from ref.fleet import groups
    from ref.sim import STATES
    from repro.core.fleet import FLEET_KINDS

    for key, want in STATES.items():
        if config.get(key) != want:
            sys.exit(f"bench: {key} {config.get(key)!r}: the reference "
                     f"states only {want!r}; nothing was run")
    try:
        fleet = groups(config)
    except ValueError as e:
        sys.exit(f"bench: {e}; nothing was run")
    out = []
    for i, g in enumerate(fleet):
        where = f"fleet group {i} ({g['kind']})"
        if g["kind"] not in FLEET_KINDS:
            sys.exit(f"bench: {where}: kind is none of the program's "
                     f"{sorted(FLEET_KINDS)}; nothing was run")
        spec = program_spec(g["kind"])
        for key, stated, theirs in spec_gaps(g, spec):
            if stated != theirs:
                sys.exit(f"bench: {where}: {key} is {stated!r} in the "
                         f"configuration and {theirs!r} in the program's "
                         f"spec; nothing was run")
        out.append((g, spec))
    return out


def program_fleet(groups: list, root: str):
    """The program's fleet: each group's GPUs on its spec, with an
    estimator on the benchmark's copy of the group's predictor weights, in
    GPU-id order; and each group's U-Net."""
    import jax.numpy as jnp
    import numpy as np

    from repro.core.estimators import UNetEstimator

    fleet, nets = [], []
    for g, spec in groups:
        pred = g["predictor"]
        with np.load(os.path.join(root, pred["weights"])) as z:
            params = {k: jnp.asarray(z[k]) for k in z.files
                      if not k.startswith("__")}
            heads = {"w": np.asarray(z["__head_w"]),
                     "r2": np.asarray(z["__head_r2"])}
        est = UNetEstimator(spec.pm, params, heads, jobs=pred["jobs"])
        fleet += [dataclasses.replace(spec, estimator=est)] * g["gpus"]
        nets.append(est.net)
    return fleet, nets


def run_cell(cell: dict, groups: list, seed: int, seconds: float,
             trace: bool, dev, trace_dir: str, root: str):
    """Set up, run the window, check; returns what the readers read."""
    from probe import Probe

    probe = Probe(spans=trace)
    try:
        return _run(cell, groups, seed, seconds, trace, dev, trace_dir,
                    probe, root)
    finally:
        probe.close()


def _run(cell, groups, seed, seconds, trace, dev, trace_dir, probe, root):
    import jax
    import numpy as np

    from probe import BUILD, STEP
    from repro.core.jobs import Job, JobProfile
    from repro.core.sim.batch import BatchSim
    from repro.core.sim.engine import ClusterSim, SimConfig

    import check
    from traffic import traces as deal

    config, traffic = cell["config_data"], cell["traffic_data"]
    fleet, nets = program_fleet(groups, root)
    pool = [JobProfile(**row) for row in config["workloads"]]
    traces = deal(traffic, len(pool), seed)
    jobs = [[Job(jid=i, profile=pool[int(p)], arrival=float(a), work=float(w))
             for i, (p, a, w) in enumerate(zip(tr["pick"], tr["arrival"],
                                               tr["work"]))]
            for tr in traces]
    for net in nets:
        levels, cols = net.levels, net.jobs
        for b in range(1, traffic["unet_batch_max"] + 1):
            np.asarray(net(np.zeros((b, levels, cols), np.float32)))
        np.asarray(net(np.zeros((levels, cols), np.float32)))
    b_rep, n_tr = traffic["replicas"], len(traces)
    cfgs = [SimConfig(n_gpus=len(fleet), policy=config["policy"],
                      placer=config["placer"], objective=config["objective"],
                      seed=i, profile=trace, **config["sim"])
            for i in range(b_rep)]

    run = types.SimpleNamespace(step_s=[], replays=0, jobs=0, offered=0,
                                events=0, prof={}, spans=probe.spans,
                                trace=None, finished={}, trace_of={})
    traced = {"on": False}

    def build(r):
        return [ClusterSim(copy.deepcopy(jobs[(r * b_rep + i) % n_tr]), c,
                           fleet=fleet) for i, c in enumerate(cfgs)]
    build = probe.spanned(BUILD, build)

    def timed_step(step):
        def one():
            t = time.perf_counter()
            with probe.annotate(STEP):
                live = step()
            run.step_s.append(time.perf_counter() - t)
            if traced["on"] and time.perf_counter() >= traced["until"]:
                stop_trace()
            return live
        return one

    def stop_trace():
        run.traced_calls = probe.calls - traced["calls"]
        traced["ann"].__exit__(None, None, None)
        jax.profiler.stop_trace()
        traced["on"] = False

    probe.calls, probe.batches = 0, type(probe.batches)()
    compiles0 = probe.compiles
    run.setup_s = time.perf_counter() - _T0
    t0 = time.perf_counter()
    if trace:
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        jax.profiler.start_trace(trace_dir, profiler_options=opts)
        traced.update(on=True, until=t0 + TRACE_SECONDS, calls=probe.calls,
                      ann=probe.annotate("traced"))
        traced["ann"].__enter__()
    while True:
        r = run.replays
        sims = build(r)
        probe.own(r, sims)
        try:
            bs = BatchSim(sims)
            bs.step = timed_step(bs.step)
            bs.run()
        except Exception:   # a replay that fails is reported, not hidden
            traceback.print_exc()
            seconds = 0.0
        for i, s in enumerate(sims):
            run.finished[r, i] = np.array(
                [np.nan if j.finish_time is None else j.finish_time
                 for j in s.jobs.values()])
            run.trace_of[r, i] = (r * b_rep + i) % n_tr
            run.jobs += len(s.completed)
            run.offered += len(s.jobs)
            run.events += next(s._counter) - len(s.events)
            if trace:
                for k, v in s.prof.items():
                    run.prof[k] = run.prof.get(k, 0.0) + v
        run.replays += 1
        if time.perf_counter() - t0 >= seconds:
            break
    run.elapsed_s = time.perf_counter() - t0
    if traced["on"]:
        stop_trace()
    probe.own(-1, [])
    run.compiles_in_window = probe.compiles - compiles0
    run.unet_calls = probe.calls
    run.unet_batches = dict(sorted(probe.batches.items()))
    stats = dev.memory_stats() or {}
    run.memory_peak_bytes = stats.get("peak_bytes_in_use")
    if trace:
        import trace_reduce
        run.trace = trace_reduce.reduce_dir(trace_dir)
    sims = bs = None    # the program's state goes before the reference runs
    run.check = check.compare(config, traces, run.trace_of, run.finished,
                              probe.windows, probe.decisions, probe.strays,
                              seed, root)
    return run


def prepare() -> None:
    """Before JAX is imported: the compile cache in the checkout, at a
    fixed path (the program's ``compile_cache.enable()`` keeps to this
    variable), and the program and the benchmark on the path."""
    os.environ["JAX_COMPILATION_CACHE_DIR"] = os.path.join(ROOT,
                                                           ".jax_cache")
    for p in (BENCH, os.path.join(ROOT, "src")):
        if p not in sys.path:
            sys.path.insert(0, p)


def main(argv=None, root: str = ROOT, chips_check=require_chips) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    if args.seconds <= 0:
        ap.error("--seconds must be > 0")
    cell = load_cell(root, args.workload, bool(args.trace))
    prepare()
    groups = check_config(cell["config_data"])
    dev = chips_check(cell["chips"])
    from repro.launch import compile_cache
    compile_cache.enable()
    import jax

    import check

    with tempfile.TemporaryDirectory(prefix="bench_trace_") as tdir:
        run = run_cell(cell, groups, args.seed, args.seconds,
                       bool(args.trace), dev, tdir, root)
    chk = run.check
    log(f"replays {run.replays} in {run.elapsed_s!r} s; events {run.events}; "
        f"jobs {run.jobs} of {run.offered}; U-Net calls {run.unet_calls}, "
        f"batch sizes {run.unet_batches}; compiles inside the window "
        f"{run.compiles_in_window}")
    log(f"reference: {chk['replicas']} replicas, {chk['windows']} windows, "
        f"{chk['decisions']} decisions in {chk['seconds']!r} s")
    metrics = {}
    for m, read in cell["metrics"]:
        value = read(run)
        if value is not None:
            metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(jax.devices()),
              "memory_peak_bytes": run.memory_peak_bytes}
    checks = {k: {"value": v, "limit": check.LIMITS[k]}
              for k, v in chk["numbers"].items()}
    correct = (all(c["value"] <= c["limit"] for c in checks.values())
               and run.jobs == run.offered)
    out = {"correct": correct, "attempted": run.offered,
           "failed": run.offered - run.jobs, "metrics": metrics,
           "device": device}
    if run.trace is not None and run.trace["devices"]:
        device["busy_s"] = run.trace["busy_s"]
        device["window_s"] = run.trace["window_s"]
        out["breakdown"] = run.trace["breakdown"]
    out["checks"] = checks
    for k, c in checks.items():
        log(f"check {k} {float(c['value'])!r} limit {c['limit']!r}")
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())

#!/usr/bin/env python3
"""Drive the simulator's device path once on one TPU chip and check it.

    python3 chip_smoke.py

Everything runs in this one process, which owns the chip.  The device path
is the U-Net MPS->MIG predictor that every a100/h100 profiling window calls.
Phases, each of which must pass:

(a) The CI sweep grids through ``repro.launch.sweep --engine batched
    --serial``, each report checked by ``benchmarks/diff_sweeps.py`` against
    its committed baseline with the CI gate (2% on JCT, STP and energy).
(b) The U-Net forward on the distinct MPS matrices phase (a) sent to it,
    run on the TPU and on the host CPU of this process: the largest
    absolute difference must stay within 1e-5.  The same difference at the
    TPU's default convolution precision is printed beside it.
(c) A production-shaped replay: 10,000 jobs synthesized from the Alibaba
    sample on a 512-GPU a100+h100 fleet under ``miso``, through ``BatchSim``
    at B=1 and then at B=8 seeds.  Every job must complete with finite
    metrics, and the B=8 replica that repeats the B=1 run must agree with
    it within the same 2% gate.

Each phase prints one ``[chip_smoke]`` line: its wall time split into
set-up, compile and run, simulated events, U-Net dispatches and the largest
batch bucket, compilations (JAX monitoring) during warm-up and inside the
run, persistent-cache hits, the device kind and the device's peak bytes in
use.  The last line of standard output is the JSON verdict.  Without a TPU
the script exits non-zero before any work, and nothing is caught so that a
run can go on: any failure ends it with a non-zero exit and no verdict.

It writes only the sweep reports, under ``chiprun_out/chip_smoke/``, and
the compilation cache (see ``repro.launch.compile_cache``).
"""
from __future__ import annotations

import collections
import copy
import functools
import json
import math
import os
import sys
import time
from unittest import mock

ROOT = os.path.dirname(os.path.abspath(__file__))
OUT_DIR = os.path.join(ROOT, "chiprun_out", "chip_smoke")

#: phase (a): the CI sweep commands (.github/workflows/ci.yml) and the
#: committed baseline each report is gated against
SWEEPS = (
    ("smoke", ["--scenarios", "smoke", "--seeds", "2"]),
    ("hetero", ["--scenarios", "hetero_smoke", "--policies", "miso,srpt",
                "--placers", "least-loaded,hetero-speed", "--seeds", "2"]),
    ("trace", ["--scenarios", "trace_replay,trace_synth",
               "--policies", "miso,srpt", "--seeds", "1"]),
)
#: phase (b): largest |TPU - CPU| accepted on the U-Net's outputs
MAX_ABS_DIFF = 1e-5
#: phase (c): the production-shaped replay (ROADMAP W1, cut to size)
REPLAY_FLEET = "a100:384+h100:128"
REPLAY_JOBS = 10_000
REPLAY_SEED = 7
REPLAY_B = 8
REPLAY_REDUCED = ("W1 is ~6,500 GPUs and 100K+ jobs; cut to 512 GPUs and "
                  "10K jobs so that the smoke stays within a few minutes")
_COMPILE_EVENTS = ("/jax/core/compile/jaxpr_trace_duration",
                   "/jax/core/compile/jaxpr_to_mlir_module_duration",
                   "/jax/core/compile/backend_compile_duration")


def _tpu():
    """The chip, or exit non-zero: no other backend stands in for it."""
    import jax
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        sys.exit(f"chip_smoke: no TPU found (JAX's first device is "
                 f"{dev.platform!r}); nothing was run")
    return dev


Mark = collections.namedtuple(
    "Mark", "compiles cache_hits dispatches events t")


class Probe:
    """What the process did, counted from the moment it is installed.

    Compilations come from JAX's monitoring events: a backend compile
    event fires for every program JAX compiles or takes from the
    persistent cache, and compile time is the union of the spans of
    tracing, lowering and backend compiles (they nest).  U-Net
    dispatches, their batch buckets and, while ``collect`` is set, the MPS
    matrices sent come from a wrapper around ``UNet.__call__``.
    Simulated events come from every ``BatchSim.run``, whose replicas get
    the engine's event counter."""

    def __init__(self):
        import jax
        import numpy as np

        from repro.core.predictor import unet
        from repro.core.sim.batch import BatchSim

        self.compiles = []            # function name per backend compile
        self.spans = []               # (start, end) of compile work
        self.cache_hits = 0
        self.dispatches = 0
        self.max_bucket = 0
        self.events = 0
        self.collect = False
        self.matrices = {}            # id(params) -> [np.ndarray (L, J)]
        jax.monitoring.register_event_time_span_listener(self._on_span)
        jax.monitoring.register_event_listener(self._on_event)
        call, run = unet.UNet.__call__, BatchSim.run
        probe = self

        @functools.wraps(call)
        def counted_call(net, mps_matrix):
            m = np.asarray(mps_matrix, np.float32)
            rows = m.reshape((-1,) + m.shape[-2:])
            probe.dispatches += 1
            probe.max_bucket = max(probe.max_bucket, unet._bucket(len(rows)))
            if probe.collect:
                probe.matrices.setdefault(id(net.params), []).append(rows)
            return call(net, mps_matrix)

        @functools.wraps(run)
        def counted_run(batch):
            for sim in batch.sims:
                if sim.prof is None:
                    sim.prof = dict.fromkeys(("placement_s", "alg1_s",
                                              "estimator_s", "total_s",
                                              "events"), 0.0)
            out = run(batch)
            probe.events += int(sum(s.prof["events"] for s in batch.sims))
            return out

        unet.UNet.__call__ = counted_call
        BatchSim.run = counted_run

    def _on_span(self, event, start, end, **kw):
        if event in _COMPILE_EVENTS:
            self.spans.append((start, end))
        if event == _COMPILE_EVENTS[-1]:
            self.compiles.append(kw.get("fun_name", "?"))

    def _on_event(self, event, **kw):
        if event == "/jax/compilation_cache/cache_hits":
            self.cache_hits += 1

    def compile_s(self, t0, t1):
        """Seconds of [t0, t1] (``time.time()``) spent compiling."""
        total, reach = 0.0, t0
        for start, end in sorted(self.spans):
            start, end = max(start, reach), min(end, t1)
            if end > start:
                total += end - start
                reach = end
        return total

    def mark(self):
        return Mark(len(self.compiles), self.cache_hits, self.dispatches,
                    self.events, time.time())


def _report(phase, probe, dev, start, run_from, extra):
    """Print one phase's line; ``start``/``run_from`` are ``Probe.mark()``
    taken at the phase's start and where its set-up ends."""
    end = probe.mark()
    setup_compile = probe.compile_s(start.t, run_from.t)
    run_compile = probe.compile_s(run_from.t, end.t)
    stats = dev.memory_stats() or {}
    rec = {
        "phase": phase,
        "wall_s": end.t - start.t,
        "setup_s": run_from.t - start.t - setup_compile,
        "compile_s": setup_compile + run_compile,
        "run_s": end.t - run_from.t - run_compile,
        "events": end.events - start.events,
        "unet_dispatches": end.dispatches - start.dispatches,
        "compiles_setup": run_from.compiles - start.compiles,
        "compiles_in_run": end.compiles - run_from.compiles,
        "compiles_in_run_by_fn": dict(collections.Counter(
            probe.compiles[run_from.compiles:end.compiles])),
        "cache_hits": end.cache_hits - start.cache_hits,
        "device_kind": dev.device_kind,
        "peak_bytes_in_use": stats.get("peak_bytes_in_use"),
    }
    rec.update(extra)
    print("[chip_smoke] " + json.dumps(rec), flush=True)


def phase_a(dev, probe):
    """The CI sweep grids in this process, gated against the baselines."""
    from benchmarks import diff_sweeps
    from repro.core.predictor import unet
    from repro.launch import sweep

    start = probe.mark()
    unet.warm_jit_cache()
    run_from = probe.mark()
    probe.collect, probe.max_bucket = True, 0
    verdicts = {}
    for name, argv in SWEEPS:
        out = os.path.join(OUT_DIR, f"BENCH_sweep_{name}.json")
        rc = sweep.main(argv + ["--engine", "batched", "--serial",
                                "--out", out])
        if rc != 0:
            raise RuntimeError(f"sweep {name} exited {rc}")
        base = os.path.join(ROOT, "benchmarks", "baselines",
                            f"BENCH_sweep_{name}.json")
        if diff_sweeps.main([base, out]) != 0:
            raise RuntimeError(f"sweep {name} fails the 2% gate vs {base}")
        verdicts[name] = "ok"
    probe.collect = False
    _require_dispatches("a", probe, run_from)
    _report("a", probe, dev, start, run_from,
            {"max_bucket": probe.max_bucket, "diff_sweeps": verdicts})


def _require_dispatches(phase, probe, since):
    """A phase that sent nothing to the U-Net never reached the device."""
    if probe.dispatches == since.dispatches:
        raise RuntimeError(f"phase {phase} made no U-Net dispatch")


def phase_b(dev, probe, params_kind):
    """Predictor agreement: the same jitted forward on the TPU and on the
    host CPU, over the distinct matrices phase (a) sent to the U-Net."""
    import jax
    import numpy as np

    from repro.core.predictor import unet

    cpu = jax.devices("cpu")[0]
    start = run_from = probe.mark()
    diffs, rows = {}, {}
    for pid, mats in probe.matrices.items():
        params, kind = params_kind[pid]
        m = np.unique(np.concatenate(mats), axis=0)
        ref = unet._apply_jit(jax.device_put(params, cpu),
                              jax.device_put(m, cpu), 3, 7)
        p_dev, m_dev = jax.device_put(params, dev), jax.device_put(m, dev)
        pinned = unet._apply_jit(p_dev, m_dev, 3, 7)
        # the TPU's default precision, for comparison only: a fresh jit
        # traced while the module's pin is lifted
        with mock.patch.object(unet, "PRECISION", None):
            default = jax.jit(lambda p, x: unet.apply(p, x))(p_dev, m_dev)
        ref = np.asarray(ref)
        diffs[kind] = {
            "pinned": float(np.abs(np.asarray(pinned) - ref).max()),
            "default_precision": float(np.abs(np.asarray(default)
                                              - ref).max())}
        rows[kind] = len(m)
    if set(rows) != {"a100", "h100"}:
        raise RuntimeError(f"phase (a) fed the U-Net of {sorted(rows)} "
                           f"only; expected a100 and h100")
    worst = max(d["pinned"] for d in diffs.values())
    _report("b", probe, dev, start, run_from,
            {"matrices": rows, "max_abs_diff": diffs,
             "limit": MAX_ABS_DIFF})
    if not worst <= MAX_ABS_DIFF:
        raise RuntimeError(f"U-Net TPU vs CPU max |diff| {worst} > "
                           f"{MAX_ABS_DIFF}")


def _replay_metrics(m, n_jobs):
    """The checked end-to-end numbers of one replica."""
    out = {"completed": len(m.jcts), "avg_jct_s": m.avg_jct,
           "stp": m.stp, "energy_j": m.energy_j}
    if out["completed"] != n_jobs:
        raise RuntimeError(f"replay completed {out['completed']} of "
                           f"{n_jobs} jobs")
    if not all(math.isfinite(v) and v > 0 for v in out.values()):
        raise RuntimeError(f"replay metrics not finite and positive: {out}")
    return out


def phase_c(dev, probe):
    """Production-shaped replay through BatchSim at B=1, then B=8."""
    from repro.core.fleet import parse_fleet
    from repro.core.sim.batch import BatchSim
    from repro.core.simulator import ClusterSim, SimConfig
    from repro.core.traces_alibaba import synthesize_alibaba_trace

    fleet = parse_fleet(REPLAY_FLEET)
    n = len(fleet)
    traces = {}
    results = {}
    for b in (1, REPLAY_B):
        start = probe.mark()
        seeds = [REPLAY_SEED + i for i in range(b)]
        for s in seeds:
            if s not in traces:
                traces[s] = synthesize_alibaba_trace(
                    REPLAY_JOBS, seed=s, load_scale=n / 16.0)
        sims = [ClusterSim(copy.deepcopy(traces[s]),
                           SimConfig(n_gpus=n, policy="miso", seed=s,
                                     profile=True), fleet=fleet)
                for s in seeds]
        run_from = probe.mark()
        probe.max_bucket = 0
        ms = BatchSim(sims).run()
        _require_dispatches(f"c B={b}", probe, run_from)
        results[b] = [_replay_metrics(m, len(traces[s]))
                      for m, s in zip(ms, seeds)]
        extra = {"B": b, "fleet": REPLAY_FLEET, "trace_rows": REPLAY_JOBS,
                 "jobs": sum(len(traces[s]) for s in seeds),
                 "max_bucket": probe.max_bucket,
                 "replica0": results[b][0], "reduced": REPLAY_REDUCED}
        if b != 1:
            one, rep0 = results[1][0], results[b][0]
            rel = {k: abs(rep0[k] - one[k]) / abs(one[k])
                   for k in ("avg_jct_s", "stp", "energy_j")}
            extra["replica0_vs_B1"] = {"identical": rep0 == one,
                                       "max_rel_diff": max(rel.values())}
            if max(rel.values()) > 0.02:
                raise RuntimeError(f"B={b} replica 0 disagrees with the "
                                   f"B=1 run beyond 2%: {rel}")
        _report(f"c B={b}", probe, dev, start, run_from, extra)


def _unet_params():
    """id(params) -> (params, kind) for the fleet's U-Net estimators; fails
    when a kind runs the oracle instead of the trained predictor."""
    from repro.core.estimators import UNetEstimator
    from repro.core.fleet import parse_fleet

    out = {}
    for spec in parse_fleet("a100:1+h100:1"):
        if not isinstance(spec.estimator, UNetEstimator):
            raise RuntimeError(f"{spec.kind} runs "
                               f"{type(spec.estimator).__name__}, not the "
                               f"U-Net predictor")
        out[id(spec.estimator.net.params)] = (spec.estimator.net.params,
                                              spec.kind)
    return out


def main() -> int:
    dev = _tpu()
    sys.path[:0] = [os.path.join(ROOT, "src"), ROOT]
    from repro.launch import compile_cache
    compile_cache.enable()
    import jax

    os.makedirs(OUT_DIR, exist_ok=True)
    params_kind = _unet_params()
    probe = Probe()
    phase_a(dev, probe)
    phase_b(dev, probe, params_kind)
    phase_c(dev, probe)
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(jax.devices())}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())

"""The simulator's profile buckets (``SimConfig(profile=True)``).

``ClusterSim.prof`` is a flat dict of floats: seconds on the host's
``time.perf_counter`` and a few counters.  ``sweep --profile`` reports it
per cell and the benchmark sums it over replicas, so every key exists from
the start and every value stays a float.  Each timed bucket below also
opens a ``jax.profiler.TraceAnnotation`` (the span name after the key), so
the host's spans sit on the device trace's clock.

* ``placement_s`` — every ``Policy.pick_gpu``;
* ``alg1_s`` — Algorithm 1 run inline (not in ``BatchSim`` stage C);
* ``estimator_s`` — estimates made inline (not in stage A), with their
  MPS measurement;
* ``total_s`` — the scalar engine's ``run()``;
* ``events`` — events popped off the heap;
* ``collect_s`` (``sim.collect``) — a replica's ``run_until_collect`` in a
  ``BatchSim`` round;
* ``mps_measure_s`` (``mps.measure``) — the simulated MPS probe: its
  noise draw in ``MisoPolicy._measure`` and, in a ``BatchSim`` round, the
  noise-free matrices that stage A solves for the windows;
* ``fused_estimator_s`` (``batch.stage_a``) — ``BatchSim`` stage A, whole;
* ``unet_host_s`` (``unet.host``) — stack the matrices, copy them in, pad,
  launch the forward, crop;
* ``unet_wait_s`` (``unet.wait``) — the host blocked until the forward's
  result is on the host;
* ``estimator_post_s`` (``estimator.post``) — linreg heads and the
  memory/QoS monitor;
* ``unet_calls`` — U-Net forwards;
* ``fused_alg1_s`` (``batch.stage_c``) — ``BatchSim`` stage C, whole;
* ``round_apply_s`` (``batch.apply``) — ``BatchSim`` stages B and D;
* ``unet_windows`` — MPS windows the U-Net's forwards carried, padding
  rows not counted;
* ``stage_a_runs`` — stage A runs (each had windows);
* ``stage_a_forwards`` — ``estimate_batch`` calls inside them, one per
  estimator object, so one per weight set in a fleet of several kinds;
* ``stage_c_runs`` — stage C runs (each had decisions);
* ``stage_c_solves`` — stacked ``optimize_partition_batch`` calls inside
  them, one per (MIG menu, power model, objective);
* ``mps_rows`` — (mix, level) fixed points stage A's MPS probe asked
  ``PerfModel.mps_matrices`` for, ``mps_rows_hit`` those its memo
  answered, ``mps_rows_stacked`` those solved in its stacked pass (the
  rest of the misses were solved one by one).

Nesting: placement, the inline Algorithm 1 and estimator and the MPS
probe's noise draw run inside ``collect_s`` or ``round_apply_s``; the
U-Net and post-processing buckets inside ``fused_estimator_s`` or
``estimator_s``, and so does the rest of the MPS probe.
``collect_s``, ``fused_estimator_s``, ``fused_alg1_s`` and
``round_apply_s`` are disjoint and together make up a replica's rounds.
Stage A's and stage C's buckets and counters are split across the
replicas they served (:func:`split`), so ``stage_a_forwards /
stage_a_runs`` over a batch's replicas is the mean number of U-Net
groups a stage A ran.
"""
from __future__ import annotations

import time
from typing import Dict, Optional, Sequence

import jax

KEYS = ("placement_s", "alg1_s", "estimator_s", "total_s", "events",
        "collect_s", "mps_measure_s", "fused_estimator_s", "unet_host_s",
        "unet_wait_s", "estimator_post_s", "unet_calls", "fused_alg1_s",
        "round_apply_s", "unet_windows", "stage_a_runs", "stage_a_forwards",
        "stage_c_runs", "stage_c_solves", "mps_rows", "mps_rows_hit",
        "mps_rows_stacked")


def new_prof() -> Dict[str, float]:
    """A profile with every bucket at 0.0."""
    return dict.fromkeys(KEYS, 0.0)


class timed:
    """Add the ``perf_counter`` seconds of the ``with`` body to
    ``prof[key]``, inside a ``jax.profiler.TraceAnnotation(name)``.

    Callers keep their ``if prof is not None`` guard: with profiling off
    nothing here runs."""

    __slots__ = ("prof", "key", "ann", "t0")

    def __init__(self, prof: Dict[str, float], key: str, name: str):
        self.prof = prof
        self.key = key
        self.ann = jax.profiler.TraceAnnotation(name)

    def __enter__(self):
        self.ann.__enter__()
        self.t0 = time.perf_counter()
        return self

    def __exit__(self, *exc):
        self.prof[self.key] += time.perf_counter() - self.t0
        self.ann.__exit__(*exc)
        return False


def split(scratch: Dict[str, float],
          owners: Sequence[Optional[Dict[str, float]]]) -> None:
    """Book a fused stage's ``scratch`` buckets to the replicas it served.

    ``owners`` holds the ``prof`` of the replica behind each item of work
    (None for an unprofiled replica); each profiled replica gets the share
    of every bucket that its items are of all the items."""
    items = [(k, v) for k, v in scratch.items() if v]
    shares: Dict[int, list] = {}
    for p in owners:
        if p is not None:
            s = shares.get(id(p))
            if s is None:
                shares[id(p)] = [p, 1]
            else:
                s[1] += 1
    n = len(owners)
    for p, c in shares.values():
        f = c / n
        for k, v in items:
            p[k] += v * f

"""From a profiler trace (``.xplane.pb``) to the benchmark's device numbers.

The traced part of the window is the host span named ``traced`` that the
harness opens right after starting the profiler.  Inside it:

* device busy time: the union of the intervals of the events on each TPU
  plane's ``XLA Ops`` line, averaged over the TPU planes;
* the U-Net's device time: the summed durations of the ``XLA Modules``
  events of its jitted forward (``_apply_jit``);
* the device operations that took most time, by op name;
* idle time (no op on the device) split by what the host was doing: the
  benchmark's spans (``event_loop``, ``estimator``, ``alg1``, ``build``),
  the rest of a ``step``, or outside any step.

Timestamps are the profiler's, in nanoseconds on one clock for host and
device planes.
"""
from __future__ import annotations

import collections
import glob
import os
from typing import Dict, Iterable, List, Optional, Tuple

Interval = Tuple[float, float]
TRACED = "traced"
UNET_MODULE = "_apply_jit"
_HOST_SPANS = ("event_loop", "estimator", "alg1", "build")


def union(intervals: Iterable[Interval]) -> List[Interval]:
    """Sorted, disjoint intervals covering the same points."""
    out: List[List[float]] = []
    for s, e in sorted(intervals):
        if e <= s:
            continue
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def length(merged: Iterable[Interval]) -> float:
    return sum(e - s for s, e in merged)


def clip(merged: Iterable[Interval], lo: float, hi: float) -> List[Interval]:
    return [(max(s, lo), min(e, hi)) for s, e in merged
            if e > lo and s < hi]


def complement(merged: List[Interval], lo: float,
               hi: float) -> List[Interval]:
    """The parts of [lo, hi] that ``merged`` leaves uncovered."""
    out, t = [], lo
    for s, e in clip(merged, lo, hi):
        if s > t:
            out.append((t, s))
        t = max(t, e)
    if hi > t:
        out.append((t, hi))
    return out


def intersect(a: List[Interval], b: List[Interval]) -> List[Interval]:
    """Intersection of two sorted, disjoint interval lists."""
    out, i, j = [], 0, 0
    while i < len(a) and j < len(b):
        s, e = max(a[i][0], b[j][0]), min(a[i][1], b[j][1])
        if e > s:
            out.append((s, e))
        if a[i][1] < b[j][1]:
            i += 1
        else:
            j += 1
    return out


def _events(line):
    for ev in line.events:
        yield ev.name, float(ev.start_ns), float(ev.start_ns + ev.duration_ns)


def reduce_file(path: str, top: int = 10) -> Optional[Dict]:
    """The numbers of one trace; None when it holds no ``traced`` span."""
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(path)
    host: Dict[str, List[Interval]] = collections.defaultdict(list)
    tpus = []
    for plane in pd.planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                for name, s, e in _events(line):
                    host[name].append((s, e))
        elif plane.name.startswith("/device:TPU:"):
            tpus.append(plane)
    if not host.get(TRACED):
        return None
    lo, hi = host[TRACED][0]
    busy_total, unet_ns = 0.0, 0.0
    ops: Dict[str, float] = collections.defaultdict(float)
    busy_union: List[Interval] = []
    for plane in tpus:
        op_iv = []
        for line in plane.lines:
            if line.name == "XLA Ops":
                for name, s, e in _events(line):
                    op_iv.append((s, e))
                    s2, e2 = max(s, lo), min(e, hi)
                    if e2 > s2:
                        ops[name] += e2 - s2
            elif line.name == "XLA Modules":
                for name, s, e in _events(line):
                    if UNET_MODULE in name:
                        unet_ns += max(0.0, min(e, hi) - max(s, lo))
        merged = clip(union(op_iv), lo, hi)
        busy_total += length(merged)
        busy_union = union(busy_union + merged)
    idle = complement(busy_union, lo, hi)
    gaps: Dict[str, float] = {}
    left = idle
    for name in _HOST_SPANS:
        inside = clip(union(host.get(name, [])), lo, hi)
        gaps[name] = length(intersect(left, inside))
        left = intersect(left, complement(inside, lo, hi))
    steps = clip(union(host.get("step", [])), lo, hi)
    gaps["step_other"] = length(intersect(left, steps))
    gaps["outside_steps"] = length(left) - gaps["step_other"]
    n = max(len(tpus), 1)
    return {
        "devices": len(tpus),
        "window_s": (hi - lo) * 1e-9,
        "busy_s": busy_total / n * 1e-9,
        "unet_device_s": unet_ns / n * 1e-9,
        "breakdown": {
            "device_ops": [[k, v / n * 1e-9] for k, v in sorted(
                ops.items(), key=lambda kv: -kv[1])[:top]],
            "idle_gaps": [[k, v / n * 1e-9] for k, v in sorted(
                gaps.items(), key=lambda kv: -kv[1])[:top] if v > 0],
        },
    }


def reduce_dir(trace_dir: str) -> Optional[Dict]:
    """:func:`reduce_file` of the one ``.xplane.pb`` under ``trace_dir``."""
    paths = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if len(paths) != 1:
        raise RuntimeError(f"expected one .xplane.pb under {trace_dir}, "
                           f"found {len(paths)}")
    return reduce_file(paths[0])

"""The reduction from a profiler trace to the benchmark's device numbers.

The interval arithmetic is checked against a sweep over every boundary,
and the whole reduction (busy union, idle share, idle time by host span,
``_apply_jit`` device time per call) on a trace built with the planes and
lines a TPU run writes, against numbers worked out from its events by
hand.  A trace recorded on the chip is still to be added."""
from __future__ import annotations

import os

import numpy as np
import pytest

import benchutil  # noqa: F401  (puts bench/ on the path)
import trace_reduce as tr


def _covered(intervals, lo, hi):
    """Length of [lo, hi] covered by any interval: a sweep over every
    boundary, counting open intervals."""
    pts = sorted({lo, hi, *(p for iv in intervals for p in iv)})
    total = 0.0
    for a, b in zip(pts, pts[1:]):
        if a >= lo and b <= hi and any(s <= a and b <= e
                                       for s, e in intervals):
            total += b - a
    return total


@pytest.mark.parametrize("seed", range(4))
def test_interval_arithmetic_matches_a_sweep(seed):
    rng = np.random.default_rng(seed)
    a = [tuple(sorted(rng.integers(0, 1000, 2).astype(float)))
         for _ in range(40)]
    b = [tuple(sorted(rng.integers(0, 1000, 2).astype(float)))
         for _ in range(25)]
    lo, hi = 100.0, 900.0
    ua, ub = tr.union(a), tr.union(b)
    assert all(s < e for s, e in ua)
    assert all(e1 < s2 for (_, e1), (s2, _) in zip(ua, ua[1:]))
    assert tr.length(tr.clip(ua, lo, hi)) == _covered(a, lo, hi)
    assert tr.length(tr.complement(ua, lo, hi)) == \
        (hi - lo) - _covered(a, lo, hi)
    both = tr.intersect(tr.clip(ua, lo, hi), tr.clip(ub, lo, hi))
    pts = sorted({lo, hi, *(p for iv in a + b for p in iv)})
    want = sum(q - p for p, q in zip(pts, pts[1:])
               if lo <= p and q <= hi
               and any(s <= p and q <= e for s, e in a)
               and any(s <= p and q <= e for s, e in b))
    assert tr.length(both) == want


def test_reduce_needs_the_traced_span(tmp_path):
    assert not os.path.exists(tmp_path / "x.xplane.pb")
    with pytest.raises(RuntimeError):
        tr.reduce_dir(str(tmp_path))


def _xspace(path):
    """A trace with the planes and lines a TPU run writes, in µs: host
    spans on the ``python3`` thread and device ops on the TPU plane."""
    from jax.profiler import ProfileData

    def events(items):
        return "".join(f"events {{ metadata_id: {m} offset_ps: {s * 10**6} "
                       f"duration_ps: {(e - s) * 10**6} }}\n"
                       for m, s, e in items)

    def meta(names):
        return "".join(f'event_metadata {{ key: {i} value {{ id: {i} '
                       f'name: "{n}" }} }}\n' for i, n in enumerate(names, 1))

    host = ["traced", "step", "event_loop", "estimator", "alg1"]
    host_ev = [(1, 0, 100), (2, 10, 50), (2, 60, 90), (3, 10, 20),
               (3, 60, 70), (4, 20, 40), (4, 70, 85), (5, 40, 45)]
    dev = ["convolution.1", "fusion.2", "jit__apply_jit(1)",
           "jit_concatenate(2)"]
    ops = [(1, 25, 30), (2, 28, 33), (1, 75, 80), (2, 95, 105)]
    mods = [(3, 25, 33), (3, 75, 80), (4, 95, 105)]
    txt = (f'planes {{ id: 1 name: "/device:TPU:0"\n'
           f'lines {{ id: 1 name: "XLA Ops" timestamp_ns: 0\n{events(ops)}}}\n'
           f'lines {{ id: 2 name: "XLA Modules" timestamp_ns: 0\n'
           f'{events(mods)}}}\n{meta(dev)}}}\n'
           f'planes {{ id: 2 name: "/host:CPU"\n'
           f'lines {{ id: 1 name: "python3" timestamp_ns: 0\n'
           f'{events(host_ev)}}}\n{meta(host)}}}\n')
    with open(path, "wb") as fh:
        fh.write(ProfileData.text_proto_to_serialized_xspace(txt))


def test_reduction_of_a_tpu_shaped_trace(tmp_path):
    import types

    d = tmp_path / "plugins" / "profile" / "run"
    d.mkdir(parents=True)
    _xspace(str(d / "host.xplane.pb"))
    got = tr.reduce_dir(str(tmp_path))
    us = 1e-6
    assert got["devices"] == 1
    assert got["window_s"] == pytest.approx(100 * us)
    # busy: [25, 33) + [75, 80) + [95, 100) once the last op is clipped
    assert got["busy_s"] == pytest.approx(18 * us)
    assert got["unet_device_s"] == pytest.approx(13 * us)
    assert dict(got["breakdown"]["device_ops"]) == pytest.approx(
        {"convolution.1": 10 * us, "fusion.2": 10 * us})
    assert dict(got["breakdown"]["idle_gaps"]) == pytest.approx(
        {"event_loop": 20 * us, "estimator": 22 * us, "alg1": 5 * us,
         "step_other": 10 * us, "outside_steps": 25 * us})
    import run

    readers = {m["name"]: read for m, read in
               run.load_cell(benchutil.REPO, "testbed.replay-b1",
                             True)["metrics"]}
    traced = types.SimpleNamespace(trace=got, traced_calls=2)
    assert readers["device_idle_share"](traced) == pytest.approx(82.0)
    assert readers["unet_device_us_per_call"](traced) == pytest.approx(6.5)

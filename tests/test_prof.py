"""The simulator's profile buckets (``core/sim/prof.py``): what a profiled
``BatchSim`` round books where, that the timers never touch simulation
state, and that the buckets' spans reach a ``jax.profiler`` trace."""
import glob
import os

import jax
import numpy as np
import pytest

from repro.core.estimators import UNetEstimator
from repro.core.partitions import a100_mig_space
from repro.core.perfmodel import PerfModel
from repro.core.predictor import linreg, unet
from repro.core.sim.batch import BatchSim
from repro.core.sim.prof import KEYS, new_prof, split, timed
from repro.core.simulator import ClusterSim, SimConfig
from repro.core.traces import generate_trace

SPACE = a100_mig_space()
PM = PerfModel(SPACE)
B = 3


@pytest.fixture(scope="module")
def est():
    params, _ = unet.init(jax.random.PRNGKey(0))
    X = np.random.default_rng(0).random((64, 3))
    Y = np.random.default_rng(1).random((64, 2))
    return UNetEstimator(PM, params, linreg.fit_linreg(X, Y))


def _sims(est, profile, n_jobs=24):
    return [ClusterSim(generate_trace(n_jobs, lam_s=60.0, seed=s),
                       SimConfig(n_gpus=4, policy="miso", seed=s,
                                 profile=profile), SPACE, PM, est)
            for s in range(B)]


def _finish_times(sims):
    return [[j.finish_time for j in s.jobs.values()] for s in sims]


@pytest.fixture(scope="module")
def runs(est):
    """The same miso batch with profiling on and off, and the U-Net calls
    the profiled run made."""
    calls = [0]
    real = unet.UNet.__call__

    def counted(net, m):
        calls[0] += 1
        return real(net, m)

    unet.UNet.__call__ = counted
    try:
        on = _sims(est, True)
        BatchSim(on).run()
    finally:
        unet.UNet.__call__ = real
    off = _sims(est, False)
    BatchSim(off).run()
    return on, off, calls[0]


def test_profiled_batch_books_every_bucket(runs):
    on, _, calls = runs
    assert calls > 0
    for s in on:
        p = s.prof
        assert set(p) == set(KEYS)
        assert all(isinstance(v, float) for v in p.values())
        # the U-Net's parts nest inside the estimator's stage or its
        # inline bucket
        assert (p["unet_host_s"] + p["unet_wait_s"] + p["estimator_post_s"]
                <= p["fused_estimator_s"] + p["estimator_s"])
        assert p["round_apply_s"] > 0
        assert p["collect_s"] > 0 and p["mps_measure_s"] > 0
        assert p["fused_alg1_s"] > 0
        # inline MPS probes run at collect time, inside the event loop
        assert p["mps_measure_s"] <= p["collect_s"] + p["estimator_s"]
    assert sum(s.prof["unet_calls"] for s in on) == pytest.approx(calls,
                                                                  rel=1e-12)


def test_profile_off_is_bit_identical(runs):
    on, off, _ = runs
    assert all(s.prof is None for s in off)
    assert _finish_times(on) == _finish_times(off)
    assert all(len(s.completed) == len(s.jobs) for s in on)


def test_scalar_engine_books_estimator_buckets(est):
    """The scalar engine estimates inline: the U-Net's buckets and the MPS
    probe fall inside ``estimator_s`` and no BatchSim stage is booked."""
    sim = _sims(est, True)[0]
    sim.run()
    p = sim.prof
    assert p["unet_calls"] > 0
    assert (p["unet_host_s"] + p["unet_wait_s"] + p["estimator_post_s"]
            + p["mps_measure_s"] <= p["estimator_s"])
    for k in ("collect_s", "fused_estimator_s", "fused_alg1_s",
              "round_apply_s"):
        assert p[k] == 0.0


def test_split_books_each_replica_its_share():
    a, b = new_prof(), new_prof()
    scratch = new_prof()
    scratch["fused_alg1_s"] = 4.0
    scratch["unet_calls"] = 1.0
    # three items from a, one from an unprofiled replica, none from b
    split(scratch, [a, None, a, a])
    assert a["fused_alg1_s"] == 3.0 and a["unet_calls"] == 0.75
    assert b == new_prof()
    with timed(b, "collect_s", "sim.collect"):
        pass
    assert b["collect_s"] > 0


def test_trace_holds_program_spans(est, tmp_path):
    from jax.profiler import ProfileData

    sims = _sims(est, True, n_jobs=8)
    jax.profiler.start_trace(str(tmp_path))
    try:
        BatchSim(sims).run()
    finally:
        jax.profiler.stop_trace()
    (path,) = glob.glob(os.path.join(str(tmp_path), "**", "*.xplane.pb"),
                        recursive=True)
    names = {ev.name for plane in ProfileData.from_file(path).planes
             if plane.name.startswith("/host:")
             for line in plane.lines for ev in line.events}
    for span in ("sim.collect", "mps.measure", "batch.stage_a", "unet.host",
                 "unet.wait", "estimator.post", "batch.stage_c",
                 "batch.apply"):
        assert span in names, span

"""The simulator's profile buckets (``core/sim/prof.py``): what a profiled
``BatchSim`` round books where, that the timers never touch simulation
state, and that the buckets' spans reach a ``jax.profiler`` trace."""
import glob
import os

import jax
import numpy as np
import pytest

from repro.core.estimators import UNetEstimator
from repro.core.fleet import parse_fleet
from repro.core.partitions import a100_mig_space
from repro.core.perfmodel import PerfModel
from repro.core.predictor import linreg, unet
from repro.core.sim import batch as batch_mod
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
        # the MPS probe draws its noise at collect time, inside the event
        # loop, and stage A solves its matrices
        assert p["mps_measure_s"] <= (p["collect_s"] + p["estimator_s"]
                                      + p["fused_estimator_s"])
        assert p["mps_rows"] > p["mps_rows_hit"] > 0
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


# -------------------------------------------- a fleet of two GPU kinds

MIXED_COUNTERS = ("unet_windows", "stage_a_runs", "stage_a_forwards",
                  "stage_c_runs", "stage_c_solves")


def _mixed_sims(profile, n_jobs=24):
    fleet = parse_fleet("a100:2+h100:2")
    return [ClusterSim(generate_trace(n_jobs, lam_s=30.0, seed=s),
                       SimConfig(n_gpus=len(fleet), policy="miso", seed=s,
                                 profile=profile), fleet=fleet)
            for s in range(B)]


def _total(sims, key):
    return sum(s.prof[key] for s in sims)


@pytest.fixture(scope="module")
def mixed_runs():
    """A profiled batch on 2 A100 and 2 H100 GPUs, stepped round by round,
    with what each round's stages did counted from outside the program;
    and the same batch unprofiled."""
    on = _mixed_sims(True)
    assert all(isinstance(s.estimator, UNetEstimator)
               for s in on[0].fleet)
    assert len({id(s.estimator) for s in on[0].fleet}) == 2
    rounds = []
    seen = {}
    real = {"est": UNetEstimator.estimate_batch,
            "a": batch_mod._estimate_groups, "c": batch_mod._solve_groups,
            "opt": batch_mod.optimize_partition_batch}

    def estimate_batch(est, requests, prof=None):
        seen["ests"].add(id(est))
        seen["windows"] += len(requests)
        return real["est"](est, requests, prof=prof)

    def stage_a(works, prof):
        seen["a"] += 1
        return real["a"](works, prof)

    def stage_c(decisions, prof):
        seen["c"] += 1
        return real["c"](decisions, prof)

    def solve(*args, **kw):
        seen["solves"] += 1
        return real["opt"](*args, **kw)

    mp = pytest.MonkeyPatch()
    mp.setattr(UNetEstimator, "estimate_batch", estimate_batch)
    mp.setattr(batch_mod, "_estimate_groups", stage_a)
    mp.setattr(batch_mod, "_solve_groups", stage_c)
    mp.setattr(batch_mod, "optimize_partition_batch", solve)
    try:
        bs = BatchSim(on)
        live = True
        while live:
            before = {k: _total(on, k) for k in MIXED_COUNTERS}
            seen.update(ests=set(), windows=0, a=0, c=0, solves=0)
            live = bs.step()
            rounds.append(({k: _total(on, k) - v
                            for k, v in before.items()}, dict(seen)))
        bs.settle()
    finally:
        mp.undo()
    off = _mixed_sims(False)
    BatchSim(off).run()
    return on, off, rounds


def test_mixed_fleet_books_the_new_counters(mixed_runs):
    on, _, _ = mixed_runs
    for s in on:
        assert set(s.prof) == set(KEYS)
        for k in MIXED_COUNTERS:
            assert isinstance(s.prof[k], float) and s.prof[k] > 0, k
        assert s.prof["unet_windows"] >= s.prof["unet_calls"]
        assert s.prof["stage_a_forwards"] >= s.prof["stage_a_runs"]
        assert s.prof["stage_c_solves"] >= s.prof["stage_c_runs"]


def test_mixed_fleet_shares_sum_to_stage_totals(mixed_runs):
    """Per round, the replicas' shares of each counter add up to what the
    round's stages did: stage A and C runs, one ``estimate_batch`` per
    estimator object, one stacked solve per menu, the windows sent."""
    _, _, rounds = mixed_runs
    for booked, did in rounds:
        want = {"unet_windows": did["windows"], "stage_a_runs": did["a"],
                "stage_a_forwards": len(did["ests"]),
                "stage_c_runs": did["c"], "stage_c_solves": did["solves"]}
        for k, v in want.items():
            assert booked[k] == pytest.approx(v, rel=1e-12, abs=1e-12), k


def test_mixed_fleet_runs_two_forwards_when_both_kinds_have_windows(
        mixed_runs):
    _, _, rounds = mixed_runs
    both = [booked for booked, did in rounds if len(did["ests"]) == 2]
    assert both, "no round had windows on both kinds"
    for booked in both:
        assert booked["stage_a_runs"] == pytest.approx(1.0, rel=1e-12)
        assert booked["stage_a_forwards"] == pytest.approx(2.0, rel=1e-12)
    # and a round of one kind runs one
    one = [booked for booked, did in rounds if len(did["ests"]) == 1]
    assert one
    assert all(b["stage_a_forwards"] == pytest.approx(1.0, rel=1e-12)
               for b in one)


def test_mixed_fleet_profile_off_is_bit_identical(mixed_runs):
    on, off, _ = mixed_runs
    assert all(s.prof is None for s in off)
    assert _finish_times(on) == _finish_times(off)
    assert all(len(s.completed) == len(s.jobs) for s in on)

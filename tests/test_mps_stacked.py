"""The MPS probe's stacked fixed point (``PerfModel.mps_matrices``): equal
bit for bit to the scalar ``mps_speeds`` and its memo, ``pysum`` equal to
the built-in ``sum()``, and a ``BatchSim`` round that defers the probe's
matrices to stage A finishing every replica as ``ClusterSim.run()`` does,
with its row counters booked to the replicas."""
import dataclasses
import random

import numpy as np
import pytest

from repro.core.estimators import UNetEstimator
from repro.core.fleet import h100_mig_space
from repro.core.jobs import DUMMY_PROFILE, WORKLOADS
from repro.core.partitions import a100_mig_space
from repro.core.perfmodel import (A100, H100, MPS_ITERS, MPS_LEVELS,
                                  MPS_STACK_MIN_ROWS, PerfModel, pysum)
from repro.core.predictor import linreg
from repro.core.sim.batch import BatchSim
from repro.core.sim.prof import KEYS
from repro.core.simulator import ClusterSim, SimConfig
from repro.core.traces import generate_trace

HW = {"a100": (a100_mig_space, A100), "h100": (h100_mig_space, H100)}
COUNTERS = ("mps_rows", "mps_rows_hit", "mps_rows_stacked")


def _pm(kind):
    space, hw = HW[kind]
    return PerfModel(space(), hw)


def _mixes(n, seed=0):
    """``n`` mixes of 1 to 7 pool profiles, repeats allowed, most padded
    with the dummy profile as the estimator pads them, and a duplicate."""
    rng = random.Random(seed)
    out = []
    for _ in range(n):
        k = rng.randint(1, 7)
        mix = [rng.choice(WORKLOADS) for _ in range(k)]
        if rng.random() < 0.7:
            mix += [DUMMY_PROFILE] * (7 - k)
        out.append(mix)
    return out + out[:1]


def _scalar(pm, mixes):
    return [np.array([pm.mps_speeds(mix, lv) for lv in MPS_LEVELS])
            for mix in mixes]


def _same(a, b):
    a, b = np.asarray(a, np.float64), np.asarray(b, np.float64)
    return a.shape == b.shape and bool(np.all(a == b))


@pytest.mark.parametrize("kind", sorted(HW))
@pytest.mark.parametrize("n_mixes", [1, 200])
def test_mps_matrices_equal_mps_speeds_bit_for_bit(kind, n_mixes):
    """One mix (3 rows, under the crossover, solved one by one) and 200
    (solved stacked): every value equals ``mps_speeds``'s with ``==``."""
    mixes = _mixes(n_mixes, seed=n_mixes)
    pm, ref = _pm(kind), _pm(kind)
    prof = dict.fromkeys(COUNTERS, 0.0)
    got = pm.mps_matrices(mixes, prof)
    want = _scalar(ref, mixes)
    assert len(got) == len(want)
    assert all(_same(g, w) for g, w in zip(got, want))
    misses = prof["mps_rows"] - prof["mps_rows_hit"]
    assert prof["mps_rows"] == len(MPS_LEVELS) * len(mixes)
    assert prof["mps_rows_hit"] >= len(MPS_LEVELS)   # the duplicate
    if misses >= MPS_STACK_MIN_ROWS:
        assert prof["mps_rows_stacked"] == misses
    else:
        assert prof["mps_rows_stacked"] == 0


@pytest.mark.parametrize("kind", sorted(HW))
def test_mps_matrices_leave_the_memo_as_mps_speeds_does(kind):
    """The same keys, the same rows (lists of Python floats) and the same
    pinned profiles; later scalar calls hit the stacked pass's rows."""
    mixes = _mixes(120, seed=7)
    pm, ref = _pm(kind), _pm(kind)
    pm.mps_matrices(mixes)
    _scalar(ref, mixes)
    assert set(pm._mps_cache) == set(ref._mps_cache)
    for key, (profs, row) in ref._mps_cache.items():
        got_profs, got_row = pm._mps_cache[key]
        assert all(a is b for a, b in zip(got_profs, profs))
        assert all(type(v) is float for v in got_row)
        assert got_row == row
    n = len(pm._mps_cache)
    again = pm.mps_speeds(mixes[3], MPS_LEVELS[1])
    assert len(pm._mps_cache) == n
    assert again == ref.mps_speeds(mixes[3], MPS_LEVELS[1])


def test_mps_matrices_mix_lengths_and_a_partial_memo():
    """Mixes of every length from 1 to 7 unpadded, each length its own
    stacked group, after scalar calls put some of their levels in the
    memo already."""
    lengths = [k for k in range(1, 8) for _ in range(3)]
    # distinct mixes: their first profiles differ (7 is prime to 32)
    mixes = [[WORKLOADS[(7 * i + j) % len(WORKLOADS)] for j in range(k)]
             for i, k in enumerate(lengths)]
    pm, ref = _pm("a100"), _pm("a100")
    for mix in mixes[::4]:
        pm.mps_speeds(mix, MPS_LEVELS[2])
    prof = dict.fromkeys(COUNTERS, 0.0)
    got = pm.mps_matrices(mixes, prof)
    assert all(_same(g, w) for g, w in zip(got, _scalar(ref, mixes)))
    assert prof["mps_rows_hit"] == len(mixes[::4])
    assert prof["mps_rows_stacked"] == prof["mps_rows"] - prof["mps_rows_hit"]


def test_stacked_pass_declines_fields_sum_adds_differently():
    """``sum()`` adds an int without compensation, so a profile whose
    ``cache_sens`` is an int is solved one by one, to the same values."""
    odd = dataclasses.replace(WORKLOADS[5], cache_sens=1)
    mixes = [[odd, WORKLOADS[i], WORKLOADS[i + 1]] for i in range(8)]
    pm, ref = _pm("a100"), _pm("a100")
    assert pm._mps_speeds_stacked(mixes, [MPS_LEVELS[0]] * len(mixes)) \
        is None
    prof = dict.fromkeys(COUNTERS, 0.0)
    got = pm.mps_matrices(mixes, prof)
    assert all(_same(g, w) for g, w in zip(got, _scalar(ref, mixes)))
    assert prof["mps_rows_stacked"] == 0


def _rows(kind):
    r = np.random.default_rng(5)
    if kind == "compensated":
        # naive left-to-right addition loses the small terms of these
        rows = [[1e16, 1.0, 1.0], [1.0, 1e100, 1.0, -1e100],
                [0.1] * 10, [1e-16, 1.0, -1.0, 1e-16, 3.0, -3.0, 1e-16]]
        return [row + [0.0] * (10 - len(row)) for row in rows]
    if kind == "wide":
        return (r.standard_normal((20000, 7))
                * 10.0 ** r.integers(-12, 17, (20000, 7))).tolist()
    if kind == "zeros":
        return r.choice([0.0, -0.0, 1.5, -1.5], (2000, 5)).tolist()
    return [[1e308, 1e308, -1.0, 0.0], [float("inf"), 1.0, 2.0, 0.5],
            [1.0, float("-inf"), float("inf"), 0.0],
            [float("nan"), 1.0, 0.0, 0.0], [1e308, -1e308, 1e308, 1e308]]


@pytest.mark.parametrize("kind", ["compensated", "wide", "zeros",
                                  "non-finite"])
def test_pysum_is_the_builtin_sum(kind):
    rows = _rows(kind)
    want = np.array([sum(row) for row in rows])
    with np.errstate(over="ignore", invalid="ignore"):
        got = pysum(np.array(rows).T)
    nan = np.isnan(want)
    assert np.array_equal(np.isnan(got), nan)
    assert np.array_equal(got[~nan].view(np.int64),
                          want[~nan].view(np.int64))
    if kind == "compensated":
        naive = np.add.accumulate(np.array(rows), axis=1)[:, -1]
        assert not np.array_equal(naive, want)


def test_pysum_of_no_terms_and_one():
    assert np.array_equal(pysum(np.zeros((0, 3))), np.zeros(3))
    one = pysum(np.array([[-0.0, 2.5, -1.0]]))
    assert one.tolist() == [sum([-0.0]), 2.5, -1.0]
    assert not np.signbit(one[0])


# ----------------------------------------------------- the fused path


class _RowsNet:
    """A stand-in forward, exact at any batch size: the MPS matrix's
    rows as the (7g, 4g, 3g) speeds, so the estimates read the probe's
    matrices and nothing else."""

    def __call__(self, m):
        return np.asarray(m, np.float32)


def _est():
    x = np.random.default_rng(0).random((64, 3))
    y = np.random.default_rng(1).random((64, 2))
    est = UNetEstimator(PerfModel(a100_mig_space()), None,
                        linreg.fit_linreg(x, y))
    est.net = _RowsNet()
    return est


def _replicas(est, sigma, profile):
    return [ClusterSim(generate_trace(30, lam_s=60.0, seed=s),
                       SimConfig(n_gpus=8, policy="miso", seed=s,
                                 mps_noise_sigma=sigma, profile=profile),
                       est.pm.space, est.pm, est)
            for s in range(8)]


def _finish_times(sims):
    return [[j.finish_time for j in s.jobs.values()] for s in sims]


@pytest.fixture(scope="module", params=[0.0, 0.05])
def fused(request):
    """8 testbed replicas through a profiled ``BatchSim``, round by round,
    with each round's probe counted from outside the program; and each
    replica alone through ``ClusterSim.run()`` on its own perf model."""
    sigma = request.param
    est = _est()
    sims = _replicas(est, sigma, True)
    pm = est.pm
    real_matrices, real_stacked = pm.mps_matrices, pm._mps_speeds_stacked
    seen = {}

    def matrices(mixes, prof=None):
        for mix in mixes:
            ids = tuple(map(id, mix))
            for lv in MPS_LEVELS:
                key = (ids, lv, MPS_ITERS)
                seen["rows"] += 1
                seen["hit"] += key in pm._mps_cache or key in seen["keys"]
                seen["keys"].add(key)
        return real_matrices(mixes, prof)

    def stacked(mixes, levels):
        out = real_stacked(mixes, levels)
        seen["stacked"] += 0 if out is None else len(out)
        return out

    pm.mps_matrices, pm._mps_speeds_stacked = matrices, stacked
    rounds = []
    bs = BatchSim(sims)
    live = True
    while live:
        before = {k: sum(s.prof[k] for s in sims) for k in COUNTERS}
        seen.update(rows=0, hit=0, stacked=0, keys=set())
        live = bs.step()
        rounds.append(({k: sum(s.prof[k] for s in sims) - v
                        for k, v in before.items()}, dict(seen)))
    bs.settle()
    for s in sims:
        s.finish(settle=False)
    alone = _replicas(_est(), sigma, False)
    for s in alone:
        s.run()
    return sims, alone, rounds


def test_batch_finishes_every_replica_as_it_runs_alone(fused):
    sims, alone, _ = fused
    assert all(len(s.completed) == len(s.jobs) for s in sims)
    assert _finish_times(sims) == _finish_times(alone)


def test_probe_counters_sum_to_the_round_totals(fused):
    sims, _, rounds = fused
    for s in sims:
        assert set(s.prof) == set(KEYS)
        assert all(isinstance(s.prof[k], float) for k in COUNTERS)
    for booked, did in rounds:
        want = {"mps_rows": did["rows"], "mps_rows_hit": did["hit"],
                "mps_rows_stacked": did["stacked"]}
        for k, v in want.items():
            assert booked[k] == pytest.approx(v, rel=1e-12, abs=1e-9), k


def test_stage_a_solves_its_misses_stacked(fused):
    """A round of 8 replicas' windows goes through the stacked pass; the
    memo answers some rows and the probe's time is booked."""
    sims, _, rounds = fused
    assert any(did["stacked"] for _, did in rounds)
    total = {k: sum(s.prof[k] for s in sims) for k in COUNTERS}
    assert total["mps_rows"] > total["mps_rows_hit"] > 0
    assert all(s.prof["mps_measure_s"] > 0 for s in sims)


def test_measure_mps_many_is_measure_mps():
    """Both halves apart, noise drawn first and applied later, all at once
    or window by window: the same float32 matrices as ``measure_mps``."""
    est, ref = _est(), _est()
    mixes = [m[:k] for m, k in zip(_mixes(9, seed=3), range(1, 8))]
    for noisy in ([True] * 7, [False] * 7, [True, False] * 3 + [True]):
        rng_a, rng_b = np.random.default_rng(9), np.random.default_rng(9)
        noises = [est.draw_mps_noise(m, 0.05 if n else 0.0, rng_a)
                  for m, n in zip(mixes, noisy)]
        got = est.measure_mps_many(mixes, noises)
        want = [ref.measure_mps(m, 0.05 if n else 0.0, rng_b)
                for m, n in zip(mixes, noisy)]
        for g, w in zip(got, want):
            assert g.dtype == w.dtype == np.float32
            assert np.array_equal(g, w)

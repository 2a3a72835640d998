"""Fused estimator service: estimate_batch contract and the engine's
same-tick phase-end coalescing (batched runs must be bit-identical to
sequential processing, including estimator RNG draw order)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

import repro.core.sim.engine as eng
from repro.core.estimators import (NoisyEstimator, OracleEstimator,
                                   UNetEstimator)
from repro.core.jobs import WORKLOADS
from repro.core.partitions import a100_mig_space
from repro.core.perfmodel import PerfModel
from repro.core.predictor import linreg, unet
from repro.core.simulator import SimConfig, simulate
from repro.core.traces import generate_trace

SPACE = a100_mig_space()
PM = PerfModel(SPACE)


# ------------------------------------------------------------ estimate_batch


def _mixes(rng, n=5):
    out = []
    for _ in range(n):
        k = int(rng.integers(1, 7))
        profs = [WORKLOADS[int(i)]
                 for i in rng.integers(0, len(WORKLOADS), k)]
        out.append((profs, None, [0] * k))
    return out


def test_oracle_estimate_batch_equals_singles():
    est = OracleEstimator(PM)
    reqs = _mixes(np.random.default_rng(0))
    batched = est.estimate_batch(reqs)
    for (profs, mat, qos), got in zip(reqs, batched):
        assert got == est.estimate(profs, mat, qos=qos)


def test_noisy_estimate_batch_consumes_rng_in_request_order():
    reqs = _mixes(np.random.default_rng(1))
    a = NoisyEstimator(PM, 0.1, seed=3).estimate_batch(reqs)
    b_est = NoisyEstimator(PM, 0.1, seed=3)
    b = [b_est.estimate(profs, mat, qos=qos) for profs, mat, qos in reqs]
    assert a == b


@pytest.fixture(scope="module")
def unet_est():
    net = unet.UNet.create(jax.random.PRNGKey(0))
    X = np.random.default_rng(0).random((64, 3))
    Y = np.random.default_rng(1).random((64, 2))
    heads = linreg.fit_linreg(X, Y)
    return UNetEstimator(PM, net.params, heads)


def test_unet_estimate_batch_single_request_bit_identical(unet_est):
    profs = list(WORKLOADS[:4])
    mat = unet_est.measure_mps(profs)
    assert unet_est.estimate_batch([(profs, mat, [0] * 4)])[0] == \
        unet_est.estimate(profs, mat, qos=[0] * 4)


def test_unet_estimate_batch_matches_singles_allclose(unet_est):
    """A stacked (B, 3, J) forward equals per-request forwards up to XLA
    batch reassociation (float32 last-ulp; see estimators module doc)."""
    rng = np.random.default_rng(2)
    reqs = []
    for profs, _, qos in _mixes(rng, n=5):
        reqs.append((profs, unet_est.measure_mps(profs), qos))
    batched = unet_est.estimate_batch(reqs)
    for (profs, mat, qos), got in zip(reqs, batched):
        single = unet_est.estimate(profs, mat, qos=qos)
        assert len(got) == len(single)
        for a, b in zip(single, got):
            assert set(a) == set(b)
            for s in a:
                assert a[s] == pytest.approx(b[s], abs=1e-5)


def test_unet_batch_bucketing_pads_and_crops(unet_est):
    mats = np.stack([np.asarray(unet_est.measure_mps([p]), np.float32)
                     for p in WORKLOADS[:3]])
    out = np.asarray(unet_est.net(mats))     # B=3 -> bucket 4 -> cropped
    assert out.shape == (3, 3, 7)


def _requests(est, rng, n):
    return [(profs, est.measure_mps(profs), qos)
            for profs, _, qos in _mixes(rng, n)]


def test_unet_estimate_batch_compiles_nothing_past_its_bucket(unet_est):
    """Host-side pad and crop: once bucket 64 is warm, batches of 37 and
    40 windows run the same one program and compile nothing (eager device
    pad and crop compiled new programs for every batch size)."""
    rng = np.random.default_rng(5)
    unet_est.estimate_batch(_requests(unet_est, rng, 64))
    batches = [_requests(unet_est, rng, n) for n in (37, 40)]
    compiles = []

    def on_duration(event, duration, **kw):
        if event == "/jax/core/compile/backend_compile_duration":
            compiles.append(event)
    jax.monitoring.register_event_duration_secs_listener(on_duration)
    try:
        for reqs in batches:
            assert len(unet_est.estimate_batch(reqs)) == len(reqs)
    finally:
        jax.monitoring.unregister_event_duration_listener(on_duration)
    assert compiles == []


def _device_padded(params, mats):
    """The forward as it was before padding moved to the host: the batch
    padded on the device to its bucket, cropped there afterwards."""
    m, b = jnp.asarray(mats, jnp.float32), len(mats)
    nb = unet._bucket(b)
    if nb != b:
        m = jnp.concatenate(
            [m, jnp.zeros((nb - b,) + m.shape[1:], jnp.float32)], axis=0)
    return np.asarray(unet._apply_jit(params, m, 3, 7)[:b])


@pytest.mark.parametrize("b", [1, 3, 40])
def test_unet_host_padding_equals_device_padding(unet_est, b, monkeypatch):
    """The rows ``estimate_batch`` hands on and ``UNet.__call__``'s
    outputs equal, bit for bit, the forward of a batch padded on the
    device and cropped there afterwards."""
    reqs = _requests(unet_est, np.random.default_rng(10 + b), b)
    mats = np.stack([mat for _, mat, _ in reqs])
    params = unet_est.net.params
    want = _device_padded(params, mats)

    np.testing.assert_array_equal(np.asarray(unet_est.net(mats)), want)
    np.testing.assert_array_equal(np.asarray(unet_est.net(mats[0])),
                                  _device_padded(params, mats[:1])[0])
    rows = []
    post = unet_est._postprocess
    monkeypatch.setattr(unet_est, "_postprocess", lambda profs, pred, qos:
                        rows.append(np.array(pred)) or post(profs, pred, qos))
    unet_est.estimate_batch(reqs)
    np.testing.assert_array_equal(np.stack(rows), want)


# -------------------------------------------------- same-tick coalescing


def _run(policy, seed, coalesce, estimator=None, n_gpus=8):
    jobs = generate_trace(30, lam_s=2.0, seed=seed, max_duration_s=1800)
    cfg = SimConfig(n_gpus=n_gpus, policy=policy)
    est = estimator or OracleEstimator(PM)
    if coalesce:
        m = simulate(jobs, cfg, SPACE, PM, est)
    else:
        orig = eng.ClusterSim._drain_same_tick_timers
        eng.ClusterSim._drain_same_tick_timers = lambda self, t, g: None
        try:
            m = simulate(jobs, cfg, SPACE, PM, est)
        finally:
            eng.ClusterSim._drain_same_tick_timers = orig
    return (m.avg_jct, m.makespan, m.stp, tuple(m.jcts),
            tuple(sorted(m.breakdown.items())))


@pytest.mark.parametrize("policy", ["miso", "miso-frag", "srpt"])
def test_coalesced_phase_ends_bit_identical(policy):
    for seed in (0, 1):
        assert _run(policy, seed, True) == _run(policy, seed, False)


def test_coalesced_noisy_estimator_preserves_rng_stream():
    for seed in (0, 1):
        a = _run("miso", seed, True, NoisyEstimator(PM, 0.1, seed=7))
        b = _run("miso", seed, False, NoisyEstimator(PM, 0.1, seed=7))
        assert a == b

"""Slice-speed estimators: how a scheduling policy learns f_i(x).

* OracleEstimator  — ground-truth speeds from the performance model (the
  paper's Oracle; also used *after* partitioning for actual execution speed).
* NoisyEstimator   — ground truth + multiplicative Gaussian error (paper
  Fig 18 sensitivity).
* UNetEstimator    — the full MISO path: the job mix's measured MPS matrix ->
  U-Net -> (7g,4g,3g), then the linear-regression heads -> (2g,1g), then the
  memory monitor zeroes OOM slices (paper §4.1 + §4.3).

Batched contract
----------------
``estimate_batch(requests)`` takes a list of ``(profs, mps_matrix, qos)``
tuples — one per co-location group / profiling window — and returns one
``estimate``-shaped result per request, in order.  Semantics:

* results are identical to calling ``estimate`` once per request in the
  same order (estimators that consume RNG draw it in request order);
* ``mps_matrix`` may be None per request; estimators that need one measure
  it themselves (as ``estimate`` does);
* the U-Net estimator stacks every request's matrix into a single
  ``(B, levels, jobs)`` jitted forward (padded to a power-of-two batch
  bucket) instead of B separate ``(1, levels, jobs)`` dispatches — the
  engine's same-tick window coalescing is the main caller.  A batched
  forward is numerically equal to per-request forwards up to XLA batch
  reassociation (float32 last-ulp); single-request batches go through the
  exact same compiled shape as ``estimate`` and are bit-identical to it.
"""
from __future__ import annotations

from typing import Dict, List, Optional, Sequence, Tuple

import numpy as np

from repro.core.jobs import DUMMY_PROFILE, JobProfile
from repro.core.partitions import PartitionSpace
from repro.core.perfmodel import PerfModel
from repro.core.predictor import linreg as linreg_mod
from repro.core.predictor import unet as unet_mod
from repro.core.predictor.dataset import LIN_SLICES, OUT_SLICES

#: one estimate_batch request: (profiles, optional MPS matrix, optional QoS)
EstimateRequest = Tuple[Sequence[JobProfile], Optional[np.ndarray],
                        Optional[Sequence[int]]]


def _apply_mem_constraints(space: PartitionSpace, prof: JobProfile,
                           speeds: Dict[int, float],
                           qos_min_slice: int = 0) -> Dict[int, float]:
    out = {}
    for size, v in speeds.items():
        st = space.slices[size]
        if prof.mem_gb > st.memory_gb or size < qos_min_slice:
            out[size] = 0.0
        else:
            out[size] = max(0.0, min(1.0, v))
    return out


class OracleEstimator:
    needs_mps = False

    def __init__(self, pm: PerfModel):
        self.pm = pm
        # per-(profile, qos) estimate memo: the oracle's slice-speed map is
        # a pure function of the (immutable) profile and the QoS floor, and
        # the oracle policy re-runs it on every repartition.  The profile is
        # pinned in the value so the id key cannot be recycled.  The result
        # dicts are shared — every consumer treats estimates as read-only
        # (the estimator-fault injector builds fresh dicts).
        self._est_cache: Dict[Tuple[int, int], Tuple[JobProfile,
                                                     Dict[int, float]]] = {}

    def _estimate_one(self, p: JobProfile, q: int) -> Dict[int, float]:
        key = (id(p), q)
        hit = self._est_cache.get(key)
        if hit is not None and hit[0] is p:
            return hit[1]
        est = _apply_mem_constraints(self.pm.space, p,
                                     self.pm.speed_vector(p), q)
        if len(self._est_cache) >= 65536:
            self._est_cache.pop(next(iter(self._est_cache)))
        self._est_cache[key] = (p, est)
        return est

    def estimate(self, profs: Sequence[JobProfile], mps_matrix=None,
                 qos=None, prof=None) -> List[Dict[int, float]]:
        qos = qos or [0] * len(profs)
        return [self._estimate_one(p, q) for p, q in zip(profs, qos)]

    def estimate_batch(self, requests: Sequence[EstimateRequest], prof=None
                       ) -> List[List[Dict[int, float]]]:
        """Default batched path: per-request ``estimate`` in request order
        (exact for any estimator whose estimate is per-request; overridden
        where a fused pass exists).  ``prof`` (profile buckets) is
        accepted and ignored: only the U-Net has buckets of its own."""
        return [self.estimate(profs, mat, qos)
                for profs, mat, qos in requests]


class NoisyEstimator(OracleEstimator):
    """Ground truth with relative error ~ N(0, sigma) (paper Fig 18).

    The inherited ``estimate_batch`` loops requests in order, so the noise
    stream is consumed exactly as back-to-back ``estimate`` calls would.
    """
    needs_mps = False

    def __init__(self, pm: PerfModel, sigma: float, seed: int = 0):
        super().__init__(pm)
        self.sigma = sigma
        self.rng = np.random.default_rng(seed)

    def estimate(self, profs, mps_matrix=None, qos=None, prof=None):
        qos = qos or [0] * len(profs)
        out = []
        for p, q in zip(profs, qos):
            sv = {s: v * float(1.0 + self.rng.normal(0.0, self.sigma))
                  for s, v in self.pm.speed_vector(p).items()}
            sv[self.pm.space.full_size] = 1.0   # normalization anchor
            out.append(_apply_mem_constraints(self.pm.space, p, sv, q))
        return out


class UNetEstimator:
    """MPS-profile -> U-Net -> linreg heads -> memory-constrained speeds."""
    needs_mps = True

    def __init__(self, pm: PerfModel, params, heads, jobs: int = 7,
                 seed: int = 0):
        self.pm = pm
        self.net = unet_mod.UNet(params, jobs=jobs)
        self.heads = heads
        self.jobs = jobs
        # fallback noise stream: advances across calls so every profiling
        # window draws fresh measurement noise (callers normally thread the
        # simulator's RNG through instead)
        self._rng = np.random.default_rng(seed)

    @classmethod
    def from_artifact(cls, pm: PerfModel, path: str, jobs: int = 7):
        from repro.core.predictor.train import load_artifact
        params, heads, _ = load_artifact(path)
        return cls(pm, params, heads, jobs=jobs)

    def measure_mps(self, profs: Sequence[JobProfile],
                    noise_sigma: float = 0.0,
                    rng: Optional[np.random.Generator] = None) -> np.ndarray:
        """The profiling measurement itself (what the 30s MPS phase yields).

        ``noise_sigma`` models measurement noise from a finite profiling
        window: speeds are averaged over ~10s per level, so shorter windows
        give noisier estimates (paper Fig 14 sensitivity: sigma ~ 1/sqrt(T)).
        Pass the simulator's ``rng`` so successive windows draw independent
        noise; without one, an instance-local stream is used (it advances
        across calls — noise is never identical between windows).
        """
        if len(profs) > self.jobs:
            raise ValueError(
                f"cannot profile {len(profs)} co-located jobs: this predictor "
                f"was trained on matrices of at most {self.jobs} columns")
        padded = list(profs) + [DUMMY_PROFILE] * (self.jobs - len(profs))
        m = np.asarray(self.pm.mps_matrix(padded), dtype=np.float32)
        if noise_sigma > 0:
            if rng is None:
                rng = self._rng
            m = m * (1.0 + rng.normal(0.0, noise_sigma, size=m.shape)
                     ).astype(np.float32)
            m = np.maximum(m, 1e-6)
        return m / np.maximum(m.max(axis=0, keepdims=True), 1e-9)

    def estimate(self, profs, mps_matrix: Optional[np.ndarray] = None,
                 qos=None, prof=None) -> List[Dict[int, float]]:
        """``prof``: profile buckets (``core/sim/prof.py``) that the
        forward's host, wait and post-processing time go to."""
        if mps_matrix is None:
            mps_matrix = self.measure_mps(profs)
        if prof is None:
            pred = np.asarray(self.net(mps_matrix))            # (3, J)
            return self._postprocess(profs, pred, qos)
        return self._profiled(
            prof, lambda: self.net(mps_matrix),
            lambda pred: self._postprocess(profs, pred, qos))

    def estimate_batch(self, requests: Sequence[EstimateRequest], prof=None
                       ) -> List[List[Dict[int, float]]]:
        """Fused path: all B requests' matrices go through one stacked
        ``(B, levels, jobs)`` jitted forward (see module docstring for the
        numerical contract); measurement (and thus any RNG use) happens in
        request order before the forward.  ``prof`` as in :meth:`estimate`."""
        if not requests:
            return []
        mats = [np.asarray(mat if mat is not None else self.measure_mps(profs),
                           dtype=np.float32)
                for profs, mat, _ in requests]

        def post(preds):
            return [self._postprocess(profs, pred, qos)
                    for (profs, _, qos), pred in zip(requests, preds)]
        if prof is None:
            return post(np.asarray(self.net(np.stack(mats))))  # (B, 3, J)
        return self._profiled(prof, lambda: self.net(np.stack(mats)), post)

    @staticmethod
    def _profiled(prof, launch, post):
        """One forward, timed: ``launch()`` returns as soon as the forward
        is enqueued (JAX dispatches asynchronously), the readback waits for
        it, then ``post`` turns the output into estimates."""
        from repro.core.sim.prof import timed   # core.sim imports this module
        with timed(prof, "unet_host_s", "unet.host"):
            out = launch()
        with timed(prof, "unet_wait_s", "unet.wait"):
            pred = np.asarray(out)
        with timed(prof, "estimator_post_s", "estimator.post"):
            ests = post(pred)
        prof["unet_calls"] += 1.0
        return ests

    def _postprocess(self, profs, pred: np.ndarray,
                     qos=None) -> List[Dict[int, float]]:
        """(3, J) U-Net output -> per-job speed dicts: linreg heads for the
        small slices, full-slice anchor, then the memory/QoS monitor."""
        qos = qos or [0] * len(profs)
        lin = linreg_mod.apply_linreg(self.heads, pred.T)  # (J, 2)
        out = []
        for j, (p, q) in enumerate(zip(profs, qos)):
            sv = {s: float(pred[r, j]) for r, s in enumerate(OUT_SLICES)}
            sv[self.pm.space.full_size] = 1.0
            for r, s in enumerate(LIN_SLICES):
                sv[s] = float(lin[j, r])
            out.append(_apply_mem_constraints(self.pm.space, p, sv, q))
        return out

"""MISO's slice-speed estimator (paper §4.1), written out plainly.

The probe measures the job mix under MPS at each level, dummy-padded to
the predictor's width and each column divided by its largest level.  The
U-Net maps that matrix to speeds on the three largest slices; linear heads
give the two smallest from those three; the whole GPU is the unit of
speed; and the memory monitor zeroes every slice a job does not fit.

The forward is NumPy float64: the (L, J) matrix is edge-replicated to
4 x 8, two 2x2 stride-2 encoder convolutions (32, 64 filters) lead into a
256-filter centre, two 2x2 stride-2 transposed convolutions with skip
connections lead back up, then a 1x1 head and a sigmoid, cropped back to
(3, J).  Leaky ReLU with slope 0.1 follows every convolution but the head.
Convolutions follow XLA's conventions: cross-correlation (no kernel flip),
HWIO kernels, ``SAME`` padding with the extra row and column on the high
side, and a transposed convolution that dilates its input by the stride
and pads one on each side for a 2x2 kernel.
"""
from __future__ import annotations

from typing import Dict, List, Sequence

import numpy as np

from ref.testbed import Profile, Testbed


def load_weights(path: str):
    """(convolution weights by name, linear heads (4, 2)) from the
    benchmark's weight file."""
    with np.load(path) as z:
        params = {k: np.asarray(z[k]) for k in z.files if not k.startswith("__")}
        heads = np.asarray(z["__head_w"])
    return params, heads


def _corr(x: np.ndarray, w: np.ndarray, stride: int) -> np.ndarray:
    """Cross-correlate NHWC ``x`` (already padded) with HWIO ``w``."""
    kh, kw = w.shape[:2]
    n, h, wd, _ = x.shape
    oh = (h - kh) // stride + 1
    ow = (wd - kw) // stride + 1
    out = np.zeros((n, oh, ow, w.shape[3]))
    for i in range(kh):
        for j in range(kw):
            patch = x[:, i:i + stride * (oh - 1) + 1:stride,
                      j:j + stride * (ow - 1) + 1:stride, :]
            out += patch @ w[i, j]
    return out


def _conv(x, p, name, stride=1):
    w = np.asarray(p[f"{name}_w"], np.float64)
    kh, kw = w.shape[:2]
    n, h, wd, c = x.shape
    oh, ow = -(-h // stride), -(-wd // stride)
    ph = max((oh - 1) * stride + kh - h, 0)
    pw = max((ow - 1) * stride + kw - wd, 0)
    x = np.pad(x, ((0, 0), (ph // 2, ph - ph // 2),
                   (pw // 2, pw - pw // 2), (0, 0)))
    return _corr(x, w, stride) + np.asarray(p[f"{name}_b"], np.float64)


def _conv_t(x, p, name, stride=2):
    n, h, wd, c = x.shape
    up = np.zeros((n, (h - 1) * stride + 1, (wd - 1) * stride + 1, c))
    up[:, ::stride, ::stride, :] = x
    up = np.pad(up, ((0, 0), (1, 1), (1, 1), (0, 0)))
    return (_corr(up, np.asarray(p[f"{name}_w"], np.float64), 1)
            + np.asarray(p[f"{name}_b"], np.float64))


def _act(x):
    return np.where(x >= 0, x, 0.1 * x)


def forward(params, mats) -> np.ndarray:
    """(batch, levels, jobs) -> (batch, 3, jobs) in (0, 1], float64."""
    m = np.asarray(mats, np.float64)
    b, h, w = m.shape
    x = np.pad(m, ((0, 0), (0, 4 - h), (0, 8 - w)), mode="edge")[..., None]
    stem = _act(_conv(x, params, "stem"))
    e1 = _act(_conv(stem, params, "enc1", stride=2))
    e2 = _act(_conv(e1, params, "enc2", stride=2))
    c = _act(_conv(e2, params, "center"))
    d1 = _act(_conv_t(c, params, "dec1_up"))
    d1 = _act(_conv(np.concatenate([d1, e1], -1), params, "dec1"))
    d2 = _act(_conv_t(d1, params, "dec2_up"))
    d2 = _act(_conv(np.concatenate([d2, stem], -1), params, "dec2"))
    out = 1.0 / (1.0 + np.exp(-_conv(d2, params, "head")[..., 0]))
    return out[:, :3, :w]


class Estimator:
    """The probe's measurement and the estimate built from a U-Net output,
    on one group's testbed, with that group's ``predictor`` and heads."""

    def __init__(self, testbed: Testbed, pred: dict, pad: dict,
                 heads: np.ndarray):
        self.tb = testbed
        self.jobs = pred["jobs"]
        self.unet_slices = tuple(pred["unet_slices"])
        self.linreg_slices = tuple(pred["linreg_slices"])
        self.pad = Profile(**{k: pad[k] for k in Profile._fields})
        self.heads = heads

    def measure(self, profs: Sequence[Profile]) -> np.ndarray:
        """The (levels, jobs) float32 matrix the probe hands the U-Net."""
        padded = list(profs) + [self.pad] * (self.jobs - len(profs))
        m = np.asarray([self.tb.mps_speeds(padded, lv)
                        for lv in self.tb.levels], dtype=np.float32)
        return m / np.maximum(m.max(axis=0, keepdims=True), 1e-9)

    def estimate(self, profs: Sequence[Profile],
                 out: np.ndarray) -> List[Dict[int, float]]:
        """Per-job slice speeds from a (3, jobs) U-Net output."""
        big = np.asarray(out).T                        # (jobs, 3)
        lin = np.concatenate([big, np.ones((len(big), 1))], axis=1) @ self.heads
        lin = np.clip(lin, 0.0, 1.0)
        est = []
        for j, p in enumerate(profs):
            sv = {s: float(big[j, r]) for r, s in enumerate(self.unet_slices)}
            sv[self.tb.full] = 1.0
            for r, s in enumerate(self.linreg_slices):
                sv[s] = float(lin[j, r])
            est.append({s: (0.0 if p.mem_gb > self.tb.slices[s]["memory_gb"]
                            else max(0.0, min(1.0, v)))
                        for s, v in sv.items()})
        return est

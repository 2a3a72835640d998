"""The MISO performance predictor: a lightweight U-Net convolutional
autoencoder (paper §4.1, Fig 7-8).

Input : (batch, L, J) MPS speed matrix — L sharing levels x J jobs
        (3 x 7 on A100; the TPU space uses 3 x 8), each column normalized
        by its max, dummy-padded to J columns.
Output: (batch, 3, J) predicted interference-free speeds on the three
        largest slice types (7g / 4g / 3g), per-column normalized.

Architecture per the paper: two encoder blocks with 32 and 64 filters into a
256-filter center, two decoder blocks with skip connections, 2x2 kernels,
(2,2) strides.  The 3x7 input is edge-replication-padded to 4x8 so the
stride-2 convs divide evenly (the paper does not specify its padding; we
avoid zero padding for the reason the paper cites — large zero regions hurt
training), and the output is cropped back.
Inference goes through one module-level jitted apply shared by every
:class:`UNet` instance (keyed on parameter shapes + input shape, so all
estimators in a process reuse one compiled executable per shape instead of
recompiling per instance), and batches are zero-padded to power-of-two
buckets on the host (numpy) so a handful of compilations serve any batch
size: a U-Net call is exactly one device program, ``_apply_jit`` at the
bucket, whose own argument transfer carries the input in.  Every
convolution runs at ``PRECISION``: float32 on every backend, where the
TPU's default would use bfloat16 passes.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from repro.utils.tree import ParamBuilder, fan_in_init

DN = ("NHWC", "HWIO", "NHWC")

# The artifacts were trained and tested in float32, and the outputs feed
# Algorithm 1's discrete partition choice, where a last-digit change can
# flip a decision.  A no-op on the CPU; on the TPU it replaces the default
# bfloat16 passes with float32-accurate ones.
PRECISION = lax.Precision.HIGHEST


def _conv_init(k_h, k_w, c_in):
    return fan_in_init(k_h * k_w * c_in)


def init(key, levels: int = 3, jobs: int = 7, dtype=jnp.float32):
    """Returns (params, specs)."""
    pb = ParamBuilder(key, dtype=dtype)

    def conv(name, kh, kw, cin, cout):
        pb.param(f"{name}_w", (kh, kw, cin, cout),
                 ("kh", "kw", "cin", "cout"), init=_conv_init(kh, kw, cin))
        pb.param(f"{name}_b", (cout,), ("cout",),
                 init=lambda k, s, d: jnp.zeros(s, d))

    conv("stem", 2, 2, 1, 16)
    conv("enc1", 2, 2, 16, 32)     # stride 2
    conv("enc2", 2, 2, 32, 64)     # stride 2
    conv("center", 2, 2, 64, 256)
    conv("dec1_up", 2, 2, 256, 64)  # transpose, stride 2
    conv("dec1", 2, 2, 64 + 32, 64)
    conv("dec2_up", 2, 2, 64, 32)   # transpose, stride 2
    conv("dec2", 2, 2, 32 + 16, 32)
    conv("head", 1, 1, 32, 1)
    return pb.build()


def _conv(x, p, name, stride=1):
    y = lax.conv_general_dilated(
        x, p[f"{name}_w"], window_strides=(stride, stride), padding="SAME",
        dimension_numbers=DN, precision=PRECISION)
    return y + p[f"{name}_b"]


def _conv_t(x, p, name):
    y = lax.conv_transpose(
        x, p[f"{name}_w"], strides=(2, 2), padding="SAME",
        dimension_numbers=DN, precision=PRECISION)
    return y + p[f"{name}_b"]


def _act(x):
    # leaky ReLU: the ASHA-tuned activation in the paper is unspecified; plain
    # ReLU collapses (dead units -> zero gradient) on this low-variance input
    return jax.nn.leaky_relu(x, negative_slope=0.1)


def pad_input(m, out_h: int = 4, out_w: int = 8):
    """Edge-replicate a (batch, L, J) matrix to (batch, out_h, out_w, 1)."""
    b, h, w = m.shape
    m = jnp.pad(m, ((0, 0), (0, out_h - h), (0, out_w - w)), mode="edge")
    return m[..., None]


def apply(params, mps_matrix, levels: int = 3, jobs: int = 7):
    """mps_matrix: (batch, levels, jobs) -> (batch, 3, jobs) in (0, 1]."""
    x = pad_input(mps_matrix)
    stem = _act(_conv(x, params, "stem"))          # (4, 8, 16)
    e1 = _act(_conv(stem, params, "enc1", stride=2))  # (2, 4, 32)
    e2 = _act(_conv(e1, params, "enc2", stride=2))    # (1, 2, 64)
    c = _act(_conv(e2, params, "center"))             # (1, 2, 256)
    d1 = _act(_conv_t(c, params, "dec1_up"))          # (2, 4, 64)
    d1 = _act(_conv(jnp.concatenate([d1, e1], -1), params, "dec1"))
    d2 = _act(_conv_t(d1, params, "dec2_up"))         # (4, 8, 32)
    d2 = _act(_conv(jnp.concatenate([d2, stem], -1), params, "dec2"))
    out = jax.nn.sigmoid(_conv(d2, params, "head"))[..., 0]  # (4, 8)
    return out[:, :3, :jobs]


@functools.partial(jax.jit, static_argnames=("levels", "jobs"))
def _apply_jit(params, mps_matrix, levels: int, jobs: int):
    return apply(params, mps_matrix, levels=levels, jobs=jobs)


def _bucket(b: int) -> int:
    """Next power-of-two batch bucket, so B estimator instances x arbitrary
    window batch sizes compile O(log B) executables instead of O(B)."""
    n = 1
    while n < b:
        n *= 2
    return n


def pad_to_bucket(m: np.ndarray) -> np.ndarray:
    """A float32 ``(batch, levels, jobs)`` host array with zero rows
    appended up to its power-of-two bucket; ``m`` itself if it is one."""
    b = len(m)
    nb = _bucket(b)
    if nb == b:
        return m
    out = np.zeros((nb,) + m.shape[1:], np.float32)
    out[:b] = m
    return out


def warm_jit_cache(levels: int = 3, jobs: int = 7,
                   batch_buckets=(1, 2, 4, 8)) -> None:
    """Compile the shared apply for the standard shapes ahead of time.

    The sweep engine calls this once per process before simulating (and
    each spawned pool worker once more: spawn shares no compiled code with
    the parent).  Compilation is keyed on parameter *shapes*, so warming
    with freshly-initialized params also covers artifact-loaded ones.
    """
    # misolint: disable=MS102 -- shape-only jit warm-up: params are discarded
    # and XLA keys its compile cache on shapes, so any constant key works
    params, _ = init(jax.random.PRNGKey(0), levels, jobs)
    for b in batch_buckets:
        m = np.zeros((b, levels, jobs), np.float32)
        _apply_jit(params, m, levels, jobs).block_until_ready()


class UNet:
    """Convenience wrapper holding params; apply is the shared jitted one."""

    def __init__(self, params, levels: int = 3, jobs: int = 7):
        self.params = params
        self.levels = levels
        self.jobs = jobs

    @classmethod
    def create(cls, key, levels: int = 3, jobs: int = 7):
        params, _ = init(key, levels, jobs)
        return cls(params, levels, jobs)

    def __call__(self, mps_matrix):
        """(levels, jobs) or (batch, levels, jobs) -> predictions of the same
        leading shape.

        The input becomes float32 on the host and, if its batch is not a
        power-of-two bucket, gets zero rows appended there (batch elements
        are independent through every conv, so padding rows never change
        real rows); then one ``_apply_jit`` call takes it to the device.
        A 3-D batch that is already a bucket comes back as the device
        array, without waiting for it; any other input is read back and
        cropped (or, for a 2-D matrix, indexed) with numpy."""
        m = np.asarray(mps_matrix, np.float32)
        single = m.ndim == 2
        if single:
            m = m[None]
        b = len(m)
        padded = pad_to_bucket(m)
        out = _apply_jit(self.params, padded, self.levels, self.jobs)
        if padded is m and not single:
            return out
        out = np.asarray(out)
        return out[0] if single else out[:b]

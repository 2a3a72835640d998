"""The comparison that decides ``correct``: sound runs pass it, and the
control and each fault the cells can have fail it.

All on the small cells on the CPU, the testbed's and a fleet of two GPU
kinds, with the plants of ``bench/faults.py``.  The control of the cells
is the program's U-Net at ``Precision.HIGH`` (three bfloat16 passes); on
the CPU that switch changes nothing, so here the control is the
reference's float64 forward computed with three-pass bfloat16 products,
put in the program's place."""
from __future__ import annotations

import sys

import ml_dtypes
import numpy as np
import pytest

from benchutil import run_small

FLEETS = pytest.mark.parametrize("fleet", ["one-kind", "two-kind"])


@pytest.fixture(autouse=True)
def _isolated(monkeypatch, tmp_path):
    monkeypatch.setattr(sys, "path", list(sys.path))
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path / "cache"))


def _plant(name, monkeypatch):
    import faults

    faults.plant(name, setattr=monkeypatch.setattr)


def _three_pass_corr(corr):
    def split(a):
        a = np.asarray(a, np.float32)
        hi = a.astype(ml_dtypes.bfloat16).astype(np.float32)
        return hi, (a - hi).astype(ml_dtypes.bfloat16).astype(np.float32)

    def corr3(x, w, stride):
        (xh, xl), (wh, wl) = split(x), split(w)
        return (corr(xh, wh, stride) + corr(xh, wl, stride)
                + corr(xl, wh, stride)).astype(np.float32)
    return corr3


@pytest.mark.parametrize("seed", [1, 2**31 + 11])
def test_sound_runs_are_correct(tmp_path, capsys, seed):
    out = run_small(tmp_path, capsys, seed=seed)
    assert out["correct"] is True
    assert out["checks"]["unet_gap"]["value"] < 1e-5
    assert out["checks"]["jct_gap"]["value"] < 1e-12


@pytest.mark.parametrize("seed", [1, 2, 2**31 + 11])
def test_sound_runs_of_two_kinds_are_correct(tmp_path, capsys, seed):
    out = run_small(tmp_path, capsys, seed=seed, fleet="two-kind")
    assert out["correct"] is True
    assert out["failed"] == 0
    assert out["checks"]["unet_gap"]["value"] < 1e-5
    assert out["checks"]["alg1_gap"]["value"] == 0.0
    assert out["checks"]["jct_gap"]["value"] < 1e-12


@pytest.mark.parametrize("key,number", [("speed_scale", "jct_gap"),
                                        ("predictor", "unet_gap")])
def test_control_reference_with_one_kind_for_all_is_not_correct(
        tmp_path, capsys, monkeypatch, key, number):
    """The reference with every group's speed scale, or every group's
    weights, taken from the first group: the per-GPU term carries weight."""
    import check

    real = check.groups
    monkeypatch.setattr(check, "groups", lambda config: [
        dict(g, **{key: real(config)[0][key]}) for g in real(config)])
    out = run_small(tmp_path, capsys, fleet="two-kind")
    assert out["checks"][number]["value"] > 1e-3
    assert out["correct"] is False


@FLEETS
def test_control_three_pass_bfloat16_is_not_correct(tmp_path, capsys,
                                                    monkeypatch, fleet):
    from ref import unet as plain

    from repro.core.predictor import unet

    corr3 = _three_pass_corr(plain._corr)

    def control(p, m, levels, jobs):
        params = {k: np.asarray(v) for k, v in p.items()}
        exact, plain._corr = plain._corr, corr3
        try:
            return plain.forward(params, np.asarray(m)).astype(np.float32)
        finally:
            plain._corr = exact

    monkeypatch.setattr(unet, "_apply_jit", control)
    out = run_small(tmp_path, capsys, fleet=fleet)
    gap = out["checks"]["unet_gap"]
    assert gap["value"] > gap["limit"]
    assert out["correct"] is False


@FLEETS
def test_fault_step_leaves_state_unchanged(tmp_path, capsys, monkeypatch, fleet):
    _plant("unchanged", monkeypatch)
    out = run_small(tmp_path, capsys, fleet=fleet)
    assert out["checks"]["jct_gap"]["value"] == 1.0
    assert out["failed"] == out["attempted"]
    assert out["correct"] is False


@FLEETS
def test_fault_half_the_batch_left_out(tmp_path, capsys, monkeypatch, fleet):
    _plant("half", monkeypatch)
    out = run_small(tmp_path, capsys, fleet=fleet)
    assert out["checks"]["jct_gap"]["value"] == 1.0
    assert out["failed"] == out["attempted"] // 2
    assert out["correct"] is False


@FLEETS
def test_fault_estimator_answer_altered(tmp_path, capsys, monkeypatch, fleet):
    _plant("unet", monkeypatch)
    out = run_small(tmp_path, capsys, fleet=fleet)
    assert out["checks"]["unet_gap"]["value"] > 1e-4
    assert out["correct"] is False


@FLEETS
def test_fault_completion_time_altered(tmp_path, capsys, monkeypatch, fleet):
    _plant("completion", monkeypatch)
    out = run_small(tmp_path, capsys, fleet=fleet)
    assert 1e-8 < out["checks"]["jct_gap"]["value"] < 1e-3
    assert out["correct"] is False


@FLEETS
def test_fault_partition_answer_altered(tmp_path, capsys, monkeypatch, fleet):
    _plant("alg1", monkeypatch)
    out = run_small(tmp_path, capsys, fleet=fleet)
    assert out["checks"]["alg1_gap"]["value"] > 1e-3
    assert out["correct"] is False


@FLEETS
def test_fault_placement_answer_altered(tmp_path, capsys, monkeypatch, fleet):
    _plant("placement", monkeypatch)
    out = run_small(tmp_path, capsys, fleet=fleet)
    assert out["checks"]["alg1_gap"]["value"] == 1.0
    assert out["correct"] is False

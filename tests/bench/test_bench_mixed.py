"""The mixed deployment ``miso-a100h100``: MISO's testbed of 8 A100-40GB
plus one 8-GPU H100-80GB node.  Its file states what the program's spec
holds, its large rows are what the cost model gives, and a cut of it runs
through the harness on the CPU, correct, and fails with the control."""
from __future__ import annotations

import copy
import dataclasses
import json
import os
import sys

import numpy as np
import pytest

from benchutil import BENCH, StandInChip, make_root
from test_bench_check import _three_pass_corr

#: the rows added to the testbed's 32: one per pool family
LARGE = ("smollm-360m/b512", "granite-dense-700m/b256", "rwkv6-430m/b512",
         "recurrentgemma-400m/b1024", "qwen2-moe-1b/b1024",
         "musicgen-300m/b512", "mixtral-micro-1b/b512",
         "chameleon-550m/b512")
CUT_CELL = {"name": "tiny-a100h100.b4", "config": "tiny-a100h100",
            "traffic": "tiny-a100h100-b4", "chips": 1, "why": "test"}


def _load(sub, name):
    with open(os.path.join(BENCH, sub, name + ".json")) as fh:
        return json.load(fh)


@pytest.fixture(autouse=True)
def _isolated(monkeypatch, tmp_path):
    monkeypatch.setattr(sys, "path", list(sys.path))
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path / "cache"))


def test_committed_configuration_passes_the_check():
    import run

    groups = run.check_config(_load("configs", "miso-a100h100"))
    assert [(g["kind"], g["gpus"], spec.speed_scale)
            for g, spec in groups] == [("a100", 8, 1.0), ("h100", 8, 2.0)]


def test_large_rows_are_the_cost_models():
    from repro.core.jobs import _POOL_CONFIGS, _SEQ, job_profile
    from repro.roofline.costs import step_costs

    config = _load("configs", "miso-a100h100")
    testbed = _load("configs", "miso-testbed")
    rows = config["workloads"]
    assert rows[:32] == testbed["workloads"]
    assert [r["name"] for r in rows[32:]] == list(LARGE)
    for row in rows[32:]:
        model, batch = row["model"], row["batch"]
        cfg = _POOL_CONFIGS[model]
        mem = step_costs(cfg, _SEQ, batch, "train").mem_bytes / 1e9
        assert 20.0 < mem <= 40.0
        smaller = [b for b in (128, 256, 512, 1024) if b < batch]
        assert all(step_costs(cfg, _SEQ, b, "train").mem_bytes / 1e9 <= 20.0
                   for b in smaller)
        assert row == dict(dataclasses.asdict(job_profile(model, batch)),
                           mem_gb=mem)


def test_large_rows_fit_an_h100_40gb_slice_and_only_a_whole_a100():
    """By the program's memory monitor on each kind's menu."""
    import run

    from repro.core.estimators import _apply_mem_constraints
    from repro.core.jobs import JobProfile

    config = _load("configs", "miso-a100h100")
    spaces = {g["kind"]: spec.space for g, spec in run.check_config(config)}
    for row in config["workloads"][32:]:
        prof = JobProfile(**row)
        fits = {kind: {space.slices[s].name for s, v in
                       _apply_mem_constraints(
                           space, prof, dict.fromkeys(space.sizes, 0.5)
                       ).items() if v > 0}
                for kind, space in spaces.items()}
        assert fits["a100"] == {"7g.40gb"}, row["name"]
        assert "3g.40gb" in fits["h100"], row["name"]


def _cut_root(tmp_path):
    """The committed configuration on 2 A100 and 2 H100 GPUs, and its
    traffic at 16 jobs over 4 replicas, the gap scaled to the capacity
    left (a quarter of it)."""
    config = _load("configs", "miso-a100h100")
    config = dict(copy.deepcopy(config), name=CUT_CELL["config"])
    for g in config["fleet"]:
        g["gpus"] = 2
    traffic = _load("traffic", "a100h100-rollout-b16")
    traffic.update(name=CUT_CELL["traffic"], replicas=4, traces=8, jobs=16,
                   mean_gap_s=traffic["mean_gap_s"] * 4, unet_batch_max=8)
    return make_root(tmp_path, [CUT_CELL], {config["name"]: config},
                     {traffic["name"]: traffic})


def _run_cut(tmp_path, capsys, trace=0, seed=5):
    import run

    rc = run.main(["--workload", CUT_CELL["name"], "--seed", str(seed),
                   "--seconds", "0.1", "--trace", str(trace)],
                  root=_cut_root(tmp_path),
                  chips_check=lambda n: StandInChip())
    assert rc == 0
    return json.loads(capsys.readouterr().out.strip().splitlines()[-1])


@pytest.mark.parametrize("trace", [0, 1])
def test_cut_configuration_runs_correct(tmp_path, capsys, trace):
    out = _run_cut(tmp_path, capsys, trace=trace, seed=2**31 + 11)
    assert out["correct"] is True
    assert out["failed"] == 0 and out["attempted"] > 0
    if trace:
        metrics = out["metrics"]
        assert metrics["unet_forwards_per_stage_a"]["value"] > 1.0
        assert metrics["alg1_solves_per_stage_c"]["value"] > 1.0
        assert metrics["unet_windows_per_call"]["value"] >= 1.0
    else:
        assert {"setup_s", "sim_jobs_per_s"} <= set(out["metrics"])


def test_cut_configuration_control_three_pass_bfloat16_is_not_correct(
        tmp_path, capsys, monkeypatch):
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
    out = _run_cut(tmp_path, capsys)
    gap = out["checks"]["unet_gap"]
    assert gap["value"] > gap["limit"]
    assert out["correct"] is False

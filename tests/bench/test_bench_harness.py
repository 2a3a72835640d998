"""The benchmark's harness: everything is found by name, names and units
keep to their characters, and no result comes without a chip."""
from __future__ import annotations

import json
import os
import re
import shutil
import subprocess
import sys

import pytest

from benchutil import (BENCH, REPO, SMALL_CELL, SMALL_TRAFFIC, TWO_KIND_CELL,
                       TWO_KIND_TRAFFIC, load_benchmark, make_root,
                       run_small, two_kind_config)
from ref.fleet import groups  # on the path through benchutil

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}


def test_every_cell_resolves_to_its_files():
    import run

    spec = load_benchmark()
    for cell in spec["workloads"]:
        for trace in (False, True):
            got = run.load_cell(REPO, cell["name"], trace)
            assert got["config_data"]["name"] == cell["config"]
            assert got["traffic_data"]["name"] == cell["traffic"]
            kinds = spec["per_layer" if trace else "end_to_end"]
            want = [m["name"] for m in kinds
                    if cell["name"] in m.get("workloads", [cell["name"]])]
            assert [m["name"] for m, _ in got["metrics"]] == want
            assert all(callable(read) for _, read in got["metrics"])
    for c in spec["configs"]:
        assert os.path.isfile(os.path.join(REPO, c["file"]))


def test_a_cell_added_as_data_alone_is_found(tmp_path):
    import run

    config = {"name": "tiny-config", "source": "test", "gpus": 2}
    cell = dict(SMALL_CELL, name="tiny.data-only", config="tiny-config")
    root = make_root(tmp_path, [cell], {"tiny-config": config},
                     {SMALL_TRAFFIC["name"]: SMALL_TRAFFIC})
    got = run.load_cell(root, cell["name"], True)
    assert got["config_data"] == config
    assert got["traffic_data"] == SMALL_TRAFFIC
    assert {m["name"] for m, _ in got["metrics"]} == {
        m["name"] for m in load_benchmark()["per_layer"]}
    with pytest.raises(SystemExit):
        run.load_cell(root, "no.such-cell", False)


def test_names_units_and_sources_keep_to_the_contract():
    spec = load_benchmark()
    assert spec["command"] == ["python3", "bench/run.py"]
    names = []
    for c in spec["configs"]:
        names.append(c["name"])
        assert all(NAME.match(k) for k in c["reduced"])
        assert c["file"].startswith("bench/configs/")
    for w in spec["workloads"]:
        names.append(w["name"])
        assert NAME.match(w["config"]) and NAME.match(w["traffic"])
        assert w["chips"] in (1, 4) and 1 <= len(w["why"]) <= 200
    for m in spec["end_to_end"] + spec["per_layer"]:
        names.append(m["name"])
        assert UNIT.match(m["unit"]), m
        assert m["better"] in ("lower", "higher") and m["source"] in SOURCES
    for m in spec["end_to_end"]:
        assert 0.01 <= m["bound"] <= 0.25
        assert m["source"] in ("host_clock", "device_trace")
    e2e = {m["name"] for m in spec["end_to_end"]}
    assert all(m["moves"] in e2e for m in spec["per_layer"])
    assert all(NAME.match(n) for n in names), names
    assert len(names) == len(set(names))


def test_config_files_state_their_cuts():
    spec = load_benchmark()
    for c in spec["configs"]:
        with open(os.path.join(REPO, c["file"])) as fh:
            data = json.load(fh)
        assert data["name"] == c["name"] and data["source"] == c["source"]
        assert sorted(data["reduced"]) == sorted(c["reduced"])
        assert all(k in data for k in c["reduced"])
        for g in groups(data):
            assert os.path.isfile(os.path.join(REPO,
                                               g["predictor"]["weights"]))


def test_a_fleet_of_one_kind_reads_as_one_group():
    with open(os.path.join(BENCH, "configs", "miso-testbed.json")) as fh:
        config = json.load(fh)
    assert groups(config) == [{
        "kind": "a100", "gpus": 8, "speed_scale": 1.0,
        "mig": config["mig"], "hardware": config["hardware"],
        "predictor": config["predictor"]}]
    mixed = groups(two_kind_config())
    assert [(g["kind"], g["gpus"], g["speed_scale"]) for g in mixed] == [
        ("a100", 2, 1.0), ("h100", 2, 2.0)]
    assert mixed[0]["mig"] == config["mig"]


def _h100_with_a100_menu(config):
    config["fleet"][1]["mig"] = config["fleet"][0]["mig"]


def _h100_at_a100_speed(config):
    config["fleet"][1]["speed_scale"] = 1.0


def _speed_aware_placer(config):
    config["placer"] = "hetero-speed"


@pytest.mark.parametrize("edit,key", [
    (_h100_with_a100_menu, "mig.slices[1].name"),
    (_h100_at_a100_speed, "speed_scale"),
    (_speed_aware_placer, "placer")])
def test_a_configuration_the_reference_does_not_state_exits_first(
        tmp_path, edit, key):
    import run

    config = two_kind_config()
    edit(config)
    root = make_root(tmp_path, [TWO_KIND_CELL], {config["name"]: config},
                     {TWO_KIND_TRAFFIC["name"]: TWO_KIND_TRAFFIC})

    def no_chip(n):
        raise AssertionError("reached the chip check")

    with pytest.raises(SystemExit) as exit_:
        run.main(["--workload", TWO_KIND_CELL["name"], "--seed", "1",
                  "--seconds", "0.1"], root=root, chips_check=no_chip)
    msg = str(exit_.value.code)
    assert key in msg and "nothing was run" in msg, msg


def _run_script(cwd, env_extra):
    env = dict(os.environ, **env_extra)
    return subprocess.run(
        [sys.executable, "bench/run.py", "--workload", "testbed.replay-b1",
         "--seed", "1", "--seconds", "1", "--trace", "0"],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=120)


def test_no_result_without_a_tpu():
    proc = _run_script(REPO, {"JAX_PLATFORMS": "cpu"})
    assert proc.returncode != 0
    assert proc.stdout == ""
    assert "needs 1 TPU chip" in proc.stderr
    assert "[bench] replays" not in proc.stderr


def test_no_result_from_the_benchmark_files_alone(tmp_path):
    shutil.copy(os.path.join(REPO, "BENCHMARK.json"), tmp_path)
    for p in load_benchmark()["paths"]:
        shutil.copytree(os.path.join(REPO, p), os.path.join(tmp_path, p),
                        ignore=shutil.ignore_patterns("__pycache__"))
    proc = _run_script(str(tmp_path), {"JAX_PLATFORMS": "cpu"})
    assert proc.returncode != 0
    assert proc.stdout == ""


def test_small_cell_runs_end_to_end(tmp_path, capsys, monkeypatch):
    monkeypatch.setattr(sys, "path", list(sys.path))
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path / "cache"))
    out = run_small(tmp_path, capsys)
    assert out["correct"] is True
    assert out["failed"] == 0 and out["attempted"] > 0
    assert set(out["metrics"]) == {"setup_s", "sim_jobs_per_s", "round_p95_ms"}
    assert all(m["value"] > 0 for m in out["metrics"].values())
    assert list(out)[-1] == "checks"
    assert set(out["checks"]) == {"unet_gap", "alg1_gap", "jct_gap"}
    assert all(c["value"] <= c["limit"] for c in out["checks"].values())
    assert out["device"]["count"] >= 1


def test_small_cell_traced_run_reads_the_host_layers(tmp_path, capsys,
                                                     monkeypatch):
    monkeypatch.setattr(sys, "path", list(sys.path))
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path / "cache"))
    out = run_small(tmp_path, capsys, trace=1)
    assert out["correct"] is True
    host = {"event_loop_us_per_event", "placement_ms_per_kjob",
            "estimator_ms_per_kjob", "estimator_calls_per_kjob",
            "alg1_ms_per_kjob"}
    assert host <= set(out["metrics"])
    # the CPU has no TPU plane: the device metrics stay out of the line
    assert "device_idle_share" not in out["metrics"]
    assert all(out["metrics"][k]["value"] > 0 for k in host)

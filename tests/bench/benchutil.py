"""Shared helpers for the benchmark's tests: a checkout-shaped root holding
``BENCHMARK.json`` and the benchmark's data files, a small cell that a CPU
can run in seconds, and a stand-in for the chip."""
from __future__ import annotations

import json
import os
import shutil
import sys

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
BENCH = os.path.join(REPO, "bench")
if BENCH not in sys.path:
    sys.path.insert(0, BENCH)

#: a small cell: four lockstep replicas of 16 jobs on the testbed
SMALL_TRAFFIC = {"name": "tiny-b4", "about": "test", "replicas": 4,
                 "traces": 8, "jobs": 16, "mean_gap_s": 60.0,
                 "work_s": {"median": 735.0951892419727, "sigma": 1.1,
                            "min": 60.0, "max": 7200.0},
                 "draw_seed": 0, "unet_batch_max": 8}
SMALL_CELL = {"name": "tiny.b4", "config": "miso-testbed",
              "traffic": "tiny-b4", "chips": 1, "why": "test"}


def load_benchmark() -> dict:
    with open(os.path.join(REPO, "BENCHMARK.json")) as fh:
        return json.load(fh)


def make_root(tmp_path, extra_cells=(), configs=None, traffic=None) -> str:
    """A root with ``BENCHMARK.json`` (plus ``extra_cells``) and copies of
    the benchmark's configs, traffic mixes, metric readers and weights,
    plus the given extra data files (name -> dict)."""
    root = str(tmp_path)
    os.makedirs(root, exist_ok=True)
    spec = load_benchmark()
    spec["workloads"] = spec["workloads"] + list(extra_cells)
    for m in spec["per_layer"] + spec["end_to_end"]:
        if "workloads" in m:
            m["workloads"] = m["workloads"] + [c["name"] for c in extra_cells]
    with open(os.path.join(root, "BENCHMARK.json"), "w") as fh:
        json.dump(spec, fh)
    for sub in ("configs", "traffic", "metrics", "weights"):
        shutil.copytree(os.path.join(BENCH, sub),
                        os.path.join(root, "bench", sub))
    for sub, files in (("configs", configs or {}), ("traffic", traffic or {})):
        for name, data in files.items():
            with open(os.path.join(root, "bench", sub, name + ".json"),
                      "w") as fh:
                json.dump(data, fh)
    return root


def small_root(tmp_path) -> str:
    return make_root(tmp_path, [SMALL_CELL],
                     traffic={SMALL_TRAFFIC["name"]: SMALL_TRAFFIC})


class StandInChip:
    """What the harness reads of a device, for runs on the CPU."""
    platform = "cpu"
    device_kind = "cpu stand-in"

    def memory_stats(self):
        return {"peak_bytes_in_use": 1}


def run_small(tmp_path, capsys, seed=5, trace=0):
    """Drive ``bench/run.py`` end to end on the small cell, chip check
    skipped; returns the result line as a dict."""
    import run

    root = small_root(tmp_path)
    rc = run.main(["--workload", SMALL_CELL["name"], "--seed", str(seed),
                   "--seconds", "0.1", "--trace", str(trace)], root=root,
                  chips_check=lambda n: StandInChip())
    assert rc == 0
    return json.loads(capsys.readouterr().out.strip().splitlines()[-1])

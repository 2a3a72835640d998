"""Shared helpers for the benchmark's tests: a checkout-shaped root holding
``BENCHMARK.json`` and the benchmark's data files, small cells that a CPU
can run in seconds (the testbed, and a fleet of two GPU kinds), and a
stand-in for the chip."""
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

#: the H100-80GB group as the program's spec states it: its MIG menu,
#: speed-model constants and speed scale
H100_GROUP = {
    "kind": "h100", "gpus": 2, "speed_scale": 2.0,
    "mig": {"compute_slots": 7, "memory_slots": 8, "exclusions": [[3, 4]],
            "slices": [
                {"size": 7, "name": "7g.80gb", "compute_slots": 7,
                 "memory_slots": 8, "memory_gb": 80.0, "max_count": 1,
                 "cache_frac": 1.0},
                {"size": 4, "name": "4g.40gb", "compute_slots": 4,
                 "memory_slots": 4, "memory_gb": 40.0, "max_count": 1,
                 "cache_frac": 0.5},
                {"size": 3, "name": "3g.40gb", "compute_slots": 3,
                 "memory_slots": 4, "memory_gb": 40.0, "max_count": 2,
                 "cache_frac": 0.5},
                {"size": 2, "name": "2g.20gb", "compute_slots": 2,
                 "memory_slots": 2, "memory_gb": 20.0, "max_count": 3,
                 "cache_frac": 0.25},
                {"size": 1, "name": "1g.10gb", "compute_slots": 1,
                 "memory_slots": 1, "memory_gb": 10.0, "max_count": 7,
                 "cache_frac": 0.125}]},
    "hardware": {"peak_flops": 989e12, "hbm_bw": 3.35e12, "mem_gb": 80.0,
                 "cache_mps_kappa": 1.5, "cache_mig_kappa": 0.45,
                 "mps_mux_overhead": 0.12, "mps_bw_loss": 0.12,
                 "sched_overhead_s": 1e-3},
    "predictor": {"weights": "bench/weights/predictor_h100.npz",
                  "levels": 3, "jobs": 7, "unet_slices": [7, 4, 3],
                  "linreg_slices": [2, 1], "precision": "float32"}}
#: the small mix on the two-kind pool: ``draw_seed`` 1 is the first whose
#: 16 jobs hold both rows of 30 GB
TWO_KIND_TRAFFIC = dict(SMALL_TRAFFIC, name="tiny-mixed-b4", draw_seed=1)
TWO_KIND_CELL = {"name": "tiny-mixed.b4", "config": "tiny-mixed",
                 "traffic": "tiny-mixed-b4", "chips": 1, "why": "test"}


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


def two_kind_config() -> dict:
    """The testbed on 2 A100 and 2 H100 GPUs.  Its pool is the testbed's
    32 rows and two of 30 GB, which an A100 holds only on its whole GPU
    and alone, and an H100 also on a 40 GB slice beside other jobs."""
    with open(os.path.join(BENCH, "configs", "miso-testbed.json")) as fh:
        config = json.load(fh)
    a100 = {k: config.pop(k) for k in ("kind", "gpus", "mig", "hardware",
                                        "predictor")}
    config.update(name="tiny-mixed", fleet=[
        dict(a100, gpus=2, speed_scale=1.0), H100_GROUP])
    config["workloads"] += [dict(row, name=row["name"] + "+30gb",
                                 mem_gb=30.0)
                            for row in config["workloads"][-2:]]
    return config


#: fleet -> (cell, configurations, traffic mixes) of its small root
SMALL = {"one-kind": (SMALL_CELL, {}, {SMALL_TRAFFIC["name"]: SMALL_TRAFFIC}),
         "two-kind": (TWO_KIND_CELL, {"tiny-mixed": two_kind_config()},
                      {TWO_KIND_TRAFFIC["name"]: TWO_KIND_TRAFFIC})}


def small_root(tmp_path, fleet="one-kind") -> str:
    cell, configs, traffic = SMALL[fleet]
    return make_root(tmp_path, [cell], configs, traffic)


class StandInChip:
    """What the harness reads of a device, for runs on the CPU."""
    platform = "cpu"
    device_kind = "cpu stand-in"

    def memory_stats(self):
        return {"peak_bytes_in_use": 1}


def run_small(tmp_path, capsys, seed=5, trace=0, fleet="one-kind"):
    """Drive ``bench/run.py`` end to end on the small cell of ``fleet``,
    chip check skipped; returns the result line as a dict."""
    import run

    root = small_root(tmp_path, fleet)
    rc = run.main(["--workload", SMALL[fleet][0]["name"], "--seed",
                   str(seed), "--seconds", "0.1", "--trace", str(trace)],
                  root=root, chips_check=lambda n: StandInChip())
    assert rc == 0
    return json.loads(capsys.readouterr().out.strip().splitlines()[-1])

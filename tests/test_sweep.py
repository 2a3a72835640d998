"""Parallel sweep engine: schema stability (v3: the objective axis and
energy columns), deterministic serial/parallel equivalence,
fleet/placer/objective overrides, the report differ's v1/v2/v3
compatibility, and the CLI entry point."""
import importlib.util
import json
import os

import pytest

from repro.launch.sweep import SCHEMA_VERSION, run_sweep, run_task

RESULT_KEYS = {"policy", "placer", "objective", "scenario", "seed", "fleet",
               "n_jobs", "n_completed", "metrics", "wall_s"}
METRIC_KEYS = {"avg_jct_s", "p50_jct_s", "p90_jct_s", "makespan_s", "stp",
               "energy_j", "avg_power_w", "energy_per_job_j",
               "jct_per_joule", "breakdown_s",
               # v4 robustness columns
               "goodput", "gross_stp", "work_lost_s", "n_fault_events",
               "blast_jobs", "blast_radius_max", "mean_recover_s",
               "quarantine_occupancy", "n_quarantines", "n_migrations"}
SUMMARY_KEYS = {"avg_jct_s_mean", "p90_jct_s_mean", "stp_mean",
                "makespan_s_mean", "energy_j_mean", "energy_per_job_j_mean",
                "goodput_mean", "work_lost_s_mean"}


def test_run_task_schema():
    r = run_task({"policy": "miso", "scenario": "smoke", "seed": 0})
    assert set(r) == RESULT_KEYS
    assert set(r["metrics"]) == METRIC_KEYS
    assert r["n_completed"] == r["n_jobs"] > 0
    assert r["fleet"] == "a100:2"            # smoke's default fleet
    assert r["placer"] == "least-loaded"     # smoke's default placer
    assert r["objective"] == "throughput"    # smoke's default objective
    assert r["metrics"]["energy_j"] > 0.0    # energy integration is live
    json.dumps(r)                            # JSON-serializable end to end


def test_run_sweep_serial_grid():
    rep = run_sweep(["miso", "srpt"], ["smoke"], seeds=[0, 1], serial=True)
    assert rep["schema_version"] == SCHEMA_VERSION
    assert rep["kind"] == "miso-sweep"
    assert len(rep["results"]) == 4
    keys = [(r["scenario"], r["policy"], r["placer"], r["objective"],
             r["seed"]) for r in rep["results"]]
    assert keys == sorted(keys)              # stable result ordering
    assert set(rep["summary"]["smoke"]) == {"miso", "srpt"}
    for by_placer in rep["summary"]["smoke"].values():
        assert set(by_placer) == {"least-loaded"}
        for by_obj in by_placer.values():
            assert set(by_obj) == {"throughput"}
            for agg in by_obj.values():
                assert set(agg) == SUMMARY_KEYS


def test_placer_axis_crosses_grid():
    rep = run_sweep(["miso"], ["smoke"], seeds=[0],
                    placers=["least-loaded", "hetero-speed"], serial=True)
    assert len(rep["results"]) == 2
    assert {r["placer"] for r in rep["results"]} == {"least-loaded",
                                                     "hetero-speed"}
    assert set(rep["summary"]["smoke"]["miso"]) == {"least-loaded",
                                                    "hetero-speed"}
    assert rep["config"]["placers"] == ["least-loaded", "hetero-speed"]
    # smoke's a100-only fleet has one speed class: hetero-speed degenerates
    # to least-loaded, so both cells carry identical metrics
    a, b = rep["results"]
    assert a["metrics"] == b["metrics"]


def test_objective_axis_crosses_grid():
    rep = run_sweep(["miso"], ["smoke"], seeds=[0],
                    objectives=["throughput", "energy", "edp"], serial=True)
    assert len(rep["results"]) == 3
    assert {r["objective"] for r in rep["results"]} == {"throughput",
                                                        "energy", "edp"}
    by_obj = rep["summary"]["smoke"]["miso"]["least-loaded"]
    assert set(by_obj) == {"throughput", "energy", "edp"}
    assert rep["config"]["objectives"] == ["throughput", "energy", "edp"]
    for agg in by_obj.values():
        assert agg["energy_j_mean"] > 0.0


def test_parallel_matches_serial():
    strip = lambda rep: [(r["policy"], r["scenario"], r["seed"], r["metrics"])
                         for r in rep["results"]]
    a = run_sweep(["miso"], ["smoke"], seeds=[0, 1], serial=True)
    b = run_sweep(["miso"], ["smoke"], seeds=[0, 1], workers=2)
    assert strip(a) == strip(b)
    assert b["config"]["workers"] == 2 and not b["config"]["serial"]


def test_fleet_and_jobs_override():
    rep = run_sweep(["miso"], ["smoke"], seeds=[0], fleet="a100:1+h100:1",
                    n_jobs=6, serial=True)
    (r,) = rep["results"]
    assert r["fleet"] == "a100:1+h100:1"
    assert r["n_jobs"] == 6
    assert rep["config"]["fleet"] == "a100:1+h100:1"


@pytest.mark.slow
def test_sweep_cli_writes_report(tmp_path):
    from repro.launch import sweep
    out = tmp_path / "report.json"
    rc = sweep.main(["--scenarios", "smoke", "--seeds", "1",
                     "--policies", "miso", "--serial", "--out", str(out)])
    assert rc == 0
    rep = json.loads(out.read_text())
    assert rep["schema_version"] == SCHEMA_VERSION
    assert rep["results"]


def test_cli_rejects_unknown_names():
    from repro.launch import sweep
    with pytest.raises(ValueError, match="unknown scheduling policy"):
        sweep.main(["--policies", "nope", "--scenarios", "smoke",
                    "--seeds", "1"])
    with pytest.raises(ValueError, match="unknown scenario"):
        sweep.main(["--policies", "miso", "--scenarios", "nope",
                    "--seeds", "1"])


# ------------------------------------------------------------- hardening

def test_error_cell_isolated_not_fatal(monkeypatch):
    """A cell whose simulation raises lands in report["errors"] with the
    failure recorded; the rest of the grid still produces results."""
    from repro.launch import sweep

    real = sweep.run_task

    def flaky(task):
        if task["seed"] == 1:
            raise RuntimeError("boom")
        return real(task)

    monkeypatch.setattr(sweep, "run_task", flaky)
    rep = sweep.run_sweep(["miso"], ["smoke"], seeds=[0, 1], serial=True,
                          retries=2)
    assert len(rep["results"]) == 1
    assert rep["results"][0]["seed"] == 0
    (err,) = rep["errors"]
    assert err["seed"] == 1 and err["attempts"] == 2
    assert "RuntimeError: boom" in err["error"]
    # error cells carry resolved identity keys and never reach the summary
    assert err["placer"] == "least-loaded"
    assert set(rep["summary"]["smoke"]["miso"]["least-loaded"]
               ["throughput"]) == SUMMARY_KEYS
    json.dumps(rep)


def test_cli_exits_nonzero_on_error_cell(tmp_path, monkeypatch):
    """The CLI writes the report with its error records and then exits
    non-zero, so a crashed cell cannot pass as a finished sweep."""
    from repro.launch import sweep

    real = sweep.run_task

    def flaky(task):
        if task["seed"] == 1:
            raise RuntimeError("boom")
        return real(task)

    monkeypatch.setattr(sweep, "run_task", flaky)
    out = tmp_path / "report.json"
    rc = sweep.main(["--scenarios", "smoke", "--seeds", "2", "--policies",
                     "miso", "--serial", "--out", str(out)])
    assert rc != 0
    rep = json.loads(out.read_text())
    (err,) = rep["errors"]
    assert "RuntimeError: boom" in err["error"]
    assert [r["seed"] for r in rep["results"]] == [0]


def test_pool_refuses_non_cpu_backend(monkeypatch):
    """On an accelerator backend the pool never starts: its workers would
    each try to load the device runtime that this process holds."""
    import jax

    from repro.launch import sweep

    live = sweep._POOL       # an earlier test's CPU pool may be alive
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    with pytest.raises(RuntimeError, match="--engine batched or --serial"):
        sweep.run_sweep(["miso"], ["smoke"], seeds=[0, 1], workers=3)
    assert sweep._POOL is live


def test_cell_timeout_records_error(monkeypatch):
    """A cell that exceeds its wall-clock budget is killed by the SIGALRM
    guard and recorded, not hung forever."""
    import signal as _signal

    import pytest as _pytest

    if not hasattr(_signal, "SIGALRM"):
        _pytest.skip("no SIGALRM on this platform")
    from repro.launch import sweep

    def hang(task):
        import time as _t
        _t.sleep(30.0)

    monkeypatch.setattr(sweep, "run_task", hang)
    rep = sweep.run_sweep(["miso"], ["smoke"], seeds=[0], serial=True,
                          cell_timeout=0.2)
    assert rep["results"] == []
    (err,) = rep["errors"]
    assert "CellTimeout" in err["error"]
    assert rep["config"]["cell_timeout_s"] == 0.2


def test_resume_skips_completed_cells(tmp_path, monkeypatch):
    """--resume carries successful cells of a partial same-schema report
    over verbatim and only runs the missing ones."""
    from repro.launch import sweep

    partial = sweep.run_sweep(["miso"], ["smoke"], seeds=[0], serial=True)
    p = tmp_path / "partial.json"
    p.write_text(json.dumps(partial))

    ran = []
    real = sweep.run_task

    def spy(task):
        ran.append(task["seed"])
        return real(task)

    monkeypatch.setattr(sweep, "run_task", spy)
    rep = sweep.run_sweep(["miso"], ["smoke"], seeds=[0, 1], serial=True,
                          resume=str(p))
    assert ran == [1]                    # seed 0 came from the partial
    assert len(rep["results"]) == 2
    assert rep["config"]["resumed_cells"] == 1
    assert rep["results"][0]["metrics"] == partial["results"][0]["metrics"]


def test_resume_ignores_other_schema_versions(tmp_path):
    """A partial report from a different schema version resumes nothing
    (its metric columns would not line up), and a non-sweep JSON is
    rejected outright."""
    from repro.launch import sweep

    old = {"schema_version": SCHEMA_VERSION - 1, "kind": "miso-sweep",
           "results": [{"scenario": "smoke", "policy": "miso",
                        "placer": "least-loaded",
                        "objective": "throughput", "seed": 0}]}
    p = tmp_path / "old.json"
    p.write_text(json.dumps(old))
    rep = sweep.run_sweep(["miso"], ["smoke"], seeds=[0], serial=True,
                          resume=str(p))
    assert rep["config"]["resumed_cells"] == 0
    assert len(rep["results"]) == 1
    bad = tmp_path / "bad.json"
    bad.write_text(json.dumps({"kind": "something-else"}))
    with pytest.raises(ValueError, match="not a miso-sweep report"):
        sweep.run_sweep(["miso"], ["smoke"], seeds=[0], serial=True,
                        resume=str(bad))


# ------------------------------------------------------------ diff_sweeps

def _load_diff_sweeps():
    path = os.path.join(os.path.dirname(__file__), "..", "benchmarks",
                        "diff_sweeps.py")
    spec = importlib.util.spec_from_file_location("diff_sweeps", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_diff_sweeps_reads_v1_v2_and_v3_summaries(tmp_path):
    """v1 (pre-placer) and v2 (pre-objective) reports normalize to
    placer=least-loaded / objective=throughput and compare cleanly against
    v3 candidates."""
    ds = _load_diff_sweeps()
    agg = {"avg_jct_s_mean": 100.0, "p90_jct_s_mean": 200.0,
           "stp_mean": 1.5, "makespan_s_mean": 400.0}
    v1 = {"schema_version": 1, "kind": "miso-sweep",
          "summary": {"smoke": {"miso": agg}}}
    v2 = {"schema_version": 2, "kind": "miso-sweep",
          "summary": {"smoke": {"miso": {"least-loaded": agg}}}}
    v3 = {"schema_version": 3, "kind": "miso-sweep",
          "summary": {"smoke": {"miso": {"least-loaded":
                                         {"throughput": agg}}}}}
    p1, p2, p3 = tmp_path / "v1.json", tmp_path / "v2.json", \
        tmp_path / "v3.json"
    p1.write_text(json.dumps(v1))
    p2.write_text(json.dumps(v2))
    p3.write_text(json.dumps(v3))
    key = ("smoke", "miso", "least-loaded", "throughput")
    assert ds.load_summary(str(p1)) == {key: agg}
    assert ds.load_summary(str(p2)) == {key: agg}
    assert ds.load_summary(str(p3)) == {key: agg}
    for old in (p1, p2):
        regressions, notes = ds.diff_reports(str(old), str(p3),
                                             threshold=0.02)
        assert regressions == [] and notes == []


def test_diff_sweeps_flags_regressions_per_placer(tmp_path):
    ds = _load_diff_sweeps()
    base_agg = {"avg_jct_s_mean": 100.0, "stp_mean": 1.5}
    bad_agg = {"avg_jct_s_mean": 150.0, "stp_mean": 1.5}
    base = {"schema_version": 2, "kind": "miso-sweep",
            "summary": {"smoke": {"miso": {"least-loaded": base_agg,
                                           "hetero-speed": base_agg}}}}
    cand = {"schema_version": 2, "kind": "miso-sweep",
            "summary": {"smoke": {"miso": {"least-loaded": base_agg,
                                           "hetero-speed": bad_agg}}}}
    pb, pc = tmp_path / "base.json", tmp_path / "cand.json"
    pb.write_text(json.dumps(base))
    pc.write_text(json.dumps(cand))
    regressions, _ = ds.diff_reports(str(pb), str(pc), threshold=0.02)
    assert len(regressions) == 1
    assert "smoke/miso/hetero-speed/throughput" in regressions[0]


def test_diff_sweeps_flags_energy_regressions(tmp_path):
    """The v3 energy columns gate exactly like the JCT ones: more joules
    than baseline (beyond threshold) fails."""
    ds = _load_diff_sweeps()
    base_agg = {"avg_jct_s_mean": 100.0, "energy_j_mean": 1.0e6}
    bad_agg = {"avg_jct_s_mean": 100.0, "energy_j_mean": 1.1e6}
    mk = lambda agg: {"schema_version": 3, "kind": "miso-sweep",
                      "summary": {"smoke": {"miso": {"least-loaded":
                                                     {"energy": agg}}}}}
    pb, pc = tmp_path / "base.json", tmp_path / "cand.json"
    pb.write_text(json.dumps(mk(base_agg)))
    pc.write_text(json.dumps(mk(bad_agg)))
    regressions, _ = ds.diff_reports(str(pb), str(pc), threshold=0.02)
    assert len(regressions) == 1
    assert "energy_j_mean" in regressions[0]
    assert "smoke/miso/least-loaded/energy" in regressions[0]


def test_diff_sweeps_flags_robustness_regressions(tmp_path):
    """The v4 robustness columns gate: losing goodput or destroying more
    work than baseline (beyond threshold) fails the diff."""
    ds = _load_diff_sweeps()
    base_agg = {"goodput_mean": 1.0, "work_lost_s_mean": 100.0}
    mk = lambda agg: {"schema_version": 4, "kind": "miso-sweep",
                      "summary": {"flaky_fleet": {"miso": {"least-loaded":
                                                  {"throughput": agg}}}}}
    pb = tmp_path / "base.json"
    pb.write_text(json.dumps(mk(base_agg)))
    for bad, metric in (({"goodput_mean": 0.9, "work_lost_s_mean": 100.0},
                         "goodput_mean"),
                        ({"goodput_mean": 1.0, "work_lost_s_mean": 150.0},
                         "work_lost_s_mean")):
        pc = tmp_path / "cand.json"
        pc.write_text(json.dumps(mk(bad)))
        regressions, _ = ds.diff_reports(str(pb), str(pc), threshold=0.02)
        assert len(regressions) == 1
        assert metric in regressions[0]
    # improvement in either direction is a note, not a regression
    pc = tmp_path / "good.json"
    pc.write_text(json.dumps(mk({"goodput_mean": 1.1,
                                 "work_lost_s_mean": 50.0})))
    regressions, notes = ds.diff_reports(str(pb), str(pc), threshold=0.02)
    assert regressions == [] and len(notes) == 2


def test_v3_report_round_trip(tmp_path):
    """A freshly-generated v3 report JSON-round-trips through the differ:
    same report on both sides -> zero regressions, objective-keyed cells."""
    ds = _load_diff_sweeps()
    rep = run_sweep(["miso"], ["smoke"], seeds=[0],
                    objectives=["throughput", "energy"], serial=True)
    p = tmp_path / "rep.json"
    p.write_text(json.dumps(rep))
    cells = ds.load_summary(str(p))
    assert ("smoke", "miso", "least-loaded", "throughput") in cells
    assert ("smoke", "miso", "least-loaded", "energy") in cells
    for agg in cells.values():
        assert agg["energy_j_mean"] > 0.0
    regressions, notes = ds.diff_reports(str(p), str(p), threshold=0.02)
    assert regressions == [] and notes == []


def _components_report(rows):
    return {"schema_version": 1, "kind": "miso-components",
            "rows": [{"name": n, "us_per_call": v, "derived": ""}
                     for n, v in rows.items()]}


def test_diff_components_gates_trace_rows_only(tmp_path):
    """The us/event gate: a trace_scaling row >threshold slower fails; a
    microbench row slowing down is a note; a vanished trace row is a
    coverage regression; improvements are notes."""
    ds = _load_diff_sweeps()
    pb = tmp_path / "base.json"
    pb.write_text(json.dumps(_components_report(
        {"trace_scaling_n8": 50.0, "trace_scaling_n512": 20.0,
         "optimizer_latency": 100.0})))
    # 50% slower trace tier -> regression; 50% slower microbench -> note
    pc = tmp_path / "cand.json"
    pc.write_text(json.dumps(_components_report(
        {"trace_scaling_n8": 75.0, "trace_scaling_n512": 20.0,
         "optimizer_latency": 150.0})))
    regressions, notes = ds.diff_components(str(pb), str(pc), threshold=0.10)
    assert len(regressions) == 1 and "trace_scaling_n8" in regressions[0]
    assert any("optimizer_latency" in n for n in notes)
    # within threshold -> note, not regression
    pc.write_text(json.dumps(_components_report(
        {"trace_scaling_n8": 52.0, "trace_scaling_n512": 18.0,
         "optimizer_latency": 100.0})))
    regressions, notes = ds.diff_components(str(pb), str(pc), threshold=0.10)
    assert regressions == []
    assert any("trace_scaling_n8" in n for n in notes)
    # a gated row missing from the candidate fails the gate
    pc.write_text(json.dumps(_components_report(
        {"trace_scaling_n8": 50.0, "optimizer_latency": 100.0})))
    regressions, _ = ds.diff_components(str(pb), str(pc), threshold=0.10)
    assert len(regressions) == 1
    assert "trace_scaling_n512" in regressions[0]
    assert "missing" in regressions[0]


def test_diff_main_autodetects_components_kind(tmp_path):
    """``main`` routes on the baseline's kind field: components reports get
    the 10% default threshold, so an 8% trace slowdown passes while a 12%
    one fails."""
    ds = _load_diff_sweeps()
    pb = tmp_path / "base.json"
    pb.write_text(json.dumps(_components_report({"trace_scaling_n8": 50.0})))
    ok = tmp_path / "ok.json"
    ok.write_text(json.dumps(_components_report({"trace_scaling_n8": 54.0})))
    bad = tmp_path / "bad.json"
    bad.write_text(json.dumps(_components_report({"trace_scaling_n8": 56.0})))
    assert ds.main([str(pb), str(ok)]) == 0
    assert ds.main([str(pb), str(bad)]) == 1
    # explicit threshold still wins
    assert ds.main([str(pb), str(bad), "--threshold", "0.2"]) == 0
    # and a sweep baseline still routes to the sweep differ (2% default)
    sb = tmp_path / "sweep.json"
    sb.write_text(json.dumps(
        {"schema_version": 4, "kind": "miso-sweep",
         "summary": {"smoke": {"miso": {"least-loaded":
                     {"throughput": {"stp_mean": 1.0}}}}}}))
    assert ds.main([str(sb), str(sb)]) == 0


def test_diff_components_gates_batch_rollout_row(tmp_path):
    """batch_rollout is a gated row like the trace tiers: slower than
    threshold fails, and vanishing from the candidate fails coverage."""
    ds = _load_diff_sweeps()
    pb = tmp_path / "base.json"
    pb.write_text(json.dumps(_components_report(
        {"batch_rollout": 60.0, "optimizer_latency": 100.0})))
    pc = tmp_path / "cand.json"
    pc.write_text(json.dumps(_components_report(
        {"batch_rollout": 90.0, "optimizer_latency": 100.0})))
    regressions, _ = ds.diff_components(str(pb), str(pc), threshold=0.10)
    assert len(regressions) == 1 and "batch_rollout" in regressions[0]
    pc.write_text(json.dumps(_components_report(
        {"optimizer_latency": 100.0})))
    regressions, _ = ds.diff_components(str(pb), str(pc), threshold=0.10)
    assert any("batch_rollout" in r and "missing" in r for r in regressions)


def test_diff_exact_flags_any_metric_drift(tmp_path):
    """``--exact`` turns sub-threshold drift into a regression: the
    batched-equivalence CI gate accepts byte-equal results only (timing
    columns stay exempt, and components reports reject the flag)."""
    ds = _load_diff_sweeps()

    def mk(stp, wall=1.0):
        return {"schema_version": 4, "kind": "miso-sweep",
                "summary": {"smoke": {"miso": {"least-loaded":
                            {"throughput": {"stp_mean": stp,
                                            "wall_s_mean": wall}}}}}}

    pb, pc = tmp_path / "b.json", tmp_path / "c.json"
    pb.write_text(json.dumps(mk(1.0)))
    pc.write_text(json.dumps(mk(1.0 + 1e-12)))
    regressions, _ = ds.diff_exact(str(pb), str(pc))
    assert len(regressions) == 1 and "stp_mean" in regressions[0]
    # drift far below 2% passes the threshold differ but fails --exact
    assert ds.main([str(pb), str(pc)]) == 0
    assert ds.main([str(pb), str(pc), "--exact"]) == 1
    # identical metrics with different wall-clock: exact passes
    pc.write_text(json.dumps(mk(1.0, wall=9.9)))
    assert ds.main([str(pb), str(pc), "--exact"]) == 0
    comp = tmp_path / "comp.json"
    comp.write_text(json.dumps(_components_report({"batch_rollout": 60.0})))
    with pytest.raises(SystemExit):
        ds.main([str(comp), str(comp), "--exact"])


def test_diff_exact_pool_vs_batched_end_to_end(tmp_path):
    """The CI equivalence gate end-to-end: the same grid through both
    engines summarizes byte-equal, so ``--exact`` returns 0."""
    ds = _load_diff_sweeps()
    kw = dict(policies=["miso", "srpt"], scenarios=["smoke"], seeds=[0])
    pa, pb = tmp_path / "pool.json", tmp_path / "batched.json"
    pa.write_text(json.dumps(run_sweep(serial=True, **kw)))
    rep = run_sweep(serial=True, engine="batched", **kw)
    assert rep["config"]["batched_cells"] == 2
    pb.write_text(json.dumps(rep))
    assert ds.main([str(pa), str(pb), "--exact"]) == 0


def test_profile_stamps_lint_version():
    """``--profile`` reports carry the misolint rule-set hash so archived
    numbers record which determinism contract the tree was clean under."""
    from misolint import ruleset_hash
    rep = run_sweep(["miso"], ["smoke"], seeds=[0], serial=True,
                    profile=True)
    assert rep["lint_version"] == ruleset_hash()
    assert len(rep["lint_version"]) == 12
    # and only --profile reports pay for the stamp
    bare = run_sweep(["miso"], ["smoke"], seeds=[0], serial=True)
    assert "lint_version" not in bare


# ------------------------------------------------------------ trace cache


def _cache_task(seed=0, n_jobs=None, trace_cache=None):
    return {"policy": "miso", "scenario": "smoke", "seed": seed,
            "n_jobs": n_jobs, "trace_cache": trace_cache}


def test_trace_memo_fifo_eviction_bounds_memory(monkeypatch):
    """The in-process trace memo is FIFO-bounded: a long rollout loop over
    many distinct cells must not accumulate every trace it ever generated."""
    from repro.core.scenarios import get_scenario
    from repro.launch import sweep as sw

    monkeypatch.setattr(sw, "_TRACE_CACHE", {})
    monkeypatch.setattr(sw, "_TRACE_CACHE_MAX", 4)
    sc = get_scenario("smoke")
    for seed in range(10):                     # 10 distinct keys
        sw._get_jobs(_cache_task(seed=seed), sc)
    assert len(sw._TRACE_CACHE) == 4
    # FIFO: the four *newest* survive, and a surviving key is a memo hit
    jobs, _, src = sw._get_jobs(_cache_task(seed=9), sc)
    assert src == "memo"
    _, _, src0 = sw._get_jobs(_cache_task(seed=0), sc)
    assert src0 == "fresh"                     # evicted long ago


def test_trace_cache_corrupt_pickle_regenerates(tmp_path, monkeypatch):
    """A truncated/corrupt on-disk trace entry regenerates (and heals the
    file) instead of crashing the cell."""
    import hashlib

    from repro.core.scenarios import get_scenario
    from repro.launch import sweep as sw

    monkeypatch.setattr(sw, "_TRACE_CACHE", {})
    sc = get_scenario("smoke")
    task = _cache_task(trace_cache=str(tmp_path))
    key = sw._trace_key(task, sc)
    h = hashlib.sha256(repr(key).encode()).hexdigest()[:24]
    path = tmp_path / f"trace_{h}.pkl"

    # cold write, then destroy the entry two ways
    jobs, _, src = sw._get_jobs(task, sc)
    assert src == "fresh" and path.exists()
    good = path.read_bytes()

    for corrupt in (good[: len(good) // 2], b"\x80garbage"):
        path.write_bytes(corrupt)
        monkeypatch.setattr(sw, "_TRACE_CACHE", {})   # force the disk tier
        jobs2, _, src2 = sw._get_jobs(task, sc)
        assert src2 == "fresh"                 # fell through, regenerated
        assert [j.jid for j in jobs2] == [j.jid for j in jobs]
        assert path.read_bytes() == good       # healed atomically
    monkeypatch.setattr(sw, "_TRACE_CACHE", {})
    _, _, src3 = sw._get_jobs(task, sc)
    assert src3 == "disk"                      # healthy entry serves again


# --------------------------------------------------------- batched engine


def _strip(rep):
    return [(r["policy"], r["scenario"], r["seed"], r["placer"],
             r["metrics"]) for r in rep["results"]]


def test_batched_engine_bit_identical_to_pool():
    """`--engine batched` coalesces same-fleet cells into one lockstep
    replica batch; every cell's metrics stay bit-identical to the scalar
    per-process path."""
    kw = dict(policies=["miso", "srpt"], scenarios=["smoke"],
              seeds=[0, 1], serial=True)
    a = run_sweep(engine="pool", **kw)
    b = run_sweep(engine="batched", **kw)
    assert _strip(a) == _strip(b)
    assert b["config"]["engine"] == "batched"
    assert b["config"]["batched_cells"] == 4
    assert not b["errors"]


def test_batched_engine_coalesces_by_fleet():
    """Cells with different fleet shapes land in different lockstep groups
    (hetero_smoke: a100+h100 vs smoke: a100-only) — all still run batched,
    none fall back."""
    rep = run_sweep(["miso"], ["smoke", "hetero_smoke"], seeds=[0],
                    serial=True, engine="batched")
    assert rep["config"]["batched_cells"] == 2
    fleets = {r["scenario"]: r["fleet"] for r in rep["results"]}
    assert fleets["smoke"] != fleets["hetero_smoke"]


def test_batched_engine_group_failure_falls_back(monkeypatch):
    """A group whose lockstep run dies raises out of the sweep: no
    fallback path re-runs it elsewhere and hides the failure."""
    from repro.core.sim import batch as batch_mod

    def boom(self):
        raise RuntimeError("injected lockstep failure")

    monkeypatch.setattr(batch_mod.BatchSim, "run", boom)
    with pytest.raises(RuntimeError, match="injected lockstep failure"):
        run_sweep(["miso"], ["smoke"], seeds=[0, 1], serial=True,
                  engine="batched")


def test_batched_engine_profile_falls_back():
    """--profile with the batched engine runs batched, and each cell
    reports every bucket of its replica's rounds: its own event loop,
    stages B and D, and its shares of the fused stages A and C."""
    from repro.core.sim.prof import KEYS

    rep = run_sweep(["miso"], ["smoke"], seeds=[0, 1], serial=True,
                    profile=True, engine="batched")
    assert rep["config"]["batched_cells"] == 2
    for r in rep["results"]:
        p = r["profile"]
        assert set(KEYS) <= set(p)
        assert p["events"] > 0
        assert p["collect_s"] > 0 and p["fused_estimator_s"] > 0
        assert p["total_s"] == pytest.approx(
            p["collect_s"] + p["round_apply_s"] + p["fused_estimator_s"]
            + p["fused_alg1_s"])
        assert p["estimator_s"] >= p["fused_estimator_s"]
        assert 0 < p["event_loop_s"] < p["total_s"]

"""Share of the (mix, level) MPS fixed points asked for in ``BatchSim``
stage A that the perf model's memo answered: ``prof["mps_rows_hit"]``
over ``prof["mps_rows"]``, in percent.  A program without these counters
reads nothing."""


def read(run):
    if not run.prof.get("mps_rows") or "mps_rows_hit" not in run.prof:
        return None
    return run.prof["mps_rows_hit"] / run.prof["mps_rows"] * 100.0

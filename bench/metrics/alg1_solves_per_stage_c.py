"""Stacked Algorithm-1 solves (``optimize_partition_batch`` calls, one per
MIG menu, power model and objective) per ``BatchSim`` stage C that had
decisions: ``prof["stage_c_solves"]`` over ``prof["stage_c_runs"]``.  A
fleet of one kind reads 1; one of two kinds up to 2."""


def read(run):
    if not run.prof.get("stage_c_runs") or "stage_c_solves" not in run.prof:
        return None
    return run.prof["stage_c_solves"] / run.prof["stage_c_runs"]

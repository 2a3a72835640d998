"""U-Net groups (``estimate_batch`` calls, one per estimator object) per
``BatchSim`` stage A that had windows: ``prof["stage_a_forwards"]`` over
``prof["stage_a_runs"]``.  A fleet of one kind reads 1; one of two kinds
up to 2, where a round's windows come from both."""


def read(run):
    if not run.prof.get("stage_a_runs") or "stage_a_forwards" not in run.prof:
        return None
    return run.prof["stage_a_forwards"] / run.prof["stage_a_runs"]

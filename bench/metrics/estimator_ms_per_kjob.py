"""Estimator time per thousand jobs completed: the benchmark's spans
around ``BatchSim._fuse_estimates`` (stage A, the fused U-Net forwards)
plus the program's ``prof["estimator_s"]`` (estimates made inline)."""
from probe import ESTIMATOR


def read(run):
    if "estimator_s" not in run.prof or not run.jobs:
        return None
    return (run.spans[ESTIMATOR] + run.prof["estimator_s"]) / run.jobs * 1e6

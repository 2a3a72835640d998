"""Algorithm-1 time per thousand jobs completed: the benchmark's spans
around ``BatchSim._solve_decisions`` (stage C, the fused DP solves) plus
the program's ``prof["alg1_s"]`` (solves made inline)."""
from probe import ALG1


def read(run):
    if "alg1_s" not in run.prof or not run.jobs:
        return None
    return (run.spans[ALG1] + run.prof["alg1_s"]) / run.jobs * 1e6

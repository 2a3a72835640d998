"""The simulated MPS probe and its noise draw (``prof["mps_measure_s"]``,
every ``MisoPolicy._measure``), per thousand jobs completed."""


def read(run):
    if "mps_measure_s" not in run.prof or not run.jobs:
        return None
    return run.prof["mps_measure_s"] / run.jobs * 1e6

"""Stages B and D of each ``BatchSim`` round, applying estimates and
choices and finalizing the tick (``prof["round_apply_s"]``), per thousand
jobs completed."""


def read(run):
    if "round_apply_s" not in run.prof or not run.jobs:
        return None
    return run.prof["round_apply_s"] / run.jobs * 1e6

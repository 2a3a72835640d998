"""The program's placement bucket (``prof["placement_s"]``, every
``pick_gpu`` call) per thousand jobs completed in the window."""


def read(run):
    if "placement_s" not in run.prof or not run.jobs:
        return None
    return run.prof["placement_s"] / run.jobs * 1e6

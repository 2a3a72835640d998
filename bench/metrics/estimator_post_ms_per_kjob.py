"""The estimator's post-processing, the linear heads and the memory/QoS
monitor on the U-Net's output (``prof["estimator_post_s"]``), per thousand
jobs completed."""


def read(run):
    if "estimator_post_s" not in run.prof or not run.jobs:
        return None
    return run.prof["estimator_post_s"] / run.jobs * 1e6

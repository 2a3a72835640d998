"""``UNet.__call__`` calls (each one device dispatch of the forward) per
thousand jobs completed in the window."""


def read(run):
    if not run.jobs:
        return None
    return run.unet_calls / run.jobs * 1e3

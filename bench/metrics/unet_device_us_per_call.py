"""Device time of the U-Net's jitted forward (``_apply_jit``) in the
traced window, over the ``UNet.__call__`` calls made while it was traced."""


def read(run):
    if run.trace is None or not run.trace["devices"]:
        return None
    if not getattr(run, "traced_calls", 0):
        return None
    if not run.trace["unet_device_s"]:
        return None
    return run.trace["unet_device_s"] / run.traced_calls * 1e6

"""MPS windows carried per U-Net forward, padding rows not counted:
``prof["unet_windows"]`` over ``prof["unet_calls"]``."""


def read(run):
    if not run.prof.get("unet_calls") or not run.prof.get("unet_windows"):
        return None
    return run.prof["unet_windows"] / run.prof["unet_calls"]

"""Host time of each U-Net forward up to its launch: stacking the
matrices, the copy in, the pad, the launch and the crop
(``prof["unet_host_s"]``) over the forwards (``prof["unet_calls"]``)."""


def read(run):
    if not run.prof.get("unet_calls") or "unet_host_s" not in run.prof:
        return None
    return run.prof["unet_host_s"] / run.prof["unet_calls"] * 1e6

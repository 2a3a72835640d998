"""Host time blocked on each U-Net forward's result, from its launch until
the output is on the host (``prof["unet_wait_s"]``), over the forwards
(``prof["unet_calls"]``)."""


def read(run):
    if not run.prof.get("unet_calls") or "unet_wait_s" not in run.prof:
        return None
    return run.prof["unet_wait_s"] / run.prof["unet_calls"] * 1e6

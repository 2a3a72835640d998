"""Jobs completed by every replica of every replay in the window, over the
window's length on the host clock (whole replays only)."""


def read(run):
    return run.jobs / run.elapsed_s

"""95th percentile (linear interpolation) of the wall time of every
``BatchSim.step()`` in the window: the lockstep round a learned policy
waits on."""
import numpy as np


def read(run):
    return float(np.percentile(run.step_s, 95)) * 1e3

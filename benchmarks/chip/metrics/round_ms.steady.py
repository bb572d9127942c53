"""95th percentile of the coordinator's round (``LcapCluster.pump``)
durations in the window."""

import numpy as np


def read(run, out):
    d = out["spans"].durations("round", run.window)
    return 1e3 * float(np.percentile(d, 95)) if d else None

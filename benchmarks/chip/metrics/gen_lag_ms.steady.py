"""95th percentile of how late the load generator appended the records
due in the window, against their due times."""

import numpy as np


def read(run, out):
    lag = out["generator_lag_s"]
    return 1e3 * float(np.percentile(lag, 95)) if len(lag) else None

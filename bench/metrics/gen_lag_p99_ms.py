"""How late the load generator sent: 99th percentile of send time minus
due time."""

import numpy as np


def read(run):
    lag = run.window.gen_lag_s
    if lag is None:
        return None
    return float(np.percentile(lag, 99) * 1e3)

"""Median over every request of the window, from its due time to its
answer in the client's hand.  A refused or lost request never gets an
answer and counts as infinitely late; where half of them do, there is no
finite median and nothing is read."""

import numpy as np


def read(run):
    lat = run.window.latency_s
    if lat is None or not len(lat):
        return None
    q = float(np.quantile(lat, 0.50, method="inverted_cdf"))
    return q * 1e3 if np.isfinite(q) else None

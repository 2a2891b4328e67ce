"""How late the open-loop generator admitted: the 95th percentile of
(admission time - Poisson due time) over the window, in milliseconds.  A
starved generator shows here, not as a fast server."""

import numpy as np


def read(record):
    lag = record["window"].get("lag_s")
    if lag is None or len(lag) == 0:
        return None
    return float(np.quantile(lag, 0.95)) * 1e3

"""The 95th percentile of due time -> commit over the open-loop window, in
milliseconds.  Read here and not bounded: on the chip's host a stall of
seconds in one run of a few swings it from ~24 ms to over a second while
the median holds, so it could not carry a bound."""

import numpy as np


def read(record):
    lat = record["window"].get("latency_s")
    if lat is None or len(lat) == 0:
        return None
    return float(np.quantile(lat, 0.95)) * 1e3

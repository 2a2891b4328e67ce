"""What the per-layer metric readers share.  A reader returns ``None`` when
its run has nothing to read, and the harness then leaves the metric out."""

from __future__ import annotations


def span_us_per_txn(record: dict, span: str) -> float | None:
    """Host microseconds in the harness span ``span`` per transaction it
    handled, over the window."""
    sec = record["spans"]["seconds"].get(span)
    n = record["spans"]["txns"].get(span)
    if not sec or not n:
        return None
    return sec / n * 1e6


def idle_share(record: dict) -> float | None:
    """1 - busy / window of the device, from the profile."""
    red = record["trace"]
    if red is None or red["window_s"] <= 0:
        return None
    return 1.0 - red["busy_s"] / red["window_s"]


def per(record: dict, num: str, den: str) -> float | None:
    c = record["counters"]
    return c[num] / c[den] if c.get(den) else None

"""Host microseconds per drained transaction inside ``IngressPool.drain``
(timed by the proxy ``serve`` drains through), over a backlog window."""

from potbench.readers import span_us_per_txn


def read(record):
    return span_us_per_txn(record, "drain")

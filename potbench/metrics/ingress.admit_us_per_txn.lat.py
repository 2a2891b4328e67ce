"""Host microseconds per transaction inside ``IngressPool.admit`` (the
harness's ``admit`` span), over an open-loop window."""

from potbench.readers import span_us_per_txn


def read(record):
    return span_us_per_txn(record, "admit")

"""PCC rounds per formed batch (``ExecTrace.rounds`` summed over the
window's batches, divided by them)."""

from potbench.readers import per


def read(record):
    return per(record, "rounds", "batches")

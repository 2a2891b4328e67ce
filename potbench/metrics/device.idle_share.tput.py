"""Share of the traced backlog window in which no operation ran on the
device: 1 - (union of the ``XLA Ops`` intervals / window)."""

from potbench.readers import idle_share


def read(record):
    return idle_share(record)

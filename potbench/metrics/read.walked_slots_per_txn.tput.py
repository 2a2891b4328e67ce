"""Instruction slots the read phase walks on the device per committed
transaction (``ExecTrace.walked_slots`` over the window's commits)."""


def read(record):
    n = record["window"]["committed"]
    return record["counters"]["walked_slots"] / n if n else None

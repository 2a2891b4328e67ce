"""Device milliseconds of the Pallas conflict kernels per engine round:
the summed durations of their events in the window's profile over the
window's rounds.

The kernels take no ``name=`` yet.  On the TPU their custom calls carry the
names of the functions in ``repro/kernels/conflict.py`` that build them,
``conflict_matrix_bits_delta`` (the K x K table's delta update, full-width
rounds) and ``conflict_matrix_bits_pair`` (row and column strips, compact
rounds), as HLO instructions such as ``conflict_matrix_bits_delta.10``."""

from potbench.trace import kernel_seconds

NAMES = ("conflict_matrix_bits",)


def read(record):
    red, rounds = record["trace"], record["counters"]["rounds"]
    if red is None or not rounds:
        return None
    sec, n = kernel_seconds(red, NAMES)
    return sec / rounds * 1e3 if n else None

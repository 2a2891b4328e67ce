"""The reduction of the program's spans, on a profile recorded on a TPU
v5e: the traced run of ``p2p.block1000.backlog`` with ``--seconds 0.25``
(seed 2147483611: 3 blocks of 1,000 transfers, 51 rounds) on a program
with the ``pot.*`` spans of ``repro.core``."""

import os

import pytest

from potbench import program_spans, registry, trace

DATA = os.path.join(os.path.dirname(__file__), "data",
                    "p2p_small_spans.xplane.pb.gz")
SPANS = ("pot.drain", "pot.drain.pack", "pot.drain.upload", "pot.step",
         "pot.step.pad", "pot.step.record")
BLOCK, BUCKET = 1000, 1024
RECORDED, ROUNDS = 3, 51
WINDOW_S = 0.284917604
# what ``python -m potbench.program_spans`` printed for this profile on
# the chip's host
PINNED = {
    "ingress.select_us_per_txn": 2.0610800000000005,
    "ingress.pack_us_per_txn": 2.4157393333333337,
    "ingress.upload_us_per_txn": 1.8098266666666667,
    "ingress.drain_us_per_txn": 6.286646,
    "session.pad_ms_per_batch": 2.745429666666667,
    "session.record_ms_per_batch": 3.3409400000000002,
    "session.idle_share": 0.0428544632854627,
    "ingress.idle_share": 0.06618322888886852,
    # the run's own device.idle_share.tput
    "device.idle_share": 0.32276762021345656,
}


@pytest.fixture(scope="module")
def red():
    return program_spans.reduce(DATA)


@pytest.fixture(scope="module")
def nums(red):
    return program_spans.numbers(red)


def test_spans_of_each_block(red):
    sp = red["spans"]
    assert set(sp) == set(SPANS)
    blocks = sp["pot.step"]["count"]
    assert blocks == RECORDED
    assert all(sp[n]["count"] == blocks for n in SPANS)
    assert sp["pot.drain"]["stats"] == {"k": BLOCK * blocks}
    assert sp["pot.step.pad"]["stats"] == {"k": BLOCK * blocks,
                                           "bk": BUCKET * blocks}


def test_numbers_the_run_gave(red, nums):
    # the numbers this profile gave on the chip
    assert red["window_s"] == pytest.approx(WINDOW_S, rel=1e-9)
    for name, v in PINNED.items():
        assert nums[name] == pytest.approx(v, rel=1e-9), name
    assert nums["session.pad_rows_share"] == pytest.approx(24 / 1024)


def test_idle_split_is_consistent(red, nums):
    dev = trace.reduce(DATA)
    assert nums["device.idle_share"] == pytest.approx(
        1 - dev["busy_s"] / dev["window_s"], rel=1e-9)
    assert red["window_s"] == pytest.approx(dev["window_s"], rel=1e-12)
    # the drain and the step do not overlap, so their idle time is a part
    # of the device's
    assert (nums["session.idle_share"] + nums["ingress.idle_share"]
            <= nums["device.idle_share"])
    assert 0 <= red["idle_outside_s"] <= red["idle_s"]
    assert len(red["gaps"]) == 5
    secs = [s for _, s in red["gaps"]]
    assert secs == sorted(secs, reverse=True)
    labels = {lab for lab, _ in red["gaps"]}
    assert labels <= set(SPANS) | set(trace.HOST_SPANS) | {
        program_spans.OUTSIDE}


def test_every_p2p_metric_reads_the_recording(red, nums):
    """The cell's per-layer metrics read a number from this profile too,
    as from the one recorded before the program had spans; so do the
    span numbers."""
    record = {"window": {"committed": BLOCK * RECORDED, "lag_s": None},
              "spans": {"seconds": {"admit": 0.021, "drain": 0.02},
                        "txns": {"admit": BLOCK * RECORDED,
                                 "drain": BLOCK * RECORDED}},
              "counters": {"batches": RECORDED, "rounds": ROUNDS,
                           "walked_slots": 165888},
              "trace": trace.reduce(DATA)}
    bench = registry.benchmark()
    for m in registry.per_layer_for(bench, "p2p.block1000.backlog"):
        v = registry.metric_reader(m["name"])(record)
        assert v is not None and v > 0, m["name"]
    for name, v in nums.items():
        assert v is not None and v > 0, name

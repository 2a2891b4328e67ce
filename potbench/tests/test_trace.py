"""The trace reduction, on a profile recorded on a TPU v5e: the traced run
of ``p2p.block1000.backlog`` with ``--seconds 0.25`` (3 blocks, 51 rounds)."""

import os

import pytest

from potbench import trace

DATA = os.path.join(os.path.dirname(__file__), "data", "p2p_small.xplane.pb.gz")


@pytest.fixture(scope="module")
def red():
    return trace.reduce(DATA)


def test_window_busy_and_idle(red):
    # the numbers the run reported on the chip for this profile
    assert red["n_devices"] == 1
    assert red["window_s"] == pytest.approx(0.275392529, rel=1e-9)
    assert red["busy_s"] == pytest.approx(0.192951525, rel=1e-9)
    idle = sum(red["idle"].values())
    assert idle == pytest.approx(red["window_s"] - red["busy_s"], rel=1e-6)
    assert set(red["idle"]) <= {"admit", "drain", "serve", "outside spans"}
    # the host was admitting for most of the device's idle time
    assert max(red["idle"], key=red["idle"].get) == "admit"


def test_kernels_by_name(red):
    sec, n = trace.kernel_seconds(red, ("conflict_matrix_bits",))
    assert n > 0
    assert sec / 51 * 1e3 == pytest.approx(3.2611904117647055, rel=1e-9)
    assert any(op.startswith("conflict_matrix_bits_delta") for op in red["ops"])
    assert any(op.startswith("conflict_matrix_bits_pair") for op in red["ops"])


def test_self_time_sums_to_busy(red):
    # self times of nested operations add up to the union of their spans
    # wherever the device runs one operation at a time
    assert sum(red["ops"].values()) == pytest.approx(red["busy_s"], rel=1e-3)


def test_breakdown_shape(red):
    b = trace.breakdown(red)
    assert 0 < len(b["device_ops"]) <= 10 and 0 < len(b["idle_gaps"]) <= 10
    assert b["device_ops"][0][0].startswith("conflict_matrix_bits_delta")
    secs = [s for _, s in b["device_ops"]]
    assert secs == sorted(secs, reverse=True)


def test_self_times_and_names():
    assert trace.self_times([(0, 10), (1, 3), (4, 8), (5, 6), (11, 12)]) \
        == [4, 2, 3, 1, 1]
    assert trace.op_name("%fusion.629 = s32[256]{0} fusion(%a)") == "fusion.629"

"""The reduction from a capture to busy time, idle gaps and time by
operation: on intervals small enough to check by hand, and on a recorded
capture from the chip (recorded/, with the numbers an independent reader
of the same bytes gave)."""

import glob
import json
import os

import pytest

from harness import trace_reduce as tr

HERE = os.path.dirname(os.path.abspath(__file__))


def planes():
    dev = {"name": "/device:TPU:0", "lines": [
        {"name": "XLA Modules", "events": [("jit_basic(123)", 100, 50),
                                            ("jit_other(9)", 400, 100)]},
        {"name": "XLA Ops", "events": [
            ("fusion", 100, 20), ("copy-done", 110, 30),   # overlap: 100..140
            ("fusion", 400, 100), ("stray", 700, 10)]},
        {"name": "Steps", "events": [("ignored", 0, 1000)]}]}
    host = {"name": "/host:CPU", "lines": [
        {"name": "python3", "events": [
            ("$server.py:1 serve_forever", 0, 1000),
            ("$ragged.py:137 _freeze", 150, 240),
            ("$short.py:1 f", 160, 1)]}]}
    return [dev, host]


def test_union():
    assert tr.union([(5, 7), (1, 3), (2, 4), (7, 8)]) == [(1, 4), (5, 8)]


def test_busy_is_the_union_of_op_intervals():
    red = tr.reduce_planes(planes())
    assert red["window_s"] == pytest.approx(1000e-9)
    assert red["busy_s"] == pytest.approx((40 + 100 + 10) * 1e-9)
    assert red["devices_traced"] == red["devices_busy"] == 1
    assert red["launches"] == {"jit_basic": 1, "jit_other": 1}


def test_time_by_operation_names_the_program():
    red = tr.reduce_planes(planes())
    ops = dict(map(tuple, red["device_ops"]))
    assert ops["jit_other/fusion"] == pytest.approx(100e-9)
    assert ops["jit_basic/fusion"] == pytest.approx(20e-9)
    assert ops["jit_basic/copy-done"] == pytest.approx(30e-9)
    assert ops["-/stray"] == pytest.approx(10e-9)
    assert red["program_s"]["jit_basic"] == pytest.approx(50e-9)


def test_gaps_name_the_most_specific_host_frame():
    red = tr.reduce_planes(planes())
    gaps = red["idle_gaps"]
    # the longest gap is 710..1000, then 140..400 (under _freeze), ...
    assert gaps[0][1] == pytest.approx(290e-9)
    assert "serve_forever" in gaps[0][0]
    assert gaps[1][1] == pytest.approx(260e-9)
    assert gaps[1][0] == "python3:ragged.py:137 _freeze"


def test_no_device_plane_reads_no_busy_time():
    red = tr.reduce_planes(planes()[1:])
    assert red["busy_s"] == 0 and red["devices_traced"] == 0


RECORDED = sorted(glob.glob(os.path.join(HERE, "recorded", "*.xplane.pb")))


@pytest.mark.parametrize("path", RECORDED or [None])
def test_recorded_capture(path):
    if path is None:
        pytest.skip("no recorded capture beside the test")
    with open(path[:-len(".xplane.pb")] + ".expected.json") as f:
        want = json.load(f)
    red = tr.reduce_planes(tr.read_capture(path))
    # ProfileData hands out whole nanoseconds, the protobuf picoseconds:
    # over a few hundred short operations they differ by about 1e-4
    assert red["busy_s"] == pytest.approx(want["busy_s"], rel=1e-3)
    assert red["window_s"] == pytest.approx(want["window_s"], rel=1e-6)
    assert red["devices_busy"] == want["devices_busy"]
    got = dict(map(tuple, red["device_ops"]))
    for name, seconds in want["device_ops"].items():
        assert got[name] == pytest.approx(seconds, rel=1e-3), name
    assert red["idle_gaps"][0][1] == pytest.approx(want["longest_gap_s"],
                                                   rel=1e-6)

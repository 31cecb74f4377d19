"""The trace reduction: busy time as the union of device operations and
the idle share, on a small recorded trace of two devices."""
import os
import sys

import pytest

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))

from bench import trace  # noqa: E402

MS = 1e6   # ns

RECORDED = {
    # overlapping ops count once; a zero-length op counts nothing
    "/device:TPU:0": [("fusion.1", 0 * MS, 100 * MS),
                      ("fusion.2", 50 * MS, 100 * MS),
                      ("copy.3", 400 * MS, 100 * MS),
                      ("fusion.1", 450 * MS, 10 * MS),
                      ("noop", 700 * MS, 0)],
    "/device:TPU:1": [("fusion.1", 0 * MS, 300 * MS)],
}


def test_busy_union_and_idle_share():
    iv = trace.busy_intervals(RECORDED["/device:TPU:0"])
    assert iv == [(0, 150 * MS), (400 * MS, 500 * MS)]
    out = trace.reduce(RECORDED, window_s=1.0)
    # device 0 busy 0.25 s, device 1 busy 0.3 s: mean 0.275 s of 1 s
    assert out["busy_s"] == pytest.approx(0.275)
    assert out["idle_share"] == pytest.approx(0.725)
    assert out["window_s"] == 1.0


def test_op_names_drop_the_hlo_text():
    assert trace.op_name("%fusion.12 = bf16[2,4]{1,0} fusion(%p), kind=kLoop") \
        == "fusion.12"


def test_no_device_ops_reads_nothing():
    assert trace.reduce({}, 1.0) is None
    assert trace.reduce({"/device:TPU:0": []}, 1.0) is None

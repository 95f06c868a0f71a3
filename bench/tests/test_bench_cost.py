"""Byte arithmetic and the roofline share built on it, against
hand-worked cases."""
import os

import numpy as np
import pytest

from bench import cost, harness


def test_port_payload_bytes():
    # vadd at n = 10: a, b read and y written, float32; n is not a buffer
    args = (10, np.zeros(10, np.float32), np.zeros(10, np.float32),
            np.zeros(10, np.float32))
    assert cost.port_payload_bytes(args) == 120
    # qs8 dot at n = 64: two int8 inputs and one int16 sum
    args = (64, np.zeros(64, np.int8), np.zeros(64, np.int8),
            np.zeros(1, np.int16))
    assert cost.port_payload_bytes(args) == 130


def test_port_roofline_hand_worked():
    """Payload bytes of the slates submitted after the trace started, at
    the chip's bandwidth, over the programs' device time: 819 MB in two
    slates against 2 s of programs: 1 ms at peak of 2 s, 0.05 %."""
    read = harness.load_module(os.path.join(harness.BENCH, "metrics",
                                            "port_roofline.py")).read
    record = {"trace_start_s": 1.0,
              "slates": [(0.5, 10**12), (1.0, 409_500_000),
                         (2.0, 409_500_000)]}
    trace = {"programs_s": {"jit__unnamed_function": 1.5, "jit_b": 0.5}}
    ctx = {"peaks": {"hbm_bytes_per_s": 819e9}}
    assert read(record, trace, ctx) == pytest.approx(0.05)
    # nothing traced, or no program in the trace: nothing to read
    assert read(dict(record, trace_start_s=None), trace, ctx) is None
    assert read(record, {"programs_s": {}}, ctx) is None

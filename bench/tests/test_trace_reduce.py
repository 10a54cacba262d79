"""The trace reduction on a small trace with hand-worked answers.

The trace (``data/trace_small.json.gz``) has the shape ``benchlib/trace.py``
keeps of a profiler trace: two chips' ``XLA Ops`` events, one collective
overlapped by compute on each, and host events that cover the idle gaps.
"""
from __future__ import annotations

import os

import pytest

from helpers import BENCH, load

trace = load("benchlib/trace.py", "bench_trace")
DATA = os.path.join(BENCH, "tests", "data")


@pytest.fixture(scope="module")
def small():
    return trace.reduce(trace.read(os.path.join(DATA, "trace_small.json.gz")))


def test_busy_union_and_idle_share(small):
    # device 0: [100,300) u [250,400) u [400,500) u [450,520) = [100,520) -> 420 ns
    # device 1: [0,200) u [600,700) u [650,1000 clipped) -> 200 + 400 = 600 ns
    assert small["window_s"] == pytest.approx(1000e-9)
    assert small["busy_s"] == pytest.approx((420 + 600) / 2 * 1e-9)
    assert small["per_device"]["/device:TPU:0"]["busy_s"] == pytest.approx(420e-9)


def test_exposed_collective_time_against_overlapping_compute(small):
    # device 0: collective [400,500), compute covers [450,520) -> 50 ns exposed
    # device 1: collective [600,700), compute covers [650,...) -> 50 ns exposed
    assert small["collective_s"] == pytest.approx(100e-9)
    assert small["exposed_collective_s"] == pytest.approx(50e-9)


def test_breakdown_names(small):
    ops = dict(small["breakdown"]["device_ops"])
    assert ops["cholesky.5"] == pytest.approx(350e-9 / 2)  # clipped at the window's end
    assert ops["fusion.1"] == pytest.approx(400e-9 / 2)
    gaps = small["breakdown"]["idle_gaps"]
    assert [g[1] for g in gaps] == pytest.approx([480e-9, 400e-9, 100e-9])
    assert gaps[0][0] == "python: $engine.py:181 _drain_one"
    assert gaps[1][0] == "python: $backends.py:396 sweep_block"
    assert gaps[2][0] == "no host span"


def test_window_span_is_required():
    with pytest.raises(ValueError):
        trace.reduce({"devices": {}, "host": [["python", "other", 0, 10]]})

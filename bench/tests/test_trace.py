"""The trace reduction, on hand-made intervals and on a small profiler
trace recorded on one v5e (``record_trace.py``): 3 paged decode calls,
a 50 ms host sleep, 2 flash prefill calls."""
from pathlib import Path

import pytest

import tiny  # noqa: F401  (puts bench/ on the path)
from lib import spec
from lib import trace as T

RECORDED = Path(__file__).parent / "data" / "small.xplane.pb"


def test_union_and_gaps():
    busy, merged = T.union_length([(5, 7), (0, 2), (1, 3), (6, 9), (9, 9)])
    assert busy == 3 + 4
    assert merged == [(0, 3), (5, 9)]
    assert T.gaps_of(merged, -1, 12) == [(-1, 0), (3, 5), (9, 12)]
    assert T.gaps_of(merged, 1, 8) == [(3, 5)]


@pytest.fixture(scope="module")
def recorded():
    if not RECORDED.exists():
        pytest.fail(f"{RECORDED} is missing; record it on a TPU with "
                    f"record_trace.py")
    return T.reduce(str(RECORDED))


def test_recorded_trace_kernels(recorded):
    paged = spec.kernel_cost("paged_decode")
    flash = spec.kernel_cost("flash_prefill")
    secs, calls = T.kernel_time(recorded, paged.PATTERN)
    assert calls == 3 and secs > 0
    secs_f, calls_f = T.kernel_time(recorded, flash.PATTERN)
    assert calls_f == 2 and secs_f > 0


def test_recorded_trace_busy_and_gap(recorded):
    assert recorded.devices == 1
    assert 0 < recorded.busy_s <= recorded.window_s
    # the busy union cannot be shorter than the longest op, nor longer
    # than all ops end to end
    total = sum(o.seconds for o in recorded.ops.values())
    assert max(o.seconds / o.count for o in recorded.ops.values()) \
        <= recorded.busy_s <= total + 1e-12
    # the 50 ms host sleep is the longest idle gap
    s, e = recorded.gaps[0]
    assert 0.045 < (e - s) * 1e-9 < 0.5
    assert recorded.marker_ns is not None
    # reducing after the marker keeps the same ops
    again = T.reduce(str(RECORDED), after_marker=(0.0, 10.0))
    assert T.kernel_time(again, paged_cost().PATTERN)[1] == 3


def paged_cost():
    return spec.kernel_cost("paged_decode")

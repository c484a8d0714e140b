"""The reduction from profiler planes to device busy time, idle share and
per-op device time, on small synthetic traces."""

import pytest

import bench_helpers  # noqa: F401  (puts bench/ on the path)
from trace_reduce import (Event, Line, Plane, busy_seconds, idle_gaps,
                          idle_share, op_name, op_seconds, window_of)

MS = 1_000_000


def _trace(device_events, host_events=()):
    planes = [Plane("/host:CPU", [Line("python", [
        Event(n, s, d) for n, s, d in host_events])])]
    if device_events is not None:
        planes.append(Plane("/device:TPU:0", [
            Line("XLA Modules", [Event("jit_call", 0, 100 * MS)]),
            Line("XLA Ops", [Event(n, s, d) for n, s, d in device_events])]))
    return planes


def test_busy_is_the_union_of_op_intervals_on_the_ops_line():
    planes = _trace([("%phase_row_max.3 = s32[128,1] custom-call()", 10 * MS,
                      20 * MS),
                     ("%copy.1 = s32[128] copy(%phase_row_max.3)", 25 * MS,
                      10 * MS),
                     ("%add.2 = s32[] add()", 50 * MS, 5 * MS)])
    assert busy_seconds(planes) == pytest.approx(0.030)
    assert idle_share(planes, 0.2) == pytest.approx(0.85)


def test_a_trace_with_no_device_op_reads_idle_one():
    assert busy_seconds(_trace(None)) == 0.0
    assert idle_share(_trace(None), 3.0) == 1.0
    assert idle_share(_trace([]), 3.0) == 1.0


def test_only_the_op_that_defines_a_kernel_is_named_for_it():
    assert op_name("%phase_row_max.3 = s32[128,1] custom-call(%p)") \
        == "phase_row_max"
    assert op_name("%copy.1 = s32[128] copy(%phase_row_max.3)") == "copy"
    planes = _trace([("%phase_row_max.3 = s32[] custom-call()", 0, 12_000),
                     ("%copy.1 = s32[] copy(%phase_row_max.3)", 12_000, 500),
                     ("%phase_row_max.7 = s32[] custom-call()", 20_000,
                      11_000)])
    rows = dict(op_seconds(planes))
    assert rows["phase_row_max"] == pytest.approx(23e-6)
    assert rows["copy"] == pytest.approx(0.5e-6)


def test_idle_gaps_are_named_by_the_host_span_they_fall_in():
    planes = _trace([("%k.1 = f32[] add()", 60 * MS, 1 * MS)],
                    [("bench/window", 0, 100 * MS),
                     ("bench/unit", 0, 58 * MS),
                     ("bench/witness", 59 * MS, 41 * MS)])
    assert window_of(planes) == (0, 100 * MS)
    gaps = idle_gaps(planes, window_of(planes))
    assert gaps[0] == ["bench/unit", pytest.approx(0.060)]
    assert gaps[1] == ["bench/witness", pytest.approx(0.039)]

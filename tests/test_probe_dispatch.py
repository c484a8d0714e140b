"""The trace reduction of ``benchmarks/probe_dispatch.py``, on a small
recorded trace built from the profiler's plane/line/event structure."""

from types import SimpleNamespace as NS

import pytest

from benchmarks.probe_dispatch import (device_event_medians, kernel_device_us,
                                       main)

KERNEL_OP = ("%phase_row_max.1 = s32[1024,1] custom-call(s32[1024,512] "
             "%args_0_.1), custom_call_target=\"tpu_custom_call\"")
COPY_OP = "%copy = s32[1024,1] copy(s32[1024,1] %phase_row_max.1)"


def _ev(name, ns):
    return NS(name=name, duration_ns=ns)


def _planes():
    ops = NS(name="XLA Ops", events=[_ev(KERNEL_OP, 11000.0),
                                     _ev(KERNEL_OP, 12000.0),
                                     _ev(KERNEL_OP, 15000.0),
                                     _ev(COPY_OP, 500.0)])
    modules = NS(name="XLA Modules", events=[_ev("jit_wrapped", 12500.0)])
    host = NS(name="python", events=[_ev(KERNEL_OP, 9e6)])
    return [NS(name="/device:TPU:0", lines=[ops, modules]),
            NS(name="/host:CPU", lines=[host])]


def test_device_event_medians_keeps_device_planes_only():
    med = device_event_medians(_planes())
    assert med == {
        ("/device:TPU:0", "XLA Ops", KERNEL_OP): (3, 12.0),
        ("/device:TPU:0", "XLA Ops", COPY_OP): (1, 0.5),
        ("/device:TPU:0", "XLA Modules", "jit_wrapped"): (1, 12.5),
    }
    # the copy reads the kernel's result and names it, but is not the kernel
    assert kernel_device_us(med) == pytest.approx(12.0)
    assert kernel_device_us(med, kernel="no_such_kernel") is None


def test_probe_refuses_to_run_off_the_tpu(tmp_path):
    # tests run with JAX_PLATFORMS=cpu: no TPU, nothing measured
    assert main(["--out", str(tmp_path)]) == 1
    assert not any(tmp_path.iterdir())

"""Per-call floor against kernel device time, on the TPU.

  PYTHONPATH=src python -m benchmarks.probe_dispatch [--out DIR]

Times one jitted call through ``block_until_ready`` on device-resident
int32 tiles from 128x128 to 512x2048, for three programs of rising work:
``x + 1``, XLA's row max, and the Pallas row-max kernel of
``repro.kernels.phase_max`` (whose output is checked against numpy).  Then
it traces 20 Pallas calls at 1024x512 and reduces the trace to the median
device duration of every event on the TPU's planes.  Where ``x + 1`` costs
as much as the kernel, the per-call floor is the dispatch-and-sync round
trip, not the kernel; only the trace gives the kernel's own device time.

Exits 1 off a TPU, and when the trace holds no ``phase_row_max`` event.
The trace is written under ``--out`` (default ``artifacts/probe_dispatch``).
The last line of stdout is one JSON object with the device and the medians.
"""

from __future__ import annotations

import argparse
import json
import statistics
import sys
import time
from collections import defaultdict
from pathlib import Path

import numpy as np

ROOT = Path(__file__).resolve().parents[1]
TILES = ((128, 128), (128, 256), (1024, 512), (512, 2048))
TRACED_TILE = (1024, 512)
TRACED_CALLS = 20
REPEATS = 50
KERNEL_NAME = "phase_row_max"


def median_call_us(fn, x, repeats: int = REPEATS) -> float:
    """Median host time of ``fn(x)`` through ``block_until_ready``, after
    one warm call (compile, allocate)."""
    import jax
    jax.block_until_ready(fn(x))
    times = []
    for _ in range(repeats):
        t0 = time.perf_counter()
        jax.block_until_ready(fn(x))
        times.append(time.perf_counter() - t0)
    return statistics.median(times) * 1e6


def device_event_medians(planes, prefix: str = "/device:TPU") -> dict:
    """``{(plane, line, event name): (count, median µs)}`` over the events
    of every plane whose name starts with ``prefix``: the reduction from a
    profiler trace (``jax.profiler.ProfileData`` planes) to device times."""
    durations = defaultdict(list)
    for plane in planes:
        if not plane.name.startswith(prefix):
            continue
        for line in plane.lines:
            for ev in line.events:
                durations[(plane.name, line.name, ev.name)].append(
                    ev.duration_ns)
    return {k: (len(v), statistics.median(v) / 1e3)
            for k, v in durations.items()}


def kernel_device_us(medians: dict, kernel: str = KERNEL_NAME):
    """Median device µs of the ops that ``kernel`` defines (HLO text
    ``%<kernel>.N = ...``), or None.  Ops that only read its result, such as
    a layout copy, name it too and do not count."""
    hits = [us for (_, line, name), (_, us) in medians.items()
            if line == "XLA Ops" and name.startswith(f"%{kernel}")]
    return statistics.median(hits) if hits else None


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--out", type=Path, default=ROOT / "artifacts"
                    / "probe_dispatch", help="directory for the trace")
    args = ap.parse_args(argv)

    import jax
    dev = jax.devices()[0]
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(jax.devices())}
    print(f"[device] platform={device['platform']} kind={device['kind']} "
          f"count={device['count']}")
    if device["platform"] != "tpu":
        print("[device] no TPU: nothing measured", file=sys.stderr)
        return 1

    from repro.kernels.phase_max import _row_max_call
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    add_one = jax.jit(lambda x: x + 1)
    xla_row_max = jax.jit(lambda x: x.max(axis=1, keepdims=True))
    rng = np.random.default_rng(0)
    call_us, tiles = {}, {}
    for shape in TILES:
        host = rng.integers(-2 ** 20, 2 ** 20, shape, dtype=np.int32)
        x = tiles[shape] = jax.device_put(host)
        pallas = _row_max_call(*shape, False)
        if not np.array_equal(np.asarray(pallas(x))[:, 0], host.max(axis=1)):
            raise RuntimeError(f"Pallas row max differs from numpy at {shape}")
        row = {name: median_call_us(fn, x) for name, fn in (
            ("x + 1", add_one), ("XLA row max", xla_row_max),
            ("Pallas row max", pallas))}
        call_us["%dx%d" % shape] = row
        print(f"[call] {shape[0]}x{shape[1]} median of {REPEATS}, host "
              "clock through block_until_ready: "
              + ", ".join(f"{k} {v:.3f} us" for k, v in row.items()))

    pallas, x = _row_max_call(*TRACED_TILE, False), tiles[TRACED_TILE]
    args.out.mkdir(parents=True, exist_ok=True)
    with jax.profiler.trace(str(args.out)):
        for _ in range(TRACED_CALLS):
            jax.block_until_ready(pallas(x))
    xplane = max(args.out.glob("plugins/profile/*/*.xplane.pb"),
                 key=lambda p: p.stat().st_mtime)
    medians = device_event_medians(
        jax.profiler.ProfileData.from_file(str(xplane)).planes)
    for (plane, line, name), (n, us) in sorted(medians.items()):
        print(f"[trace] {plane} {line} {name[:120]}: n {n}, median {us:.3f} us")
    kernel_us = kernel_device_us(medians)
    if kernel_us is None:
        print(f"[trace] no {KERNEL_NAME} op in {xplane}", file=sys.stderr)
        return 1
    print(json.dumps({"device": device, "call_us": call_us,
                      "kernel_device_us": kernel_us,
                      "traced": {"tile": "%dx%d" % TRACED_TILE,
                                 "calls": TRACED_CALLS}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())

"""One run of one benchmark cell.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Run from the root of a checkout on a machine with the chips the cell asks
for.  It finds the cell in ``BENCHMARK.json``, its configuration, traffic
mix, the module for its kind of cell and metric readers by name (``bench/harness.py``), sets up,
measures for ``--seconds``, checks what the measured window produced
against the plain reference, and prints one JSON line as the last line of
standard output.  With ``--trace 0`` the line holds the cell's end-to-end
metrics; with ``--trace 1`` the per-layer ones, read from a profiler trace
of the window and from the benchmark's own wrappers around the program's
layer entry points.

Without a TPU, or with fewer chips than the cell asks for, it exits 2 and
prints no result.
"""

from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

sys.path.insert(0, str(Path(__file__).resolve().parent))
sys.path.insert(1, str(Path(__file__).resolve().parents[1] / "src"))

import harness  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    if args.seed < 0 or args.seconds <= 0:
        ap.error("--seed must be >= 0 and --seconds > 0")
    try:
        cell = harness.find_cell(harness.load_benchmark(), args.workload,
                                 args.seed, args.seconds, bool(args.trace))
        device = harness.device_info(cell.chips)
    except harness.Refused as e:
        print(f"bench: {e}; no result", file=sys.stderr)
        return 2
    print(f"bench: {cell.name} seed {cell.seed} on {device['count']} x "
          f"{device['kind']} ({device['platform']})", file=sys.stderr)
    harness.enable_compile_cache()
    mod = harness.load_kind(cell.traffic["kind"], cell.root)
    result, checks = mod.run(cell, device, T_START)
    harness.emit(result, checks)
    return 0


if __name__ == "__main__":
    sys.exit(main())

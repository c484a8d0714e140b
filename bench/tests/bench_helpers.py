"""Small cells for the benchmark's own tests: the real configuration and
traffic files, cut to a few jobs so a run fits a test on the CPU."""

from __future__ import annotations

import sys
from pathlib import Path

BENCH = Path(__file__).resolve().parents[1]
ROOT = BENCH.parent
for p in (str(BENCH), str(ROOT / "src")):
    if p not in sys.path:
        sys.path.insert(0, p)

import harness  # noqa: E402


def small_cell(workload: str, num_jobs: int, seed: int = 3,
               seconds: float = 0.01, trace: bool = False) -> harness.Cell:
    cell = harness.find_cell(harness.load_benchmark(), workload, seed,
                             seconds, trace)
    cell.config["jobs"]["num_jobs"] = num_jobs
    cell.traffic["warm_jobs"] = min(int(cell.traffic["warm_jobs"]), num_jobs)
    return cell


def run_small(cell: harness.Cell):
    """Drive the rest of a run, without the harness's look for a chip."""
    device = harness.device_info(cell.chips, require_tpu=False)
    mod = harness.load_kind(cell.traffic["kind"], cell.root)
    return mod.run(cell, device, 0.0)

"""Readings that the limits of a campaign cell are set from.

    python3 bench/control.py --workload <cell> --seeds 1,2,3 [--jobs N]

For each seed: one unit of the cell at its own size on the trace that the
seed draws, through the timed path (``run_campaign`` as the window calls
it), then

* the *program's* reading: the numbers ``correct`` compares, with the
  program's answers against the float64 reference;
* the *control's* reading: the same numbers with the reference computed
  in float32 put in the program's place.

The program's readings over a dozen seeds give each limit's lower end; the
control's smallest reading gives its upper end.  Runs on the chip's host
like the benchmark (it refuses no device: the campaign path runs on the
host); the benchmark's own runs never run it.
"""

from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent))
sys.path.insert(1, str(Path(__file__).resolve().parents[1] / "src"))

import numpy as np  # noqa: E402

import gen  # noqa: E402
import harness  # noqa: E402
import reference  # noqa: E402


def _answers(rows, arrivals):
    """A reference's rows as the program's answers: ``(jwts, jcts)``."""
    return ([float(r[0]) - a for r, a in zip(rows, arrivals)],
            [float(r[2]) for r in rows])


def readings(cell: harness.Cell, seed: int) -> dict:
    camp_mod = harness.load_kind(cell.traffic["kind"], cell.root)
    camp = camp_mod.Campaign(cell)
    tap = camp_mod.Tap(timed=False)
    for name in camp.isolated_placers:
        tap.wrap(type(camp.strats[name]), "place", "place",
                 after=lambda out, s, ctx, jid, n, job=None:
                 camp.capture(out, s, ctx, jid, n, job))
    try:
        trace = gen.make_trace(camp.mix, seed)
        t0 = time.perf_counter()
        lanes = camp.unit(trace)
        unit_s = time.perf_counter() - t0
    finally:
        tap.close()
    unit = {"trace_id": 0, "trace": trace, "lanes": lanes,
            "placements": camp.placements}
    refs = camp_mod.references(cell, camp, [unit])
    prog = camp_mod.check(cell, camp, [unit], refs)
    # the control: the float32 reference's answers in the program's place
    arrivals = [j.arrival for j in trace]
    ctrl_lanes = list(lanes)
    ctrl_place = {}
    for (_, li), (rows, _) in camp_mod.references(
            cell, camp, [unit], np.float32).items():
        strat, s, n_fin, _, _ = lanes[li]
        jwts, jcts = _answers(rows, arrivals)
        ctrl_lanes[li] = (strat, s, n_fin, jwts, jcts)
        ctrl_place[(strat, s)] = {j.job_id: tuple(r[3:])
                                  for j, r in zip(trace, rows)
                                  if len(r) > 3}
    ctrl = camp_mod.check(cell, camp, [dict(unit, lanes=ctrl_lanes,
                                            placements=ctrl_place)], refs)
    return {"seed": seed, "unit_s": unit_s, "program": prog,
            "control": ctrl}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--jobs", type=int, default=0,
                    help="cut the trace to this many jobs (tests only)")
    args = ap.parse_args(argv)
    for seed in (int(s) for s in args.seeds.split(",")):
        cell = harness.find_cell(harness.load_benchmark(), args.workload,
                                 seed, 1.0, False)
        if args.jobs:
            cell.config["jobs"]["num_jobs"] = args.jobs
        print(json.dumps(readings(cell, seed)), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())

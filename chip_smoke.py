#!/usr/bin/env python
"""Bring-up smoke: the campaign simulator's main path with its rate
resolution on one TPU chip.

    python chip_smoke.py                           # on a machine with a TPU
    JAX_PLATFORMS=cpu python chip_smoke.py --rehearse   # CPU rehearsal

One process, no children.  Phases, each of which raises on failure:

1. device  — ``jax.devices()``: anything but a TPU exits 1 here (unless
   ``--rehearse``);
2. goldens — the standing 200-job CLUSTER512 trace through
   ``run_campaign(engine="batched")``: avg JCT ecmp 13417.8, sr 3731.4,
   best 2949.3;
3. campaign — CLUSTER2048, 10,000 helios-mix jobs (the
   ``benchmarks/bench_scale.py`` workload), best/sr/ecmp × fifo × seeds 0,1:
   ``run_campaign(engine="batched")`` is the host reference, then the same
   lanes run through ``run_lanes(..., pw_backend="pallas")`` so every event
   round's rate solve runs the Pallas kernel on the chip; every per-job JCT
   must be bit-identical;
4. kernels — numpy, jitted ``segment_max`` and Pallas must agree exactly at
   the recorded dispatch shapes (``benchmarks/bench_fairshare.py``), and
   the water-filling kernel must match numpy within 1e-6 above
   ``AUTOTUNE_FLOOR``; their median times are printed as chip numbers for
   ROADMAP A2, not as a benchmark.  Every time is a host clock through
   ``block_until_ready``, so a call on a device-resident tile gives the
   dispatch-and-sync floor, not the kernel's device time, which only a
   trace gives (``benchmarks/probe_dispatch.py``).

``--rehearse`` cuts the campaign to 200 jobs and the timing repeats to 3;
off the TPU the kernels run in Pallas interpret mode.  A rehearsal never
prints the ok line.  On success the last line of stdout is
``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": ...}}``.
"""

from __future__ import annotations

import argparse
import json
import statistics
import sys
import time
from collections import Counter
from pathlib import Path

import numpy as np

ROOT = Path(__file__).resolve().parent
sys.path.insert(0, str(ROOT / "src"))

GOLDEN_AVG_JCT = {"ecmp": 13417.8, "sr": 3731.4, "best": 2949.3}
STRATEGIES = ("best", "sr", "ecmp")
_COMPILE_EVENTS = ("/jax/core/compile/jaxpr_trace_duration",
                   "/jax/core/compile/jaxpr_to_mlir_module_duration",
                   "/jax/core/compile/backend_compile_duration")


def check(ok: bool, what: str) -> None:
    if not ok:
        raise RuntimeError(f"chip smoke failed: {what}")


class Probe:
    """Kernel rounds, their padded shapes and compile seconds, from JAX's
    own monitoring events."""

    def __init__(self):
        import jax
        from repro.kernels.phase_max import CALL_EVENT
        self.shapes: Counter = Counter()
        self.compile_s = 0.0

        def on_event(event, **kw):
            if event == CALL_EVENT:
                self.shapes[kw["shape"]] += 1

        def on_duration(event, secs, **kw):
            if event in _COMPILE_EVENTS:
                self.compile_s += secs

        jax.monitoring.register_event_listener(on_event)
        jax.monitoring.register_event_duration_secs_listener(on_duration)


def phase_goldens() -> None:
    from repro.core import (CLUSTER512, CampaignGrid, WorkloadSpec,
                            run_campaign)
    wl = WorkloadSpec(num_jobs=200, seed=0, mean_interarrival=120.0,
                      max_gpus=256)
    grid = CampaignGrid(strategies=tuple(GOLDEN_AVG_JCT), loads=(120.0,),
                        seeds=(0,))
    res = run_campaign(CLUSTER512, grid, workload=wl, engine="batched")
    got = {c.strategy: round(c.report.avg_jct, 1) for c in res.cells}
    check(got == GOLDEN_AVG_JCT, f"goldens {got} != {GOLDEN_AVG_JCT}")
    print(f"[goldens] CLUSTER512 200 jobs, avg JCT {got}: match")


def phase_campaign(num_jobs: int, probe: Probe) -> None:
    from repro.core import (CLUSTER2048, CampaignGrid, WorkloadSpec,
                            generate_trace, get_strategy, run_campaign)
    from repro.core.batched import run_lanes
    wl = WorkloadSpec(num_jobs=num_jobs, mean_interarrival=30.0,
                      max_gpus=1024, seed=0)
    grid = CampaignGrid(strategies=STRATEGIES, schedulers=("fifo",),
                        loads=(wl.mean_interarrival,), seeds=(0, 1))
    t0 = time.perf_counter()
    ref = run_campaign(CLUSTER2048, grid, workload=wl, engine="batched")
    t_ref = time.perf_counter() - t0
    check(len(ref.cells) == grid.size, "reference campaign lost cells")
    print(f"[campaign] host reference: CLUSTER2048, {len(ref.cells)} lanes "
          f"x {num_jobs} jobs, run_campaign(engine='batched') "
          f"{t_ref:.3f} s wall")

    lanes = [(generate_trace(wl.with_load(c.load).with_seed(c.seed)),
              get_strategy(c.strategy), c.seed) for c in ref.cells]
    rounds0, compile0 = sum(probe.shapes.values()), probe.compile_s
    t0 = time.perf_counter()
    reps = run_lanes(CLUSTER2048, lanes, pw_backend="pallas")
    wall = time.perf_counter() - t0
    rounds = sum(probe.shapes.values()) - rounds0
    check(rounds > 0, "no event round reached the Pallas kernel")
    for cell, rep in zip(ref.cells, reps):
        check(rep.n_finished == num_jobs,
              f"{cell.strategy}/seed {cell.seed}: {rep.n_finished} of "
              f"{num_jobs} jobs finished")
        check(rep.jcts == cell.report.jcts,
              f"{cell.strategy}/seed {cell.seed}: per-job JCTs differ from "
              f"the host reference")
    largest = max(probe.shapes, key=lambda s: tuple(map(int, s.split("x"))))
    print(f"[campaign] pallas: kernel rounds {rounds}, distinct kernel "
          f"shapes {len(probe.shapes)} (largest {largest}), compile "
          f"{probe.compile_s - compile0:.3f} s, wall {wall:.3f} s; "
          f"{len(reps) * num_jobs} per-job JCTs bit-identical to the "
          f"reference")


def _median_us(fn, *args, repeats: int) -> float:
    import jax
    jax.block_until_ready(fn(*args))               # warm: compile, allocate
    times = []
    for _ in range(repeats):
        t0 = time.perf_counter()
        jax.block_until_ready(fn(*args))
        times.append(time.perf_counter() - t0)
    return statistics.median(times) * 1e6


def phase_kernels(repeats: int, label: str) -> None:
    import jax
    from benchmarks.bench_fairshare import BATCHED_DISPATCH_SHAPES
    from repro.core.fairshare import (AUTOTUNE_FLOOR, maxmin_fair_jax,
                                      maxmin_fair_numpy, phase_worst_jax,
                                      phase_worst_numpy, problem_size)
    from repro.kernels import phase_max
    rng = np.random.default_rng(0)
    interpret = jax.default_backend() != "tpu"
    for tag, nvals, nseg in BATCHED_DISPATCH_SHAPES:
        vals = rng.integers(1, 40, nvals).astype(np.int64)
        ptr = np.sort(rng.integers(0, nvals, nseg - 1))
        ptr = np.concatenate([[0], ptr, [nvals]]).astype(np.int64)
        want = phase_worst_numpy(vals, ptr)
        check(np.array_equal(phase_worst_jax(vals, ptr), want),
              f"segment_max differs from numpy at {tag}")
        check(np.array_equal(phase_max.phase_worst_pallas(vals, ptr), want),
              f"Pallas differs from numpy at {tag}")
        tile = jax.device_put(phase_max._dense_tile(vals, ptr))
        kernel = phase_max._row_max_call(*tile.shape, interpret)
        us = {name: _median_us(fn, *a, repeats=repeats) for name, fn, a in (
            ("numpy", phase_worst_numpy, (vals, ptr)),
            ("segment_max", phase_worst_jax, (vals, ptr)),
            ("pallas", phase_max.phase_worst_pallas, (vals, ptr)),
            ("pallas call on a device-resident tile (dispatch+sync)",
             kernel, (tile,)))}
        print(f"[kernels] {tag} {nvals} values / {nseg} segments, tile "
              f"{tile.shape[0]}x{tile.shape[1]}: identical outputs; median "
              + ", ".join(f"{k} {v:.1f} us" for k, v in us.items())
              + f" ({label})")

    flows = [rng.choice(64, size=3, replace=False).tolist()
             for _ in range(4096)]
    size = problem_size(flows)
    check(size > AUTOTUNE_FLOOR, "water-filling probe below AUTOTUNE_FLOOR")
    diff = float(np.abs(maxmin_fair_numpy(flows)
                        - maxmin_fair_jax(flows)).max())
    check(diff <= 1e-6, f"water-filling differs from numpy by {diff}")
    us = {name: _median_us(fn, flows, repeats=repeats)
          for name, fn in (("numpy", maxmin_fair_numpy),
                           ("jax", maxmin_fair_jax))}
    print(f"[kernels] water-filling 4096 flows x 64 links (size {size}): "
          f"max |jax - numpy| {diff:.3g}; median "
          + ", ".join(f"{k} {v:.1f} us" for k, v in us.items())
          + f" ({label})")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--rehearse", action="store_true",
                    help="reduced size; runs off the TPU (Pallas interpret "
                         "mode) and never prints the ok line")
    args = ap.parse_args(argv)

    import jax
    dev = jax.devices()[0]
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(jax.devices())}
    on_tpu = device["platform"] == "tpu"
    print(f"[device] platform={device['platform']} kind={device['kind']} "
          f"count={device['count']}", file=sys.stdout if on_tpu else sys.stderr)
    if not on_tpu and not args.rehearse:
        print("[device] no TPU: nothing runs (--rehearse for a CPU "
              "rehearsal)", file=sys.stderr)
        return 1

    from repro.launch.compile_cache import enable_compile_cache
    print(f"[cache] compilation cache: {enable_compile_cache()}")
    probe = Probe()
    phase_goldens()
    phase_campaign(200 if args.rehearse else 10_000, probe)
    label = (f"{device['kind']} chip numbers for ROADMAP A2, not a benchmark"
             if on_tpu else "CPU rehearsal, not a device number")
    phase_kernels(3 if args.rehearse else 50, label)
    if args.rehearse:
        print("[done] rehearsal: every phase ran; no ok line",
              file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": device}))
    return 0


if __name__ == "__main__":
    sys.exit(main())

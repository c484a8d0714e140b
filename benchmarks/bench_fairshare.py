"""Max-min fair solver: numpy↔JAX crossover microbenchmark.

Times the sparse numpy water-filling against the dense jitted JAX kernel
over growing flow×link incidences, reports the measured auto-dispatch
crossover (``repro.core.fairshare.maxmin_fair_auto``), and does the same
for the v2 engine's batched bottleneck solve (``phase_worst_loads``).
Agreement between backends is asserted as part of the run — a divergence
raises and fails the harness (1e-6 here; tests/test_simulator.py pins
1e-9 on float64-representable cases).

  PYTHONPATH=src python -m benchmarks.bench_fairshare [--full]
"""

from __future__ import annotations

import time

import numpy as np

from repro.core.fairshare import (autotune_crossover, maxmin_fair_jax,
                                  maxmin_fair_numpy, phase_worst_jax,
                                  phase_worst_numpy, problem_size)
from repro.kernels.phase_max import phase_worst_pallas

#: (nvals, nsegments) CSR shapes observed at :func:`phase_worst_loads`
#: dispatch inside ``run_lanes`` on a fabric-heavy 72-lane campaign
#: (CLUSTER512, 300 jobs/lane, max_gpus=64, best/sr/ecmp × 8 seeds ×
#: 3 loads): the batched engine concatenates every affected job of every
#: lane into one call, so these are far larger than the per-event v2
#: shapes the old 4096-val probe modelled.
BATCHED_DISPATCH_SHAPES = (
    ("p50", 3345, 62),
    ("p90", 22652, 398),
    ("max", 43593, 753),
)


def _best_of(fn, *args, n: int = 3) -> float:
    fn(*args)                     # warm (JIT / allocator)
    best = float("inf")
    for _ in range(n):
        t0 = time.perf_counter()
        fn(*args)
        best = min(best, time.perf_counter() - t0)
    return best


def run(fast: bool = True):
    rows = []
    rng = np.random.default_rng(0)
    sizes = (64, 512, 2048) if fast else (64, 512, 2048, 8192)
    for nflows in sizes:
        flow_links = [rng.choice(64, size=3, replace=False).tolist()
                      for _ in range(nflows)]
        t_np = _best_of(maxmin_fair_numpy, flow_links)
        t_jx = _best_of(maxmin_fair_jax, flow_links)
        agree = float(np.abs(maxmin_fair_numpy(flow_links)
                             - maxmin_fair_jax(flow_links)).max())
        assert agree < 1e-6, \
            f"maxmin backends diverge at {nflows} flows: {agree}"
        rows.append({
            "name": f"maxmin_fair[{nflows}flows]",
            "us_per_call": round(min(t_np, t_jx) * 1e6, 1),
            "derived": {"size": problem_size(flow_links),
                        "numpy_us": round(t_np * 1e6, 1),
                        "jax_us": round(t_jx * 1e6, 1),
                        "jax_wins": bool(t_jx < t_np),
                        "max_abs_diff": agree},
        })

    nvals = 4096 if fast else 65536
    vals = rng.integers(1, 40, nvals).astype(np.int64)
    ptr = np.sort(rng.integers(0, nvals, 255))
    ptr = np.concatenate([[0], ptr, [nvals]]).astype(np.int64)
    t_np = _best_of(phase_worst_numpy, vals, ptr)
    t_jx = _best_of(phase_worst_jax, vals, ptr)
    exact = bool((phase_worst_numpy(vals, ptr)
                  == phase_worst_jax(vals, ptr)).all())
    assert exact, "phase_worst backends disagree (must be integer-exact)"
    rows.append({
        "name": f"phase_worst[{nvals}vals]",
        "us_per_call": round(min(t_np, t_jx) * 1e6, 1),
        "derived": {"numpy_us": round(t_np * 1e6, 1),
                    "jax_us": round(t_jx * 1e6, 1),
                    "identical_int_output": exact,
                    # export REPRO_PHASE_WORST_CROSSOVER with this to move
                    # the v2 engine's batched solve onto the JAX kernel
                    "recommended_crossover":
                        (nvals if t_jx < t_np else "inf")},
    })

    # --- batched-engine dispatch shapes -------------------------------
    # Re-measure the numpy↔accelerator crossover at the CSR sizes the
    # lane-batched engine actually dispatches (cross-lane concatenation,
    # see BATCHED_DISPATCH_SHAPES above) instead of the historical
    # per-event probe.  The recorded crossover is whatever this box
    # honestly measures — "inf" on hosts where the reduceat path wins at
    # every real shape, which is the expected outcome on CPU-only builds.
    pw_crossover: float | str = "inf"
    shape_rows = {}
    for tag, nvals, nseg in BATCHED_DISPATCH_SHAPES:
        vals = rng.integers(1, 40, nvals).astype(np.int64)
        ptr = np.sort(rng.integers(0, nvals, nseg - 1))
        ptr = np.concatenate([[0], ptr, [nvals]]).astype(np.int64)
        t_np = _best_of(phase_worst_numpy, vals, ptr)
        t_ac = _best_of(phase_worst_pallas, vals, ptr)
        exact = bool((phase_worst_numpy(vals, ptr)
                      == phase_worst_pallas(vals, ptr)).all())
        assert exact, f"phase_worst backends disagree at {tag} shape"
        shape_rows[tag] = {"nvals": nvals, "nseg": nseg,
                           "numpy_us": round(t_np * 1e6, 1),
                           "accel_us": round(t_ac * 1e6, 1)}
        if t_ac < t_np and pw_crossover == "inf":
            pw_crossover = nvals
    rows.append({
        "name": "phase_worst[batched_dispatch]",
        "us_per_call": min(r["numpy_us"] for r in shape_rows.values()),
        "derived": {"shapes": shape_rows,
                    "identical_int_output": True,
                    # export REPRO_PHASE_WORST_CROSSOVER with this value to
                    # move run_lanes' rate resolution onto the accelerator
                    "recommended_crossover": pw_crossover},
    })

    crossover = autotune_crossover()
    rows.append({
        "name": "maxmin_crossover[autotune]",
        "us_per_call": 0.0,
        "derived": {"crossover_dense_size":
                    ("inf" if crossover == float("inf") else crossover),
                    # re-measured every recording (not a stale default):
                    # autotune_crossover() probes numpy vs JAX afresh and
                    # returns inf only when numpy wins at every probe size
                    "measured_on_this_host": True},
    })
    return rows


if __name__ == "__main__":
    import argparse

    from .common import emit
    ap = argparse.ArgumentParser()
    ap.add_argument("--full", action="store_true")
    args = ap.parse_args()
    emit(run(fast=not args.full))

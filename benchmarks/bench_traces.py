"""Million-job trace replay benchmark: bounded-memory streaming ingestion.

The acceptance bar for the TraceSource layer (docs/traces.md): a ≥1M-job
generated trace must replay through a windowed campaign via the streaming
reader (1) inside a recorded peak-RSS bound — the reader never
materialises the whole trace — and (2) bit-identical to the eager loader
on a shared prefix.  This module:

(1) writes a 1M-row native-schema trace CSV (vectorized generation),
(2) runs a windowed campaign over it (``run_windowed_campaign``,
    ``store="stream"``) in a **subprocess** and reads the child's
    ``ru_maxrss`` — a clean peak-RSS measurement no parent allocations
    can pollute — recording the ``rss_within_bound`` flag, and
(3) checks ``stream_eq_eager``: the streaming reader's first N jobs
    against an eager ``TraceSource.load()`` of the same N-row prefix.

Both flags gate in ``scripts/bench_gate.py`` when present (older
recordings tolerated, like prior cells).

  PYTHONPATH=src python -m benchmarks.bench_traces [--full]
"""

from __future__ import annotations

import itertools
import json
import os
import subprocess
import sys
import tempfile

import numpy as np

from repro.core.jobs import BATCHES, PROFILES

from .common import timed

N_JOBS = 1_000_000
PREFIX_JOBS = 5_000          # shared streaming-vs-eager parity prefix
WINDOW_JOBS = 1_000
STRIDE_JOBS = 100_000        # sample the long trace, don't simulate it all
RSS_BOUND_MB = 512           # streaming must stay under this; eager 1M-job
                             # Job lists measure well above it

_CHILD = r"""
import json, resource, sys
from repro.core import CLUSTER512, CampaignGrid, run_windowed_campaign
from repro.core.traces import TraceSource

path, window, stride, max_windows = (
    sys.argv[1], int(sys.argv[2]), int(sys.argv[3]), int(sys.argv[4]))
res = run_windowed_campaign(
    CLUSTER512, CampaignGrid(strategies=("ecmp",)),
    TraceSource(path, format="csv"), window, stride, max_windows)
row = res.aggregate()[0]
rss_kb = resource.getrusage(resource.RUSAGE_SELF).ru_maxrss
print(json.dumps({"windows": len(res.grid.seeds),
                  "n_finished": int(row["n_finished"]),
                  "jct_mean": round(row["jct_mean"], 1),
                  "peak_rss_mb": round(rss_kb / 1024.0, 1)}))
"""


def _write_trace(path: str, n: int) -> int:
    """Vectorized native-schema trace: Poisson arrivals, small GPU sizes
    (the benchmark measures ingestion, not placement pressure)."""
    rng = np.random.default_rng(0)
    arrivals = np.cumsum(rng.exponential(5.0, n))
    gpus = rng.choice([1, 2, 4, 8], n, p=[0.4, 0.3, 0.2, 0.1])
    iters = rng.integers(50, 500, n)
    models = sorted(PROFILES)
    batches = {m: BATCHES[m][0] for m in models}
    with open(path, "w", newline="") as f:
        f.write("job_id,model,num_gpus,batch_size,arrival,num_iters,"
                "allreduce_algo,deadline\n")
        chunk: list = []
        for i in range(n):
            m = models[i % len(models)]
            chunk.append(f"{i},{m},{gpus[i]},{batches[m]},"
                         f"{arrivals[i]:.6f},{iters[i]},ring,\n")
            if len(chunk) == 100_000:
                f.writelines(chunk)
                chunk.clear()
        f.writelines(chunk)
    return os.path.getsize(path)


def run(fast: bool = True):
    from repro.core.traces import TraceSource

    rows = []
    max_windows = 10 if fast else 20
    tmp = tempfile.mkdtemp(prefix="bench_traces-")
    path = os.path.join(tmp, "trace_1m.csv")

    size = {}
    rows.append(timed(f"bench_traces[generate_{N_JOBS // 1000}k]",
                      lambda: size.setdefault("b", _write_trace(path,
                                                                N_JOBS))))
    rows[-1]["derived"] = {"jobs": N_JOBS,
                           "mb": round(size["b"] / 1e6, 1)}

    # -- (2) windowed campaign over the 1M-job stream, child-process RSS ----
    def windowed():
        r = subprocess.run(
            [sys.executable, "-c", _CHILD, path, str(WINDOW_JOBS),
             str(STRIDE_JOBS), str(max_windows)],
            capture_output=True, text=True,
            # the replay is host work: the child never takes the chip
            # from a parent that may own it (docs/robustness.md)
            env=dict(os.environ, JAX_PLATFORMS="cpu",
                     PYTHONPATH=os.pathsep.join(
                         filter(None, [os.path.join(os.path.dirname(__file__),
                                                    os.pardir, "src"),
                                       os.environ.get("PYTHONPATH", "")]))))
        if r.returncode != 0:
            raise RuntimeError(f"windowed replay child failed: "
                               f"{r.stderr[-2000:]}")
        out = json.loads(r.stdout)
        out.update({
            "trace_jobs": N_JOBS, "window_jobs": WINDOW_JOBS,
            "stride_jobs": STRIDE_JOBS, "store": "stream",
            "rss_bound_mb": RSS_BOUND_MB,
            "rss_within_bound": out["peak_rss_mb"] <= RSS_BOUND_MB,
        })
        return out
    rows.append(timed("bench_traces[stream_1m_windowed]", windowed))

    # -- (3) streaming ≡ eager on a shared prefix ---------------------------
    def parity():
        prefix = os.path.join(tmp, "prefix.csv")
        with open(path) as f, open(prefix, "w") as g:
            g.writelines(itertools.islice(f, PREFIX_JOBS + 1))
        eager = TraceSource(prefix, format="csv").load()
        stream = list(itertools.islice(
            TraceSource(path, format="csv").iter_jobs(), PREFIX_JOBS))
        return {"prefix_jobs": PREFIX_JOBS,
                "stream_eq_eager": stream == eager}
    rows.append(timed("bench_traces[stream_eq_eager]", parity))

    for p in (path, os.path.join(tmp, "prefix.csv")):
        if os.path.exists(p):
            os.unlink(p)
    os.rmdir(tmp)
    return rows


if __name__ == "__main__":
    import argparse

    from .common import emit
    ap = argparse.ArgumentParser()
    ap.add_argument("--full", action="store_true",
                    help="double the windowed-replay coverage")
    emit(run(fast=not ap.parse_args().full))

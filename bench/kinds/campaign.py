"""Campaign cells: the simulator's users run grids of
strategy x policy cells over job traces, and pay for simulated jobs per
second.

A cell's traffic names a fixed set of ``traces`` traces (trace ``t`` is
the mix's population in the order that seed ``t`` draws).  One *unit* is
one ``repro.core.run_campaign(spec, grid, trace=..., engine="batched")``
call, with the program's own dispatch, on one trace of the set; one
*cycle* runs every trace of the set once, in an order drawn from
``--seed`` and the cycle's index.  So every run does the same work, in
another order.  Set-up builds the cluster from the configuration, draws
the population and runs one short warm unit.  The window then runs
cycles back to back; no cycle starts after ``--seconds``, and the window
ends when the last cycle ends.  ``jobs_per_s`` is every unit's finished
jobs over the window's wall time.

A traced run adds, after the window and inside the trace, one
rate-resolution solve on the chip through the program's Pallas path on
seeded data (the *witness*): the default dispatch keeps the campaign on
the host, and a traced run needs a device operation to read.  Then the
plain reference (``bench/reference.py``) checks what the window produced.
"""

from __future__ import annotations

import dataclasses
import math
import shutil
import sys
import time
from contextlib import contextmanager, nullcontext
from typing import Dict, List

import numpy as np

import gen
import harness
import reference

WITNESS_SEGMENTS = 128      # the witness tile is always 128 x 128


class Tap:
    """Wraps named module or class attributes of the program for one run
    and puts them back afterwards.  Each wrapper counts calls and, when
    ``timed``, sums the seconds spent inside."""

    def __init__(self, timed: bool):
        self.timed = timed
        self.calls: Dict[str, int] = {}
        self.seconds: Dict[str, float] = {}
        self._undo = []

    def wrap(self, owner, attr: str, key: str, after=None) -> None:
        orig = getattr(owner, attr)
        if getattr(orig, "_bench_tap", None) is self:
            return
        self.calls.setdefault(key, 0)
        self.seconds.setdefault(key, 0.0)
        timed, calls, secs = self.timed, self.calls, self.seconds
        perf = time.perf_counter

        def tapped(*a, **k):
            calls[key] += 1
            if timed:
                t0 = perf()
                out = orig(*a, **k)
                secs[key] += perf() - t0
            else:
                out = orig(*a, **k)
            if after is not None:
                after(out, *a, **k)
            return out

        tapped._bench_tap = self
        setattr(owner, attr, tapped)
        self._undo.append((owner, attr, orig))

    def close(self) -> None:
        for owner, attr, orig in reversed(self._undo):
            setattr(owner, attr, orig)
        self._undo.clear()


def witness_input(seed: int):
    """A seeded CSR rate-resolution input whose Pallas tile is 128 x 128:
    128 segments of 1 to 128 link loads."""
    rng = np.random.default_rng([int(seed), 0x5EED])
    widths = rng.integers(1, 129, WITNESS_SEGMENTS)
    ptr = np.concatenate([[0], np.cumsum(widths)]).astype(np.int64)
    vals = rng.integers(1, 1 << 20, int(ptr[-1])).astype(np.int64)
    return vals, ptr


def segment_max(vals: np.ndarray, ptr: np.ndarray) -> np.ndarray:
    return np.asarray([int(vals[a:b].max()) if b > a else 0
                       for a, b in zip(ptr[:-1], ptr[1:])], dtype=np.int64)


@contextmanager
def _profile(out_dir, on: bool):
    if not on:
        yield None
        return
    import jax
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 1
    shutil.rmtree(out_dir, ignore_errors=True)
    out_dir.mkdir(parents=True)
    jax.profiler.start_trace(str(out_dir), profiler_options=opts)
    try:
        yield out_dir
    finally:
        jax.profiler.stop_trace()


def _annotate(name: str, on: bool):
    if not on:
        return nullcontext()
    import jax
    return jax.profiler.TraceAnnotation(name)


class Campaign:
    """The system under test as one cell drives it."""

    def __init__(self, cell: harness.Cell):
        from repro.core import CampaignGrid, ClusterSpec, get_strategy
        cfg, tr = cell.config, cell.traffic
        self.cell = cell
        self.spec = ClusterSpec(**cfg["cluster"])
        self.ocs_spec = (dataclasses.replace(self.spec, **cfg["ocs_twin"])
                         if cfg.get("ocs_twin") else None)
        self.mix = cfg["jobs"]
        self.grid = CampaignGrid(strategies=tuple(tr["strategies"]),
                                 schedulers=("fifo",),
                                 loads=(float(self.mix["mean_interarrival"]),),
                                 seeds=tuple(range(int(tr["sim_seeds"]))))
        self.strats = {s: get_strategy(s) for s in tr["strategies"]}
        unknown = set(tr["strategies"]) - set(reference.RESIMULATED)
        if unknown:
            raise harness.Refused(f"no reference for {sorted(unknown)}")
        self.isolated_placers = [s for s in tr["strategies"]
                                 if s not in reference.LOCALITY_PACKED]
        self.placements: Dict[tuple, Dict[int, tuple]] = {}

    def capture(self, res, strat, ctx, job_id, num_gpus, job=None):
        """Keep every committed placement of an isolated strategy (under
        FIFO a successful placement is committed at once)."""
        gpus = getattr(res, "gpus", None)
        if gpus is None:
            return
        links = res.vclos.links if getattr(res, "vclos", None) else {}
        self.placements.setdefault((strat.name, ctx.seed), {})[job_id] = (
            list(gpus), dict(links), len(getattr(res, "xconn_ports", ())))

    def unit(self, trace) -> list:
        from repro.core import run_campaign
        jobs = gen.to_program_jobs(trace)
        res = run_campaign(self.spec, self.grid, trace=jobs,
                           engine="batched", ocs_spec=self.ocs_spec,
                           ilp_time_limit=float(
                               self.cell.config["ilp_time_limit"]))
        return [(c.strategy, c.seed, c.report.n_finished,
                 list(c.report.jwts), list(c.report.jcts))
                for c in res.cells]


def _gap(arrival, jwts, jcts, ref) -> float:
    """Widest gap of a job's wait or JCT from the reference's, relative to
    the reference's JCT (``ref`` rows are ``(start, finish, jct, ...)``, or
    None for a job the reference never finished)."""
    if len(jcts) != len(ref) or len(jwts) != len(ref):
        return math.inf
    if not ref:
        return 0.0
    if any(r is None for r in ref):
        return math.inf
    r_jct = np.asarray([float(r[2]) for r in ref])
    r_wait = np.asarray([float(r[0]) for r in ref]) - np.asarray(arrival)
    gap = np.maximum(np.abs(np.asarray(jcts, dtype=np.float64) - r_jct),
                     np.abs(np.asarray(jwts, dtype=np.float64) - r_wait))
    return float(np.max(gap / r_jct))


def sampled_unit(cell: harness.Cell, units: List[dict]) -> int:
    """The unit, drawn from the run's seed, whose every locality-packed
    lane the reference re-simulates."""
    rng = np.random.default_rng([cell.seed, 0xC0FFEE])
    return int(rng.integers(len(units)))


def references(cell: harness.Cell, camp: Campaign, units: List[dict],
               dtype=np.float64) -> Dict[tuple, tuple]:
    """The reference's answer for every lane it checks, keyed by (unit
    index, lane index): each ocs-vclos lane of every unit (one
    re-simulation per trace of the set), and every lane of one sampled
    unit for the locality-packed strategies.  Each answer is ``(rows,
    guarantee faults)``."""
    out: Dict[tuple, tuple] = {}
    by_trace: Dict[int, tuple] = {}
    for ui, u in enumerate(units):
        for li, lane in enumerate(u["lanes"]):
            if lane[0] == "ocs-vclos":
                if u["trace_id"] not in by_trace:
                    by_trace[u["trace_id"]] = reference.simulate_ocs_vclos(
                        cell.config, u["trace"], dtype)
                out[(ui, li)] = by_trace[u["trace_id"]]
    if units:
        ui = sampled_unit(cell, units)
        u = units[ui]
        for li, (strat, seed, *_rest) in enumerate(u["lanes"]):
            if strat in reference.LOCALITY_PACKED:
                out[(ui, li)] = (reference.simulate_locality_packed(
                    cell.config, u["trace"], strat, seed, dtype), 0)
    return out


def check(cell: harness.Cell, camp: Campaign, units: List[dict],
          refs: Dict[tuple, tuple]) -> Dict[str, float]:
    """The numbers that decide ``correct`` for the window's units, against
    the reference answers ``refs`` (see :func:`references`)."""
    num_jobs = int(camp.mix["num_jobs"])
    size = camp.grid.size
    unfinished = 0
    faults = 0
    mismatch = 0
    gap = 0.0
    for u in units:
        unfinished += num_jobs * (size - len(u["lanes"]))
        for _, _, n_fin, _, _ in u["lanes"]:
            unfinished += num_jobs - n_fin
    for (ui, li), (rows, ref_faults) in refs.items():
        u = units[ui]
        strat, seed, _, jwts, jcts = u["lanes"][li]
        gap = max(gap, _gap([j.arrival for j in u["trace"]], jwts, jcts,
                            rows))
        faults += ref_faults
        if strat in camp.isolated_placers:
            got = u["placements"].get((strat, seed), {})
            for j, r in zip(u["trace"], rows):
                if r is None or got.get(j.job_id) != tuple(r[3:]):
                    mismatch += 1
    out = {"unfinished_jobs": float(unfinished), "jct_gap": gap}
    if camp.isolated_placers:
        out.update(placement_mismatch=float(mismatch),
                   guarantee_faults=float(faults))
    return out


def cycle_order(seed: int, cycle: int, k: int) -> List[int]:
    """The order in which cycle ``cycle`` of a run with ``seed`` visits the
    ``k`` traces of the set."""
    return [int(t) for t in
            np.random.default_rng([int(seed), int(cycle)]).permutation(k)]


def run(cell: harness.Cell, device: dict, t_start: float):
    """One run of a campaign cell: ``(result, checks)``."""
    import jax
    from repro.core import batched, fairshare, simulator
    from repro.kernels.phase_max import CALL_EVENT

    tr = cell.traffic
    camp = Campaign(cell)
    tap = Tap(timed=cell.trace)
    counting = {"on": False, "kernel_calls": 0}

    def on_event(event, **kw):
        if event == CALL_EVENT and counting["on"]:
            counting["kernel_calls"] += 1

    jax.monitoring.register_event_listener(on_event)
    try:
        for name in camp.isolated_placers:
            strat = camp.strats[name]
            tap.wrap(type(strat), "place", "place",
                     after=lambda out, s, ctx, jid, n, job=None:
                     camp.capture(out, s, ctx, jid, n, job))
        if cell.trace:
            for mod in (batched, simulator):
                tap.wrap(mod, "phase_worst_loads", "rate")
            for name in camp.grid.strategies:
                if name not in camp.isolated_placers:
                    tap.wrap(type(camp.strats[name]), "place", "place")

        # -- set-up: the trace set, one short warm unit on a trace outside
        # it, and in a traced run the witness program
        pop = gen.Population(camp.mix)
        k_set = int(tr["traces"])
        traces = [pop.trace(t) for t in range(k_set)]
        camp.unit(pop.trace(k_set)[:int(tr["warm_jobs"])])
        camp.placements.clear()
        if cell.trace:
            wv, wp = witness_input(cell.seed)
            fairshare.phase_worst_loads(wv, wp, backend="pallas")
        for k in tap.calls:
            tap.calls[k], tap.seconds[k] = 0, 0.0

        # -- the measured window
        with _profile(cell.out_dir / "trace", cell.trace) as tdir:
            with _annotate("bench/window", cell.trace):
                counting["on"] = True

                def one_cycle(c):
                    units = []
                    for t in cycle_order(cell.seed, c, k_set):
                        camp.placements = {}
                        with _annotate("bench/unit", cell.trace):
                            lanes = camp.unit(traces[t])
                        units.append({"trace_id": t, "trace": traces[t],
                                      "lanes": lanes,
                                      "placements": camp.placements})
                    return units

                cycles, t_w0, t_w1 = harness.run_window(
                    cell.seconds, one_cycle, time.perf_counter)
                counting["on"] = False
                if cell.trace:
                    with _annotate("bench/witness", True):
                        got = fairshare.phase_worst_loads(wv, wp,
                                                          backend="pallas")
            t_trace = time.perf_counter() - t_w0
        window_s = t_w1 - t_w0
        setup_s = t_w0 - t_start
    finally:
        tap.close()
        jax.monitoring.unregister_event_listener(on_event)
    device = dict(device, memory_peak_bytes=harness.memory_peak_bytes())

    units = [u for c in cycles for u in c]
    jobs_done = sum(n for u in units for _, _, n, _, _ in u["lanes"])
    attempted = len(units) * camp.grid.size * int(camp.mix["num_jobs"])
    checks_v = check(cell, camp, units, references(cell, camp, units))
    if cell.trace:
        checks_v["witness_mismatch"] = float(
            np.count_nonzero(np.asarray(got) != segment_max(wv, wp)))
    limits = tr["limits"]
    checks = {k: {"value": v, "limit": float(limits[k])}
              for k, v in checks_v.items()}
    correct = all(c["value"] <= c["limit"] for c in checks.values())

    result = {"correct": correct, "attempted": attempted,
              "failed": int(checks_v["unfinished_jobs"]),
              "device": device}
    if not cell.trace:
        result["metrics"] = {
            "jobs_per_s": {"value": jobs_done / window_s, "unit": "jobs/s"},
            "setup_s": {"value": setup_s, "unit": "s"}}
        return result, checks

    from trace_reduce import (busy_seconds, idle_gaps, idle_share, load,
                              op_seconds, window_of)
    xplanes = sorted(tdir.glob("plugins/profile/*/*.xplane.pb"))
    planes = load(xplanes[-1]) if xplanes else []
    busy = busy_seconds(planes)
    record = {
        "window_s": window_s, "jobs": jobs_done, "units": len(units),
        "calls": dict(tap.calls), "seconds": dict(tap.seconds),
        "kernel_calls": counting["kernel_calls"],
        "idle_share": idle_share(planes, t_trace)}
    result["metrics"] = harness.read_per_layer(cell, record)
    result["device"] = dict(device, busy_s=busy, window_s=t_trace)
    result["breakdown"] = {"device_ops": op_seconds(planes),
                           "idle_gaps": idle_gaps(planes, window_of(planes))}
    shutil.rmtree(cell.out_dir, ignore_errors=True)
    print(f"layers: units {len(units)} jobs {jobs_done} window "
          f"{window_s:.3f} s; calls {tap.calls}; seconds "
          f"{ {k: round(v, 6) for k, v in tap.seconds.items()} }",
          file=sys.stderr)
    return result, checks

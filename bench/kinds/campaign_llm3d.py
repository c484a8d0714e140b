"""3D-parallel LLM campaign cells: a Megatron-LM pretraining fleet, every
job a published GPT layout (tensor parallel inside a server, pipeline
stages across servers, one data-parallel ring per (tp, pp)), scheduled by
the lane engine.  Users pay for simulated jobs per second, as in the
campaign cells.

The cell's configuration names the GPT rows and which row a job of each
size runs.  Its population is drawn once from ``population_seed``: per
job a size from the size mix, lognormal iterations and an exponential gap
(mean ``mean_interarrival``, set so that the offered GPU load is the
configuration's ``offered_load``).  Trace ``t`` is that population in the
order seed ``t`` draws (``gen.Population``), and a cell names a fixed set
of ``traces``.  A *unit* is one ``run_campaign(..., engine="batched")``
call on one trace (best/sr/ecmp x fifo x sim seeds: six lanes in
lockstep); a *cycle* runs every trace of the set once, in an order drawn
from ``--seed``.  Set-up checks that the program knows every row, draws
the set and runs one short warm unit.

Every run checks, against the plain reference
(``bench/reference_llm3d.py``): every job of every unit finished
(``unfinished_jobs``); every job's tensor-parallel groups sat inside one
server in every lane of every unit (``tp_split``: jobs whose placement
split a group, or that the run did not see placed); and every lane of
one unit drawn from ``--seed`` gives every job's wait and JCT within
``jct_gap`` of the reference's.

A traced run turns the program's spans and counters (``repro.obs``) on
over the window and puts their snapshot in the record, then runs the
campaign kind's device witness after the window.
"""

from __future__ import annotations

import shutil
import sys
import time
from typing import Dict, List

import numpy as np

import gen
import harness
import reference_llm3d

camp_mod = harness.load_kind("campaign")
Tap, _profile, _annotate = camp_mod.Tap, camp_mod._profile, camp_mod._annotate
cycle_order, sampled_unit, _gap = (camp_mod.cycle_order,
                                   camp_mod.sampled_unit, camp_mod._gap)


def _population(mix: dict):
    """``num_jobs`` job shapes and gaps from the mix's own seed, drawn per
    job in the order size, iterations, gap."""
    rng = np.random.default_rng(int(mix["population_seed"]))
    sizes = [int(s) for s, _ in mix["size_mix"]]
    probs = np.asarray([float(p) for _, p in mix["size_mix"]])
    probs = probs / probs.sum()
    shapes, gaps = [], []
    for _ in range(int(mix["num_jobs"])):
        n = sizes[rng.choice(len(sizes), p=probs)]
        model = mix["size_to_row"][str(n)]
        iters = int(rng.lognormal(mean=float(mix["iters_log_mean"]),
                                  sigma=float(mix["iters_log_sigma"])))
        gaps.append(float(rng.exponential(float(mix["mean_interarrival"]))))
        shapes.append((model, n, int(mix["rows"][model]["batch"]),
                       max(iters, int(mix["min_iters"])), "ring"))
    return shapes, gaps


class Population(gen.Population):
    """The fleet's jobs and gaps, drawn once; :meth:`trace` orders them."""

    def __init__(self, mix: dict):
        self.num_jobs = int(mix["num_jobs"])
        self.shapes, self.gaps = _population(mix)


class Fleet:
    """The system under test as one cell drives it."""

    def __init__(self, cell: harness.Cell):
        from repro.core import CampaignGrid, ClusterSpec
        from repro.core.jobs import PROFILES
        cfg, tr = cell.config, cell.traffic
        self.cell = cell
        self.mix = cfg["jobs"]
        missing = sorted(set(self.mix["rows"]) - set(PROFILES))
        if missing:
            raise RuntimeError(f"the program has no job profile for "
                               f"{missing}: it cannot run this cell")
        unknown = set(tr["strategies"]) - set(reference_llm3d.LANES)
        if unknown:
            raise harness.Refused(f"no reference for {sorted(unknown)}")
        self.spec = ClusterSpec(**cfg["cluster"])
        self.grid = CampaignGrid(strategies=tuple(tr["strategies"]),
                                 schedulers=("fifo",),
                                 loads=(float(self.mix["mean_interarrival"]),),
                                 seeds=tuple(range(int(tr["sim_seeds"]))))
        self.tp = {name: int(r["t"]) for name, r in self.mix["rows"].items()}
        self.gps = int(cfg["cluster"]["gpus_per_server"])
        self.placed = 0
        self.split = 0

    def see_placement(self, _out, _engine, _l, _lane, _jidx, job, gpus,
                      *_rest) -> None:
        """Count a job the lane engine placed, and whether a TP group of
        it spans two servers."""
        self.placed += 1
        t = self.tp[job.model]
        if t > 1:
            srv = np.asarray(gpus) // self.gps
            self.split += int((srv.reshape(-1, t) != srv[::t, None]).any())

    def unit(self, trace) -> list:
        from repro.core import run_campaign
        jobs = gen.to_program_jobs(trace)
        self.placed = self.split = 0
        res = run_campaign(self.spec, self.grid, trace=jobs,
                           engine="batched")
        return [(c.strategy, c.seed, c.report.n_finished,
                 list(c.report.jwts), list(c.report.jcts))
                for c in res.cells]


def references(cell: harness.Cell, units: List[dict],
               dtype=np.float64) -> Dict[int, list]:
    """The reference's rows for every lane of the unit drawn from the
    run's seed, keyed by lane index."""
    if not units:
        return {}
    u = units[sampled_unit(cell, units)]
    return {li: reference_llm3d.simulate_lane(cell.config, u["trace"], strat,
                                              seed, dtype)
            for li, (strat, seed, *_rest) in enumerate(u["lanes"])}


def check(cell: harness.Cell, fleet: Fleet, units: List[dict],
          refs: Dict[int, list]) -> Dict[str, float]:
    """The numbers that decide ``correct`` for the window's units."""
    num_jobs = int(fleet.mix["num_jobs"])
    size = fleet.grid.size
    unfinished = split = 0
    for u in units:
        unfinished += num_jobs * (size - len(u["lanes"]))
        unfinished += sum(num_jobs - n for _, _, n, _, _ in u["lanes"])
        split += u["split"] + max(0, size * num_jobs - u["placed"])
    gap = 0.0
    if units:
        u = units[sampled_unit(cell, units)]
        arrival = [j.arrival for j in u["trace"]]
        for li, rows in refs.items():
            _, _, _, jwts, jcts = u["lanes"][li]
            gap = max(gap, _gap(arrival, jwts, jcts, rows))
    return {"unfinished_jobs": float(unfinished), "tp_split": float(split),
            "jct_gap": gap}


def run(cell: harness.Cell, device: dict, t_start: float):
    """One run of a 3D-parallel LLM campaign cell: ``(result, checks)``."""
    from repro import obs
    from repro.core import fairshare
    from repro.core.batched import _BatchedEngine

    tr = cell.traffic
    fleet = Fleet(cell)
    tap = Tap(timed=False)
    try:
        tap.wrap(_BatchedEngine, "_add_running", "place",
                 after=fleet.see_placement)
        # -- set-up: the trace set, one short warm unit on a trace outside
        # it, and in a traced run the witness program
        pop = Population(fleet.mix)
        k_set = int(tr["traces"])
        traces = [pop.trace(t) for t in range(k_set)]
        fleet.unit(pop.trace(k_set)[:int(tr["warm_jobs"])])
        if cell.trace:
            wv, wp = camp_mod.witness_input(cell.seed)
            fairshare.phase_worst_loads(wv, wp, backend="pallas")

        # -- the measured window
        with _profile(cell.out_dir / "trace", cell.trace) as tdir:
            with _annotate("bench/window", cell.trace):
                if cell.trace:
                    obs.enable()

                def one_cycle(c):
                    units = []
                    for t in cycle_order(cell.seed, c, k_set):
                        with _annotate("bench/unit", cell.trace):
                            lanes = fleet.unit(traces[t])
                        units.append({"trace_id": t, "trace": traces[t],
                                      "lanes": lanes,
                                      "placed": fleet.placed,
                                      "split": fleet.split})
                    return units

                cycles, t_w0, t_w1 = harness.run_window(
                    cell.seconds, one_cycle, time.perf_counter)
                snap = None
                if cell.trace:
                    snap = obs.snapshot()
                    obs.disable()
                    with _annotate("bench/witness", True):
                        got = fairshare.phase_worst_loads(wv, wp,
                                                          backend="pallas")
            t_trace = time.perf_counter() - t_w0
        window_s = t_w1 - t_w0
        setup_s = t_w0 - t_start
    finally:
        obs.disable()
        tap.close()
    device = dict(device, memory_peak_bytes=harness.memory_peak_bytes())

    units = [u for c in cycles for u in c]
    jobs_done = sum(n for u in units for _, _, n, _, _ in u["lanes"])
    attempted = len(units) * fleet.grid.size * int(fleet.mix["num_jobs"])
    checks_v = check(cell, fleet, units, references(cell, units))
    if cell.trace:
        checks_v["witness_mismatch"] = float(np.count_nonzero(
            np.asarray(got) != camp_mod.segment_max(wv, wp)))
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
    record = {"window_s": window_s, "jobs": jobs_done, "units": len(units),
              "obs": snap, "idle_share": idle_share(planes, t_trace)}
    result["metrics"] = harness.read_per_layer(cell, record)
    result["device"] = dict(device, busy_s=busy_seconds(planes),
                            window_s=t_trace)
    spans = {k: round(v["total_s"], 6) for k, v in snap["spans"].items()}
    result["breakdown"] = {"device_ops": op_seconds(planes),
                           "idle_gaps": idle_gaps(planes, window_of(planes)),
                           "spans_s": spans, "counters": snap["counters"],
                           "idle_share": record["idle_share"]}
    shutil.rmtree(cell.out_dir, ignore_errors=True)
    print(f"layers: units {len(units)} jobs {jobs_done} window "
          f"{window_s:.3f} s; spans {spans}; counters {snap['counters']}",
          file=sys.stderr)
    return result, checks

"""The benchmark's trace generator and plain reference against the
program, at sizes a test can hold, and the float32 control that the
reference's limit has to reject."""

import json

import numpy as np
import pytest

from bench_helpers import BENCH
import gen
import reference

CFG = json.loads((BENCH / "configs" / "cluster512-helios.json").read_text())
LIMIT = json.loads((BENCH / "traffic" / "lanes.json").read_text())[
    "limits"]["jct_gap"]


def _mix(num_jobs, lam=None):
    mix = dict(CFG["jobs"], num_jobs=num_jobs)
    if lam is not None:
        mix["mean_interarrival"] = lam
    return mix


def test_population_is_the_paper_generators_draw():
    from repro.core import WorkloadSpec, generate_trace
    mix = _mix(50)
    shapes, gaps = gen._population(mix, 50)
    prog = generate_trace(WorkloadSpec(
        num_jobs=50, mean_interarrival=mix["mean_interarrival"],
        max_gpus=mix["max_gpus"], seed=mix["population_seed"]))
    assert shapes == [(j.model, j.num_gpus, j.batch_size, j.num_iters,
                       j.allreduce_algo) for j in prog]
    arrivals = np.cumsum(gaps)
    assert np.allclose(arrivals, [j.arrival for j in prog], rtol=1e-12)


def test_a_seed_reorders_the_same_work():
    mix = _mix(120)
    a, b = gen.make_trace(mix, 5), gen.make_trace(mix, 2 ** 31 + 5)
    assert a == gen.make_trace(mix, 5)
    key = lambda t: sorted((j.model, j.num_gpus, j.num_iters) for j in t)
    assert key(a) == key(b)
    assert [j.num_gpus for j in a] != [j.num_gpus for j in b]
    assert a[-1].arrival == pytest.approx(b[-1].arrival, rel=1e-12)


def _program_lanes(trace, strategies, seeds):
    from repro.core import (CampaignGrid, ClusterSpec, run_campaign)
    spec = ClusterSpec(**CFG["cluster"])
    grid = CampaignGrid(strategies=strategies, loads=(1.0,), seeds=seeds)
    res = run_campaign(spec, grid, trace=gen.to_program_jobs(trace),
                       engine="batched")
    return res.cells


@pytest.mark.parametrize("strategy", ["best", "sr", "ecmp"])
def test_reference_resimulates_the_lane_engine(strategy):
    trace = gen.make_trace(_mix(90, lam=20.0), 7)
    (cell,) = _program_lanes(trace, (strategy,), (4,))
    ref = reference.simulate_locality_packed(CFG, trace, strategy, 4)
    got = np.asarray(cell.report.jcts)
    want = np.asarray([r[2] for r in ref])
    assert np.max(np.abs(got - want) / want) <= LIMIT
    # the control: the same reference in float32 fails the limit
    ref32 = reference.simulate_locality_packed(CFG, trace, strategy, 4,
                                               np.float32)
    low = np.asarray([r[2] for r in ref32], dtype=np.float64)
    assert np.max(np.abs(low - want) / want) > LIMIT


@pytest.mark.parametrize("seed", [4, 19, 2 ** 31 + 8])
def test_reference_resimulates_ocs_vclos(seed):
    import control
    from bench_helpers import small_cell
    cell = small_cell("c512-ocs-vclos", 150, seed=seed)
    cell.config["jobs"]["mean_interarrival"] = 20.0
    camp_mod = control.harness.load_kind("campaign")
    camp = camp_mod.Campaign(cell)
    tap = camp_mod.Tap(timed=False)
    tap.wrap(type(camp.strats["ocs-vclos"]), "place", "place",
             after=lambda out, s, ctx, jid, n, job=None:
             camp.capture(out, s, ctx, jid, n, job))
    trace = gen.make_trace(camp.mix, seed)
    try:
        lanes = camp.unit(trace)
    finally:
        tap.close()
    placed = camp.placements[("ocs-vclos", 0)]
    # the trace reaches the stages across leafs: reserved channels, and
    # ports patched leaf to leaf
    assert any(links for _, links, _ in placed.values())
    assert any(n for _, _, n in placed.values())
    unit = {"trace_id": 0, "trace": trace, "lanes": lanes,
            "placements": camp.placements}
    refs = camp_mod.references(cell, camp, [unit])
    got = camp_mod.check(cell, camp, [unit], refs)
    assert got["placement_mismatch"] == 0 and got["guarantee_faults"] == 0
    assert got["unfinished_jobs"] == 0 and got["jct_gap"] <= LIMIT
    (rows, _), = refs.values()
    assert [tuple(r[3:]) for r in rows] == [placed[j.job_id] for j in trace]


@pytest.mark.parametrize("workload,jobs", [("c2048-lanes", 120),
                                           ("c512-ocs-vclos", 60)])
def test_control_readings_straddle_the_limit(workload, jobs):
    import control
    from bench_helpers import small_cell
    cell = small_cell(workload, jobs, seed=2 ** 31 + 3)
    r = control.readings(cell, cell.seed)
    limit = cell.traffic["limits"]["jct_gap"]
    assert r["program"]["jct_gap"] <= limit < r["control"]["jct_gap"]

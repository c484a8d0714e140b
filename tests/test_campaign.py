"""Campaign engine: v2 ≡ the v1 reference, grid sweep, aggregation."""

import json

import numpy as np
import pytest

from repro.core import (CLUSTER512, CLUSTER512_OCS, CampaignGrid,
                        WorkloadSpec, generate_trace, run_campaign, simulate)
from repro.core.metrics import cdf
from repro.core.scheduler import order_queue
from repro.core.jobs import Job


# ---------------------------------------------------------------------------
# v2 heap engine ≡ the v1 full-recompute reference (the regression fixture)
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("seed", [11, 5, 23])
@pytest.mark.parametrize("strategy", ["ecmp", "sr", "balanced", "vclos",
                                      "ocs-relax", "contention-affinity"])
def test_v2_matches_reference(strategy, seed):
    """v2 re-solves only the jobs crossing a link whose load changed, from
    a completion heap; v1 scans the running set and re-solves every job.
    The schedules must be bit-identical: same completions, same JCT/JWT
    floats, same slowdowns."""
    jobs = generate_trace(WorkloadSpec(num_jobs=80, mean_interarrival=100.0,
                                       seed=seed, max_gpus=128))
    v1 = simulate(CLUSTER512, jobs, strategy, engine="v1")
    v2 = simulate(CLUSTER512, jobs, strategy, engine="v2")
    assert v1.n_finished == v2.n_finished
    assert v1.jcts == v2.jcts               # exact float equality, per job
    assert v1.jwts == v2.jwts
    assert v1.slowdowns == v2.slowdowns
    assert (v1.frag_gpu, v1.frag_network) == (v2.frag_gpu, v2.frag_network)


def test_v2_engine_matches_v1_ocs_vclos():
    """OCS rewiring paths (xconn release, renormalisation) interleave with
    the event loop — the heap engine must preserve the exact sequence."""
    jobs = generate_trace(WorkloadSpec(num_jobs=60, mean_interarrival=90.0,
                                       seed=7, max_gpus=128))
    v1 = simulate(CLUSTER512_OCS, jobs, "ocs-vclos", engine="v1")
    v2 = simulate(CLUSTER512_OCS, jobs, "ocs-vclos", engine="v2")
    assert v1.n_finished == v2.n_finished
    assert v1.jcts == v2.jcts
    assert v1.jwts == v2.jwts


@pytest.mark.parametrize("strategy", ["ecmp", "contention-affinity"])
@pytest.mark.parametrize("scheduler", ["ff", "edf"])
def test_v2_engine_matches_v1_queueing_policies(scheduler, strategy):
    """Placement memoisation must not change which queued job places when
    the scheduler reorders the queue (ff/edf retry every waiting job)."""
    jobs = generate_trace(WorkloadSpec(num_jobs=70, mean_interarrival=80.0,
                                       seed=3, max_gpus=128,
                                       deadline_slack=(1.5, 4.0)))
    v1 = simulate(CLUSTER512, jobs, strategy, scheduler=scheduler,
                  engine="v1")
    v2 = simulate(CLUSTER512, jobs, strategy, scheduler=scheduler,
                  engine="v2")
    assert v1.jcts == v2.jcts
    assert v1.jwts == v2.jwts


def test_v2_golden_trace_jct_snapshot():
    """Golden JCTs for the default (v2) engine — the recorded values every
    semantic-preserving refactor must reproduce (update consciously)."""
    jobs = generate_trace(WorkloadSpec(num_jobs=200, mean_interarrival=120.0,
                                       seed=0, max_gpus=256))
    golden = {"ecmp": 13417.8, "sr": 3731.4, "best": 2949.3}
    for strat, want in golden.items():
        got = simulate(CLUSTER512, jobs, strat, engine="v2").avg_jct
        assert round(got, 1) == pytest.approx(want), strat


def test_unknown_strategy_rejected():
    with pytest.raises(ValueError, match="unknown strategy"):
        simulate(CLUSTER512, [], "warp-drive")
    with pytest.raises(ValueError, match="queueing policy"):
        simulate(CLUSTER512, [], "ecmp", scheduler="sjf")
    with pytest.raises(ValueError, match="unknown engine"):
        simulate(CLUSTER512, [], "ecmp", engine="v3")


# ---------------------------------------------------------------------------
# vectorized fast paths ≡ their scalar twins (the simulator's phase builder
# only uses the vectorized side, so drift here would silently shift every
# published table while the engine-identity test above still passed)
# ---------------------------------------------------------------------------

def test_vectorized_link_counts_match_scalar_routing():
    from collections import Counter

    from repro.core.routing import (ECMPRouting, SourceRouting,
                                    alltoall_link_counts)
    from repro.core.traffic import Flow, pairwise_alltoall

    spec = CLUSTER512
    rng = np.random.default_rng(3)
    src = rng.integers(0, spec.num_gpus, 300)
    dst = rng.integers(0, spec.num_gpus, 300)
    for routing in (ECMPRouting(spec, seed=5), SourceRouting(spec)):
        scalar = Counter()
        for s, d in zip(src.tolist(), dst.tolist()):
            for link in routing.route(Flow(s, d, 1.0), flow_id=7):
                scalar[link] += 1
        vec = routing.phase_link_counts(src.astype(np.int64),
                                        dst.astype(np.int64), 7)
        assert vec == scalar

    # AlltoAll aggregate == per-step counts max-reduced over steps
    ranks = sorted(rng.choice(spec.num_gpus, 48, replace=False).tolist())
    routing = ECMPRouting(spec, seed=1)
    agg = Counter()
    for phase in pairwise_alltoall(ranks, 1.0):
        counts = Counter()
        for f in phase:
            for link in routing.route(f, flow_id=9):
                counts[link] += 1
        for link, c in counts.items():
            agg[link] = max(agg[link], c)
    assert alltoall_link_counts(routing, ranks, flow_id=9) == agg


def test_dense_link_counts_match_counter_paths():
    """The v2 engine's dense (LinkSpace-indexed) count builders must agree
    entry-for-entry with the Counter-based vectorized paths."""
    from repro.core.routing import (ECMPRouting, LinkSpace, SourceRouting,
                                    alltoall_dense_counts,
                                    alltoall_link_counts,
                                    multi_phase_dense_counts,
                                    multi_phase_link_counts)

    spec = CLUSTER512
    ls = LinkSpace(spec)
    rng = np.random.default_rng(5)
    src = rng.integers(0, spec.num_gpus, 400).astype(np.int64)
    dst = rng.integers(0, spec.num_gpus, 400).astype(np.int64)
    pidx = rng.integers(0, 5, 400).astype(np.int64)
    ranks = sorted(rng.choice(spec.num_gpus, 24, replace=False).tolist())
    for routing in (ECMPRouting(spec, seed=2), SourceRouting(spec)):
        counters = multi_phase_link_counts(routing, src, dst, pidx, 5, 3)
        dense = multi_phase_dense_counts(routing, ls, src, dst, pidx, 5, 3)
        assert dense.shape == (5, ls.nlinks)
        for c, row in zip(counters, dense):
            assert sum(c.values()) == row.sum()
            for link, cnt in c.items():
                assert row[ls.id_of(link)] == cnt
        agg_c = alltoall_link_counts(routing, ranks, flow_id=9)
        agg_d = alltoall_dense_counts(routing, ls, ranks, flow_id=9)
        for link, cnt in agg_c.items():
            assert agg_d[ls.id_of(link)] == cnt
        assert agg_d.sum() == sum(agg_c.values())


def test_ar_phase_arrays_match_ar_phases():
    rng = np.random.default_rng(0)
    cases = [("bert", "hd", 24),                   # non-power-of-2 fold
             ("bert", "hd", 32),
             ("vgg16", "hierarchical_ring", 48),
             ("vgg16", "hierarchical_ring", 9),    # non-divisible: flat ring
             ("resnet50", "ring", 10)]
    for model, algo, n in cases:
        ranks = sorted(rng.choice(4096, n, replace=False).tolist())
        job = Job(0, model, n, 32, 0.0, 10, allreduce_algo=algo)
        phases = job.ar_phases(ranks)
        metas, src, dst, pidx = job.ar_phase_arrays(ranks)
        assert len(metas) == len(phases), (model, algo, n)
        for i, ((kind, phase), (kind2, nbytes)) in enumerate(zip(phases,
                                                                 metas)):
            assert kind == kind2
            assert nbytes == max((f.nbytes for f in phase), default=0.0)
            mask = pidx == i
            assert sorted((f.src, f.dst) for f in phase) == \
                sorted(zip(src[mask].tolist(), dst[mask].tolist()))


def test_slowdowns_reported():
    jobs = generate_trace(WorkloadSpec(num_jobs=50, mean_interarrival=150.0,
                                       seed=0, max_gpus=64))
    best = simulate(CLUSTER512, jobs, "best")
    ecmp = simulate(CLUSTER512, jobs, "ecmp")
    assert len(best.slowdowns) == best.n_finished
    assert all(abs(s - 1.0) < 1e-6 for s in best.slowdowns)
    assert all(s >= 1.0 - 1e-9 for s in ecmp.slowdowns)
    assert max(ecmp.slowdowns) > 1.0        # some contention under hashing


def test_metrics_extensions():
    jobs = generate_trace(WorkloadSpec(num_jobs=40, mean_interarrival=150.0,
                                       seed=1, max_gpus=64))
    rep = simulate(CLUSTER512, jobs, "sr")
    assert rep.makespan > 0
    assert rep.p99_jct >= rep.avg_jct
    assert len(rep.jcts) == rep.n_finished == len(rep.jwts)


# ---------------------------------------------------------------------------
# queueing-policy ordering (shared scheduler logic)
# ---------------------------------------------------------------------------

def test_order_queue_policies():
    jobs = [Job(0, "vgg16", 16, 32, 0.0, 10, deadline=50.0),
            Job(1, "vgg16", 2, 32, 1.0, 10, deadline=10.0),
            Job(2, "vgg16", 8, 32, 2.0, 10)]
    assert [j.job_id for j in order_queue(jobs, "fifo")] == [0, 1, 2]
    assert [j.job_id for j in order_queue(jobs, "ff")] == [1, 2, 0]
    # edf: job 2 has no deadline -> sorts by arrival (2.0), before 10/50
    assert [j.job_id for j in order_queue(jobs, "edf")] == [2, 1, 0]
    with pytest.raises(ValueError):
        order_queue(jobs, "lifo")


# ---------------------------------------------------------------------------
# campaign sweeps
# ---------------------------------------------------------------------------

def test_campaign_grid_validation():
    with pytest.raises(ValueError, match="unknown strategy"):
        CampaignGrid(strategies=("warp",))
    with pytest.raises(ValueError, match="queueing policy"):
        CampaignGrid(schedulers=("lifo",))
    grid = CampaignGrid(strategies=("best", "sr"), schedulers=("fifo", "ff"),
                        loads=(100.0, 200.0), seeds=(0, 1, 2))
    assert grid.size == 2 * 2 * 2 * 3 == len(list(grid.cells()))


def test_campaign_runs_and_aggregates():
    grid = CampaignGrid(strategies=("best", "ecmp"), loads=(200.0,),
                        seeds=(0, 1))
    res = run_campaign(CLUSTER512, grid,
                       workload=WorkloadSpec(num_jobs=40, max_gpus=64))
    assert len(res.cells) == grid.size
    rows = res.aggregate()
    assert len(rows) == 2                   # one row per (strategy, sched, load)
    by_strat = {r["strategy"]: r for r in rows}
    assert by_strat["best"]["seeds"] == 2
    assert by_strat["best"]["n_finished"] == 80
    # contention-free upper bound cannot lose to the hashing baseline
    assert by_strat["best"]["jct_mean"] <= by_strat["ecmp"]["jct_mean"]
    assert by_strat["best"]["contention_ratio_mean"] <= \
        by_strat["ecmp"]["contention_ratio_mean"] + 1e-9
    for row in rows:
        for key in ("jct_p99", "queue_delay_mean", "queue_delay_p99",
                    "makespan_mean", "sim_seconds"):
            assert key in row


def test_campaign_cdfs_and_json():
    grid = CampaignGrid(strategies=("ecmp",), loads=(200.0,), seeds=(0,))
    res = run_campaign(CLUSTER512, grid,
                       workload=WorkloadSpec(num_jobs=30, max_gpus=64))
    curve = res.contention_cdf("ecmp")
    assert curve, "expected contention samples"
    xs = [x for x, _ in curve]
    ys = [y for _, y in curve]
    assert xs == sorted(xs) and ys == sorted(ys)
    assert ys[-1] == pytest.approx(1.0)
    assert min(xs) >= 1.0 - 1e-9            # slowdown is ≥ 1 by definition
    blob = json.dumps(res.to_json())        # fully serialisable
    assert "jct_cdfs" in blob


def test_campaign_parallel_workers_match_serial():
    """Cells sharded across a process pool merge in grid order with
    bit-identical per-cell schedules (seed-stable, deterministic merge)."""
    grid = CampaignGrid(strategies=("ecmp", "sr"), loads=(150.0,),
                        seeds=(0, 1))
    wl = WorkloadSpec(num_jobs=40, max_gpus=64)
    ser = run_campaign(CLUSTER512, grid, workload=wl)
    par = run_campaign(CLUSTER512, grid, workload=wl, workers=2)
    assert [(c.strategy, c.scheduler, c.load, c.seed) for c in ser.cells] \
        == [(c.strategy, c.scheduler, c.load, c.seed) for c in par.cells]
    for a, b in zip(ser.cells, par.cells):
        assert a.report.jcts == b.report.jcts
        assert a.report.jwts == b.report.jwts


def test_campaign_streaming_store():
    """store="stream" bounds per-cell memory: ≤ max_samples order stats,
    exact pooled means (weighted scalars), approximate percentiles."""
    grid = CampaignGrid(strategies=("ecmp",), loads=(150.0,), seeds=(0, 1))
    wl = WorkloadSpec(num_jobs=60, max_gpus=64)
    full = run_campaign(CLUSTER512, grid, workload=wl)
    stream = run_campaign(CLUSTER512, grid, workload=wl, store="stream")
    for c in stream.cells:
        assert c.report.condensed
        assert len(c.report.jcts) <= 512
    rf = full.aggregate()[0]
    rs = stream.aggregate()[0]
    assert rs["jct_mean"] == pytest.approx(rf["jct_mean"], rel=1e-12)
    assert rs["queue_delay_mean"] == pytest.approx(rf["queue_delay_mean"],
                                                   rel=1e-12)
    assert rs["contention_ratio_mean"] == pytest.approx(
        rf["contention_ratio_mean"], rel=1e-12)
    assert rs["jct_p99"] == pytest.approx(rf["jct_p99"], rel=0.05)
    json.dumps(stream.to_json())            # still fully serialisable
    with pytest.raises(ValueError, match="store"):
        run_campaign(CLUSTER512, grid, workload=wl, store="bogus")


def test_metrics_condense_small_report_lossless():
    from repro.core.metrics import MetricsReport
    rep = MetricsReport(1, 1, 1, 0, 1, 3, jcts=[3.0, 1.0, 2.0],
                        jwts=[0.5, 0.1, 0.2], slowdowns=[1.1, 1.0, 1.3])
    rep.condense(max_samples=8)
    assert rep.condensed
    assert rep.jcts == [1.0, 2.0, 3.0]      # below the cap: just sorted
    assert rep.slowdown_mean == pytest.approx(np.mean([1.1, 1.0, 1.3]))
    assert rep.n_slowdowns == 3


def test_campaign_explicit_trace():
    trace = generate_trace(WorkloadSpec(num_jobs=30, max_gpus=64, seed=3))
    grid = CampaignGrid(strategies=("sr",), loads=(120.0,), seeds=(0,))
    res = run_campaign(CLUSTER512, grid, trace=trace)
    assert res.cells[0].report.n_finished == 30
    with pytest.raises(ValueError, match="loads axis"):
        run_campaign(CLUSTER512,
                     CampaignGrid(strategies=("sr",), loads=(1.0, 2.0)),
                     trace=trace)


def test_cdf_helper():
    assert cdf([]) == []
    curve = cdf(list(range(1000)), num_points=20)
    assert len(curve) <= 21
    assert curve[-1][1] == pytest.approx(1.0)

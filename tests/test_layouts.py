"""3D-parallel jobs (tensor / pipeline / data parallel, Megatron-LM rank
order): the per-group traffic against a plain enumeration, the GPT rows
against their formulas, the lane engine and v1 against the v2 reference,
TP groups inside servers, strategies that refuse layouts, the goldens, and
the ``repro.obs`` counters."""

import dataclasses

import numpy as np
import pytest

from repro import obs
from repro.core.batched import _BatchedEngine
from repro.core.campaign import CampaignGrid, run_campaign
from repro.core.jobs import (BATCHES, GBPS, MODEL_FAMILIES, PROFILES, Job,
                             megatron_gpt)
from repro.core.patterns import comm_duty_cycle
from repro.core.simulator import ClusterSimulator, simulate
from repro.core.topology import CLUSTER512, CLUSTER512_OCS, ClusterSpec
from repro.core.traffic import ring_allreduce
from repro.core.workloads import WorkloadSpec, generate_trace

SMALL = ClusterSpec(num_leafs=4, num_spines=4, gpus_per_leaf=32,
                    gpus_per_server=8)
LAYOUTS = [(t, p) for t in (1, 2, 4, 8) for p in (1, 2)]


def _name(t, p):
    return f"test-gpt-t{t}p{p}"


@pytest.fixture
def layouts(monkeypatch):
    """A small GPT profile per (t, p) of LAYOUTS, registered for the test
    (names fixed per layout, so the lane engine's per-model cache stays
    valid across tests)."""
    for t, p in LAYOUTS:
        monkeypatch.setitem(PROFILES, _name(t, p), megatron_gpt(
            _name(t, p), hidden=1024, layers=4, tp=t, pp=p, gpus=32,
            batch=64, tflops=100.0))
    yield


def _job(model, n, jid=0, arrival=0.0, iters=100, batch=None):
    return Job(jid, model, n, batch or PROFILES[model].batch_ref, arrival,
               iters)


def _plain_flows(ranks, t, p):
    """Every DP ring of ring_allreduce over each (tp, pp) rank set, and
    every stage boundary's (dp, tp) pairs both ways, as (src, dst) GPU
    pairs."""
    n = len(ranks)
    d = n // (t * p)

    def rank(pp, dp, tp):
        return pp * d * t + dp * t + tp

    ring = []
    for pp in range(p):
        for tp in range(t):
            group = [ranks[rank(pp, dp, tp)] for dp in range(d)]
            phases = ring_allreduce(group, 1.0)
            ring += [(f.src, f.dst) for f in (phases[0] if phases else [])]
    stage = []
    for pp in range(p - 1):
        for dp in range(d):
            for tp in range(t):
                a, b = ranks[rank(pp, dp, tp)], ranks[rank(pp + 1, dp, tp)]
                stage += [(a, b), (b, a)]
    return sorted(ring), sorted(stage)


@pytest.mark.parametrize("n", [16, 32, 64])
@pytest.mark.parametrize("t,p", LAYOUTS)
def test_group_flows_equal_a_plain_enumeration(layouts, t, p, n):
    job = _job(_name(t, p), n)
    ranks = [int(g) for g in np.random.default_rng(n + t + p).permutation(
        4 * n)[:n]]
    want_ring, want_stage = _plain_flows(ranks, t, p)
    d = n // (t * p)
    ar, unc = job.comm_bytes()
    prof = PROFILES[_name(t, p)]
    assert ar == pytest.approx(2 * prof.param_bytes / (t * p) * (d - 1) / d)
    want_pp = (job.batch_size / d) * prof.boundary_bytes / t if p > 1 else 0
    assert unc == pytest.approx(want_pp)

    metas, src, dst, pidx = job.ar_phase_arrays(ranks)
    kinds = [k for k, _ in metas]
    assert kinds == ["ar"] * (d > 1) + ["pp"] * (p > 1)
    pairs = list(zip(src.tolist(), dst.tolist()))
    got = {k: sorted(pr for pr, i in zip(pairs, pidx) if kinds[i] == k)
           for k in ("ar", "pp")}
    assert got["ar"] == want_ring
    assert got["pp"] == want_stage
    assert dict(metas).get("pp", 0.0) == unc
    # the Flow-level twin carries the same flows and bytes
    flows = {k: (sorted((f.src, f.dst) for f in ph),
                 {f.nbytes for f in ph}) for k, ph in job.ar_phases(ranks)}
    assert flows.get("ar", ([], set()))[0] == want_ring
    assert flows.get("pp", ([], set()))[0] == want_stage
    for k, b in metas:
        assert flows[k][1] == {b}


def test_a_layout_needs_whole_replicas_and_ring(layouts):
    with pytest.raises(ValueError, match="whole number of model replicas"):
        _job(_name(8, 2), 24).comm_bytes()
    hd = dataclasses.replace(_job(_name(4, 1), 32), allreduce_algo="hd")
    with pytest.raises(ValueError, match="does not apply"):
        hd.ar_phase_arrays(list(range(32)))
    with pytest.raises(ValueError, match="AlltoAll"):
        dataclasses.replace(PROFILES["moe"], tp=2)


@pytest.mark.parametrize("model,params,compute", [
    ("gpt-1.7b", 1.652e9, 3.53), ("gpt-39.1b", 3.909e10, 14.45)])
def test_gpt_rows_match_the_formulas(model, params, compute):
    prof = PROFILES[model]
    n = prof.ref_gpus
    job = _job(model, n)
    assert prof.param_bytes / 2 == pytest.approx(params, rel=1e-3)
    assert job.compute_time() == pytest.approx(compute, abs=0.005)
    d = n // (prof.tp * prof.pp)
    ring = 2 * (prof.param_bytes / (prof.tp * prof.pp)) * (d - 1) / d
    m = BATCHES[model][0] / d
    stage = m * 2048 * {"gpt-1.7b": 2304, "gpt-39.1b": 8192}[model] * 2 \
        / prof.tp if prof.pp > 1 else 0.0
    bw = 100.0 * GBPS
    assert job.iter_time(1.0) == pytest.approx(
        job.compute_time() + ring / bw + stage / bw, rel=1e-12)
    # beta = 0: the rings and the pipeline sends are the duty cycle
    assert comm_duty_cycle(job) == pytest.approx(
        (ring + stage) / bw / job.iter_time(1.0), rel=1e-12)
    # compute scales with the GPUs a job gets (strong scaling)
    assert _job(model, 2 * n).compute_time() == pytest.approx(
        job.compute_time() / 2)


def test_the_family_draws_the_row_for_each_size():
    trace = generate_trace(WorkloadSpec(num_jobs=60, size_mix="tpuv4",
                                        models=("megatron-gpt",), seed=1))
    rows = MODEL_FAMILIES["megatron-gpt"]
    assert {j.num_gpus for j in trace} <= set(rows)
    for j in trace:
        assert j.model == rows[j.num_gpus]
        assert j.batch_size == PROFILES[j.model].batch_ref
        assert j.allreduce_algo == "ring"
    with pytest.raises(ValueError, match="no row for"):
        generate_trace(WorkloadSpec(num_jobs=5, size_mix="helios",
                                    models=("megatron-gpt",)))


def _trace3d(seed, num_jobs=40):
    """Jobs of every layout of LAYOUTS at 16-64 GPUs, arriving fast enough
    that the small fabric queues and contends."""
    rng = np.random.default_rng(seed)
    out, t = [], 0.0
    for i in range(num_jobs):
        lt, lp = LAYOUTS[rng.integers(len(LAYOUTS))]
        n = int(rng.choice([16, 32, 64]))
        t += float(rng.exponential(2.0))
        out.append(Job(i, _name(lt, lp), n, 64, t,
                       int(rng.integers(50, 400))))
    return out


def _schedules(res):
    return [(c.strategy, c.seed, list(c.report.jwts), list(c.report.jcts))
            for c in res.cells]


def _grid(strategies=("best", "sr", "ecmp")):
    return CampaignGrid(strategies=strategies, schedulers=("fifo",),
                        loads=(2.0,), seeds=(0, 1))


@pytest.mark.parametrize("engine", ["batched", "v1"])
@pytest.mark.parametrize("seed", [0, 1])
def test_engines_equal_the_v2_reference_on_3d_jobs(layouts, engine, seed):
    trace = _trace3d(seed)
    ref = run_campaign(SMALL, _grid(), trace=trace, engine="v2")
    got = run_campaign(SMALL, _grid(), trace=trace, engine=engine)
    assert _schedules(got) == _schedules(ref)
    # the fabric strategies felt contention: a schedule that never slowed
    # a job would not test the rate path
    best, ecmp = (np.mean(c.report.jcts) for c in ref.cells
                  if c.seed == 0 and c.strategy in ("best", "ecmp"))
    assert ecmp > best


@pytest.mark.parametrize("engine", ["batched", "v2"])
def test_tp_groups_stay_inside_servers(layouts, monkeypatch, engine):
    placed = []
    if engine == "batched":
        orig = _BatchedEngine._add_running

        def spy(self, l, lane, jidx, job, gpus, *a):
            placed.append((job, np.asarray(gpus)))
            return orig(self, l, lane, jidx, job, gpus, *a)

        monkeypatch.setattr(_BatchedEngine, "_add_running", spy)
    else:
        orig = ClusterSimulator._add_running_v2

        def spy(self, job, placement):
            placed.append((job, np.asarray(placement.gpus[:job.num_gpus])))
            return orig(self, job, placement)

        monkeypatch.setattr(ClusterSimulator, "_add_running_v2", spy)
    trace = _trace3d(2)
    run_campaign(SMALL, _grid(), trace=trace, engine=engine)
    assert len(placed) == 6 * len(trace)
    gps = SMALL.gpus_per_server
    for job, gpus in placed:
        servers = (gpus // gps).reshape(-1, job.profile.tp)
        assert (servers == servers[:, :1]).all(), (job.model, gpus)


@pytest.mark.parametrize("strategy", ["vclos", "ocs-vclos", "ocs-relax",
                                      "balanced", "contention-affinity",
                                      "contention-affinity-time"])
def test_a_strategy_that_splits_tp_groups_refuses_a_layout(strategy):
    spec = CLUSTER512_OCS if "ocs" in strategy else CLUSTER512
    jobs = [_job("gpt-1.7b", 32, jid=0), _job("gpt-7.5b", 128, jid=1)]
    with pytest.raises(ValueError, match="keep tensor-parallel groups"):
        simulate(spec, jobs, strategy)
    # a t = p = 1 row is a plain data-parallel job: every strategy runs it
    assert simulate(spec, jobs[:1], strategy).n_finished == 1


@pytest.mark.parametrize("strategy,ok", [("vclos", False), ("sr", True)])
def test_the_daemon_refuses_an_unplaceable_layout_at_submit(strategy, ok):
    from repro.core import SimConfig
    from repro.service import LiveCluster
    live = LiveCluster(CLUSTER512, SimConfig(strategy=strategy, seed=0,
                                             engine="v2"))
    if not ok:
        with pytest.raises(ValueError, match="keep tensor-parallel groups"):
            live.new_job("gpt-7.5b", 128, 10)
        return
    live.submit(live.new_job("gpt-7.5b", 128, 10), tenant="t")
    live.drain_all()
    assert live.report().n_finished == 1


def test_servers_a_tp_group_cannot_tile_are_refused():
    spec = dataclasses.replace(SMALL, gpus_per_server=4)
    with pytest.raises(ValueError, match="do not tile servers"):
        simulate(spec, [_job("gpt-18.4b", 32)], "sr", engine="batched")


@pytest.mark.parametrize("strategy,want", [
    ("ecmp", 13417.8), ("sr", 3731.4), ("best", 2949.3)])
def test_golden_jcts_hold_on_the_lane_engine(strategy, want):
    jobs = generate_trace(WorkloadSpec(num_jobs=200, mean_interarrival=120.0,
                                       seed=0, max_gpus=256))
    assert not any(PROFILES[j.model].layered for j in jobs)
    got = simulate(CLUSTER512, jobs, strategy, engine="batched").avg_jct
    assert round(got, 1) == pytest.approx(want)


def _enumerated_counts(placed, gpl):
    """flows.dp / flows.pp / groups.dp of the placed jobs, from the plain
    enumeration."""
    want = {"flows.dp": 0, "flows.pp": 0, "groups.dp": 0}
    for job, gpus in placed:
        if len(set((gpus // SMALL.gpus_per_server).tolist())) == 1:
            continue
        prof = job.profile
        ring, stage = _plain_flows(gpus.tolist(), prof.tp, prof.pp)
        want["flows.dp"] += sum(a // gpl != b // gpl for a, b in ring)
        want["flows.pp"] += sum(a // gpl != b // gpl for a, b in stage)
        want["groups.dp"] += prof.tp * prof.pp * (job.num_gpus
                                                  > prof.tp * prof.pp)
    return want


@pytest.mark.parametrize("engine", ["batched", "v2"])
def test_obs_on_equals_off_and_counts_the_enumerated_flows(
        layouts, monkeypatch, engine):
    trace = _trace3d(3)
    off = _schedules(run_campaign(SMALL, _grid(), trace=trace,
                                  engine=engine))
    placed = []
    orig = (_BatchedEngine._add_running if engine == "batched"
            else ClusterSimulator._add_running_v2)

    def spy(self, *a):
        if engine == "batched":
            job, gpus = a[3], a[4]
        else:
            job, gpus = a[0], a[1].gpus[:a[0].num_gpus]
        placed.append((job, np.asarray(gpus)))
        return orig(self, *a)

    owner = _BatchedEngine if engine == "batched" else ClusterSimulator
    monkeypatch.setattr(owner, orig.__name__, spy)
    obs.enable()
    try:
        on = _schedules(run_campaign(SMALL, _grid(), trace=trace,
                                     engine=engine))
        snap = obs.snapshot()
    finally:
        obs.disable()
    assert on == off
    want = _enumerated_counts(placed, SMALL.gpus_per_leaf)
    assert want["flows.dp"] > 0 and want["flows.pp"] > 0
    for name, n in want.items():
        assert snap["counters"].get(name, 0) == n, name
    if engine == "batched":
        # one entry build per job of the four sr and ecmp lanes (every job
        # spans servers), inside the scheduling pass
        entries = snap["spans"]["lanes.entries"]
        assert entries["count"] == 4 * len(trace)
        assert entries["total_s"] <= snap["spans"]["lanes.schedule"]["total_s"]

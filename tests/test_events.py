"""Dynamic cluster events: semantics, engine parity, goldens, replay.

The event subsystem (repro.core.events) must satisfy three contracts:

  1. **Semantics** — preemption checkpoint-restarts with bounded penalty,
     failures fence resources and kill exactly the jobs touching them,
     resize restarts at the new size, defrag only ever moves a job to a
     strictly more local placement.
  2. **Parity** — v2 ≡ the v1 full-recompute reference stays bit-identical
     under any event trace (the events extension of the engine contract).
  3. **Replay** — a fixed ``SimConfig.seed`` yields a bit-identical event
     log and metrics regardless of campaign workers / store mode.
"""

import dataclasses

import pytest

from repro.core import (CLUSTER512, CampaignGrid, ClusterEvent,
                        ClusterSimulator, ClusterSpec, SimConfig,
                        WorkloadSpec, frag_index, generate_events,
                        generate_trace, run_campaign, simulate,
                        validate_events)
from repro.core.events import FAIL_GPU_OWNER
from repro.core.jobs import Job

# the pinned churn scenario: every event kind fires and failures actually
# kill running jobs (see test_churn_golden_trace_jct_snapshot)
CHURN_WL = WorkloadSpec(num_jobs=200, mean_interarrival=120.0, seed=0,
                        max_gpus=256, preempt_fraction=0.15,
                        resize_fraction=0.08, server_mtbf=6000.0,
                        link_mtbf=8000.0, fail_duration=2400.0)


def churn_fixture(num_jobs=80, seed=3, **over):
    wl = dataclasses.replace(CHURN_WL, num_jobs=num_jobs, seed=seed,
                             mean_interarrival=80.0, max_gpus=128, **over)
    jobs = generate_trace(wl)
    return jobs, tuple(generate_events(wl, jobs, CLUSTER512))


# ---------------------------------------------------------------------------
# event-trace generation
# ---------------------------------------------------------------------------

def test_generate_events_deterministic_and_trace_invariant():
    jobs, events = churn_fixture()
    jobs2, events2 = churn_fixture()
    assert events == events2
    # churn fields draw from a separate RNG stream: the job trace is the
    # one a churn-free spec produces (golden JCTs survive churn sweeps)
    plain = generate_trace(WorkloadSpec(num_jobs=80, mean_interarrival=80.0,
                                        seed=3, max_gpus=128))
    assert jobs == plain
    assert all(e.time >= 0 for e in events)
    times = [e.time for e in events]
    assert times == sorted(times)
    kinds = {e.kind for e in events}
    assert {"preempt", "resize"} <= kinds


def test_fail_recover_events_pair_up():
    jobs, events = churn_fixture(server_mtbf=800.0, link_mtbf=900.0,
                                 fail_duration=500.0)
    for fail, recover in (("server-fail", "server-recover"),
                          ("link-fail", "link-recover")):
        fails = [e for e in events if e.kind == fail]
        recs = [e for e in events if e.kind == recover]
        assert len(fails) == len(recs) > 0
        for f in fails:
            assert any(r.time == f.time + 500.0
                       and (r.server, r.leaf, r.spine)
                       == (f.server, f.leaf, f.spine) for r in recs)


def test_event_validation():
    with pytest.raises(ValueError, match="unknown event kind"):
        ClusterEvent(time=0.0, kind="meteor-strike")
    with pytest.raises(ValueError, match="time"):
        ClusterEvent(time=-1.0, kind="preempt", job_id=0)
    with pytest.raises(ValueError, match="out of range"):
        validate_events([ClusterEvent(time=0.0, kind="server-fail",
                                      server=10**6)], CLUSTER512)
    with pytest.raises(ValueError, match="out of range"):
        validate_events([ClusterEvent(time=0.0, kind="link-fail",
                                      leaf=0, spine=99)], CLUSTER512)
    with pytest.raises(TypeError):
        SimConfig(strategy="ecmp", events=("not-an-event",))
    with pytest.raises(ValueError, match="defrag_interval"):
        SimConfig(strategy="ecmp", defrag_interval=-1.0)


# ---------------------------------------------------------------------------
# event semantics (single-job micro-traces through ClusterSimulator so the
# fabric state is inspectable)
# ---------------------------------------------------------------------------

def one_job(num_gpus=8, num_iters=2000, arrival=0.0, job_id=0):
    return Job(job_id, "resnet50", num_gpus, 32, arrival, num_iters)


def test_preempt_requeues_with_restart_penalty():
    base = simulate(CLUSTER512, [one_job()], "best")
    ev = ClusterEvent(time=base.avg_jrt / 2, kind="preempt", job_id=0,
                      restart_iters=100.0)
    churned = simulate(CLUSTER512, [one_job()],
                       config=SimConfig(strategy="best", events=(ev,)))
    assert churned.preemptions == 1
    assert churned.n_finished == 1
    # restart redoes 100 iterations of 2000: ~5% longer, never shorter
    assert churned.avg_jct > base.avg_jct
    assert churned.avg_jct == pytest.approx(base.avg_jct * 1.05, rel=0.01)
    assert churned.goodput < base.goodput
    # JWT measures time-to-FIRST-placement: the restart does not reset it
    assert churned.avg_jwt == base.avg_jwt == 0.0


def test_preempt_penalty_clamped_to_original_work():
    base = simulate(CLUSTER512, [one_job(num_iters=100)], "best")
    ev = ClusterEvent(time=base.avg_jrt / 2, kind="preempt", job_id=0,
                      restart_iters=10**9)     # absurd penalty
    churned = simulate(CLUSTER512, [one_job(num_iters=100)],
                       config=SimConfig(strategy="best", events=(ev,)))
    # a job never owes more work than it started with: worst case it
    # restarts from scratch at t=ev.time
    assert churned.avg_jct <= ev.time + base.avg_jct + 1e-9
    assert churned.n_finished == 1


def test_preempt_of_unstarted_job_is_noop():
    rep = simulate(CLUSTER512, [one_job()],
                   config=SimConfig(strategy="best", events=(
                       ClusterEvent(time=0.0, kind="preempt", job_id=77),)))
    assert rep.preemptions == 0
    assert rep.event_log[0][4] == 0          # n_affected


def test_server_fail_kills_fences_and_recovers():
    # job 0 lands on server 0 (best-fit into an empty cluster); the failure
    # kills it and fences the server, recovery frees it again
    events = (ClusterEvent(time=50.0, kind="server-fail", server=0,
                           restart_iters=0.0),
              ClusterEvent(time=60.0, kind="server-recover", server=0))
    sim = ClusterSimulator(CLUSTER512,
                           config=SimConfig(strategy="best", events=events))
    rep = sim.run([one_job()])
    assert rep.failures == 1
    assert rep.n_finished == 1
    # the restarted placement could not use server 0 while it was down
    assert sim.state.gpu_owner == {}         # no leaked GPUs or fences
    assert sim.state.link_owner == {}
    log_kinds = [e[1] for e in rep.event_log]
    assert log_kinds == ["server-fail", "server-recover"]
    assert rep.event_log[0][4] == 1          # one job killed


def test_server_fail_fence_blocks_placement_until_recover():
    spec = ClusterSpec(num_leafs=1, num_spines=2, gpus_per_leaf=8,
                       gpus_per_server=8)    # one server total
    events = (ClusterEvent(time=10.0, kind="server-fail", server=0),
              ClusterEvent(time=500.0, kind="server-recover", server=0))
    sim = ClusterSimulator(spec,
                           config=SimConfig(strategy="best", events=events))
    job = one_job(num_gpus=8, num_iters=100, arrival=20.0)
    rep = sim.run([job])
    assert rep.n_finished == 1
    assert job.start_time >= 500.0           # waited out the outage
    assert rep.frag_gpu >= 1                 # blocked attempts recorded


def test_link_fail_kills_reserving_vclos_job():
    # 64 GPUs on CLUSTER512 exceed one leaf (32): vclos stage 2 reserves a
    # (2 leafs × 32 spines) sub-Clos including link (leaf 0, spine 0) —
    # killing that link must checkpoint-kill the job
    events = (ClusterEvent(time=50.0, kind="link-fail", leaf=0, spine=0,
                           restart_iters=0.0),
              ClusterEvent(time=60.0, kind="link-recover", leaf=0, spine=0))
    sim = ClusterSimulator(CLUSTER512,
                           config=SimConfig(strategy="vclos", events=events))
    rep = sim.run([one_job(num_gpus=64)])
    assert rep.failures == 1
    assert rep.n_finished == 1
    assert sim.state.link_owner == {}        # reservations and fence gone
    assert sim.state.gpu_owner == {}


def test_link_fail_kills_flow_users_under_ecmp():
    # under ECMP a 64-GPU ring on leafs 0-1 hashes its two inter-leaf flows
    # onto some (leaf, spine) links; failing every pair on those leafs is
    # guaranteed to catch it through the engine-maintained link→jobs index
    # (the restarted job may be caught again by a *later* event covering
    # its re-hashed route, so the kill count is ≥ 1, not exactly 1)
    events = tuple(ClusterEvent(time=50.0, kind="link-fail", leaf=lf,
                                spine=sp)
                   for lf in (0, 1) for sp in range(CLUSTER512.num_spines))
    sim = ClusterSimulator(CLUSTER512, config=SimConfig(strategy="ecmp",
                                                        events=events))
    rep = sim.run([one_job(num_gpus=64)])
    assert rep.failures >= 1
    assert rep.n_finished == 1


def test_resize_restarts_at_new_size():
    base = simulate(CLUSTER512, [one_job(num_gpus=8, num_iters=5000)], "best")
    ev = ClusterEvent(time=base.avg_jrt / 2, kind="resize", job_id=0,
                      new_gpus=16, restart_iters=0.0)
    sim = ClusterSimulator(CLUSTER512,
                           config=SimConfig(strategy="best", events=(ev,)))
    job = one_job(num_gpus=8, num_iters=5000)
    rep = sim.run([job])
    assert rep.resizes == 1
    assert job.num_gpus == 16
    assert rep.n_finished == 1


def test_resize_of_queued_job_applies_before_start():
    spec = ClusterSpec(num_leafs=1, num_spines=2, gpus_per_leaf=8,
                       gpus_per_server=8)
    blocker = one_job(num_gpus=8, num_iters=2000, job_id=0)
    queued = one_job(num_gpus=8, num_iters=100, arrival=1.0, job_id=1)
    # shrink the queued job while it waits; it must start at the new size
    ev = ClusterEvent(time=2.0, kind="resize", job_id=1, new_gpus=4)
    sim = ClusterSimulator(spec, config=SimConfig(strategy="best",
                                                  events=(ev,)))
    rep = sim.run([blocker, queued])
    assert rep.resizes == 1
    assert queued.num_gpus == 4
    assert rep.n_finished == 2


# ---------------------------------------------------------------------------
# migration defragmentation
# ---------------------------------------------------------------------------

def test_defrag_migrates_to_more_local_placement():
    # 2 leafs × 4 servers × 4 GPUs.  Two 12-GPU jobs pin 3 servers in each
    # leaf; an 8-GPU job then has to span both leafs.  Once the big jobs
    # finish, the defrag tick must migrate it under a single leaf.
    spec = ClusterSpec(num_leafs=2, num_spines=4, gpus_per_leaf=16,
                       gpus_per_server=4)
    jobs = [Job(0, "resnet50", 12, 32, 0.0, 10),
            Job(1, "resnet50", 12, 32, 0.0, 10),
            Job(2, "resnet50", 8, 32, 0.0, 50000)]
    cfg = SimConfig(strategy="best", defrag_interval=200.0,
                    migration_iters=5.0)
    sim = ClusterSimulator(spec, config=cfg)
    rep = sim.run(jobs)
    assert rep.migrations == 1
    assert rep.migration_bytes > 0
    assert rep.n_finished == 3
    # fragmentation index drops across the migration tick
    ticks = [e for e in rep.event_log if e[1] == "defrag"]
    assert ticks and ticks[0][2] == 1        # one job moved on first tick
    assert sim.state.gpu_owner == {}


def test_defrag_noop_for_non_migratable_strategy_but_samples_frag():
    jobs = generate_trace(WorkloadSpec(num_jobs=30, mean_interarrival=100.0,
                                       seed=2, max_gpus=64))
    rep = simulate(CLUSTER512, jobs,
                   config=SimConfig(strategy="ecmp", defrag_interval=2000.0))
    assert rep.migrations == 0
    assert rep.frag_series                    # ticks still sample the index
    assert all(0.0 <= f <= 1.0 for _, f in rep.frag_series)
    base = simulate(CLUSTER512, jobs, "ecmp")
    assert rep.jcts == base.jcts              # sampling never perturbs JCTs


def test_defrag_never_degrades_locality_for_best():
    jobs, events = churn_fixture(num_jobs=40)
    on = simulate(CLUSTER512, jobs,
                  config=SimConfig(strategy="best", events=events,
                                   defrag_interval=3000.0))
    assert on.n_finished == 40
    # JCT with defrag should not collapse (weak sanity: all jobs finish,
    # migrations bounded by job count × ticks)
    assert on.migrations <= 40 * (len(on.event_log) + 1)


# ---------------------------------------------------------------------------
# engine parity under churn (the tentpole acceptance)
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("strategy,seed", [
    ("ecmp", 3), ("sr", 3), ("best", 3), ("vclos", 3),
    ("ecmp", 8), ("sr", 14)])
def test_v2_matches_v1_with_events(strategy, seed):
    jobs, events = churn_fixture(seed=seed)
    cfg = SimConfig(strategy=strategy, events=events, defrag_interval=4000.0)
    v1 = simulate(CLUSTER512, jobs, config=cfg, engine="v1")
    v2 = simulate(CLUSTER512, jobs, config=cfg, engine="v2")
    assert v1.n_finished == v2.n_finished
    assert v1.jcts == v2.jcts
    assert v1.jwts == v2.jwts
    assert v1.slowdowns == v2.slowdowns
    assert v1.event_log == v2.event_log
    assert v1.frag_series == v2.frag_series
    assert (v1.preemptions, v1.failures, v1.resizes, v1.migrations) == \
        (v2.preemptions, v2.failures, v2.resizes, v2.migrations)


@pytest.mark.slow
@pytest.mark.parametrize("strategy", ["balanced", "ocs-relax",
                                      "contention-affinity"])
def test_v2_matches_v1_with_events_extended(strategy):
    jobs, events = churn_fixture()
    cfg = SimConfig(strategy=strategy, events=events,
                    defrag_interval=4000.0)
    v1 = simulate(CLUSTER512, jobs, config=cfg, engine="v1")
    v2 = simulate(CLUSTER512, jobs, config=cfg, engine="v2")
    assert v1.jcts == v2.jcts
    assert v1.jwts == v2.jwts
    assert v1.event_log == v2.event_log


def test_churn_golden_trace_jct_snapshot():
    """Golden JCTs for the pinned churn scenario (update consciously, like
    the churn-free golden in test_campaign.py)."""
    jobs = generate_trace(CHURN_WL)
    events = tuple(generate_events(CHURN_WL, jobs, CLUSTER512))
    kinds = {e.kind for e in events}
    assert kinds == {"preempt", "resize", "server-fail", "server-recover",
                     "link-fail", "link-recover"}
    golden = {"ecmp": 12099.6, "sr": 3937.7, "best": 2887.6}
    for strat, want in golden.items():
        cfg = SimConfig(strategy=strat, events=events,
                        defrag_interval=10000.0)
        rep = simulate(CLUSTER512, jobs, config=cfg)
        assert round(rep.avg_jct, 1) == pytest.approx(want), strat
        assert rep.n_finished == 200


def test_event_clock_monotone_and_no_resource_leaks():
    jobs, events = churn_fixture(server_mtbf=2000.0, link_mtbf=2000.0,
                                 fail_duration=800.0)
    sim = ClusterSimulator(CLUSTER512,
                           config=SimConfig(strategy="ecmp", events=events,
                                            defrag_interval=3000.0))
    rep = sim.run(list(jobs))
    times = [e[0] for e in rep.event_log]
    assert times == sorted(times)
    assert rep.n_finished == len(jobs)       # failures recover: no job lost
    # fences released, reservations returned, every GPU freed
    leaked = {g: o for g, o in sim.state.gpu_owner.items()
              if o != FAIL_GPU_OWNER}
    assert leaked == {}
    assert all(o == FAIL_GPU_OWNER for o in sim.state.gpu_owner.values())


# ---------------------------------------------------------------------------
# deterministic replay across campaign execution modes
# ---------------------------------------------------------------------------

@pytest.mark.slow
def test_campaign_churn_replay_workers_and_stream():
    """Identical seeds ⇒ bit-identical event log and metrics whether cells
    run serially, across 4 workers, or with streaming aggregation."""
    wl = dataclasses.replace(CHURN_WL, num_jobs=40, max_gpus=64,
                             server_mtbf=3000.0, link_mtbf=4000.0)
    grid = CampaignGrid(strategies=("ecmp", "best"), loads=(150.0,),
                        seeds=(0, 1))
    cfg = SimConfig(strategy="ecmp", defrag_interval=3000.0)
    ser = run_campaign(CLUSTER512, grid, workload=wl, config=cfg)
    par = run_campaign(CLUSTER512, grid, workload=wl, config=cfg, workers=4)
    stream = run_campaign(CLUSTER512, grid, workload=wl, config=cfg,
                          store="stream")
    for a, b in zip(ser.cells, par.cells):
        assert a.report.event_log == b.report.event_log
        assert a.report.jcts == b.report.jcts
        assert a.report.jwts == b.report.jwts
        assert a.report.frag_series == b.report.frag_series
    for a, c in zip(ser.cells, stream.cells):
        assert a.report.event_log == c.report.event_log   # log stays exact
        assert a.report.avg_jct == c.report.avg_jct
    # churn actually fired and the new aggregate columns surface it
    rows = ser.aggregate()
    assert any(r["preemptions"] + r["failures"] + r["resizes"] > 0
               for r in rows)
    for r in rows:
        for col in ("preemptions", "failures", "resizes", "migrations",
                    "migration_bytes", "goodput_mean", "frag_index_mean"):
            assert col in r


def test_campaign_events_identical_across_strategies_per_cell():
    """Every strategy cell of one (load, seed) slice replays the same
    generated event sequence (paired churn ablation)."""
    wl = dataclasses.replace(CHURN_WL, num_jobs=30, max_gpus=64)
    grid = CampaignGrid(strategies=("ecmp", "sr"), loads=(150.0,),
                        seeds=(0,))
    res = run_campaign(CLUSTER512, grid, workload=wl)
    logs = {c.strategy: c.report.event_log for c in res.cells}
    # same *injected* events: the (time, kind) schedule matches even though
    # per-strategy n_affected may differ
    assert [(t, k) for t, k, *_ in logs["ecmp"]] == \
        [(t, k) for t, k, *_ in logs["sr"]]


@pytest.mark.parametrize("engine", ["v1", "v2"])
def test_defrag_clock_terminates_on_dead_ended_run(engine):
    """An unpaired failure can leave a queued job permanently unplaceable;
    the defrag clock must not keep such a run alive forever — once nothing
    runs and no events/arrivals remain, the loop ends (job unfinished),
    exactly like the pre-events engines did."""
    spec = ClusterSpec(num_leafs=1, num_spines=2, gpus_per_leaf=8,
                       gpus_per_server=8)
    cfg = SimConfig(strategy="best", engine=engine, defrag_interval=100.0,
                    events=(ClusterEvent(time=1.0, kind="server-fail",
                                         server=0),))
    sim = ClusterSimulator(spec, config=cfg)
    rep = sim.run([one_job(num_gpus=8, num_iters=1000)])
    assert rep.n_finished == 0               # returned instead of hanging
    assert rep.failures == 1                 # killed at t=1, never re-placed


def test_frag_index_bounds():
    from repro.core.topology import FabricState
    spec = ClusterSpec(num_leafs=2, num_spines=4, gpus_per_leaf=16,
                       gpus_per_server=4)
    st = FabricState(spec)
    assert frag_index(st) == 0.0             # all capacity whole under a leaf
    st.allocate_gpus(0, list(range(32)))
    assert frag_index(st) == 0.0             # no idle capacity at all
    st.release_job(0)
    # occupy one GPU per server: idle capacity exists, zero whole servers
    st.allocate_gpus(1, [0, 4, 8, 12, 16, 20, 24, 28])
    assert frag_index(st) == 1.0

"""Online scheduler service (repro.service) + what-if digital twin.

The contracts under test (ISSUE 8, docs/service.md):

* **Differential replay oracle** — a trace fed through the service event
  loop (submit-at-a-time, churn via ``ingest``) yields placements *and*
  the full metrics report bit-identical to offline ``simulate()`` on the
  same trace, per strategy (including an isolated one).
* **Crash-restart** — a daemon killed mid-trace and reopened on its event
  log replays to the exact pre-crash state; a torn final record (never
  acknowledged) is dropped, not corrupting.
* **Twin memoisation** — what-if answers are cached per fabric version;
  any observable mutation (submit, event, completion, clock movement)
  invalidates them.
* **Admission** — per-tenant GPU quotas over running+queued demand, and
  cluster-infeasibility, on both the dry-run and the submit path.
* **Protocol** — the JSON-lines TCP server round-trips every op and shuts
  down cleanly.
"""

import copy
import json
import math

import pytest

from repro.core import (CLUSTER512, ClusterEvent, ClusterSimulator,
                        JournalMismatch, SimConfig, WorkloadSpec,
                        generate_events, generate_trace)
from repro.service import (DigitalTwin, LiveCluster, RecordingSimulator,
                           SchedClient, SchedulerService, ServerThread,
                           ServiceError, job_from_json, job_to_json,
                           replay_trace)

CFG = dict(scheduler="fifo", seed=0, engine="v2")


def fresh(jobs):
    """Fresh copies with runtime state reset — both sides of the oracle
    must start from pure input jobs, as ``simulate()`` does."""
    out = [copy.copy(j) for j in jobs]
    for j in out:
        j.start_time = j.finish_time = j.remaining_iters = None
    return out


def trace(n=60, seed=3, **kw):
    return generate_trace(WorkloadSpec(num_jobs=n, mean_interarrival=60.0,
                                       seed=seed, **kw))


def oracle(strategy, jobs, events=()):
    """(service report, service placements) vs (offline report, offline
    placements) on identical inputs."""
    cfg = SimConfig(strategy=strategy, **CFG)
    live = LiveCluster(CLUSTER512, cfg)
    rep_live = replay_trace(live, fresh(jobs), events=events)
    off = RecordingSimulator(
        CLUSTER512, config=cfg.with_overrides(events=tuple(events)))
    rep_off = off.run(fresh(jobs))
    return rep_live, live.sim.placements, rep_off, off.placements


# ---------------------------------------------------------------------------
# differential replay oracle
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("strategy", ["ecmp", "sr", "vclos"])
def test_oracle_replay_identical(strategy):
    # vclos is the isolated representative (the acceptance bar requires one)
    rep_live, pl_live, rep_off, pl_off = oracle(strategy, trace())
    assert rep_live.to_journal() == rep_off.to_journal()
    assert pl_live == pl_off
    assert len(pl_off) >= 60          # every job placed at least once


@pytest.mark.parametrize("strategy", ["ecmp", "sr"])
def test_oracle_with_churn_events(strategy):
    jobs = trace(50, seed=5)
    wl = WorkloadSpec(num_jobs=50, mean_interarrival=60.0, seed=5,
                      preempt_fraction=0.1, resize_fraction=0.1,
                      server_mtbf=30000.0)
    events = generate_events(wl, jobs, CLUSTER512)
    assert events, "churn spec produced no events — test is vacuous"
    rep_live, pl_live, rep_off, pl_off = oracle(strategy, jobs, events)
    assert rep_live.to_journal() == rep_off.to_journal()
    assert pl_live == pl_off
    assert rep_off.preemptions + rep_off.failures + rep_off.resizes > 0


def test_step_surface_matches_simulate():
    """The v2 step the daemon uses, driven by hand — ``arrive`` per job,
    completions through ``next_finish`` / ``finish_next`` — gives the
    offline run's completion order and JCTs."""
    cfg = SimConfig(strategy="ecmp", **CFG)
    jobs = trace(40, seed=9)
    off_jobs = fresh(jobs)
    rep_off = ClusterSimulator(CLUSTER512, config=cfg).run(off_jobs)
    sim = ClusterSimulator(CLUSTER512, config=cfg)
    mine = sorted(fresh(jobs), key=lambda j: (j.arrival, j.job_id))
    done = []
    for job in mine:
        while sim.next_finish() <= job.arrival:
            done.append(sim.finish_next())
        sim.now = job.arrival
        sim.arrive(job)
    while sim.next_finish() < math.inf:
        done.append(sim.finish_next())
    off_done = sorted((j.finish_time, j.job_id) for j in off_jobs)
    assert [(t, jid) for jid, t in done] == off_done
    assert len(done) == len(jobs)
    rep = sim.build_report(mine)
    assert rep.jcts == rep_off.jcts
    assert rep.jwts == rep_off.jwts


def test_report_counts_denied_free(tmp_path):
    # report() covers admitted jobs only; denials never pollute metrics
    live = LiveCluster(CLUSTER512, SimConfig(strategy="sr", **CFG),
                       quotas={"t": 8})
    live.submit(live.new_job("resnet50", 8, 200), tenant="t")
    denied = live.submit(live.new_job("resnet50", 8, 200), tenant="t")
    assert not denied["admitted"]
    live.drain_all()
    rep = live.report()
    assert rep.n_finished == 1 and live.denied == 1


# ---------------------------------------------------------------------------
# durable event log: crash-restart, torn tail, schema guard
# ---------------------------------------------------------------------------

def submit_stream(live, jobs, upto=None):
    for job in fresh(jobs)[:upto]:
        live.submit(job)


def test_crash_restart_replays_to_identical_state(tmp_path):
    jobs = sorted(trace(40, seed=7), key=lambda j: j.arrival)
    cfg = SimConfig(strategy="sr", **CFG)
    path = str(tmp_path / "schedd.log")

    # uninterrupted reference
    ref = LiveCluster(CLUSTER512, cfg)
    submit_stream(ref, jobs)
    ref.drain_all()

    # crash: first half ingested, process dies without close()
    live = LiveCluster.open(path, CLUSTER512, cfg, fsync=False)
    submit_stream(live, jobs, upto=20)
    del live                                    # no close(): a real crash

    # restart: replay + the rest of the trace
    live2 = LiveCluster.open(path, CLUSTER512, cfg, fsync=False)
    assert live2.ingested == 20
    for job in fresh(jobs)[20:]:
        live2.submit(job)
    live2.drain_all()
    assert live2.report().to_journal() == ref.report().to_journal()
    assert live2.sim.placements == ref.sim.placements
    assert live2.version == ref.version
    live2.close()


def test_crash_restart_torn_tail_dropped(tmp_path):
    jobs = sorted(trace(10, seed=1), key=lambda j: j.arrival)
    cfg = SimConfig(strategy="ecmp", **CFG)
    path = str(tmp_path / "schedd.log")
    live = LiveCluster.open(path, CLUSTER512, cfg, fsync=False)
    submit_stream(live, jobs)
    # a submit record the crash cut mid-write (never acknowledged)
    with open(path, "a") as f:
        f.write('{"kind": "submit", "tenant": "defa')
    live2 = LiveCluster.open(path, CLUSTER512, cfg, fsync=False)
    assert live2.ingested == 10                 # torn record dropped
    with open(path) as f:
        assert all(json.loads(ln) for ln in f)  # file healed: all lines parse
    live2.close()


def test_resume_refuses_different_schema(tmp_path):
    path = str(tmp_path / "schedd.log")
    LiveCluster.open(path, CLUSTER512, SimConfig(strategy="sr", **CFG),
                     fsync=False).close()
    with pytest.raises(JournalMismatch, match="strategy"):
        LiveCluster.open(path, CLUSTER512,
                         SimConfig(strategy="ecmp", **CFG), fsync=False)
    with pytest.raises(JournalMismatch, match="quotas"):
        LiveCluster.open(path, CLUSTER512, SimConfig(strategy="sr", **CFG),
                         quotas={"x": 8}, fsync=False)


def test_denied_submits_replay_to_denials(tmp_path):
    # the log is a pure input stream: denials are logged and re-derived
    path = str(tmp_path / "schedd.log")
    cfg = SimConfig(strategy="sr", **CFG)
    live = LiveCluster.open(path, CLUSTER512, cfg, quotas={"t": 16},
                            fsync=False)
    live.submit(live.new_job("resnet50", 16, 500), tenant="t")
    assert not live.submit(live.new_job("bert", 8, 500),
                           tenant="t")["admitted"]
    live.close()
    live2 = LiveCluster.open(path, CLUSTER512, cfg, quotas={"t": 16})
    assert live2.denied == 1 and len(live2.jobs) == 1
    assert live2.version == live.version
    live2.close()


# ---------------------------------------------------------------------------
# LiveCluster ingestion contracts
# ---------------------------------------------------------------------------

def test_monotonicity_enforced():
    live = LiveCluster(CLUSTER512, SimConfig(strategy="sr", **CFG))
    live.advance(100.0)
    with pytest.raises(ValueError, match="monotonicity"):
        live.submit(live.new_job("resnet50", 8, 100, arrival=50.0))
    with pytest.raises(ValueError, match="monotonicity"):
        live.ingest(ClusterEvent(time=99.0, kind="preempt", job_id=0))
    with pytest.raises(ValueError, match="monotonicity"):
        live.advance(10.0)


def test_rejects_offline_config_knobs():
    ev = ClusterEvent(time=1.0, kind="preempt", job_id=0)
    with pytest.raises(ValueError, match="ingest"):
        LiveCluster(CLUSTER512, SimConfig(strategy="sr", events=(ev,)))
    with pytest.raises(ValueError, match="defrag"):
        LiveCluster(CLUSTER512, SimConfig(strategy="sr", defrag_interval=50))


def test_rejects_probe_range_and_duplicate_ids():
    from repro.service.state import PROBE_ID_BASE
    live = LiveCluster(CLUSTER512, SimConfig(strategy="sr", **CFG))
    job = live.new_job("resnet50", 8, 100)
    live.submit(job)
    with pytest.raises(ValueError, match="duplicate"):
        live.submit(copy.copy(job))
    bad = live.new_job("resnet50", 8, 100)
    bad.job_id = PROBE_ID_BASE + 5
    with pytest.raises(ValueError, match="probe"):
        live.submit(bad)


def test_unknown_model_rejected_at_materialisation():
    live = LiveCluster(CLUSTER512, SimConfig(strategy="sr", **CFG))
    with pytest.raises(ValueError, match="unknown model"):
        live.new_job("gpt17", 8, 100)


def test_job_json_roundtrip():
    job = trace(1, seed=9)[0]
    assert job_from_json(job_to_json(job)) == job
    # and through actual JSON text, as the log stores it
    assert job_from_json(json.loads(json.dumps(job_to_json(job)))) == job


def test_event_json_roundtrip():
    ev = ClusterEvent(time=12.5, kind="resize", job_id=3, new_gpus=32,
                      restart_iters=80.0)
    assert ClusterEvent.from_json(json.loads(json.dumps(ev.to_json()))) == ev


def test_admission_quota_and_feasibility():
    live = LiveCluster(CLUSTER512, SimConfig(strategy="sr", **CFG),
                       quotas={"teamA": 64})
    assert live.admission("default", 512) == (True, "ok")
    ok, reason = live.admission("default", 513)
    assert not ok and "cluster" in reason
    assert live.admission("teamA", 64)[0]
    live.submit(live.new_job("resnet50", 32, 1000), tenant="teamA")
    ok, reason = live.admission("teamA", 64)
    assert not ok and "quota" in reason
    # queued demand counts too: fill the cluster so the next job queues
    assert live.admission("teamA", 32)[0]


# ---------------------------------------------------------------------------
# digital twin
# ---------------------------------------------------------------------------

def twin_fixture():
    live = LiveCluster(CLUSTER512, SimConfig(strategy="sr", **CFG))
    for job in fresh(trace(12, seed=2)):
        live.submit(job)
    return live, DigitalTwin(live)


def test_twin_memo_hit_same_version():
    live, twin = twin_fixture()
    a = twin.whatif("moe", 32, 2000, strategies=["sr", "ecmp", "vclos"])
    assert not a["cached"] and twin.misses == 1
    # 1 shared baseline fork + 1 evaluate fork per candidate strategy
    assert twin.forks == 4
    b = twin.whatif("moe", 32, 2000, strategies=["sr", "ecmp", "vclos"])
    assert b["cached"] and twin.hits == 1 and twin.forks == 4
    assert {k: v for k, v in a.items() if k != "cached"} \
        == {k: v for k, v in b.items() if k != "cached"}


def test_twin_invalidated_by_version_bump():
    live, twin = twin_fixture()
    a = twin.whatif("moe", 32, 2000)
    v0 = live.version
    live.submit(live.new_job("resnet50", 16, 500))      # bumps version
    assert live.version > v0
    b = twin.whatif("moe", 32, 2000)
    assert not b["cached"] and twin.misses == 2
    assert b["fabric_version"] != a["fabric_version"]


def test_twin_invalidated_by_pure_clock_advance():
    # no completions, just clock movement: predictions are in absolute
    # time, so even this must recompute
    live, twin = twin_fixture()
    twin.whatif("moe", 32, 2000)
    live.advance(live.now + 1.0)
    assert not twin.whatif("moe", 32, 2000)["cached"]


def test_twin_fork_never_leaks_into_live():
    live, twin = twin_fixture()
    before = (live.version, live.now, len(live.sim.running),
              len(live.sim.queue), live.sim.state.num_free_gpus())
    twin.whatif("dlrm", 64, 3000, strategies=["sr", "ecmp"])
    after = (live.version, live.now, len(live.sim.running),
             len(live.sim.queue), live.sim.state.num_free_gpus())
    assert before == after
    assert all(jid < 2_000_000_000 for jid in live.sim.running)


def test_twin_prediction_matches_actual_submit():
    # on a quiet cluster the twin's JCT must be exactly what really
    # happens when the job is then submitted for real
    live = LiveCluster(CLUSTER512, SimConfig(strategy="sr", **CFG))
    twin = DigitalTwin(live)
    pred = twin.whatif("resnet50", 16, 4000)["strategies"]["sr"]
    assert pred["placed_now"] and pred["predicted_wait"] == 0.0
    r = live.submit(live.new_job("resnet50", 16, 4000))
    assert r["placed"] and r["gpus"] == pred["gpus"]
    (jid, t_fin), = live.drain_all()
    assert t_fin == pytest.approx(pred["predicted_jct"], abs=1e-9)


def test_twin_unsupported_strategy_reported_not_raised():
    live, twin = twin_fixture()
    out = twin.whatif("moe", 32, 2000, strategies=["ocs-vclos"])
    pred = out["strategies"]["ocs-vclos"]
    assert pred["supported"] is False and "OCS" in pred["reason"]


# ---------------------------------------------------------------------------
# TCP protocol end-to-end
# ---------------------------------------------------------------------------

def test_server_end_to_end(tmp_path):
    live = LiveCluster.open(str(tmp_path / "log"), CLUSTER512,
                            SimConfig(strategy="sr", **CFG),
                            quotas={"teamA": 64}, fsync=False)
    server = ServerThread(SchedulerService(live))
    host, port = server.start()
    with SchedClient(host, port) as c:
        assert c.stats()["version"] == 0
        r = c.submit("resnet50", 16, 4000, tenant="teamA")
        assert r["placed"] and len(r["gpus"]) == 16
        assert not c.admit("teamA", 64)["admit"]
        w = c.whatif("moe", 32, 2000, strategies=["sr", "ecmp"])
        assert w["strategies"]["sr"]["supported"]
        assert c.whatif("moe", 32, 2000,
                        strategies=["sr", "ecmp"])["cached"]
        p = c.place("bert", 8, 100)
        assert p["placed"]
        ev = c.event({"time": 50.0, "kind": "preempt", "job_id": r["job_id"],
                      "restart_iters": 10.0})
        assert ev["kind"] == "preempt" and ev["n_affected"] == 1
        done = c.drain()
        assert done["completed"], "preempted job never finished"
        with pytest.raises(ServiceError, match="unknown op"):
            c.call("frobnicate")
        with pytest.raises(ServiceError, match="monotonicity"):
            c.advance(0.0)
        stats = c.stats()
        assert stats["errors"] == 2 and stats["requests"] > 5
        c.shutdown()
    server.join()


def test_server_protocol_malformed_json_keeps_session(tmp_path):
    live = LiveCluster(CLUSTER512, SimConfig(strategy="sr", **CFG))
    server = ServerThread(SchedulerService(live))
    host, port = server.start()
    with SchedClient(host, port) as c:
        c._fh.write(b"this is not json\n")
        c._fh.flush()
        resp = json.loads(c._fh.readline())
        assert not resp["ok"] and "bad JSON" in resp["error"]
        assert c.stats()["version"] == 0     # session still alive
        c.shutdown()
    server.join()

"""In-program spans and counters (``repro.obs``): nothing recorded while
off, schedules unchanged while on, exact self time, counts that agree with
outside wrappers, the spans on the profiler's host plane, and compiles."""

import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import obs
from repro.core import batched, ocs
from repro.core.batched import _BatchedEngine
from repro.core.campaign import CampaignGrid, run_campaign
from repro.core.placement import PlacementFailure
from repro.core.strategies import get_strategy
from repro.core.topology import CLUSTER512, CLUSTER512_OCS
from repro.core.workloads import WorkloadSpec, generate_trace

LANE_SPANS = ("campaign.run", "lanes.prepare", "lanes.run",
              "lanes.schedule", "lanes.entries", "lanes.rate",
              "lanes.report", "rate.solve")
OCS_SPANS = ("campaign.run", "place", "ocs.findclos", "ocs.rewire")


@pytest.fixture(autouse=True)
def _recording_off():
    obs.disable()
    yield
    obs.disable()


def _trace(num_jobs, max_gpus, seed=3):
    return generate_trace(WorkloadSpec(num_jobs=num_jobs,
                                       mean_interarrival=60.0,
                                       max_gpus=max_gpus, seed=seed))


def _lanes():
    grid = CampaignGrid(strategies=("best", "sr", "ecmp"),
                        schedulers=("fifo",), loads=(60.0,), seeds=(0, 1))
    return run_campaign(CLUSTER512, grid, trace=_trace(60, 256),
                        engine="batched")


def _ocs(num_jobs=60):
    grid = CampaignGrid(strategies=("ocs-vclos",), schedulers=("fifo",),
                        loads=(60.0,), seeds=(0,))
    return run_campaign(CLUSTER512, grid, trace=_trace(num_jobs, 256, seed=5),
                        engine="batched", ocs_spec=CLUSTER512_OCS)


def _schedules(res):
    return [(c.strategy, c.seed, list(c.report.jwts), list(c.report.jcts),
             c.report.frag_gpu, c.report.frag_network) for c in res.cells]


def test_importing_obs_does_not_import_jax():
    code = ("import sys, repro.obs; "
            "sys.exit('jax' in sys.modules)")
    out = subprocess.run([sys.executable, "-c", code], capture_output=True,
                         text=True, timeout=60)
    assert out.returncode == 0, out.stderr


def test_off_a_span_does_nothing_and_the_store_stays_empty():
    obs.enable()
    obs.disable()
    assert obs.span("a") is obs.span("b")
    with obs.span("a"):
        obs.tag("fail")
        obs.count("c", 3)
    _lanes()
    assert obs.snapshot() == {"spans": {}, "counters": {obs.COMPILES: 0}}


@pytest.mark.parametrize("run", [_lanes, _ocs], ids=["lanes", "ocs-vclos"])
def test_schedules_are_bit_identical_with_recording_on(run):
    off = _schedules(run())
    obs.enable()
    on = _schedules(run())
    names = obs.snapshot()["spans"]
    obs.disable()
    assert on == off
    expected = LANE_SPANS if run is _lanes else OCS_SPANS
    assert set(expected) <= set(names)


def test_self_time_is_exact_on_a_synthetic_nest(monkeypatch):
    ticks = iter([0, 10, 12, 20, 30, 40, 70, 100, 200, 300])
    monkeypatch.setattr(obs, "_now", lambda: next(ticks))
    obs.enable()
    with obs.span("a"):
        with obs.span("b"):
            with obs.span("c"):
                pass
        with obs.span("b"):
            obs.tag("fail")
    with obs.span("open"):
        snap = obs.snapshot()
    s = snap["spans"]
    assert set(s) == {"a", "b", "c"}
    assert s["a"] == {"count": 1, "total_s": 100 / 1e9,
                      "self_s": 50 / 1e9, "tags": {}}
    assert s["b"]["count"] == 2
    assert s["b"]["total_s"] == 50 / 1e9
    assert s["b"]["self_s"] == 42 / 1e9
    assert s["b"]["tags"] == {"fail": {"count": 1, "total_s": 30 / 1e9,
                                       "self_s": 30 / 1e9}}
    assert s["c"] == {"count": 1, "total_s": 8 / 1e9, "self_s": 8 / 1e9,
                      "tags": {}}


def _counting(monkeypatch, owner, attr, calls, key, fails=None):
    orig = getattr(owner, attr)

    def wrapped(*a, **k):
        calls[key] = calls.get(key, 0) + 1
        out = orig(*a, **k)
        if fails is not None and isinstance(out, PlacementFailure):
            fails[key] = fails.get(key, 0) + 1
        return out

    monkeypatch.setattr(owner, attr, wrapped)


def test_lane_counts_agree_with_outside_wrappers(monkeypatch):
    calls = {}
    _counting(monkeypatch, batched, "phase_worst_loads", calls, "rate")
    _counting(monkeypatch, _BatchedEngine, "_schedule_lanes", calls, "sched")
    _counting(monkeypatch, _BatchedEngine, "_recompute", calls, "recompute")
    obs.enable()
    _lanes()
    snap = obs.snapshot()
    st = obs._store
    obs.disable()
    s = snap["spans"]
    assert calls["rate"] > 0 and calls["sched"] > 0
    assert s["rate.solve"]["count"] == calls["rate"]
    assert s["lanes.schedule"]["count"] == calls["sched"]
    assert s["lanes.rate"]["count"] == calls["recompute"] == calls["sched"]
    assert s["campaign.run"]["count"] == 1
    assert s["lanes.run"]["count"] == s["lanes.report"]["count"] == 1
    assert s["lanes.prepare"]["count"] == 2
    # every span of the call descends from its root through parent links
    names, parents = st.rec[obs._NAME::obs._FIELDS], \
        st.rec[obs._PARENT::obs._FIELDS]
    assert st.names[names[0]] == "campaign.run" and parents[0] == -1
    assert all(p >= 0 for p in parents[1:])
    assert s["lanes.run"]["self_s"] == pytest.approx(
        s["lanes.run"]["total_s"] - s["lanes.schedule"]["total_s"]
        - s["lanes.rate"]["total_s"], abs=1e-9)


def test_ocs_counts_agree_with_outside_wrappers(monkeypatch):
    calls, fails = {}, {}
    strat = type(get_strategy("ocs-vclos"))
    _counting(monkeypatch, strat, "place", calls, "place", fails)
    obs.enable()
    _ocs()
    snap = obs.snapshot()
    obs.disable()
    s, c = snap["spans"], snap["counters"]
    assert calls["place"] > 0 and fails.get("place", 0) > 0
    assert s["place"]["count"] == calls["place"]
    assert s["place"]["tags"]["fail"]["count"] == fails["place"]
    assert 0 < s["ocs.findclos"]["count"] <= calls["place"]
    assert c["ocs.budget"] == s["ocs.findclos"]["count"]
    assert c["ocs.candidates"] >= c["ocs.budget"]
    assert "rate.solve" not in s       # ocs-vclos is isolated


def test_rewire_counts_agree_with_outside_wrappers(monkeypatch):
    """``ocs.channels`` counts add_channel calls, ``ocs.rewire`` one span
    per ``ensure``, opened inside the search or stage 2's ``place``."""
    calls = {}
    _counting(monkeypatch, ocs.RewirePlanner, "add_channel", calls, "add")
    _counting(monkeypatch, ocs.RewirePlanner, "ensure", calls, "ensure")
    obs.enable()
    _ocs(120)
    snap = obs.snapshot()
    st = obs._store
    obs.disable()
    assert calls["add"] > 0 and calls["ensure"] > 0
    assert snap["counters"]["ocs.channels"] == calls["add"]
    assert snap["spans"]["ocs.rewire"]["count"] == calls["ensure"]
    names = st.rec[obs._NAME::obs._FIELDS]
    parents = st.rec[obs._PARENT::obs._FIELDS]
    rewire = st.ids["ocs.rewire"]
    outer = [st.names[names[parents[i] // obs._FIELDS]]
             for i, name in enumerate(names) if name == rewire]
    assert len(outer) == calls["ensure"]
    assert "ocs.findclos" in outer
    assert set(outer) <= {"ocs.findclos", "place"}


def test_off_an_ocs_run_keeps_nothing():
    obs.enable()
    obs.disable()
    _ocs(120)
    assert obs.snapshot() == {"spans": {}, "counters": {obs.COMPILES: 0}}


def _gpt_lanes():
    """The lane grid over a Megatron-LM fleet: the Table 7 size mix, each
    job the GPT row published for its size (tp/pp layouts)."""
    grid = CampaignGrid(strategies=("best", "sr", "ecmp"),
                        schedulers=("fifo",), loads=(600.0,), seeds=(0,))
    trace = generate_trace(WorkloadSpec(
        num_jobs=30, mean_interarrival=600.0, size_mix="tpuv4",
        models=("megatron-gpt",), seed=3))
    return run_campaign(CLUSTER512, grid, trace=trace, engine="batched")


@pytest.mark.parametrize("run", [_lanes, _gpt_lanes], ids=["dp", "gpt"])
def test_group_counters_agree_with_outside_wrappers(monkeypatch, run):
    """``lanes.entries`` counts the entry builds; ``flows.dp``,
    ``flows.pp`` and ``groups.dp`` count the cross-leaf flows and DP
    groups of the Flow-level phases of every job placed across
    servers."""
    calls = {}
    _counting(monkeypatch, _BatchedEngine, "_build_entries", calls,
              "entries")
    placed = []
    orig = _BatchedEngine._add_running

    def spy(self, l, lane, jidx, job, gpus, srv_u, cnt_u):
        if len(srv_u) > 1:
            placed.append((job, list(gpus)))
        return orig(self, l, lane, jidx, job, gpus, srv_u, cnt_u)

    monkeypatch.setattr(_BatchedEngine, "_add_running", spy)
    obs.enable()
    run()
    snap = obs.snapshot()
    obs.disable()
    gpl = CLUSTER512.gpus_per_leaf
    want = {"flows.dp": 0, "flows.pp": 0, "groups.dp": 0}
    for job, gpus in placed:
        for kind, phase in job.ar_phases(gpus):
            key = "flows.pp" if kind == "pp" else "flows.dp"
            want[key] += sum(f.src // gpl != f.dst // gpl for f in phase)
        prof = job.profile
        if job.num_gpus > prof.tp * prof.pp:
            want["groups.dp"] += prof.tp * prof.pp
    assert calls["entries"] > 0 and want["flows.dp"] > 0
    assert (want["flows.pp"] > 0) == (run is _gpt_lanes)
    assert snap["spans"]["lanes.entries"]["count"] == calls["entries"]
    for name, n in want.items():
        assert snap["counters"].get(name, 0) == n, name


def test_every_span_shows_on_the_host_plane_of_a_profiler_trace(tmp_path):
    jax.profiler.start_trace(str(tmp_path))
    try:
        obs.enable()
        _lanes()
        _ocs()
        snap = obs.snapshot()
        obs.disable()
    finally:
        jax.profiler.stop_trace()
    path = next(tmp_path.glob("plugins/profile/*/*.xplane.pb"))
    data = jax.profiler.ProfileData.from_file(str(path))
    seen = {}
    for plane in data.planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for ev in line.events:
                if ev.name in snap["spans"]:
                    seen[ev.name] = seen.get(ev.name, 0) + 1
    assert set(snap["spans"]) >= set(LANE_SPANS) | set(OCS_SPANS)
    assert seen == {k: v["count"] for k, v in snap["spans"].items()}


def test_a_fresh_jit_counts_one_compile_and_a_second_call_none():
    x = np.arange(21.0).reshape(7, 3)
    obs.enable()
    f = jax.jit(lambda a: jnp.tanh(a) * 3.0 + 1.0)
    f(x).block_until_ready()
    first = obs.snapshot()["counters"][obs.COMPILES]
    f(x).block_until_ready()
    second = obs.snapshot()["counters"][obs.COMPILES]
    obs.disable()
    assert (first, second - first) == (1, 0)

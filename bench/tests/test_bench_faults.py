"""A whole run off the chip, with the timed path broken underneath: each
fault that a cell can have must turn ``correct`` false.  (The benchmark's
cells run on one chip, so there is no exchange between chips to leave
out.)"""

from bench_helpers import run_small, small_cell


def _correct(cell):
    result, checks = run_small(cell)
    return result["correct"], checks


def test_sound_runs_are_correct():
    for workload, jobs in (("c2048-lanes", 60), ("c512-ocs-vclos", 40)):
        ok, checks = _correct(small_cell(workload, jobs))
        assert ok, checks


def test_an_answer_altered_where_it_is_produced(monkeypatch):
    from repro.core import metrics
    orig = metrics.job_metrics

    def altered(jobs):
        rep = orig(jobs)
        rep.jcts = list(rep.jcts)
        rep.jcts[len(rep.jcts) // 2] *= 1.0 + 1e-6
        return rep

    monkeypatch.setattr("repro.core.batched.job_metrics", altered)
    ok, checks = _correct(small_cell("c2048-lanes", 60))
    assert not ok and checks["jct_gap"]["value"] > checks["jct_gap"]["limit"]


def test_rate_resolution_that_returns_the_state_unchanged(monkeypatch):
    import numpy as np
    monkeypatch.setattr("repro.core.batched.phase_worst_loads",
                        lambda vals, ptr, backend="auto":
                        np.ones(len(ptr) - 1, dtype=np.int64))
    ok, checks = _correct(small_cell("c2048-lanes", 120))
    assert not ok and checks["jct_gap"]["value"] > checks["jct_gap"]["limit"]


def test_half_of_the_lanes_left_out(monkeypatch):
    from repro.core import batched
    orig = batched.run_lanes

    def half(spec, lanes_in, pw_backend="auto"):
        return orig(spec, list(lanes_in)[::2], pw_backend)

    monkeypatch.setattr(batched, "run_lanes", half)
    ok, checks = _correct(small_cell("c2048-lanes", 60))
    assert not ok, checks


def test_placement_that_leaves_the_fabric_state_unchanged(monkeypatch):
    monkeypatch.setattr("repro.core.simulator.commit", lambda state, p: None)
    monkeypatch.setattr("repro.core.simulator.release",
                        lambda state, jid, p=None: None)
    monkeypatch.setattr("repro.core.simulator.ocs_release",
                        lambda state, p: None)
    ok, checks = _correct(small_cell("c512-ocs-vclos", 60))
    assert not ok and checks["placement_mismatch"]["value"] > 0


def test_placement_that_fails_spuriously(monkeypatch):
    from repro.core.placement import PlacementFailure
    from repro.core.strategies import builtin
    orig, calls = builtin.ocs_vclos_place, {"n": 0}

    def flaky(state, job_id, n):
        calls["n"] += 1
        if calls["n"] % 7 == 0:
            return PlacementFailure("network")
        return orig(state, job_id, n)

    monkeypatch.setattr(builtin, "ocs_vclos_place", flaky)
    ok, checks = _correct(small_cell("c512-ocs-vclos", 60))
    assert not ok and checks["jct_gap"]["value"] > checks["jct_gap"]["limit"]


def test_placement_that_picks_a_worse_leaf(monkeypatch):
    import numpy as np
    from repro.core import ocs
    from repro.core.placement import Placement

    def worst_fit(state, job_id, n):
        spec = state.spec
        req = -(-n // spec.gpus_per_server)
        counts = state.idle_server_counts()
        if counts.max() < req:
            return None
        leaf = int(np.argmax(counts))
        servers = state.idle_servers_of_leaf(leaf)[:req]
        gpus = [g for sv in servers for g in spec.gpus_of_server(sv)][:n]
        return Placement(job_id, gpus, "leaf")

    monkeypatch.setattr(ocs, "stage1_leaf", worst_fit)
    ok, checks = _correct(small_cell("c512-ocs-vclos", 60))
    assert not ok and checks["placement_mismatch"]["value"] > 0

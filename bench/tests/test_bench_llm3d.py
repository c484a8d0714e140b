"""The 3D-parallel LLM cell off the chip: its plain reference against the
program on a small fabric, the reference's independence, the
configuration's offered load, faults planted under a whole run, the
float32 control, and a program without the GPT rows."""

import ast
import json
import subprocess
import sys

import numpy as np
import pytest

from bench_helpers import BENCH, run_small, small_cell

import control_llm3d  # noqa: E402
import harness  # noqa: E402
import reference_llm3d  # noqa: E402

CELL = "c2048-megatron3d-lanes"


def _small(num_jobs=48, seed=3):
    """The cell on a 512-GPU cut of its fabric (16 leafs x 8 spines), at
    the same offered load: every row, the 512-GPU gpt-39.1b included,
    still fits."""
    cell = small_cell(CELL, num_jobs, seed=seed)
    cluster = cell.config["cluster"]
    scale = cluster["num_leafs"] / 16
    cluster.update(num_leafs=16, num_spines=8)
    cell.config["jobs"]["mean_interarrival"] *= scale
    return cell


def _kind():
    return harness.load_kind("campaign_llm3d")


def test_the_reference_agrees_with_the_program_on_a_small_fabric():
    cell = _small()
    kind = _kind()
    fleet = kind.Fleet(cell)
    trace = kind.Population(fleet.mix).trace(11)
    assert {j.model for j in trace} >= {"gpt-7.5b", "gpt-39.1b"}
    lanes = fleet.unit(trace)
    arrival = [j.arrival for j in trace]
    slowed = 0
    for strat, seed, n_fin, jwts, jcts in lanes:
        rows = reference_llm3d.simulate_lane(cell.config, trace, strat, seed)
        assert n_fin == len(trace)
        assert kind._gap(arrival, jwts, jcts, rows) <= 1e-12, (strat, seed)
        slowed += strat != "best" and any(
            a > b * (1 + 1e-9) for a, b in zip(jcts, lanes[0][4]))
    assert slowed        # the fabric lanes felt contention


def test_the_reference_imports_nothing_of_the_program():
    src = (BENCH / "reference_llm3d.py").read_text()
    names = set()
    for node in ast.walk(ast.parse(src)):
        if isinstance(node, ast.Import):
            names |= {a.name for a in node.names}
        elif isinstance(node, ast.ImportFrom):
            names.add(node.module)
    assert names == {"__future__", "math", "typing", "numpy", "reference"}
    code = (f"import sys; sys.path.insert(0, {str(BENCH)!r}); "
            "import reference_llm3d; "
            "sys.exit(any(m == 'repro' or m.startswith('repro.') "
            "for m in sys.modules))")
    out = subprocess.run([sys.executable, "-c", code], capture_output=True,
                         text=True, timeout=60)
    assert out.returncode == 0, out.stderr


def test_the_configuration_offers_its_stated_gpu_load():
    cell = harness.find_cell(harness.load_benchmark(), CELL, 1, 1.0, False)
    mix = cell.config["jobs"]
    pop = _kind().Population(mix)
    for t in range(int(cell.traffic["traces"])):
        assert reference_llm3d.offered_load(cell.config, pop.trace(t)) == \
            pytest.approx(mix["offered_load"], rel=1e-9)


def test_a_sound_run_is_correct():
    result, checks = run_small(_small())
    assert result["correct"], checks
    assert set(checks) == {"unfinished_jobs", "tp_split", "jct_gap"}


def _pp_bytes_one_micro_batch_more(monkeypatch):
    from repro.core import batched, jobs
    orig = jobs.Job.stage_bytes

    def more(self):
        b = orig(self)
        p = self.profile
        if b:           # m = batch / d micro-batches of one sample
            m = self.batch_size / p.dp_size(self.num_gpus)
            b = b * (m + 1) / m
        return b

    monkeypatch.setattr(jobs.Job, "stage_bytes", more)
    monkeypatch.setattr(batched, "_PRE_CACHE", {})
    return "jct_gap"


def _tp_group_split_across_servers(monkeypatch):
    from repro.core.batched import _BatchedEngine
    orig = _BatchedEngine._commit

    def rolled(self, l, lane, gpus, srv_u, cnt_u):
        # the same servers, ranks shifted by one GPU: every TP group of
        # two or more GPUs straddles a server boundary
        return orig(self, l, lane, np.roll(gpus, 1), srv_u, cnt_u)

    monkeypatch.setattr(_BatchedEngine, "_commit", rolled)
    return "tp_split"


def _one_job_left_unfinished(monkeypatch):
    from repro.core import batched
    orig = batched.job_metrics

    def one_short(jobs):
        rep = orig(jobs)
        rep.n_finished -= 1
        return rep

    monkeypatch.setattr(batched, "job_metrics", one_short)
    return "unfinished_jobs"


@pytest.mark.parametrize("plant", [_pp_bytes_one_micro_batch_more,
                                   _tp_group_split_across_servers,
                                   _one_job_left_unfinished])
def test_a_planted_fault_turns_correct_false(monkeypatch, plant):
    name = plant(monkeypatch)
    result, checks = run_small(_small())
    assert not result["correct"]
    assert checks[name]["value"] > checks[name]["limit"], checks


def test_the_float32_control_fails_jct_gap():
    r = control_llm3d.readings(_small(), 5)
    limit = float(json.loads((BENCH / "traffic" / "megatron3d-lanes.json")
                             .read_text())["limits"]["jct_gap"])
    assert r["program"]["jct_gap"] <= limit < r["control"]["jct_gap"]
    assert r["program"]["tp_split"] == r["program"]["unfinished_jobs"] == 0


def test_a_program_without_the_gpt_rows_stops_at_set_up(monkeypatch):
    from repro.core.jobs import PROFILES
    monkeypatch.delitem(PROFILES, "gpt-39.1b")
    with pytest.raises(RuntimeError, match="no job profile"):
        run_small(_small())


def test_a_traced_run_reports_its_spans_and_counters():
    cell = _small()
    cell.trace = True
    result, checks = run_small(cell)
    assert result["correct"], checks
    m = result["metrics"]
    assert set(m) == {"lane_entries_share.llm3d",
                      "cross_leaf_flows_per_job.llm3d"}
    assert 0 < m["lane_entries_share.llm3d"]["value"] < 100
    counters = result["breakdown"]["counters"]
    assert m["cross_leaf_flows_per_job.llm3d"]["value"] > 0
    assert counters["flows.pp"] > 0 and counters["groups.dp"] > 0
    assert checks["witness_mismatch"]["value"] == 0

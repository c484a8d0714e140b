"""The harness finds everything by name, counts the window right and
refuses to run off a TPU."""

import json
import os
import shutil
import subprocess
import sys

import pytest

from bench_helpers import BENCH, ROOT, harness

BENCHMARK = harness.load_benchmark()


@pytest.mark.parametrize("workload", [w["name"] for w in
                                      BENCHMARK["workloads"]])
def test_cell_files_found_by_name(workload):
    cell = harness.find_cell(BENCHMARK, workload, 1, 1.0, False)
    assert cell.config["name"] in {c["name"] for c in BENCHMARK["configs"]}
    assert (BENCH / "kinds" / f"{cell.traffic['kind']}.py").is_file()
    assert cell.per_layer, "every cell reports a per-layer metric"
    for m in cell.per_layer:
        assert callable(harness.metric_reader(m["name"]))
    names = {m["name"] for m in cell.end_to_end}
    assert "setup_s" in names and len(names) >= 2


def test_new_metric_file_is_picked_up_without_editing_another(tmp_path):
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path / "BENCHMARK.json")
    shutil.copytree(BENCH, tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    (tmp_path / "bench" / "metrics" / "units_seen.campaign.py").write_text(
        "def read(rec):\n    return rec['units']\n")
    bench = json.loads((tmp_path / "BENCHMARK.json").read_text())
    bench["per_layer"].append({
        "name": "units_seen.campaign", "unit": "units", "better": "higher",
        "source": "host_clock", "layer": "event engine",
        "moves": "jobs_per_s", "workloads": ["c2048-lanes"]})
    cell = harness.find_cell(bench, "c2048-lanes", 1, 1.0, True,
                             root=tmp_path)
    record = {"units": 7, "window_s": 2.0, "jobs": 10, "calls": {},
              "seconds": {}, "kernel_calls": 0, "idle_share": 1.0}
    got = harness.read_per_layer(cell, record)
    assert got["units_seen.campaign"] == {"value": 7.0, "unit": "units"}


def test_a_reader_that_finds_nothing_is_left_out():
    cell = harness.find_cell(BENCHMARK, "c2048-lanes", 1, 1.0, True)
    record = {"units": 1, "window_s": 2.0, "jobs": 10, "calls": {},
              "seconds": {}, "kernel_calls": 0, "idle_share": 1.0}
    got = harness.read_per_layer(cell, record)
    assert "rate_resolution_share.campaign" not in got
    assert got["device_idle_share.campaign"]["value"] == 100.0


def test_jobs_per_s_counts_every_unit_to_the_end_of_the_last():
    clock = {"t": 100.0}

    def unit(k):
        clock["t"] += 4.0           # each unit takes 4 s and finishes 50 jobs
        return 50

    units, t0, t1 = harness.run_window(10.0, unit, lambda: clock["t"])
    assert len(units) == 3          # started at 0, 4 and 8 s; none at 12 s
    assert t1 - t0 == 12.0
    assert sum(units) / (t1 - t0) == pytest.approx(150 / 12)


def test_run_exits_nonzero_without_a_result_off_a_tpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("ALLOW_MULTIPLE_LIBTPU_LOAD", None)
    out = subprocess.run(
        [sys.executable, str(BENCH / "run.py"), "--workload", "c2048-lanes",
         "--seed", str(2 ** 31 + 11), "--seconds", "1", "--trace", "0"],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=120)
    assert out.returncode == 2
    assert out.stdout.strip() == ""
    assert "no result" in out.stderr


def test_benchmark_names_and_shapes():
    import re
    name = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
    for w in BENCHMARK["workloads"]:
        assert name.match(w["name"]) and w["chips"] == 1
        assert 0 < len(w["why"]) <= 200
        assert (BENCH / "traffic" / f"{w['traffic']}.json").is_file()
    for m in BENCHMARK["end_to_end"] + BENCHMARK["per_layer"]:
        assert name.match(m["name"]) and m["better"] in ("lower", "higher")
    for c in BENCHMARK["configs"]:
        assert (ROOT / c["file"]).is_file() and c["reduced"] == []


def test_every_cycle_runs_the_whole_trace_set_in_a_seeded_order():
    camp = harness.load_kind("campaign")
    seed = 2 ** 31 + 17
    orders = [camp.cycle_order(seed, c, 8) for c in range(4)]
    assert all(sorted(o) == list(range(8)) for o in orders)
    assert orders == [camp.cycle_order(seed, c, 8) for c in range(4)]
    assert orders != [camp.cycle_order(seed + 1, c, 8) for c in range(4)]

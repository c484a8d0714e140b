"""Fault-tolerant campaign runtime (repro.core.runtime) + chaos harness.

The contract under test (ISSUE 7, docs/robustness.md): a campaign that
crashes, hangs, or raises mid-grid can be resumed from its cell journal
and the merged ``CampaignResult`` is **bit-identical** to an
uninterrupted run — across workers=1/4 and store full/stream.  Failures
are injected deterministically by cell index via ``REPRO_CHAOS``
(:mod:`repro.testing.chaos`), so every recovery path runs in CI without
flakiness.
"""

import dataclasses
import json
import os
import time

import pytest

from repro.core import (CLUSTER512, CampaignError, CampaignGrid, CellJournal,
                        JournalMismatch, MetricsReport, SimConfig,
                        WorkloadSpec, atomic_write_text, backoff_delay,
                        classify_exception, run_campaign)
from repro.testing.chaos import (ChaosError, TransientChaosError, chaos_hook,
                                 parse_chaos)

GRID = CampaignGrid(strategies=("ecmp", "sr"), loads=(120.0,), seeds=(0, 1))
WL = WorkloadSpec(num_jobs=30, max_gpus=64)
# retry_backoff=0: recovery paths shouldn't sleep in CI
FAST = dict(retry_backoff=0.0)


def run(**kw):
    cfg = SimConfig(**{**FAST, **kw.pop("cfg", {})})
    return run_campaign(CLUSTER512, GRID, workload=WL, config=cfg, **kw)


def cell_reports(res):
    return [(c.strategy, c.scheduler, c.load, c.seed, c.report)
            for c in res.cells]


def table_no_wall(res):
    # sim_seconds is wall-clock measurement, not simulation output — it can
    # never match across separate processes; everything else must, exactly
    return [{k: v for k, v in row.items() if k != "sim_seconds"}
            for row in res.aggregate()]


@pytest.fixture
def clean():
    return run()


# ---------------------------------------------------------------------------
# units: classification, backoff, chaos grammar, atomic writes
# ---------------------------------------------------------------------------

def test_classify_exception():
    assert classify_exception(OSError("boom")) == "transient"
    assert classify_exception(EOFError()) == "transient"
    assert classify_exception(MemoryError()) == "transient"
    assert classify_exception(ConnectionResetError()) == "transient"
    assert classify_exception(TransientChaosError("x")) == "transient"
    assert classify_exception(ValueError("bug")) == "error"
    assert classify_exception(ChaosError("x")) == "error"


def test_backoff_deterministic_bounded():
    d1 = backoff_delay(7, 3, 1, base=0.1)
    assert d1 == backoff_delay(7, 3, 1, base=0.1)       # seeded jitter
    assert d1 != backoff_delay(7, 3, 2, base=0.1)       # varies per attempt
    assert 0.1 <= d1 <= 0.125
    d2 = backoff_delay(7, 3, 2, base=0.1)
    assert 0.2 <= d2 <= 0.25                            # exponential
    assert backoff_delay(0, 0, 50, base=1.0) <= 30.0    # capped
    assert backoff_delay(0, 0, 1, base=0.0) == 0.0      # disabled


def test_parse_chaos_grammar():
    rules = parse_chaos("crash@3,flaky@7:2, hang@12 ,raise@0:1")
    assert [(r.kind, r.cell, r.attempts) for r in rules] == [
        ("crash", 3, None), ("flaky", 7, 2), ("hang", 12, None),
        ("raise", 0, 1)]
    assert rules[1].fires(7, 0) and rules[1].fires(7, 1)
    assert not rules[1].fires(7, 2) and not rules[1].fires(6, 0)
    for bad in ("boom@1", "crash", "crash@x", "crash@-1", "crash@1:0"):
        with pytest.raises(ValueError):
            parse_chaos(bad)


def test_chaos_crash_refused_in_main_process(monkeypatch):
    # a crash rule firing without a worker pool would kill the whole
    # campaign (journal and all) — the hook must refuse, not os._exit
    monkeypatch.setenv("REPRO_CHAOS", "crash@0")
    with pytest.raises(RuntimeError, match="refused"):
        chaos_hook(0, 0)


def test_atomic_write_text(tmp_path):
    p = tmp_path / "out.json"
    atomic_write_text(p, "first")
    atomic_write_text(p, "second")
    assert p.read_text() == "second"
    assert list(tmp_path.iterdir()) == [p]              # no .tmp leftovers


# ---------------------------------------------------------------------------
# journal: round-trip exactness, schema guard, torn-tail tolerance
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("condense", [False, True])
def test_metrics_journal_roundtrip_exact(condense):
    from repro.core import generate_trace, simulate
    rep = simulate(CLUSTER512, generate_trace(WL.with_seed(3)), "ecmp")
    rep.event_log = [(0.0, "preempt", 1, -1, 2)]        # tuples must survive
    if condense:
        rep.condense(max_samples=16)
    back = MetricsReport.from_journal(
        json.loads(json.dumps(rep.to_journal())))
    assert back == rep                                  # exact, field-for-field
    assert back.event_log == rep.event_log
    assert all(isinstance(e, tuple) for e in back.event_log)


def test_journal_create_refuses_existing(tmp_path):
    p = str(tmp_path / "j.jsonl")
    CellJournal.create(p, {"v": 1}).close()
    with pytest.raises(ValueError, match="resume"):
        CellJournal.create(p, {"v": 1})


def test_journal_schema_mismatch(tmp_path):
    p = str(tmp_path / "j.jsonl")
    CellJournal.create(p, {"grid": [1, 2], "store": "full"}).close()
    with pytest.raises(JournalMismatch, match="store"):
        CellJournal.resume(p, {"grid": [1, 2], "store": "stream"})
    # tuples vs lists must NOT mismatch (JSON-normalised comparison)
    jr, completed = CellJournal.resume(p, {"grid": (1, 2), "store": "full"})
    jr.close()
    assert completed == {}


def test_journal_torn_tail_dropped_midfile_corruption_raises(tmp_path):
    p = str(tmp_path / "j.jsonl")
    jr = CellJournal.create(p, {"v": 1})
    rep = MetricsReport(1.0, 2.0, 3.0, 0.0, 0.0, 1)
    jr.append(("ecmp", "fifo", 120.0, 0), rep, 0.5)
    jr.append(("sr", "fifo", 120.0, 0), rep, 0.5)
    jr.close()
    with open(p, "a") as f:
        f.write('{"kind": "cell", "cell": ["ecmp", "fifo"')   # torn tail
    jr2, completed = CellJournal.resume(p, {"v": 1})
    jr2.close()
    assert set(completed) == {("ecmp", "fifo", 120.0, 0),
                              ("sr", "fifo", 120.0, 0)}
    assert completed[("sr", "fifo", 120.0, 0)][0] == rep
    # resume truncated the torn bytes: every line on disk parses again
    lines = open(p).read().splitlines()
    assert all(json.loads(line) for line in lines)
    # the same torn line anywhere but the tail is external corruption
    lines.insert(1, '{"kind": "cell", "cell": ["ecmp", "fifo"')
    open(p, "w").write("\n".join(lines))
    with pytest.raises(ValueError, match="corrupt at line 2"):
        CellJournal.resume(p, {"v": 1})


def test_journal_torn_tail_truncated_then_reappend(tmp_path):
    """Regression: resume() must *truncate* the torn bytes, not just skip
    them — otherwise the first appended record concatenates onto the
    partial line, planting mid-file corruption that makes the next
    resume refuse with 'corrupt', losing access to every journaled cell."""
    p = str(tmp_path / "j.jsonl")
    jr = CellJournal.create(p, {"v": 1})
    rep = MetricsReport(1.0, 2.0, 3.0, 0.0, 0.0, 1)
    jr.append(("ecmp", "fifo", 120.0, 0), rep, 0.5)
    jr.close()
    with open(p, "a") as f:
        f.write('{"kind": "cell", "cell": ["sr", "fifo"')      # torn tail
    jr2, completed = CellJournal.resume(p, {"v": 1})
    assert set(completed) == {("ecmp", "fifo", 120.0, 0)}
    jr2.append(("sr", "fifo", 120.0, 0), rep, 0.5)             # re-simulated
    jr2.close()
    jr3, completed = CellJournal.resume(p, {"v": 1})           # crash again
    jr3.close()
    assert set(completed) == {("ecmp", "fifo", 120.0, 0),
                              ("sr", "fifo", 120.0, 0)}
    assert completed[("sr", "fifo", 120.0, 0)][0] == rep


def test_journal_missing_final_newline_restored(tmp_path):
    """A write torn between the JSON and its "\\n" terminator leaves a
    complete final record with no newline: the record must be kept and
    the terminator restored so the next append starts a fresh line."""
    p = str(tmp_path / "j.jsonl")
    jr = CellJournal.create(p, {"v": 1})
    rep = MetricsReport(1.0, 2.0, 3.0, 0.0, 0.0, 1)
    jr.append(("ecmp", "fifo", 120.0, 0), rep, 0.5)
    jr.close()
    with open(p, "r+b") as f:                   # tear off just the "\n"
        f.truncate(os.path.getsize(p) - 1)
    jr2, completed = CellJournal.resume(p, {"v": 1})
    assert set(completed) == {("ecmp", "fifo", 120.0, 0)}      # record kept
    jr2.append(("sr", "fifo", 120.0, 0), rep, 0.5)
    jr2.close()
    jr3, completed = CellJournal.resume(p, {"v": 1})
    jr3.close()
    assert set(completed) == {("ecmp", "fifo", 120.0, 0),
                              ("sr", "fifo", 120.0, 0)}


def test_journal_fsync_opt_in(tmp_path, monkeypatch):
    """``fsync=True`` (ISSUE 8: the scheduler-service event log) must
    fsync once per appended record — and per the header — while the
    default flush-only mode never calls fsync at all."""
    calls = []
    real_fsync = os.fsync
    monkeypatch.setattr(os, "fsync",
                        lambda fd: (calls.append(fd), real_fsync(fd))[1])
    rep = MetricsReport(1.0, 2.0, 3.0, 0.0, 0.0, 1)

    jr = CellJournal.create(str(tmp_path / "flush.jsonl"), {"v": 1})
    jr.append(("ecmp", "fifo", 120.0, 0), rep, 0.5)
    jr.close()
    assert calls == []                          # default: flush, no fsync

    jr = CellJournal.create(str(tmp_path / "sync.jsonl"), {"v": 1},
                            fsync=True)
    assert len(calls) == 1                      # header synced
    jr.append(("ecmp", "fifo", 120.0, 0), rep, 0.5)
    jr.append(("sr", "fifo", 120.0, 0), rep, 0.5)
    assert len(calls) == 3                      # one per record
    jr.close()

    # resume keeps the knob
    jr2, completed = CellJournal.resume(str(tmp_path / "sync.jsonl"),
                                        {"v": 1}, fsync=True)
    assert len(completed) == 2
    n = len(calls)
    jr2.append(("ecmp", "ff", 120.0, 0), rep, 0.5)
    assert len(calls) == n + 1
    jr2.close()


# ---------------------------------------------------------------------------
# serial campaigns: resume bit-identity, retries, quarantine
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("store", ["full", "stream"])
def test_crash_at_cell_resume_bit_identical_serial(clean, tmp_path,
                                                   monkeypatch, store):
    """Deterministic failure at cell 2 aborts with a journal holding the
    finished cells; the resumed run merges bit-identically to clean."""
    jp = str(tmp_path / "c.jsonl")
    monkeypatch.setenv("REPRO_CHAOS", "raise@2")
    with pytest.raises(CampaignError) as ei:
        run(journal=jp, cfg=dict(store=store))
    assert ei.value.failed.kind == "error"
    assert jp in str(ei.value)                      # actionable resume hint
    monkeypatch.delenv("REPRO_CHAOS")
    res = run(resume=jp, cfg=dict(store=store))
    base = run(cfg=dict(store=store)) if store != "full" else clean
    assert res.resumed_cells == 2
    assert cell_reports(res) == cell_reports(base)
    assert table_no_wall(res) == table_no_wall(base)


def test_resume_from_complete_journal(clean, tmp_path):
    jp = str(tmp_path / "c.jsonl")
    run(journal=jp)
    res = run(resume=jp)
    assert res.resumed_cells == GRID.size and res.complete
    assert cell_reports(res) == cell_reports(clean)


def test_flaky_cell_retried_to_success(clean, monkeypatch):
    monkeypatch.setenv("REPRO_CHAOS", "flaky@1:2")      # 2 transient fails
    res = run()                                          # default 2 retries
    assert cell_reports(res) == cell_reports(clean)
    # one more transient failure than retries -> permanent
    monkeypatch.setenv("REPRO_CHAOS", "flaky@1:3")
    with pytest.raises(CampaignError) as ei:
        run()
    assert ei.value.failed.kind == "transient"
    assert ei.value.failed.attempts == 3


def test_quarantine_accounting(clean, monkeypatch):
    monkeypatch.setenv("REPRO_CHAOS", "raise@1")
    res = run(quarantine=True)
    assert len(res.cells) == GRID.size - 1
    assert [f.kind for f in res.failed_cells] == ["error"]
    fc = res.failed_cells[0]
    assert (fc.strategy, fc.scheduler, fc.load, fc.seed) in set(GRID.cells())
    assert res.missing_cells() == [fc.key()] and not res.complete
    # surviving cells are untouched by the neighbour's failure
    want = {(c.strategy, c.scheduler, c.load, c.seed): c.report
            for c in clean.cells}
    for c in res.cells:
        assert c.report == want[(c.strategy, c.scheduler, c.load, c.seed)]
    j = res.to_json()
    assert j["failed_cells"][0]["kind"] == "error"
    assert j["missing_cells"] == [list(fc.key())]
    assert j["resumed_cells"] == 0
    # the aggregate row for the quarantined slice pools one seed less
    row = next(r for r in res.aggregate()
               if (r["strategy"], r["scheduler"]) == (fc.strategy,
                                                      fc.scheduler))
    assert row["seeds"] == 1


def test_journal_resume_arg_validation(tmp_path):
    with pytest.raises(ValueError, match="not two different paths"):
        run(journal=str(tmp_path / "a"), resume=str(tmp_path / "b"))
    with pytest.raises(ValueError, match="does not exist"):
        run(resume=str(tmp_path / "missing.jsonl"))
    jp = str(tmp_path / "j.jsonl")
    run(journal=jp)
    # a journal written by a different campaign is refused with a diff
    # (grid seeds override the workload seed, so vary the trace length)
    with pytest.raises(JournalMismatch, match="traces"):
        run_campaign(CLUSTER512, GRID,
                     workload=dataclasses.replace(WL, num_jobs=25),
                     config=SimConfig(**FAST), resume=jp)


def test_campaign_result_save_atomic(tmp_path, clean):
    out = tmp_path / "res.json"
    clean.save(str(out))
    data = json.loads(out.read_text())
    assert data["resumed_cells"] == 0 and data["missing_cells"] == []
    assert not (tmp_path / "res.json.tmp").exists()
    clean.write_csv(str(tmp_path / "res.csv"))
    assert (tmp_path / "res.csv").read_text().startswith("strategy,")


# ---------------------------------------------------------------------------
# pool campaigns: worker death, isolation, timeouts (slow: real processes)
# ---------------------------------------------------------------------------

def test_shutdown_pool_kills_hung_workers():
    """Regression: _shutdown_pool(kill=True) must terminate the worker
    *processes* (an operator-precedence bug once made it iterate raw PIDs,
    so terminate() never ran and hung workers leaked past the kill)."""
    from concurrent.futures import ProcessPoolExecutor

    from repro.core.runtime import _shutdown_pool
    pool = ProcessPoolExecutor(max_workers=2)
    pool.submit(time.sleep, 300)                # hang both workers
    pool.submit(time.sleep, 300)
    deadline = time.monotonic() + 10.0
    while len(pool._processes or {}) < 2 and time.monotonic() < deadline:
        time.sleep(0.05)
    procs = list(pool._processes.values())
    assert procs
    _shutdown_pool(pool, kill=True)
    for p in procs:
        p.join(timeout=10.0)
        # the pool's manager thread may reap the worker first, and then
        # is_alive() reports the reaped pid as alive: ask the kernel too
        assert not (p.is_alive() and os.path.exists(f"/proc/{p.pid}")), \
            "worker still running"              # dead, not sleeping out 300s


def _dispatch_crossover():
    from repro.core.fairshare import phase_worst_crossover
    return phase_worst_crossover()


def test_pool_workers_pin_host_dispatch(monkeypatch):
    """Campaign pool workers never reach for the chip: their auto dispatch
    is numpy at every size, whatever the crossover says in the parent."""
    from repro.core import fairshare
    from repro.core.runtime import _shutdown_pool, host_pool
    monkeypatch.setenv("REPRO_PHASE_WORST_CROSSOVER", "0")
    monkeypatch.setattr(fairshare, "_pw_crossover", {})
    assert fairshare.phase_worst_crossover() == 0.0     # the parent's
    pool = host_pool(1)
    try:
        got = pool.submit(_dispatch_crossover).result(timeout=60)
    finally:
        _shutdown_pool(pool, kill=False)
    assert got == float("inf")

@pytest.mark.slow
@pytest.mark.parametrize("store", ["full", "stream"])
def test_worker_crash_resume_bit_identical_pool(tmp_path, monkeypatch,
                                                store):
    """A worker killed mid-campaign (os._exit, as an OOM kill would)
    surfaces as a crash, is isolated and retried; with the crash armed on
    every attempt the cell poisons out, and resuming the journal without
    chaos merges bit-identically to a clean run — workers=4."""
    base = run(cfg=dict(store=store))
    jp = str(tmp_path / "p.jsonl")
    monkeypatch.setenv("REPRO_CHAOS", "crash@2")
    with pytest.raises(CampaignError) as ei:
        run(journal=jp, cfg=dict(store=store, workers=4))
    assert ei.value.failed.kind == "crash"
    monkeypatch.delenv("REPRO_CHAOS")
    res = run(resume=jp, cfg=dict(store=store, workers=4))
    assert cell_reports(res) == cell_reports(base)
    assert table_no_wall(res) == table_no_wall(base)


@pytest.mark.slow
def test_worker_crash_once_recovers_via_isolation(clean, monkeypatch):
    """crash@2:1 kills whichever workers are in flight alongside cell 2;
    the runner isolates the suspects, attributes the crash, and the retry
    (attempt 1, rule expired) completes the full grid bit-identically —
    innocent cells never burn an attempt."""
    monkeypatch.setenv("REPRO_CHAOS", "crash@2:1")
    res = run(cfg=dict(workers=4))
    assert cell_reports(res) == cell_reports(clean)
    assert res.complete and not res.failed_cells


@pytest.mark.slow
def test_hung_cell_timeout_quarantined(clean, monkeypatch):
    """A hung worker trips cell_timeout, the pool is killed (the only way
    to stop it), the cell quarantines as `timeout`, and the innocent
    cells complete unharmed."""
    monkeypatch.setenv("REPRO_CHAOS", "hang@0")
    monkeypatch.setenv("REPRO_CHAOS_HANG", "60")
    res = run(cfg=dict(workers=2, cell_timeout=3.0, max_retries=0,
                       quarantine=True))
    assert [f.kind for f in res.failed_cells] == ["timeout"]
    assert "cell_timeout" in res.failed_cells[0].error
    want = {(c.strategy, c.scheduler, c.load, c.seed): c.report
            for c in clean.cells}
    assert len(res.cells) == GRID.size - 1
    for c in res.cells:
        assert c.report == want[(c.strategy, c.scheduler, c.load, c.seed)]


@pytest.mark.slow
def test_hung_cell_timeout_retry_recovers(clean, monkeypatch):
    """hang@3:1 hangs only the first attempt; cell_timeout kills it and
    the retry completes — also proves cell_timeout>0 forces the pool path
    at workers=1 (the serial path could never interrupt the hang)."""
    monkeypatch.setenv("REPRO_CHAOS", "hang@3:1")
    monkeypatch.setenv("REPRO_CHAOS_HANG", "60")
    res = run(cfg=dict(cell_timeout=3.0))
    assert cell_reports(res) == cell_reports(clean)
    assert res.complete and not res.failed_cells


# ---------------------------------------------------------------------------
# partial figures / reports: gaps render visibly, gates refuse silence
# ---------------------------------------------------------------------------

def test_partial_figure_gap_annotation(monkeypatch):
    from repro.core import build_figure, qualitative_checks
    from repro.launch.report import render_markdown
    monkeypatch.setenv("REPRO_CHAOS", "raise@3")
    tab = build_figure("jct-vs-load", scale="smoke",
                       fault=dict(quarantine=True, max_retries=0,
                                  retry_backoff=0.0))
    meta = tab.meta_dict()
    assert meta["missing_cells"] == 1 and meta["failed_cells"] == 1
    assert meta["grid_cells"] == 8
    # gates refuse silently-incomplete data...
    problems = qualitative_checks([tab])
    assert problems and "incomplete" in problems[0]
    # ...allow_partial renders it, but never silently
    assert qualitative_checks([tab], allow_partial=True) == []
    md = render_markdown([tab], "smoke")
    assert "Partial data" in md and "1 of 8 grid cells missing" in md


def test_complete_figure_has_no_partial_meta():
    # the committed (byte-gated) gallery must not change on the clean
    # path: partial-accounting keys appear only when cells are missing
    from repro.core import build_figure
    tab = build_figure("ocs-comparison", scale="smoke")
    meta = tab.meta_dict()
    assert "missing_cells" not in meta and "failed_cells" not in meta


def test_figure_journal_resume_dir(tmp_path, monkeypatch):
    from repro.core import build_figure
    monkeypatch.setenv("REPRO_CHAOS", "raise@3")
    with pytest.raises(CampaignError):
        build_figure("jct-vs-load", scale="smoke",
                     fault=dict(retry_backoff=0.0, max_retries=0),
                     resume_dir=str(tmp_path))
    assert (tmp_path / "jct-vs-load.journal.jsonl").exists()
    monkeypatch.delenv("REPRO_CHAOS")
    resumed = build_figure("jct-vs-load", scale="smoke",
                           resume_dir=str(tmp_path))
    assert resumed == build_figure("jct-vs-load", scale="smoke")


# ---------------------------------------------------------------------------
# CLI validation (mirrors the --events pattern: actionable argparse errors)
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("argv", [
    ["--cell-timeout", "0"],
    ["--cell-timeout", "-2"],
    ["--max-retries", "-1"],
    ["--resume", "/nonexistent/journal.jsonl"],
    ["--journal", "/tmp/a.jsonl", "--resume", "/tmp/b.jsonl"],
])
def test_sweep_campaign_cli_validation(argv, capsys):
    from repro.launch.sweep import campaign_main
    with pytest.raises(SystemExit) as ei:
        campaign_main(argv)
    assert ei.value.code == 2
    err = capsys.readouterr().err
    assert argv[0].lstrip("-").split()[0] in err.replace("_", "-") \
        or "journal" in err


def test_sweep_campaign_cli_journal_exists(tmp_path, capsys):
    jp = tmp_path / "exists.jsonl"
    jp.write_text("{}\n")
    from repro.launch.sweep import campaign_main
    with pytest.raises(SystemExit) as ei:
        campaign_main(["--journal", str(jp)])
    assert ei.value.code == 2
    assert "--resume" in capsys.readouterr().err

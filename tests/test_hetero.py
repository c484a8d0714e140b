"""Differential suite for heterogeneous fabrics + time-domain interleaving.

The homogeneous engines are the oracle (docs/heterogeneous.md): per-tier
link speeds (``leaf_uplink_gbps`` / ``server_nic_gbps``) and mixed GPU
generations (``server_scale`` via :func:`apply_gpu_mix`) route every run
through the speed-aware rate-resolution path, and this suite pins the three
contracts that make that path trustworthy:

  * **v1 ≡ v2 on hetero configs** — bit-identical schedules across every
    builtin strategy, both bundled plugins, fifo/ff/edf and ≥3 seeds
    (the hetero twin of ``tests/test_batched.py``);
  * **batched delegation** — hetero specs never qualify for the lane
    engine; ``engine="batched"`` transparently falls through to v2 and
    must stay cell-for-cell exact through the campaign driver;
  * **degenerate equivalence** — a spec with *explicit* unit ratios
    (leaf=nic=link speed, every server scale 1.0) still takes the hetero
    code path (``is_hetero`` is True) yet reproduces the homogeneous
    schedules byte-for-byte, including the pinned campaign goldens
    ecmp=13417.8 / sr=3731.4 / best=2949.3.

Satellites ride along: ClusterSpec/apply_gpu_mix validation, the
``--gpu-mix``/``--link-speeds`` CLI flags, the fairshare ``flow_cap``
parametrisation (the old hard-coded unit NIC bound), the straggler model,
and the phase-offset (duty-cycle) primitives behind
``contention-affinity-time``.
"""

import copy
import dataclasses
import math

import numpy as np
import pytest

from repro.core.batched import run_lanes, try_run_batched
from repro.core.campaign import CampaignGrid, run_campaign
from repro.core.fairshare import (maxmin_fair, maxmin_fair_jax,
                                  maxmin_fair_numpy)
from repro.core.jobs import PROFILES, Job
from repro.core.metrics import MetricsReport
from repro.core.patterns import comm_duty_cycle, duty_overflow
from repro.core.simulator import ClusterSimulator, simulate
from repro.core.strategies import get_strategy
from repro.core.topology import (CLUSTER512, CLUSTER512_OCS, TESTBED32,
                                 ClusterSpec, apply_gpu_mix)
from repro.core.workloads import WorkloadSpec, generate_trace

BUILTINS = ("best", "sr", "ecmp", "balanced", "vclos", "ocs-vclos",
            "ocs-relax")
PLUGINS = ("contention-affinity", "contention-affinity-time")
FAST = ("best", "sr", "ecmp")
SEEDS = (0, 1, 2)

#: the suite's reference fleet mix: half current-gen, half prior-gen at 62%
MIX = [("h100", 1.0, 0.5), ("a100", 0.62, 0.5)]


def _hetero(spec, leaf=200.0, nic=80.0, mix=MIX):
    """Spec with over-provisioned leaf uplinks, slower NICs and mixed
    GPU generations — exercises every hetero branch at once."""
    s = dataclasses.replace(spec, leaf_uplink_gbps=leaf,
                            server_nic_gbps=nic)
    return apply_gpu_mix(s, mix) if mix else s


HET32 = _hetero(TESTBED32)
HET512 = _hetero(CLUSTER512)
HET512_OCS = _hetero(CLUSTER512_OCS)

#: explicit unit ratios — is_hetero is True (the hetero code path runs) but
#: every share and compute time must match the homogeneous engines exactly
DEGENERATE512 = dataclasses.replace(
    CLUSTER512, leaf_uplink_gbps=CLUSTER512.link_gbps,
    server_nic_gbps=CLUSTER512.link_gbps,
    server_scale=(1.0,) * CLUSTER512.num_servers)


def _trace(num_jobs, load, max_gpus, seed):
    return generate_trace(WorkloadSpec(num_jobs=num_jobs,
                                       mean_interarrival=load,
                                       max_gpus=max_gpus, seed=seed))


def _run(spec, strategy, scheduler, seed, jobs, engine, **kw):
    sim = ClusterSimulator(spec, strategy=strategy, scheduler=scheduler,
                           seed=seed, engine=engine, **kw)
    rep = sim.run(copy.deepcopy(jobs))
    return sim, rep


def _assert_reports_equal(ra: MetricsReport, rb: MetricsReport):
    """Bit-exact schedule equality, not approximate metric agreement."""
    assert ra.n_finished == rb.n_finished
    np.testing.assert_array_equal(np.asarray(ra.jcts), np.asarray(rb.jcts))
    np.testing.assert_array_equal(np.asarray(ra.jwts), np.asarray(rb.jwts))
    np.testing.assert_array_equal(np.asarray(ra.slowdowns),
                                  np.asarray(rb.slowdowns))
    assert ra.frag_gpu == rb.frag_gpu
    assert ra.frag_network == rb.frag_network
    assert ra.avg_jct == rb.avg_jct
    assert ra.avg_jwt == rb.avg_jwt
    assert ra.stability == rb.stability
    assert ra.makespan == rb.makespan


# ---------------------------------------------------------------------------
# ClusterSpec hetero kwargs: validation + derived properties
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("field", ["leaf_uplink_gbps", "server_nic_gbps"])
@pytest.mark.parametrize("bad", [0.0, -100.0])
def test_spec_rejects_non_positive_speeds(field, bad):
    with pytest.raises(ValueError, match="positive speed"):
        dataclasses.replace(TESTBED32, **{field: bad})


def test_spec_rejects_wrong_scale_length():
    with pytest.raises(ValueError, match="one entry per server"):
        dataclasses.replace(TESTBED32, server_scale=(1.0, 0.5))
    # the message points at the helper that gets the expansion right
    with pytest.raises(ValueError, match="apply_gpu_mix"):
        dataclasses.replace(TESTBED32, server_scale=(1.0,))


def test_spec_rejects_non_positive_scale():
    scales = [1.0] * TESTBED32.num_servers
    scales[3] = 0.0
    with pytest.raises(ValueError, match=r"server_scale\[3\].*positive"):
        dataclasses.replace(TESTBED32, server_scale=tuple(scales))


def test_spec_rejects_gen_without_or_mismatching_scale():
    gens = ("h100",) * TESTBED32.num_servers
    with pytest.raises(ValueError, match="server_scale"):
        dataclasses.replace(TESTBED32, server_gen=gens)
    with pytest.raises(ValueError, match="one tag per server"):
        dataclasses.replace(TESTBED32, server_gen=gens[:-1],
                            server_scale=(1.0,) * TESTBED32.num_servers)


def test_spec_hetero_properties():
    assert not TESTBED32.is_hetero
    assert TESTBED32.leaf_ratio == 1.0 and TESTBED32.nic_ratio == 1.0
    assert TESTBED32.scale_of_server(0) == 1.0
    # explicit unit values still flip the hetero switch: the degenerate
    # case must *exercise* the speed-aware path, not skip it
    assert DEGENERATE512.is_hetero
    assert DEGENERATE512.leaf_ratio == 1.0
    assert DEGENERATE512.nic_ratio == 1.0
    assert HET32.is_hetero
    assert HET32.leaf_ratio == 2.0
    assert HET32.nic_ratio == pytest.approx(0.8)
    # MIX halves the 8 testbed servers: 4 × h100 then 4 × a100
    assert [HET32.scale_of_server(s) for s in range(8)] == \
        [1.0] * 4 + [0.62] * 4
    assert HET32.server_gen == ("h100",) * 4 + ("a100",) * 4


# ---------------------------------------------------------------------------
# apply_gpu_mix: expansion + validation
# ---------------------------------------------------------------------------

def test_gpu_mix_expansion_deterministic():
    a = apply_gpu_mix(TESTBED32, MIX)
    b = apply_gpu_mix(TESTBED32, MIX)
    assert a == b
    assert a.server_scale == (1.0,) * 4 + (0.62,) * 4


def test_gpu_mix_remainder_goes_to_last_entry():
    # 0.5/0.25/0.25 of 8 servers → 4/2/2; 0.4/0.4/0.2 → 3/3/2 (remainder 1
    # lands on the last generation, keeping blocks contiguous)
    mix = [("a", 1.0, 0.4), ("b", 0.8, 0.4), ("c", 0.5, 0.2)]
    spec = apply_gpu_mix(TESTBED32, mix)
    assert spec.server_gen == ("a",) * 3 + ("b",) * 3 + ("c",) * 2


@pytest.mark.parametrize("mix,msg", [
    ([], "empty"),
    ([("a", 0.0, 1.0)], "positive"),
    ([("a", 1.0, -0.5), ("b", 1.0, 1.5)], "positive"),
    ([("a", 1.0, 0.5)], "sum to 1"),
    ([("a", 1.0, 0.5), ("b", 1.0, 0.5), ("c", 1.0, 1e-10)],
     "leaves no servers"),
], ids=["empty", "zero-scale", "neg-frac", "bad-sum", "no-servers"])
def test_gpu_mix_validation(mix, msg):
    with pytest.raises(ValueError, match=msg):
        apply_gpu_mix(TESTBED32, mix)


# ---------------------------------------------------------------------------
# Degenerate equivalence: explicit unit ratios reproduce the homogeneous
# schedules byte-for-byte — including the pinned campaign goldens
# ---------------------------------------------------------------------------

def test_degenerate_reproduces_goldens():
    """The hetero rate path at ratio 1.0 must hit the exact golden JCTs of
    test_campaign.py — same trace, same strategies, same rounding."""
    jobs = generate_trace(WorkloadSpec(num_jobs=200, mean_interarrival=120.0,
                                       seed=0, max_gpus=256))
    golden = {"ecmp": 13417.8, "sr": 3731.4, "best": 2949.3}
    for strat, want in golden.items():
        got = simulate(DEGENERATE512, jobs, strat, engine="v2").avg_jct
        assert round(got, 1) == pytest.approx(want), strat


@pytest.mark.parametrize("engine", ["v1", "v2"])
@pytest.mark.parametrize("strategy", FAST + ("balanced",))
def test_degenerate_bit_identical_to_homogeneous(strategy, engine):
    """Beyond the rounded goldens: every per-job JCT/JWT must be the same
    float64 bit pattern as the plain homogeneous spec (min(1, 1/w) and the
    ÷1.0 compute scaling are exact)."""
    jobs = _trace(120, 60.0, 128, 1)
    _, hom = _run(CLUSTER512, strategy, "fifo", 0, jobs, engine)
    _, deg = _run(DEGENERATE512, strategy, "fifo", 0, jobs, engine)
    _assert_reports_equal(deg, hom)


# ---------------------------------------------------------------------------
# v1 ≡ v2 on heterogeneous configs (the tentpole differential contract)
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("seed", SEEDS)
@pytest.mark.parametrize("strategy", FAST + PLUGINS)
def test_hetero_parity_fast(strategy, seed):
    jobs = _trace(80, 25.0, 16, seed)
    _, r1 = _run(HET32, strategy, "fifo", seed, jobs, "v1")
    _, r2 = _run(HET32, strategy, "fifo", seed, jobs, "v2")
    _assert_reports_equal(r1, r2)


@pytest.mark.slow
@pytest.mark.parametrize("seed", SEEDS)
@pytest.mark.parametrize("strategy", BUILTINS + PLUGINS)
def test_hetero_parity_all_strategies(strategy, seed):
    """Every builtin + both plugins on the mixed-generation 512-GPU fleet
    with per-tier speeds: the scan and heap engines must agree bit-for-bit
    exactly as they do on homogeneous specs."""
    spec = HET512_OCS if get_strategy(strategy).requires_ocs else HET512
    jobs = _trace(120, 40.0, 64, seed)
    _, r1 = _run(spec, strategy, "fifo", seed, jobs, "v1")
    _, r2 = _run(spec, strategy, "fifo", seed, jobs, "v2")
    _assert_reports_equal(r1, r2)


@pytest.mark.slow
@pytest.mark.parametrize("scheduler", ("fifo", "ff", "edf"))
@pytest.mark.parametrize("strategy", ("best", "sr"))
def test_hetero_parity_queue_policies(strategy, scheduler):
    for seed in SEEDS:
        jobs = generate_trace(WorkloadSpec(
            num_jobs=70, mean_interarrival=20.0, max_gpus=16, seed=seed,
            deadline_slack=(1.5, 4.0)))
        _, r1 = _run(HET32, strategy, scheduler, seed, jobs, "v1")
        _, r2 = _run(HET32, strategy, scheduler, seed, jobs, "v2")
        _assert_reports_equal(r1, r2)


@pytest.mark.parametrize("strategy", ("best", "ecmp"))
def test_hetero_v2_matches_reference(strategy):
    """v2's link-scoped re-solve vs the v1 full recompute on a hetero spec
    and a trace seed the parity sweeps above do not use — the speed-aware
    shares must settle identically either way."""
    jobs = _trace(60, 30.0, 16, 3)
    v1 = simulate(HET32, jobs, strategy, engine="v1")
    v2 = simulate(HET32, jobs, strategy, engine="v2")
    _assert_reports_equal(v1, v2)


def test_hetero_churn_parity():
    """Hetero rate resolution × dynamic cluster events (preempt + server
    failures): the engines re-solve after every churn event and must stay
    bit-identical."""
    for seed in SEEDS:
        jobs = generate_trace(WorkloadSpec(
            num_jobs=60, mean_interarrival=25.0, max_gpus=16, seed=seed,
            preempt_fraction=0.1, server_mtbf=30000.0))
        _, r1 = _run(HET32, "best", "fifo", seed, jobs, "v1")
        _, r2 = _run(HET32, "best", "fifo", seed, jobs, "v2")
        _assert_reports_equal(r1, r2)


# ---------------------------------------------------------------------------
# Batched-engine delegation: hetero specs never take the lane fast path
# ---------------------------------------------------------------------------

def test_try_run_batched_delegates_hetero():
    """An otherwise-qualifying config (best/fifo, no churn) on a hetero
    spec must return None — speed-aware resolution lives in v1/v2 only."""
    jobs = _trace(40, 30.0, 16, 0)
    sim = ClusterSimulator(HET32, strategy="best", seed=0, engine="batched")
    assert try_run_batched(sim, sorted(jobs, key=lambda j: j.arrival),
                           math.inf) is None
    # the degenerate spec delegates too: is_hetero gates the predicate
    sim = ClusterSimulator(DEGENERATE512, strategy="best", seed=0,
                           engine="batched")
    assert try_run_batched(sim, sorted(jobs, key=lambda j: j.arrival),
                           math.inf) is None


def test_run_lanes_rejects_hetero():
    jobs = _trace(10, 30.0, 8, 0)
    with pytest.raises(ValueError, match="qualify"):
        run_lanes(HET32, [(jobs, get_strategy("best"), 0)])


@pytest.mark.parametrize("seed", SEEDS)
@pytest.mark.parametrize("strategy", FAST)
def test_hetero_batched_engine_matches_v2(strategy, seed):
    """engine="batched" on a hetero spec silently falls through to the v2
    loop — and the fallthrough must be exact, so a delegation bug can't
    masquerade as engine parity."""
    jobs = _trace(80, 25.0, 16, seed)
    _, rv = _run(HET32, strategy, "fifo", seed, jobs, "v2")
    _, rb = _run(HET32, strategy, "fifo", seed, jobs, "batched")
    _assert_reports_equal(rb, rv)


@pytest.mark.slow
def test_hetero_campaign_batched_matches_v2():
    """Campaign-level grouping on a hetero spec: every cell delegates, and
    the batched campaign must reproduce the serial v2 campaign cell for
    cell (the churn-free half of the acceptance criteria)."""
    grid = CampaignGrid(strategies=("best", "sr", "ecmp"),
                        schedulers=("fifo",), loads=(20.0, 35.0),
                        seeds=(0, 1))
    wl = WorkloadSpec(num_jobs=60, max_gpus=16)
    res_v = run_campaign(HET32, grid, workload=wl, engine="v2")
    res_b = run_campaign(HET32, grid, workload=wl, engine="batched")
    rows_v = res_v.aggregate()
    rows_b = res_b.aggregate()
    assert len(rows_v) == len(rows_b) == 6
    for a, b in zip(rows_v, rows_b):
        assert {k: v for k, v in a.items() if k != "sim_seconds"} == \
            {k: v for k, v in b.items() if k != "sim_seconds"}
    for cv, cb in zip(res_v.cells, res_b.cells):
        assert (cv.strategy, cv.scheduler, cv.load, cv.seed) == \
            (cb.strategy, cb.scheduler, cb.load, cb.seed)
        _assert_reports_equal(cb.report, cv.report)


# ---------------------------------------------------------------------------
# Straggler model: a job runs at its slowest member's compute scale
# ---------------------------------------------------------------------------

def _one_job(num_gpus):
    return [Job(job_id=0, model="resnet50", num_gpus=num_gpus,
                batch_size=32, arrival=0.0, num_iters=100)]


def test_straggler_single_gpu_exact_scaling():
    """A 1-GPU job has no communication: on a uniformly half-speed fleet
    its JCT is exactly 2× the homogeneous one (binary-exact: ÷0.5)."""
    slow = dataclasses.replace(
        TESTBED32, server_scale=(0.5,) * TESTBED32.num_servers)
    base = simulate(TESTBED32, _one_job(1), "ecmp").jcts[0]
    half = simulate(slow, _one_job(1), "ecmp").jcts[0]
    assert half == 2.0 * base


def test_straggler_min_rule_spanning_job():
    """A job spanning fast and slow servers is pinned to the slowest
    member: mixed fleet ≡ all-slow fleet for a cluster-wide job, and both
    are strictly slower than the homogeneous fleet."""
    n = TESTBED32.num_servers
    mixed = dataclasses.replace(
        TESTBED32, server_scale=(1.0,) * (n // 2) + (0.62,) * (n - n // 2))
    slow = dataclasses.replace(TESTBED32, server_scale=(0.62,) * n)
    jobs = _one_job(TESTBED32.num_gpus)        # spans every server
    jct_base = simulate(TESTBED32, copy.deepcopy(jobs), "ecmp").jcts[0]
    jct_mixed = simulate(mixed, copy.deepcopy(jobs), "ecmp").jcts[0]
    jct_slow = simulate(slow, copy.deepcopy(jobs), "ecmp").jcts[0]
    assert jct_mixed == jct_slow
    assert jct_mixed > jct_base


def test_faster_leaf_uplinks_never_hurt():
    """Over-provisioned leaf↔spine uplinks (leaf_ratio 2.0) can only help:
    mean JCT under contention is ≤ the homogeneous fabric's."""
    fat = dataclasses.replace(TESTBED32, leaf_uplink_gbps=200.0)
    jobs = _trace(60, 15.0, 16, 0)
    base = simulate(TESTBED32, copy.deepcopy(jobs), "ecmp").avg_jct
    fast = simulate(fat, copy.deepcopy(jobs), "ecmp").avg_jct
    assert fast <= base


def test_slower_nic_never_helps():
    """A 0.8× NIC tier bounds every flow below the homogeneous rate: mean
    JCT can only get worse."""
    thin = dataclasses.replace(TESTBED32, server_nic_gbps=80.0)
    jobs = _trace(60, 15.0, 16, 0)
    base = simulate(TESTBED32, copy.deepcopy(jobs), "ecmp").avg_jct
    slow = simulate(thin, copy.deepcopy(jobs), "ecmp").avg_jct
    assert slow >= base


# ---------------------------------------------------------------------------
# fairshare: the unit NIC bound is now the flow_cap parameter (satellite —
# hard-coded-capacity audit).  Homogeneous defaults must be byte-identical.
# ---------------------------------------------------------------------------

FLOWS = [["a", "b"], ["b"], [], ["a", "c"], ["c"], ["c"]]


def test_flow_cap_default_pins_homogeneous_rates():
    """The historical behaviour, pinned: default flow_cap=1.0 reproduces
    the exact progressive-filling rates of the unparametrised solver."""
    want = np.array([0.5, 0.5, 1.0, 1 / 3, 1 / 3, 1 / 3])
    np.testing.assert_array_equal(maxmin_fair_numpy(FLOWS), want)
    np.testing.assert_array_equal(maxmin_fair(FLOWS), want)
    np.testing.assert_allclose(maxmin_fair_jax(FLOWS), want, atol=2e-7)


def test_flow_cap_bounds_every_flow():
    for cap in (0.8, 0.5, 0.25):
        r = maxmin_fair_numpy(FLOWS, flow_cap=cap)
        assert r.max() <= cap
        # unconstrained (link-less) flows sit exactly at the NIC bound
        assert r[2] == cap
        # per-link sums still respect link capacity
        for link in ("a", "b", "c"):
            used = sum(r[i] for i, ls in enumerate(FLOWS) if link in ls)
            assert used <= 1.0 + 1e-12
        rj = maxmin_fair_jax(FLOWS, flow_cap=cap)
        np.testing.assert_allclose(rj, r, atol=2e-7)


def test_flow_cap_below_bottleneck_is_uniform():
    """When the NIC is the bottleneck everywhere, progressive filling
    freezes every flow at flow_cap in one round."""
    r = maxmin_fair_numpy([["x"], ["y"]], flow_cap=0.3)
    np.testing.assert_array_equal(r, [0.3, 0.3])


# ---------------------------------------------------------------------------
# Phase-offset (duty-cycle) primitives behind contention-affinity-time
# ---------------------------------------------------------------------------

def test_comm_duty_cycle_range_and_degenerate():
    for model, prof in PROFILES.items():
        # a 3D-parallel GPT row at its published GPU count (its layout
        # needs whole model replicas), every other profile at 8
        j = Job(job_id=0, model=model, num_gpus=prof.ref_gpus or 8,
                batch_size=prof.batch_ref, arrival=0.0, num_iters=1)
        assert 0.0 <= comm_duty_cycle(j) < 1.0
    single = Job(job_id=0, model="resnet50", num_gpus=1, batch_size=32,
                 arrival=0.0, num_iters=1)
    assert comm_duty_cycle(single) == 0.0


def test_comm_duty_cycle_separates_profiles():
    """The scoring signal exists: alltoall-heavy models (moe/dlrm) have
    strictly higher duty than overlap-covered allreduce models (resnet)."""
    def duty(model, batch):
        return comm_duty_cycle(Job(job_id=0, model=model, num_gpus=8,
                                   batch_size=batch, arrival=0.0,
                                   num_iters=1))
    assert duty("resnet50", 32) == 0.0      # β-overlap covers the allreduce
    assert duty("moe", 8) > 0.3
    assert duty("dlrm", 256) > duty("moe", 8)


def test_duty_overflow_semantics():
    assert duty_overflow([]) == 0.0
    assert duty_overflow([0.4, 0.5]) == 0.0          # interleavable
    assert duty_overflow([0.7, 0.6]) == pytest.approx(0.3)
    # fsum-backed: permutation invariant bit-for-bit
    vals = [0.31, 0.47, 0.113, 0.29]
    assert duty_overflow(vals) == duty_overflow(list(reversed(vals)))


def test_affinity_time_degenerates_to_affinity_without_duty():
    """With an all-compute-bound workload every duty score is 0, and the
    time-aware plugin must reproduce contention-affinity's placements
    bit-for-bit (the tie falls through to the offset-blind keys)."""
    jobs = [Job(job_id=i, model="resnet50", num_gpus=8, batch_size=32,
                arrival=60.0 * i, num_iters=200) for i in range(20)]
    ra = simulate(TESTBED32, copy.deepcopy(jobs), "contention-affinity")
    rt = simulate(TESTBED32, copy.deepcopy(jobs), "contention-affinity-time")
    _assert_reports_equal(rt, ra)


# ---------------------------------------------------------------------------
# CLI: sweep campaign --gpu-mix / --link-speeds (satellite — flag
# validation in the --events mold)
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("argv,frag", [
    (["--gpu-mix", "h100:1.0:0.5"], "sum to 1"),
    (["--gpu-mix", "h100:1.0"], "NAME:SCALE:FRACTION"),
    (["--gpu-mix", ":1.0:1.0"], "NAME:SCALE:FRACTION"),
    (["--gpu-mix", "h100:abc:1.0"], "non-numeric"),
    (["--gpu-mix", "h100:-1:1.0"], "positive"),
    (["--link-speeds", "spine=10"], "leaf"),
    (["--link-speeds", "leaf=fast"], "not a number"),
    (["--link-speeds", "leaf=-5"], "positive speed"),
    (["--link-speeds", "leaf="], "bad entry"),
], ids=["frac-sum", "two-fields", "empty-name", "nan-scale", "neg-scale",
        "bad-key", "nan-speed", "neg-speed", "empty-val"])
def test_cli_hetero_flag_validation(argv, frag, capsys):
    from repro.launch.sweep import campaign_main
    with pytest.raises(SystemExit) as ei:
        campaign_main(argv)
    assert ei.value.code == 2
    assert frag in capsys.readouterr().err


def test_cli_hetero_flags_cross_validate_and_run(capsys):
    """Both flags together on the testbed: the campaign runs on the
    combined spec and reports finished cells."""
    from repro.launch.sweep import campaign_main
    campaign_main(["--cluster", "testbed", "--strategies", "ecmp",
                   "--loads", "60", "--jobs", "20", "--max-gpus", "8",
                   "--seeds", "0",
                   "--gpu-mix", "h100:1.0:0.5,a100:0.62:0.5",
                   "--link-speeds", "leaf=200,nic=100"])
    out = capsys.readouterr().out
    assert "ecmp,fifo,60.0,20," in out


def test_cli_hetero_matches_library_path(capsys):
    """The CLI's spec surgery is exactly dataclasses.replace +
    apply_gpu_mix: the printed mean JCT matches a direct library run."""
    from repro.launch.sweep import campaign_main
    spec = apply_gpu_mix(
        dataclasses.replace(TESTBED32, leaf_uplink_gbps=200.0),
        [("h100", 1.0, 0.5), ("a100", 0.62, 0.5)])
    wl = WorkloadSpec(num_jobs=20, mean_interarrival=60.0, max_gpus=8)
    jobs = generate_trace(dataclasses.replace(wl, seed=0))
    want = simulate(spec, jobs, "ecmp").avg_jct
    campaign_main(["--cluster", "testbed", "--strategies", "ecmp",
                   "--loads", "60", "--jobs", "20", "--max-gpus", "8",
                   "--seeds", "0", "--gpu-mix", "h100:1.0:0.5,a100:0.62:0.5",
                   "--link-speeds", "leaf=200"])
    out = capsys.readouterr().out
    row = [l for l in out.splitlines() if l.startswith("ecmp,")][0]
    assert float(row.split(",")[4]) == round(want, 1)

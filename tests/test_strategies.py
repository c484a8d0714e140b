"""Strategy plugin API: registry, SimConfig, contention-affinity."""

import contextlib
import io

import numpy as np
import pytest

from repro.core import (CLUSTER512, IsolatedScheduler, SimConfig, Strategy,
                        WorkloadSpec, ClusterSimulator, generate_trace,
                        get_strategy, register_strategy,
                        registered_strategies, simulate, strategy_names,
                        unregister_strategy)
from repro.core.placement import Placement, PlacementFailure
from repro.core.simulator import STRATEGIES
from repro.core.strategies.builtin import locality_packed_place
from repro.core.topology import FabricState

BUILTINS = ("best", "sr", "ecmp", "balanced", "vclos", "ocs-vclos",
            "ocs-relax")


# ---------------------------------------------------------------------------
# registry
# ---------------------------------------------------------------------------

def test_builtins_registered_in_legacy_order():
    assert strategy_names()[:7] == BUILTINS
    assert "contention-affinity" in strategy_names()


def test_registry_metadata():
    assert get_strategy("vclos").isolated
    assert not get_strategy("vclos").memoize_failures   # MILP wall clock
    assert get_strategy("ecmp").memoize_failures
    assert get_strategy("ocs-vclos").requires_ocs
    assert get_strategy("ocs-vclos").wants_ocs_spec
    assert get_strategy("ocs-relax").wants_ocs_spec
    assert not get_strategy("ocs-relax").requires_ocs
    for name in strategy_names():
        assert get_strategy(name).description


def test_get_strategy_error_lists_registered_names():
    with pytest.raises(ValueError, match="unknown strategy") as ei:
        get_strategy("warp-drive")
    msg = str(ei.value)
    for name in ("ecmp", "contention-affinity"):
        assert name in msg


def test_duplicate_registration_rejected():
    class Dup(Strategy):
        name = "ecmp"
    with pytest.raises(ValueError, match="already registered"):
        register_strategy(Dup)


def test_strategies_alias_tuple_compat():
    """The deprecated alias stays drop-in for iteration, membership,
    indexing and concatenation; hashing fails loudly (a live view's hash
    would drift whenever a plugin registers — snapshot with tuple())."""
    assert STRATEGIES + ("mine",) == tuple(STRATEGIES) + ("mine",)
    assert ("x",) + STRATEGIES == ("x",) + tuple(STRATEGIES)
    assert list(STRATEGIES)[0] == STRATEGIES[0] == "best"
    with pytest.raises(TypeError, match="unhashable"):
        hash(STRATEGIES)


def test_traffic_views_are_read_only():
    """Placement-context traffic views must be immutable under both
    engines — a plugin mutating the v2 view would corrupt rate state."""
    for engine in ("v1", "v2"):
        sim = ClusterSimulator(CLUSTER512, "ecmp", engine=engine)
        load = sim.dense_link_load()
        with pytest.raises(ValueError):
            load[0] = 1
        assert sim.leaf_link_load().shape == (CLUSTER512.num_leafs,)


def test_strategies_alias_is_live_registry_view():
    """repro.core.simulator.STRATEGIES is a deprecated alias that can never
    drift from the registry: runtime registrations appear immediately."""
    assert tuple(STRATEGIES) == strategy_names()
    assert "ecmp" in STRATEGIES
    assert STRATEGIES == strategy_names()

    class Phantom(Strategy):
        name = "phantom-test-strategy"
        description = "registry drift canary"

        def place(self, ctx, job_id, num_gpus, job=None):
            return locality_packed_place(ctx, job_id, num_gpus)

    register_strategy(Phantom)
    try:
        assert "phantom-test-strategy" in STRATEGIES
        assert tuple(STRATEGIES) == strategy_names()
    finally:
        unregister_strategy("phantom-test-strategy")
    assert "phantom-test-strategy" not in STRATEGIES
    assert tuple(STRATEGIES) == strategy_names()


# ---------------------------------------------------------------------------
# registry round-trip: a toy plugin through the public API, both engines
# ---------------------------------------------------------------------------

def test_toy_strategy_round_trip_both_engines():
    """Register a strategy through the public API only and run it through
    simulate() on both engines — the plugin surface the tentpole promises."""

    @register_strategy
    class ReverseServerStrategy(Strategy):
        name = "toy-reverse"
        description = "locality packing from the highest server id down"

        def place(self, ctx, job_id, num_gpus, job=None):
            state, spec = ctx.state, ctx.spec
            free = state.server_free_array()
            # highest server that still fits (worst-fit flavour, but
            # deterministic) — else whole idle servers from the top
            cand = np.flatnonzero(free >= num_gpus)
            if num_gpus <= spec.gpus_per_server and len(cand):
                sv = int(cand[-1])
                return Placement(job_id,
                                 state.idle_gpus_of_server(sv)[:num_gpus],
                                 "server")
            return locality_packed_place(ctx, job_id, num_gpus)

    try:
        jobs = generate_trace(WorkloadSpec(num_jobs=50,
                                           mean_interarrival=120.0,
                                           seed=5, max_gpus=64))
        v1 = simulate(CLUSTER512, jobs, "toy-reverse", engine="v1")
        v2 = simulate(CLUSTER512, jobs, "toy-reverse", engine="v2")
        assert v1.n_finished == v2.n_finished == 50
        assert v1.jcts == v2.jcts
        assert v1.jwts == v2.jwts
    finally:
        unregister_strategy("toy-reverse")


def test_strategy_instance_accepted_without_registration():
    """SimConfig.strategy (and simulate's strategy arg) may be a Strategy
    instance — handy for throwaway experiments and test doubles."""
    class Inline(Strategy):
        name = "inline"

        def place(self, ctx, job_id, num_gpus, job=None):
            return locality_packed_place(ctx, job_id, num_gpus)

    jobs = generate_trace(WorkloadSpec(num_jobs=20, seed=2, max_gpus=32))
    rep = simulate(CLUSTER512, jobs, Inline())
    ref = simulate(CLUSTER512, jobs, "sr")
    assert rep.jcts == ref.jcts          # same placement + routing as sr
    assert "inline" not in strategy_names()


# ---------------------------------------------------------------------------
# SimConfig
# ---------------------------------------------------------------------------

def test_simconfig_matches_legacy_kwargs():
    """A SimConfig and the equivalent loose kwargs produce bit-identical
    schedules through both simulate() and ClusterSimulator."""
    jobs = generate_trace(WorkloadSpec(num_jobs=60, mean_interarrival=100.0,
                                       seed=9, max_gpus=128,
                                       deadline_slack=(1.5, 4.0)))
    for engine in ("v1", "v2"):
        legacy = simulate(CLUSTER512, jobs, "ecmp", scheduler="edf", seed=4,
                          engine=engine)
        cfg = SimConfig(strategy="ecmp", scheduler="edf", seed=4,
                        engine=engine)
        unified = simulate(CLUSTER512, jobs, config=cfg)
        assert legacy.jcts == unified.jcts
        assert legacy.jwts == unified.jwts
        assert legacy.slowdowns == unified.slowdowns
    sim = ClusterSimulator(CLUSTER512, "ecmp", scheduler="edf", seed=4)
    assert sim.config == SimConfig(strategy="ecmp", scheduler="edf", seed=4)


def test_simconfig_strategy_override():
    """Campaigns sweep one base config across cells by overriding the
    strategy alongside config= — same precedence rule in simulate() and
    ClusterSimulator (strategy beats config.strategy, config wins rest)."""
    jobs = generate_trace(WorkloadSpec(num_jobs=30, seed=1, max_gpus=64))
    base = SimConfig(scheduler="ff", seed=7)
    a = simulate(CLUSTER512, jobs, "sr", config=base)
    b = simulate(CLUSTER512, jobs, "sr", scheduler="ff", seed=7)
    assert a.jcts == b.jcts
    sim = ClusterSimulator(CLUSTER512, "sr",
                           config=SimConfig(strategy="ecmp", seed=7))
    assert sim.strategy == "sr" and sim.seed == 7
    # every loose kwarg explicitly passed alongside config= overrides that
    # config field — no silent discard
    sim2 = ClusterSimulator(CLUSTER512, config=SimConfig(engine="v2",
                                                         seed=7),
                            engine="v1", scheduler="ff")
    assert (sim2.engine, sim2.scheduler, sim2.seed) == ("v1", "ff", 7)
    v1 = simulate(CLUSTER512, jobs, config=SimConfig(strategy="ecmp"),
                  engine="v1")
    v2 = simulate(CLUSTER512, jobs, config=SimConfig(strategy="ecmp",
                                                     engine="v2"))
    assert v1.jcts == v2.jcts               # override took the v1 path


def test_simconfig_validation():
    with pytest.raises(ValueError, match="unknown strategy"):
        SimConfig(strategy="warp-drive")
    with pytest.raises(ValueError, match="queueing policy"):
        SimConfig(scheduler="sjf")
    with pytest.raises(ValueError, match="unknown engine"):
        SimConfig(engine="v3")
    with pytest.raises(ValueError, match="store"):
        SimConfig(store="bogus")
    with pytest.raises(ValueError, match="strategy name"):
        simulate(CLUSTER512, [], None)


def test_queue_policy_compatibility_enforced():
    class FifoOnly(Strategy):
        name = "fifo-only"
        queue_policies = ("fifo",)

        def place(self, ctx, job_id, num_gpus, job=None):
            return locality_packed_place(ctx, job_id, num_gpus)

    with pytest.raises(ValueError, match="does not support queueing"):
        ClusterSimulator(CLUSTER512, FifoOnly(), scheduler="ff")
    ClusterSimulator(CLUSTER512, FifoOnly(), scheduler="fifo")  # fine


def test_requires_ocs_enforced_at_construction():
    with pytest.raises(ValueError, match="OCS-equipped"):
        ClusterSimulator(CLUSTER512, "ocs-vclos")


def test_campaign_grid_rejects_incompatible_policy_cells():
    """Incompatible strategy × scheduler pairs fail at grid construction,
    not mid-campaign after other cells already ran."""
    from repro.core import CampaignGrid

    class FifoOnly(Strategy):
        name = "fifo-only-grid"
        queue_policies = ("fifo",)

        def place(self, ctx, job_id, num_gpus, job=None):
            return locality_packed_place(ctx, job_id, num_gpus)

    register_strategy(FifoOnly)
    try:
        with pytest.raises(ValueError, match="does not support queueing"):
            CampaignGrid(strategies=("ecmp", "fifo-only-grid"),
                         schedulers=("ff",))
        CampaignGrid(strategies=("fifo-only-grid",), schedulers=("fifo",))
    finally:
        unregister_strategy("fifo-only-grid")


def test_campaign_workers_with_instance_strategy_config():
    """A SimConfig holding an (unpicklable, locally defined) Strategy
    instance still shards across workers: cells travel by grid name."""
    from repro.core import CampaignGrid, run_campaign

    class Local(Strategy):
        name = "local-instance"

        def place(self, ctx, job_id, num_gpus, job=None):
            return locality_packed_place(ctx, job_id, num_gpus)

    grid = CampaignGrid(strategies=("sr", "ecmp"), loads=(200.0,), seeds=(0,))
    wl = WorkloadSpec(num_jobs=20, max_gpus=64)
    res = run_campaign(CLUSTER512, grid, workload=wl, workers=2,
                       config=SimConfig(strategy=Local()))
    assert [c.strategy for c in res.cells] == ["sr", "ecmp"]
    assert all(c.report.n_finished == 20 for c in res.cells)


# ---------------------------------------------------------------------------
# IsolatedScheduler over the registry
# ---------------------------------------------------------------------------

def test_isolated_scheduler_serves_grantable_only():
    with pytest.raises(ValueError, match="grantable"):
        IsolatedScheduler(CLUSTER512, strategy="ecmp")
    sched = IsolatedScheduler(CLUSTER512, strategy="vclos")
    grant = sched.submit(0, 64)
    assert grant is not None and len(grant.placement.gpus) >= 64
    sched.release(0)
    assert sched.utilization() == 0.0
    # the facade honours the Strategy.place fast-fail contract: an
    # oversized request fails "gpu" without ever dispatching to the plugin
    assert sched.submit(1, CLUSTER512.num_gpus + 8) is None
    assert sched.last_failure == "gpu"


# ---------------------------------------------------------------------------
# contention-affinity
# ---------------------------------------------------------------------------

def test_contention_affinity_avoids_loaded_leafs():
    """The placement context is duck-typed: drive the strategy with a test
    double and check multi-leaf jobs steer around busy leafs."""
    spec = CLUSTER512

    class Ctx:
        def __init__(self, load):
            self.spec = spec
            self.state = FabricState(spec)
            self.seed = 0
            self.ilp_time_limit = 2.0
            self._leaf_load = np.asarray(load, dtype=np.int64)

        def leaf_link_load(self):
            return self._leaf_load

    # leafs 0/1 busy, the rest quiet: a 2-leaf job must land on leafs 2+3
    load = np.zeros(spec.num_leafs, dtype=np.int64)
    load[0] = 40
    load[1] = 25
    ctx = Ctx(load)
    p = get_strategy("contention-affinity").place(ctx, 0,
                                                 2 * spec.gpus_per_leaf)
    leafs = sorted({spec.leaf_of_gpu(g) for g in p.gpus})
    assert leafs == [2, 3]

    # all-quiet fabric: ties break toward the lowest leaf ids
    p2 = get_strategy("contention-affinity").place(Ctx(np.zeros(16)), 1,
                                                  2 * spec.gpus_per_leaf)
    assert sorted({spec.leaf_of_gpu(g) for g in p2.gpus}) == [0, 1]


def test_contention_affinity_no_worse_than_ecmp_on_contention():
    """Same routing as ecmp, traffic-aware placement: pooled contention
    ratio must not regress vs the ecmp baseline on a shared trace."""
    jobs = generate_trace(WorkloadSpec(num_jobs=120, mean_interarrival=100.0,
                                       seed=0, max_gpus=128))
    aff = simulate(CLUSTER512, jobs, "contention-affinity")
    ecmp = simulate(CLUSTER512, jobs, "ecmp")
    assert aff.n_finished == ecmp.n_finished == 120
    assert float(np.mean(aff.slowdowns)) <= float(np.mean(ecmp.slowdowns)) \
        + 1e-9


@pytest.mark.parametrize("scheduler", ["fifo", "ff", "edf"])
def test_contention_affinity_all_queue_policies(scheduler):
    jobs = generate_trace(WorkloadSpec(num_jobs=40, mean_interarrival=120.0,
                                       seed=3, max_gpus=64,
                                       deadline_slack=(1.5, 4.0)))
    rep = simulate(CLUSTER512, jobs, "contention-affinity",
                   scheduler=scheduler)
    assert rep.n_finished == 40


def test_contention_affinity_campaign_cli_both_engines():
    """End-to-end through the campaign CLI under both engines."""
    from repro.launch.sweep import campaign_main

    outputs = {}
    for engine in ("v1", "v2"):
        buf = io.StringIO()
        with contextlib.redirect_stdout(buf):
            campaign_main(["--strategies", "contention-affinity,ecmp",
                           "--jobs", "30", "--max-gpus", "64",
                           "--loads", "200", "--engine", engine])
        outputs[engine] = buf.getvalue()
        assert "contention-affinity,fifo,200.0,30" in outputs[engine]
    # engines print identical aggregate tables (bit-identical schedules)
    tail = lambda s: s[s.index("strategy,scheduler"):]
    assert tail(outputs["v1"]) == tail(outputs["v2"])


def test_list_strategies_cli():
    from repro.launch.sweep import campaign_main

    buf = io.StringIO()
    with contextlib.redirect_stdout(buf):
        campaign_main(["--list-strategies"])
    out = buf.getvalue()
    for name in strategy_names():
        assert name in out
        assert registered_strategies()[name].description.split()[0] in out

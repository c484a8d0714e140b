"""Max-min fair bandwidth allocation (water-filling).

The flow-level simulator's inner solver (RapidNetSim-style, §9.1): given a
flow×link incidence structure and per-link capacities, compute each flow's
max-min fair rate.  Classic progressive filling: repeatedly find the
bottleneck link (smallest capacity/active-flow ratio), freeze its flows at
that fair share, remove the frozen bandwidth, repeat.

Two implementations:
  * :func:`maxmin_fair_numpy` — sparse dict-based, used for small phases.
  * :func:`maxmin_fair_jax`   — dense ``jnp`` + ``lax.while_loop`` version
    (the "composable JAX module" form); vectorised over links so thousands
    of concurrent flows solve in a handful of fused XLA iterations.

Both return rates in the same units as capacities (fraction of link rate
when capacities are 1.0).
"""

from __future__ import annotations

from functools import partial
from typing import Dict, Hashable, List, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np


def maxmin_fair_numpy(flow_links: Sequence[Sequence[Hashable]],
                      capacity: Dict[Hashable, float] | float = 1.0,
                      flow_cap: float = 1.0) -> np.ndarray:
    """Progressive filling over an explicit link list per flow.

    flow_links[i] — links used by flow i (empty ⇒ unconstrained, rate
    ``flow_cap``).  ``flow_cap`` is the per-flow rate ceiling — the
    server-NIC tier: no flow can exceed its host NIC regardless of fabric
    headroom.  The historical hard-coded ``1.0`` assumed a homogeneous
    fabric; on per-tier-speed specs derive it from the spec instead
    (``spec.nic_ratio``, docs/heterogeneous.md).  The default reproduces
    the homogeneous behaviour bit-for-bit (tests/test_hetero.py).
    """
    nflows = len(flow_links)
    rates = np.full(nflows, float(flow_cap))
    links: Dict[Hashable, List[int]] = {}
    for i, ls in enumerate(flow_links):
        for l in ls:
            links.setdefault(l, []).append(i)
    if not links:
        return rates
    cap = {l: (capacity if isinstance(capacity, (int, float))
               else capacity.get(l, 1.0)) for l in links}
    remaining = dict(cap)
    active = {l: set(fs) for l, fs in links.items()}
    frozen = np.zeros(nflows, dtype=bool)
    # flows with no links are unconstrained
    for i, ls in enumerate(flow_links):
        if not ls:
            frozen[i] = True
    while True:
        # bottleneck link = min remaining/|active|
        best, best_share = None, np.inf
        for l, fs in active.items():
            if not fs:
                continue
            share = remaining[l] / len(fs)
            if share < best_share - 1e-15:
                best, best_share = l, share
        if best is None:
            break
        share = min(best_share, flow_cap)  # NIC-bounded: flow ≤ its NIC rate
        for i in list(active[best]):
            rates[i] = share
            frozen[i] = True
            for l in flow_links[i]:
                if i in active.get(l, ()):  # remove from all its links
                    active[l].discard(i)
                    remaining[l] -= share
        if share >= flow_cap:
            # everything else is also NIC-limited; clamp and exit
            rates[~frozen] = flow_cap
            break
    return np.clip(rates, 0.0, flow_cap)


@partial(jax.jit, static_argnames=("max_iters",))
def _maxmin_kernel(incidence: jnp.ndarray, cap: jnp.ndarray,
                   flow_cap: jnp.ndarray,
                   max_iters: int = 0) -> jnp.ndarray:
    """incidence: (links, flows) 0/1; cap: (links,); flow_cap: scalar
    per-flow ceiling (the NIC tier).  Returns (flows,)."""
    nlinks, nflows = incidence.shape
    iters = max_iters or nlinks + 1

    def body(state):
        rates, frozen, remaining, it = state
        act = incidence * (1.0 - frozen)[None, :]
        nact = act.sum(axis=1)
        share = jnp.where(nact > 0, remaining / jnp.maximum(nact, 1), jnp.inf)
        share = jnp.minimum(share, flow_cap)
        b = jnp.argmin(share)
        s = share[b]
        hit = act[b] > 0          # flows on the bottleneck link
        any_hit = hit.any()
        new_rates = jnp.where(hit, s, rates)
        new_frozen = jnp.where(hit, 1.0, frozen)
        # subtract frozen bandwidth from every link these flows touch
        used = (incidence * hit[None, :]).sum(axis=1) * s
        new_remaining = remaining - used
        done = jnp.logical_not(any_hit)
        rates = jnp.where(done, rates, new_rates)
        frozen = jnp.where(done, frozen, new_frozen)
        remaining = jnp.where(done, remaining, new_remaining)
        return rates, frozen, remaining, it + 1

    def cond(state):
        rates, frozen, remaining, it = state
        act = incidence * (1.0 - frozen)[None, :]
        return jnp.logical_and(act.sum() > 0, it < iters)

    rates0 = jnp.full(nflows, flow_cap, dtype=jnp.float32)
    frozen0 = (incidence.sum(axis=0) == 0).astype(jnp.float32)
    state = jax.lax.while_loop(
        cond, body, (rates0, frozen0, cap.astype(jnp.float32), 0))
    return jnp.clip(state[0], 0.0, flow_cap)


def maxmin_fair_jax(flow_links: Sequence[Sequence[Hashable]],
                    capacity: Dict[Hashable, float] | float = 1.0,
                    flow_cap: float = 1.0) -> np.ndarray:
    """Dense-incidence wrapper around the jitted water-filling kernel.
    ``flow_cap`` as in :func:`maxmin_fair_numpy`."""
    nflows = len(flow_links)
    link_ids: Dict[Hashable, int] = {}
    for ls in flow_links:
        for l in ls:
            link_ids.setdefault(l, len(link_ids))
    if not link_ids:
        return np.full(nflows, float(flow_cap))
    inc = np.zeros((len(link_ids), nflows), dtype=np.float32)
    for i, ls in enumerate(flow_links):
        for l in ls:
            inc[link_ids[l], i] = 1.0
    if isinstance(capacity, (int, float)):
        cap = np.full(len(link_ids), float(capacity), dtype=np.float32)
    else:
        cap = np.array([capacity.get(l, 1.0) for l in link_ids],
                       dtype=np.float32)
    return np.asarray(_maxmin_kernel(
        jnp.asarray(inc), jnp.asarray(cap),
        jnp.float32(flow_cap)))


def maxmin_fair(flow_links, capacity=1.0, backend: str = "numpy",
                flow_cap: float = 1.0) -> np.ndarray:
    if backend == "jax":
        return maxmin_fair_jax(flow_links, capacity, flow_cap)
    if backend == "auto":
        return maxmin_fair_auto(flow_links, capacity, flow_cap)
    return maxmin_fair_numpy(flow_links, capacity, flow_cap)


# ---------------------------------------------------------------------------
# Auto-dispatch: numpy for small solves, the jitted JAX kernel above an
# auto-tuned crossover size.  "Size" is the dense incidence entry count
# (flows × distinct links) — what the JAX kernel actually materialises.
# ---------------------------------------------------------------------------

#: Below this dense size the numpy path always wins (and the auto path never
#: pays JIT warm-up); above it the measured crossover decides.
AUTOTUNE_FLOOR = 1 << 16

_CROSSOVER_ENV = "REPRO_MAXMIN_CROSSOVER"
_crossover: Dict[str, float] = {}          # {"value": size} once resolved


def problem_size(flow_links: Sequence[Sequence[Hashable]]) -> int:
    """Dense incidence entries of one max-min problem (flows × links)."""
    links = set()
    for ls in flow_links:
        links.update(ls)
    return len(flow_links) * len(links)


def _bench_once(fn, flow_links) -> float:
    import time
    fn(flow_links)                         # warm (JIT compile / allocator)
    best = float("inf")
    for _ in range(3):
        t0 = time.perf_counter()
        fn(flow_links)
        best = min(best, time.perf_counter() - t0)
    return best


def autotune_crossover(probe_flows: Sequence[int] = (64, 256, 1024, 4096),
                       nlinks: int = 64, seed: int = 0) -> float:
    """Measure numpy vs JAX water-filling over growing problem sizes and
    return the smallest dense size where the JAX kernel wins (``inf`` when
    it never does — the common case on host-only builds).  The result is
    cached module-wide; ``REPRO_MAXMIN_CROSSOVER`` overrides it."""
    rng = np.random.default_rng(seed)
    crossover = float("inf")
    for nflows in probe_flows:
        flow_links = [rng.choice(nlinks, size=3, replace=False).tolist()
                      for _ in range(nflows)]
        t_np = _bench_once(maxmin_fair_numpy, flow_links)
        t_jx = _bench_once(maxmin_fair_jax, flow_links)
        if t_jx < t_np:
            crossover = problem_size(flow_links)
            break
    return crossover


def maxmin_crossover() -> float:
    """Resolved numpy→JAX crossover size (env override > cached autotune)."""
    import os
    if "value" not in _crossover:
        env = os.environ.get(_CROSSOVER_ENV)
        if env is not None:
            _crossover["value"] = float(env)
        else:
            _crossover["value"] = autotune_crossover()
    return _crossover["value"]


def maxmin_fair_auto(flow_links: Sequence[Sequence[Hashable]],
                     capacity: Dict[Hashable, float] | float = 1.0,
                     flow_cap: float = 1.0) -> np.ndarray:
    """Size-dispatched max-min: sparse numpy below the crossover, the dense
    jitted JAX kernel above it.  Both solvers agree to float32 resolution
    (asserted by ``tests/test_simulator.py``)."""
    size = problem_size(flow_links)
    if size < AUTOTUNE_FLOOR or size < maxmin_crossover():
        return maxmin_fair_numpy(flow_links, capacity, flow_cap)
    return maxmin_fair_jax(flow_links, capacity, flow_cap)


# ---------------------------------------------------------------------------
# Batched bottleneck solve for the v2 simulator engine: per-phase worst link
# load over a CSR-style (values, row-pointer) layout.  Integer in/out, so the
# numpy and JAX paths are bit-identical by construction and the engine's
# schedules cannot depend on the dispatch decision.
# ---------------------------------------------------------------------------

def phase_worst_numpy(vals: np.ndarray, ptr: np.ndarray) -> np.ndarray:
    """``out[i] = max(vals[ptr[i]:ptr[i+1]])`` (0 for empty segments)."""
    nseg = len(ptr) - 1
    out = np.zeros(nseg, dtype=np.int64)
    if not len(vals):
        return out
    width = np.diff(ptr)
    nonempty = width > 0
    if nonempty.any():
        # reduceat over non-empty starts only: each reduction spans to the
        # next non-empty start, absorbing the interleaved empty segments
        # (which contribute nothing) — sidesteps reduceat's empty-segment
        # misbehaviour (it would return vals[ptr[i]])
        out[nonempty] = np.maximum.reduceat(vals, ptr[:-1][nonempty])
    return out


@partial(jax.jit, static_argnames=("num_segments",))
def _segment_max_kernel(vals: jnp.ndarray, seg: jnp.ndarray,
                        num_segments: int) -> jnp.ndarray:
    out = jax.ops.segment_max(vals, seg, num_segments=num_segments)
    return jnp.maximum(out, 0)         # empty segments -> 0, not int-min


def phase_worst_jax(vals: np.ndarray, ptr: np.ndarray) -> np.ndarray:
    """JAX twin of :func:`phase_worst_numpy` (identical integer output).

    Pads values and segment count to powers of two so the jitted kernel
    is reused across the engine's (ragged) event-time batch shapes."""
    nseg = len(ptr) - 1
    if not len(vals):
        return np.zeros(nseg, dtype=np.int64)
    seg = np.repeat(np.arange(nseg, dtype=np.int32), np.diff(ptr))
    n = 1 << int(np.ceil(np.log2(max(len(vals), 1))))
    nseg_pad = 1 << int(np.ceil(np.log2(max(nseg, 1))))
    vp = np.zeros(n, dtype=np.int32)
    vp[:len(vals)] = vals
    sp = np.full(n, nseg_pad - 1, dtype=np.int32)
    sp[:len(vals)] = seg
    out = np.asarray(_segment_max_kernel(jnp.asarray(vp),
                                         jnp.asarray(sp), nseg_pad))
    res = out[:nseg].astype(np.int64)
    if nseg == nseg_pad and len(vals) < n:
        # padding shared the last real segment: recompute it exactly
        res[-1] = vals[ptr[-2]:].max() if ptr[-1] > ptr[-2] else 0
    return res


#: numpy→Pallas dispatch size for :func:`phase_worst_loads`.  Resolved from
#: ``REPRO_PHASE_WORST_CROSSOVER`` once; default ``inf`` (numpy) — numpy
#: wins at every recorded dispatch shape, on the host and on a TPU v5e
#: chip alike (``chip_smoke.py`` and ``benchmarks/bench_fairshare.py``
#: time both; PERF.md).  Deliberately *not* autotuned inline:
#: a JIT-compiling benchmark must never fire mid-simulation, and the
#: water-filling crossover above is tuned on a different kernel.
_PW_CROSSOVER_ENV = "REPRO_PHASE_WORST_CROSSOVER"
_pw_crossover: Dict[str, float] = {}


def phase_worst_crossover() -> float:
    import os
    if "value" not in _pw_crossover:
        _pw_crossover["value"] = float(
            os.environ.get(_PW_CROSSOVER_ENV, "inf"))
    return _pw_crossover["value"]


def pin_host_dispatch() -> None:
    """Pin this process's ``"auto"`` dispatch to numpy at every size.

    The initializer of campaign pool workers (``repro.core.runtime``): a
    chip belongs to one process, so a worker never reaches for it, whatever
    ``REPRO_PHASE_WORST_CROSSOVER`` says (docs/robustness.md)."""
    _pw_crossover["value"] = float("inf")


def phase_worst_loads(vals: np.ndarray, ptr: np.ndarray,
                      backend: str = "auto") -> np.ndarray:
    """Batched per-phase bottleneck loads with numpy↔accelerator size
    dispatch — the contended-subgraph solve of the v2/batched engines' rate
    resolution.  Integer in/out, so the dispatch can never change a
    schedule.  ``backend``: ``"numpy"`` / ``"jax"`` / ``"pallas"`` force a
    path; ``"auto"`` uses numpy below the crossover and the Pallas kernel
    above it.  A kernel that fails to lower or compile raises: no backend
    stands in for another."""
    if backend == "numpy":
        return phase_worst_numpy(vals, ptr)
    if backend == "jax":
        return phase_worst_jax(vals, ptr)
    if backend != "pallas" and len(vals) < phase_worst_crossover():
        return phase_worst_numpy(vals, ptr)
    # deferred: repro.kernels also carries the LM kernels, which the
    # numpy-only hot path never needs
    from repro.kernels.phase_max import phase_worst_pallas
    return phase_worst_pallas(vals, ptr)

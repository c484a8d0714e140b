"""OCS-vClos: optical-circuit-switch assisted vClos (paper §7 + Appendix A.3).

Pipeline (Algorithm 2):
  * Stage 0/1 — identical to vClos (single server / single leaf).
  * Stage 2  — single-spine virtual Clos: rewire idle circuits so every job
    GPU's uplink lands on one spine; any permutation is then contention-free
    (each GPU owns its uplink and its downlink).  Includes the paper's
    special 2-leaf case: direct leaf↔leaf OCS circuits using **zero** spine
    ports (Fig. 3).
  * Stage 3  — OCSFINDCLOS (Algorithm 4): general ``l × s`` vClos where link
    capacity is *made* by rewiring rather than found.  We solve the
    aggregated port-count ILP (eqs. 7–11 with the per-OCS index summed out —
    exact port-conservation constraints, see DESIGN.md) and then realise the
    circuits per OCS with greedy swaps; realisation failure falls back to
    the next (l, s) candidate.

Only *idle* circuits are ever moved (50 ms OCS switching would drop live
traffic, §7): a circuit is movable iff the (leaf, spine) channel it realises
has spare unreserved capacity.
"""

from __future__ import annotations

import math
from dataclasses import dataclass
from typing import Dict, List, Optional, Tuple

import numpy as np

from repro import obs

from .placement import (Placement, PlacementFailure, VirtualClos,
                        stage0_server, stage1_leaf, _factorizations,
                        candidate_sizes)
from .topology import ClusterSpec, FabricState, ocs_ports


# ---------------------------------------------------------------------------
# Port budget of one placement call
# ---------------------------------------------------------------------------

class PortBudget:
    """The fabric's port counts, taken once at the top of a placement call
    and shared by every (l, s) candidate of its search.

    Lists indexed by leaf ``n`` or spine ``m``:

    * ``idle[n]``: fully idle servers of leaf n;
    * ``cap[n][m]``, ``reserved[n][m]``, ``spare[n][m]``: channels the
      circuits make, channels jobs hold, and the difference;
    * ``leaf_free[n]``: rewirable uplink ports of leaf n, as
      :meth:`FabricState.leaf_free_ports_ocs` counts them;
    * ``spine_free[m]``: unreserved downlink channels of spine m, as
      :meth:`FabricState.spine_free_ports` counts them.

    The counts hold only while the fabric is unchanged: a budget never
    outlives the call that took it, nor any commit, release or rewire.
    """

    __slots__ = ("idle", "cap", "reserved", "spare", "leaf_free",
                 "spine_free")

    def __init__(self, state: FabricState):
        obs.count("ocs.budget")
        spec = state.spec
        self.idle = state.idle_server_counts().tolist()
        self.cap = state.capacity()
        self.reserved = state.reserved_matrix()
        held = [0] * spec.num_leafs
        if state.xconn_owner:
            ports = ocs_ports(spec)
            for k, lp in state.xconn_owner:
                held[ports[k].leaf_of[lp]] += 1
        self.spare = [[c - r for c, r in zip(crow, rrow)]
                      for crow, rrow in zip(self.cap, self.reserved)]
        self.leaf_free = [spec.uplinks_per_leaf - sum(rrow) - h
                          for rrow, h in zip(self.reserved, held)]
        self.spine_free = [sum(col) for col in zip(*self.spare)]


# ---------------------------------------------------------------------------
# Rewiring engine
# ---------------------------------------------------------------------------

class RewirePlanner:
    """Plans circuit swaps to create requested (leaf, spine) capacity.

    Works against live OCS state; movable = circuit whose channel has spare
    (unreserved, unpinned) capacity.  All moves are collected and applied
    atomically by the caller via ``apply``.  A ``budget`` of the same,
    unchanged fabric saves recounting its spare channels.

    Lookups walk only the ports of the leaf and spine at hand, through the
    shape's port index (:class:`~repro.core.topology.OCSPorts`), against
    two structures every move keeps current: ``_wired[k]``, OCS k's
    circuits from the spine side, and ``_taken``, the leaf ports held by
    cross-connects, live or planned.
    """

    def __init__(self, state: FabricState,
                 budget: Optional[PortBudget] = None):
        assert state.ocs is not None, "OCS layer required"
        self.state = state
        self.spec = state.spec
        self.ocs = state.ocs
        # working copies (ensure pins channels by decrementing spare)
        self.circuits = [dict(c) for c in self.ocs.circuits]
        spare = budget.spare if budget is not None else state.free_capacity()
        self.spare = [row[:] for row in spare]
        self.moves: List[Tuple[int, int, int]] = []     # (k, leaf_port, spine_port)
        self.unwired: List[Tuple[int, int, int]] = []   # (k, leaf_port, old spine port)
        self._ports = ocs_ports(self.spec)
        self._wired = [{sp: lp for lp, sp in c.items()} for c in self.circuits]
        self._taken = set(state.xconn_owner)

    # -- lookups over the working copy --------------------------------------
    def _movable_leaf_port(self, k: int, leaf: int,
                           avoid_spine: Optional[int] = None) -> Optional[int]:
        ports, circuits = self._ports[k], self.circuits[k]
        row, taken = self.spare[leaf], self._taken
        for lp in ports.leaf_ports_of[leaf]:
            if (k, lp) in taken:
                continue
            sp = circuits.get(lp)
            if sp is None:
                return lp  # unwired: free to use
            m = ports.spine_of[sp]
            if m == avoid_spine:
                continue  # already on the target spine — moving it is a no-op
            if row[m] > 0:
                return lp
        return None

    def _free_spine_port(self, k: int, spine: int,
                         for_leaf: Optional[int] = None) -> Optional[int]:
        """A spine-side port on OCS k that is unwired (preferred — no
        eviction) or ends a movable circuit.  Never evicts a circuit from
        ``for_leaf`` itself — that would undo the channel being built."""
        wired, leaf_of = self._wired[k], self._ports[k].leaf_of
        evictable = None
        for sp in self._ports[k].spine_ports_of[spine]:
            lp = wired.get(sp)
            if lp is None:
                return sp
            if evictable is None:
                n2 = leaf_of[lp]
                if n2 != for_leaf and self.spare[n2][spine] > 0:
                    evictable = sp
        return evictable

    # -- operations -----------------------------------------------------------
    def _headroom(self, k: int, leaf: int, spine: int) -> int:
        """How much slack OCS k has for a (leaf, spine) circuit: counts of
        movable leaf ports × available spine ports (0 when either missing)."""
        ports, circuits = self._ports[k], self.circuits[k]
        spine_of, spare, taken = ports.spine_of, self.spare, self._taken
        row = spare[leaf]
        nl = 0
        for lp in ports.leaf_ports_of[leaf]:
            if (k, lp) in taken:
                continue
            sp = circuits.get(lp)
            if sp is None:
                nl += 1
                continue
            m = spine_of[sp]
            if m != spine and row[m] > 0:
                nl += 1
        if nl == 0:
            return 0
        wired, leaf_of = self._wired[k], ports.leaf_of
        ns = 0
        for sp in ports.spine_ports_of[spine]:
            lp = wired.get(sp)
            if lp is None:
                ns += 2  # unwired spine port: cheapest (no eviction)
                continue
            n2 = leaf_of[lp]
            if n2 != leaf and spare[n2][spine] > 0:
                ns += 1
        return min(nl, ns) if ns else 0

    def add_channel(self, leaf: int, spine: int) -> bool:
        """Create one extra channel leaf→spine, choosing the OCS with the
        most remaining slack (load-balances circuits across OCSes so later
        demands don't starve).  Nothing moves before a move returns, so
        each OCS's headroom is taken once."""
        obs.count("ocs.channels")
        head = [self._headroom(k, leaf, spine)
                for k in range(self.spec.num_ocs)]
        order = sorted(range(self.spec.num_ocs), key=lambda k: -head[k])
        for k in order:
            if head[k] <= 0:
                break
            lp = self._movable_leaf_port(k, leaf, avoid_spine=spine)
            if lp is None:
                continue
            sp = self._free_spine_port(k, spine, for_leaf=leaf)
            if sp is None:
                continue
            ports, circuits = self._ports[k], self.circuits[k]
            wired, spare = self._wired[k], self.spare
            lp2 = wired.get(sp)
            # 1. detach lp from its old spine port (frees old channel)
            old_sp = circuits.pop(lp, None)
            if old_sp is not None:
                del wired[old_sp]
                spare[leaf][ports.spine_of[old_sp]] -= 1  # channel disappears
            # 2. evict the circuit currently on sp, if any — rehome its leaf
            #    port onto lp's old spine port (classic 2-swap)
            if lp2 is not None and lp2 != lp:
                n2 = ports.leaf_of[lp2]
                spare[n2][spine] -= 1
                del circuits[lp2]
                del wired[sp]
                if old_sp is not None:
                    circuits[lp2] = old_sp
                    wired[old_sp] = lp2
                    spare[n2][ports.spine_of[old_sp]] += 1
                    self.moves.append((k, lp2, old_sp))
            # 3. wire lp -> sp
            circuits[lp] = sp
            wired[sp] = lp
            spare[leaf][spine] += 1
            self.moves.append((k, lp, sp))
            return True
        return False

    def ensure(self, need: Dict[Tuple[int, int], int]) -> bool:
        """Create capacity so every (n, m) has ≥ need[n, m] spare channels.

        Pins created channels so later swaps cannot cannibalise them.
        Bounded by the total port count — a livelock guard, not a budget.
        """
        with obs.span("ocs.rewire"):
            guard = 4 * self.spec.num_leafs * self.spec.uplinks_per_leaf
            for (n, m), cnt in sorted(need.items()):
                while self.spare[n][m] < cnt:
                    guard -= 1
                    if guard <= 0 or not self.add_channel(n, m):
                        return False
                self.spare[n][m] -= cnt  # pin
            return True

    def take_xconn(self, leaf_a: int, leaf_b: int, count: int) -> bool:
        """Unwire `count` movable ports on each of two leafs sharing an OCS
        and patch them pairwise (2-leaf direct case, zero spine ports).
        Original circuits are recorded so release can restore them."""
        done = 0
        for k in range(self.spec.num_ocs):
            while done < count:
                pa = self._movable_leaf_port(k, leaf_a)
                pb = self._movable_leaf_port(k, leaf_b)
                if pa is None or pb is None:
                    break  # need both endpoints on the same OCS
                for p in (pa, pb):
                    orig = self.circuits[k].get(p)
                    self._unwire(k, p)
                    self.unwired.append((k, p, -1 if orig is None else orig))
                    self._taken.add((k, p))
                done += 1
            if done >= count:
                return True
        return done >= count

    def _unwire(self, k: int, lp: int) -> None:
        sp = self.circuits[k].pop(lp, None)
        if sp is not None:
            del self._wired[k][sp]
            ports = self._ports[k]
            self.spare[ports.leaf_of[lp]][ports.spine_of[sp]] -= 1

    def apply(self) -> None:
        """Write the planned circuit layout back to the live OCS."""
        self.ocs.circuits = [dict(c) for c in self.circuits]


# ---------------------------------------------------------------------------
# Stage 2: single spine (incl. 2-leaf direct)
# ---------------------------------------------------------------------------

def collect_idle_servers(state: FabricState, n_servers: int,
                         max_leafs: Optional[int] = None) -> Optional[List[int]]:
    """Pick idle servers best-fit across leafs (fewest idle servers first).
    Public building block for strategy plugins (docs/strategies.md)."""
    counts = state.idle_server_counts()
    by_leaf = sorted((int(c), n) for n, c in enumerate(counts.tolist()) if c)
    servers: List[int] = []
    leafs_used = 0
    for _, leaf in by_leaf:
        if max_leafs is not None and leafs_used >= max_leafs:
            break
        idle = state.idle_servers_of_leaf(leaf)
        take = min(len(idle), n_servers - len(servers))
        servers.extend(idle[:take])
        leafs_used += 1
        if len(servers) >= n_servers:
            return servers
    return None


# deprecated alias (pre-registry name)
_collect_servers = collect_idle_servers


def _stage2_single_spine(state: FabricState, job_id: int,
                         n: int) -> Optional[Placement]:
    spec = state.spec
    req_servers = math.ceil(n / spec.gpus_per_server)
    servers = collect_idle_servers(state, req_servers)
    if servers is None:
        return None
    leafs_cnt: Dict[int, int] = {}
    for sv in servers:
        leaf = spec.leaf_of_server(sv)
        leafs_cnt[leaf] = leafs_cnt.get(leaf, 0) + 1

    # --- 2-leaf direct OCS cross-connect (zero spine ports, Fig. 3) -------
    if len(leafs_cnt) == 2 and state.ocs is not None:
        (la, ca), (lb, cb) = sorted(leafs_cnt.items())
        circuits = min(ca, cb) * spec.gpus_per_server
        planner = RewirePlanner(state)
        if planner.take_xconn(la, lb, circuits):
            planner.apply()
            gpus = [g for sv in servers for g in spec.gpus_of_server(sv)][:n]
            vc = VirtualClos(leafs=[la, lb], spines=[], links={},
                             gpus_per_leaf=max(ca, cb) * spec.gpus_per_server)
            return Placement(job_id, gpus, "ocs-xconn", vclos=vc,
                             xconn_ports=list(planner.unwired))

    if state.ocs is None or len(leafs_cnt) < 2:
        return None
    # --- single spine: every cross-leaf GPU needs one channel to spine m ---
    # choose spine best-fit: fewest-but-enough free downlink channels
    cap = state.capacity()
    cands = []
    for m in range(spec.num_spines):
        free = state.spine_free_ports(m, cap)
        if free >= n:
            cands.append((free, m))
    if not cands:
        return None
    cands.sort()
    for _, m in cands:
        need = {(leaf, m): cnt * spec.gpus_per_server
                for leaf, cnt in leafs_cnt.items()}
        planner = RewirePlanner(state)
        if planner.ensure(need):
            planner.apply()
            gpus = [g for sv in servers for g in spec.gpus_of_server(sv)][:n]
            links = {k: v for k, v in need.items()}
            routing_maps: Dict[int, Dict[int, Tuple[int, int]]] = {}
            for leaf in leafs_cnt:
                rmap = {}
                for idx, g in enumerate(g for g in gpus
                                        if spec.leaf_of_gpu(g) == leaf):
                    rmap[spec.port_of_gpu(g)] = (m, idx)
                routing_maps[leaf] = rmap
            vc = VirtualClos(leafs=sorted(leafs_cnt), spines=[m], links=links,
                             gpus_per_leaf=max(leafs_cnt.values())
                             * spec.gpus_per_server)
            return Placement(job_id, gpus, "ocs-spine", vclos=vc,
                             routing_maps=routing_maps)
    return None


# ---------------------------------------------------------------------------
# Stage 3: OCSFINDCLOS
# ---------------------------------------------------------------------------

def _stage3_findclos(state: FabricState, job_id: int,
                     n: int) -> Optional[Placement]:
    with obs.span("ocs.findclos"):
        spec = state.spec
        budget = PortBudget(state)   # nothing changes until a candidate applies
        for size in candidate_sizes(n, spec):
            for l, s in _factorizations(size, spec):
                obs.count("ocs.candidates")
                sol = _choose_leafs_spines_ocs(state, l, s, budget)
                if sol is None:
                    continue
                leaf_alloc, spines = sol
                need: Dict[Tuple[int, int], int] = {}
                for leaf, vleafs in leaf_alloc.items():
                    for m in spines:
                        need[(leaf, m)] = need.get((leaf, m), 0) + vleafs
                planner = RewirePlanner(state, budget)
                if not planner.ensure(need):
                    continue
                planner.apply()
                return _materialize_ocs(state, job_id, n, leaf_alloc, spines,
                                        s, need, overalloc=size - n)
        return None


def _choose_leafs_spines_ocs(state: FabricState, l: int, s: int,
                             budget: PortBudget
                             ) -> Optional[Tuple[Dict[int, int], List[int]]]:
    """Aggregated port-count selection (eqs. 7–11 with OCS index summed out).

    Multiple virtual leafs per physical leaf are allowed (the L_{n,a}
    linearisation): leaf n can host a_n = idle_servers·T // s virtual leafs.
    Feasibility is pure port counting, on ``budget``, the counts of
    ``state`` taken for this placement call; circuit realisation is checked
    by the RewirePlanner afterwards.
    """
    spec = state.spec
    req_servers_per_vleaf = s // spec.gpus_per_server
    # capacity of each leaf in virtual leafs, and free movable uplink ports
    avail: List[Tuple[int, int, int]] = []  # (idle_servers, leaf, max_vleafs)
    for leaf, (idle, free_up) in enumerate(zip(budget.idle,
                                               budget.leaf_free)):
        max_v = min(idle // req_servers_per_vleaf, free_up // s)
        if max_v > 0:
            avail.append((idle, leaf, max_v))
    if sum(a[2] for a in avail) < l:
        return None
    avail.sort()  # best-fit: fewest idle servers first
    leaf_alloc: Dict[int, int] = {}
    left = l
    for _, leaf, max_v in avail:
        take = min(max_v, left)
        if take:
            leaf_alloc[leaf] = take
            left -= take
        if not left:
            break
    if left:
        return None
    # spines: need l free downlink channels each; best-fit fewest free ports
    cands = sorted((free, m) for m, free in enumerate(budget.spine_free)
                   if free >= l)
    if len(cands) < s:
        return None
    return leaf_alloc, [m for _, m in cands[:s]]


def _materialize_ocs(state: FabricState, job_id: int, n_requested: int,
                     leaf_alloc: Dict[int, int], spines: List[int], s: int,
                     links: Dict[Tuple[int, int], int],
                     overalloc: int) -> Placement:
    spec = state.spec
    req_servers_per_vleaf = s // spec.gpus_per_server
    gpus: List[int] = []
    routing_maps: Dict[int, Dict[int, Tuple[int, int]]] = {}
    leaf_order: List[int] = []
    for leaf, vleafs in sorted(leaf_alloc.items()):
        servers = state.idle_servers_of_leaf(leaf)[:vleafs * req_servers_per_vleaf]
        leaf_gpus = [g for sv in servers for g in spec.gpus_of_server(sv)]
        gpus.extend(leaf_gpus)
        rmap: Dict[int, Tuple[int, int]] = {}
        for idx, g in enumerate(leaf_gpus):
            rmap[spec.port_of_gpu(g)] = (spines[idx % len(spines)], 0)
        routing_maps[leaf] = rmap
        leaf_order.extend([leaf] * vleafs)
    vclos = VirtualClos(leafs=leaf_order, spines=list(spines),
                        links=dict(links), gpus_per_leaf=s)
    return Placement(job_id,
                     gpus if overalloc else gpus[:n_requested],
                     "ocs-vclos", vclos=vclos, routing_maps=routing_maps,
                     overallocated=overalloc)


# ---------------------------------------------------------------------------
# Release: restore xconn-unwired ports into the leaf-spine fabric
# ---------------------------------------------------------------------------

def ocs_release(state: FabricState, placement: Placement) -> None:
    """Release a job placed by OCS-vClos; rewires xconn ports back onto their
    original spine-side ports (falling back to any free port) so fabric
    capacity is not lost, then renormalises drifted circuits."""
    state.release_job(placement.job_id)
    if state.ocs is None:
        return
    ocs = state.ocs
    for k, lp, orig_sp in placement.xconn_ports:
        state.xconn_owner.pop((k, lp), None)
        if lp in ocs.circuits[k]:
            continue
        used = set(ocs.circuits[k].values())
        if orig_sp >= 0 and orig_sp not in used:
            ocs.circuits[k][lp] = orig_sp
        else:
            nports = len(ocs.spine_ports(k))
            free_sp = next((sp for sp in range(nports) if sp not in used), None)
            if free_sp is not None:
                ocs.circuits[k][lp] = free_sp
    renormalize(state)


def renormalize(state: FabricState, max_moves: int = 64) -> None:
    """Drift control: swap *idle* circuits back toward the uniform Latin
    wiring (leaf n port j -> spine (j+n) mod S).  Mirrors Minimal-Rewiring
    [59]-style background reconfiguration; only unreserved channels move."""
    if state.ocs is None:
        return
    spec, ocs = state.spec, state.ocs
    spare = state.free_capacity()
    moves = 0
    for k in range(spec.num_ocs):
        lports = ocs.leaf_ports(k)
        sports = ocs.spine_ports(k)
        sp_by_spine: Dict[int, List[int]] = {}
        for sp, (m, _) in enumerate(sports):
            sp_by_spine.setdefault(m, []).append(sp)
        used = set(ocs.circuits[k].values())
        wired = {sp: lp for lp, sp in ocs.circuits[k].items()}
        for lp, (n, j) in enumerate(lports):
            if moves >= max_moves:
                return
            if (k, lp) in state.xconn_owner:
                continue  # live cross-connect patch — never touch
            target_m = (j + n) % spec.num_spines
            cur_sp = ocs.circuits[k].get(lp)
            if cur_sp is not None:
                m_cur, _ = sports[cur_sp]
                if m_cur == target_m or spare[n][m_cur] <= 0:
                    continue
            free_target = next((sp for sp in sp_by_spine.get(target_m, [])
                                if sp not in used), None)
            if free_target is None:
                # 2-swap: evict a movable circuit off a target-spine port
                for sp_t in sp_by_spine.get(target_m, []):
                    lp2 = wired.get(sp_t)
                    if lp2 is None or lp2 == lp or (k, lp2) in state.xconn_owner:
                        continue
                    n2, _ = lports[lp2]
                    if n2 == n or spare[n2][target_m] <= 0 or cur_sp is None:
                        continue
                    # swap spine ports of lp and lp2
                    m_cur, _ = sports[cur_sp]
                    ocs.circuits[k][lp] = sp_t
                    ocs.circuits[k][lp2] = cur_sp
                    wired[sp_t] = lp
                    wired[cur_sp] = lp2
                    spare[n][m_cur] -= 1
                    spare[n][target_m] += 1
                    spare[n2][target_m] -= 1
                    spare[n2][m_cur] += 1
                    moves += 1
                    break
                continue
            if cur_sp is not None:
                m_cur, _ = sports[cur_sp]
                used.discard(cur_sp)
                wired.pop(cur_sp, None)
                spare[n][m_cur] -= 1
            ocs.circuits[k][lp] = free_target
            used.add(free_target)
            wired[free_target] = lp
            spare[n][target_m] += 1
            moves += 1


# ---------------------------------------------------------------------------
# Top-level (Algorithm 2)
# ---------------------------------------------------------------------------

def ocs_vclos_place(state: FabricState, job_id: int, n: int):
    spec = state.spec
    if n <= spec.gpus_per_server:
        p = stage0_server(state, job_id, n)
        return p if p else PlacementFailure("gpu")
    p = stage1_leaf(state, job_id, n)
    if p is not None:
        return p
    p = _stage2_single_spine(state, job_id, n)
    if p is not None:
        return p
    p = _stage3_findclos(state, job_id, n)
    if p is not None:
        return p
    idle_servers = sum(1 for sv in range(spec.num_servers) if state.server_idle(sv))
    need = math.ceil(n / spec.gpus_per_server)
    return PlacementFailure("network" if idle_servers >= need else "gpu")

"""Plain reference for the campaign cells.  Imports nothing of the system
under test and takes nothing it made: it works from the configuration's
cluster and job model and the benchmark's own trace.

Each re-simulates a whole lane from scratch, under FIFO with
head-of-line blocking:

* ``simulate_locality_packed``, for ``best``, ``sr`` and ``ecmp``:
  locality-packed placement (best-fit server, else best-fit leaf of whole
  idle servers, else whole idle servers across leafs, fewest idle first),
  the strategy's routing (ideal switch, static source map, or the 5-tuple
  hash), the flow-level contention model (a phase's share is 1 over its
  most loaded link) and the event loop, in which a job's remaining work is
  settled only when its rate changes.
* ``simulate_ocs_vclos``, for ``ocs-vclos``: placement of Algorithm 2
  (one server, one leaf, two leafs joined through the OCS, one spine, a
  virtual Clos of l leafs x s spines) on circuits it rewires itself, with
  reserved channels that give every job share 1, so a job finishes its
  iterations at the contention-free iteration time.  It returns each
  job's placement beside its times, and counts the guarantees its own
  schedule breaks.

``dtype`` sets the precision of every time, rate and byte count: float64
is what the configuration states, float32 is the control.
"""

from __future__ import annotations

import math
from typing import Dict, List, Optional, Sequence, Tuple

import numpy as np

_M64 = (1 << 64) - 1

#: strategies whose placement is locality-packed and whose whole lane the
#: reference re-simulates
LOCALITY_PACKED = ("best", "sr", "ecmp")
#: every strategy whose lane the reference re-simulates
RESIMULATED = LOCALITY_PACKED + ("ocs-vclos",)


# ---------------------------------------------------------------------------
# Fabric and collectives
# ---------------------------------------------------------------------------

class Fabric:
    """Leaf-spine wiring of a configuration's ``cluster`` block."""

    def __init__(self, cluster: dict):
        self.L = int(cluster["num_leafs"])
        self.S = int(cluster["num_spines"])
        self.gpl = int(cluster["gpus_per_leaf"])
        self.gps = int(cluster["gpus_per_server"])
        self.channels = int(cluster.get("channels", 1))
        self.uplinks = int(self.gpl * self.channels
                           * float(cluster.get("uplink_factor", 1.0)))
        self.base_channels = self.uplinks // self.S
        self.C = max(1, self.base_channels)
        self.half = self.L * self.S * self.C
        self.num_gpus = self.L * self.gpl
        self.num_servers = self.num_gpus // self.gps
        self.spl = self.gpl // self.gps
        self.link_gbps = float(cluster["link_gbps"])

    def up_id(self, leaf, spine, ch):
        return (leaf * self.S + spine) * self.C + ch

    def down_id(self, spine, leaf, ch):
        return self.half + (spine * self.L + leaf) * self.C + ch


def _mix64(x: np.ndarray) -> np.ndarray:
    x = x.astype(np.uint64)
    x ^= x >> np.uint64(33)
    x *= np.uint64(0xFF51AFD7ED558CCD)
    x ^= x >> np.uint64(33)
    x *= np.uint64(0xC4CEB9FE1A85EC53)
    x ^= x >> np.uint64(33)
    return x


def _mix64_int(x: int) -> int:
    return int(_mix64(np.asarray([x & _M64], dtype=np.uint64))[0])


def _hash(src, dst, flow_id: int, seed: int, nway: int) -> np.ndarray:
    """The switch's 5-tuple hash proxy: a 64-bit finalizer over
    ``src << 40 ^ dst << 18 ^ flow_id << 1 ^ mix(seed)``, modulo ``nway``."""
    x = ((src.astype(np.uint64) << np.uint64(40))
         ^ (dst.astype(np.uint64) << np.uint64(18))
         ^ np.uint64((flow_id << 1) & _M64)
         ^ np.uint64(_mix64_int(seed)))
    return (_mix64(x) % np.uint64(nway)).astype(np.int64)


def route(fab: Fabric, strategy: str, src: np.ndarray, dst: np.ndarray,
          flow_id: int, seed: int) -> np.ndarray:
    """Link ids of the flows ``src -> dst`` (two per flow that leaves its
    leaf, none for a flow within a leaf or a server)."""
    ls, ld = src // fab.gpl, dst // fab.gpl
    m = ls != ld
    src, dst, ls, ld = src[m], dst[m], ls[m], ld[m]
    if strategy == "sr":
        up = (src % fab.gpl) * fab.channels
        spine, ch = up % fab.S, up // fab.S
        dch = ch
    elif strategy == "ecmp":
        up = _hash(src, dst, flow_id, seed, fab.uplinks)
        spine, ch = up % fab.S, up // fab.S
        dch = (_hash(dst, src, flow_id, seed + 1, fab.base_channels)
               if fab.base_channels > 1 else np.zeros_like(spine))
    else:
        raise ValueError(f"no fabric routing for strategy {strategy!r}")
    return np.concatenate([fab.up_id(ls, spine, ch),
                           fab.down_id(spine, ld, dch)])


def phases(job, ranks: np.ndarray, prof: dict):
    """The job's communication phases over its ranks: ``[(kind, bytes per
    flow, src, dst)]``, allreduce phases first.  AlltoAll steps are listed
    one by one; the caller folds more than eight into one."""
    n = len(ranks)
    out = []
    if n < 2:
        return out
    P = float(prof["param_bytes"])
    r = ranks
    algo = job.allreduce_algo
    if algo == "hd":
        pow2 = 1 << int(math.floor(math.log2(n)))
        extra = n - pow2
        if extra:
            out.append(("ar", P, r[:extra], r[pow2:]))
        core = r[extra:]
        idx = np.arange(pow2)
        steps = int(math.log2(pow2))
        sz = P / 2
        for t in range(steps):
            out.append(("ar", sz, core, core[idx ^ (1 << t)]))
            sz /= 2
        sz = P / pow2
        for t in reversed(range(steps)):
            out.append(("ar", sz, core, core[idx ^ (1 << t)]))
            sz *= 2
        if extra:
            out.append(("ar", P, r[pow2:], r[:extra]))
    elif algo == "hierarchical_ring":
        lead = r[::8] if (n > 8 and n % 8 == 0) else r
        m = len(lead)
        if m > 1:
            out.append(("ar", 2.0 * P * (m - 1) / m, lead, np.roll(lead, -1)))
        else:
            out.append(("ar", 0.0, r[:0], r[:0]))
    else:
        out.append(("ar", 2.0 * P * (n - 1) / n, r, np.roll(r, -1)))
    A = float(prof["alltoall_bytes"])
    if A > 0:
        for t in range(n - 1):
            out.append(("a2a", A / n, r, r[(np.arange(n) + t + 1) % n]))
    return out


class _Job:
    """One placed job: per-phase bytes and, for a contended strategy, its
    per-phase link counts as a flat (phase, link, count) list."""

    __slots__ = ("jid", "gpus", "nb", "is_ar", "c", "beta", "bw", "ii",
                 "ph", "lk", "cnt", "ucnt", "uidx", "uval", "rate",
                 "left", "last", "t_fin")


def _counts(links: np.ndarray):
    u, c = np.unique(links, return_counts=True)
    return u, c


def build_job(fab: Fabric, job, gpus: np.ndarray, prof: dict, cfg_model: dict,
              strategy: Optional[str], seed: int, dtype) -> _Job:
    """Phases, links and contention-free iteration time of one placed job.
    ``strategy`` None means no fabric link is contended (isolated, or the
    ideal switch)."""
    f = dtype
    rj = _Job()
    rj.jid = job.job_id
    rj.gpus = gpus
    n = len(gpus)
    intra = int(gpus.min()) // fab.gps == int(gpus.max()) // fab.gps
    ph = phases(job, gpus, prof)
    ar = [p for p in ph if p[0] == "ar"]
    a2a = [p for p in ph if p[0] == "a2a"]
    fold = len(a2a) > 8
    kinds = [p[0] for p in ar] + (["a2a"] if fold else [p[0] for p in a2a])
    nbytes = [p[1] for p in ar] + ([sum(p[1] for p in a2a)] if fold
                                   else [p[1] for p in a2a])
    rj.nb = np.asarray(nbytes, dtype=f)
    rj.is_ar = np.asarray([k == "ar" for k in kinds], dtype=bool)
    rj.c = f(float(prof["compute_ref"]) * job.batch_size
             / float(prof["batch_ref"]))
    rj.beta = f(float(prof["overlap_beta"]))
    rj.bw = f(fab.link_gbps * float(cfg_model["bytes_per_gbps"])
              * (float(cfg_model["nvlink_speedup"]) if intra else 1.0))
    rows = []                      # (phase, link ids, counts)
    if strategy is not None and not intra:
        for k, p in enumerate(ar):
            u, c = _counts(route(fab, strategy, p[2], p[3], job.job_id, seed))
            rows.append((k, u, c))
        if a2a:
            per = [_counts(route(fab, strategy, p[2], p[3], job.job_id, seed))
                   for p in a2a]
            if fold:
                agg: Dict[int, int] = {}
                for u, c in per:
                    for l, v in zip(u.tolist(), c.tolist()):
                        agg[l] = max(agg.get(l, 0), v)
                ks = sorted(agg)
                rows.append((len(ar), np.asarray(ks, dtype=np.int64),
                             np.asarray([agg[l] for l in ks], dtype=np.int64)))
            else:
                for k, (u, c) in enumerate(per):
                    rows.append((len(ar) + k, u, c))
    rows = [r for r in rows if len(r[1])]
    if rows:
        rj.ph = np.concatenate([np.full(len(u), k) for k, u, _ in rows])
        rj.lk = np.concatenate([u for _, u, _ in rows])
        rj.cnt = np.concatenate([c for _, _, c in rows])
        union: Dict[int, int] = {}
        for l, v in zip(rj.lk.tolist(), rj.cnt.tolist()):
            union[l] = max(union.get(l, 0), v)
        rj.ucnt = np.asarray([union[l] for l in rj.lk.tolist()],
                             dtype=np.int64)
        ks = sorted(union)
        rj.uidx = np.asarray(ks, dtype=np.int64)
        rj.uval = np.asarray([union[l] for l in ks], dtype=np.int64)
    else:
        rj.ph = rj.lk = rj.cnt = rj.ucnt = None
        rj.uidx = rj.uval = None
    rj.ii = iter_time(rj, np.ones(len(rj.nb), dtype=f))
    return rj


def iter_time(rj: _Job, shares: np.ndarray):
    """Iteration time at per-phase shares: compute, plus the allreduce time
    that backward compute cannot hide, plus the AlltoAll time."""
    c = rj.c
    if not len(rj.nb):
        return c + max(c * 0, -rj.beta * c)
    t = rj.nb / (rj.bw * np.maximum(shares, shares.dtype.type(1e-9)))
    t_ar = t[rj.is_ar].sum(dtype=t.dtype)
    t_a2a = t[~rj.is_ar].sum(dtype=t.dtype)
    return c + max(c * 0, t_ar - rj.beta * c) + t_a2a


# ---------------------------------------------------------------------------
# Locality-packed strategies: the whole lane, re-simulated
# ---------------------------------------------------------------------------

class _Cluster:
    def __init__(self, fab: Fabric):
        self.fab = fab
        self.server_free = np.full(fab.num_servers, fab.gps, dtype=np.int64)
        self.gpu_free = np.ones(fab.num_gpus, dtype=bool)

    def place(self, n: int) -> Optional[np.ndarray]:
        fab = self.fab
        if int(self.gpu_free.sum()) < n:
            return None
        if n <= fab.gps:
            cand = np.flatnonzero(self.server_free >= n)
            if not len(cand):
                return None
            sv = int(cand[np.argmin(self.server_free[cand])])
            g = np.arange(sv * fab.gps, (sv + 1) * fab.gps)
            return g[self.gpu_free[g]][:n]
        req = -(-n // fab.gps)
        idle = (self.server_free == fab.gps).reshape(fab.L, fab.spl)
        counts = idle.sum(axis=1)
        cand = np.flatnonzero(counts >= req)
        if len(cand):
            leaf = int(cand[np.argmin(counts[cand])])
            servers = (leaf * fab.spl + np.flatnonzero(idle[leaf]))[:req]
        else:
            servers = []
            for c, leaf in sorted((int(c), lf) for lf, c in enumerate(counts)
                                  if c):
                servers.extend((leaf * fab.spl
                                + np.flatnonzero(idle[leaf])).tolist())
                if len(servers) >= req:
                    break
            if len(servers) < req:
                return None
            servers = np.asarray(servers[:req])
        g = (np.asarray(servers)[:, None] * fab.gps
             + np.arange(fab.gps)[None, :]).ravel()
        return g[:n]

    def hold(self, gpus: np.ndarray, sign: int) -> None:
        self.gpu_free[gpus] = sign > 0
        np.add.at(self.server_free, gpus // self.fab.gps, sign)


def simulate_locality_packed(config: dict, trace: Sequence, strategy: str,
                             seed: int, dtype=np.float64):
    """Every job's ``(start, finish, jct)`` under ``strategy`` (``best``,
    ``sr`` or ``ecmp``) and FIFO, or None for a job that never finished."""
    f = dtype
    fab = Fabric(config["cluster"])
    models = config["jobs"]["models"]
    cfg_model = config["job_model"]
    contended = strategy in ("sr", "ecmp")
    cl = _Cluster(fab)
    load = np.zeros(2 * fab.half, dtype=np.int64)
    jobs = list(trace)
    arr = [f(j.arrival) for j in jobs]
    start: List[Optional[float]] = [None] * len(jobs)
    finish: List[Optional[float]] = [None] * len(jobs)
    running: Dict[int, _Job] = {}          # insertion order = placement order
    queue: List[int] = []
    now = f(0.0)
    tiny = f(1e-12)

    def settle(rj):
        rj.left = rj.left - (now - rj.last) * rj.rate / rj.ii
        rj.last = now

    def try_schedule() -> bool:
        changed = False
        while queue:
            i = queue[0]
            gpus = cl.place(jobs[i].num_gpus)
            if gpus is None:
                break
            cl.hold(gpus, -1)
            queue.pop(0)
            start[i] = now
            rj = build_job(fab, jobs[i], gpus, models[jobs[i].model],
                           cfg_model, strategy if contended else None,
                           seed, f)
            rj.rate, rj.left, rj.last = f(1.0), f(jobs[i].num_iters), now
            rj.t_fin = now + rj.left * rj.ii / rj.rate
            running[i] = rj
            if rj.uidx is not None:
                load[rj.uidx] += rj.uval
            changed = True
        return changed

    def recompute() -> None:
        if not contended:
            return
        live = [rj for rj in running.values() if rj.lk is not None]
        for rj in live:
            worst = np.ones(len(rj.nb), dtype=np.int64)
            np.maximum.at(worst, rj.ph, load[rj.lk] - rj.ucnt + rj.cnt)
            eff = iter_time(rj, f(1.0) / worst.astype(f))
            new = rj.ii / eff if eff > 0 else f(1.0)
            if new != rj.rate:
                settle(rj)
                rj.rate = new
                rj.t_fin = now + rj.left * rj.ii / max(rj.rate, tiny)

    ai = 0
    while ai < len(jobs) or queue or running:
        t_arr = arr[ai] if ai < len(jobs) else f(math.inf)
        fin, t_fin = None, f(math.inf)
        for i, rj in running.items():
            if rj.t_fin < t_fin:
                fin, t_fin = i, rj.t_fin
        t = min(t_arr, t_fin)
        if math.isinf(t):
            break
        now = t
        if fin is not None and t_fin <= t_arr:
            rj = running.pop(fin)
            finish[fin] = now
            cl.hold(rj.gpus, +1)
            if rj.uidx is not None:
                load[rj.uidx] -= rj.uval
            try_schedule()
            recompute()
        else:
            queue.append(ai)
            ai += 1
            if try_schedule():
                recompute()
    out = []
    for i in range(len(jobs)):
        if finish[i] is None:
            out.append(None)
        else:
            out.append((start[i], finish[i],
                        (finish[i] - start[i]) + (start[i] - arr[i])))
    return out


# ---------------------------------------------------------------------------
# OCS-vClos: the whole lane, re-simulated
# ---------------------------------------------------------------------------

class _OcsFabric:
    """A leaf-spine fabric whose leaf uplinks reach the spines through
    optical circuit switches, with the jobs placed on it.

    OCS ``k`` owns uplink ``j`` of every leaf with ``j % K == k`` (its
    leaf-side ports, in (leaf, j) order) and downlink ``i`` of every spine
    with ``i % K == k`` (its spine-side ports, in (spine, i) order).  A
    circuit joins one leaf-side port to one spine-side port of the same
    OCS, and is one channel from that leaf to that spine.  A job reserves
    channels; a channel that carries a reservation is never rewired."""

    def __init__(self, cluster: dict, num_ocs: int):
        self.fab = fab = Fabric(cluster)
        self.K = K = int(num_ocs)
        self.U = fab.uplinks
        self.D = fab.L * self.U // fab.S
        self.lport = [[(n, j) for n in range(fab.L)
                       for j in range(k, self.U, K)] for k in range(K)]
        self.sport = [[(m, i) for m in range(fab.S)
                       for i in range(k, self.D, K)] for k in range(K)]
        # the uniform wiring: uplink j of leaf n to spine (j + n) mod S
        self.wire: List[Dict[int, int]] = []
        for k in range(K):
            left = {m: [p for p, (mm, _) in enumerate(self.sport[k])
                        if mm == m] for m in range(fab.S)}
            w = {}
            for lp, (n, j) in enumerate(self.lport[k]):
                m = (j + n) % fab.S
                if not left[m]:
                    m = next(mm for mm in range(fab.S) if left[mm])
                w[lp] = left[m].pop(0)
            self.wire.append(w)
        self.owner = np.full(fab.num_gpus, -1, dtype=np.int64)
        self.res = np.zeros((fab.L, fab.S), dtype=np.int64)
        self.job_links: Dict[int, Dict[Tuple[int, int], int]] = {}
        self.patched: Dict[Tuple[int, int], int] = {}   # (k, lp) -> job

    # -- views ---------------------------------------------------------------
    def cap(self, wire=None) -> np.ndarray:
        c = np.zeros((self.fab.L, self.fab.S), dtype=np.int64)
        for k, w in enumerate(self.wire if wire is None else wire):
            for lp, sp in w.items():
                c[self.lport[k][lp][0], self.sport[k][sp][0]] += 1
        return c

    def server_free(self) -> np.ndarray:
        fab = self.fab
        return (self.owner < 0).reshape(fab.num_servers, fab.gps).sum(axis=1)

    def idle_servers(self, leaf: int) -> List[int]:
        fab = self.fab
        free = self.server_free()
        return [sv for sv in range(leaf * fab.spl, (leaf + 1) * fab.spl)
                if free[sv] == fab.gps]

    def idle_counts(self) -> List[int]:
        return [len(self.idle_servers(n)) for n in range(self.fab.L)]

    def spine_free(self, m: int, cap: np.ndarray) -> int:
        return int((cap[:, m] - self.res[:, m]).sum())

    def leaf_budget(self, n: int) -> int:
        """Uplink ports of leaf ``n`` that a new job may still use: all of
        them, less the reserved channels and the ports patched leaf to
        leaf."""
        held = sum(1 for (k, lp) in self.patched if self.lport[k][lp][0] == n)
        return self.U - int(self.res[n].sum()) - held

    def gpus_of(self, servers) -> List[int]:
        g = self.fab.gps
        return [x for sv in servers for x in range(sv * g, (sv + 1) * g)]


class _Rewire:
    """A plan of circuit moves on a copy of the wiring, made one channel
    at a time and applied only when the whole demand is met."""

    def __init__(self, f: _OcsFabric):
        self.f = f
        self.wire = [dict(w) for w in f.wire]
        self.spare = f.cap() - f.res
        self.unwired: List[Tuple[int, int, int]] = []   # (k, lp, old sp)

    def _taken(self):
        return {(k, lp) for k, lp, _ in self.unwired} | set(self.f.patched)

    def _spine_of(self, k, sp):
        return self.f.sport[k][sp][0]

    def movable(self, k: int, leaf: int, avoid=None) -> Optional[int]:
        """A leaf-side port of ``leaf`` on OCS ``k`` that may be rewired:
        unwired, or on a channel with spare capacity (not ``avoid``)."""
        taken = self._taken()
        for lp, (n, _) in enumerate(self.f.lport[k]):
            if n != leaf or (k, lp) in taken:
                continue
            sp = self.wire[k].get(lp)
            if sp is None:
                return lp
            m = self._spine_of(k, sp)
            if m != avoid and self.spare[n, m] > 0:
                return lp
        return None

    def spine_port(self, k: int, spine: int, leaf: int) -> Optional[int]:
        """A spine-side port of ``spine`` on OCS ``k``: an unwired one, else
        the first whose circuit comes from another leaf with spare."""
        at = {sp: lp for lp, sp in self.wire[k].items()}
        spare_one = None
        for sp, (m, _) in enumerate(self.f.sport[k]):
            if m != spine:
                continue
            if sp not in at:
                return sp
            if spare_one is None:
                n2 = self.f.lport[k][at[sp]][0]
                if n2 != leaf and self.spare[n2, spine] > 0:
                    spare_one = sp
        return spare_one

    def slack(self, k: int, leaf: int, spine: int) -> int:
        taken = self._taken()
        nl = 0
        for lp, (n, _) in enumerate(self.f.lport[k]):
            if n != leaf or (k, lp) in taken:
                continue
            sp = self.wire[k].get(lp)
            if sp is None:
                nl += 1
            elif (self._spine_of(k, sp) != spine
                  and self.spare[n, self._spine_of(k, sp)] > 0):
                nl += 1
        if not nl:
            return 0
        at = {sp: lp for lp, sp in self.wire[k].items()}
        ns = 0
        for sp, (m, _) in enumerate(self.f.sport[k]):
            if m != spine:
                continue
            if sp not in at:
                ns += 2
            else:
                n2 = self.f.lport[k][at[sp]][0]
                if n2 != leaf and self.spare[n2, spine] > 0:
                    ns += 1
        return min(nl, ns) if ns else 0

    def one_channel(self, leaf: int, spine: int) -> bool:
        """Make one more leaf -> spine channel on the OCS with the most
        slack: rewire a movable leaf port onto a spine port of ``spine``;
        a circuit already there takes the leaf port's old spine port."""
        K = self.f.K
        slack = [self.slack(k, leaf, spine) for k in range(K)]
        for k in sorted(range(K), key=lambda k: -slack[k]):
            if slack[k] <= 0:
                break
            lp = self.movable(k, leaf, avoid=spine)
            if lp is None:
                continue
            sp = self.spine_port(k, spine, leaf)
            if sp is None:
                continue
            w = self.wire[k]
            at = {s_: l_ for l_, s_ in w.items()}
            old = w.pop(lp, None)
            if old is not None:
                self.spare[leaf, self._spine_of(k, old)] -= 1
            if sp in at:
                lp2 = at[sp]
                n2 = self.f.lport[k][lp2][0]
                self.spare[n2, spine] -= 1
                del w[lp2]
                if old is not None:
                    w[lp2] = old
                    self.spare[n2, self._spine_of(k, old)] += 1
            w[lp] = sp
            self.spare[leaf, spine] += 1
            return True
        return False

    def ensure(self, need: Dict[Tuple[int, int], int]) -> bool:
        """Spare channels for every (leaf, spine) of ``need``, set aside as
        they are made; False when the fabric cannot make them."""
        budget = 4 * self.f.fab.L * self.f.U
        for (n, m), cnt in sorted(need.items()):
            while self.spare[n, m] < cnt:
                budget -= 1
                if budget <= 0 or not self.one_channel(n, m):
                    return False
            self.spare[n, m] -= cnt
        return True

    def patch(self, la: int, lb: int, count: int) -> bool:
        """Take ``count`` movable ports of each of two leafs, pairwise on
        one OCS, out of the fabric: they become direct leaf-to-leaf
        circuits that use no spine port."""
        done = 0
        for k in range(self.f.K):
            while done < count:
                pa, pb = self.movable(k, la), self.movable(k, lb)
                if pa is None or pb is None:
                    break
                for p in (pa, pb):
                    old = self.wire[k].pop(p, None)
                    if old is not None:
                        self.spare[self.f.lport[k][p][0],
                                   self._spine_of(k, old)] -= 1
                    self.unwired.append((k, p, -1 if old is None else old))
                done += 1
            if done >= count:
                return True
        return False

    def apply(self) -> None:
        self.f.wire = [dict(w) for w in self.wire]


def _factor_pairs(n: int, fab: Fabric) -> List[Tuple[int, int]]:
    """(leafs, GPUs per leaf) with leafs x GPUs = n, whole servers per leaf
    that a leaf can hold, at most one spine per GPU of a leaf, 2 or more
    leafs; powers of two per leaf first, then the most per leaf."""
    out = [(l, n // l) for l in range(2, min(n, fab.L) + 1)
           if n % l == 0 and (n // l) % fab.gps == 0
           and (n // l) // fab.gps <= fab.spl and n // l <= fab.S]
    pow2 = lambda s: s & (s - 1) == 0
    return sorted(out, key=lambda ls: (not pow2(ls[1]), -ls[1]))


def _ocs_place(f: _OcsFabric, n: int):
    """OCS-vClos placement on the fabric's current state: ``(gpus, links,
    patched ports)`` or None.  Stages in order: one server (best fit), one
    leaf of whole idle servers (best fit), then across leafs: two leafs
    joined directly through the OCS, one spine that every GPU reaches
    through its own channel, or a virtual Clos of l leafs x s spines."""
    fab = f.fab
    if int((f.owner < 0).sum()) < n:
        return None
    if n <= fab.gps:
        free = f.server_free()
        cand = [sv for sv in range(fab.num_servers) if free[sv] >= n]
        if not cand:
            return None
        sv = min(cand, key=lambda s: (free[s], s))
        g = [x for x in range(sv * fab.gps, (sv + 1) * fab.gps)
             if f.owner[x] < 0]
        return g[:n], {}, []
    req = -(-n // fab.gps)
    counts = f.idle_counts()
    cand = [lf for lf in range(fab.L) if counts[lf] >= req]
    if cand:
        leaf = min(cand, key=lambda lf: (counts[lf], lf))
        return f.gpus_of(f.idle_servers(leaf)[:req])[:n], {}, []
    # idle servers across leafs, fewest idle first
    servers: List[int] = []
    for c, leaf in sorted((c, lf) for lf, c in enumerate(counts) if c):
        servers += f.idle_servers(leaf)[:req - len(servers)]
        if len(servers) >= req:
            break
    if len(servers) >= req:
        per_leaf: Dict[int, int] = {}
        for sv in servers:
            per_leaf[sv // fab.spl] = per_leaf.get(sv // fab.spl, 0) + 1
        gpus = f.gpus_of(servers)[:n]
        if len(per_leaf) == 2:
            (la, ca), (lb, cb) = sorted(per_leaf.items())
            plan = _Rewire(f)
            if plan.patch(la, lb, min(ca, cb) * fab.gps):
                plan.apply()
                return gpus, {}, plan.unwired
        cap = f.cap()
        for _, m in sorted((f.spine_free(m, cap), m) for m in range(fab.S)
                           if f.spine_free(m, cap) >= n):
            need = {(lf, m): c * fab.gps for lf, c in per_leaf.items()}
            plan = _Rewire(f)
            if plan.ensure(need):
                plan.apply()
                return gpus, need, []
    # a virtual Clos over l leafs and s spines, at n or the next size that
    # factors (the extra GPUs are held too)
    sizes = [n] + [m for m in range(n + 1, n + 65)
                   if _factor_pairs(m, fab)][:1]
    for size in sizes:
        for l, s in _factor_pairs(size, fab):
            per = s // fab.gps
            avail = []
            for lf in range(fab.L):
                v = min(counts[lf] // per, f.leaf_budget(lf) // s)
                if v > 0:
                    avail.append((counts[lf], lf, v))
            if sum(a[2] for a in avail) < l:
                continue
            alloc: Dict[int, int] = {}
            left = l
            for _, lf, v in sorted(avail):
                take = min(v, left)
                if take:
                    alloc[lf] = take
                    left -= take
                if not left:
                    break
            cap = f.cap()
            spines = sorted((f.spine_free(m, cap), m) for m in range(fab.S)
                            if f.spine_free(m, cap) >= l)
            if len(spines) < s:
                continue
            spines = [m for _, m in spines[:s]]
            need: Dict[Tuple[int, int], int] = {}
            for lf, v in alloc.items():
                for m in spines:
                    need[(lf, m)] = need.get((lf, m), 0) + v
            plan = _Rewire(f)
            if not plan.ensure(need):
                continue
            plan.apply()
            gpus = []
            for lf, v in sorted(alloc.items()):
                gpus += f.gpus_of(f.idle_servers(lf)[:v * per])
            return (gpus if size > n else gpus[:n]), need, []
    return None


def _ocs_release(f: _OcsFabric, jid: int, patched) -> None:
    """Free a job's GPUs and channels.  Ports it patched leaf to leaf go
    back to their old spine port (else the first free one), and then idle
    circuits drift back toward the uniform wiring, at most 64 moves."""
    f.owner[f.owner == jid] = -1
    for (n, m), c in f.job_links.pop(jid, {}).items():
        f.res[n, m] -= c
    if not patched:
        return
    for k, lp, old in patched:
        f.patched.pop((k, lp), None)
        if lp in f.wire[k]:
            continue
        used = set(f.wire[k].values())
        if old >= 0 and old not in used:
            f.wire[k][lp] = old
        else:
            sp = next((p for p in range(len(f.sport[k])) if p not in used),
                      None)
            if sp is not None:
                f.wire[k][lp] = sp
    spare = f.cap() - f.res
    S = f.fab.S
    moves = 0
    for k in range(f.K):
        w = f.wire[k]
        by_spine: Dict[int, List[int]] = {}
        for sp, (m, _) in enumerate(f.sport[k]):
            by_spine.setdefault(m, []).append(sp)
        used = set(w.values())
        at = {sp: lp for lp, sp in w.items()}
        for lp, (n, j) in enumerate(f.lport[k]):
            if moves >= 64:
                return
            if (k, lp) in f.patched:
                continue
            target = (j + n) % S
            cur = w.get(lp)
            if cur is not None:
                mc = f.sport[k][cur][0]
                if mc == target or spare[n, mc] <= 0:
                    continue
            free = next((sp for sp in by_spine.get(target, [])
                         if sp not in used), None)
            if free is None:
                if cur is None:
                    continue
                for sp_t in by_spine.get(target, []):
                    lp2 = at.get(sp_t)
                    if lp2 is None or lp2 == lp or (k, lp2) in f.patched:
                        continue
                    n2 = f.lport[k][lp2][0]
                    if n2 == n or spare[n2, target] <= 0:
                        continue
                    mc = f.sport[k][cur][0]
                    w[lp], w[lp2] = sp_t, cur
                    at[sp_t], at[cur] = lp, lp2
                    spare[n, mc] -= 1
                    spare[n, target] += 1
                    spare[n2, target] -= 1
                    spare[n2, mc] += 1
                    moves += 1
                    break
                continue
            if cur is not None:
                used.discard(cur)
                at.pop(cur, None)
                spare[n, f.sport[k][cur][0]] -= 1
            w[lp] = free
            used.add(free)
            at[free] = lp
            spare[n, target] += 1
            moves += 1


def simulate_ocs_vclos(config: dict, trace: Sequence, dtype=np.float64):
    """Every job of an OCS-vClos lane under FIFO with head-of-line
    blocking: ``(jobs, faults)``.  ``jobs[i]`` is ``(start, finish, jct,
    gpus, links, patched port count)``, or None for a job that never ran.
    Reserved channels make a job's share 1, so it runs its iterations at
    the contention-free iteration time.  ``faults`` counts broken
    guarantees of the reference's own schedule: a GPU held twice, a
    reservation over a channel's circuits, a leaf's or a spine's ports
    over-used."""
    f_ = dtype
    cfg = config["cluster"]
    f = _OcsFabric(cfg, config["ocs_twin"]["num_ocs"])
    fab = f.fab
    models = config["jobs"]["models"]
    cfg_model = config["job_model"]
    jobs = list(trace)
    arr = [f_(j.arrival) for j in jobs]
    out: List[Optional[tuple]] = [None] * len(jobs)
    running: Dict[int, tuple] = {}        # insertion order = start order
    queue: List[int] = []
    faults = 0
    now = f_(0.0)
    failed_at = None                      # fabric version of the last miss
    version = 0

    def schedule():
        nonlocal version, failed_at, faults
        while queue:
            if failed_at == version:
                return
            i = queue[0]
            got = _ocs_place(f, jobs[i].num_gpus)
            if got is None:
                failed_at = version
                return
            gpus, links, patched = got
            g = np.asarray(gpus, dtype=np.int64)
            if (f.owner[g] >= 0).any():
                faults += 1
            f.owner[g] = i
            for (n, m), c in links.items():
                f.res[n, m] += c
            f.job_links[i] = dict(links)
            for k, lp, _ in patched:
                f.patched[(k, lp)] = i
            cap = f.cap()
            held = np.zeros(fab.L, dtype=np.int64)
            for k, lp in f.patched:
                held[f.lport[k][lp][0]] += 1
            if ((f.res > cap).any() or (f.res.sum(axis=1) + held > f.U).any()
                    or (f.res.sum(axis=0) > f.D).any()):
                faults += 1
            version += 1
            queue.pop(0)
            rj = build_job(fab, jobs[i], g[:jobs[i].num_gpus],
                           models[jobs[i].model], cfg_model, None, 0, f_)
            t_fin = now + f_(jobs[i].num_iters) * rj.ii
            running[i] = (t_fin, now, gpus, dict(links), patched)

    ai = 0
    while ai < len(jobs) or queue or running:
        t_arr = arr[ai] if ai < len(jobs) else f_(math.inf)
        fin, t_fin = None, f_(math.inf)
        for i, r in running.items():
            if r[0] < t_fin:
                fin, t_fin = i, r[0]
        t = min(t_arr, t_fin)
        if math.isinf(t):
            break
        now = t
        if fin is not None and t_fin <= t_arr:
            _, start, gpus, links, patched = running.pop(fin)
            out[fin] = (start, now, (now - start) + (start - arr[fin]),
                        gpus, links, len(patched))
            _ocs_release(f, fin, patched)
            version += 1
            schedule()
        else:
            queue.append(ai)
            ai += 1
            schedule()
    return out, faults

"""Plain reference for the 3D-parallel LLM campaign cells.  Imports nothing
of the system under test and takes nothing it made: it works from the
configuration's cluster block, its GPT rows' published inputs and the
formulas its ``job_model`` states, and the benchmark's own trace.  Of the
campaign reference it uses only the fabric's wiring and routing
(``reference.Fabric``, ``reference.route``), unchanged.

``simulate_lane`` re-simulates a whole ``best``, ``sr`` or ``ecmp`` lane
from scratch, under FIFO with head-of-line blocking:

* each job runs its size's GPT row with the row's layout (t tensor
  -parallel GPUs, p pipeline stages, d = n/(t p) data-parallel replicas,
  Megatron's rank order r = pp (d t) + dp t + tp);
* locality-packed placement: best-fit server, else best-fit leaf of whole
  idle servers, else whole idle servers across leafs, fewest idle first;
  GPUs in server order are the job's ranks;
* the job's fabric traffic: one phase of t p concurrent DP rings (each
  rank to the rank one DP step ahead), coverable by compute, and for
  p > 1 one phase of stage-boundary sends both ways, uncoverable;
* the strategy's routing (ideal switch, static source map, 5-tuple hash),
  a phase's share is 1 over its most loaded link, and a job's remaining
  work is settled only when its rate changes.

``dtype`` sets the precision of every time, rate and byte count: float64
is what the configuration states, float32 is the control.
"""

from __future__ import annotations

import math
from typing import Dict, List, Optional, Sequence

import numpy as np

from reference import Fabric, route

LANES = ("best", "sr", "ecmp")


class Row:
    """One GPT row's job model, from the configuration's published inputs:
    parameters, compute per iteration, the layout and the bytes each flow
    of its DP rings and pipeline sends carries per iteration."""

    def __init__(self, jobs_cfg: dict, name: str, dtype=np.float64):
        r = jobs_cfg["rows"][name]
        f = dtype
        h, l = f(r["hidden"]), f(r["layers"])
        s, v = f(jobs_cfg["seq"]), f(jobs_cfg["vocab"])
        g = f(jobs_cfg["dtype_bytes"])
        b = f(jobs_cfg["micro_batch"])
        big_b = f(r["batch"])
        self.t, self.p, self.n = int(r["t"]), int(r["p"]), int(r["gpus"])
        self.d = self.n // (self.t * self.p)
        self.params = f(12) * l * h * h * (
            f(1) + f(13) / (f(12) * h) + (v + s) / (f(12) * l * h))
        flops = f(96) * big_b * s * l * h * h * (
            f(1) + s / (f(6) * h) + v / (f(16) * l * h))
        self.compute = flops / (f(self.n) * f(r["tflops"]) * f(1e12))
        d = f(self.d)
        self.ring_bytes = (f(2) * (self.params * g / f(self.t * self.p))
                           * (d - f(1)) / d)
        m = big_b / (b * d)
        self.stage_bytes = (m * b * s * h * g / f(self.t) if self.p > 1
                            else f(0))
        self.beta = f(jobs_cfg["overlap_beta"])

    def rank(self, pp, dp, tp):
        return pp * self.d * self.t + dp * self.t + tp

    def flows(self, gpus: np.ndarray):
        """``[(is_dp, src GPUs, dst GPUs)]``: the DP rings, then the
        pipeline sends."""
        out = []
        pp, dp, tp = np.meshgrid(np.arange(self.p), np.arange(self.d),
                                 np.arange(self.t), indexing="ij")
        pp, dp, tp = pp.ravel(), dp.ravel(), tp.ravel()
        if self.d > 1:
            out.append((True, gpus[self.rank(pp, dp, tp)],
                        gpus[self.rank(pp, (dp + 1) % self.d, tp)]))
        if self.p > 1:
            up = pp < self.p - 1
            a = gpus[self.rank(pp[up], dp[up], tp[up])]
            b = gpus[self.rank(pp[up] + 1, dp[up], tp[up])]
            out.append((False, np.concatenate([a, b]),
                        np.concatenate([b, a])))
        return out


class _Job:
    __slots__ = ("gpus", "row", "nb", "is_dp", "bw", "ii", "ph", "lk",
                 "cnt", "ucnt", "uidx", "uval", "rate", "left", "last",
                 "t_fin")


def _iter_time(rj: _Job, shares: np.ndarray):
    """Compute, plus the DP ring time that backward compute cannot hide,
    plus the pipeline sends."""
    c = rj.row.compute
    if not len(rj.nb):
        return c
    t = rj.nb / (rj.bw * shares)
    return (c + max(c * 0, t[rj.is_dp].sum(dtype=t.dtype) - rj.row.beta * c)
            + t[~rj.is_dp].sum(dtype=t.dtype))


def place_job(fab: Fabric, jid: int, row: Row, gpus: np.ndarray,
              strategy: str, seed: int, cfg_model: dict, dtype) -> _Job:
    """A placed job's phases, link counts and contention-free iteration
    time.  ``best`` contends on no fabric link."""
    f = dtype
    rj = _Job()
    rj.gpus, rj.row = gpus, row
    intra = int(gpus.min()) // fab.gps == int(gpus.max()) // fab.gps
    phases = row.flows(gpus)
    rj.nb = np.asarray([row.ring_bytes if dp else row.stage_bytes
                        for dp, _, _ in phases], dtype=f)
    rj.is_dp = np.asarray([dp for dp, _, _ in phases], dtype=bool)
    rj.bw = f(fab.link_gbps * float(cfg_model["bytes_per_gbps"])
              * (float(cfg_model["nvlink_speedup"]) if intra else 1.0))
    ph, lk, cnt = [], [], []
    if strategy != "best" and not intra:
        for k, (_, src, dst) in enumerate(phases):
            u, c = np.unique(route(fab, strategy, src, dst, jid, seed),
                             return_counts=True)
            ph.append(np.full(len(u), k))
            lk.append(u)
            cnt.append(c)
    if ph and sum(len(u) for u in lk):
        rj.ph, rj.lk, rj.cnt = (np.concatenate(x) for x in (ph, lk, cnt))
        union: Dict[int, int] = {}
        for l, v in zip(rj.lk.tolist(), rj.cnt.tolist()):
            union[l] = max(union.get(l, 0), v)
        rj.ucnt = np.asarray([union[l] for l in rj.lk.tolist()])
        rj.uidx = np.asarray(sorted(union), dtype=np.int64)
        rj.uval = np.asarray([union[l] for l in rj.uidx.tolist()])
    else:
        rj.ph = rj.lk = rj.cnt = rj.ucnt = rj.uidx = rj.uval = None
    rj.ii = _iter_time(rj, np.ones(len(rj.nb), dtype=f))
    return rj


def _place(fab: Fabric, server_free: np.ndarray, gpu_free: np.ndarray,
           n: int) -> Optional[np.ndarray]:
    if int(gpu_free.sum()) < n:
        return None
    if n <= fab.gps:
        cand = np.flatnonzero(server_free >= n)
        if not len(cand):
            return None
        sv = int(cand[np.argmin(server_free[cand])])
        g = np.arange(sv * fab.gps, (sv + 1) * fab.gps)
        return g[gpu_free[g]][:n]
    req = -(-n // fab.gps)
    idle = (server_free == fab.gps).reshape(fab.L, fab.spl)
    counts = idle.sum(axis=1)
    cand = np.flatnonzero(counts >= req)
    if len(cand):
        leaf = int(cand[np.argmin(counts[cand])])
        servers = (leaf * fab.spl + np.flatnonzero(idle[leaf]))[:req]
    else:
        servers = []
        for _, leaf in sorted((int(c), lf) for lf, c in enumerate(counts)
                              if c):
            servers += (leaf * fab.spl + np.flatnonzero(idle[leaf])).tolist()
            if len(servers) >= req:
                break
        if len(servers) < req:
            return None
        servers = np.asarray(servers[:req])
    return (np.asarray(servers)[:, None] * fab.gps
            + np.arange(fab.gps)[None, :]).ravel()[:n]


def simulate_lane(config: dict, trace: Sequence, strategy: str, seed: int,
                  dtype=np.float64):
    """Every job's ``(start, finish, jct)`` under ``strategy`` and FIFO, or
    None for a job that never finished."""
    if strategy not in LANES:
        raise ValueError(f"no reference lane for strategy {strategy!r}")
    f = dtype
    fab = Fabric(config["cluster"])
    rows = {name: Row(config["jobs"], name, f)
            for name in config["jobs"]["rows"]}
    cfg_model = config["job_model"]
    server_free = np.full(fab.num_servers, fab.gps, dtype=np.int64)
    gpu_free = np.ones(fab.num_gpus, dtype=bool)
    load = np.zeros(2 * fab.half, dtype=np.int64)
    jobs = list(trace)
    arr = [f(j.arrival) for j in jobs]
    start: List[Optional[float]] = [None] * len(jobs)
    finish: List[Optional[float]] = [None] * len(jobs)
    running: Dict[int, _Job] = {}          # insertion order = placement order
    queue: List[int] = []
    now = f(0.0)

    def hold(gpus, sign):
        gpu_free[gpus] = sign > 0
        np.add.at(server_free, gpus // fab.gps, sign)

    def try_schedule() -> bool:
        changed = False
        while queue:
            i = queue[0]
            row = rows[jobs[i].model]
            if jobs[i].num_gpus != row.n:
                raise ValueError(f"job {jobs[i].job_id}: {jobs[i].num_gpus} "
                                 f"GPUs, but {jobs[i].model} runs on {row.n}")
            gpus = _place(fab, server_free, gpu_free, row.n)
            if gpus is None:
                break
            hold(gpus, -1)
            queue.pop(0)
            start[i] = now
            rj = place_job(fab, jobs[i].job_id, row, gpus, strategy, seed,
                           cfg_model, f)
            rj.rate, rj.left, rj.last = f(1.0), f(jobs[i].num_iters), now
            rj.t_fin = now + rj.left * rj.ii / rj.rate
            running[i] = rj
            if rj.uidx is not None:
                load[rj.uidx] += rj.uval
            changed = True
        return changed

    def recompute() -> None:
        for rj in running.values():
            if rj.lk is None:
                continue
            worst = np.ones(len(rj.nb), dtype=np.int64)
            np.maximum.at(worst, rj.ph, load[rj.lk] - rj.ucnt + rj.cnt)
            eff = _iter_time(rj, f(1.0) / worst.astype(f))
            new = rj.ii / eff
            if new != rj.rate:
                rj.left = rj.left - (now - rj.last) * rj.rate / rj.ii
                rj.last = now
                rj.rate = new
                rj.t_fin = now + rj.left * rj.ii / rj.rate

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
            hold(rj.gpus, +1)
            if rj.uidx is not None:
                load[rj.uidx] -= rj.uval
            try_schedule()
            recompute()
        else:
            queue.append(ai)
            ai += 1
            if try_schedule():
                recompute()
    return [None if finish[i] is None
            else (start[i], finish[i], (finish[i] - start[i])
                  + (start[i] - arr[i]))
            for i in range(len(jobs))]


def offered_load(config: dict, trace: Sequence) -> float:
    """Offered GPU load of a trace: sum over jobs of GPUs times the
    contention-free run time, over the fabric's GPUs times the arrival
    span (the last arrival)."""
    fab = Fabric(config["cluster"])
    rows = {name: Row(config["jobs"], name)
            for name in config["jobs"]["rows"]}
    work = 0.0
    for j in trace:
        row = rows[j.model]
        gpus = np.arange(row.n)            # whole servers: never intra
        rj = place_job(fab, j.job_id, row, gpus, "best", 0,
                       config["job_model"], np.float64)
        work += row.n * j.num_iters * float(rj.ii)
    return work / (fab.num_gpus * max(j.arrival for j in trace))

"""Job traces for the benchmark, made from a configuration's job mix and a
seed.  Plain data only: nothing here imports the system under test.

The population of a trace (each job's GPU count, model, batch size,
allreduce algorithm and iteration count, and the multiset of arrival gaps)
is drawn once from the mix's ``population_seed`` with the same draw order
as the paper's Poisson generator: size from the empirical size mix, model,
batch, algorithm, lognormal iterations, exponential gap.  ``--seed`` then
permutes the jobs and, independently, the gaps.  So every seed offers the
same work (the same sizes, durations and total arrival span) in another
order, which keeps runs with different seeds comparable while no two seeds
give the same trace.
"""

from __future__ import annotations

from dataclasses import dataclass
from typing import List, Sequence

import numpy as np

ALLREDUCE_ALGOS = ("ring", "hierarchical_ring", "hd")


@dataclass(frozen=True)
class TraceJob:
    job_id: int
    model: str
    num_gpus: int
    batch_size: int
    arrival: float
    num_iters: int
    allreduce_algo: str


def _population(mix: dict, num_jobs: int):
    """``num_jobs`` job shapes and gaps from the mix's own seed."""
    rng = np.random.default_rng(int(mix["population_seed"]))
    sizes = [int(s) for s, _ in mix["size_mix"]]
    probs = np.asarray([float(p) for _, p in mix["size_mix"]])
    probs = probs / probs.sum()
    models = list(mix["models"])
    max_gpus = mix.get("max_gpus")
    shapes, gaps = [], []
    for _ in range(num_jobs):
        n = sizes[rng.choice(len(sizes), p=probs)]
        if max_gpus:
            n = min(n, int(max_gpus))
        model = models[rng.integers(len(models))]
        batches = mix["models"][model]["batches"]
        batch = int(batches[rng.integers(len(batches))])
        algo = ALLREDUCE_ALGOS[rng.integers(len(ALLREDUCE_ALGOS))]
        iters = int(rng.lognormal(mean=float(mix["iters_log_mean"]),
                                  sigma=float(mix["iters_log_sigma"])))
        gaps.append(float(rng.exponential(float(mix["mean_interarrival"]))))
        shapes.append((model, n, batch, max(iters, int(mix["min_iters"])),
                       algo))
    return shapes, gaps


class Population:
    """A mix's jobs and gaps, drawn once; :meth:`trace` orders them."""

    def __init__(self, mix: dict):
        self.num_jobs = int(mix["num_jobs"])
        self.shapes, self.gaps = _population(mix, self.num_jobs)

    def trace(self, seed: int) -> List[TraceJob]:
        """The population in the order ``seed`` draws: one permutation of
        the jobs and an independent one of the gaps."""
        rng = np.random.default_rng(int(seed))
        order = rng.permutation(self.num_jobs)
        gap_order = rng.permutation(self.num_jobs)
        jobs, t = [], 0.0
        for i in range(self.num_jobs):
            t += self.gaps[gap_order[i]]
            model, n, batch, iters, algo = self.shapes[order[i]]
            jobs.append(TraceJob(i, model, n, batch, t, iters, algo))
        return jobs


def make_trace(mix: dict, seed: int) -> List[TraceJob]:
    """One trace of ``mix`` in the order ``seed`` draws."""
    return Population(mix).trace(seed)


def to_program_jobs(trace: Sequence[TraceJob]):
    """The trace as the system under test's own ``Job`` objects."""
    from repro.core.jobs import Job
    return [Job(j.job_id, j.model, j.num_gpus, j.batch_size, j.arrival,
                j.num_iters, allreduce_algo=j.allreduce_algo) for j in trace]

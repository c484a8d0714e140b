"""The data-driven part of the benchmark: it finds a cell's configuration,
traffic mix, the code for its kind of cell and per-layer metric readers
by the names in ``BENCHMARK.json``, and prints the result.

Layout, all under ``bench/``:

* ``configs/<config>.json``: a deployment (the file ``BENCHMARK.json``
  names for it);
* ``traffic/<traffic>.json``: a traffic mix, whose ``kind`` names the
  module that runs it;
* ``kinds/<kind>.py``: ``run(cell, device, t_start) -> (result,
  checks)`` drives one run of a kind of cell;
* ``metrics/<metric>.py``: ``read(record) -> float | None`` reads one
  per-layer metric from a run's record.

A later cell, mix, configuration or metric is a new file and a new entry;
no existing file changes.
"""

from __future__ import annotations

import importlib.util
import json
import os
import sys
from dataclasses import dataclass
from pathlib import Path
from typing import Callable, Dict, List

ROOT = Path(__file__).resolve().parents[1]


class Refused(RuntimeError):
    """The run cannot produce a result here (no chip, missing file)."""


def load_benchmark(root: Path = ROOT) -> dict:
    with open(root / "BENCHMARK.json") as f:
        return json.load(f)


def _load_module(path: Path, name: str):
    if not path.is_file():
        raise Refused(f"no file {path}")
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@dataclass
class Cell:
    """Everything one run of one cell needs, found by name."""

    name: str
    chips: int
    config: dict
    traffic: dict
    end_to_end: List[dict]
    per_layer: List[dict]
    seed: int
    seconds: float
    trace: bool
    root: Path = ROOT

    @property
    def out_dir(self) -> Path:
        """Scratch space inside the checkout, fixed per cell and seed."""
        return self.root / ".bench_out" / f"{self.name}.{self.seed}"


def find_cell(bench: dict, workload: str, seed: int, seconds: float,
              trace: bool, root: Path = ROOT) -> Cell:
    cells = {w["name"]: w for w in bench["workloads"]}
    if workload not in cells:
        raise Refused(f"unknown workload {workload!r}; have {sorted(cells)}")
    w = cells[workload]
    configs = {c["name"]: c for c in bench["configs"]}
    with open(root / configs[w["config"]]["file"]) as f:
        config = json.load(f)
    with open(root / "bench" / "traffic" / f"{w['traffic']}.json") as f:
        traffic = json.load(f)

    def mine(metrics):
        return [m for m in metrics
                if workload in m.get("workloads", [workload])]

    return Cell(workload, int(w["chips"]), config, traffic,
                mine(bench["end_to_end"]), mine(bench["per_layer"]),
                seed, seconds, trace, root)


def load_kind(kind: str, root: Path = ROOT):
    return _load_module(root / "bench" / "kinds" / f"{kind}.py",
                        f"bench_{kind}")


def metric_reader(name: str, root: Path = ROOT) -> Callable:
    return _load_module(root / "bench" / "metrics" / f"{name}.py",
                        "bench_metric_" + name.replace(".", "_")).read


def read_per_layer(cell: Cell, record: dict) -> Dict[str, dict]:
    """Every per-layer metric of the cell that its reader finds."""
    out = {}
    for m in cell.per_layer:
        v = metric_reader(m["name"], cell.root)(record)
        if v is not None:
            out[m["name"]] = {"value": float(v), "unit": m["unit"]}
    return out


def device_info(chips: int, require_tpu: bool = True) -> dict:
    """Platform, kind and count as JAX reports them.  Refuses anything but
    a TPU with at least ``chips`` devices when ``require_tpu``."""
    import jax
    devs = jax.devices()
    info = {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}
    if require_tpu and (info["platform"] != "tpu" or info["count"] < chips):
        raise Refused(f"needs {chips} TPU chip(s); JAX found "
                      f"{info['count']} {info['platform']} device(s)")
    return info


def memory_peak_bytes() -> int:
    """Peak bytes in use on the fullest device (0 where not reported)."""
    import jax
    peaks = []
    for d in jax.devices():
        stats = d.memory_stats() or {}
        peaks.append(int(stats.get("peak_bytes_in_use", 0)))
    return max(peaks) if peaks else 0


def enable_compile_cache(root: Path = ROOT) -> str:
    """JAX's persistent cache at ``$JAX_COMPILATION_CACHE_DIR``, else at
    the fixed ``<checkout>/.jax_cache``."""
    import jax
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR") or str(
        root / ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    return path


def emit(result: dict, checks: Dict[str, dict]) -> None:
    """Each compared number beside its limit, last on stderr; then the
    result line, with the checks last, as the last line of stdout."""
    for name, c in checks.items():
        print(f"check {name} {c['value']!r} limit {c['limit']!r}",
              file=sys.stderr)
    line = dict(result)
    line["checks"] = checks
    sys.stdout.flush()
    print(json.dumps(line), flush=True)


def run_window(seconds: float, unit: Callable[[int], object],
               clock: Callable[[], float]):
    """Units ``unit(0)``, ``unit(1)``, ... back to back; none starts once
    ``seconds`` have passed since the first.  Returns the units' results,
    the window's start and its end, which is when the last unit ended."""
    results = []
    t0 = clock()
    while clock() - t0 < seconds:
        results.append(unit(len(results)))
    return results, t0, clock()

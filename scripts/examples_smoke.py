#!/usr/bin/env python
"""Examples stay runnable against the live API (``make examples-smoke``).

Three layers, cheapest first:

  1. every ``examples/*.py`` byte-compiles;
  2. every ``import repro...`` / ``from repro... import name`` statement in
     them resolves against the installed package — renamed/removed API
     fails here without executing the example;
  3. the cheap examples actually run end-to-end in a subprocess
     (``contention_analysis.py``, ``multi_tenant_cluster.py --jobs 12``),
     and the argparse-guarded heavy ones at least parse ``--help`` (which
     executes their module-level imports for real).

``quickstart.py`` and ``train_lm.py`` train models (~25 s each), so their
full runs are opt-in: ``EXAMPLES_FULL=1 python scripts/examples_smoke.py``.

Run: python scripts/examples_smoke.py   (or: make examples-smoke)
"""

from __future__ import annotations

import ast
import importlib
import os
import py_compile
import subprocess
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parent.parent
EXAMPLES = sorted((ROOT / "examples").glob("*.py"))

#: fully executed every run (cheap); None = no extra argv
RUN_FULL = {"contention_analysis.py": [],
            "multi_tenant_cluster.py": ["--jobs", "12"]}
#: heavy examples: --help executes module-level imports, then exits
RUN_HELP = {"train_lm.py"}
#: heavy examples run only under EXAMPLES_FULL=1
RUN_OPT_IN = {"quickstart.py": [], "train_lm.py": ["--tiny", "--steps", "2"]}

errors: list[str] = []


def check_imports(path: Path) -> None:
    """Resolve the example's repro.* imports without executing it."""
    tree = ast.parse(path.read_text(), filename=str(path))
    for node in ast.walk(tree):
        if isinstance(node, ast.Import):
            for a in node.names:
                if a.name.split(".")[0] == "repro":
                    importlib.import_module(a.name)
        elif isinstance(node, ast.ImportFrom) and node.module \
                and node.module.split(".")[0] == "repro":
            mod = importlib.import_module(node.module)
            for a in node.names:
                if not hasattr(mod, a.name):
                    # a submodule is importable but not yet an attribute
                    importlib.import_module(f"{node.module}.{a.name}")


def run_example(path: Path, argv: list[str]) -> None:
    t0 = time.time()
    try:
        r = subprocess.run(
            [sys.executable, str(path)] + argv, cwd=ROOT, timeout=600,
            # a smoke, not a chip run: the child never takes the chip
            # (docs/robustness.md)
            env={**os.environ, "PYTHONPATH": str(ROOT / "src"),
                 "JAX_PLATFORMS": "cpu"},
            capture_output=True, text=True)
    except subprocess.TimeoutExpired:
        errors.append(f"{path.name} {' '.join(argv)}: timeout > 600s")
        return
    if r.returncode != 0:
        tail = (r.stderr or r.stdout).strip().splitlines()[-8:]
        errors.append(f"{path.name} {' '.join(argv)}: exit {r.returncode}\n"
                      + "\n".join(f"      {ln}" for ln in tail))
    else:
        print(f"  ran {path.name} {' '.join(argv)} "
              f"[{time.time() - t0:.1f}s]")


def main() -> int:
    sys.path.insert(0, str(ROOT / "src"))
    full = os.environ.get("EXAMPLES_FULL") == "1"
    for path in EXAMPLES:
        try:
            py_compile.compile(str(path), doraise=True)
        except py_compile.PyCompileError as e:
            errors.append(f"{path.name}: does not compile: {e.msg}")
            continue
        try:
            check_imports(path)
        except Exception as e:
            errors.append(f"{path.name}: import smoke failed: "
                          f"{type(e).__name__}: {e}")
            continue
        if path.name in RUN_FULL:
            run_example(path, RUN_FULL[path.name])
        elif path.name in RUN_HELP:
            run_example(path, ["--help"])
        if full and path.name in RUN_OPT_IN:
            run_example(path, RUN_OPT_IN[path.name])
    if errors:
        print("examples-smoke: FAILED")
        for e in errors:
            print(f"  - {e}")
        return 1
    print(f"examples-smoke: OK ({len(EXAMPLES)} examples"
          f"{', full runs included' if full else ''})")
    return 0


if __name__ == "__main__":
    sys.exit(main())

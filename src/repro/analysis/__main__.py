"""``python -m repro.analysis`` — run all three checkers; exit 0 == clean.

Order: AST lint (pure host, fast) -> compile-budget sentinel -> HLO
invariant checker.  The mesh budget needs multiple devices, so when no
device-count flag is configured we force 8 virtual CPU devices BEFORE jax
is imported (the same setting as the CI ``static-analysis`` job).
"""
from __future__ import annotations

import argparse
import os
import pathlib
import sys

# Must precede any jax import (the checkers import jax lazily, so setting
# it here at module import time is early enough).
if "--xla_force_host_platform_device_count" not in os.environ.get(
        "XLA_FLAGS", ""):
    os.environ["XLA_FLAGS"] = (
        os.environ.get("XLA_FLAGS", "")
        + " --xla_force_host_platform_device_count=8").strip()
os.environ.setdefault("JAX_PLATFORMS", "cpu")

_REPO_ROOT = pathlib.Path(__file__).resolve().parents[3]


def _lint_roots() -> list[str]:
    roots = []
    for name in ("src/repro", "examples", "benchmarks"):
        p = _REPO_ROOT / name
        if p.exists():
            roots.append(str(p))
    return roots or [str(_REPO_ROOT)]


def main(argv: list[str] | None = None) -> int:
    ap = argparse.ArgumentParser(
        prog="repro.analysis",
        description="trace-safety lint + compile-budget + HLO invariants")
    ap.add_argument("--skip-lint", action="store_true")
    ap.add_argument("--skip-budget", action="store_true")
    ap.add_argument("--skip-hlo", action="store_true")
    args = ap.parse_args(argv)

    failures = 0

    if not args.skip_lint:
        from repro.analysis import lint

        violations = lint.lint_paths(_lint_roots())
        for v in violations:
            print(v)
        print(f"[1/3] lint: {len(violations)} violation(s)")
        failures += len(violations)
    else:
        print("[1/3] lint: skipped")

    # One world shared by the two dynamic checkers (data build is the
    # expensive part; models stay per-scenario for fresh jit caches).
    world = None
    if not (args.skip_budget and args.skip_hlo):
        from repro.analysis.compile_budget import make_world

        world = make_world()

    if not args.skip_budget:
        from repro.analysis import compile_budget

        errors = compile_budget.check(world=world)
        for e in errors:
            print(f"FAIL {e}")
        print(f"[2/3] compile_budget: {len(errors)} violation(s)")
        failures += len(errors)
    else:
        print("[2/3] compile_budget: skipped")

    if not args.skip_hlo:
        from repro.analysis import hlo_lint

        errors = hlo_lint.check(world=world)
        for e in errors:
            print(f"FAIL {e}")
        print(f"[3/3] hlo_lint: {len(errors)} violation(s)")
        failures += len(errors)
    else:
        print("[3/3] hlo_lint: skipped")

    print(f"repro.analysis: {'CLEAN' if not failures else 'FAILED'} "
          f"({failures} total violation(s))")
    return 1 if failures else 0


if __name__ == "__main__":
    sys.exit(main())

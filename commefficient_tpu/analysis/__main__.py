"""CLI: ``python -m commefficient_tpu.analysis [paths...]``.

Exit codes: 0 clean (after baseline), 1 violations or stale baseline
or lint errors, 2 usage errors. Configuration lives in pyproject.toml
under ``[tool.graftlint]`` (paths, baseline, exclude) — flags override.
"""
from __future__ import annotations

import argparse
import os
import sys
from typing import Optional

from commefficient_tpu.analysis.engine import (
    Baseline, LintError, lint_paths, load_pyproject_tool,
)
from commefficient_tpu.analysis.rules import RULE_DOCS


def main(argv: Optional[list] = None) -> int:
    conf = load_pyproject_tool("graftlint")
    ap = argparse.ArgumentParser(
        prog="graftlint",
        description="trace-safety static analysis for the round engine "
                    "(rules GL001-GL015; see --list-rules)")
    ap.add_argument("paths", nargs="*",
                    default=conf.get("paths", ["commefficient_tpu"]),
                    help="files/directories to lint")
    ap.add_argument("--baseline", default=conf.get(
        "baseline", "graftlint.baseline.json"),
        help="baseline file of grandfathered hits")
    ap.add_argument("--no-baseline", action="store_true",
                    help="report every hit, ignoring the baseline")
    ap.add_argument("--write-baseline", action="store_true",
                    help="regenerate the baseline from the current tree")
    ap.add_argument("--list-rules", action="store_true")
    args = ap.parse_args(argv)

    if args.list_rules:
        for code, doc in sorted(RULE_DOCS.items()):
            print(f"{code}  {doc}")
        return 0

    for p in args.paths:
        if not os.path.exists(p):
            print(f"graftlint: no such path: {p}", file=sys.stderr)
            return 2

    try:
        violations = lint_paths(args.paths,
                                exclude=conf.get("exclude", ()))
    except LintError as e:
        print(f"graftlint: {e}", file=sys.stderr)
        return 1

    if args.write_baseline:
        Baseline.from_violations(violations).dump(args.baseline)
        print(f"graftlint: wrote {len(violations)} grandfathered hit(s) "
              f"to {args.baseline}")
        return 0

    baseline = Baseline()
    if not args.no_baseline and os.path.exists(args.baseline):
        baseline = Baseline.load(args.baseline)
    new, stale = baseline.apply(violations)

    for v in new:
        print(v.render())
    for msg in stale:
        print(f"graftlint: {msg}")
    n_files = len(set(v.path for v in violations))
    if new or stale:
        print(f"graftlint: {len(new)} violation(s)"
              + (f", {len(stale)} baseline problem(s)" if stale else ""))
        return 1
    grandfathered = len(violations)
    print("graftlint: clean"
          + (f" ({grandfathered} grandfathered hit(s) in {n_files} "
             f"file(s) — see {args.baseline})" if grandfathered else ""))
    return 0


if __name__ == "__main__":
    sys.exit(main())

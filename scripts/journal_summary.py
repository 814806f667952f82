#!/usr/bin/env python
"""Validate + summarize a telemetry run journal (JSONL).

The cheap check of the journal invariants (ISSUE 4 satellite): run a
tiny driver smoke with the journal on and then this tool over the
result — a malformed line, a wrong schema version,
or a duplicate/out-of-order round event fails the build, so the record
format every perf investigation depends on cannot silently rot.

ISSUE 5 extended the checked surface: per-round accountant byte
totals (`down_bytes`/`up_bytes` on round events) must be non-negative
numbers whose `run_end` cumulative covers the per-round sums, and
`schedule` events (the round scheduler's decisions) must carry an
integer round + sampler name with non-negative deadline/estimate
payloads (a smoke under `--sampler throughput --deadline_quantile
0.9` writes those records); the
summary line includes down_mib/up_mib and the deadline-round count.

ISSUE 13 (graftscope): journals from `--trace` runs additionally
report the stage-level analytics block — per-stage p50/p95 over the
trace spans (`trace_stages`), the inter-round cadence histogram
(monotonic `mono` deltas, reset at each `run_start`), writer
queue-depth gauges (`writer_queue_max`), and `overlap_efficiency`
(device-busy / wall over the `device_execute` span union). Export the
same spans to Perfetto with scripts/trace_export.py.

ISSUE 18 (graftnum): analysis-audit events may carry a
`num_audit_digest` — the sha256 of the canonical graftnum numerics
report.  The validator holds it to the same 64-hex-char contract as
the other analysis digests and checks the `ulp` worst-case
reassociation bounds block (non-negative ints per program); the
summary surfaces the digests (`analysis_digests`) and finding count
(`num_audit_findings`) so a CI run records which numerics contract it
was green against.
ISSUE 20 (control/): `control` events — one per controller-bank
adjustment — are schema-checked (integer `round`, `controller`
registered in analysis.domains.CONTROL_FIELDS, numeric
`signal`/`old`/`new`, boolean `clamped`), and the summary grows a
`controllers` block with per-controller adjustment/clamp counts and
the final value, so a self-tuning smoke can gate on "every
controller actually moved" from one summary read.

Usage:
    python scripts/journal_summary.py <journal.jsonl> [--quiet]

Exit codes: 0 valid journal, 1 invariant violations (listed on
stderr), 2 unreadable input.
"""
from __future__ import annotations

import argparse
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

from commefficient_tpu.telemetry.journal import (  # noqa: E402
    summarize, validate_journal,
)


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__)
    p.add_argument("journal", help="path to a journal.jsonl")
    p.add_argument("--quiet", action="store_true",
                   help="suppress the summary line (problems still "
                        "print to stderr)")
    args = p.parse_args(argv)

    counters: dict = {}
    try:
        records, problems = validate_journal(args.journal,
                                             counters=counters)
    except OSError as e:
        print(f"journal_summary: cannot read {args.journal!r}: {e}",
              file=sys.stderr)
        return 2

    if not records and not problems:
        problems = ["journal is empty (no records at all)"]

    if not args.quiet:
        # corrupt interior lines are skipped-and-counted, not
        # violations (ISSUE 12 satellite) — the count rides in the
        # summary so a journal that survived a mid-batch writer crash
        # says so
        print(json.dumps(summarize(
            records,
            corrupt_lines=counters.get("corrupt_interior", 0))))
    if problems:
        for prob in problems:
            print(f"journal_summary: INVALID: {prob}", file=sys.stderr)
        return 1
    return 0


if __name__ == "__main__":
    raise SystemExit(main())

#!/usr/bin/env python3
"""Readings for the limits of `correct`: the program, its control and
its faults over several seeds, in one process.

    python3 fedbench/control.py --workload <name> --seeds 1,2,3 \\
        --mode program|bf16|half_batch|frozen [--out FILE]

`program` gives the lower readings (sound runs). `bf16` is the
control: the program with its own lower precision switched on
(Config.do_bf16) against the same reference. `half_batch` and
`frozen` plant the faults a training cell can have (harness.run's
`fault`). Each seed is one `harness.run` with a one-second window: a
training cell's readings come from the first rounds of set-up and need
no measured window. The benchmark's own runs never come here.
"""
from __future__ import annotations

import argparse
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)


def main(argv=None) -> int:
    p = argparse.ArgumentParser()
    p.add_argument("--workload", required=True)
    p.add_argument("--seeds", required=True)
    p.add_argument("--mode", default="program",
                   choices=("program", "bf16", "half_batch", "frozen"))
    p.add_argument("--manifest", default=None)
    p.add_argument("--platform", default="tpu")
    p.add_argument("--out", default=None)
    args = p.parse_args(argv)
    from fedbench import harness
    for seed in (int(s) for s in args.seeds.split(",")):
        result = harness.run(
            args.workload, seed, 1.0, False,
            manifest_path=args.manifest,
            expect_platform=args.platform or None,
            fault=(args.mode if args.mode in ("half_batch", "frozen")
                   else None),
            bf16=args.mode == "bf16")
        row = {"workload": args.workload, "mode": args.mode,
               "seed": seed, "correct": result["correct"],
               "checks": {k: v["value"]
                          for k, v in result["checks"].items()}}
        print(json.dumps(row), flush=True)
        if args.out:
            os.makedirs(os.path.dirname(args.out) or ".", exist_ok=True)
            with open(args.out, "a") as f:
                f.write(json.dumps(row) + "\n")
    return 0


if __name__ == "__main__":
    raise SystemExit(main())

#!/usr/bin/env python3
"""What the instruments cost: `round_ms` of one benchmark cell with the
program's TRACE off or on, and in each case with the JAX profiler off
and then on (PERF.md section 3 has the table this fills).

    python3 scripts/instrument_cost.py --workload <cell> --seed <n> \
        --trace <0|1> [--seconds 30] [--profile-seconds 8]

One process is one job (built with or without `--trace`, as
`fedbench/harness.py` builds it) and two legs over the harness's own
driver loop: `--seconds` with the profiler off, then
`--profile-seconds` under the profiler with the harness's options.
Per leg it prints one JSON line: rounds, `round_ms`, the loader's and
`FedModel.__call__`'s host ms a round (the driver loop's own clock,
so they exist with TRACE off too) and, with TRACE on, the journaled
`collect` and `device_wait` spans' ms a round. The benchmark's cells
run on the chip only.
"""
from __future__ import annotations

import argparse
import json
import os
import shutil
import sys
import time

T_START = time.perf_counter()
ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)


def leg(drv, job, gen, seconds: float, profile_dir: str | None) -> dict:
    import jax

    if profile_dir:
        options = jax.profiler.ProfileOptions()
        options.python_tracer_level = 0
        options.host_tracer_level = 1
        jax.profiler.start_trace(profile_dir, profiler_options=options)
    stage, api = [], []
    m0, t0 = time.monotonic(), time.perf_counter()
    while True:
        out = next(gen)
        stage.append(out.stage_s)
        api.append(out.api_s)
        if time.perf_counter() - t0 >= seconds:
            break
    drv.sync(job)
    t1, m1 = time.perf_counter(), time.monotonic()
    if profile_dir:
        jax.profiler.stop_trace()
        shutil.rmtree(profile_dir, ignore_errors=True)
    n = len(api)
    return {"profiler": bool(profile_dir), "rounds": n,
            "round_ms": (t1 - t0) / n * 1e3,
            "loader_ms": sum(stage) / n * 1e3,
            "call_ms": sum(api) / n * 1e3, "mono": (m0, m1)}


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__)
    p.add_argument("--workload", required=True)
    p.add_argument("--seed", type=int, required=True)
    p.add_argument("--trace", type=int, choices=(0, 1), required=True)
    p.add_argument("--seconds", type=float, default=30.0)
    p.add_argument("--profile-seconds", type=float, default=8.0)
    p.add_argument("--manifest", default=None,
                   help="another manifest (rehearsals off the chip)")
    args = p.parse_args(argv)

    from fedbench import harness, reduce as reducer, traffic
    from commefficient_tpu.utils.cache import (
        enable_persistent_compilation_cache,
    )
    enable_persistent_compilation_cache()
    cell = harness.Cell(
        args.manifest or os.path.join(ROOT, "BENCHMARK.json"),
        args.workload)
    device = harness.device_record(
        cell.chips, None if args.manifest else "tpu")
    cache_dir = os.path.join(cell.bench_dirs[0], ".cache")
    data_dir = traffic.ensure_corpus(cell.traffic, cache_dir)
    run_dir = os.path.join(cache_dir, "runs", args.workload + ".cost")
    shutil.rmtree(run_dir, ignore_errors=True)
    os.makedirs(run_dir)
    journal = os.path.join(run_dir, "journal.jsonl")
    drv = cell.driver
    job = drv.build(cell.config, cell.traffic, cell.ref_module, args.seed,
                    data_dir, journal, trace=bool(args.trace))
    ok, legs = False, []
    try:
        gen = drv.rounds(job)
        for _ in range(harness.WARM_ROUNDS):
            next(gen)
        drv.sync(job)
        setup_s = time.perf_counter() - T_START
        legs.append(leg(drv, job, gen, args.seconds, None))
        legs.append(leg(drv, job, gen, args.profile_seconds,
                        os.path.join(run_dir, "trace")))
        ok = True
    finally:
        drv.close(job, ok)
    spans = reducer.journal_spans(journal)
    for row in legs:
        m0, m1 = row.pop("mono")
        for name in ("collect", "device_wait", "load", "round"):
            durs = [float(s["dur"]) for s in spans
                    if s.get("name") == name
                    and m0 <= float(s.get("t0", -1.0)) <= m1]
            row["span_" + name + "_ms"] = (
                sum(durs) / row["rounds"] * 1e3 if durs else None)
        row.update(workload=args.workload, seed=args.seed,
                   trace=bool(args.trace), device=device["kind"],
                   setup_s=setup_s)
        print(json.dumps(row), flush=True)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())

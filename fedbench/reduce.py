"""From the profiler's `.xplane.pb` and the program's journaled TRACE
spans to numbers: device busy time, time per op and per jitted module,
idle gaps attributed to what the host was doing in them.

What counts as a device op: on a TPU, the events of the line "XLA Ops"
of each plane "/device:TPU:<n>" (the line "XLA Modules" gives one
event per run of a jitted program); on the CPU backend, which only
the tests use, the events that carry an `hlo_op` stat. Host activity
is read from the `fedbench:*` TraceAnnotations the driver loop writes
into the same trace, so both are on one clock.

    python3 fedbench/reduce.py <file.xplane.pb>      prints what it finds
"""
from __future__ import annotations

import glob
import json
import os
import sys
from collections import defaultdict

OP_LINE = "XLA Ops"
MODULE_LINE = "XLA Modules"
HOST_PREFIX = "fedbench:"
# device gaps shorter than this are between back-to-back ops
GAP_FLOOR_S = 20e-6


def find_xplane(trace_dir: str) -> str:
    found = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return found[-1]


def union_seconds(intervals) -> float:
    """Length of the union of [start, end) intervals."""
    total, end = 0.0, None
    for a, b in sorted(intervals):
        if end is None or a > end:
            total += b - a
            end = b
        elif b > end:
            total += b - end
            end = b
    return total


def gaps_of(intervals, floor: float = GAP_FLOOR_S) -> list:
    """Idle [start, end) stretches between the first and last
    interval, longer than `floor`."""
    out, end = [], None
    for a, b in sorted(intervals):
        if end is not None and a - end > floor:
            out.append((end, a))
        end = b if end is None else max(end, b)
    return out


def read_events(path: str) -> dict:
    """{"devices": {name: {"ops": [(name, t0, t1, module)],
    "modules": [(name, t0, t1)]}}, "host": [(name, t0, t1)]} with
    times in seconds."""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    devices = defaultdict(lambda: {"ops": [], "modules": []})
    host = []
    for plane in data.planes:
        on_tpu = plane.name.startswith("/device:TPU:")
        for line in plane.lines:
            for ev in line.events:
                t0 = ev.start_ns * 1e-9
                t1 = t0 + ev.duration_ns * 1e-9
                if on_tpu:
                    if line.name == OP_LINE:
                        devices[plane.name]["ops"].append(
                            (ev.name, t0, t1, ""))
                    elif line.name == MODULE_LINE:
                        devices[plane.name]["modules"].append(
                            (ev.name, t0, t1))
                    continue
                if ev.name.startswith(HOST_PREFIX):
                    host.append((ev.name[len(HOST_PREFIX):], t0, t1))
                    continue
                if plane.name.startswith("/host:"):
                    stats = dict(ev.stats)
                    if "hlo_op" in stats:
                        devices["cpu"]["ops"].append(
                            (ev.name, t0, t1,
                             str(stats.get("hlo_module", ""))))
    for dev in devices.values():
        if not dev["modules"]:
            # the CPU backend has no module line: a module's time is
            # the sum of its ops
            by = defaultdict(list)
            for name, t0, t1, module in dev["ops"]:
                if module:
                    by[module].append((t0, t1))
            dev["modules"] = [(m, a, b) for m, iv in by.items()
                              for a, b in iv]
    return {"devices": dict(devices), "host": host}


def short_op(name: str) -> str:
    """A TPU op event is named by its whole HLO line; keep the
    instruction's name and its result's type, no spaces:
    '%fusion.2 = bf16[1024,1024]{...} fusion(...)' ->
    'fusion.2:bf16[1024,1024]'."""
    if " = " not in name:
        return name[:80].replace(" ", "_")
    lhs, rhs = name.split(" = ", 1)
    result = rhs.split("{", 1)[0].strip().lstrip("(")
    return (lhs.strip().lstrip("%") + ":" + result)[:80].replace(" ", "")


def module_key(name: str) -> str:
    """'jit_round_step(1234)' -> 'jit_round_step'."""
    return name.split("(")[0].strip()


def reduce_events(events: dict) -> dict:
    """busy_s and window_s (averaged over devices), seconds per op name
    and per module (summed per device, averaged over devices), and
    the first device's idle gaps attributed to host activity."""
    devices = events["devices"]
    if not devices:
        raise RuntimeError("the trace holds no device operation")
    n = len(devices)
    busy = window = 0.0
    ops = defaultdict(float)
    modules = defaultdict(float)
    module_runs = defaultdict(int)
    for dev in devices.values():
        iv = [(a, b) for _, a, b, _ in dev["ops"]]
        if not iv:
            continue
        busy += union_seconds(iv) / n
        window += (max(b for _, b in iv) - min(a for a, _ in iv)) / n
        for name, a, b, _ in dev["ops"]:
            ops[short_op(name)] += (b - a) / n
        for name, a, b in dev["modules"]:
            modules[module_key(name)] += (b - a) / n
            module_runs[module_key(name)] += 1
    first = devices[sorted(devices)[0]]
    gaps = gaps_of([(a, b) for _, a, b, _ in first["ops"]])
    host = events["host"]
    by_activity = defaultdict(float)
    longest = []
    for a, b in gaps:
        overlap = defaultdict(float)
        for name, h0, h1 in host:
            if name == "round":
                continue
            o = min(b, h1) - max(a, h0)
            if o > 0:
                overlap[name] += o
        covered = sum(overlap.values())
        overlap["other"] = max((b - a) - covered, 0.0)
        for name, o in overlap.items():
            by_activity[name] += o
        longest.append((max(overlap, key=overlap.get), b - a))
    longest.sort(key=lambda g: -g[1])
    return {"busy_s": busy, "window_s": window, "devices": n,
            "ops": dict(ops), "modules": dict(modules),
            "module_runs": {k: v // n for k, v in module_runs.items()},
            "gap_s": dict(by_activity), "longest_gaps": longest[:10],
            "host_s": _host_totals(host)}


def _host_totals(host) -> dict:
    out = defaultdict(float)
    for name, a, b in host:
        out[name] += b - a
    return dict(out)


def reduce_trace(path: str) -> dict:
    return reduce_events(read_events(path))


def breakdown(reduced: dict) -> dict:
    """The traced run's `breakdown`: the ten device ops that took most
    time, and the idle time by host activity followed by the longest
    single gaps, each under what the host was doing."""
    top = sorted(reduced["ops"].items(), key=lambda kv: -kv[1])[:10]
    idle = sorted(reduced["gap_s"].items(), key=lambda kv: -kv[1])
    idle = [[f"all:{k}", v] for k, v in idle if v > 0][:4]
    idle += [[f"longest:{k}", v] for k, v in reduced["longest_gaps"]]
    return {"device_ops": [[k, v] for k, v in top],
            "idle_gaps": idle[:10]}


def journal_spans(journal_path: str) -> list:
    """The program's TRACE span records (`trace` journal events)."""
    spans = []
    if not os.path.isfile(journal_path):
        return spans
    with open(journal_path) as f:
        for line in f:
            try:
                rec = json.loads(line)
            except ValueError:
                continue   # a torn last line
            if rec.get("event") == "trace":
                spans.extend(rec.get("spans", ()))
    return spans


def main(argv) -> int:
    events = read_events(argv[1])
    from jax.profiler import ProfileData
    data = ProfileData.from_file(argv[1])
    for plane in data.planes:
        print("PLANE", plane.name)
        for line in plane.lines:
            evs = list(line.events)
            names = defaultdict(float)
            for ev in evs:
                names[ev.name] += ev.duration_ns * 1e-9
            top = sorted(names.items(), key=lambda kv: -kv[1])[:12]
            print(f"  LINE {line.name!r}: {len(evs)} events")
            for k, v in top:
                print(f"      {v:10.6f} s  {k[:100]}")
            if evs:
                print("      stats of first:", dict(evs[0].stats))
    red = reduce_events(events)
    red["ops"] = dict(sorted(red["ops"].items(),
                             key=lambda kv: -kv[1])[:20])
    print(json.dumps(red, indent=1, default=str))
    return 0


if __name__ == "__main__":
    raise SystemExit(main(sys.argv))

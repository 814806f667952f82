"""What the per-scope and per-span readers share: the run's own files,
device seconds per layer scope, idle time per program span, and the
program's journaled spans from the part of the window in which the
profiler is off. Each is worked out once per run and kept.

Layer scopes. The program wraps its layers in `jax.named_scope`
(`commefficient_tpu/scopes.py`); the names travel in each op's
op-name path, which the chip's `.xplane.pb` holds per op in the TPU
plane's `event_metadata` table as the stat `tf_op`
(`jit(round_step)/.../fed_fwdbwd/conv_general_dilated:`).
`jax.profiler.ProfileData` does not show that table, so this module
reads the few XSpace fields it needs with a small wire-format reader
(field numbers from tsl/profiler/protobuf/xplane.proto; no
tensorflow, no protobuf package). An op belongs to the OUTERMOST
scope name on its path, matched as a whole component also inside
`jvp(...)`, `transpose(...)`, `vmap(...)`; a scope's seconds are the
union of its ops' intervals on the line "XLA Ops" (a `while` op's
event covers its body's). XLA names a fusion after one of its ops, so
the split is exact to a fusion.

Program spans. While the program's TRACE is enabled each span also
opens a `fed:<stage>` TraceAnnotation, so the profiler's trace holds
them on its own clock; each idle gap of the device goes to the
innermost `fed:*` span of the dispatching thread that covers it.

A program without scopes or spans (an older commit) gives None from
every reader here, never an error.
"""
from __future__ import annotations

import bisect
import glob
import json
import os
import re
import sys
from collections import defaultdict

from fedbench import reduce as reducer

BENCH_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SCOPE = re.compile(r"(?:^|[/(])fed_([a-z_]+)(?=[/):]|$)")
SPAN_PREFIX = "fed:"
# the programs whose unscoped device time `device_unscoped_ms` reports
ROUND_MODULES = ("round_step", "round_full", "train_rounds")

_cache: dict = {}


def say(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


# ---------------- the run's own files ------------------------------------

def run_dir(ctx) -> str | None:
    """`ctx["run_dir"]` where a harness gives it; else the newest
    `.cache/runs/<cell>` under the fedbench tree that holds a trace
    (the harness removes the trace after the readers have run)."""
    if ctx.get("run_dir"):
        return ctx["run_dir"]
    key = ("run_dir", ctx["cell"])
    if key not in _cache or not os.path.isdir(
            os.path.join(_cache[key], "trace")):
        found = [d for d in glob.glob(os.path.join(
            BENCH_ROOT, "**", ".cache", "runs", ctx["cell"]),
            recursive=True) if os.path.isdir(os.path.join(d, "trace"))]
        if not found:
            return None
        _cache[key] = max(found, key=os.path.getmtime)
    return _cache[key]


def _xplane(ctx) -> str | None:
    d = run_dir(ctx)
    if d is None:
        return None
    try:
        return reducer.find_xplane(os.path.join(d, "trace"))
    except FileNotFoundError:
        return None


# ---------------- a wire-format reader for XSpace ------------------------

def _varint(buf, i):
    out = shift = 0
    while True:
        b = buf[i]
        i += 1
        out |= (b & 0x7F) << shift
        if b < 0x80:
            return out, i
        shift += 7


def _fields(buf, i, end):
    """(field number, wire type, value) of one message: ints for
    varints and fixed words, (start, end) for length-delimited."""
    while i < end:
        key, i = _varint(buf, i)
        wire = key & 7
        if wire == 0:
            value, i = _varint(buf, i)
        elif wire == 2:
            n, i = _varint(buf, i)
            value = (i, i + n)
            i += n
        elif wire == 1:
            value = (i, i + 8)
            i += 8
        elif wire == 5:
            value = (i, i + 4)
            i += 4
        else:
            raise ValueError(f"wire type {wire} at byte {i}")
        yield key >> 3, wire, value


def _text(buf, span) -> str:
    return buf[span[0]:span[1]].decode("utf-8", "replace")


def _map_entries(buf, spans):
    """`map<int64, Message>` entries: {key: (start, end) of value}."""
    out = {}
    for a, b in spans:
        key, value = 0, None
        for num, _, v in _fields(buf, a, b):
            if num == 1:
                key = v
            elif num == 2:
                value = v
        if value is not None:
            out[key] = value
    return out


def read_device_planes(path: str) -> dict:
    """{plane name: {"tf_op": {metadata id: path}, "names": {metadata
    id: name}, "lines": {line name: [(metadata id, t0, t1)]}}} for the
    planes "/device:TPU:<n>", times in seconds on the profiler's
    clock. Only the metadata tables and the lines "XLA Ops" and "XLA
    Modules" are decoded."""
    with open(path, "rb") as f:
        buf = f.read()
    planes = {}
    for num, _, span in _fields(buf, 0, len(buf)):
        if num != 1:
            continue
        name, lines, emeta, smeta = "", [], [], []
        for pnum, _, v in _fields(buf, *span):
            if pnum == 2:
                name = _text(buf, v)
            elif pnum == 3:
                lines.append(v)
            elif pnum == 4:
                emeta.append(v)
            elif pnum == 5:
                smeta.append(v)
        if not name.startswith("/device:TPU:"):
            continue
        stat_names = {}
        for key, v in _map_entries(buf, smeta).items():
            for snum, _, sv in _fields(buf, *v):
                if snum == 2:
                    stat_names[key] = _text(buf, sv)
        tf_op_ids = {k for k, n in stat_names.items() if n == "tf_op"}
        tf_op, names = {}, {}
        for key, v in _map_entries(buf, emeta).items():
            for mnum, _, mv in _fields(buf, *v):
                if mnum == 2:
                    names[key] = _text(buf, mv)
                elif mnum == 5:
                    stat_id, text = None, None
                    for snum, _, sv in _fields(buf, *mv):
                        if snum == 1:
                            stat_id = sv
                        elif snum == 5:
                            text = _text(buf, sv)
                        elif snum == 7:   # a reference into stat_metadata
                            text = stat_names.get(sv, "")
                    if stat_id in tf_op_ids and text is not None:
                        tf_op[key] = text
        out_lines = {}
        for a, b in lines:
            line_name, t_line, events = "", 0, []
            for lnum, _, v in _fields(buf, a, b):
                if lnum == 2:
                    line_name = _text(buf, v)
                elif lnum == 3:
                    t_line = v
                elif lnum == 4:
                    events.append(v)
            if line_name not in (reducer.OP_LINE, reducer.MODULE_LINE):
                continue
            rows = []
            for ea, eb in events:
                meta = offset = dur = 0
                for enum, wire, v in _fields(buf, ea, eb):
                    if wire:
                        continue
                    if enum == 1:
                        meta = v
                    elif enum == 2:
                        offset = v
                    elif enum == 3:
                        dur = v
                t0 = t_line * 1e-9 + offset * 1e-12
                rows.append((meta, t0, t0 + dur * 1e-12))
            out_lines[line_name] = rows
        planes[name] = {"tf_op": tf_op, "names": names, "lines": out_lines}
    return planes


def _device_planes(path: str) -> dict:
    key = ("planes", path)
    if key not in _cache:
        _cache[key] = read_device_planes(path)
    return _cache[key]


def scope_of(tf_op: str) -> str | None:
    m = SCOPE.search(tf_op)
    return m.group(1) if m else None


# ---------------- device seconds per scope --------------------------------

def scope_seconds(ctx) -> dict | None:
    """{"scopes": {scope: s}, "unscoped": {module: s}, "pathless":
    {module: s}, "modules": {module: s}} averaged over the devices, or
    None where the trace has no `tf_op` or the program lays no scope.
    `pathless` is the part of `unscoped` whose ops carry no op path at
    all: instructions the compiler made (a scatter's loop, mask
    packing, layout copies), which no named scope can reach."""
    path = _xplane(ctx)
    if path is None:
        return None
    key = ("scopes", path)
    if key not in _cache:
        _cache[key] = _scope_seconds(_device_planes(path))
        _say_scopes(ctx, _cache[key])
    return _cache[key]


def _scope_seconds(planes: dict) -> dict | None:
    planes = {k: p for k, p in planes.items()
              if p["lines"].get(reducer.OP_LINE)}
    if not planes or not any(p["tf_op"] for p in planes.values()):
        return None
    if not any(scope_of(t) for p in planes.values()
               for t in p["tf_op"].values()):
        return None
    n = len(planes)
    scopes = defaultdict(float)
    unscoped = defaultdict(float)
    pathless = defaultdict(float)
    modules = defaultdict(float)
    for plane in planes.values():
        mods = sorted((t0, t1, reducer.module_key(plane["names"].get(m, "")))
                      for m, t0, t1 in plane["lines"].get(
                          reducer.MODULE_LINE, ()))
        starts = [m[0] for m in mods]
        by_scope = defaultdict(list)
        by_module = defaultdict(list)
        no_path = defaultdict(list)
        scope_by_meta = {m: scope_of(t) for m, t in plane["tf_op"].items()}
        for meta, t0, t1 in plane["lines"][reducer.OP_LINE]:
            scope = scope_by_meta.get(meta)
            if scope is not None:
                by_scope[scope].append((t0, t1))
                continue
            i = bisect.bisect_right(starts, t0) - 1
            inside = i >= 0 and t0 < mods[i][1]
            module = mods[i][2] if inside else "(no module)"
            by_module[module].append((t0, t1))
            if meta not in plane["tf_op"]:
                no_path[module].append((t0, t1))
        for scope, iv in by_scope.items():
            scopes[scope] += reducer.union_seconds(iv) / n
        for module, iv in by_module.items():
            unscoped[module] += reducer.union_seconds(iv) / n
        for module, iv in no_path.items():
            pathless[module] += reducer.union_seconds(iv) / n
        for t0, t1, module in mods:
            modules[module] += (t1 - t0) / n
    return {"scopes": dict(scopes), "unscoped": dict(unscoped),
            "pathless": dict(pathless), "modules": dict(modules)}


def _say_scopes(ctx, found) -> None:
    if found is None:
        say("[fedbench] scopes: the trace names no layer scope")
        return
    m = max(ctx["rounds"], 1)

    def ms(d):
        return {k: round(v / m * 1e3, 3) for k, v in sorted(d.items())}
    say("[fedbench] device ms a round per scope: "
        + json.dumps(ms(found["scopes"])) + "; unscoped, per module: "
        + json.dumps(ms(found["unscoped"]))
        + "; of it with no op path (compiler-made): "
        + json.dumps(ms(found["pathless"])))


def scope_ms(ctx, *names):
    """ms a round of the named scopes together; 0.0 where the program
    lays scopes and these have no op."""
    found = scope_seconds(ctx)
    if found is None or not ctx["rounds"]:
        return None
    return sum(found["scopes"].get(n, 0.0) for n in names) \
        / ctx["rounds"] * 1e3


def unscoped_ms(ctx):
    found = scope_seconds(ctx)
    if found is None or not ctx["rounds"]:
        return None
    return sum(s for module, s in found["unscoped"].items()
               if any(n in module for n in ROUND_MODULES)) \
        / ctx["rounds"] * 1e3


# ---------------- idle time per program span ------------------------------

def read_program_annotations(path: str) -> list:
    """[(stage, t0, t1, round or None)] of the `fed:*` annotations on
    the thread that dispatches (the one that holds `fed:dispatch`,
    else any), by start."""
    from jax.profiler import ProfileData

    by_line = []
    for plane in ProfileData.from_file(path).planes:
        if plane.name.startswith("/device:"):
            continue
        for line in plane.lines:
            found = []
            for ev in line.events:
                # a tagged annotation may keep its `#round=3#` suffix
                name = ev.name.split("#", 1)[0]
                if name.startswith(SPAN_PREFIX):
                    t0 = ev.start_ns * 1e-9
                    found.append((name[len(SPAN_PREFIX):], t0,
                                  t0 + ev.duration_ns * 1e-9,
                                  dict(ev.stats).get("round")))
            if found:
                by_line.append(found)
    dispatching = [f for f in by_line
                   if any(span[0] == "dispatch" for span in f)]
    return sorted(sum(dispatching or by_line, []), key=lambda s: s[1])


def idle_by_span(ctx) -> dict | None:
    """{"idle": s, "by_span": {stage: s}, "unattributed": s} over the
    first device's idle gaps, or None where the trace holds no `fed:*`
    annotation."""
    path = _xplane(ctx)
    if path is None:
        return None
    key = ("idle", path)
    if key not in _cache:
        spans = read_program_annotations(path)
        planes = _device_planes(path)
        _cache[key] = _idle_by_span(spans, planes)
        if _cache[key] is not None:
            say("[fedbench] one clock, ms from each round's fed:dispatch"
                " start [round, fed:stage end, first op of its "
                "round program]: " + json.dumps(clock_rows(spans, planes)))
            m = max(ctx["rounds"], 1)
            say("[fedbench] idle ms a round per program span: "
                + json.dumps({k: round(v / m * 1e3, 3) for k, v in sorted(
                    _cache[key]["by_span"].items())})
                + f"; unattributed "
                  f"{_cache[key]['unattributed'] / m * 1e3:.3f} of "
                  f"{_cache[key]['idle'] / m * 1e3:.3f}")
    return _cache[key]


def _idle_by_span(spans: list, planes: dict) -> dict | None:
    if not spans:
        return None
    planes = {k: p for k, p in planes.items()
              if p["lines"].get(reducer.OP_LINE)}
    if planes:
        first = planes[sorted(planes)[0]]
        ops = [(t0, t1) for _, t0, t1 in first["lines"][reducer.OP_LINE]]
    else:
        return None
    return attribute_gaps(reducer.gaps_of(ops), spans)


def attribute_gaps(gaps: list, spans: list) -> dict:
    """Each stretch of each gap goes to the innermost (shortest) span
    that covers it."""
    by_span = defaultdict(float)
    idle = unattributed = 0.0
    starts = [s[1] for s in spans]
    longest = max((s[2] - s[1] for s in spans), default=0.0)
    for a, b in gaps:
        idle += b - a
        lo = bisect.bisect_left(starts, a - longest)
        hi = bisect.bisect_right(starts, b)
        over = [s for s in spans[lo:hi] if s[2] > a and s[1] < b]
        cuts = sorted({a, b, *(t for s in over for t in s[1:3]
                               if a < t < b)})
        for x, y in zip(cuts, cuts[1:]):
            mid = (x + y) / 2
            cover = [s for s in over if s[1] <= mid < s[2]]
            if cover:
                name = min(cover, key=lambda s: s[2] - s[1])[0]
                by_span[name] += y - x
            else:
                unattributed += y - x
    return {"idle": idle, "by_span": dict(by_span),
            "unattributed": unattributed}


def clock_rows(spans: list, planes: dict, limit: int = 10) -> list:
    """For the first `limit` rounds: [round, end of its `fed:stage`,
    start of the first device op of its round program], both in ms
    from the start of its `fed:dispatch`. The k-th dispatch in the
    trace started the k-th run of the round program (the window
    opens with the device idle)."""
    planes = [planes[k] for k in sorted(planes)
              if planes[k]["lines"].get(reducer.MODULE_LINE)]
    if not planes:
        return []
    first = planes[0]
    runs = sorted(t0 for m, t0, _ in first["lines"][reducer.MODULE_LINE]
                  if any(n in first["names"].get(m, "")
                         for n in ROUND_MODULES))
    ops = sorted(t0 for _, t0, _ in first["lines"].get(reducer.OP_LINE, ()))
    stage_end = {s[3]: s[2] for s in spans if s[0] == "stage"}
    rows = []
    dispatches = [s for s in spans if s[0] == "dispatch"]
    for k, (_, t0, _, rnd) in enumerate(dispatches[:limit]):
        if k >= len(runs):
            break
        i = bisect.bisect_left(ops, runs[k])
        first_op = ops[i] if i < len(ops) else runs[k]
        rows.append([rnd,
                     None if rnd not in stage_end
                     else round((stage_end[rnd] - t0) * 1e3, 3),
                     round((first_op - t0) * 1e3, 3)])
    return rows


# ---------------- journaled spans, profiler off ---------------------------

def untraced_spans(ctx) -> list:
    """The journal's span records that begin after the last span of
    `ctx["program_spans"]`: the part of the window in which the
    profiler is off. What the program has flushed so far (it writes
    a few times a second)."""
    d = run_dir(ctx)
    if d is None:
        return []
    # a run is known by its trace file (the directory is the cell's)
    key = ("journal", _xplane(ctx) or d, len(ctx["program_spans"]))
    if key not in _cache:
        cut = max((float(s.get("t0", 0.0)) + float(s.get("dur", 0.0))
                   for s in ctx["program_spans"]), default=None)
        spans = reducer.journal_spans(os.path.join(d, "journal.jsonl"))
        _cache[key] = [] if cut is None else [
            s for s in spans if float(s.get("t0", -1.0)) > cut]
    return _cache[key]


def untraced_span_ms(ctx, name: str, per: str):
    """Mean ms of span `name` per span `per` (the span that counts
    rounds or batches) in the untraced part of the window."""
    spans = untraced_spans(ctx)
    durs = [float(s["dur"]) for s in spans
            if s.get("name") == name and "dur" in s]
    n = sum(1 for s in spans if s.get("name") == per)
    if not durs or not n:
        return None
    return sum(durs) / n * 1e3

"""graftscope: cross-thread round-lifecycle tracing (ISSUE 13).

The engine overlaps five concurrent actors per round — host staging,
device execution, the journal/checkpoint/spill writer threads (ISSUE
10/11), and the broadcast control plane (ISSUE 12) — but the journal
records only round-granularity events, so "where did this round's
120 ms go?" and "is the pipeline actually overlapping?" were
unanswerable without ad-hoc printf. This module is the measurement
substrate: monotonic-clock spans around every stage of the round
lifecycle, tagged with the correlation keys that stitch cross-thread
(and cross-controller) records into one timeline.

Design constraints, in order:

  * ALWAYS AVAILABLE, DEFAULT OFF. The global `TRACE` object exists
    unconditionally so instrumentation sites (`with TRACE.span(...)`)
    cost one attribute check + one call when disabled — no journal
    writes, no ring appends, no allocation beyond the shared no-op
    context manager. `--trace` (Config.trace) enables it.
  * ZERO TRACED-PROGRAM CHANGES. Every span brackets HOST code — a
    dispatch call, a queue wait, an fsync — never anything inside a
    jitted program. The three-round-programs contract, the
    graftaudit/graftmesh baselines, and transfer-guard cleanliness
    are untouched whether tracing is on or off (tests/test_trace.py
    pins ServerState bit-identity on vs off).
  * MONOTONIC CLOCK. Span timestamps come from time.monotonic() — a
    wall-clock (time.time) difference is not a duration (NTP steps;
    graftlint GL011). The journal's per-record `ts`/`mono` pair maps
    monotonic trace time back onto wall time for export.
  * BEST-EFFORT, BOUNDED. Spans buffer in per-thread rings (bounded;
    overflow drops-and-counts, never blocks) and flush as batched
    `trace` journal events at span boundaries, at most four a second
    unless a ring is half full (TelemetrySession._flush_trace; close
    forces the rest out) — one fsync per flush, torn-tail rules
    intact, I/O failures warn-once like all telemetry
    (TelemetrySession._safe_write).
  * ONE CLOCK WITH THE PROFILER. While enabled, every `span()` also
    holds open a `jax.profiler.TraceAnnotation` named `fed:<stage>`
    carrying the span's `round`, so a profiler capture shows the
    program's own spans beside the device ops on the profiler's
    clock. `record()` and `instant()` have no interval of their own
    to bracket and stay journal-only.

Span records are small dicts:

    {"name": <stage>, "t0": <monotonic s>, "dur": <s>,
     "thread": <thread name>, ...tags}

with the correlation tags:

    round   the producing round index (round_idx)
    span    the scanned-span index (the same counter --profile_spans
            selects on, so a jax.profiler capture of spans [A, B)
            correlates with the device_execute trace spans tagged
            span=A..B-1)
    seq     per-writer submission sequence number: a producer-side
            `*_enqueue` instant and the writer-thread `*_qwait` /
            `*_write` spans of one queued item share a `seq`, which is
            how a writer thread's work stitches back to the round that
            produced it
    q       queue depth observed at enqueue (writer back-pressure
            gauge; summarize() surfaces the max per writer)

The stage names (README "Tracing" has the full table): round, plan,
plan_install, stage, gather, round_dispatch, scatter, dispatch,
device_execute, collect, device_wait, load, load_sample, load_fetch,
load_assemble, tier_spill, tier_restore, checkpoint, journal_write,
plus the per-writer {journal,checkpoint,state-spill}
_enqueue/_qwait/_write families.

Nested spans inherit their enclosing span's `round`/`span` tags
(thread-local stack), so e.g. a checkpoint writer enqueue recorded
inside the `checkpoint` span carries the checkpoint's round without
every call site re-plumbing indices.

`scripts/trace_export.py` converts a journal's trace events into
Chrome trace-event JSON loadable in Perfetto (one process row per
controller, one thread row per thread); `journal.summarize()` computes
the stage-level analytics block (per-stage p50/p95, inter-round
cadence histogram, writer queue-depth gauges, and the pipeline
overlap-efficiency metric device-busy/wall).
"""
from __future__ import annotations

import threading
import time
from typing import Callable, Dict, List, Optional, Tuple

__all__ = ["TRACE", "Tracer", "device_busy_wall", "overlap_efficiency",
           "stage_stats"]

# tags inherited by nested spans / instants from the innermost open
# span on the same thread (correlation keys, not payload)
_INHERITED_TAGS = ("round", "span")
# a span's name in a profiler capture: `fed:<stage>`
ANNOTATION_PREFIX = "fed:"


class _NullSpan:
    """Shared no-op context manager: the disabled-tracer fast path
    allocates nothing per call."""

    __slots__ = ()

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return False

    def tag(self, **tags) -> None:
        pass


_NULL_SPAN = _NullSpan()


class _Span:
    """One open span: context manager that commits its record on exit.
    Pushed on the owning thread's open-span stack so nested spans and
    instants inherit its correlation tags. It also holds open a
    `jax.profiler.TraceAnnotation` named `fed:<stage>` with the span's
    `round` tag, so a profiler capture shows every program span on the
    profiler's own clock beside the device ops (a TraceAnnotation
    outside a capture records nothing)."""

    __slots__ = ("_tracer", "rec", "_stack", "_annotation")

    def __init__(self, tracer: "Tracer", rec: dict, stack: list):
        self._tracer = tracer
        self.rec = rec
        self._stack = stack
        self._annotation = None

    def __enter__(self):
        make = self._tracer._annotation
        if make is not None:
            rec = self.rec
            tags = {"round": rec["round"]} if "round" in rec else {}
            self._annotation = make(ANNOTATION_PREFIX + rec["name"], **tags)
            self._annotation.__enter__()
        self.rec["t0"] = self._tracer._clock()
        self._stack.append(self.rec)
        return self

    def tag(self, **tags) -> None:
        """Payload learned while the span is open (a byte count, a
        sub-total); lands on its record."""
        self.rec.update(tags)

    def __exit__(self, *exc):
        t1 = self._tracer._clock()
        if self._annotation is not None:
            self._annotation.__exit__(*exc)
        stack = self._stack
        if stack and stack[-1] is self.rec:
            stack.pop()
        rec = self.rec
        rec["dur"] = round(t1 - rec["t0"], 6)
        rec["t0"] = round(rec["t0"], 6)
        self._tracer._commit(rec)
        return False


class Tracer:
    """Per-thread ring buffers of monotonic-clock stage spans.

    Thread-safe by one small lock held only for ring append/drain —
    spans are committed a handful of times per ROUND, not per op, so
    contention is negligible and the lock keeps drain() exact (no
    torn hand-off with a writer thread mid-append).
    """

    def __init__(self, enabled: bool = False, controller: int = 0,
                 ring_size: int = 4096,
                 clock: Callable[[], float] = time.monotonic):
        self.enabled = False
        self.controller = int(controller)
        self.ring_size = int(ring_size)
        self._clock = clock
        # jax.profiler.TraceAnnotation while enabled (imported at
        # enable(), so a disabled tracer never touches the profiler)
        self._annotation = None
        self._lock = threading.Lock()
        # thread ident -> list of committed span records (the ring)
        self._rings: Dict[int, List[dict]] = {}
        self._dropped = 0
        # per-thread stack of OPEN span records (tag inheritance);
        # thread-local so no lock is needed on the span enter/exit path
        self._open = threading.local()
        if enabled:
            self.enable()

    # ---------------- recording ------------------------------------------
    def _thread_stack(self) -> list:
        stack = getattr(self._open, "stack", None)
        if stack is None:
            stack = self._open.stack = []
        return stack

    def _make_rec(self, name: str, tags: dict) -> Tuple[dict, list]:
        rec = {"name": str(name),
               "thread": threading.current_thread().name}
        stack = self._thread_stack()
        if stack:
            parent = stack[-1]
            for key in _INHERITED_TAGS:
                if key in parent and key not in tags:
                    rec[key] = parent[key]
        for k, v in tags.items():
            if v is not None:
                rec[k] = v
        return rec, stack

    def current_tags(self) -> dict:
        """The innermost open span's correlation tags on THIS thread
        (round/span), or {}. Writer submit paths capture these so the
        writer-thread spans of a queued item carry the producing
        round even though they run on another thread."""
        if not self.enabled:
            return {}
        stack = self._thread_stack()
        if not stack:
            return {}
        parent = stack[-1]
        return {k: parent[k] for k in _INHERITED_TAGS if k in parent}

    def span(self, name: str, **tags):
        """Context manager bracketing one stage; commits a span record
        with the enclosed wall (monotonic) duration on exit. The
        disabled path returns a shared no-op."""
        if not self.enabled:
            return _NULL_SPAN
        rec, stack = self._make_rec(name, tags)
        return _Span(self, rec, stack)

    def instant(self, name: str, **tags) -> None:
        """Zero-duration marker (e.g. a writer-queue enqueue, carrying
        its `seq`/`q` tags and the enclosing span's round)."""
        if not self.enabled:
            return
        rec, _ = self._make_rec(name, tags)
        rec["t0"] = round(self._clock(), 6)
        rec["dur"] = 0.0
        self._commit(rec)

    def record(self, name: str, t0: float, t1: float, **tags) -> None:
        """Commit a span with EXPLICIT monotonic endpoints — the
        dispatch/collect seam uses this to bracket device execution
        ([t_dispatched, t_blocked], measured where those instants
        naturally exist rather than where the record is written)."""
        if not self.enabled:
            return
        rec, _ = self._make_rec(name, tags)
        rec["t0"] = round(float(t0), 6)
        rec["dur"] = round(max(float(t1) - float(t0), 0.0), 6)
        self._commit(rec)

    def _commit(self, rec: dict) -> None:
        if not self.enabled:
            # a span that straddled disable (session close) drops
            return
        ident = threading.get_ident()
        with self._lock:
            ring = self._rings.get(ident)
            if ring is None:
                ring = self._rings[ident] = []
            if len(ring) >= self.ring_size:
                self._dropped += 1
                return
            ring.append(rec)

    # ---------------- draining / lifecycle --------------------------------
    def drain(self) -> Tuple[List[dict], int]:
        """Swap out every thread's ring; returns (spans sorted by t0,
        drops since the last drain). The flush path (TelemetrySession)
        batches the result into ONE `trace` journal event."""
        with self._lock:
            spans: List[dict] = []
            for ident in list(self._rings):
                ring = self._rings[ident]
                if ring:
                    spans.extend(ring)
                    self._rings[ident] = []
            dropped, self._dropped = self._dropped, 0
        spans.sort(key=lambda r: r.get("t0", 0.0))
        return spans, dropped

    def enable(self, controller: Optional[int] = None) -> None:
        if controller is not None:
            self.controller = int(controller)
        if self._annotation is None:
            from jax.profiler import TraceAnnotation
            self._annotation = TraceAnnotation
        self.enabled = True

    def fill(self) -> float:
        """The fullest ring's share of `ring_size` (the flush cadence
        writes early once a ring is half full)."""
        with self._lock:
            longest = max((len(r) for r in self._rings.values()),
                          default=0)
        return longest / max(self.ring_size, 1)

    def disable(self) -> None:
        """Turn tracing off and discard anything buffered (the session
        drains before disabling on a clean close)."""
        self.enabled = False
        self._annotation = None
        with self._lock:
            self._rings.clear()
            self._dropped = 0


# The process-global tracer every instrumentation site records into.
# Default OFF: `attach_run_telemetry` enables it under Config.trace and
# the owning TelemetrySession disables it again at close, so tracing
# never leaks across in-process runs (tests) or into untraced ones.
TRACE = Tracer(enabled=False)


# ---------------- stage analytics (summarize()'s trace block) -----------

def _percentile(sorted_vals: List[float], q: float) -> float:
    """Nearest-rank percentile over an already-sorted list — tiny,
    dependency-free (summarize() must not require numpy arrays of
    every stage)."""
    if not sorted_vals:
        return 0.0
    idx = min(int(q / 100.0 * len(sorted_vals)), len(sorted_vals) - 1)
    return sorted_vals[idx]


def stage_stats(spans: List[dict]) -> dict:
    """Per-stage duration stats over trace span records: count,
    p50/p95 seconds, total seconds — the journal_summary block a perf
    investigation reads first."""
    by_stage: Dict[str, List[float]] = {}
    for rec in spans:
        name = rec.get("name")
        dur = rec.get("dur")
        if not isinstance(name, str) or not isinstance(dur, (int, float)):
            continue
        by_stage.setdefault(name, []).append(float(dur))
    out = {}
    for name in sorted(by_stage):
        durs = sorted(by_stage[name])
        out[name] = {
            "n": len(durs),
            "p50_s": round(_percentile(durs, 50), 6),
            "p95_s": round(_percentile(durs, 95), 6),
            "total_s": round(sum(durs), 6),
        }
    return out


def device_busy_wall(spans: List[dict]
                     ) -> Optional[Tuple[float, float]]:
    """(device-busy seconds, wall seconds) over ONE trace segment —
    spans whose monotonic t0 share a process lifetime (consumers must
    split at run_start before calling; mono bases differ across
    processes). Busy is the UNION of the `device_execute` spans'
    intervals (under --pipeline consecutive spans overlap — summing
    would overcount); wall is the extent of the whole segment. None
    when no device_execute spans (or no wall extent) exist."""
    dev = sorted((float(r["t0"]), float(r["t0"]) + float(r["dur"]))
                 for r in spans
                 if r.get("name") == "device_execute"
                 and isinstance(r.get("t0"), (int, float))
                 and isinstance(r.get("dur"), (int, float)))
    times = [float(r["t0"]) for r in spans
             if isinstance(r.get("t0"), (int, float))]
    ends = [float(r["t0"]) + float(r.get("dur", 0.0)) for r in spans
            if isinstance(r.get("t0"), (int, float))]
    if not dev or not times:
        return None
    wall = max(ends) - min(times)
    if wall <= 0:
        return None
    busy = 0.0
    cur_lo, cur_hi = dev[0]
    for lo, hi in dev[1:]:
        if lo > cur_hi:
            busy += cur_hi - cur_lo
            cur_lo, cur_hi = lo, hi
        else:
            cur_hi = max(cur_hi, hi)
    busy += cur_hi - cur_lo
    return busy, wall


def overlap_efficiency(spans: List[dict]) -> Optional[float]:
    """Pipeline overlap efficiency: device-busy time / wall time over
    one trace segment. 1.0 means the device never waited on host
    staging or persistence. For multi-segment journals
    (resume/takeover), summarize() sums device_busy_wall per segment
    instead of calling this across segments."""
    bw = device_busy_wall(spans)
    if bw is None:
        return None
    busy, wall = bw
    return round(min(busy / wall, 1.0), 4)

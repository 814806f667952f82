"""Structured run journal: an append-only JSONL event log in the run
dir.

Every record is one JSON object per line with three mandatory fields —
`v` (schema version), `event` (record kind), `ts` (wall-clock epoch
seconds) — plus a monotonic-clock `mono` twin of `ts` and
kind-specific payload. `ts` is for humans and cross-machine
correlation; `mono` is what DURATIONS are derived from (inter-record
deltas of `ts` are not durations — an NTP step mid-run corrupts them;
graftlint GL011 holds that line in code, `mono` holds it in the
record format). `mono` values share a base only within one process
lifetime: consumers must reset delta tracking at each `run_start`. One schema serves every
producer: training runs (round/span metrics, checkpoint saves, XLA
compile events, retry attempts, injected faults) and the analysis
tiers (scripts/audit.sh, num.sh, sync.sh append their reports as
`*_audit_digest` events), so an investigation reads ONE record format.

Durability: appends route through utils/atomic_io.atomic_append_line
(flush + fsync per record); a preemption can tear at most the final
line, which `read_journal`/`validate_journal` detect and report
without losing committed records. Only the coordinator of a
multi-controller run writes (drivers construct the journal behind
`mh.is_coordinator()`).

Known event kinds written by the framework (all optional-fielded;
consumers must tolerate kinds they don't know):

  run_start / run_end     driver lifecycle, config snapshot / ok flag;
                          run_end also carries cumulative
                          down_bytes_total / up_bytes_total when the
                          accountant fed the session
  round                   one federated round: `round` index, optional
                          `metrics` dict named per telemetry.metrics.
                          METRIC_NAMES, optional `seconds`, optional
                          down_bytes / up_bytes accountant totals
  schedule                one round's scheduler decision
                          (commefficient_tpu/scheduler): sampler,
                          n_sampled, optional deadline_s /
                          est_round_s / expected_round_s /
                          truncated_slots
  state_tier              tiered client-state residency deltas
                          (ISSUE 11, federated/statestore): working-
                          set hits/misses, spill/restore counts and
                          bytes since the last record, plus resident
                          row count and working_set size; carries
                          `round` (per-round path) or `first_round` +
                          `rounds` (span path)
  span                    one scanned span: first_round, rounds,
                          dispatch_s (host staging + dispatch),
                          block_s (device completion wait)
  epoch                   driver epoch summary row
  checkpoint              one rotated save: path, seconds
  compile / compile_warning   XLA backend compile (via the
                          analysis/runtime listener); the _warning
                          variant marks a compile AFTER steady state —
                          an unexpected retrace
  retry                   one utils/retry backoff attempt
  injected_fault          a utils/faults InjectedFault about to raise
  profile_start / profile_stop   jax.profiler capture of operator-
                          selected spans (--profile_spans)
  trace                   one batched flush of graftscope stage spans
                          (ISSUE 13, telemetry/trace.py): `spans` is a
                          list of {name, t0 (monotonic s), dur,
                          thread, ...correlation tags}, `controller`
                          the recording controller, `dropped` the
                          ring-overflow count — the record
                          scripts/trace_export.py turns into a
                          Perfetto-loadable Chrome trace and
                          summarize() turns into per-stage p50/p95 +
                          overlap efficiency
  screened                value-fault screening (ISSUE 16,
                          federated/round `update_screen`): one round
                          admitted fewer clients than survived —
                          `round`, `n_screened` (clients excluded by
                          the in-round admission mask), `kind`
                          ("finite" or "norm")
  aggregator              Byzantine-robust aggregation (ISSUE 17,
                          federated/round `Config.aggregator`): one
                          round's robust-reduction stats — `round`,
                          `aggregator` (coord_median / trimmed_mean /
                          norm_clip), `n_trimmed` (mean clients
                          trimmed per sketch cell), `n_clipped`
                          (clients norm-clipped), `residual_l2`
                          (robust-vs-mean aggregate distance; -1.0
                          when non-finite), `n_contrib`
  screen_adapt            adaptive screening (ISSUE 17, scheduler
                          AdaptiveScreenController): the norm-screen
                          multiplier moved — `round`, `old_mult`,
                          `new_mult`, `rate` (observed screened
                          fraction), `target`
  control                 controller bank (ISSUE 20, control/): one
                          plan-riding controller adjusted its value —
                          `round`, `controller` (a name registered in
                          analysis.domains.CONTROL_FIELDS), `signal`
                          (the observed metric), `old`, `new`,
                          `clamped` (the bound bit). The trajectory a
                          crash-resume/takeover replay must reproduce
                          bit-exactly from the plan stream
  numeric_trip            the finite-frontier watch tripped: a
                          watched telemetry metric (update_l2 /
                          error_l2) went non-finite — `round`,
                          `metrics` (the offending metric names).
                          Opens a new validation SEGMENT like
                          run_start: the driver rolls back to the
                          newest finite checkpoint and legitimately
                          replays rounds after this record
  state_quarantine        a checksummed state-tier chunk failed
                          verification at restore time
                          (federated/statestore) and the row was
                          re-initialized from its init base —
                          `client`, `field`
  audit_digest            graftaudit's static cost report
                          (analysis/audit): sha256 `digest`,
                          per-program `programs` {flops, hbm_bytes},
                          the traced `geometry`, and the finding count
  mesh_audit_digest       graftmesh's per-link collective report
                          (analysis/shardaudit): sha256 `digest`,
                          per-program `programs` {ici_bytes,
                          dcn_bytes, dcn_collectives}, the `meshes`
                          link models, geometry, finding count
  sync_audit_digest       graftsync's concurrency-audit report
                          (analysis/syncaudit): 64-hex sha256
                          `digest` (bit-identical across runs),
                          per-rule `rules` counts, the `registry`
                          sizes (shared-state guards / ordering
                          edges), and the finding count
  num_audit_digest        graftnum's numerics-audit report
                          (analysis/numaudit): 64-hex sha256
                          `digest` (bit-identical across runs),
                          per-rule NU `rules` counts, per-program
                          `ulp` worst-case reassociation bounds, and
                          the finding count
  privacy                 differential privacy (ISSUE 19, dp_sketch
                          mode): one committed round's cumulative
                          Rényi-DP budget — `round`, `epsilon`
                          (cumulative; never decreases within a
                          segment), `sigma` (noise multiplier),
                          `clip` (per-client l2 bound), `delta`
  compressor              one committed round's compressor billing
                          (ISSUE 19, compress/ plugins): `round`,
                          `mode`, `wire_bytes` (the plugin's static
                          per-client wire geometry), `up_bytes` (the
                          round's accounted upload total) —
                          summarize() folds these into the per-mode
                          bytes-on-wire table
"""
from __future__ import annotations

import json
import math
import os
import queue
import threading
import time
from typing import Callable, List, Optional, Tuple

import numpy as np

from commefficient_tpu.analysis.domains import CONTROL_FIELDS
from commefficient_tpu.telemetry.trace import (
    TRACE, device_busy_wall, stage_stats,
)
from commefficient_tpu.utils.atomic_io import atomic_append_lines

SCHEMA_VERSION = 1

# fields every record must carry to be schema-valid
REQUIRED_FIELDS = ("v", "event", "ts")


def _jsonable(obj):
    """json.dumps default hook: numpy scalars/arrays -> python."""
    if isinstance(obj, np.bool_):
        return bool(obj)
    if isinstance(obj, np.integer):
        return int(obj)
    if isinstance(obj, np.floating):
        return float(obj)
    if isinstance(obj, np.ndarray):
        return obj.tolist()
    raise TypeError(f"not JSON-serializable: {type(obj).__name__}")


# strict-JSON sentinels for non-finite floats (see _finite)
NONFINITE = {"nan": "NaN", "inf": "Infinity", "-inf": "-Infinity"}


def _finite(obj):
    """Replace non-finite floats with their string sentinels ("NaN",
    "Infinity", "-Infinity"), recursively. Python's json module would
    happily emit bare `NaN` tokens (allow_nan defaults True) — lines
    no strict JSONL consumer (jq, Go/Rust/JS parsers) accepts; a
    diverging run's train_loss is exactly when the journal matters
    most, so the value is preserved as a recoverable string instead of
    dropped or left spec-invalid."""
    if isinstance(obj, float) and not np.isfinite(obj):
        return NONFINITE[repr(obj)]
    if isinstance(obj, np.floating) and not np.isfinite(obj):
        return NONFINITE[repr(float(obj))]
    if isinstance(obj, dict):
        return {k: _finite(v) for k, v in obj.items()}
    if isinstance(obj, (list, tuple)):
        return [_finite(v) for v in obj]
    return obj


# inverse of NONFINITE: the exact sentinel strings _finite writes,
# mapped back to the float values they stood for
NONFINITE_INVERSE = {"NaN": math.nan, "Infinity": math.inf,
                     "-Infinity": -math.inf}


def _unfinite(obj):
    """Inverse of `_finite`, applied by `read_journal` (ISSUE 16
    satellite): the exact sentinel strings "NaN" / "Infinity" /
    "-Infinity" round-trip back to floats, recursively, so consumers
    (summarize, the rollback drill's resume-equivalence check,
    np.isfinite over metrics) see numbers, not strings. Only the
    three exact sentinels convert — every other string passes
    through untouched. Dict KEYS are never rewritten."""
    if isinstance(obj, str):
        return NONFINITE_INVERSE.get(obj, obj)
    if isinstance(obj, dict):
        return {k: _unfinite(v) for k, v in obj.items()}
    if isinstance(obj, list):
        return [_unfinite(v) for v in obj]
    return obj


class RunJournal:
    """Append-only JSONL writer for one run.

    Construction creates the parent directory but writes nothing; the
    first `event()` call creates the file. In the default synchronous
    mode the object is stateless beyond its path — safe to
    reconstruct (e.g. `append_event`) and to leave unclosed; every
    record is durable as soon as `event` returns.

    async_writer=True (ISSUE 10, Config.pipeline) moves the
    flush+fsync onto a bounded-queue writer thread: `event`/`events`
    SERIALIZE the record on the caller's thread (so later mutation of
    passed values cannot corrupt it) and enqueue the finished lines;
    one daemon thread drains the queue strictly FIFO through the same
    `atomic_append_lines` path, so record content, ordering, batching
    (a span's records stay ONE queued fsync) and the torn-tail seal
    are byte-identical to the synchronous mode — only durability
    timing changes. The queue is bounded (a dead disk back-pressures
    rather than ballooning memory); `flush()` blocks until everything
    queued is durable and `close()` flushes then stops the thread —
    the crash-drill path (drivers close the session in `finally`)
    drains exactly like a clean shutdown. Writer-side I/O failures
    keep the best-effort contract: warn once, keep training."""

    _SENTINEL = object()

    def __init__(self, path: str, run_id: str = "",
                 clock: Callable[[], float] = time.time,
                 mono_clock: Callable[[], float] = time.monotonic,
                 async_writer: bool = False, max_queue: int = 256,
                 drain_timeout: float = 0.0):
        self.path = path
        self.run_id = run_id
        self._clock = clock
        self._mono = mono_clock
        # graftscope correlation (ISSUE 13): per-journal submission
        # sequence — an async append's producer-side enqueue instant
        # and its writer-thread qwait/write spans share a `seq`
        self._seq = 0
        # writer-thread watchdog (ISSUE 12 satellite): flush()/close()
        # deadline in seconds; 0 = wait forever (the old behavior)
        self._drain_timeout = float(drain_timeout)
        # a torn tail can only predate this writer's first append —
        # seal-check once, then skip the per-record read
        self._tail_checked = False
        os.makedirs(os.path.dirname(path) or ".", exist_ok=True)
        self._q: Optional["queue.Queue"] = None
        self._thread = None
        self._warned = False
        if async_writer:
            self._q = queue.Queue(maxsize=max(max_queue, 1))
            self._thread = threading.Thread(
                target=self._drain_loop, args=(self._q,),
                name="journal-writer", daemon=True)
            self._thread.start()

    def _record(self, kind: str, fields: dict) -> dict:
        # dual timestamps (ISSUE 13 satellite): `ts` stays the human/
        # cross-machine wall clock, `mono` is the monotonic twin every
        # duration derivation (cadence stats, bench gap histograms)
        # must use — wall-clock deltas are NTP-step-hazardous
        rec = {"v": SCHEMA_VERSION, "event": str(kind),
               "ts": round(float(self._clock()), 6),
               "mono": round(float(self._mono()), 6)}
        if self.run_id:
            rec["run_id"] = self.run_id
        rec.update(fields)
        return rec

    def _append(self, lines, check_tail: bool) -> None:
        atomic_append_lines(self.path, lines, check_tail=check_tail)

    def _drain_loop(self, q: "queue.Queue") -> None:
        # the queue rides in as an argument: close() detaches self._q
        # before the final join, and the loop must keep draining the
        # ORIGINAL queue through that handoff
        while True:
            item = q.get()
            try:
                if item is self._SENTINEL:
                    return
                lines, check_tail, enq_mono, seq, tags = item
                if enq_mono is not None:
                    # queue-wait span: enqueue -> dequeue, the
                    # back-pressure interval graftscope charges to
                    # this writer (same `seq` as the producer-side
                    # journal_enqueue instant)
                    TRACE.record("journal_qwait", enq_mono,
                                 time.monotonic(), seq=seq, **tags)
                try:
                    if enq_mono is not None:
                        with TRACE.span("journal_write", seq=seq,
                                        **tags):
                            self._append(lines, check_tail)
                    else:
                        self._append(lines, check_tail)
                except (OSError, ValueError) as e:
                    # best-effort like the sync path's _safe_write
                    # wrapper: observability must never kill training
                    if not self._warned:
                        print(f"journal writer: append failed ({e}); "
                              f"further failures silent")
                        self._warned = True
            finally:
                q.task_done()

    def _emit(self, lines, trace_tags: Optional[dict] = None) -> None:
        """Write or enqueue serialized lines. `trace_tags`: graftscope
        correlation tags ({} = trace with no tags, None = do NOT trace
        this append — the flush of `trace` events themselves, which
        would otherwise self-generate one span per flush forever)."""
        check_tail = not self._tail_checked
        self._tail_checked = True
        traced = trace_tags is not None and TRACE.enabled
        if self._q is None:
            if traced:
                with TRACE.span("journal_write", **trace_tags):
                    self._append(lines, check_tail)
            else:
                self._append(lines, check_tail)
            return
        if traced:
            seq, self._seq = self._seq, self._seq + 1
            TRACE.instant("journal_enqueue", seq=seq,
                          q=self._q.qsize(), **trace_tags)
            self._q.put((list(lines), check_tail,
                         time.monotonic(), seq, dict(trace_tags)))
        else:
            self._q.put((list(lines), check_tail, None, 0, {}))

    @staticmethod
    def _tags_of(recs) -> Optional[dict]:
        """Correlation tags for one append: the first record's round
        index (round or first_round), or untagged. `trace` records
        return None — their own appends are never traced (see
        _emit)."""
        if any(r.get("event") == "trace" for r in recs):
            return None
        for r in recs:
            for key in ("round", "first_round"):
                v = r.get(key)
                if isinstance(v, int):
                    return {"round": v}
        return {}

    def event(self, kind: str, /, **fields) -> dict:
        """Append one record; returns the dict that was written.
        `kind` is positional-only: the `screened` event (ISSUE 16)
        carries a FIELD named `kind`, which must stay usable as a
        keyword."""
        rec = self._record(kind, fields)
        self._emit((json.dumps(_finite(rec), default=_jsonable),),
                   trace_tags=self._tags_of((rec,)))
        return rec

    def events(self, batch) -> List[dict]:
        """Append many records — `batch` is (kind, fields) pairs — with
        ONE flush+fsync for the lot. The span-boundary path uses this:
        a span's N round records are produced at the same instant, so
        per-record fsyncs would buy no durability, only a host stall
        proportional to span length. Under the async writer the whole
        batch rides the queue as ONE item — still one fsync."""
        recs = [self._record(kind, fields) for kind, fields in batch]
        self._emit([json.dumps(_finite(r), default=_jsonable)
                    for r in recs],
                   trace_tags=self._tags_of(recs))
        return recs

    def flush(self) -> None:
        """Block until every queued record is durable (async mode); a
        no-op in synchronous mode, where `event` already fsynced. The
        crash-boundary writers (FedModel._journal_fault) call this so
        an injected_fault record is on disk before the raise. With a
        `drain_timeout`, a hung writer raises TimeoutError naming the
        journal (utils/watchdog) instead of hanging the caller."""
        if self._q is not None:
            from commefficient_tpu.utils.watchdog import drain_queue
            drain_queue(self._q, self._drain_timeout, "journal")

    def close(self) -> None:
        """Drain and stop the writer thread (async mode); in sync mode
        there is no buffered state — kept so callers can treat the
        journal like a file handle. Idempotent. Honors the
        drain_timeout watchdog like flush()."""
        if self._q is not None:
            from commefficient_tpu.utils.watchdog import drain_queue
            q, self._q = self._q, None
            drain_queue(q, self._drain_timeout, "journal")
            q.put(self._SENTINEL)
            self._thread.join()
            self._thread = None


def append_event(path: str, kind: str, /, **fields) -> dict:
    """One-shot append for producers without a long-lived journal
    (the analysis tiers' digests)."""
    return RunJournal(path).event(kind, **fields)


# ---------------- reading + invariant validation -------------------------

def read_journal(path: str,
                 counters: Optional[dict] = None
                 ) -> Tuple[List[dict], List[str]]:
    """Parse a journal file. Returns (records, problems): records are
    the successfully parsed lines in order; problems are human-readable
    descriptions of malformed lines that invalidate the journal.

    Corruption tolerance (ISSUE 12 satellite): a torn FINAL line (the
    shape a preemption mid-append produces) is reported as a problem
    but does not invalidate the committed records before it — the
    original contract. Corrupt INTERIOR lines — possible since the
    PR-10 async batch writer can die mid-batch, and a sealed torn tail
    becomes interior once a resumed run appends past it — are SKIPPED
    AND COUNTED rather than treated as validation failures: every
    parseable record still reads, and the count is surfaced through
    `counters` (key "corrupt_interior", plus "corrupt_lines" detailing
    line numbers) so `summarize()` can report it. Pass a dict as
    `counters` to receive the counts; the (records, problems) return
    shape is unchanged for the many existing callers."""
    records: List[dict] = []
    problems: List[str] = []
    skipped: List[int] = []
    with open(path) as f:
        lines = f.read().splitlines()

    def _skip_or_problem(i: int, desc: str) -> None:
        if i == len(lines):
            # the final line: the one torn shape a clean-history
            # journal can have — report it, the committed prefix
            # stands
            problems.append(f"line {i}: {desc} (torn tail?)")
        else:
            skipped.append(i)

    for i, line in enumerate(lines, 1):
        if not line.strip():
            _skip_or_problem(i, "blank line")
            continue
        try:
            rec = json.loads(line)
        except ValueError:
            _skip_or_problem(i, "not valid JSON")
            continue
        if not isinstance(rec, dict):
            _skip_or_problem(i, "not a JSON object")
            continue
        records.append(_unfinite(rec))
    if counters is not None:
        counters["corrupt_interior"] = len(skipped)
        counters["corrupt_lines"] = list(skipped)
    return records, problems


def validate_journal(path: str,
                     counters: Optional[dict] = None
                     ) -> Tuple[List[dict], List[str]]:
    """Journal invariants as a checkable function (shared by
    scripts/journal_summary.py and tests/test_telemetry.py):

      * every line parses as a JSON object carrying v/event/ts;
      * `v` matches SCHEMA_VERSION;
      * `round` events carry an integer `round` and never repeat a
        round index WITHIN one run segment (a duplicate means two code
        paths journaled the same round);
      * `round` indices are strictly increasing within a segment;
      * `metrics` payloads (when present) are {str: number} dicts;
      * `down_bytes`/`up_bytes` (when present) are non-negative
        numbers, and a segment's `run_end` cumulative
        down_bytes_total/up_bytes_total covers at least the sum of its
        journaled per-round totals (accounting.py's per-round and
        cumulative views must agree);
      * `schedule` events carry an integer `round` and a `sampler`
        name; their optional deadline_s/est_round_s payloads are
        non-negative numbers;
      * `state_tier` events (tiered client state, ISSUE 11) carry
        non-negative integer hits/misses/spills/restores and
        non-negative spill_bytes/restore_bytes/resident/working_set —
        the residency record of a working-set run;
      * `trace` events (graftscope, telemetry/trace.py) carry a list
        `spans` of objects each with a string `name`, string
        `thread`, numeric non-negative `t0` (monotonic seconds) and
        `dur`; optional `dropped` must be a non-negative integer —
        the record trace_export.py and the stage analytics read, so
        its shape must not rot;
      * `mono` (when present) is a non-negative number — the
        monotonic twin of `ts` durations are derived from;
      * `audit_digest` events (graftaudit cost reports) carry a
        non-empty string `digest` and a `programs` object mapping each
        audited program to non-negative numeric flops/hbm_bytes — the
        record a cost-regression investigation greps for, so its shape
        must not rot;
      * `mesh_audit_digest` events (graftmesh per-link reports) carry
        the same digest/programs shape with non-negative numeric
        ici_bytes/dcn_bytes/dcn_collectives per program — the record
        the million-client refactor's before/after comm table reads;
      * `sync_audit_digest` events (graftsync concurrency reports,
        analysis/syncaudit) carry a 64-hex string `digest`, a `rules`
        object mapping each SY rule to a non-negative integer count,
        and a non-negative integer `findings` — the record scripts/sync.sh
        journals, so its shape must not rot;
      * `num_audit_digest` events (graftnum numerics reports,
        analysis/numaudit) carry the same 64-hex `digest` / `rules`
        counts / optional `findings` shape plus a `ulp` object
        mapping each audited program to a non-negative integer
        worst-case reassociation bound — the record scripts/num.sh
        journals, so its shape must not rot;
      * `screened` events (ISSUE 16 value-fault admission) carry an
        integer `round`, a non-negative integer `n_screened`, and a
        non-empty string `kind`;
      * `aggregator` events (ISSUE 17 robust aggregation) carry an
        integer `round`, a non-empty string `aggregator`, numeric
        `n_trimmed`/`residual_l2`, and non-negative integers
        `n_clipped`/`n_contrib`;
      * `screen_adapt` events (ISSUE 17 adaptive screening) carry an
        integer `round` and numeric `old_mult`/`new_mult`/`rate`/
        `target`, with both multipliers positive;
      * `privacy` events (ISSUE 19 differential privacy) carry an
        integer `round`, a non-negative numeric `epsilon` that never
        DECREASES within a run segment (the RDP budget only
        accumulates), positive `sigma`/`clip`, and `delta` in (0, 1);
      * `compressor` events (ISSUE 19 compressor plugins) carry an
        integer `round`, a non-empty string `mode`, and non-negative
        numeric `wire_bytes`/`up_bytes`;
      * `numeric_trip` events carry an integer `round` and a list of
        metric-name strings `metrics`; a trip also opens a new run
        SEGMENT (see below) — the driver rolls back and replays;
      * `state_quarantine` events carry a non-negative integer
        `client` and a non-empty string `field`.

    A `run_start` event opens a new run SEGMENT and resets the round
    tracking: a preempted run resumed with the same --journal_path
    legitimately replays rounds journaled after its last checkpoint
    (its run_start carries `resumed_round`), so cross-segment repeats
    are healthy history, not violations.

    Corrupt INTERIOR lines are skipped-and-counted, not violations
    (read_journal; the PR-10 async batch writer can die mid-batch) —
    pass a `counters` dict to receive the count for summarize().

    Returns (records, problems); an empty problems list means the
    journal is valid."""
    records, problems = read_journal(path, counters=counters)
    seen_rounds = set()
    last_round = None
    seg_down = seg_up = 0.0
    last_epsilon = None

    def _comm_field(rec, n, field):
        """Validate one byte-total field; returns its value or None."""
        v = rec.get(field)
        if v is None:
            return None
        if not isinstance(v, (int, float)) or v < 0:
            problems.append(
                f"record {n}: `{field}` must be a non-negative "
                f"number (got {v!r})")
            return None
        return float(v)

    for n, rec in enumerate(records, 1):
        if rec.get("event") == "run_start":
            seen_rounds = set()
            last_round = None
            seg_down = seg_up = 0.0
            last_epsilon = None
        if rec.get("event") == "numeric_trip":
            # finite-frontier rollback (ISSUE 16): the driver walks
            # back to the newest finite checkpoint and REPLAYS rounds
            # after this record — round repeats across a trip are
            # healthy history, exactly like a resume's run_start.
            # Byte accumulation is NOT reset: the accountant keeps
            # counting across the rollback, so run_end totals still
            # cover every journaled per-round sum including replays.
            # The epsilon tracker IS reset: epsilon is a pure function
            # of the committed-round count, so replayed rounds
            # legitimately re-journal the lower values of the window.
            seen_rounds = set()
            last_round = None
            last_epsilon = None
        for field in REQUIRED_FIELDS:
            if field not in rec:
                problems.append(f"record {n}: missing `{field}`")
        v = rec.get("v")
        if v is not None and v != SCHEMA_VERSION:
            problems.append(
                f"record {n}: schema version {v!r} != {SCHEMA_VERSION}")
        if not isinstance(rec.get("ts", 0.0), (int, float)):
            problems.append(f"record {n}: non-numeric `ts`")
        mono = rec.get("mono")
        if mono is not None and not (isinstance(mono, (int, float))
                                     and mono >= 0):
            problems.append(
                f"record {n}: `mono` must be a non-negative number "
                f"(got {mono!r})")
        if rec.get("event") == "trace":
            spans = rec.get("spans")
            if not isinstance(spans, list):
                problems.append(
                    f"record {n}: trace event `spans` is not a list")
            else:
                for j, sp in enumerate(spans):
                    if not isinstance(sp, dict):
                        problems.append(
                            f"record {n}: trace span {j} is not an "
                            "object")
                        continue
                    for field in ("name", "thread"):
                        if not isinstance(sp.get(field), str):
                            problems.append(
                                f"record {n}: trace span {j} "
                                f"`{field}` must be a string (got "
                                f"{sp.get(field)!r})")
                    for field in ("t0", "dur"):
                        v2 = sp.get(field)
                        if not (isinstance(v2, (int, float))
                                and v2 >= 0):
                            problems.append(
                                f"record {n}: trace span {j} "
                                f"`{field}` must be a non-negative "
                                f"number (got {v2!r})")
            d2 = rec.get("dropped")
            if d2 is not None and not (isinstance(d2, int)
                                       and d2 >= 0):
                problems.append(
                    f"record {n}: trace `dropped` must be a "
                    f"non-negative integer (got {d2!r})")
        if rec.get("event") == "schedule":
            if not isinstance(rec.get("round"), int):
                problems.append(
                    f"record {n}: schedule event without an integer "
                    f"`round` (got {rec.get('round')!r})")
            if not isinstance(rec.get("sampler"), str):
                problems.append(
                    f"record {n}: schedule event without a `sampler` "
                    "name")
            for field in ("deadline_s", "est_round_s",
                          "expected_round_s"):
                _comm_field(rec, n, field)
        if rec.get("event") == "state_tier":
            for field in ("hits", "misses", "spills", "restores"):
                v2 = rec.get(field)
                if not (isinstance(v2, int) and v2 >= 0):
                    problems.append(
                        f"record {n}: state_tier `{field}` must be a "
                        f"non-negative integer (got {v2!r})")
            for field in ("spill_bytes", "restore_bytes",
                          "resident", "working_set"):
                _comm_field(rec, n, field)
        if rec.get("event") == "screened":
            # value-fault admission (ISSUE 16): the record the drill
            # matrix and a poisoned smoke read, so its shape
            # must not rot
            if not isinstance(rec.get("round"), int):
                problems.append(
                    f"record {n}: screened event without an integer "
                    f"`round` (got {rec.get('round')!r})")
            ns = rec.get("n_screened")
            if not (isinstance(ns, int) and ns >= 0):
                problems.append(
                    f"record {n}: screened `n_screened` must be a "
                    f"non-negative integer (got {ns!r})")
            k2 = rec.get("kind")
            if not (isinstance(k2, str) and k2):
                problems.append(
                    f"record {n}: screened event without a non-empty "
                    f"string `kind` (got {k2!r})")
        if rec.get("event") == "aggregator":
            # robust aggregation (ISSUE 17): the record the drill
            # matrix and an adversarial smoke read
            if not isinstance(rec.get("round"), int):
                problems.append(
                    f"record {n}: aggregator event without an integer "
                    f"`round` (got {rec.get('round')!r})")
            a2 = rec.get("aggregator")
            if not (isinstance(a2, str) and a2):
                problems.append(
                    f"record {n}: aggregator event without a "
                    f"non-empty string `aggregator` (got {a2!r})")
            for field in ("n_trimmed", "residual_l2"):
                v2 = rec.get(field)
                if not isinstance(v2, (int, float)):
                    problems.append(
                        f"record {n}: aggregator `{field}` must be "
                        f"numeric (got {v2!r})")
            for field in ("n_clipped", "n_contrib"):
                v2 = rec.get(field)
                if not (isinstance(v2, int) and v2 >= 0):
                    problems.append(
                        f"record {n}: aggregator `{field}` must be a "
                        f"non-negative integer (got {v2!r})")
        if rec.get("event") == "screen_adapt":
            # adaptive screening (ISSUE 17): the threshold trajectory
            # the resume-bit-exactness drill replays
            if not isinstance(rec.get("round"), int):
                problems.append(
                    f"record {n}: screen_adapt event without an "
                    f"integer `round` (got {rec.get('round')!r})")
            for field in ("rate", "target"):
                v2 = rec.get(field)
                if not isinstance(v2, (int, float)):
                    problems.append(
                        f"record {n}: screen_adapt `{field}` must be "
                        f"numeric (got {v2!r})")
            for field in ("old_mult", "new_mult"):
                v2 = rec.get(field)
                if not (isinstance(v2, (int, float)) and v2 > 0):
                    problems.append(
                        f"record {n}: screen_adapt `{field}` must be "
                        f"a positive number (got {v2!r})")
        if rec.get("event") == "control":
            # controller bank (ISSUE 20): the plan-riding adjustment
            # trajectory the replay-exactness drills compare, so the
            # shape — and the controller name's registration in
            # analysis.domains.CONTROL_FIELDS — must not rot
            if not isinstance(rec.get("round"), int):
                problems.append(
                    f"record {n}: control event without an integer "
                    f"`round` (got {rec.get('round')!r})")
            c2 = rec.get("controller")
            if not (isinstance(c2, str) and c2 in CONTROL_FIELDS):
                problems.append(
                    f"record {n}: control `controller` must be a "
                    f"name registered in analysis.domains."
                    f"CONTROL_FIELDS (got {c2!r})")
            for field in ("signal", "old", "new"):
                v2 = rec.get(field)
                if not isinstance(v2, (int, float)):
                    problems.append(
                        f"record {n}: control `{field}` must be "
                        f"numeric (got {v2!r})")
            if not isinstance(rec.get("clamped"), bool):
                problems.append(
                    f"record {n}: control `clamped` must be a bool "
                    f"(got {rec.get('clamped')!r})")
        if rec.get("event") == "privacy":
            # differential privacy (ISSUE 19): the budget record the
            # monotone-epsilon gate of a dp smoke reads, so its
            # shape — and the monotonicity itself — must not rot
            if not isinstance(rec.get("round"), int):
                problems.append(
                    f"record {n}: privacy event without an integer "
                    f"`round` (got {rec.get('round')!r})")
            eps = rec.get("epsilon")
            if not (isinstance(eps, (int, float)) and eps >= 0):
                problems.append(
                    f"record {n}: privacy `epsilon` must be a "
                    f"non-negative number (got {eps!r})")
            else:
                if last_epsilon is not None and eps < last_epsilon:
                    problems.append(
                        f"record {n}: privacy `epsilon` decreased "
                        f"({last_epsilon!r} -> {eps!r}) — the RDP "
                        f"budget only accumulates within a segment")
                last_epsilon = float(eps)
            for field in ("sigma", "clip"):
                v2 = rec.get(field)
                if not (isinstance(v2, (int, float)) and v2 > 0):
                    problems.append(
                        f"record {n}: privacy `{field}` must be a "
                        f"positive number (got {v2!r})")
            d3 = rec.get("delta")
            if not (isinstance(d3, (int, float)) and 0 < d3 < 1):
                problems.append(
                    f"record {n}: privacy `delta` must be in (0, 1) "
                    f"(got {d3!r})")
        if rec.get("event") == "compressor":
            # compressor plugin billing (ISSUE 19): the per-mode
            # bytes-on-wire record summarize() accumulates
            if not isinstance(rec.get("round"), int):
                problems.append(
                    f"record {n}: compressor event without an integer "
                    f"`round` (got {rec.get('round')!r})")
            m2 = rec.get("mode")
            if not (isinstance(m2, str) and m2):
                problems.append(
                    f"record {n}: compressor event without a "
                    f"non-empty string `mode` (got {m2!r})")
            for field in ("wire_bytes", "up_bytes"):
                v2 = rec.get(field)
                if not (isinstance(v2, (int, float)) and v2 >= 0):
                    problems.append(
                        f"record {n}: compressor `{field}` must be a "
                        f"non-negative number (got {v2!r})")
        if rec.get("event") == "numeric_trip":
            if not isinstance(rec.get("round"), int):
                problems.append(
                    f"record {n}: numeric_trip event without an "
                    f"integer `round` (got {rec.get('round')!r})")
            m2 = rec.get("metrics")
            if not (isinstance(m2, list)
                    and all(isinstance(x, str) for x in m2)):
                problems.append(
                    f"record {n}: numeric_trip `metrics` must be a "
                    f"list of metric-name strings (got {m2!r})")
        if rec.get("event") == "state_quarantine":
            c2 = rec.get("client")
            if not (isinstance(c2, int) and c2 >= 0):
                problems.append(
                    f"record {n}: state_quarantine `client` must be "
                    f"a non-negative integer (got {c2!r})")
            f2 = rec.get("field")
            if not (isinstance(f2, str) and f2):
                problems.append(
                    f"record {n}: state_quarantine event without a "
                    f"non-empty string `field` (got {f2!r})")
        # the two analysis-tier digest records share a shape: sha256
        # digest + per-program cost object, with tier-specific fields
        digest_fields = {
            "audit_digest": ("flops", "hbm_bytes"),
            "mesh_audit_digest": ("ici_bytes", "dcn_bytes",
                                  "dcn_collectives"),
        }
        ev = rec.get("event")
        if ev in digest_fields:
            d = rec.get("digest")
            if not (isinstance(d, str) and d):
                problems.append(
                    f"record {n}: {ev} without a non-empty "
                    f"string `digest` (got {d!r})")
            progs = rec.get("programs")
            if not isinstance(progs, dict):
                problems.append(
                    f"record {n}: {ev} `programs` is not an "
                    "object")
            else:
                for prog, cost in sorted(progs.items()):
                    if not isinstance(cost, dict):
                        problems.append(
                            f"record {n}: {ev} program "
                            f"{prog!r} cost is not an object")
                        continue
                    for field in digest_fields[ev]:
                        v2 = cost.get(field)
                        if not (isinstance(v2, (int, float))
                                and v2 >= 0):
                            problems.append(
                                f"record {n}: {ev} program "
                                f"{prog!r} `{field}` must be a "
                                f"non-negative number (got {v2!r})")
        if rec.get("event") in ("sync_audit_digest",
                                "num_audit_digest"):
            # graftsync/graftnum: the digest is pinned to 64-hex —
            # the bit-identical-across-runs claim is checked on
            # exactly this value, so a truncated or non-canonical
            # digest is a schema rot, not a style choice
            ev2 = rec.get("event")
            d = rec.get("digest")
            if not (isinstance(d, str) and len(d) == 64
                    and all(c in "0123456789abcdef" for c in d)):
                problems.append(
                    f"record {n}: {ev2} `digest` must be "
                    f"a 64-char lowercase hex string (got {d!r})")
            rls = rec.get("rules")
            if not isinstance(rls, dict):
                problems.append(
                    f"record {n}: {ev2} `rules` is not "
                    "an object")
            else:
                for rule, cnt in sorted(rls.items()):
                    if not (isinstance(cnt, int) and cnt >= 0):
                        problems.append(
                            f"record {n}: {ev2} rule "
                            f"{rule!r} count must be a non-negative "
                            f"integer (got {cnt!r})")
            fnd = rec.get("findings")
            if fnd is not None and not (isinstance(fnd, int)
                                        and fnd >= 0):
                problems.append(
                    f"record {n}: {ev2} `findings` must "
                    f"be a non-negative integer (got {fnd!r})")
        if rec.get("event") == "num_audit_digest":
            # graftnum additionally journals the per-program
            # worst-case reassociation ulp bounds the baseline diffs
            ulp = rec.get("ulp")
            if not isinstance(ulp, dict):
                problems.append(
                    f"record {n}: num_audit_digest `ulp` is not an "
                    "object")
            else:
                for prog, bound in sorted(ulp.items()):
                    if not (isinstance(bound, int) and bound >= 0):
                        problems.append(
                            f"record {n}: num_audit_digest program "
                            f"{prog!r} ulp bound must be a "
                            f"non-negative integer (got {bound!r})")
        if rec.get("event") == "run_end":
            total_down = _comm_field(rec, n, "down_bytes_total")
            total_up = _comm_field(rec, n, "up_bytes_total")
            # cumulative totals must cover the segment's journaled
            # per-round sums (0.5-byte slack for float accumulation)
            if total_down is not None and total_down < seg_down - 0.5:
                problems.append(
                    f"record {n}: down_bytes_total {total_down} < "
                    f"sum of per-round down_bytes {seg_down}")
            if total_up is not None and total_up < seg_up - 0.5:
                problems.append(
                    f"record {n}: up_bytes_total {total_up} < "
                    f"sum of per-round up_bytes {seg_up}")
        if rec.get("event") == "round":
            d = _comm_field(rec, n, "down_bytes")
            u = _comm_field(rec, n, "up_bytes")
            seg_down += d or 0.0
            seg_up += u or 0.0
            r = rec.get("round")
            if not isinstance(r, int):
                problems.append(f"record {n}: round event without an "
                                f"integer `round` (got {r!r})")
                continue
            if r in seen_rounds:
                problems.append(f"record {n}: duplicate round {r}")
            elif last_round is not None and r <= last_round:
                problems.append(
                    f"record {n}: round {r} out of order "
                    f"(after round {last_round})")
            seen_rounds.add(r)
            last_round = r if last_round is None else max(last_round, r)
            m = rec.get("metrics")
            if m is not None:
                if not isinstance(m, dict):
                    problems.append(
                        f"record {n}: `metrics` is not an object")
                else:
                    # the non-finite sentinels (_finite) are legal —
                    # a diverging run's NaN loss is valid telemetry
                    ok_strings = set(NONFINITE.values())
                    bad = [k for k, val in m.items()
                           if not (isinstance(val, (int, float))
                                   or val in ok_strings)]
                    if bad:
                        problems.append(
                            f"record {n}: non-numeric metrics {bad}")
    return records, problems


# inter-round cadence histogram buckets (seconds): log-ish edges with
# human labels — coarse on purpose (the p50/p95 carry the precision;
# the histogram shows the SHAPE: bimodal cadence = a periodic stall)
_CADENCE_EDGES = (
    (0.001, "<1ms"), (0.003, "1-3ms"), (0.01, "3-10ms"),
    (0.03, "10-30ms"), (0.1, "30-100ms"), (0.3, "0.1-0.3s"),
    (1.0, "0.3-1s"), (3.0, "1-3s"), (10.0, "3-10s"),
)


def _cadence_bucket(dt: float) -> str:
    for edge, label in _CADENCE_EDGES:
        if dt < edge:
            return label
    return ">=10s"


def summarize(records: List[dict], corrupt_lines: int = 0) -> dict:
    """Small host-side digest of a journal: event-kind counts, round
    coverage, total journaled wall time in spans/checkpoints.
    `corrupt_lines`: the skipped-interior-line count from
    read_journal/validate_journal's `counters` — surfaced in the
    summary (ISSUE 12 satellite) so a journal that survived a
    mid-batch writer crash says so instead of silently looking
    clean.

    Stage-level analytics (ISSUE 13, graftscope): with `trace` events
    present the summary grows per-stage p50/p95 (`trace_stages`), the
    writer queue-depth gauges (`writer_queue_max`, from the enqueue
    spans' `q` tags), and the pipeline overlap-efficiency metric
    (`overlap_efficiency` = device-busy / wall over the
    device_execute spans) and, where FedLoader's `load_fetch` spans
    are among them, `loader_cohort_share` (the share of its rounds
    fetched and transformed as one cohort, from their `cohort` tags),
    and where TrainRound's `gather`/`scatter` spans are,
    `state_motion_rows_per_round` / `state_motion_bytes_per_round`
    (what the client state motion has to move, from their tags).
    Independently, round events carrying the
    `mono` timestamp yield the inter-round `cadence` block
    (p50/p95 + histogram) — deltas are taken on the MONOTONIC clock,
    reset at every run_start (each process has its own mono base,
    and a wall-clock delta is not a duration)."""
    kinds: dict = {}
    rounds = []
    span_s = ckpt_s = 0.0
    down_b = up_b = 0.0
    deadlines = 0
    tier_hits = tier_misses = tier_spills = 0
    tier_spill_b = 0.0
    screened_total = 0
    trimmed_total = 0.0
    clipped_total = 0
    epsilon_spent = None
    privacy_sigma = privacy_delta = None
    wire_by_mode: dict = {}
    control_by_ctl: dict = {}
    # trace spans SEGMENTED at run_start: monotonic t0 values share a
    # base only within one process lifetime, so the wall-extent math
    # (overlap efficiency) must never mix segments from a resumed run
    # or a coordinator takeover
    trace_segments: List[List[dict]] = [[]]
    trace_dropped = 0
    cadence: List[float] = []
    prev_mono = None
    # the four analysis tiers' journaled report digests (last record
    # of each wins — a re-run within one journal supersedes)
    tier_digests: dict = {}
    num_findings = None
    for rec in records:
        kind = rec.get("event", "?")
        kinds[kind] = kinds.get(kind, 0) + 1
        if kind in ("audit_digest", "mesh_audit_digest",
                    "sync_audit_digest", "num_audit_digest"):
            d = rec.get("digest")
            if isinstance(d, str) and d:
                tier_digests[kind] = d
            if kind == "num_audit_digest":
                f2 = rec.get("findings")
                if isinstance(f2, int):
                    num_findings = f2
        if kind == "run_start":
            # new segment: a resumed process has its own monotonic
            # base, so cross-segment deltas are meaningless
            prev_mono = None
            if trace_segments[-1]:
                trace_segments.append([])
        if kind == "trace":
            spans = rec.get("spans")
            if isinstance(spans, list):
                trace_segments[-1].extend(
                    sp for sp in spans if isinstance(sp, dict))
            d = rec.get("dropped")
            if isinstance(d, int) and d > 0:
                trace_dropped += d
        if kind == "screened":
            screened_total += int(rec.get("n_screened", 0) or 0)
        if kind == "aggregator":
            trimmed_total += float(rec.get("n_trimmed", 0) or 0)
            clipped_total += int(rec.get("n_clipped", 0) or 0)
        if kind == "privacy":
            # cumulative by construction — the LAST record is the
            # budget spent (a rollback's replay re-journals the lower
            # window values, and the last one still wins)
            eps = rec.get("epsilon")
            if isinstance(eps, (int, float)):
                epsilon_spent = float(eps)
            if isinstance(rec.get("sigma"), (int, float)):
                privacy_sigma = float(rec["sigma"])
            if isinstance(rec.get("delta"), (int, float)):
                privacy_delta = float(rec["delta"])
        if kind == "compressor":
            m2 = rec.get("mode")
            ub = rec.get("up_bytes")
            if isinstance(m2, str) and isinstance(ub, (int, float)):
                acc = wire_by_mode.setdefault(
                    m2, {"rounds": 0, "up_bytes": 0.0,
                         "wire_bytes": 0.0})
                acc["rounds"] += 1
                acc["up_bytes"] += float(ub)
                if isinstance(rec.get("wire_bytes"), (int, float)):
                    acc["wire_bytes"] = float(rec["wire_bytes"])
        if kind == "control":
            c2 = rec.get("controller")
            if isinstance(c2, str) and c2:
                acc = control_by_ctl.setdefault(
                    c2, {"adjustments": 0, "clamped": 0,
                         "final": None})
                acc["adjustments"] += 1
                if rec.get("clamped") is True:
                    acc["clamped"] += 1
                if isinstance(rec.get("new"), (int, float)):
                    # records are appended in commit order, so the
                    # last `new` IS the controller's final value
                    acc["final"] = float(rec["new"])
        if kind == "state_tier":
            tier_hits += int(rec.get("hits", 0) or 0)
            tier_misses += int(rec.get("misses", 0) or 0)
            tier_spills += int(rec.get("spills", 0) or 0)
            tier_spill_b += float(rec.get("spill_bytes", 0) or 0)
        if kind == "round" and isinstance(rec.get("round"), int):
            rounds.append(rec["round"])
            mono = rec.get("mono")
            if isinstance(mono, (int, float)):
                if prev_mono is not None and mono > prev_mono:
                    cadence.append(float(mono) - prev_mono)
                prev_mono = float(mono)
            if isinstance(rec.get("down_bytes"), (int, float)):
                down_b += float(rec["down_bytes"])
            if isinstance(rec.get("up_bytes"), (int, float)):
                up_b += float(rec["up_bytes"])
        elif kind == "span":
            span_s += float(rec.get("dispatch_s", 0.0))
            span_s += float(rec.get("block_s", 0.0))
        elif kind == "checkpoint":
            ckpt_s += float(rec.get("seconds", 0.0))
        elif kind == "schedule" and rec.get("deadline_s") is not None:
            deadlines += 1
    out = {
        "records": len(records),
        "events": dict(sorted(kinds.items())),
        "rounds": len(rounds),
        "first_round": min(rounds) if rounds else None,
        "last_round": max(rounds) if rounds else None,
        "span_seconds": round(span_s, 3),
        "checkpoint_seconds": round(ckpt_s, 3),
        "down_mib": round(down_b / (1024 ** 2), 3),
        "up_mib": round(up_b / (1024 ** 2), 3),
        "deadline_rounds": deadlines,
    }
    if (kinds.get("screened") or kinds.get("numeric_trip")
            or kinds.get("state_quarantine")):
        # numeric-robustness counters (ISSUE 16): how many client
        # updates the in-round admission excluded, how many times the
        # finite-frontier watch tripped (each trip = one rollback),
        # and how many state-tier rows were quarantined at restore
        out["screened_total"] = screened_total
        out["numeric_trips"] = kinds.get("numeric_trip", 0)
        out["state_quarantines"] = kinds.get("state_quarantine", 0)
    if kinds.get("aggregator") or kinds.get("screen_adapt"):
        # Byzantine-robustness counters (ISSUE 17): cumulative
        # trimmed/clipped clients across the robust-aggregated rounds
        # and how many times adaptive screening moved the threshold
        out["trimmed_total"] = round(trimmed_total, 3)
        out["clipped_total"] = clipped_total
        out["screen_adaptations"] = kinds.get("screen_adapt", 0)
    if epsilon_spent is not None:
        # differential privacy (ISSUE 19): cumulative budget spent —
        # the one number a DP run is answerable for
        out["epsilon_spent"] = round(epsilon_spent, 6)
        if privacy_sigma is not None:
            out["privacy_sigma"] = privacy_sigma
        if privacy_delta is not None:
            out["privacy_delta"] = privacy_delta
    if wire_by_mode:
        # compressor plugins (ISSUE 19): per-mode bytes-on-wire —
        # round count, per-client wire geometry, cumulative upload
        out["compressor_modes"] = {
            m: {"rounds": acc["rounds"],
                "wire_bytes": round(acc["wire_bytes"], 3),
                "up_mib": round(acc["up_bytes"] / (1024 ** 2), 3)}
            for m, acc in sorted(wire_by_mode.items())}
    if control_by_ctl:
        # controller bank (ISSUE 20): per-controller adjustment count,
        # clamp count, and final value — the one-line answer to "what
        # did the self-tuning loop actually do this run"
        out["controllers"] = {
            c: {"adjustments": acc["adjustments"],
                "clamped": acc["clamped"],
                "final": (None if acc["final"] is None
                          else round(acc["final"], 6))}
            for c, acc in sorted(control_by_ctl.items())}
    if tier_hits or tier_misses:
        # tiered client state (ISSUE 11): working-set hit rate +
        # spill traffic — the run's residency summary line
        out["state_hit_rate"] = round(
            tier_hits / max(tier_hits + tier_misses, 1), 4)
        out["state_spills"] = tier_spills
        out["state_spill_mib"] = round(tier_spill_b / (1024 ** 2), 3)
    if cadence:
        hist: dict = {}
        for dt in cadence:
            label = _cadence_bucket(dt)
            hist[label] = hist.get(label, 0) + 1
        srt = sorted(cadence)
        out["cadence"] = {
            "rounds": len(cadence),
            "p50_s": round(srt[min(len(srt) // 2, len(srt) - 1)], 6),
            "p95_s": round(
                srt[min(int(0.95 * len(srt)), len(srt) - 1)], 6),
            "hist": hist,
        }
    trace_spans = [sp for seg in trace_segments for sp in seg]
    if trace_spans:
        # graftscope (ISSUE 13): the stage-level analytics block.
        # Stage durations pool across segments (each dur is already a
        # within-process interval); busy/wall sums PER segment.
        out["trace_spans"] = len(trace_spans)
        out["trace_stages"] = stage_stats(trace_spans)
        fetches = [sp["cohort"] for sp in trace_spans
                   if sp.get("name") == "load_fetch" and "cohort" in sp]
        if fetches:
            # beside the loader's stages: the share of FedLoader's
            # rounds that went through the transform's cohort form
            # (the rest were fetched and transformed client by client)
            out["loader_cohort_share"] = round(
                sum(fetches) / len(fetches), 4)
        moved = [sp for sp in trace_spans
                 if sp.get("name") in ("gather", "scatter")
                 and "bytes" in sp]
        gathers = sum(sp["name"] == "gather" for sp in moved)
        if gathers:
            # client state motion (federated/round TrainRound): the
            # rows and bytes a round's cohort gather and scatter-back
            # have to move, both ways summed — over `state_motion_ms`
            # of a device trace, the bandwidth the two programs reach
            out["state_motion_rows_per_round"] = round(
                sum(sp["rows"] for sp in moved) / gathers, 2)
            out["state_motion_bytes_per_round"] = round(
                sum(sp["bytes"] for sp in moved) / gathers, 1)
        busy = wall = 0.0
        for seg in trace_segments:
            bw = device_busy_wall(seg)
            if bw is not None:
                busy += bw[0]
                wall += bw[1]
        if wall > 0:
            out["overlap_efficiency"] = round(min(busy / wall, 1.0), 4)
        qmax: dict = {}
        for sp in trace_spans:
            q = sp.get("q")
            name = sp.get("name", "")
            if isinstance(q, int) and isinstance(name, str) \
                    and name.endswith("_enqueue"):
                writer = name[:-len("_enqueue")]
                qmax[writer] = max(qmax.get(writer, 0), q)
        if qmax:
            out["writer_queue_max"] = dict(sorted(qmax.items()))
        if trace_dropped:
            out["trace_dropped"] = trace_dropped
    if tier_digests:
        # the analysis tiers' report digests side by side (graftaudit
        # / graftmesh / graftsync / graftnum), so "which exact audit
        # reports does this run vouch for" is one summary read
        out["analysis_digests"] = dict(sorted(tier_digests.items()))
        if num_findings is not None:
            out["num_audit_findings"] = num_findings
    if corrupt_lines:
        out["corrupt_lines"] = int(corrupt_lines)
    return out

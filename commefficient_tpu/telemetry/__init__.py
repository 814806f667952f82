"""commefficient_tpu.telemetry — the observability subsystem (ISSUE 4).

Three parts, one session object tying them together:

  * `metrics` — a fixed-shape NAMED f32 metric vector computed inside
    the jitted round (round loss, update/error norms, survivor count,
    processed examples, realized top-k, sketch estimate-residual
    proxy), carried through scanned spans and exported to the host
    only at span boundaries via explicit `device_get` — the
    transfer-guard and three-programs contracts hold with telemetry
    permanently on, and `ServerState` bits are provably unchanged;
  * `journal` — an append-only JSONL event log in the run dir
    recording round/span metrics, wall-clock spans, checkpoint saves,
    XLA compile events, retry attempts, and injected faults; the
    analysis tiers append their digests in the same schema;
  * `clients` — per-client EMA throughput + participation, persisted
    in the checkpoint resume-bit-exact: the measurement substrate for
    the ROADMAP's deadline-estimation and straggler-aware-sampling
    openings.

`TelemetrySession` is the host-side conductor FedModel dispatches into
(`FedModel.attach_telemetry`): it buffers device metric vectors with a
ONE-ROUND lag on the per-round path (materializing a round that has
already completed costs no sync — the same discipline the drivers'
metric emission uses, PERF.md), consumes whole spans at their natural
boundary on the scanned path, feeds the throughput tracker, journals
everything, and drives `jax.profiler` capture of operator-selected
spans (`--profile_spans A:B`).
"""
from __future__ import annotations

import contextlib
import os
import time
from typing import Callable, Optional, Tuple

import numpy as np

import jax

from commefficient_tpu.analysis import runtime as _runtime
from commefficient_tpu.telemetry import metrics as tmetrics
from commefficient_tpu.telemetry.clients import ClientThroughputTracker
from commefficient_tpu.telemetry.journal import RunJournal, append_event
from commefficient_tpu.telemetry.trace import TRACE

__all__ = [
    "ClientThroughputTracker", "NumericTripError", "RunJournal",
    "TRACE", "TelemetrySession", "append_event",
    "attach_run_telemetry", "parse_profile_spans", "tmetrics",
]

# the telemetry metrics the finite-frontier watch trips on (ISSUE
# 16): non-finite update or error-feedback l2 means corruption
# reached the server state — the persistent-poison condition the
# auto-rollback recovers from. Both are EXISTING metrics; the watch
# adds no device work.
WATCHED_METRICS = ("update_l2", "error_l2")

# the least time between two `trace` journal writes of a session
# whose rings are under half full (TelemetrySession._flush_trace).
# A quarter of a second and not a whole one: what reads the journal
# while the run goes on (the benchmark's `host_api_ms`, right after
# its window) sees spans at most this old, and its own test window
# is half a second long.
TRACE_FLUSH_S = 0.25


class NumericTripError(RuntimeError):
    """A watched telemetry metric went non-finite: value corruption
    reached ServerState (error feedback makes it persistent —
    PAPER.md). Raised by TelemetrySession at the round's one-lag
    emission, AFTER the `numeric_trip` journal event is durable. The
    drivers catch this, halt the span, roll back to the newest
    finite checkpoint (utils/checkpoint.load_resilient with
    require_finite) and resume with screening force-enabled
    (FedModel.force_screen_rounds); Config.max_numeric_rollbacks
    bounds the retries before failing loud."""

    def __init__(self, round_idx: int, metrics=()):
        super().__init__(
            f"non-finite {'/'.join(metrics) or 'telemetry'} at round "
            f"{round_idx}: value corruption reached the server state")
        self.round_idx = int(round_idx)
        self.metrics = tuple(metrics)


def parse_profile_spans(spec: str) -> Optional[Tuple[int, int]]:
    """Parse `--profile_spans A:B` into a half-open span-index range
    [A, B), or None for the empty spec. Raises ValueError on malformed
    input (caught at config validation, not mid-run)."""
    if not spec:
        return None
    lo, sep, hi = spec.partition(":")
    try:
        if not sep:
            raise ValueError
        a, b = int(lo), int(hi)
    except ValueError:
        raise ValueError(
            f"--profile_spans expects 'A:B' (half-open span indices, "
            f"e.g. '2:4'), got {spec!r}") from None
    if a < 0 or b <= a:
        raise ValueError(
            f"--profile_spans {spec!r}: need 0 <= A < B")
    return a, b


def attach_run_telemetry(model, cfg, log_dir: str, coord: bool,
                         driver: str,
                         materialize: Callable = jax.device_get):
    """Build + attach a run's TelemetrySession (both drivers share
    this wiring): journal on the coordinator only (cfg.journal_path or
    <run dir>/journal.jsonl), profiler capture per cfg.profile_spans,
    the model's own throughput tracker, and the caller's device->host
    materializer (multihost.gather_host in the drivers). Journals
    `run_start` and returns the session — the caller owns close() —
    or None under --no_telemetry."""
    if not cfg.telemetry:
        return None
    journal = None
    if coord:
        jpath = cfg.journal_path or os.path.join(
            log_dir or ".", "journal.jsonl")
        # --pipeline: appends ride a bounded-queue writer thread (one
        # fsync per queued batch, drained on close/crash) so journal
        # durability leaves the round loop's critical path
        journal = RunJournal(jpath, run_id=log_dir or driver,
                             async_writer=bool(cfg.pipeline),
                             drain_timeout=float(getattr(
                                 cfg, "writer_drain_timeout_s", 0.0)))
    tele = TelemetrySession(
        journal=journal, tracker=model.throughput,
        profile_spans=cfg.profile_spans,
        profile_dir=os.path.join(log_dir or ".", "profile_spans"),
        materialize=materialize,
        # graftscope (ISSUE 13): --trace enables the process-global
        # stage tracer for this run (session-owned; disabled at
        # close); the controller tag keys cross-controller stitching
        trace=bool(getattr(cfg, "trace", False)),
        controller=jax.process_index(), mixer_layers=cfg.mixer_layers)
    model.attach_telemetry(tele)
    tele.journal_event(
        "run_start", driver=driver, mode=cfg.mode,
        trace=bool(getattr(cfg, "trace", False)),
        dataset=cfg.dataset_name, num_workers=cfg.num_workers,
        num_clients=model.num_clients, grad_size=model.cfg.grad_size,
        # a journal reader attributing up_bytes needs to know what
        # dtype rode the wire
        sketch_table_dtype=cfg.sketch_table_dtype,
        # residency provenance (ISSUE 11): a reader of state_tier
        # events needs the tier and working-set cap in the run record
        state_tier=cfg.state_tier,
        state_working_set=int(cfg.state_working_set),
        scan_rounds=bool(cfg.scan_rounds),
        transfer_guard=bool(cfg.debug_transfer_guard),
        resumed_round=int(np.asarray(
            materialize(model.server.round_idx))))
    return tele


class TelemetrySession:
    """Host-side telemetry conductor for one run.

    journal:       RunJournal or None (non-coordinator processes pass
                   None — tracker updates still run, since every
                   process gathers identical metrics)
    tracker:       ClientThroughputTracker or None; FedModel.
                   attach_telemetry fills in the model's own tracker
                   when unset
    profile_spans: `--profile_spans` spec ("" = no capture)
    profile_dir:   where jax.profiler traces land
    materialize:   device->host function for buffered metric arrays;
                   pass multihost.gather_host in multi-controller runs
                   (the default jax.device_get only handles
                   process-addressable arrays)
    mixer_layers:  Config.mixer_layers, to name the vector's counters
    """

    def __init__(self, journal: Optional[RunJournal] = None,
                 tracker: Optional[ClientThroughputTracker] = None,
                 profile_spans: str = "",
                 profile_dir: str = "profile_spans",
                 materialize: Callable = jax.device_get,
                 clock: Callable[[], float] = time.monotonic,
                 trace: bool = False, controller: int = 0,
                 mixer_layers: int = 0):
        self.journal = journal
        self._mixer_layers = mixer_layers
        self.tracker = tracker
        # graftscope (ISSUE 13, --trace): enable the process-global
        # tracer for this run; the session owns it — drained at every
        # round/span boundary into batched `trace` journal events and
        # DISABLED again at close, so tracing never leaks into a
        # later in-process run
        self._owns_trace = bool(trace)
        if trace:
            TRACE.enable(controller=controller)
        self._materialize = materialize
        self._clock = clock
        self._trace_flushed = clock()
        self._spans = parse_profile_spans(profile_spans)
        self._profile_dir = profile_dir
        self._profiling = False
        self._steady = False
        # per-round path: (round_idx, ids, vec, counts, t) buffer — the
        # previous round materializes when the next one arrives (its
        # device values are complete by then; device_get costs no sync)
        self._pending = None
        self._closed = False
        self._journal_warned = False
        # cumulative communication byte totals (accounting.py feeds
        # per-round sums through on_round/on_span; run_end carries the
        # cumulative pair so a journal is self-contained on cost)
        self._cum_down_bytes = 0.0
        self._cum_up_bytes = 0.0
        self._comm_seen = False
        _runtime.add_compile_listener(self._on_compile)

    # ---------------- journal passthrough --------------------------------
    def _safe_write(self, write: Callable[[], object]) -> None:
        """Observability must never kill training: a journal append
        that fails (disk full, unwritable path mid-run) warns once and
        the run continues. Notably the retry hook journals from
        INSIDE utils/retry.with_retries; an exception there would turn
        a recoverable transient into a fatal span failure."""
        try:
            write()
        except (OSError, TypeError, ValueError) as e:
            # TypeError included: a field json can't serialize must
            # degrade to a lost record, not a crashed run
            if not self._journal_warned:
                print(f"telemetry: journal write failed ({e}); "
                      f"training continues, further failures silent")
                self._journal_warned = True

    def journal_event(self, kind: str, /, **fields) -> None:
        if self.journal is not None:
            self._safe_write(lambda: self.journal.event(kind, **fields))

    def _flush_trace(self, force: bool = False) -> None:
        """Drain the graftscope rings into ONE batched `trace` journal
        event. Called at every round and span boundary, but it writes
        only when TRACE_FLUSH_S of monotonic time have passed since
        the last write or a ring is half full: an append and fsync
        per round sat on the dispatching thread, where every host
        millisecond is a round millisecond. `force` (flush(), and
        with it close()) writes whatever is buffered. Without a
        journal (non-coordinator processes) the drain still runs so
        the rings stay bounded — the spans are simply discarded, like
        every other coordinator-only record."""
        if not TRACE.enabled:
            return
        now = self._clock()
        if (not force and now - self._trace_flushed < TRACE_FLUSH_S
                and TRACE.fill() < 0.5):
            return
        self._trace_flushed = now
        spans, dropped = TRACE.drain()
        if not spans and not dropped:
            return
        if self.journal is None:
            return
        fields = {"controller": TRACE.controller, "spans": spans}
        if dropped:
            fields["dropped"] = int(dropped)
        self._safe_write(
            lambda: self.journal.event("trace", **fields))

    # ---------------- compile events (analysis/runtime listener) ---------
    def mark_steady_state(self) -> None:
        """After this, every backend compile is journaled as a
        `compile_warning` — steady-state recompiles are retrace bugs
        (new treedef/shape/weak-type leak), the regression class
        assert_program_count(3) pins in tests and this surfaces in
        production journals. The drivers call it once the first full
        epoch (train spans + eval) has compiled everything a
        steady-state run legitimately needs."""
        self._steady = True

    @contextlib.contextmanager
    def expect_compiles(self, why: str = ""):
        """Temporarily allow compiles without warnings (e.g. a final
        eval program that legitimately first-compiles long after the
        training loop reached steady state)."""
        prev, self._steady = self._steady, False
        try:
            yield
        finally:
            self._steady = prev

    def _on_compile(self, event_name: str, duration: float) -> None:
        if self.journal is None:
            return
        fields = {"event_name": event_name}
        if duration is not None:
            fields["seconds"] = round(float(duration), 4)
        if self._steady:
            self.journal_event(
                "compile_warning", unexpected=True,
                why="backend compile after steady state: an accidental "
                    "retrace (see analysis/runtime.py)", **fields)
        else:
            self.journal_event("compile", **fields)

    # ---------------- per-round path (FedModel.__call__) -----------------
    def on_round(self, round_idx: int, client_ids, telemetry_vec,
                 num_examples, comm=None, scheduled=None) -> None:
        """Buffer one round's device metrics; materialize + journal the
        PREVIOUS round (one-round lag, so no per-round host sync).
        comm: optional (download_bytes, upload_bytes) round totals from
        the accountant — journaled on the round event and accumulated
        into the run_end cumulative pair. scheduled: optional [W]
        mask; zero slots are idle scheduler pads, excluded from the
        throughput tracker (telemetry/clients.update_round)."""
        now = self._clock()
        prev, self._pending = self._pending, (
            int(round_idx), np.asarray(client_ids), telemetry_vec,
            num_examples, now, comm, scheduled)
        if prev is not None:
            self._emit_round(prev, now - prev[4])

    def _record_comm(self, fields: dict, comm) -> None:
        if comm is None:
            return
        down, up = float(comm[0]), float(comm[1])
        self._cum_down_bytes += down
        self._cum_up_bytes += up
        self._comm_seen = True
        fields["down_bytes"] = down
        fields["up_bytes"] = up

    def _emit_round(self, rec, seconds: Optional[float]) -> None:
        round_idx, ids, vec, counts, _, comm, scheduled = rec
        # the host blocks here when the buffered round has not
        # finished on the device: time this work waited for it
        with TRACE.span("device_wait", of=round_idx):
            counts_h = np.asarray(self._materialize(counts))
            vec_h = (None if vec is None else np.asarray(
                self._materialize(vec), np.float32))
        if (self.tracker is not None and seconds is not None
                and seconds > 0):
            self.tracker.update_round(ids, counts_h, seconds,
                                      scheduled=scheduled)
        named = tmetrics.named(vec_h, self._mixer_layers)
        if self.journal is not None:
            fields = {"round": round_idx}
            if named:
                fields["metrics"] = named
            if seconds is not None:
                fields["seconds"] = round(seconds, 6)
            self._record_comm(fields, comm)
            self.journal_event("round", **fields)
        elif comm is not None:
            self._record_comm({}, comm)
        # per-round boundary = the unscanned path's span boundary:
        # flush the stage spans this round produced as one batch
        self._flush_trace()
        self._check_trip(round_idx, named)

    def _check_trip(self, round_idx: int, named) -> None:
        """The finite-frontier watch (ISSUE 16): a non-finite watched
        metric journals a durable `numeric_trip` event and raises
        NumericTripError for the driver's rollback handler. Armed
        whenever telemetry metrics flow (no extra device work; every
        process trips identically since all gather the same metrics);
        disarmed during close() so a trailing flush cannot raise out
        of the shutdown path."""
        if not named or self._closed:
            return
        bad = [k for k in WATCHED_METRICS
               if k in named and not np.isfinite(named[k])]
        if not bad:
            return
        self.journal_event("numeric_trip", round=int(round_idx),
                           metrics=bad)
        if self.journal is not None:
            self._safe_write(self.journal.flush)
        raise NumericTripError(round_idx, bad)

    def discard_pending(self) -> None:
        """Drop the one-round-lag buffer WITHOUT journaling it — the
        rollback path: the buffered round belongs to the halted
        stream (and likely carries the same non-finite metrics that
        tripped), so emitting it after the rollback would double-
        count the trip against Config.max_numeric_rollbacks."""
        self._pending = None

    def flush(self) -> None:
        """Drain the one-round-lag buffer (end of epoch/run; before a
        deliberate crash boundary). The drained round has no interval
        measurement, so it journals without `seconds` and skips the
        tracker. Also barriers the journal's async writer queue (a
        no-op for the default synchronous journal), so a crash-
        boundary caller knows its records are on disk before it
        raises."""
        prev, self._pending = self._pending, None
        if prev is not None:
            self._emit_round(prev, None)
        self._flush_trace(force=True)
        if self.journal is not None:
            self._safe_write(self.journal.flush)

    def journal_flush(self) -> None:
        """Barrier ONLY the journal's async writer queue, leaving the
        one-round-lag metric buffer alone (draining it here would
        journal the pending round without an interval measurement and
        skip its tracker feeding). The write-ahead plan seal (ISSUE
        12, FedModel._flush_write_ahead) needs exactly this: sealed
        `schedule` records durable before dispatch, telemetry
        semantics untouched. A no-op for the default synchronous
        journal, whose events are durable when event() returns."""
        if self.journal is not None:
            self._safe_write(self.journal.flush)

    # ---------------- span path (FedModel.run_rounds) --------------------
    def on_span(self, first_round: int, ids_rows: np.ndarray,
                telemetry_rows: Optional[np.ndarray],
                counts_rows: np.ndarray,
                dispatch_s: float, block_s: float,
                comm_rows=None, scheduled_rows=None) -> None:
        """Consume one completed scanned span: host-materialized
        [N, W] ids/counts and [N, M] metric rows (the caller did the
        explicit span-boundary device_get). Journals one `span` event
        plus one `round` event per round, and feeds the tracker with
        the span-amortized per-round wall time. comm_rows: optional
        per-round (download_bytes, upload_bytes) totals (None entries
        for unaccounted rounds — FedModel.run_rounds(account=False)).
        scheduled_rows: optional per-round [W] masks whose zero slots
        are idle scheduler pads, excluded from the tracker."""
        # a pending per-round buffer holds an EARLIER round (mixed
        # per-round + scanned usage): drain it first so the journal's
        # round events stay strictly ordered
        self.flush()
        n = int(np.asarray(ids_rows).shape[0])
        per_round_s = (dispatch_s + block_s) / max(n, 1)
        if self.tracker is not None:
            for i in range(n):
                self.tracker.update_round(
                    ids_rows[i], counts_rows[i], per_round_s,
                    scheduled=(None if scheduled_rows is None
                               else scheduled_rows[i]))
        if self.journal is not None:
            batch = [("span", {"first_round": int(first_round),
                               "rounds": n,
                               "dispatch_s": round(dispatch_s, 6),
                               "block_s": round(block_s, 6)})]
            for i in range(n):
                fields = {"round": int(first_round) + i,
                          "seconds": round(per_round_s, 6)}
                if telemetry_rows is not None:
                    named = tmetrics.named(
                        np.asarray(telemetry_rows[i], np.float32),
                        self._mixer_layers)
                    if named:
                        fields["metrics"] = named
                if comm_rows is not None:
                    self._record_comm(fields, comm_rows[i])
                batch.append(("round", fields))
            # one append + fsync for the whole span's records
            self._safe_write(lambda: self.journal.events(batch))
        elif comm_rows is not None:
            for comm in comm_rows:
                self._record_comm({}, comm)
        # span-boundary graftscope flush: the span's stage spans (and
        # any writer-thread spans committed since the last boundary)
        # land as one batched trace event — one additional fsync
        self._flush_trace()
        # finite-frontier watch over the span's rows, in round order:
        # the FIRST tripped round raises (its journal records above
        # are already durable), matching the per-round path's boundary
        if telemetry_rows is not None:
            for i in range(n):
                self._check_trip(
                    int(first_round) + i,
                    tmetrics.named(np.asarray(telemetry_rows[i],
                                              np.float32),
                                   self._mixer_layers))

    # ---------------- profiler capture (--profile_spans) -----------------
    def span_profile_begin(self, span_idx: int) -> None:
        """Start a jax.profiler trace when `span_idx` enters the
        requested [A, B) window (called by scanloop before each span's
        dispatch). One contiguous capture covers the whole window."""
        if (self._spans is None or self._profiling
                or not (self._spans[0] <= span_idx < self._spans[1])):
            return
        os.makedirs(self._profile_dir, exist_ok=True)
        jax.profiler.start_trace(self._profile_dir)
        self._profiling = True
        self.journal_event("profile_start", span=span_idx,
                           dir=self._profile_dir)

    def span_profile_end(self, span_idx: int) -> None:
        """Stop the capture once the window's last span completed (the
        caller's run_rounds already forced device completion, so the
        trace covers the span's real device work)."""
        if not self._profiling or span_idx < self._spans[1] - 1:
            return
        jax.profiler.stop_trace()
        self._profiling = False
        self.journal_event("profile_stop", span=span_idx,
                           dir=self._profile_dir)

    # ---------------- lifecycle ------------------------------------------
    def close(self, **fields) -> None:
        """Drain buffers, stop a live profiler capture, detach the
        compile listener, and journal `run_end` with `fields`."""
        if self._closed:
            return
        self._closed = True
        self.flush()
        if self._profiling:
            jax.profiler.stop_trace()
            self._profiling = False
            self.journal_event("profile_stop", span=-1,
                               dir=self._profile_dir)
        _runtime.remove_compile_listener(self._on_compile)
        if self.journal is not None:
            if self._comm_seen:
                # cumulative accountant totals: the journal is
                # self-contained on communication cost (validated
                # against the per-round sums by validate_journal)
                fields.setdefault("down_bytes_total",
                                  self._cum_down_bytes)
                fields.setdefault("up_bytes_total", self._cum_up_bytes)
            self.journal_event("run_end", **fields)
            self.journal.close()
        if self._owns_trace:
            # the session enabled the global tracer; a leaked enable
            # would trace (and buffer) every later in-process run
            TRACE.disable()

"""Checkpoint / resume.

The reference only saves a final `state_dict` (reference:
CommEfficient/cv_train.py:418-421 via the FedModel.__getattr__ hack at
fed_aggregator.py:372-376) and HF `save_pretrained` for GPT2
(fed_aggregator.py:208-211); there is no mid-run resume anywhere
(SURVEY.md §5). Here checkpointing is a first-class subsystem: the
full training state — PS weights, server momentum/error state, round
counter, per-client persistent state, scheduler step — round-trips
through one .npz file, enabling both the reference's end-of-training
save and true mid-run resume.

Preemption safety (the ROADMAP north-star environment is preemptible
TPU pods):

  * every write is ATOMIC — the bytes go to `<path>.tmp` and only a
    successful flush is `os.replace`d over the real name, so a
    preemption mid-write can never corrupt the previous checkpoint;
  * `save_rotating` keeps the newest `keep_last` round-stamped files
    plus a `<prefix>.latest` JSON manifest; `load_latest` resumes from
    the manifest (falling back to a glob, then to the legacy fixed
    `<prefix>.npz` name);
  * each checkpoint embeds a config FINGERPRINT
    (mode/grad_size/num_clients/error_type); `load_checkpoint`
    validates it against the resuming run and raises
    `CheckpointMismatchError` naming the offending field — instead of
    the opaque KeyError/broadcast error a shape mismatch used to
    surface as.
"""
from __future__ import annotations

import errno
import glob as _glob
import json
import os
import queue
import shutil
import threading
import zipfile
import zlib
from typing import Callable, Dict, List, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from commefficient_tpu.federated.round import (
    ClientState, RowBlock, ServerState,
)
from commefficient_tpu.parallel import multihost as mh
from commefficient_tpu.telemetry.trace import TRACE

# the config fields a checkpoint must agree on to be loadable into a
# run (order fixed; all serialized as strings in the .npz)
FINGERPRINT_FIELDS = ("mode", "grad_size", "num_clients", "error_type")


class CheckpointMismatchError(ValueError):
    """A checkpoint written under a different config was loaded into
    this run. Carries the first offending fingerprint field so the
    error is actionable ('grad_size: checkpoint has 7840, this run
    expects 122570') rather than an opaque broadcast failure."""

    def __init__(self, path: str, field: str, found, expected):
        self.field, self.found, self.expected = field, found, expected
        super().__init__(
            f"checkpoint {path!r} does not match this run's config: "
            f"{field}: checkpoint has {found!r}, this run expects "
            f"{expected!r}. Point --checkpoint_path at a checkpoint "
            f"written with the same mode/model/client-count, or start "
            f"fresh without --resume.")


def config_fingerprint(cfg, num_clients: Optional[int] = None) -> dict:
    """The compatibility fingerprint embedded in every checkpoint."""
    return {
        "mode": cfg.mode,
        "grad_size": int(cfg.grad_size),
        "num_clients": int(num_clients if num_clients is not None
                           else (cfg.num_clients or 0)),
        "error_type": cfg.error_type,
    }


def validate_fingerprint(found: dict, expected: dict,
                         path: str) -> None:
    """Raise CheckpointMismatchError on the first FINGERPRINT_FIELDS
    entry where `found` disagrees with `expected`. Fields absent from
    `found` (legacy partial fingerprints) are skipped; values compare
    as strings (the .npz round-trips them that way)."""
    for k in FINGERPRINT_FIELDS:
        if k in found and str(found[k]) != str(expected[k]):
            raise CheckpointMismatchError(path, k, found[k], expected[k])


class AsyncCheckpointWriter:
    """Bounded-queue writer thread for off-critical-path checkpoint
    persistence (ISSUE 10, Config.pipeline).

    The device->host state GATHER stays on the caller's thread (it is
    collective in multi-controller runs and must block on span
    completion anyway); what moves off the critical path is the
    SERIALIZATION — np.savez + flush + fsync + atomic rename, plus the
    manifest/prune bookkeeping — which at checkpoint-every-span
    cadence otherwise stalls the round loop for the full disk write.
    Jobs run strictly FIFO on one thread, so the stamped file always
    lands before its manifest entry and rotation order is preserved;
    the atomic `.tmp` + os.replace discipline is unchanged (the
    closures are the same code, just executed later).

    The queue is BOUNDED (default: one write in flight plus one
    queued): a slow disk back-pressures the training loop instead of
    accumulating unbounded dirty state in memory. `drain()` blocks
    until every submitted write is durable and re-raises the first
    writer-side failure on the caller's thread — callers drain before
    any synchronous save (ordering) and in their crash/finally paths,
    so an InjectedFault drill flushes exactly like a clean shutdown."""

    _SENTINEL = object()

    def __init__(self, max_pending: int = 2,
                 drain_timeout: float = 0.0,
                 name: str = "checkpoint"):
        self._q: "queue.Queue" = queue.Queue(maxsize=max(max_pending, 1))
        # deferred writer failure: stored on the writer thread,
        # consumed (cleared) on the caller's thread at drain/submit —
        # cross-thread state, guarded (graftsync SY001,
        # analysis/domains.SHARED_STATE) so a failure landing while
        # the caller swaps the slot is never lost
        self._exc: Optional[BaseException] = None
        self._exc_lock = threading.Lock()
        self._closed = False
        # writer-thread watchdog (ISSUE 12 satellite): drain()/close()
        # deadline in seconds (0 = wait forever); `name` labels the
        # TimeoutError so a hung spill queue reads "state-spill
        # writer", not "checkpoint writer"
        self._drain_timeout = float(drain_timeout)
        self._name = str(name)
        # graftscope correlation (ISSUE 13): per-writer submission
        # sequence — the producer-side `<name>_enqueue` instant and
        # this item's writer-thread `<name>_qwait`/`<name>_write`
        # spans share a `seq`, stitching the deferred write back to
        # the round that produced it
        self._seq = 0
        self._thread = threading.Thread(
            target=self._run, name=f"{name}-writer", daemon=True)
        self._thread.start()

    def _run(self) -> None:
        import time as _time
        while True:
            item = self._q.get()
            try:
                if item is self._SENTINEL:
                    return
                job, enq_mono, seq, tags = item
                if enq_mono is not None:
                    TRACE.record(f"{self._name}_qwait", enq_mono,
                                 _time.monotonic(), seq=seq, **tags)
                try:
                    if enq_mono is not None:
                        with TRACE.span(f"{self._name}_write",
                                        seq=seq, **tags):
                            job()
                    else:
                        job()
                except BaseException as e:  # graftlint: disable=GL005 -- not swallowed: deferred re-raise on the caller's thread at drain()/submit() (_raise_pending); jobs are write closures, never fault-harness code
                    with self._exc_lock:
                        if self._exc is None:
                            self._exc = e
            finally:
                self._q.task_done()

    def _raise_pending(self) -> None:
        with self._exc_lock:
            exc, self._exc = self._exc, None
        if exc is not None:
            raise exc

    def submit(self, job: Callable[[], None]) -> None:
        """Queue one write closure; blocks when the queue is full (the
        bounded-memory back-pressure). A failure from an EARLIER job
        re-raises here so write errors surface at the next save, not
        silently at shutdown."""
        if self._closed:
            raise RuntimeError("AsyncCheckpointWriter is closed")
        self._raise_pending()
        if TRACE.enabled:
            import time as _time
            seq, self._seq = self._seq, self._seq + 1
            # the enqueue instant runs on the PRODUCER thread inside
            # whatever stage span is open there (checkpoint, or the
            # tier_spill chunk), so its inherited round tag — carried
            # into the queue item — labels the writer-thread spans
            tags = TRACE.current_tags()
            TRACE.instant(f"{self._name}_enqueue", seq=seq,
                          q=self._q.qsize(), **tags)
            self._q.put((job, _time.monotonic(), seq, tags))
        else:
            self._q.put((job, None, 0, {}))

    def drain(self) -> None:
        """Block until every submitted write is durable; re-raise the
        first writer-side failure (an ENOSPC from a queued save
        surfaces HERE, on the caller's thread, not silently at
        shutdown). With a drain_timeout, a hung fsync raises
        TimeoutError naming this writer (utils/watchdog)."""
        from commefficient_tpu.utils.watchdog import drain_queue
        drain_queue(self._q, self._drain_timeout, self._name)
        self._raise_pending()

    def close(self) -> None:
        """Drain, then stop the thread. Idempotent. Honors the
        drain_timeout watchdog like drain()."""
        if self._closed:
            return
        from commefficient_tpu.utils.watchdog import drain_queue
        drain_queue(self._q, self._drain_timeout, self._name)
        self._closed = True
        self._q.put(self._SENTINEL)
        self._thread.join()
        self._raise_pending()


class Checkpoint(NamedTuple):
    """Loaded training state; accounting state rides along so resumed
    runs keep cumulative comm totals correct, the per-client
    throughput-tracker state (telemetry/clients.py) so measured
    client speeds survive preemption bit-exactly, and the round
    scheduler's counters (commefficient_tpu/scheduler, `sched_*`
    keys) for the same reason."""
    server: ServerState
    clients: Optional[ClientState]
    scheduler_step: int
    accountant_state: Optional[dict] = None
    prev_change_words: Optional[np.ndarray] = None
    fingerprint: Optional[dict] = None
    throughput: Optional[dict] = None
    scheduler: Optional[dict] = None
    # FedSampler stream state (data/sampler.py state_dict, `smp_*`
    # keys): rng + mid-epoch cursor/permutations, so a non-uniform
    # (throughput-aware) mid-epoch resume replays the exact same data
    # stream instead of re-drawing the epoch head
    sampler: Optional[dict] = None
    # O(cohort) client-state rows (ISSUE 9, `crows_*` keys): the
    # touched-row ids, per-block rows for exactly those ids, and the
    # init-weights base untouched topk_down rows reconstruct from —
    # checkpoint bytes scale with clients-ever-sampled, not the
    # population (FedModel.client_rows_payload / load_state). When
    # present, `clients` above is None: the two formats are exclusive.
    # Under Config.state_tier=host (ISSUE 11) the same dict also
    # carries `lru_ids`/`lru_slots` — the working set's recency order
    # and slot map, drained-spill-queue consistent — so a resumed run
    # replays the exact eviction stream; a device-tier loader ignores
    # them (row values are tier-independent).
    client_rows: Optional[dict] = None
    # pending async-admission entries (ISSUE 10, `asyb_*` keys):
    # deferred straggler contributions not yet admitted
    # (federated/async_agg.AsyncAdmitBuffer.state_dict), so a resumed
    # run admits exactly what the uninterrupted one would have
    async_admit: Optional[dict] = None


def save_checkpoint(path: str, server: ServerState,
                    clients: Optional[ClientState] = None,
                    scheduler_step: int = 0,
                    include_clients: bool = True,
                    accountant=None,
                    prev_change_words: Optional[np.ndarray] = None,
                    chunk_rows: int = 256,
                    fingerprint: Optional[dict] = None,
                    throughput: Optional[dict] = None,
                    scheduler: Optional[dict] = None,
                    sampler: Optional[dict] = None,
                    client_rows: Optional[dict] = None,
                    async_admit: Optional[dict] = None,
                    writer: Optional[AsyncCheckpointWriter] = None
                    ) -> str:
    """Write training state to `path` (.npz appended if absent).
    Per-client state can be excluded (include_clients=False) to keep
    files small when clients are stateless (error_type != local and
    no local momentum). Pass the FedModel's CommAccountant (and its
    _prev_change_words bitset) so resumed runs continue download
    accounting instead of restarting from 'round 1 is free'.

    The write is ATOMIC on the coordinator: bytes land in
    `<path>.tmp` and are `os.replace`d over the final name only after
    a successful flush, so a preemption mid-write leaves the previous
    checkpoint intact (a stray .tmp at most). Pass `fingerprint`
    (config_fingerprint(...)) so load_checkpoint can reject a resume
    under an incompatible config with an actionable error."""
    os.makedirs(os.path.dirname(path) or ".", exist_ok=True)
    if not path.endswith(".npz"):
        path = path + ".npz"
    # gather_host: per-client state is cross-process sharded in
    # multi-controller runs. The gathers are collective — every process
    # must reach this call — but only the coordinator writes the file
    # (guard below), the reference's rank-0-saves discipline. The big
    # [num_clients, D] blocks go through the CHUNKED gather so
    # non-coordinator hosts never materialize them whole (multihost.
    # zeros' own no-host-global-materialization rule).
    arrays = {
        "ps_weights": mh.gather_host(server.ps_weights),
        "Vvelocity": mh.gather_host(server.Vvelocity),
        "Verror": mh.gather_host(server.Verror),
        "round_idx": mh.gather_host(server.round_idx),
        "scheduler_step": np.asarray(scheduler_step),
    }
    if include_clients and client_rows is not None:
        # O(cohort) format (ISSUE 9): persist ONLY the touched rows
        # (FedModel.client_rows_payload) — checkpoint bytes stay flat
        # while the population grows. Takes precedence over the dense
        # `clients` blocks; the loader reconstructs init + rows.
        for k, v in client_rows.items():
            arrays[f"crows_{k}"] = np.asarray(v)
    elif include_clients and clients is not None:
        arrays["client_errors"] = _gather_rows(clients.errors, chunk_rows)
        arrays["client_velocities"] = _gather_rows(clients.velocities,
                                                   chunk_rows)
        arrays["client_weights"] = _gather_rows(clients.weights, chunk_rows)
    if accountant is not None:
        for k, v in accountant.state_dict().items():
            arrays[f"acct_{k}"] = v
    if prev_change_words is not None:
        arrays["acct_prev_change_words"] = np.asarray(prev_change_words)
    if throughput is not None:
        # per-client throughput-tracker state (telemetry/clients.py
        # state_dict()); plain arrays, so the resume is bit-exact
        for k, v in throughput.items():
            arrays[f"thr_{k}"] = np.asarray(v)
    if scheduler is not None:
        # round-scheduler counters (scheduler.RoundScheduler
        # state_dict()); same bit-exact-resume contract as thr_*
        for k, v in scheduler.items():
            arrays[f"sched_{k}"] = np.asarray(v)
    if sampler is not None:
        # FedSampler stream state (data/sampler.py state_dict());
        # restores the exact mid-epoch data stream under non-uniform
        # sampling — same bit-exact-resume contract as thr_*/sched_*
        for k, v in sampler.items():
            arrays[f"smp_{k}"] = np.asarray(v)
    if async_admit is not None:
        # pending async-admission entries (ISSUE 10): deferred
        # straggler contributions awaiting their admit round — same
        # bit-exact-resume contract as thr_*/sched_*/smp_*
        for k, v in async_admit.items():
            arrays[f"asyb_{k}"] = np.asarray(v)
    if fingerprint is not None:
        for k in FINGERPRINT_FIELDS:
            arrays[f"fp_{k}"] = np.asarray(str(fingerprint[k]))

    def _write():
        # the atomic .tmp + os.replace write — unchanged whether it
        # runs inline or (writer given) on the persistence thread
        tmp = path + ".tmp"
        try:
            with open(tmp, "wb") as f:
                np.savez(f, **arrays)
                f.flush()
                os.fsync(f.fileno())
            os.replace(tmp, path)
        except OSError as e:
            if e.errno == errno.ENOSPC:
                # actionable disk-full error (ISSUE 12 satellite):
                # names the checkpoint rather than surfacing as a bare
                # "No space left on device" from deep inside numpy —
                # under the async writer this re-raises on the
                # caller's thread at the next submit()/drain()
                raise OSError(
                    e.errno,
                    f"checkpoint write to {path!r} failed: disk full "
                    "(ENOSPC). Free space on the checkpoint "
                    "filesystem or point --checkpoint_path at a "
                    "volume with room; the previous checkpoint is "
                    "intact (atomic .tmp+replace).") from e
            raise

    if mh.is_coordinator():
        if writer is None:
            _write()
        else:
            # off-critical-path serialization (Config.pipeline): the
            # gathers above already completed on this thread (they are
            # collective and block on device state anyway); only the
            # coordinator-local disk write is deferred. Durability is
            # writer.drain()'s contract — callers drain before any
            # synchronous save and at shutdown/crash.
            writer.submit(_write)
    mh.sync_processes("checkpoint-written")
    return path


def _gather_rows(x, chunk_rows: int = 256):
    """Gather a clients-sharded block to the COORDINATOR's host in
    bounded chunks of client rows: every process participates in each
    chunk's collective gather, but only the coordinator accumulates
    the full array — non-coordinators' transient peak is one chunk.
    Returns the full array on the coordinator, an empty placeholder
    elsewhere. A RowBlock (the device's whole-tile storage,
    federated/round) comes back in the checkpoint's own form, a plain
    [rows, D] array: the file format is independent of the device
    layout, so files written before and after the tile form load
    alike."""
    if isinstance(x, RowBlock):
        tiles = _gather_rows(x.tiles, chunk_rows)
        if tiles.ndim != 3:
            return tiles          # a non-coordinator's placeholder
        return np.asarray(RowBlock(tiles, x.D))
    if (not mh.is_multihost() or getattr(x, "ndim", 1) < 2
            or x.shape[0] <= chunk_rows):
        return mh.gather_host(x)
    rows = x.shape[0]
    out = (np.empty(x.shape, np.dtype(x.dtype))
           if mh.is_coordinator() else None)
    for lo in range(0, rows, chunk_rows):
        hi = min(lo + chunk_rows, rows)
        block = mh.gather_host(x[lo:hi])
        if out is not None:
            out[lo:hi] = block
        del block
    return out if out is not None else np.zeros((0,), np.float32)


def _as_block(saved: np.ndarray):
    """A saved client_* array back in the blocks' form: a [rows, D]
    block becomes a RowBlock (its tiles still on the host: the
    loading model places them, FedModel.load_state), a zero-size
    placeholder stays a plain array."""
    if saved.ndim == 2:
        return RowBlock.from_rows(saved)
    return jnp.asarray(saved)


def load_checkpoint(path: str,
                    expect_fingerprint: Optional[dict] = None
                    ) -> Checkpoint:
    """Read training state back.

    `expect_fingerprint`: the resuming run's config_fingerprint(...) /
    FedModel.checkpoint_fingerprint. A checkpoint carrying a
    different fingerprint raises CheckpointMismatchError naming the
    offending field. Legacy checkpoints without a fingerprint get a
    best-effort grad_size check from the stored ps_weights shape —
    still a clear error instead of the downstream broadcast failure."""
    if not path.endswith(".npz"):
        path = path + ".npz"
    z = np.load(path)
    fingerprint = None
    if "fp_mode" in z.files:
        # tolerate partial fingerprints: a checkpoint written before
        # FINGERPRINT_FIELDS grew lacks the newer fp_* entries —
        # validate_fingerprint skips absent fields
        fingerprint = {k: str(z[f"fp_{k}"]) for k in FINGERPRINT_FIELDS
                       if f"fp_{k}" in z.files}
    if expect_fingerprint is not None:
        found = fingerprint
        if found is None:
            # legacy file: the flat weight vector length is still a
            # decisive compatibility signal
            found = {"grad_size": str(int(z["ps_weights"].shape[0]))}
        validate_fingerprint(found, expect_fingerprint, path)
    server = ServerState(
        ps_weights=jnp.asarray(z["ps_weights"]),
        Vvelocity=jnp.asarray(z["Vvelocity"]),
        Verror=jnp.asarray(z["Verror"]),
        round_idx=jnp.asarray(z["round_idx"]),
    )
    clients = None
    client_rows = None
    if "crows_ids" in z.files:
        client_rows = {k[len("crows_"):]: z[k] for k in z.files
                       if k.startswith("crows_")}
    elif "client_errors" in z:
        clients = ClientState(
            errors=_as_block(z["client_errors"]),
            velocities=_as_block(z["client_velocities"]),
            weights=_as_block(z["client_weights"]),
        )
    acct = {k[len("acct_"):]: z[k] for k in z.files
            if k.startswith("acct_") and k != "acct_prev_change_words"}
    prev = (z["acct_prev_change_words"]
            if "acct_prev_change_words" in z.files else None)
    thr = {k[len("thr_"):]: z[k] for k in z.files
           if k.startswith("thr_")}
    sched = {k[len("sched_"):]: z[k] for k in z.files
             if k.startswith("sched_")}
    smp = {k[len("smp_"):]: z[k] for k in z.files
           if k.startswith("smp_")}
    asyb = {k[len("asyb_"):]: z[k] for k in z.files
            if k.startswith("asyb_")}
    return Checkpoint(server, clients, int(z["scheduler_step"]),
                      acct or None, prev, fingerprint, thr or None,
                      sched or None, smp or None, client_rows,
                      asyb or None)


# ---------------- keep-last-k rotation + latest manifest -----------------

def _manifest_path(prefix: str) -> str:
    return prefix + ".latest"


def _round_stamp(basename: str) -> int:
    """Round index from a `<name>-r<round:08d>.npz` basename, or -1
    for anything that doesn't match the stamp pattern."""
    try:
        return int(basename.rsplit("-r", 1)[1].split(".", 1)[0])
    except (IndexError, ValueError):
        return -1


def _atomic_write_text(path: str, text: str) -> None:
    tmp = path + ".tmp"
    with open(tmp, "w") as f:
        f.write(text)
        f.flush()
        os.fsync(f.fileno())
    os.replace(tmp, path)


# ---------------- checkpoint integrity (ISSUE 12 satellite) --------------

class CorruptCheckpointError(ValueError):
    """A checkpoint file failed its integrity check: unreadable npz
    (truncated/torn bytes) or a per-array checksum mismatch against
    the manifest recorded at save time. The resilient loader
    (load_resilient) treats this as 'fall back to the previous
    rotation', not a crash."""


# the errors np.load raises on a truncated/corrupted .npz — the shapes
# a torn write, a partial copy, or bit rot actually produce
_NPZ_READ_ERRORS = (OSError, ValueError, KeyError, EOFError,
                    zipfile.BadZipFile)


def file_integrity(path: str) -> Tuple[Dict[str, int], bool]:
    """ONE read pass over a checkpoint .npz: per-array CRC32s plus a
    finite verdict (every float-dtype array is all-finite). The bytes
    come from DISK (save_rotating re-reads the file it just wrote, so
    the manifest vouches for the written artifact, not the in-memory
    arrays it came from); the finite bit rides the same pass because
    a second full read at every rotation would double checkpoint IO.
    The verdict feeds the manifest's `finite` map (ISSUE 16): the
    rollback loader skips checkpoints recorded non-finite instead of
    resuming into the same poisoned state it just tripped on."""
    out: Dict[str, int] = {}
    finite = True
    with np.load(path) as z:
        for name in z.files:
            a = np.ascontiguousarray(z[name])
            out[name] = zlib.crc32(a.tobytes()) & 0xFFFFFFFF
            if finite and np.issubdtype(a.dtype, np.floating):
                finite = bool(np.isfinite(a).all())
    return out, finite


def file_checksums(path: str) -> Dict[str, int]:
    """Per-array CRC32s of a checkpoint .npz (see file_integrity)."""
    return file_integrity(path)[0]


def verify_checkpoint_file(path: str,
                           checksums: Optional[Dict[str, int]]
                           ) -> None:
    """Integrity-check one checkpoint file: it must be a readable npz
    and, when the manifest recorded `checksums` for it, every array's
    CRC32 must match (missing/extra arrays are mismatches too).
    Raises CorruptCheckpointError; `checksums=None` (a legacy manifest
    or the glob/fixed-name fallback) checks readability only."""
    try:
        found = file_checksums(path)
    except _NPZ_READ_ERRORS as e:
        raise CorruptCheckpointError(
            f"checkpoint {path!r} is unreadable "
            f"({type(e).__name__}: {e}) — truncated or torn write?"
        ) from e
    if not checksums:
        return
    expect = {k: int(v) for k, v in checksums.items()}
    if found != expect:
        bad = sorted(set(expect) ^ set(found)
                     | {k for k in set(expect) & set(found)
                        if expect[k] != found[k]})
        raise CorruptCheckpointError(
            f"checkpoint {path!r} failed its integrity check: "
            f"array(s) {bad[:5]} disagree with the manifest checksums "
            "recorded at save time — corrupted on disk?")


def load_resilient(prefix: str,
                   expect_fingerprint: Optional[dict] = None,
                   on_fallback: Optional[Callable[[str, str], None]]
                   = None,
                   require_finite: bool = False
                   ) -> Optional[Tuple[str, Checkpoint]]:
    """Corruption-tolerant auto-resume (ISSUE 12 satellite): walk the
    rotation newest-first — manifest history, then stamped files the
    manifest lost, then the legacy fixed name — integrity-checking
    each candidate (verify_checkpoint_file, with the manifest's
    per-array checksums when recorded) and loading the FIRST good one.
    A corrupt/truncated newest checkpoint therefore falls back to the
    previous keep-last-k rotation instead of crashing mid-resume;
    every skipped candidate fires `on_fallback(path, reason)` (the
    drivers journal a loud `checkpoint_fallback` event) and prints.

    A CheckpointMismatchError (config fingerprint disagreement) is NOT
    corruption and re-raises immediately: silently falling back past a
    wrong-config checkpoint would resume from an ancestor of a
    different run. Returns (path, Checkpoint) or None when nothing
    loadable exists.

    `require_finite=True` (ISSUE 16 numeric rollback): ALSO skip any
    candidate whose manifest `finite` entry records False — a save
    that captured non-finite state, exactly what the rollback must
    walk past. A MISSING finite entry (pre-16 manifest, or the
    glob/fixed-name fallback with no manifest at all) means
    unknown-but-loadable, so old rotations stay resumable; the
    loaded arrays are the authority then."""
    ckpt_dir = os.path.dirname(prefix) or "."
    candidates: List[str] = []
    checksums: Dict[str, Dict[str, int]] = {}
    finite_map: Dict[str, bool] = {}
    try:
        with open(_manifest_path(prefix)) as f:
            manifest = json.load(f)
        for base in manifest.get("history", []):
            candidates.append(os.path.join(ckpt_dir, base))
        checksums = manifest.get("checksums", {}) or {}
        finite_map = manifest.get("finite", {}) or {}
    except (OSError, ValueError):
        pass
    # stamped files the manifest lost track of, newest first; then the
    # legacy fixed name — the latest_checkpoint_path fallback order
    seen = set(candidates)
    for p in sorted(_glob.glob(prefix + "-r*.npz"), reverse=True):
        if p not in seen:
            candidates.append(p)
    fixed = prefix if prefix.endswith(".npz") else prefix + ".npz"
    if fixed not in seen and os.path.exists(fixed):
        candidates.append(fixed)
    for path in candidates:
        if not os.path.exists(path):
            continue
        if require_finite and \
                finite_map.get(os.path.basename(path)) is False:
            reason = ("manifest records non-finite state at save "
                      "time (numeric rollback skips it)")
            print(f"checkpoint fallback: skipping non-finite "
                  f"{path!r}; trying the previous rotation")
            if on_fallback is not None:
                on_fallback(path, reason)
            continue
        try:
            verify_checkpoint_file(
                path, checksums.get(os.path.basename(path)))
            return path, load_checkpoint(
                path, expect_fingerprint=expect_fingerprint)
        except CheckpointMismatchError:
            raise
        except (CorruptCheckpointError, *_NPZ_READ_ERRORS) as e:
            reason = f"{type(e).__name__}: {e}"
            print(f"checkpoint fallback: skipping corrupt {path!r} "
                  f"({reason}); trying the previous rotation")
            if on_fallback is not None:
                on_fallback(path, reason)
    return None


def save_rotating(prefix: str, server: ServerState,
                  clients: Optional[ClientState] = None,
                  keep_last: int = 3, max_age_hours: float = 0.0,
                  writer: Optional[AsyncCheckpointWriter] = None,
                  **kw) -> str:
    """Atomic round-stamped save + `<prefix>.latest` manifest update +
    keep-last-k pruning. Returns the written path.

    Files are `<prefix>-r<round:08d>.npz`; the manifest is JSON
    {"latest": basename, "history": [basenames newest-first],
    "checksums": {...}, "finite": {basename: bool}} written
    atomically AFTER the checkpoint itself, so a preemption between
    the two leaves the manifest pointing at the previous (intact)
    file. Pruning removes only files the rotation itself wrote (they
    must match the stamp pattern), never a legacy fixed-name
    checkpoint. Collective in multi-controller runs (save_checkpoint
    gathers); only the coordinator touches the filesystem.

    max_age_hours > 0 ALSO prunes kept entries older than that
    wall-clock age (file mtime) — keep-last-k bounds disk by count,
    age pruning bounds it by time for long slow-rotating pod runs.
    The just-written `latest` entry is exempt (its mtime is fresh
    anyway), so the manifest can never dangle: every basename it
    lists — `latest` included — names a file that survived pruning."""
    round_idx = int(np.asarray(mh.gather_host(server.round_idx)))
    path = f"{prefix}-r{round_idx:08d}.npz"
    save_checkpoint(path, server, clients, writer=writer, **kw)

    def _manifest_and_prune():
        base = os.path.basename(path)
        mpath = _manifest_path(prefix)
        history = []
        old_sums: dict = {}
        old_fin: dict = {}
        try:
            with open(mpath) as f:
                m = json.load(f)
            history = list(m.get("history", []))
            old_sums = dict(m.get("checksums", {}) or {})
            old_fin = dict(m.get("finite", {}) or {})
        except (OSError, ValueError):
            pass
        # entries stamped AFTER this round belong to an abandoned
        # timeline (a dir reused without --resume, or a resume from an
        # older checkpoint): drop them from the history so the prune
        # below removes their files — otherwise a lost manifest would
        # let the glob fallback resume the abandoned run
        history = [h for h in history if _round_stamp(h) <= round_idx]
        history = [base] + [h for h in history if h != base]
        keep = history[:max(keep_last, 1)]
        if max_age_hours > 0:
            # age filter BEFORE the manifest write: the history must
            # only ever list files the prune below leaves on disk.
            # keep[0] is the file written moments ago — never pruned,
            # so `latest` always resolves.
            import time
            cutoff_ts = time.time() - max_age_hours * 3600.0
            ckpt_dir = os.path.dirname(prefix) or "."

            def fresh(basename: str) -> bool:
                try:
                    return (os.path.getmtime(
                        os.path.join(ckpt_dir, basename)) >= cutoff_ts)
                except OSError:
                    return False
            keep = [keep[0]] + [h for h in keep[1:] if fresh(h)]
        # per-array checksums (ISSUE 12 satellite) + finite bit
        # (ISSUE 16): computed in ONE pass by RE-READING the
        # just-written file, so the manifest vouches for the bytes on
        # disk — load_resilient verifies checksums at resume and
        # falls back on mismatch, and the numeric-rollback loader
        # (require_finite) walks past entries recording finite=False.
        # Prior entries carry forward; both dicts are trimmed to the
        # kept history so they cannot grow without bound.
        try:
            old_sums[base], old_fin[base] = file_integrity(path)
        except _NPZ_READ_ERRORS as e:
            # a checkpoint that cannot be re-read right after its
            # atomic replace is ALREADY corrupt — keep the manifest
            # entry checksum-less (readability is still checked at
            # load) but say so loudly
            print(f"checkpoint warning: cannot checksum just-written "
                  f"{path!r} ({e})")
        sums = {b: old_sums[b] for b in keep if b in old_sums}
        fins = {b: old_fin[b] for b in keep if b in old_fin}
        _atomic_write_text(mpath, json.dumps(
            {"latest": base, "history": keep, "checksums": sums,
             "finite": fins}, indent=2))
        # prune every stamped file NOT in the kept history (not just
        # the manifest's own tail): a lost/corrupt manifest must not
        # orphan earlier stamped files forever, and stale
        # higher-round files from a pre-resume timeline must not
        # shadow the live one in the glob fallback
        keep_set = set(keep)
        for old in _glob.glob(prefix + "-r*.npz"):
            if os.path.basename(old) not in keep_set:
                try:
                    os.remove(old)
                except OSError:
                    pass

    if mh.is_coordinator():
        if writer is None:
            _manifest_and_prune()
        else:
            # FIFO on the single writer thread: the stamped .npz write
            # submitted by save_checkpoint above lands before this
            # manifest update, preserving the "manifest never points
            # at a missing file" invariant
            writer.submit(_manifest_and_prune)
    mh.sync_processes("checkpoint-rotated")
    return path


def save_final(prefix: str, server: ServerState,
               clients: Optional[ClientState] = None,
               keep_last: int = 3, max_age_hours: float = 0.0,
               **kw) -> str:
    """End-of-run save: ONE collective gather, two artifacts — the
    rotated stamped checkpoint (+ manifest, so a later --resume sees
    this final state) and the legacy fixed `<prefix>.npz` the
    finetune/HF tooling loads. The fixed name is a coordinator-side
    atomic copy of the stamped bytes, not a second gather+serialize
    (which would double a multi-GB device->host transfer at
    shutdown). Returns the fixed-name path."""
    writer = kw.pop("writer", None)
    stamped = save_rotating(prefix, server, clients,
                            keep_last=keep_last,
                            max_age_hours=max_age_hours,
                            writer=writer, **kw)
    if writer is not None:
        # the fixed-name copy below reads the stamped bytes — the
        # queued write must be durable first
        writer.drain()
    fixed = prefix if prefix.endswith(".npz") else prefix + ".npz"
    if mh.is_coordinator():
        tmp = fixed + ".tmp"
        shutil.copyfile(stamped, tmp)
        os.replace(tmp, fixed)
    mh.sync_processes("checkpoint-final")
    return fixed


def latest_checkpoint_path(prefix: str) -> Optional[str]:
    """Resolve the newest checkpoint for `prefix`: the manifest's
    `latest` entry if it names an existing file, else the
    highest-round `<prefix>-r*.npz` on disk (manifest lost), else the
    legacy fixed `<prefix>.npz`, else None."""
    ckpt_dir = os.path.dirname(prefix) or "."
    try:
        with open(_manifest_path(prefix)) as f:
            base = json.load(f).get("latest")
        if base:
            cand = os.path.join(ckpt_dir, base)
            if os.path.exists(cand):
                return cand
    except (OSError, ValueError):
        pass
    stamped = sorted(_glob.glob(prefix + "-r*.npz"))
    if stamped:
        return stamped[-1]
    if os.path.exists(prefix + ".npz"):
        return prefix + ".npz"
    return None


def load_latest(prefix: str,
                expect_fingerprint: Optional[dict] = None
                ) -> Optional[Checkpoint]:
    """Auto-resume entry point: load the newest checkpoint for
    `prefix` (see latest_checkpoint_path), or None when there is
    nothing to resume from. Fingerprint-validated like
    load_checkpoint."""
    path = latest_checkpoint_path(prefix)
    if path is None:
        return None
    return load_checkpoint(path, expect_fingerprint=expect_fingerprint)


def transfer_for_finetune(old_params, new_template):
    """Head-swap transfer (reference resnet9.py:105-130 + finetune load
    at cv_train.py:377-384): copy every leaf whose path+shape matches
    the new model; leaves that differ (e.g. the classifier head for a
    different class count) keep the new model's fresh initialization.
    Returns (params, frozen_mask_pytree) where frozen_mask marks the
    transferred (frozen in the reference) leaves with 1.0."""
    old_flat = dict(jax.tree_util.tree_flatten_with_path(old_params)[0])
    new_flat, treedef = jax.tree_util.tree_flatten_with_path(new_template)

    out, frozen = [], []
    for path, leaf in new_flat:
        prev = old_flat.get(path)
        if prev is not None and prev.shape == leaf.shape:
            out.append(jnp.asarray(prev))
            frozen.append(jnp.ones((), jnp.float32))
        else:
            out.append(leaf)
            frozen.append(jnp.zeros((), jnp.float32))
    params = jax.tree_util.tree_unflatten(treedef, out)
    mask = jax.tree_util.tree_unflatten(treedef, frozen)
    return params, mask

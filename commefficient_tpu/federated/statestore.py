"""Tiered cold client state (ISSUE 11): a bounded device-HBM working
set over a host-spilled long tail.

PR 9 made every per-round *cost* O(active cohort), but the
`[padded_population, D]` client-state blocks (each a RowBlock,
federated/round: stored `[rows, T, 128]`, a row as whole tiles) still
lived sharded in device HBM — ~78 TB at flagship D for 1e6
local_topk clients, so "million clients" was real for compute but
not for residency. Behind ``Config.state_tier=host`` this module
caps the device-resident rows
at an LRU working set of ``Config.state_working_set`` recently-active
clients: the ClientState blocks shrink to ``[working_set, D]``
(federated/round.client_state_rows) and rows are addressed by device
SLOT, while the cold tail lives on the host (optionally disk-backed
sparse memmaps under ``Config.state_spill_dir``) as plain
``[rows, D]`` tables: the tile form is the device's alone, and every
row crossing between the tiers does so as a ``[W, D]`` CohortState
through the two state-motion programs, which convert it.

The PR-9 cohort-gather/scatter-back state-motion pair is the single
choke point extended — and stays the ONLY pair of state-motion
programs per config:

  * a cohort member already resident is a working-set HIT: its slot
    rides straight into the gather's index operand;
  * a MISS is RESTORED before the round through the *same jitted
    scatter program* — its row (host tail, a still-in-flight spill,
    or the init value for a never-seen client) is built host-side,
    explicitly placed with the gather's own cohort shardings, and
    scattered into the assigned slot;
  * the eviction victim's row is SPILLED through the *same jitted
    gather program*: gathered by slot, its device->host copy started
    asynchronously (multihost.async_gather_host), and committed to
    the host tail by a bounded-queue writer thread — the ISSUE-10
    off-critical-path persistence pattern, so a slow host never
    stalls the round loop. Spills are CORRECTNESS (not best-effort
    observability): writer failures re-raise on the caller's thread
    at the next submit/flush.

The three round programs still see only ``[num_workers, D]``
CohortState operands (graftaudit AU004-strict keeps them honest while
the tier moves underneath), and because f32 rows round-trip the host
bit-exactly and the round program is trace-identical between tiers,
the PER-ROUND path is BIT-IDENTICAL to ``state_tier=device``
(tests/test_statetier.py). The scanned span traces a different
program (the block shape rides the carry), so cross-tier agreement
there is the usual cross-program class — exact at test geometries,
float-level where XLA compiles the two spans differently (the PR-9
caveat); each tier's own scanned run is deterministic and resumes
bit-exactly.

Determinism: the LRU advances only in ``plan_round`` — a pure
function of the cohort-id stream — slots are assigned in ascending
order, and the LRU recency order + slot map ride in checkpoints
(``crows_lru_ids`` / ``crows_lru_slots``), so a resumed run replays
the exact eviction stream of the uninterrupted one. A checkpoint
drains the spill queue first, so a crash with spills in flight
resumes bit-exactly from the last saved boundary (the mid-spill
contract).
"""
from __future__ import annotations

import errno
import os
import threading
import zlib
from collections import OrderedDict, deque
from typing import Dict, List, NamedTuple, Optional, Tuple

import jax
import numpy as np
from jax.sharding import PartitionSpec as P

from commefficient_tpu.federated import round as fround
from commefficient_tpu.parallel import multihost as mh
from commefficient_tpu.telemetry.trace import TRACE

# the tracked client-state blocks, in ClientState field order — the
# serialization contract shared with utils/checkpoint's crows_* keys
STATE_FIELDS = ("errors", "velocities", "weights")


def _row_crc(row: np.ndarray) -> int:
    """CRC32 of one tail row's f32 bytes — the per-chunk checksum the
    tiers record at spill time and verify at restore (ISSUE 16). f32
    rows round-trip the host bit-exactly, so any mismatch is real
    corruption (torn memmap page, bit rot, an external write), never
    a representation artifact."""
    return zlib.crc32(np.ascontiguousarray(
        row, dtype=np.float32).tobytes()) & 0xFFFFFFFF


def tracked_fields(cfg) -> Dict[str, bool]:
    """Which ClientState blocks this config actually materializes
    (zero-size placeholders are untracked). Delegates to round.py's
    own predicates — the one source of truth for which blocks the
    gather/scatter programs carry — so a widened tracking rule there
    can never silently diverge from the store's spill format."""
    return {
        "errors": fround._has_errors(cfg),
        "velocities": fround._has_velocities(cfg),
        "weights": cfg.do_topk_down,
    }


class TierPlan(NamedTuple):
    """One round's tier motion, decided at STAGE time (pure host LRU
    bookkeeping — safe to run one round ahead under Config.pipeline)
    and executed at COMMIT time against the then-current device block
    (the victim values captured are post-scatter-back of every
    earlier round)."""
    slots: np.ndarray                 # [W] int32 device slot per cohort member
    restores: Tuple[Tuple[int, int], ...]  # (client_id, slot) misses
    spills: Tuple[Tuple[int, int], ...]    # (victim_id, slot) evictions


def _make_spill_writer(max_pending: int = 4,
                       drain_timeout: float = 0.0):
    """The spill queue IS utils/checkpoint.AsyncCheckpointWriter — the
    ISSUE-10 bounded-queue FIFO thread with deferred re-raise at
    submit()/drain(), exactly the contract a correctness-critical
    spill needs (a failed spill LOSES CLIENT STATE, so it must not be
    best-effort like the journal writer). `drain_timeout` is the
    ISSUE-12 watchdog (Config.writer_drain_timeout_s): a hung spill
    fsync raises TimeoutError naming the state-spill writer instead
    of silently hanging flush()/checkpoint drains. Imported lazily: at
    module scope, importing utils.checkpoint from here would re-enter
    a partially-initialized checkpoint module whenever checkpoint
    itself is the import root (checkpoint -> federated package -> api
    -> this module -> checkpoint); by store-construction time every
    module is fully initialized."""
    from commefficient_tpu.utils.checkpoint import AsyncCheckpointWriter
    return AsyncCheckpointWriter(max_pending=max_pending,
                                 drain_timeout=drain_timeout,
                                 name="state-spill")


class _RamTail:
    """Host-RAM long tail: one growable [cap, D] f32 table per tracked
    block + an id->row map. O(clients-ever-evicted) memory — the
    design point: the tail holds what device HBM no longer does."""

    def __init__(self, fields: List[str], D: int):
        self._fields = list(fields)
        self._D = int(D)
        self._rowmap: Dict[int, int] = {}
        self._tables: Dict[str, np.ndarray] = {
            f: np.zeros((0, self._D), np.float32) for f in fields}

    def _grow(self, need: int) -> None:
        have = next(iter(self._tables.values())).shape[0] \
            if self._tables else 0
        if need <= have:
            return
        cap = max(need, have * 2, 64)
        for f in self._fields:
            t = self._tables[f]
            nt = np.zeros((cap, self._D), np.float32)
            nt[:t.shape[0]] = t
            self._tables[f] = nt

    def put(self, ids, rows: Dict[str, np.ndarray]) -> None:
        for i, cid in enumerate(int(c) for c in ids):
            row = self._rowmap.get(cid)
            if row is None:
                row = len(self._rowmap)
                self._grow(row + 1)
                self._rowmap[cid] = row
            for f in self._fields:
                self._tables[f][row] = rows[f][i]

    def has(self, cid: int) -> bool:
        return int(cid) in self._rowmap

    def get(self, cid: int) -> Dict[str, np.ndarray]:
        row = self._rowmap[int(cid)]
        return {f: self._tables[f][row] for f in self._fields}

    def get_many(self, ids) -> Dict[str, np.ndarray]:
        """Bulk read — one fancy-indexed copy per field instead of a
        per-client Python loop (checkpoint/resume assemble the whole
        touched population through this)."""
        rows = np.fromiter((self._rowmap[int(c)] for c in ids),
                           np.int64, count=len(ids))
        return {f: self._tables[f][rows] for f in self._fields}

    def ids(self) -> List[int]:
        return sorted(self._rowmap)

    def clear(self) -> None:
        self._rowmap.clear()
        for f in self._fields:
            self._tables[f] = np.zeros((0, self._D), np.float32)

    def close(self) -> None:
        pass


class _DiskTail:
    """Disk-backed long tail (Config.state_spill_dir): one sparse
    [num_clients, D] f32 memmap per tracked block, indexed by client
    id — POSIX sparse files make never-spilled rows free on disk.
    Scratch state: created fresh per run and rebuilt from crows_*
    checkpoint rows on resume (the files carry no cross-run
    authority)."""

    def __init__(self, dirpath: str, fields: List[str],
                 num_clients: int, D: int):
        self._dir = str(dirpath)
        self._fields = list(fields)
        self._present: set = set()
        self._maps: Dict[str, np.ndarray] = {}
        try:
            os.makedirs(dirpath, exist_ok=True)
            for f in fields:
                path = os.path.join(dirpath, f"tail_{f}.npy")
                self._maps[f] = np.lib.format.open_memmap(
                    path, mode="w+", dtype=np.float32,
                    shape=(int(num_clients), int(D)))
        except OSError as e:
            raise self._spill_error(e) from e

    def _spill_error(self, e: OSError) -> OSError:
        """Disk-full/IO failure on the spill tail, made actionable
        (ISSUE 12 satellite): spills are CORRECTNESS — a lost spill
        is lost client state — so the error must fail loud and name
        the knob, not surface as a bare errno from inside numpy."""
        why = ("disk full (ENOSPC)" if e.errno == errno.ENOSPC
               else f"{type(e).__name__}: {e}")
        return OSError(
            e.errno or errno.EIO,
            f"state spill write under --state_spill_dir "
            f"{self._dir!r} failed: {why}. Spilled rows are the "
            "authoritative copy of evicted client state — free space "
            "on (or relocate) --state_spill_dir, or drop the flag to "
            "keep the tail in host RAM.")

    def put(self, ids, rows: Dict[str, np.ndarray]) -> None:
        # Residual risk: these are stores into SPARSE memmap pages, so
        # a filesystem that fills up mid-run can deliver the
        # allocation failure as SIGBUS on first-touch (uncatchable)
        # rather than an OSError — the actionable message below covers
        # creation, flush, and whatever the kernel does surface as
        # errno. Preallocating would close that hole but defeats the
        # sparse tail (disk O(touched rows), the point of this class).
        idx = np.asarray(ids, np.int64)
        try:
            for f in self._fields:
                self._maps[f][idx] = rows[f][:len(idx)]
        except OSError as e:
            raise self._spill_error(e) from e
        self._present.update(int(c) for c in idx)

    def has(self, cid: int) -> bool:
        return int(cid) in self._present

    def get(self, cid: int) -> Dict[str, np.ndarray]:
        return {f: np.array(self._maps[f][int(cid)])
                for f in self._fields}

    def get_many(self, ids) -> Dict[str, np.ndarray]:
        """Bulk read — one fancy-indexed memmap gather per field (the
        kernel batches the page reads) instead of per-client random
        reads."""
        idx = np.asarray(ids, np.int64)
        return {f: np.asarray(self._maps[f][idx], np.float32)
                for f in self._fields}

    def ids(self) -> List[int]:
        return sorted(self._present)

    def clear(self) -> None:
        self._present.clear()

    def close(self) -> None:
        try:
            for m in self._maps.values():
                m.flush()
        except OSError as e:
            raise self._spill_error(e) from e


class TieredStateStore:
    """The host-side conductor of ``state_tier=host`` (module
    docstring). Owned by FedModel; every device op routes through the
    round handle's existing gather/scatter jits, so the two
    state-motion programs stay the only programs touching the
    ClientState blocks."""

    def __init__(self, cfg, mesh, handle, init_weights,
                 num_clients: int):
        self.cfg = cfg
        self.mesh = mesh
        self.handle = handle
        self.num_clients = int(num_clients)
        self.tracked = tracked_fields(cfg)
        self.fields = [f for f in STATE_FIELDS if self.tracked[f]]
        self.D = int(cfg.grad_size)
        n = mesh.shape["clients"]
        # init_client_state pads the working set up to the mesh's
        # clients axis; every padded row is a usable slot
        self.slots = -(-int(cfg.state_working_set) // n) * n
        self._lru: "OrderedDict[int, int]" = OrderedDict()
        self._free: deque = deque(range(self.slots))
        self._tail = (_DiskTail(cfg.state_spill_dir, self.fields,
                                self.num_clients, self.D)
                      if cfg.state_spill_dir
                      else _RamTail(self.fields, self.D))
        # spills in flight: id -> (per-field completer dict, row idx);
        # readable synchronously until the writer commits them to the
        # tail (the lock covers tail + pending, both threads touch)
        self._pending: Dict[int, Tuple[dict, int]] = {}
        # per-row CRC32s of what the tail holds (ISSUE 16): cid ->
        # field -> checksum, recorded at every tail write (spill
        # commit on the writer thread, checkpoint/legacy imports on
        # the staging thread) and verified at every tail read. Guarded
        # by _lock like tail/pending (graftsync SHARED_STATE registry,
        # analysis/domains) — both threads write it.
        self._sums: Dict[int, Dict[str, int]] = {}
        # quarantine events awaiting journal drain (take_quarantine_
        # events): rows whose tail bytes failed verification and were
        # re-initialized from the init base. Same _lock guard — the
        # writer thread never appends today, but the list rides the
        # same tail/sums transaction so the registry keeps it honest.
        self._quarantined: List[dict] = []
        self._lock = threading.Lock()
        self._writer = _make_spill_writer(
            drain_timeout=float(getattr(cfg, "writer_drain_timeout_s",
                                        0.0)))
        # scheduler prefetch cache (working-set-aware prefetch of the
        # next plan's cohort): host rows warmed ahead of their restore
        # — LRU-NEUTRAL by construction, so prefetch timing can never
        # perturb the eviction stream
        self._warm: Dict[int, Dict[str, np.ndarray]] = {}
        # clients-ever-resident, maintained incrementally: the tail
        # never drops ids and every client enters the LRU before it
        # can reach pending/tail, so this set always equals
        # lru ∪ pending ∪ tail. snapshot_tier() runs at EVERY span
        # boundary under --pipeline — recomputing the union there
        # would sort the whole touched population per span. The
        # sorted copy is cached and invalidated only when a
        # never-seen client becomes resident. Staging-thread-only.
        self._ever: set = set()
        self._ever_sorted: Optional[np.ndarray] = None
        self._init_weights = (np.asarray(init_weights, np.float32)
                              if cfg.do_topk_down else None)
        # telemetry counters (journal `state_tier` events read the
        # deltas via take_journal_fields)
        self.hits = 0
        self.misses = 0
        self.spills = 0
        self.restores = 0
        self.spill_bytes = 0
        self.restore_bytes = 0
        self.quarantines = 0
        self._emitted = {"hits": 0, "misses": 0, "spills": 0,
                         "restores": 0, "spill_bytes": 0,
                         "restore_bytes": 0, "quarantines": 0}

    # ---------------- planning (stage time, pure host) -------------------
    def plan_round(self, client_ids,
                   pinned: Optional[set] = None) -> TierPlan:
        """Assign a device slot to every cohort member and advance the
        LRU: residents are hits, misses take a free slot or evict the
        least-recently-used non-pinned client. Deterministic in the
        cohort-id stream alone. `pinned` protects ids a surrounding
        span still needs resident (plan_span)."""
        ids = np.asarray(client_ids, np.int64).reshape(-1)
        pin = {int(c) for c in ids}
        if pinned:
            pin |= {int(c) for c in pinned}
        slots = np.empty(len(ids), np.int32)
        restores: List[Tuple[int, int]] = []
        spills: List[Tuple[int, int]] = []
        for i, cid in enumerate(int(c) for c in ids):
            slot = self._lru.get(cid)
            if slot is not None:
                self._lru.move_to_end(cid)
                slots[i] = slot
                self.hits += 1
                continue
            self.misses += 1
            if self._free:
                slot = self._free.popleft()
            else:
                victim = next((c for c in self._lru if c not in pin),
                              None)
                if victim is None:
                    raise ValueError(
                        f"state_working_set={self.cfg.state_working_set} "
                        f"({self.slots} slots) cannot hold the "
                        f"{len(pin)} distinct clients this "
                        "round/span needs resident at once — raise "
                        "--state_working_set or (scanned path) lower "
                        "--scan_span")
                slot = self._lru.pop(victim)
                spills.append((victim, slot))
                self.spills += 1
            if cid not in self._ever:
                self._ever.add(cid)
                self._ever_sorted = None
            self._lru[cid] = slot
            restores.append((cid, slot))
            self.restores += 1
            slots[i] = slot
        return TierPlan(slots, tuple(restores), tuple(spills))

    def plan_span(self, ids_rounds) -> List[TierPlan]:
        """Per-round plans for one scanned span ([N, W] cohort ids).
        The span executes as ONE device program with the block on the
        scan carry and every restore happens BEFORE dispatch, so all
        the span's distinct clients must be simultaneously resident:
        every round's plan pins the whole span's ids — an earlier
        round's slot must not be reassigned by a later round's
        restore (the gather inside the scan would read the wrong
        row), and a too-small working set raises the plan_round error
        above instead of corrupting rows."""
        ids_rounds = np.asarray(ids_rounds)
        span_ids = {int(c) for row in ids_rounds for c in row}
        return [self.plan_round(row, pinned=span_ids)
                for row in ids_rounds]

    # ---------------- execution (commit time, device ops) ----------------
    def execute(self, clients, plan: TierPlan):
        """Run one plan's tier motion against the current device
        block: spill-gathers first (victim values must be captured
        before their slots are overwritten), then restore-scatters.
        Both ride the handle's existing jitted gather/scatter — cache
        hits after the first tiered dispatch. Returns the new block
        (scatter donates the old one under Config.donate_round_state,
        exactly like the post-round scatter-back)."""
        W = int(self.cfg.num_workers)
        # graftscope (ISSUE 13): tier motion as distinct stage spans
        # (one per chunk dispatch) — round/span tags inherit from the
        # caller's tier_motion bracket (federated/api)
        for lo in range(0, len(plan.spills), W):
            with TRACE.span("tier_spill"):
                self._spill_chunk(clients, plan.spills[lo:lo + W], W)
        for lo in range(0, len(plan.restores), W):
            with TRACE.span("tier_restore"):
                clients = self._restore_chunk(
                    clients, plan.restores[lo:lo + W], W)
        return clients

    def _spill_chunk(self, clients, chunk, W: int) -> None:
        idx = np.fromiter((s for _, s in chunk), np.int32,
                          count=len(chunk))
        # pad by repeating the first victim slot: duplicate READS are
        # benign, and the padded rows are dropped host-side
        padded = np.concatenate(
            [idx, np.full(W - len(idx), idx[0], np.int32)])
        placed = mh.globalize(self.mesh, P(), padded)
        rows = self.handle.gather(clients, placed)
        completers = {f: mh.async_gather_host(getattr(rows, f))
                      for f in self.fields}
        # ORDERING, not politeness: when the restore scatter that
        # follows DONATES the block it writes in place, and nothing in
        # its dataflow depends on this gather — without the barrier
        # the in-place write races the gather's read of the same
        # buffer (observed as heap corruption / garbage rows on the
        # CPU thunk runtime). The barrier waits only for the gather's
        # compute; the device->host copy and tail commit stay on the
        # writer thread. When the scatter does NOT donate (donation
        # off, or pipeline+tiered — round.py keeps the block alive for
        # the deferred boundary checkpoint there) no in-place write
        # exists and the barrier would stall pipelined staging on the
        # PREVIOUS span's whole program, so it is skipped.
        if self.handle.scatter_donate_argnums:
            jax.block_until_ready(rows)
        ids = [cid for cid, _ in chunk]
        with self._lock:
            for i, cid in enumerate(ids):
                self._pending[cid] = (completers, i)
                self._warm.pop(cid, None)
        self.spill_bytes += len(ids) * self.D * 4 * len(self.fields)

        def commit():
            host = {f: np.asarray(completers[f]())
                    for f in self.fields}
            rows = {f: host[f][:len(ids)] for f in self.fields}
            # per-row checksums BEFORE the lock (crc32 over host
            # bytes, no shared state touched): the tail write and the
            # sums record then land in one locked transaction, so a
            # concurrent verify can never see a row without its sum
            sums = {cid: {f: _row_crc(rows[f][i])
                          for f in self.fields}
                    for i, cid in enumerate(ids)}
            with self._lock:
                self._tail.put(ids, rows)
                self._sums.update(sums)
                for cid in ids:
                    ent = self._pending.get(cid)
                    if ent is not None and ent[0] is completers:
                        del self._pending[cid]

        self._writer.submit(commit)

    def _init_row(self, field: str) -> np.ndarray:
        """The init-base row a quarantined (or never-seen) client's
        field re-initializes from: zeros, except topk_down weights
        which restore from the init-weights vector."""
        if field == "weights" and self._init_weights is not None:
            return np.array(self._init_weights, np.float32)
        return np.zeros(self.D, np.float32)

    def _verify_tail_bulk(self, ids, rows: dict) -> None:
        """Checksum-verify tail rows (field -> [n, D], copies from a
        get_many) against the sums recorded at spill time. LOCK HELD.
        A mismatching field is QUARANTINED: re-initialized from its
        init base in place, healed back into the tail with a fresh
        sum (so one corruption fires one event, not one per read),
        counted, and queued for the `state_quarantine` journal drain
        (take_quarantine_events). Rows with no recorded sum — a
        legacy import, or a pre-16 resume — verify vacuously:
        unknown-but-loadable, matching the checkpoint manifest's
        missing-finite-bit contract."""
        for i, cid in enumerate(int(c) for c in ids):
            expect = self._sums.get(cid)
            if not expect:
                continue
            bad = [f for f in self.fields
                   if f in expect and _row_crc(rows[f][i]) != expect[f]]
            if not bad:
                continue
            # the three heal-writes below mutate guarded state; the
            # guard is held by EVERY caller (_rows_for, the prefetch
            # warm fill, checkpoint_rows — all call under
            # `with self._lock:`), it just isn't lexical here, which
            # is what SY001 checks
            for f in bad:
                rows[f][i] = self._init_row(f)
                self.quarantines += 1
                self._quarantined.append(  # graftsync: disable=SY001 -- caller holds self._lock
                    {"client": cid, "field": f})
            self._tail.put(  # graftsync: disable=SY001 -- caller holds self._lock
                [cid], {f: rows[f][i][None] for f in self.fields})
            self._sums[cid] = {  # graftsync: disable=SY001 -- caller holds self._lock
                f: _row_crc(rows[f][i]) for f in self.fields}

    def _verify_tail_row(self, cid: int, rows: dict) -> dict:
        """Single-client wrapper over _verify_tail_bulk (LOCK HELD);
        returns verified (possibly re-initialized) rows. Copies first:
        a RAM tail's get() hands back table views, and verification
        must never scribble re-init values through a view before the
        heal-write commits them."""
        stacked = {f: np.array(rows[f], np.float32)[None]
                   for f in self.fields}
        self._verify_tail_bulk([cid], stacked)
        return {f: stacked[f][0] for f in self.fields}

    def _rows_for(self, cid: int) -> dict:
        """The authoritative host-side rows (ALL tracked fields at
        once) for a non-resident client: a still-in-flight spill, the
        prefetch cache, the tail, or the init values for a never-seen
        client. Every source materializes the whole row set per
        client — a tail .get reads one record, a pending spill's
        completers share one gathered block — so fetching per-field
        would multiply that work by len(fields). All f32 round trips
        — restores are bit-exact."""
        with self._lock:
            ent = self._pending.get(cid)
            warm = self._warm.get(cid)
            if ent is None and warm is None and self._tail.has(cid):
                # checksum-verify-before-restore (graftsync ORDERING_
                # EDGES): the tail bytes are validated HERE, before
                # this row can reach the restore scatter below
                return self._verify_tail_row(cid, self._tail.get(cid))
        if ent is not None:
            completers, i = ent
            return {f: np.asarray(completers[f]())[i]
                    for f in self.fields}
        if warm is not None:
            return warm
        zero = np.zeros(self.D, np.float32)
        rows = {f: zero for f in self.fields}
        if "weights" in rows and self._init_weights is not None:
            rows["weights"] = self._init_weights
        return rows

    def _restore_chunk(self, clients, chunk, W: int):
        m = len(chunk)
        idx = np.fromiter((s for _, s in chunk), np.int32, count=m)
        # pad by repeating the FIRST restore's slot AND row: duplicate
        # scatter writes of an identical value are deterministic
        padded = np.concatenate(
            [idx, np.full(W - m, idx[0], np.int32)])
        values = {f: np.empty((W, self.D), np.float32)
                  for f in self.fields}
        for i, (cid, _) in enumerate(chunk):
            rows = self._rows_for(cid)
            for f in self.fields:
                values[f][i] = rows[f]
        for f in self.fields:
            values[f][m:] = values[f][0]
        dummy = np.zeros(W, np.float32)
        cohort = fround.CohortState(
            errors=values.get("errors", dummy),
            velocities=values.get("velocities", dummy),
            weights=values.get("weights", dummy))
        # explicit placement with the gather program's own cohort
        # shardings (round.make_train_fn exposes them on the handle),
        # so the restore hits the same compiled scatter the post-round
        # writeback uses and dispatch stays transfer-guard-clean
        cohort = jax.device_put(cohort, self.handle.cohort_shardings)
        placed = mh.globalize(self.mesh, P(), padded)
        self.restore_bytes += m * self.D * 4 * len(self.fields)
        return self.handle.scatter(clients, placed, cohort)

    # ---------------- scheduler prefetch ---------------------------------
    def prefetch_host_rows(self, client_ids) -> None:
        """Working-set-aware prefetch of an upcoming plan's cohort
        (scheduler.RoundScheduler wires this): warm the HOST side of
        the coming restores — in-flight spill materialization and
        tail reads (a disk-backed tail pages its rows into RAM here)
        — without touching the LRU or the device, so prefetch timing
        can never change the eviction stream or the training bits."""
        for cid in (int(c) for c in np.asarray(client_ids).reshape(-1)):
            if cid in self._lru or cid in self._warm:
                continue
            with self._lock:
                ent = self._pending.get(cid)
                in_tail = ent is None and self._tail.has(cid)
            if ent is not None:
                completers, i = ent
                # materialize the in-flight spill OUTSIDE the lock
                # (the completer blocks on the device->host copy —
                # the SY004 hostage class); only the cache insert
                # needs the guard
                rows = {f: np.array(np.asarray(completers[f]())[i])
                        for f in self.fields}
                with self._lock:
                    self._warm[cid] = rows
            elif in_tail:
                with self._lock:
                    self._warm[cid] = self._verify_tail_row(
                        cid, self._tail.get(cid))
            # never-seen clients restore from init — nothing to warm
        # the cache is consumed by _rows_for and bounded: drop entries
        # once it exceeds a few cohorts' worth (under the guard — the
        # commit thread's _rows_for reads _warm concurrently)
        with self._lock:
            if len(self._warm) > 4 * max(self.cfg.num_workers, 1):
                for cid in list(self._warm)[:len(self._warm) // 2]:
                    del self._warm[cid]

    # ---------------- telemetry ------------------------------------------
    def take_journal_fields(self) -> dict:
        """Delta counters since the last take — the payload of one
        `state_tier` journal event (telemetry/journal.py schema)."""
        totals = {"hits": self.hits, "misses": self.misses,
                  "spills": self.spills, "restores": self.restores,
                  "spill_bytes": self.spill_bytes,
                  "restore_bytes": self.restore_bytes,
                  "quarantines": self.quarantines}
        out = {k: totals[k] - self._emitted[k] for k in totals}
        self._emitted = totals
        out["resident"] = len(self._lru)
        out["working_set"] = self.slots
        return out

    def take_quarantine_events(self) -> List[dict]:
        """Drain the pending quarantine records — one {client, field}
        dict per re-initialized row — for the caller to journal as
        `state_quarantine` events (federated/api does this at the
        same boundary it journals `state_tier` deltas)."""
        with self._lock:
            out, self._quarantined = self._quarantined, []
        return out

    # ---------------- checkpoint round-trip (bit-exact) -------------------
    def snapshot_tier(self) -> dict:
        """Cheap host copies of the tier bookkeeping at one span's
        boundary — the pipelined staging loop captures this right
        after a span's dispatch (training/scanloop take_snapshot), so
        a ONE-SPAN-LATE save builds the payload for the RIGHT span:
        the next span's staging advances the LRU and enqueues new
        spills, but those spills capture rows from this span's result
        block, so the deferred tail reads stay span-consistent."""
        return {
            "lru_ids": np.fromiter(self._lru.keys(), np.int64,
                                   count=len(self._lru)),
            "lru_slots": np.fromiter(self._lru.values(), np.int64,
                                     count=len(self._lru)),
            "touched": np.asarray(self.touched_ids(), np.int64),
        }

    def checkpoint_rows(self, clients, tier: Optional[dict] = None
                        ) -> dict:
        """The crows_* payload under the tiered store (satellite fix:
        O(working set) device work per save). Drains the spill queue
        (the tail is then authoritative for every evicted id), gathers
        ONLY the resident rows from the device block — a padded-256
        slot gather bounded by the working set, never the touched
        population — and reads every evicted row straight from the
        host tail. Also records the LRU recency order + slot map
        (`lru_ids`/`lru_slots`) so a resume replays the exact eviction
        stream. `tier`: an earlier snapshot_tier() dict — the
        pipelined one-span-late save passes the boundary-time
        bookkeeping while `clients` is that boundary's block."""
        self.flush()
        if tier is None:
            tier = self.snapshot_tier()
        lru_ids = np.asarray(tier["lru_ids"], np.int64)
        lru_slots = np.asarray(tier["lru_slots"], np.int64)
        resident = set(int(c) for c in lru_ids)
        evicted = [int(c) for c in np.asarray(tier["touched"])
                   if int(c) not in resident]
        all_ids = np.sort(np.concatenate(
            [lru_ids, np.asarray(evicted, np.int64)])
            if len(lru_ids) or evicted else np.zeros((0,), np.int64))
        payload = {"ids": all_ids,
                   "lru_ids": lru_ids, "lru_slots": lru_slots}
        if self._init_weights is not None:
            payload["base_weights"] = self._init_weights
        device_rows: Dict[str, np.ndarray] = {}
        if len(lru_ids):
            padded = np.pad(lru_slots.astype(np.int32),
                            (0, (-len(lru_slots)) % 256), mode="edge")
            gidx = mh.globalize(self.mesh, P(), padded)
            for f in self.fields:
                block = getattr(clients, f)
                device_rows[f] = np.asarray(
                    mh.gather_host(block[gidx]))[:len(lru_ids)]
        # vectorized assembly — the touched population is the design
        # point (~1e6 ids), so the merge must be fancy-indexed numpy,
        # not a per-client Python loop, and the tail is read in ONE
        # bulk get per field (the lock is held only for that read,
        # not a per-client comprehension that would stall the writer)
        res_mask = np.isin(all_ids, lru_ids)
        pos_in_lru = {int(c): i for i, c in enumerate(lru_ids)}
        res_pos = np.fromiter(
            (pos_in_lru[int(c)] for c in all_ids[res_mask]),
            np.int64, count=int(res_mask.sum()))
        evicted_sel = all_ids[~res_mask]
        with self._lock:
            if len(evicted_sel):
                tail_rows = self._tail.get_many(evicted_sel)
                # the checkpoint payload must carry VERIFIED rows — a
                # corrupt tail row is quarantined here, not persisted
                self._verify_tail_bulk(evicted_sel, tail_rows)
            else:
                tail_rows = {f: np.zeros((0, self.D), np.float32)
                             for f in self.fields}
        empty = np.zeros((0,), np.float32)
        for name in STATE_FIELDS:
            if name not in self.fields:
                payload[name] = empty
                continue
            out = np.empty((len(all_ids), self.D), np.float32)
            if len(res_pos):
                out[res_mask] = device_rows[name][res_pos]
            out[~res_mask] = tail_rows[name]
            payload[name] = out
        return payload

    def load_rows(self, clients, rows: dict):
        """Rebuild the tiers from a crows_* checkpoint payload:
        resident rows scatter back into their recorded slots (the
        same eviction stream then replays), everything else lands in
        the host tail. A payload without lru_* keys — written by a
        state_tier=device run — restores with a COLD working set
        (all rows in the tail), which is still bit-exact: tier
        residency never changes row values. Returns the new device
        block."""
        import jax.numpy as jnp

        self._reset()
        ids = np.asarray(rows["ids"], np.int64).reshape(-1)
        self._ever = set(int(c) for c in ids)
        self._ever_sorted = None
        lru_ids = np.asarray(rows.get("lru_ids", ()),
                             np.int64).reshape(-1)
        lru_slots = np.asarray(rows.get("lru_slots", ()),
                               np.int64).reshape(-1)
        compatible = (len(lru_ids) == len(lru_slots)
                      and len(lru_ids) <= self.slots
                      and (len(lru_slots) == 0
                           or int(lru_slots.max()) < self.slots))
        if not compatible:
            # a resume under a different --state_working_set: cold
            # working set, rows all in the tail — values unchanged
            lru_ids = np.zeros((0,), np.int64)
            lru_slots = np.zeros((0,), np.int64)
        pos = {int(c): j for j, c in enumerate(ids)}
        field_rows = {name: np.asarray(rows.get(name, ()), np.float32)
                      for name in self.fields}
        # vectorized: the payload rows are in `ids` order, so the tail
        # entries are one mask + fancy-index per field — resume over a
        # million-client payload must not loop per row in Python
        tail_mask = ~np.isin(ids, lru_ids)
        if tail_mask.any():
            tail_ids = ids[tail_mask]
            tail_vals = {name: field_rows[name][tail_mask]
                         for name in self.fields}
            sums = {int(cid): {f: _row_crc(tail_vals[f][i])
                               for f in self.fields}
                    for i, cid in enumerate(tail_ids)}
            with self._lock:
                self._tail.put(tail_ids, tail_vals)
                self._sums.update(sums)
        for cid, slot in zip(lru_ids, lru_slots):
            self._lru[int(cid)] = int(slot)
        used = set(self._lru.values())
        self._free = deque(s for s in range(self.slots)
                           if s not in used)
        if len(lru_ids):
            gidx = jnp.asarray(lru_slots.astype(np.int32))
            new = clients
            for name in self.fields:
                data = np.stack([field_rows[name][pos[int(c)]]
                                 for c in lru_ids])
                field = getattr(new, name)
                placed = mh.globalize(self.mesh, P(), data)
                new = new._replace(
                    **{name: field.set_rows(gidx, placed)})
            clients = new
        return clients

    def import_dense(self, dense_rows: Dict[str, np.ndarray]):
        """Legacy dense checkpoint (client_* blocks) into the tiered
        store: every row differing from its init value goes to the
        host tail (a vectorized diff recovers the touched set the
        dense format never recorded), the working set starts cold.
        `dense_rows` maps tracked field -> host [rows, D] block."""
        self._reset()
        n = min(self.num_clients,
                *(dense_rows[f].shape[0] for f in self.fields))
        diff = np.zeros(n, bool)
        for f in self.fields:
            block = np.asarray(dense_rows[f][:n], np.float32)
            init = (self._init_weights if f == "weights"
                    and self._init_weights is not None
                    else np.zeros(self.D, np.float32))
            diff |= (block != init[None, :]).any(axis=1)
        touched = np.nonzero(diff)[0]
        if len(touched):
            vals = {f: np.asarray(dense_rows[f][touched], np.float32)
                    for f in self.fields}
            sums = {int(cid): {f: _row_crc(vals[f][i])
                               for f in self.fields}
                    for i, cid in enumerate(touched)}
            with self._lock:
                self._tail.put(touched, vals)
                self._sums.update(sums)
        self._ever = set(int(c) for c in touched)
        self._ever_sorted = None
        return [int(c) for c in touched]

    def set_init_weights(self, vec: Optional[np.ndarray]) -> None:
        """Rebase the init-weights row untouched topk_down clients
        restore from (load_state installs the checkpoint's saved
        base)."""
        if self.cfg.do_topk_down and vec is not None:
            self._init_weights = np.asarray(vec, np.float32)

    def touched_ids(self) -> np.ndarray:
        """Every client whose row may differ from init: residents plus
        the spilled tail (pending spills are already in the LRU-exit
        path — flush before reading for checkpoint purposes). Served
        from the incrementally-maintained `_ever` set (== the live
        lru ∪ pending ∪ tail union; see __init__), cached sorted —
        snapshot_tier() calls this at every pipelined span boundary,
        where re-sorting the touched population each time would stall
        staging."""
        if self._ever_sorted is None:
            self._ever_sorted = np.fromiter(
                sorted(self._ever), np.int64, count=len(self._ever))
        return self._ever_sorted

    def _reset(self) -> None:
        self.flush()
        self._lru.clear()
        self._free = deque(range(self.slots))
        self._ever = set()
        self._ever_sorted = None
        with self._lock:
            self._tail.clear()
            self._pending.clear()
            self._warm.clear()
            self._sums.clear()

    # ---------------- lifecycle ------------------------------------------
    def flush(self) -> None:
        """Block until every queued spill is committed to the tail
        (checkpoint payloads and crash paths call this); re-raises
        writer-side failures."""
        self._writer.drain()

    def close(self) -> None:
        self._writer.close()
        self._tail.close()

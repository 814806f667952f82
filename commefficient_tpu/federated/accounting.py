"""Per-client communication accounting — the headline observability
feature of the system (SURVEY.md §5; reference:
CommEfficient/fed_aggregator.py:170-299).

Semantics parity:
  * upload bytes per participating client per round: 4 bytes x
    mode-dependent float count (reference :291-299) — grad_size for
    uncompressed/true_topk/fedavg, k for local_topk, r*c for sketch.
    One deliberate divergence (ISSUE 6): a sketch table quantized for
    the wire (--sketch_table_dtype bf16/int8) is billed at the WIRE
    element size (Config.upload_bytes), not at f32 — the reference
    has no quantized transport to bill.
    The local_topk count stays the ANALYTIC k, exactly like the
    reference's; above ops/flat.py's TOPK_THRESHOLD_MIN_D the actual
    transmitted support is k within ~1% sampling noise — PLUS any
    threshold-tie widening (sampled_threshold_mask keeps every
    coordinate tied at the threshold, so a tie-heavy vector can
    transmit far more than k). The analytic number remains the billed
    one, but CommAccountant records the REALIZED nonzero count of each
    round's aggregate update next to it (realized_nonzeros /
    max_realized_nonzeros) so a blowout is visible rather than
    silently under-billed (download bytes are unaffected — they count
    actual changed weights via the bitset).
  * download bytes per participating client: 4 bytes x number of
    weights that changed since that client last participated
    (reference :239-289), with the same cheap path (single
    updated-since-init boolean when num_epochs <= 1 and whole-dataset
    batches, :171-177) and bounded-staleness clamp (deque maxlen =
    10/participation, :179-194 — under-counts clients stale for longer,
    with probability < e^-10 as the reference's comment derives).

TPU-first re-design of the expensive path: the reference keeps a deque
of FULL weight vectors (maxlen x D floats — 28 MB x maxlen for
ResNet9) and diffs against each participant's snapshot every round,
O(maxlen x D) host work. The information actually needed is only
*which coordinates changed each round*, and for the compressed modes
that set is k-sparse. So the device packs the round's change mask into
a D/32-word bitset (one small transfer), and the host keeps a deque of
bitsets (875 KB each for 7M params): a client stale for s rounds costs
one OR-reduction over s bitsets + popcount — exactly the
"disagrees with the client's snapshot" count, modulo coordinates that
changed and changed back to the identical float (which the reference
counts as unchanged; measure-zero in practice).
"""
from __future__ import annotations

from collections import deque
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from commefficient_tpu.config import Config
from commefficient_tpu.scopes import scope

DEQUE_MAXLEN_MULT = 10  # (reference fed_aggregator.py:21)

_POPCOUNT_TABLE = np.array([bin(i).count("1") for i in range(256)],
                           dtype=np.uint32)

try:  # fused C kernels (commefficient_tpu/native/accounting.c)
    from commefficient_tpu.native import native_accounting as _native
except ImportError:
    _native = None


def pack_change_bits(update: jax.Array) -> jax.Array:
    """Device-side: pack (update != 0) into uint32 words. Runs under
    jit; the host transfer is D/32 words instead of D floats.

    The packing arithmetic is f32: a dot of 16 {0,1} bits with
    [1, 2, ..., 2^15] is exact in f32 (sum < 2^16 < 2^24), and TPU
    multiplies/reduces floats natively while 32-bit integer
    multiply-accumulate is emulated scalar code (measured ~75 ms/round
    at D=6.6M for the all-uint32 formulation — it dominated the whole
    federated round; see PERF.md). One emulated shift+or per WORD
    (D/32 elements) remains."""
    d = update.shape[0]
    n_words = -(-d // 32)
    with scope("pack_change_bits"):
        bits = jnp.not_equal(update, 0.0)
        bits = jnp.pad(bits, (0, n_words * 32 - d))
        halves = bits.reshape(n_words, 2, 16).astype(jnp.float32)
        w16 = jnp.asarray(2.0, jnp.float32) ** jnp.arange(16)
        packed = halves @ w16                             # [n_words, 2]
        lo = packed[:, 0].astype(jnp.uint32)
        hi = packed[:, 1].astype(jnp.uint32)
        return lo | (hi << jnp.uint32(16))


# coordinates a row of `pack_change_bits_tiled`'s words covers
TILE_BITS = 32 * 128


def tiled_words(d: int) -> int:
    return -(-d // TILE_BITS) * 128


def pack_change_bits_tiled(update: jax.Array) -> jax.Array:
    """`pack_change_bits` for vectors of hundreds of millions of
    coordinates (Config.server_in_place), inside the round program:
    the same bits in another order. The vector is read as
    [rows, 32, 128] and bit j of word (row, lane) is coordinate
    row * 4096 + j * 128 + lane, so a word is assembled across
    sublanes, a full 128-lane register at a time, and no
    [D/32, 2, 16] operand exists (its minor dimension of 16 pads to
    128 lanes: 21 GB at D = 6.6e8). The host only ORs and counts
    these words (CommAccountant), which no order of bits changes;
    the tail past `d` is zero. Returns [tiled_words(d)] u32."""
    d = update.shape[0]
    rows = -(-d // TILE_BITS)
    with scope("pack_change_bits"):
        bits = jnp.not_equal(update, 0.0)
        bits = jnp.pad(bits, (0, rows * TILE_BITS - d))
        halves = bits.reshape(rows, 2, 16, 128).astype(jnp.float32)
        w16 = jnp.asarray(2.0, jnp.float32) ** jnp.arange(16)
        packed = (halves * w16[None, None, :, None]).sum(axis=2)
        lo = packed[:, 0].astype(jnp.uint32)
        hi = packed[:, 1].astype(jnp.uint32)
        return (lo | (hi << jnp.uint32(16))).reshape(-1)


def _popcount(words: np.ndarray) -> int:
    if _native is not None:
        return int(_native.popcount_words(
            np.ascontiguousarray(words).data))
    return int(_POPCOUNT_TABLE[words.view(np.uint8)].sum())


def _prefix_or_popcounts(changes, depths, n_words: int,
                         covered=()) -> dict:
    """{s: popcount(OR of the last s change bitsets)} for each needed
    staleness s in `depths`. The OR prefix must walk every depth up to
    max(depths) either way; the C fast path fuses OR+popcount in one
    64-bit pass per depth, while the numpy fallback popcounts ONLY at
    the requested depths (each popcount materializes a byte-table
    temporary, so popcounting every depth would dominate).

    `covered` (aligned with `changes`, or empty): True where a bitset
    is a subset of the one appended after it. The walk runs from the
    newest bitset back, so by the time it reaches a covered one the
    running OR already holds its successor and with it every bit of
    it: it is skipped, and the count at its depth is the count of the
    depth before. A dense update (uncompressed with momentum: once a
    coordinate has moved it moves every round) makes every bitset
    cover the one before it, and the walk is one bitset long however
    stale the client; at D = 6.6e8 a bitset is 82 MB."""
    depths = sorted(set(int(d) for d in depths))
    if not depths:
        return {}
    max_depth = depths[-1]
    if max_depth == 0:
        return {0: 0}
    n = len(changes)
    # depth d -> how many of the last d bitsets the walk has to OR
    keep = [i for i in range(n - max_depth, n)
            if not (covered and covered[i])]
    walked = {d: sum(1 for i in keep if i >= n - d) for d in depths}
    rows = [changes[i] for i in keep]
    counts = {0: 0}
    if _native is not None:
        # zero-copy: each deque entry's buffer is consumed directly
        bufs = [np.ascontiguousarray(np.asarray(c), np.uint32).data
                for c in rows]
        got = _native.prefix_or_popcounts(bufs, n_words, len(rows))
        counts.update({k: got[k] for k in set(walked.values())})
    else:
        acc = np.zeros(n_words, np.uint32)
        need = set(walked.values())
        for k in range(1, len(rows) + 1):
            acc |= rows[-k]
            if k in need:
                counts[k] = int(_POPCOUNT_TABLE[acc.view(np.uint8)].sum())
    return {d: counts[walked[d]] for d in depths}


class CommAccountant:
    def __init__(self, cfg: Config, num_clients: int,
                 frozen_count: int = 0):
        self.cfg = cfg
        self.num_clients = num_clients
        self.n_words = (tiled_words(cfg.grad_size) if cfg.server_in_place
                        else -(-cfg.grad_size // 32))
        # finetune-frozen coordinates transmit nothing in the dense-
        # upload modes (the reference's requires_grad=False params are
        # not in the flat vector at all); sketch tables and the top-k
        # budget keep their full size regardless
        self.upload_floats = cfg.upload_floats
        if frozen_count and cfg.mode in ("uncompressed", "true_topk",
                                         "fedavg"):
            self.upload_floats = cfg.grad_size - frozen_count
        # billed upload BYTES at the wire dtype (ISSUE 6 accounting
        # fix): a bf16/int8 sketch table must not be charged at f32
        # element size. Config.upload_bytes is the mode's Compressor
        # plugin answering at its realized wire dtype (ISSUE 19);
        # the frozen-count adjustment above overrides it for the
        # dense modes whose payload genuinely shrinks (those all
        # transmit f32, so bytes stay 4 x floats exactly as before).
        # These are the `up_bytes` the journal records (api.py ->
        # telemetry).
        self.upload_bytes = (4.0 * self.upload_floats
                             if frozen_count
                             and cfg.mode in ("uncompressed",
                                              "true_topk", "fedavg")
                             else float(cfg.upload_bytes))
        # local_topk blowout observability (module docstring: the
        # upload charge stays the ANALYTIC k): ops/flat.py's
        # sampled_threshold_mask can select MORE than k on threshold
        # ties, and above TOPK_THRESHOLD_MIN_D the count also carries
        # ~1% sampling noise. record_round therefore keeps the
        # REALIZED nonzero count of the round's aggregate update
        # (popcount of its change bitset, one lag behind like the
        # download math) next to the analytic per-client k, so a tie
        # blowout is visible — compare realized_nonzeros against
        # (surviving uploaders x k): the union of W k-sparse uploads
        # is at most W*k except when ties widen a client's support.
        self.realized_nonzeros: Optional[int] = None
        self.max_realized_nonzeros = 0
        # cheap path applies when every client re-downloads everything
        # changed since init (reference fed_aggregator.py:171-177)
        self.cheap = (cfg.num_epochs <= 1 and cfg.local_batch_size == -1)
        if self.cheap:
            self.updated_since_init = np.zeros(self.n_words, np.uint32)
        else:
            # expected gap between a client's COMPLETED rounds is
            # 1 / (sampling rate * survival rate): client dropout
            # lengthens absences, and an overflowed window would make
            # the stale clip below silently undercharge the
            # accumulated download a returning client owes
            participation = (cfg.num_workers / num_clients
                             * (1.0 - cfg.client_dropout))
            maxlen = int(DEQUE_MAXLEN_MULT / participation)
            self.changes: deque = deque([], maxlen=maxlen)
            # per bitset: is it a subset of the one appended after it
            # (_prefix_or_popcounts skips those); the newest is False
            self._covered: deque = deque([], maxlen=maxlen)
            self._empty: Optional[np.ndarray] = None
            # SPARSE staleness (ISSUE 9): a dense [num_clients] int64
            # vector made accountant state O(population). Staleness of
            # client c is `rounds_seen - last reset`, where the reset
            # round is stored only for clients that have ever
            # participated (never-seen clients default to reset 0 =
            # stale since the beginning, exactly the dense vector's
            # semantics) — O(clients-ever-seen) state and checkpoint.
            self.rounds_seen = 0
            self._last_reset: dict = {}

    def _append(self, words: np.ndarray) -> None:
        """A round's change bitset joins the window; the one before it
        is marked where the new one covers it, and then gives its
        memory back: the walk never reads a covered bitset and bitsets
        leave the window oldest first, so its successor outlives it
        and an empty set in its place (a subset of anything) changes
        no count, now or after a checkpoint. Only a dense bitset (over
        half the coordinates) is compared: two rounds' sparse top-k
        supports are never nested, and the check would cost them a
        pass for nothing."""
        if self.changes:
            dense = 2 * _popcount(words) > 32 * self.n_words
            if dense and not np.any(self.changes[-1] & ~words):
                self._covered[-1] = True
                if self._empty is None:
                    self._empty = np.zeros(self.n_words, np.uint32)
                    self._empty.setflags(write=False)
                self.changes[-1] = self._empty
        self.changes.append(words)
        self._covered.append(False)

    def _check_ids(self, participating: np.ndarray) -> None:
        """The dense stale vector this storage replaced bounds-checked
        ids implicitly via fancy indexing; the sparse map must do it
        explicitly or a caller bug books phantom clients that ride
        into every checkpoint (same guard as the tracker's
        _rows_for)."""
        if participating.size and (
                int(participating.min()) < 0
                or int(participating.max()) >= self.num_clients):
            raise ValueError(
                f"client id out of range for a {self.num_clients}-"
                f"client population: {participating}")

    def staleness(self, client_ids) -> np.ndarray:
        """Rounds since each client's last COMPLETED round (unclipped;
        the download math clips to the change-window length). Exposed
        because the dense `stale` vector is gone — staleness is now
        derived from the sparse reset map."""
        ids = np.asarray(client_ids, np.int64).reshape(-1)
        return np.array([self.rounds_seen - self._last_reset.get(int(c), 0)
                         for c in ids], np.int64)

    def record_round(self, participating: np.ndarray,
                     prev_changed_words: Optional[np.ndarray],
                     survivors: Optional[np.ndarray] = None):
        """Account one round. `prev_changed_words` is the packed change
        bitset of the PREVIOUS round's weight update (None on the first
        round — weights haven't changed since clients were initialized,
        so round 1 downloads are free, matching reference :258-261).

        `survivors`: optional [W] {0,1} mask aligned with
        `participating` (client dropout). A dropped client completed
        neither its download nor its upload, so it is charged NOTHING
        and its staleness keeps growing — it will pay the accumulated
        download the next round it actually finishes.

        ISSUE 17 narrows what the caller passes here: under value
        screening the mask is the ADMITTED set (screened == dropped ==
        not billed), and under a robust aggregator it is the
        CONTRIBUTOR set — a client every one of whose cells was
        trimmed out of the order statistics contributed nothing to the
        aggregate and is not billed upload bytes either. The mask
        producer changed; this method's contract did not.

        Returns (download_bytes, upload_bytes), each [W] COHORT-indexed
        — aligned slot-for-slot with `participating`, dropped slots
        charged 0.0. (Before ISSUE 9 these were [num_clients] vectors:
        two population-length allocations per round, the exact
        O(population) host cost the refactor removes. Every consumer
        only ever indexed participants or summed.)
        """
        participating = np.asarray(participating).reshape(-1)
        self._check_ids(participating)
        W = participating.shape[0]
        alive = (np.ones(W, bool) if survivors is None
                 else np.asarray(survivors).reshape(-1) > 0)
        completed = participating[alive]
        download = np.zeros(W)

        if self.cheap:
            if prev_changed_words is not None:
                self.updated_since_init |= np.asarray(prev_changed_words)
            download[alive] = 4.0 * _popcount(self.updated_since_init)
        else:
            if prev_changed_words is not None:
                self._append(np.asarray(prev_changed_words))
            if len(self.changes) and len(completed):
                stale = np.clip(self.staleness(completed), 0,
                                len(self.changes))
                # staleness values share one OR-reduction prefix walk
                counts = _prefix_or_popcounts(
                    self.changes, np.unique(stale), self.n_words,
                    self._covered)
                download[alive] = [4.0 * counts[int(s)] for s in stale]
            for c in completed:
                self._last_reset[int(c)] = self.rounds_seen
            self.rounds_seen += 1

        upload = np.zeros(W)
        upload[alive] = self.upload_bytes

        if self.cfg.mode == "local_topk" and prev_changed_words is not None:
            # realized support of the previous round's aggregate
            # update, recorded next to the analytic k (__init__ note)
            self.realized_nonzeros = _popcount(
                np.asarray(prev_changed_words))
            self.max_realized_nonzeros = max(self.max_realized_nonzeros,
                                             self.realized_nonzeros)
        return download, upload

    def advance_round(self, participating: np.ndarray,
                      prev_changed_words: Optional[np.ndarray],
                      survivors: Optional[np.ndarray] = None) -> None:
        """Advance the accountant's state for a round whose byte totals
        the caller doesn't want (FedModel.run_rounds(account=False)):
        the change deque and staleness bookkeeping move exactly as in
        record_round (dropped clients' staleness included), only the
        popcount work is skipped. Without this, the first accounted
        round after an unaccounted span would misattribute download
        bytes."""
        participating = np.asarray(participating).reshape(-1)
        self._check_ids(participating)
        if survivors is not None:
            participating = participating[
                np.asarray(survivors).reshape(-1) > 0]
        if self.cheap:
            if prev_changed_words is not None:
                self.updated_since_init |= np.asarray(prev_changed_words)
        else:
            if prev_changed_words is not None:
                self._append(np.asarray(prev_changed_words))
            for c in participating:
                self._last_reset[int(c)] = self.rounds_seen
            self.rounds_seen += 1

    # -- checkpoint round-trip (utils.checkpoint serializes this so
    #    resumed runs keep cumulative comm totals correct) -------------
    def state_dict(self) -> dict:
        state = {}
        if self.cheap:
            state["updated_since_init"] = self.updated_since_init.copy()
        else:
            # sparse staleness (ISSUE 9): O(clients-ever-seen) arrays,
            # not the dense [num_clients] vector — checkpoints stay
            # O(cohort) at million-client populations
            ids = np.array(sorted(self._last_reset), np.int64)
            state["stale_rounds"] = np.int64(self.rounds_seen)
            state["stale_ids"] = ids
            state["stale_at"] = np.array(
                [self._last_reset[int(c)] for c in ids], np.int64)
            state["changes"] = (np.stack(list(self.changes))
                                if len(self.changes)
                                else np.zeros((0, self.n_words), np.uint32))
        return state

    def load_state_dict(self, state: dict) -> None:
        if self.cheap:
            self.updated_since_init = np.asarray(
                state["updated_since_init"], np.uint32)
        else:
            if "stale_ids" in state:
                self.rounds_seen = int(np.asarray(state["stale_rounds"]))
                ids = np.asarray(state["stale_ids"], np.int64)
                at = np.asarray(state["stale_at"], np.int64)
                self._last_reset = {int(c): int(a)
                                    for c, a in zip(ids, at)}
            else:
                # legacy dense vector: recover an equivalent sparse
                # map. Absolute round counts beyond the change-window
                # clip never matter, so anchoring rounds_seen at the
                # vector's max staleness preserves every observable
                # charge (never-seen clients sat AT the max).
                stale = np.asarray(state["stale"], np.int64)
                self.rounds_seen = int(stale.max()) if stale.size else 0
                self._last_reset = {
                    int(c): int(self.rounds_seen - s)
                    for c, s in enumerate(stale)
                    if int(s) != self.rounds_seen}
            rows = np.asarray(state["changes"], np.uint32)
            if self.changes.maxlen is not None and \
                    len(rows) > self.changes.maxlen:
                # the checkpoint was written under a config with a
                # wider window (e.g. a higher client_dropout, which
                # isn't — deliberately — in the fingerprint): grow to
                # fit rather than silently dropping the oldest rows,
                # which would undercharge returning clients' downloads
                self.changes = deque([], maxlen=len(rows))
            self.changes.clear()
            self._covered = deque([], maxlen=self.changes.maxlen)
            for row in rows:
                self._append(row)

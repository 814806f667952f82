"""The federated round engine: one round = one jitted SPMD program.

This module is the TPU-native fusion of the reference's entire process
topology (reference: CommEfficient/fed_aggregator.py:213-335 `_call_train`
+ fed_worker.py:14-138 `worker_loop` + fed_aggregator.py:429-458
`FedOptimizer.step`). The reference needs three communication planes —
multiprocessing queues for batch dispatch, POSIX shared memory for PS
weights and per-client state, and a NCCL sum-reduce of the compressed
update (SURVEY.md §1). Here all three collapse into one `shard_map`
over the `clients` mesh axis:

  * batch dispatch        -> sharded batch arrays, P('clients')
  * shared-memory weights -> replicated ps_weights operand, P()
  * NCCL reduce           -> `lax.psum` of the compressed quantity

Per-client persistent state (errors/velocities/stale weights,
reference fed_aggregator.py:105-129) lives as RowBlocks: logically
[padded_population, D], stored [padded_population, T, 128] with
T = 8 * ceil(D / 1024) so that one client's row is a whole number of
the chip's (8, 128) tiles (zero padding behind D; below D = 1,024
whole lanes only, T = ceil(D / 128)), sharded
`P('clients', None, None)` across hosts. Since ISSUE 9
the participant-row motion happens OUTSIDE the jitted round: a
dedicated cohort-GATHER program pulls the sampled rows into a
[num_workers, D] CohortState before dispatch, and a SCATTER-BACK
program writes the updated rows after — so the three traced round
programs see only O(cohort) operands, never a population-shaped
buffer (graftaudit AU004 now hard-errors on one), and device traffic
per round is O(active) regardless of the population size. The
gather/scatter pair (SURVEY.md hard part #3) are the only two
programs allowed to touch the population blocks; they move each row
as one contiguous piece and convert the cohort between the tile form
and [num_workers, D] in one pass, so the round programs trace what
they always did.

True-top-k momentum factor masking of client velocities — broken in
the reference via an unset global (SURVEY.md §7.4 D6) — is just data
flow here: the server helper returns a mask, and the round engine
applies it to the participating rows in the same jitted program.
"""
from __future__ import annotations

import dataclasses
import operator
from functools import partial
from typing import Callable, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax, shard_map
from jax.lax import pcast
# not exported by jax 0.9.0 (the one pinned in pyproject.toml): the
# all_gather whose result is typed replicated, see its one use below
from jax._src.lax.parallel import all_gather_invariant
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from commefficient_tpu import compress
from commefficient_tpu.config import Config
from commefficient_tpu.federated import client as fclient
from commefficient_tpu.federated import server as fserver
from commefficient_tpu.ops.flat import masked_topk
from commefficient_tpu.scopes import scope
from commefficient_tpu.telemetry import metrics as tmetrics
from commefficient_tpu.telemetry.trace import TRACE


# ---------------------------------------------------------------------------
# the storage format of the per-client blocks. A float32 [rows, D]
# array is tiled (8, 128) over (client, coordinate) on the chip, so one
# client's row is one sublane of D/128 tiles that also hold seven other
# clients, and moving a row reads and writes eight. Stored
# [rows, T, 128] the tiled dimensions are the row's own: a row is T/8
# whole tiles in one piece. The two functions below are the whole
# format; everything that reads or writes block rows goes through them
# (or through RowBlock, which does), and the checkpoint on disk and
# the host tier's tables stay [rows, D].

LANES = 128
_ROW_QUANTUM = 8 * LANES


def lane_rows(D: int) -> int:
    """T: how many 128-lane rows one client's D coordinates take:
    whole (8, 128) tiles from D = 1,024 up. Below it whole lanes only:
    such a row is under one tile wherever it lies (the chip pads T to
    eight itself), and eight sublanes of zeros would be up to 64 times
    the state of a tiny model with a large population."""
    lanes = -(-int(D) // LANES)
    return lanes if D < _ROW_QUANTUM else -(-lanes // 8) * 8


def rows_to_tiles(rows):
    """[..., D] -> [..., T, 128], zero padding behind D (numpy in,
    numpy out; a jax array or tracer stays one)."""
    xp = jnp if isinstance(rows, jax.Array) else np
    D = rows.shape[-1]
    T = lane_rows(D)
    pad = [(0, 0)] * (rows.ndim - 1) + [(0, T * LANES - D)]
    return xp.pad(rows, pad).reshape(rows.shape[:-1] + (T, LANES))


def tiles_to_rows(tiles, D: int):
    """[..., T, 128] -> [..., D]: the inverse; the padding is cut,
    never read."""
    return tiles.reshape(tiles.shape[:-2] + (-1,))[..., :D]


def take_rows(tiles, ids, sharded: bool):
    """[rows, T, 128] tiles, [W] ids -> those rows, [W, T, 128].
    On one device a loop of W row copies (`dynamic_slice` out,
    `dynamic_update_slice` in: a row is one contiguous run of whole
    tiles, written in place), not XLA's `gather` op, which the chip's
    compiler lowers to hundreds of strided pieces with gigabytes of
    temporaries. The ids are distinct and in range (the sampler's
    contract). A `fori_loop` and not W unrolled slices concatenated:
    no compiler fuses a loop into what reads the cohort, so composed
    into one program (`round_full`) the round's arithmetic is fused,
    and rounded, as the round program alone fuses it.

    `sharded`: the tiles lie across a clients mesh, the rows change
    devices, and that is left to GSPMD's partitioning of the indexing
    (each shard gathers the rows it holds, masked, and the cohort is
    all-reduced): the same loop under `shard_map` with its own
    reduction deadlocks the CPU runtime's eight virtual devices in
    long unsynchronised loops (PERF.md, section 7). The branch goes
    once the loop is shown on a real mesh (ROADMAP S3)."""
    if sharded:
        return tiles[ids]

    def copy_row(i, out):
        row = lax.dynamic_slice_in_dim(tiles, ids[i], 1, axis=0)
        return lax.dynamic_update_slice_in_dim(out, row, i, axis=0)
    return lax.fori_loop(
        0, ids.shape[0], copy_row,
        jnp.zeros((ids.shape[0],) + tiles.shape[1:], tiles.dtype))


@partial(jax.jit, static_argnames=("D", "sharded"))
def _rows_at(tiles, ids, D: int, sharded: bool):
    # one program for the host's reads of a few rows (one compile,
    # where the three eager steps would be three)
    return tiles_to_rows(take_rows(tiles, ids, sharded), D)


@partial(jax.tree_util.register_dataclass,
         data_fields=["tiles"], meta_fields=["D"])
@dataclasses.dataclass(frozen=True)
class RowBlock:
    """One tracked per-client block: logically float32 [rows, D] (what
    `shape`, indexing and `np.asarray` say), stored as `tiles`
    [rows, T, 128] (see above). A pytree with the one leaf, so a
    ClientState of RowBlocks is donated, sharded and scanned like the
    arrays it replaced. Only the cohort-gather and scatter-back
    programs read `tiles` on the device; the methods here are the
    host's occasional access (checkpoints, the state tier, tests)."""
    tiles: jax.Array             # [rows, T, 128]
    D: int

    @classmethod
    def from_rows(cls, rows) -> "RowBlock":
        return cls(rows_to_tiles(rows), int(rows.shape[-1]))

    @property
    def shape(self):
        return (self.tiles.shape[0], self.D)

    ndim = 2

    @property
    def dtype(self):
        return self.tiles.dtype

    def __getitem__(self, idx):
        """Rows by a client id, a slice or a 1-D integer array of ids
        -> [..., D]."""
        if isinstance(idx, slice):
            return tiles_to_rows(self.tiles[idx], self.D)
        if np.ndim(idx) == 0:
            return tiles_to_rows(self.tiles[operator.index(idx)], self.D)
        ids = jnp.asarray(idx)
        if ids.ndim != 1 or not jnp.issubdtype(ids.dtype, jnp.integer):
            raise TypeError(
                "a RowBlock is read by a client id, a slice or a 1-D "
                f"integer array of ids, not {ids.dtype}{list(ids.shape)}")
        return _rows_at(self.tiles, ids, self.D,
                        len(self.tiles.sharding.device_set) > 1)

    def set_rows(self, ids, rows) -> "RowBlock":
        """A new block with `rows` ([len(ids), D]) written at `ids`."""
        return RowBlock(
            self.tiles.at[ids].set(rows_to_tiles(jnp.asarray(rows))),
            self.D)

    def __array__(self, dtype=None, copy=None):
        out = np.ascontiguousarray(
            tiles_to_rows(np.asarray(self.tiles), self.D))
        return out if dtype is None else out.astype(dtype)

    def is_deleted(self) -> bool:
        return self.tiles.is_deleted()


class ServerState(NamedTuple):
    """All PS-side mutable state (reference globals g_ps_weights /
    FedOptimizer.Vvelocity / .Verror, fed_aggregator.py:37-44,408-409)."""
    ps_weights: jax.Array        # [D] replicated
    Vvelocity: jax.Array         # [D] or [r, c]
    Verror: jax.Array            # [D] or [r, c]
    round_idx: jax.Array         # scalar int32


class ClientState(NamedTuple):
    """Per-client persistent state (reference shared-memory arrays at
    fed_aggregator.py:105-129): each tracked field a RowBlock,
    logically [padded_population, D] and stored in whole tiles
    [padded_population, T, 128], sharded over the mesh's clients axis
    (CLIENT_STATE_RULES). Fields are zero-size plain-array
    placeholders when the config doesn't need them.

    The jitted round NEVER takes this treedef as an operand: only the
    cohort-gather and scatter-back state-motion programs touch it
    (module docstring; graftaudit AU004 enforces the contract).

    Under `Config.state_tier=host` (ISSUE 11) the same treedef holds
    the bounded [working_set, ...] device block instead — rows are
    indexed by LRU slot, not client id, and the cold tail lives on
    the host (federated/statestore.py; client_state_rows picks the
    allocation size)."""
    # each a RowBlock [padded_population, D], or a plain [0]
    errors: "RowBlock | jax.Array"
    velocities: "RowBlock | jax.Array"
    weights: "RowBlock | jax.Array"


class CohortState(NamedTuple):
    """The gathered participant rows one round operates on —
    [num_workers, D] per tracked block, or a [num_workers] f32 dummy
    when the config doesn't track that block (the dummies keep the
    shard_map operand count static; they are never read).

    Produced by the cohort-gather program, consumed and returned
    (merged: dropped clients keep their gathered values) by the jitted
    round, written back by the scatter-back program. O(cohort) in every
    dimension — this treedef is what makes the round programs
    population-free. Plain [num_workers, D] arrays: the blocks' tile
    form ends inside the gather program and begins again inside the
    scatter program."""
    errors: jax.Array            # [num_workers, D] or [num_workers]
    velocities: jax.Array        # [num_workers, D] or [num_workers]
    weights: jax.Array           # [num_workers, D] or [num_workers]


# partition rules for the persistent client-state blocks — the
# match_partition_rules pattern (SNIPPETS.md [1], parallel/multihost)
# applied to the one treedef that matters at population scale: every
# live RowBlock's [padded_population, T, 128] tiles shard over the
# clients axis, placeholders/scalars replicate (no rule matches them).
CLIENT_STATE_RULES = (
    (r"\.(errors|velocities|weights)\.tiles$", P("clients", None, None)),
)


def client_state_specs(state) -> "ClientState":
    """PartitionSpec tree for a ClientState (or any same-treedef value)
    via CLIENT_STATE_RULES."""
    from commefficient_tpu.parallel import multihost as mh
    return mh.match_partition_rules(CLIENT_STATE_RULES, state)


class RoundBatch(NamedTuple):
    """One round's input: `num_workers` participating clients, each
    with a padded local batch (static shapes; SURVEY.md §7.3 #2).

    survivors: optional [num_workers] f32 {0,1} mask — 0 marks a
    sampled client that FAILED to complete the round (client dropout,
    Config.client_dropout / utils.faults). Dropped clients contribute
    nothing to the aggregate (survivor-count reweighting), their
    persistent state rows are written back bit-untouched, and a
    zero-survivor round leaves ps_weights/Vvelocity/Verror bit-exact
    (only round_idx advances, so the PRNG stream moves on). None —
    the default, and the only treedef dropout-free callers ever build
    — traces the original mask-free program: dropout machinery is
    free when disabled.

    work: optional [num_workers] f32 work fractions in (0, 1] —
    stragglers (Config.straggler_* / utils.faults). A client with
    fraction f completes only its first ceil(f * valid) examples
    (single-step modes) or ceil(f * steps) local SGD steps (fedavg);
    the aggregate weights by examples actually processed, so partial
    work doesn't bias the average (FedNova-style). None — the default
    — traces the work-free program (the surv-only dropout program or
    the original mask-free one), so straggler machinery is free when
    disabled. Below-cutoff fractions never appear here: the host
    (api._faults_for_round) degrades them to dropout and re-normalizes
    an all-ones work vector back to None.

    poison: optional [num_workers] f32 {0,1} — value-fault injection
    (ISSUE 16, Config.poison_rate / utils.faults.FaultSchedule.
    poison). A flagged client's TRANSMITTED update is corrupted
    device-side per Config.poison_kind after its local compute (its
    losses and persistent state rows stay clean — only the wire is
    poisoned). Presence of this operand selects the SCREENED program
    family: the host supplies it (zeros-filled) whenever screening or
    poisoning is configured, together with a survivors operand
    (ones-filled) and the `screen` flag below. None — the default —
    keeps the three original programs byte-identical.

    screen: optional scalar f32 {0,1} — whether the in-round
    admission screen APPLIES this round. Traced as data (not static
    config) so the finite-frontier rollback can force screening on
    for Config.rollback_screen_rounds without retracing, and a
    poison-only run (screen 0) lets the corruption through to the
    server state — the injection path the numeric-trip drill
    exercises. Rides if-and-only-if `poison` does."""
    client_ids: jax.Array        # [num_workers] int32
    data: Tuple[jax.Array, ...]  # pytree of [num_workers, B, ...]
    mask: jax.Array              # [num_workers, B] f32 validity
    survivors: Optional[jax.Array] = None  # [num_workers] f32 or None
    work: Optional[jax.Array] = None       # [num_workers] f32 or None
    poison: Optional[jax.Array] = None     # [num_workers] f32 or None
    screen: Optional[jax.Array] = None     # scalar f32 or None


class RoundMetrics(NamedTuple):
    """Per-round outputs that are NOT training state. `telemetry` is
    the fixed-shape named f32 vector of telemetry/metrics.METRIC_NAMES
    (zero-size when Config.telemetry is off, so the treedef per config
    is stable) — pure observation computed from values the round
    already produced; it feeds nothing back, so ServerState is
    bit-identical with telemetry on or off.

    admitted: the EFFECTIVE survivor mask after in-round admission
    ([num_workers] f32 {0,1}; screened-family programs only, None —
    no new leaves — everywhere else). host survivors x device admit:
    the mask accounting and the journal must see so a screened client
    is charged exactly like a dropped one (federated/api reads it
    back at commit/collect time).

    contributors (ISSUE 17, robust aggregators only): the subset of
    `admitted` whose values actually reached the robust aggregate —
    a client β-trimmed out of EVERY cell is admitted but contributes
    nothing, and the accountant must not bill upload bytes for it
    (screened==dropped bit-exactness extended to bytes). Identical
    to `admitted` for coord_median/norm_clip (every admitted client
    is order-statistic / clipped-sum material).

    agg_stats (robust aggregators only): [4] f32 —
    (clients trimmed per cell on average, clients norm-clipped,
    l2 residual of robust-vs-mean aggregate, contributing clients) —
    the per-round `aggregator` journal event's payload; the residual
    is the attack-severity gauge (large when the mean is being
    dragged somewhere the order statistics refuse to follow)."""
    losses: jax.Array            # [num_workers] per-client mean loss
    metrics: Tuple[jax.Array, ...]  # per-client means, each [num_workers]
    num_examples: jax.Array      # [num_workers]
    telemetry: jax.Array = None  # [telemetry.metrics.NUM_METRICS] or [0]
    admitted: Optional[jax.Array] = None  # [num_workers] f32 or None
    contributors: Optional[jax.Array] = None  # [num_workers] f32 or None
    agg_stats: Optional[jax.Array] = None     # [4] f32 or None
    # Config.server_in_place only: the round's packed change bits
    # (accounting.pack_change_bits of the applied update), [D/32] u32
    change_bits: Optional[jax.Array] = None


def init_server_state(cfg: Config, ps_weights: jax.Array,
                      mesh: Optional[Mesh] = None) -> ServerState:
    """Server-state pytree. With a mesh, every field is built as a
    GLOBAL replicated array — required in multi-controller runs, a
    no-op placement in single-process ones (parallel/multihost.py)."""
    shape = cfg.state_shape
    # a server that never reads its error keeps no D-sized zeros for
    # it where D is large (Config.server_in_place)
    err_shape = ((1,) if cfg.server_in_place and cfg.server_error_unused
                 else shape)
    if mesh is None:
        return ServerState(
            ps_weights=ps_weights.astype(jnp.float32),
            Vvelocity=jnp.zeros(shape, jnp.float32),
            Verror=jnp.zeros(err_shape, jnp.float32),
            round_idx=jnp.zeros((), jnp.int32),
        )
    from commefficient_tpu.parallel import multihost as mh
    return ServerState(
        ps_weights=mh.globalize(
            mesh, P(), jnp.asarray(ps_weights, jnp.float32)),
        Vvelocity=mh.zeros(mesh, P(), shape),
        Verror=mh.zeros(mesh, P(), err_shape),
        round_idx=mh.globalize(mesh, P(), jnp.zeros((), jnp.int32)),
    )


def init_client_state(cfg: Config, num_clients: int,
                      ps_weights: Optional[jax.Array] = None,
                      mesh: Optional[Mesh] = None) -> ClientState:
    """Allocate per-client state rows as RowBlocks (sharded over the
    mesh's clients axis when a mesh is given, since at 17K+ clients
    these arrays are the memory hazard — SURVEY.md §7.0).

    The row count is padded up to a multiple of the mesh axis so any
    num_clients shards (e.g. CIFAR's 10 natural clients on an 8-device
    mesh, which the reference handles with 8 GPU workers too). Padding
    rows are inert: the round engine gathers/scatters participant rows
    by client id, and ids are always < the true num_clients."""
    D = cfg.grad_size
    n = mesh.shape["clients"] if mesh is not None else 1
    rows = -(-num_clients // n) * n
    shape = (rows, lane_rows(D), LANES)

    if mesh is not None:
        from commefficient_tpu.parallel import multihost as mh

        # even the zero-size placeholders must be global arrays in a
        # multi-controller run (every jit operand needs a sharding on
        # the global mesh). One DISTINCT buffer per placeholder field:
        # donation (Config.donate_round_state) marks every leaf of the
        # client-state operand donatable, and XLA rejects the same
        # buffer donated twice.
        def empty():
            return mh.zeros(mesh, P(), (0,))

        def alloc():
            # global sharded allocation: shard-local zeros only — in a
            # multi-controller run no host ever materializes the full
            # block
            return RowBlock(
                mh.zeros(mesh, P("clients", None, None), shape), D)
    else:
        def empty():
            return jnp.zeros((0,), jnp.float32)

        def alloc():
            return RowBlock(jnp.zeros(shape, jnp.float32), D)

    errors = alloc() if _has_errors(cfg) else empty()
    velocities = alloc() if _has_velocities(cfg) else empty()
    if cfg.do_topk_down:
        assert ps_weights is not None
        if mesh is not None:
            # host-side conversion of the one base row; the tile is
            # materialized shard-locally
            base = rows_to_tiles(np.asarray(ps_weights))
            weights = RowBlock(mh.tile_rows(mesh, base, rows), D)
        else:
            weights = RowBlock(jnp.broadcast_to(
                rows_to_tiles(ps_weights), shape).copy(), D)
    else:
        weights = empty()
    return ClientState(errors, velocities, weights)


# which per-client [population, D] state blocks the config tracks —
# a plugin decision since ISSUE 19 (powersgd repurposes the velocity
# block for its warm-started Q factor); the classic plugins answer
# with the original error_type/local_momentum checks, so default
# allocations are unchanged
def _has_errors(cfg):
    return compress.get_compressor(cfg.mode).has_errors(cfg)


def _has_velocities(cfg):
    return compress.get_compressor(cfg.mode).has_velocities(cfg)


def client_state_rows(cfg: Config, num_clients: int) -> int:
    """How many client rows this config's ClientState blocks are
    allocated for: the full population under the default
    `state_tier=device`, or the bounded LRU working set under
    `state_tier=host` (ISSUE 11) — the blocks then hold only
    recently-active clients' rows while the cold tail lives on the
    host (federated/statestore.py), and the SAME gather/scatter
    state-motion programs move rows by device SLOT index instead of
    global client id. Every allocator of a ClientState (FedModel, the
    audit tiers, bench sweeps) routes through this so the audited
    gather/scatter programs are the dispatched ones."""
    if cfg.state_tier != "device":
        return int(cfg.state_working_set)
    return int(num_clients)


# ---------------------------------------------------------------------------
# program registry: the contract surface graftaudit (analysis/audit)
# traces and checks. Exactly three single-round programs exist per
# config — one per RoundBatch treedef — and the two dispatch entry
# points declare which of their inputs are DEAD after dispatch (safe
# to donate: the caller never reads them again).

# the three traced round programs, in the order the fault machinery
# grows them (ROADMAP invariant; analysis/runtime.assert_program_count
# proves the count dynamically, graftaudit walks each one statically).
# Since ISSUE 9 each round program operates on CohortState rows; the
# cohort-gather and scatter-back STATE-MOTION programs compile once
# per config alongside them (STATE_MOTION_PROGRAMS) and are the only
# programs whose operands may carry the population dimension.
PROGRAM_VARIANTS = ("mask_free", "dropout", "dropout_stragglers")

# ISSUE 16 screened family: when value-fault screening OR poison
# injection is configured the host supplies the survivor mask
# (ones-filled), a poison mask (zeros-filled), and the traced
# screen-enable scalar on EVERY dispatch, so exactly two programs
# exist — screened, and screened+stragglers — and the per-round
# decision "does the admission screen apply" is data, never a
# retrace. Default configs never build this treedef, keeping the
# three programs above byte-identical. ISSUE 17 extends the family
# (same two variant NAMES, config-keyed program bodies): byzantine
# adversaries ride the poison operand with an ATTACK transform
# instead of a corruption kind, robust aggregators replace the
# psum-mean tail with in-round order statistics over the gathered
# client tables, and under adaptive screening the screen scalar's
# VALUE is the live norm multiplier — all static config branches, so
# a PR-16 screened config still traces its exact pre-17 programs.
SCREENED_PROGRAM_VARIANTS = ("screened", "screened_stragglers")

# multiplier applied by the "scale" poison kind: large enough that a
# single poisoned client blows past any sane norm screen and (through
# error feedback) trips the finite/driver telemetry watch, small
# enough to stay finite in f32 so the norm screen (not just the
# finite screen) is what catches it.
POISON_SCALE = 2.0 ** 40

# the two state-motion programs every TrainRound dispatch brackets the
# round program with (compiled once; cache hits thereafter)
STATE_MOTION_PROGRAMS = ("gather", "scatter")

# per-round dispatch (TrainRound.__call__, three programs): the
# gathered CohortState is dead after the round program — the caller
# scatters the RETURNED rows — and the full ClientState is dead after
# scatter-back (the caller reassigns it from the result), so both are
# donated. ServerState is NOT: _call_train reads the previous
# ps_weights AFTER dispatch for the one-round-lagged accounting bitset,
# so donating it would hand accounting a deleted buffer. graftaudit's
# donation audit uses exactly these declarations.
ROUND_DEAD_ARGNUMS = (1,)      # round program: the CohortState operand
# ... and the ServerState too where the program packs the change bits
# itself (Config.server_in_place): nothing reads the old weights after
ROUND_DEAD_ARGNUMS_IN_PLACE = (0, 1)
SCATTER_DEAD_ARGNUMS = (0,)    # scatter-back: the full ClientState
# scanned-span dispatch (TrainRound.train_rounds): both state operands
# are dead — run_rounds computes the change bitset INSIDE the span and
# assigns all state from the result.
SPAN_DEAD_ARGNUMS = (0, 1)


def screened_family(cfg: Config) -> bool:
    """Whether `cfg` steady-state dispatches the SCREENED program
    family (in-round admission, value-fault injection, byzantine
    adversaries, or a robust aggregator configured — the latter two
    because attacks ride the poison operand and the robust reductions
    need the per-client transmits plus the admission mask, so both
    always take the per-client screened path). A default config can
    still dispatch screened programs transiently — the finite-frontier
    rollback force-enables screening for a bounded window — but its
    audited steady-state program set is the three defaults."""
    return (cfg.update_screen != "off" or cfg.poison_rate > 0
            or cfg.byzantine_rate > 0 or cfg.robust_aggregation)


def program_variants_for(cfg: Config) -> tuple:
    """The steady-state traced round-program set for `cfg` — the
    contract surface graftaudit/graftmesh walk and the program-count
    pins assert."""
    return (SCREENED_PROGRAM_VARIANTS if screened_family(cfg)
            else PROGRAM_VARIANTS)


def program_variant(batch: RoundBatch) -> str:
    """Which traced program `batch`'s treedef selects."""
    if batch.poison is not None:
        return ("screened_stragglers" if batch.work is not None
                else "screened")
    if batch.work is not None:
        return "dropout_stragglers"
    if batch.survivors is not None:
        return "dropout"
    return "mask_free"


def audit_batch_variants(batch: RoundBatch,
                         cfg: Optional[Config] = None) -> dict:
    """The RoundBatch treedef variants derived from one concrete
    batch — the exact programs a run with the config's fault machinery
    enabled dispatches: the three default programs, or (when `cfg` is
    given and selects the screened family) the two screened ones.
    Survivor/work/poison operands are inert values (all-survive,
    half-work, poison-nobody, screen-on) chosen only to pin the
    treedef; graftaudit traces each variant abstractly so the values
    never execute."""
    ones = jnp.ones(batch.client_ids.shape[0], jnp.float32)
    if cfg is not None and screened_family(cfg):
        zeros = jnp.zeros_like(ones)
        on = jnp.ones((), jnp.float32)
        return {
            "screened": batch._replace(
                survivors=ones, work=None, poison=zeros, screen=on),
            "screened_stragglers": batch._replace(
                survivors=ones, work=ones * 0.5, poison=zeros,
                screen=on),
        }
    return {
        "mask_free": batch._replace(survivors=None, work=None,
                                    poison=None, screen=None),
        "dropout": batch._replace(survivors=ones, work=None,
                                  poison=None, screen=None),
        "dropout_stragglers": batch._replace(survivors=ones,
                                             work=ones * 0.5,
                                             poison=None, screen=None),
    }


def stack_batch_for_span(batch: RoundBatch, n_rounds: int) -> RoundBatch:
    """A scanned-span RoundBatch from one single-round batch: every
    field gains a leading [n_rounds] axis carrying the same round
    repeated — the treedef `train_rounds` dispatches. Audit hook
    (graftaudit/graftmesh trace the span program through this;
    FedModel.trace_round_programs(include_span=True) is the
    real-workload surface): values never execute, only the
    shapes/treedef matter."""
    def stack(x):
        return None if x is None else jnp.stack([x] * n_rounds)
    return RoundBatch(
        stack(batch.client_ids),
        jax.tree.map(stack, batch.data),
        stack(batch.mask),
        stack(batch.survivors),
        stack(batch.work),
        stack(batch.poison),
        stack(batch.screen))


def make_round_fns(loss_fn: fclient.LossFn, unravel: Callable,
                   cfg: Config, mesh: Mesh, grad_mask=None):
    """Build the jitted (train-round, eval) pair. Thin wrapper over the
    split factories below, kept for callers that want both from one
    loss function."""
    return (make_train_fn(loss_fn, unravel, cfg, mesh, grad_mask),
            make_eval_fn(loss_fn, unravel, cfg, mesh))


def make_train_fn(loss_fn: fclient.LossFn, unravel: Callable,
                  cfg: Config, mesh: Mesh, grad_mask=None):
    """Build the jitted train-round function.

    loss_fn(params_pytree, batch_tuple, mask) -> (loss, metrics_tuple)
    is the workload callback — the API contract preserved from the
    reference (SURVEY.md §3.5): FedModel(model, compute_loss, args).

    grad_mask: optional [D] f32 mask multiplied into every client
    gradient *before* compression — frozen (finetune-transferred)
    coordinates are zeroed at the source, so they consume no k-budget
    or sketch capacity. This matches the reference's freezing
    semantics, where requires_grad=False params never produce
    gradients at all (cv_train.py:377-384).
    """
    cfg.validate()
    # the fused path produces a dense shard gradient sum; in sketch
    # mode the shared aggregation tail must therefore be the one to
    # encode it. Today fused_client_backward's gate is a strict subset
    # of defer_sketch_encode's — this assert keeps that implication
    # from silently breaking if either gate gains a condition.
    if cfg.fused_client_backward and cfg.mode == "sketch":
        assert cfg.defer_sketch_encode, (
            "fused_client_backward requires defer_sketch_encode in "
            "sketch mode (dense shard sum must be encoded in the "
            "shared tail)")
    if fclient.is_cohort_loss(loss_fn) and not cfg.fused_client_backward:
        raise ValueError(
            "this model's loss takes a whole cohort at once "
            "(client.is_cohort_loss) and trains only through the fused "
            "backward: mode sketch, uncompressed or true_topk with no "
            "per-client state, clipping or microbatching "
            "(Config.fused_client_backward)")
    flat_grad = fclient.make_flat_grad_fn(
        loss_fn, unravel,
        compute_dtype=jnp.bfloat16 if cfg.do_bf16 else None)
    flat_loss = (fclient.make_flat_loss_fn(
        loss_fn, unravel,
        compute_dtype=jnp.bfloat16 if cfg.do_bf16 else None)
        if cfg.fused_client_backward else None)
    if grad_mask is not None:
        grad_mask = jnp.asarray(grad_mask, jnp.float32)
    # clients sharded over the `clients` axis only — further axes
    # (tensor-parallel `model`) don't divide the client population
    n_shards = mesh.shape["clients"]
    # the mode's Compressor plugin (ISSUE 19) — static config,
    # resolved once per traced-program family
    comp = compress.get_compressor(cfg.mode)

    # ---------------- per-shard client phase ----------------------------
    def shard_train(ps_weights, data, mask, err_rows, vel_rows, w_rows,
                    keys, lr, surv=None, work=None, pois=None,
                    screen=None):
        """Runs on one shard: simulate W = num_workers/n_shards clients
        (vmap), locally sum their compressed updates, psum across the
        clients axis (the reference's per-GPU client loop
        fed_worker.py:60-131 + NCCL reduce :138).

        surv: optional [W_shard] f32 survivor mask — a dropped client's
        transmit and example count are zeroed BEFORE the local sum, so
        the psum'd aggregate and its divide-by-total reweighting see
        survivors only. Its per-client loss/metric rows are still
        reported (simulation diagnostics), but num_examples is zeroed
        so count-weighted consumers exclude it.

        work: optional [W_shard] f32 work fractions (stragglers). For
        the single-local-step modes the fraction truncates the
        client's VALIDITY MASK to its first ceil(f * valid) examples
        before any compute — its mean gradient, its example count,
        and therefore its weight in the psum'd aggregate all reflect
        examples actually processed (the divide-by-total below is
        then exactly the FedNova-style processed-example reweighting).
        For fedavg the fraction is a completed-steps budget applied
        inside fedavg_step instead (truncating the dataset would
        change WHICH examples every epoch sees, not how far local
        training got).

        pois/screen (ISSUE 16, screened family only — ride together):
        pois is the [W_shard] f32 {0,1} value-fault mask; a flagged
        client's TRANSMIT is corrupted per Config.poison_kind after
        its local compute, so losses/metrics/state rows stay clean.
        screen is the traced scalar admission flag: when > 0 the
        per-client admit mask (finite check over every transmit leaf,
        plus the cohort-median norm-outlier check under
        update_screen=norm) multiplies into the survivor mask BEFORE
        aggregation — a screened client takes the dropped-client path
        exactly. When 0 the admit mask is computed but NOT applied,
        so injected corruption reaches the server (the rollback
        drill's trip path). NaN-safety: screened-family aggregation
        zeroes excluded clients with `where`, never multiplication
        (NaN * 0 is NaN)."""
        # Cast the replicated weights to shard-varying before any
        # jax.grad: differentiating w.r.t. an *unvarying* operand under
        # shard_map makes JAX psum the cotangent across shards (correct
        # for grad-through-shard_map, wrong here — each client needs its
        # own local gradient, not the cross-client sum).
        ps_weights = pcast(ps_weights, "clients", to="varying")

        if work is not None and not comp.local_sgd:
            # completed-examples budget: keep each client's first
            # ceil(f * valid) valid examples (cumsum walks valid
            # examples in order, so padding rows stay excluded and a
            # straggler's partial batch is a prefix — the examples it
            # got through before the deadline)
            def budget(m, f):
                kept = jnp.cumsum(m) <= jnp.ceil(f * m.sum())
                return m * kept.astype(m.dtype)
            mask = jax.vmap(budget)(mask, work)

        def one_client(cdata, cmask, err, vel, w_stale, key, cwork=None):
            if cfg.do_topk_down:
                # download compression: client only receives the top-k
                # of its weight staleness gap (fed_worker.py:232-247);
                # down_k decouples the download budget from the
                # upload/server k (Config.down_k)
                diff = ps_weights - w_stale
                weights = w_stale + masked_topk(diff,
                                                k=cfg.down_k or cfg.k)
            else:
                weights = ps_weights

            if comp.local_sgd:
                res = fclient.fedavg_step(
                    flat_grad, weights, cdata, cmask, cfg, lr, key,
                    grad_mask=grad_mask, work=cwork)
            else:
                res = fclient.local_step(
                    flat_grad, weights, cdata, cmask, err, vel, cfg, key,
                    grad_mask=grad_mask)
            new_w = (weights if cfg.do_topk_down
                     else jnp.zeros_like(cmask, shape=()))
            return res, new_w

        # only the client-compute step branches; the encode/psum
        # aggregation tail below is shared, so the fused and
        # per-client paths cannot drift apart. The screened family
        # needs per-client transmits (poison lands on the wire, the
        # admit mask inspects it), so it always takes the per-client
        # path even on fused-eligible configs.
        if cfg.fused_client_backward and pois is None:
            # one backward for the whole shard (gate guarantees
            # equality with the per-client path — Config property and
            # fclient.fused_shard_grads docstrings); survivors weight
            # each client's term of the fused objective, so dropped
            # clients contribute exactly nothing to the shard gradient
            local_sum, losses, metrics, counts = fclient.fused_shard_grads(
                flat_loss, ps_weights, data, mask, cfg,
                grad_mask=grad_mask, survivors=surv)
            dummy = jnp.zeros_like(mask, shape=mask.shape[:1])
            new_err = new_vel = new_w_rows = dummy
        else:
            if work is not None and comp.local_sgd:
                results, new_w_rows = jax.vmap(one_client)(
                    data, mask, err_rows, vel_rows, w_rows, keys, work)
            else:
                results, new_w_rows = jax.vmap(one_client)(
                    data, mask, err_rows, vel_rows, w_rows, keys)
            if pois is not None:
                # ---- screened family (ISSUE 16 / ISSUE 17) ----
                # fault injection first: corrupt flagged clients'
                # transmits. With an all-zero mask every `where`
                # passes the clean value through bit-exactly, so a
                # screened run without live poison computes the
                # identical wire values. Under Config.byzantine_rate
                # the SAME operand carries adversary flags instead
                # (validate() keeps the two mutually exclusive) and
                # the transform is the scripted ATTACK — a static
                # branch, so PR-16 screened programs are untouched.
                def corrupt(t):
                    flag = pois.reshape(
                        pois.shape + (1,) * (t.ndim - 1)) > 0
                    if cfg.poison_kind == "scale":
                        return t * jnp.where(
                            flag, jnp.asarray(POISON_SCALE, t.dtype),
                            jnp.ones((), t.dtype))
                    bad = (jnp.inf if cfg.poison_kind == "inf"
                           else jnp.nan)
                    return jnp.where(flag, jnp.asarray(bad, t.dtype), t)

                def attack(trans):
                    """Byzantine adversary transform (ISSUE 17):
                    flagged clients REPLACE their transmit per
                    Config.attack. sign_flip/scaled are per-client
                    (gradient reversal / magnitude domination — both
                    caught by a norm screen); colluding submits ONE
                    coordinated crafted update — the negated honest
                    mean direction at a 0.9 margin UNDER the norm
                    screen's admission threshold (mult x cohort
                    median; high-norm attackers can only push the
                    cohort median above the honest median, so
                    0.9*mult*med_honest <= mult*med_cohort and the
                    screen provably admits it): finite, norm-
                    plausible, and maximally damaging — the class
                    admission screening provably cannot catch, the
                    negative control that justifies the robust
                    aggregators. little_is_
                    enough stays inside one honest standard deviation
                    per coordinate (Baruch et al.) — mild per-cell,
                    damaging in aggregate. The honest-cohort stats
                    are computed over the all_gathered per-client
                    tables, so every shard crafts the identical
                    update."""
                    leaves, treedef = jax.tree.flatten(trans)
                    W = leaves[0].shape[0]
                    V = jnp.concatenate(
                        [t.reshape(W, -1).astype(jnp.float32)
                         for t in leaves], axis=1)
                    if cfg.attack == "sign_flip":
                        A = -V
                    elif cfg.attack == "scaled":
                        A = V * jnp.float32(100.0)
                    else:
                        allV = jax.lax.all_gather(
                            V, "clients").reshape(-1, V.shape[1])
                        allF = jax.lax.all_gather(
                            pois, "clients").reshape(-1) > 0
                        allS = jax.lax.all_gather(
                            surv, "clients").reshape(-1) > 0
                        honest = ((~allF) & allS
                                  & jnp.isfinite(allV).all(axis=1))
                        nh = jnp.maximum(honest.sum(), 1)
                        hmean = jnp.where(
                            honest[:, None], allV, 0.0).sum(0) / nh
                        if cfg.attack == "little_is_enough":
                            hvar = jnp.where(
                                honest[:, None],
                                jnp.square(allV - hmean[None, :]),
                                0.0).sum(0) / nh
                            crafted = hmean - jnp.sqrt(hvar)
                        else:  # colluding
                            hnorm = jnp.sqrt(jnp.square(allV).sum(1))
                            med = jnp.nanmedian(
                                jnp.where(honest, hnorm, jnp.nan))
                            med = jnp.where(honest.sum() > 0, med,
                                            jnp.float32(1.0))
                            # the admission envelope the adversary
                            # provably fits under (the screen's own
                            # mult expression; >= 1 keeps the attack
                            # meaningful when screening is off)
                            amult = jnp.maximum(
                                (screen if cfg.adaptive_screen
                                 else jnp.float32(
                                     cfg.screen_norm_mult)),
                                jnp.float32(1.0))
                            d = -hmean
                            crafted = d * (
                                jnp.float32(0.9) * amult * med
                                / jnp.maximum(
                                    jnp.sqrt(jnp.square(d).sum()),
                                    jnp.float32(1e-12)))
                        A = jnp.broadcast_to(crafted[None, :], V.shape)
                    out_flat = jnp.where(pois[:, None] > 0, A, V)
                    outs, off = [], 0
                    for t in leaves:
                        n = t[0].size
                        outs.append(out_flat[:, off:off + n].reshape(
                            t.shape).astype(t.dtype))
                        off += n
                    return jax.tree.unflatten(treedef, outs)

                if cfg.byzantine_rate > 0:
                    tx = attack(results.transmit)
                else:
                    tx = jax.tree.map(corrupt, results.transmit)

                # admission screen: per-client finite bit over every
                # transmit leaf ...
                leaves = jax.tree.leaves(tx)
                ok = None
                for t in leaves:
                    fin_t = jnp.isfinite(t).reshape(
                        t.shape[0], -1).all(axis=1)
                    ok = fin_t if ok is None else ok & fin_t
                if cfg.update_screen == "norm":
                    # ... plus the norm-outlier check: update l2
                    # against the COHORT median (all_gather across the
                    # clients axis so every shard sees the same
                    # median). Only surviving, finite, nonzero-l2
                    # clients are eligible median material; a round
                    # with no eligible clients admits everyone rather
                    # than comparing against NaN.
                    l2sq = None
                    for t in leaves:
                        s = jnp.square(t.astype(jnp.float32)).reshape(
                            t.shape[0], -1).sum(axis=1)
                        l2sq = s if l2sq is None else l2sq + s
                    l2 = jnp.sqrt(l2sq)
                    all_l2 = jax.lax.all_gather(
                        l2, "clients").reshape(-1)
                    all_surv = jax.lax.all_gather(
                        surv, "clients").reshape(-1)
                    elig = ((all_surv > 0) & jnp.isfinite(all_l2)
                            & (all_l2 > 0))
                    med = jnp.nanmedian(
                        jnp.where(elig, all_l2, jnp.nan))
                    # adaptive screening (ISSUE 17): the screen
                    # operand's VALUE is the live norm multiplier —
                    # the AdaptiveScreenController's plan-journaled
                    # adjustments reach the traced program as data,
                    # never a retrace. Static branch: non-adaptive
                    # configs trace the exact PR-16 constant.
                    mult = (screen if cfg.adaptive_screen
                            else cfg.screen_norm_mult)
                    norm_ok = jnp.where(
                        elig.sum() > 0, l2 <= mult * med, True)
                    ok = ok & norm_ok
                # the traced enable flag: screen off -> admit mask
                # computed but not applied (corruption flows through
                # to the server state — the trip-drill injection path)
                admit = jnp.where(screen > 0,
                                  ok.astype(jnp.float32), 1.0)
                surv_eff = surv * admit
                counts = results.num_examples * surv_eff
                admitted = surv_eff
                if cfg.robust_aggregation:
                    # ---- robust cross-client reduction (ISSUE 17) --
                    # Order statistics over the gathered per-client
                    # tables replace the psum-mean: per-cell
                    # coordinate-median / β-trimmed-mean, or
                    # norm-clipping-to-cohort-median. Computed in
                    # AGGREGATION SPACE — in sketch mode each client's
                    # transmit is encoded (and wire-quantized)
                    # individually first, so the reduction runs over
                    # [N, r, c] sketch tables exactly as FetchSGD's
                    # linearity suggests; the deferred shard-sum
                    # encode below is bypassed (an order statistic
                    # does not distribute over the sum). Screened or
                    # dropped clients are excluded per cell via
                    # `where` masks (zero-survivor safe, NaN-safe);
                    # ranks are taken on the per-client MEAN updates
                    # (example weights normalize out) while the kept
                    # aggregate stays example-weighted, preserving
                    # the FedNova work-reweighting. (trimmed_mean
                    # with trim_beta == 0.0 never reaches this block:
                    # Config.robust_aggregation strength-reduces it
                    # to the plain mean program, which is the only
                    # way to stay bit-identical under the deferred
                    # shard-sum encode below.)
                    txa = tx
                    with scope("encode"):
                        if cfg.defer_sketch_encode:
                            txa = jax.vmap(
                                fserver.args2sketch(cfg).encode)(txa)
                        if (cfg.mode == "sketch"
                                and cfg.sketch_table_dtype != "f32"):
                            from commefficient_tpu.ops.quant import (
                                wire_roundtrip,
                            )
                            txa = wire_roundtrip(
                                txa, cfg.sketch_table_dtype)
                    leaves_a, treedef_a = jax.tree.flatten(txa)
                    Wl = leaves_a[0].shape[0]
                    V = jnp.concatenate(
                        [t.reshape(Wl, -1).astype(jnp.float32)
                         for t in leaves_a], axis=1)
                    # the INVARIANT all_gather: its result is typed
                    # replicated over `clients`, which is what lets
                    # shard_map's check_vma accept the P() out_specs
                    # of the aggregate and its stats below
                    allV = all_gather_invariant(
                        V, "clients").reshape(-1, V.shape[1])
                    n_w = all_gather_invariant(
                        counts, "clients").reshape(-1)
                    adm = all_gather_invariant(
                        surv_eff, "clients").reshape(-1) > 0
                    # per-cell eligibility: admitted AND finite (a
                    # screen-off round may admit NaN/Inf transmits;
                    # order statistics must stay well-defined)
                    E = adm[:, None] & jnp.isfinite(allV)
                    wcol = n_w[:, None]
                    total_w = n_w.sum()
                    # per-client mean updates: the rank/norm material
                    U = allV / jnp.maximum(n_w, 1.0)[:, None]
                    mean_agg = (jnp.where(E, allV, 0.0).sum(0)
                                / jnp.maximum(total_w, 1.0))
                    n_trim = n_clip = jnp.float32(0.0)
                    keep = E
                    if cfg.aggregator == "coord_median":
                        med = jnp.nanmedian(
                            jnp.where(E, U, jnp.nan), axis=0)
                        agg = jnp.where(E.any(axis=0), med, 0.0)
                    elif cfg.aggregator == "trimmed_mean":
                        vals = jnp.where(E, U, jnp.inf)
                        order = jnp.argsort(vals, axis=0)
                        ranks = jnp.argsort(order, axis=0)
                        n_e = E.sum(axis=0)
                        # trim floor(β·n_e) per side, clamped so at
                        # least one value survives per nonempty cell
                        m = jnp.minimum(
                            jnp.floor(cfg.trim_beta
                                      * n_e).astype(jnp.int32),
                            jnp.maximum(n_e - 1, 0) // 2)
                        keep = (E & (ranks >= m[None, :])
                                & (ranks < (n_e - m)[None, :]))
                        ksum = jnp.where(keep, wcol, 0.0).sum(0)
                        agg = (jnp.where(keep, allV, 0.0).sum(0)
                               / jnp.maximum(ksum, 1.0))
                        n_trim = (jnp.where(E & ~keep, 1.0, 0.0).sum()
                                  / jnp.float32(V.shape[1]))
                    else:  # norm_clip
                        l2u = jnp.sqrt(
                            jnp.where(E, jnp.square(U), 0.0).sum(1))
                        elign = adm & (l2u > 0) & jnp.isfinite(l2u)
                        medn = jnp.nanmedian(
                            jnp.where(elign, l2u, jnp.nan))
                        clip = jnp.where(
                            elign & (l2u > medn),
                            medn / jnp.maximum(l2u,
                                               jnp.float32(1e-30)),
                            jnp.float32(1.0))
                        n_clip = (clip < 1.0).sum().astype(jnp.float32)
                        agg = (jnp.where(E, allV * clip[:, None],
                                         0.0).sum(0)
                               / jnp.maximum(total_w, 1.0))
                    resid = jnp.sqrt(jnp.square(agg - mean_agg).sum())
                    contrib_all = (adm & keep.any(axis=1)).astype(
                        jnp.float32)
                    contrib = jax.lax.dynamic_slice_in_dim(
                        contrib_all,
                        jax.lax.axis_index("clients") * Wl, Wl)
                    agg_stats = jnp.stack(
                        [n_trim, n_clip, resid, contrib_all.sum()])
                    outs, off = [], 0
                    for t in leaves_a:
                        n = t[0].size
                        outs.append(agg[off:off + n].reshape(
                            t.shape[1:]).astype(t.dtype))
                        off += n
                    robust_tx = jax.tree.unflatten(treedef_a, outs)
                    local_sum = None
                else:
                    # `where`, NOT multiplication: a poisoned excluded
                    # client's NaN/Inf must become an exact zero in
                    # the local sum (NaN * 0 is NaN) — this is also
                    # what makes a screened client bit-identical to a
                    # dropped one
                    with scope("aggregate"):
                        local_sum = jax.tree.map(
                            lambda t: jnp.where(
                                surv_eff.reshape(
                                    surv_eff.shape
                                    + (1,) * (t.ndim - 1)) > 0,
                                t, jnp.zeros_like(t)).sum(axis=0),
                            tx)
            elif surv is not None:
                # zero dropped clients' uploads BEFORE the local sum —
                # the psum'd aggregate and the divide-by-total see
                # survivors only (survivor-count reweighting)
                with scope("aggregate"):
                    local_sum = jax.tree.map(
                        lambda t: (t * surv.reshape(
                            surv.shape
                            + (1,) * (t.ndim - 1))).sum(axis=0),
                        results.transmit)
                    counts = results.num_examples * surv
            else:
                with scope("aggregate"):
                    local_sum = jax.tree.map(
                        lambda t: t.sum(axis=0), results.transmit)
                counts = results.num_examples
            losses, metrics = results.loss, results.metrics
            new_err, new_vel = results.error, results.velocity

        if pois is not None and cfg.robust_aggregation:
            # robust aggregate (ISSUE 17): already encoded,
            # quantized, normalized and replicated (a pure function
            # of the all_gathered tables — every shard computed the
            # identical value, so no psum is needed); `total` still
            # reports the admitted example mass for the round_step
            # alive gate and telemetry parity
            with scope("aggregate"):
                total = jax.lax.psum(counts.sum(), "clients")
            out = (robust_tx, total, new_err, new_vel, new_w_rows,
                   losses, metrics, counts, admitted, contrib,
                   agg_stats)
            return out
        if cfg.defer_sketch_encode:
            # sketch linearity: encode the per-shard client sum ONCE
            # (clients returned dense gradients; see Config property
            # docstring). The psum below then moves the [r, c] table —
            # upload compression on the wire, exactly like the
            # reference's NCCL reduce of sketch tables.
            with scope("encode"):
                local_sum = fserver.args2sketch(cfg).encode(local_sum)
        if cfg.mode == "sketch" and cfg.sketch_table_dtype != "f32":
            # quantized sketch transport (--sketch_table_dtype): the
            # shard's client-sum table rides the wire at bf16/int8 —
            # quantize at the sender, dequantize before the
            # aggregation/decode. wire_roundtrip is the IDENTITY for
            # f32, and the branch itself is static config, so the
            # default traces the exact pre-quantization program. The
            # rounding noise lands in the server's virtual error
            # accumulator like any other compression noise
            # (ops/quant.py); the accountant bills the wire
            # bytes (Config.upload_bytes).
            from commefficient_tpu.ops.quant import wire_roundtrip
            with scope("encode"):
                local_sum = wire_roundtrip(local_sum,
                                           cfg.sketch_table_dtype)
        with scope("aggregate"):
            transmit = jax.lax.psum(local_sum, "clients")
            total = jax.lax.psum(counts.sum(), "clients")
        out = (transmit, total, new_err, new_vel, new_w_rows,
               losses, metrics, counts)
        if pois is not None:
            # screened programs additionally report the effective
            # (post-admission) survivor mask so the host accounting
            # and journal charge screened clients as dropped ones
            out = out + (admitted,)
        return out

    state_spec = P("clients")

    shard_train_mapped = shard_map(
        shard_train, mesh=mesh,
        in_specs=(P(), P("clients"), P("clients"), P("clients"),
                  P("clients"), P("clients"), P("clients"), P()),
        out_specs=(P(), P(), state_spec, state_spec, state_spec,
                   P("clients"), P("clients"), P("clients")),
        # manual only over `clients`; any further mesh axes (`model`
        # for tensor parallelism) stay AUTO — GSPMD partitions the
        # client computation over them, steered by the workload's
        # with_sharding_constraint calls (parallel/tp.py)
        axis_names=frozenset({"clients"}),
    )

    # dropout variant: same program plus a [W] survivor-mask operand,
    # sharded like every other per-client row. Built as a SEPARATE
    # mapped fn (rather than a ones-mask default operand) so the
    # dropout-free treedef traces the original mask-free program —
    # client_dropout=0.0 stays bit-identical to a build without the
    # feature.
    shard_train_surv_mapped = shard_map(
        shard_train, mesh=mesh,
        in_specs=(P(), P("clients"), P("clients"), P("clients"),
                  P("clients"), P("clients"), P("clients"), P(),
                  P("clients")),
        out_specs=(P(), P(), state_spec, state_spec, state_spec,
                   P("clients"), P("clients"), P("clients")),
        axis_names=frozenset({"clients"}),
    )

    # straggler variant: survivor mask + per-client work fractions.
    # Work always rides WITH a survivor operand (the host supplies
    # ones when nothing dropped) so there are exactly three programs:
    # mask-free, dropout, dropout+stragglers — and the first two stay
    # bit-identical to their pre-straggler builds.
    shard_train_work_mapped = shard_map(
        shard_train, mesh=mesh,
        in_specs=(P(), P("clients"), P("clients"), P("clients"),
                  P("clients"), P("clients"), P("clients"), P(),
                  P("clients"), P("clients")),
        out_specs=(P(), P(), state_spec, state_spec, state_spec,
                   P("clients"), P("clients"), P("clients")),
        axis_names=frozenset({"clients"}),
    )

    # screened family (ISSUE 16): survivors + poison mask + traced
    # screen-enable scalar, with the effective admitted mask as a
    # ninth output. Two programs — with and without the straggler
    # work operand — mirroring the default family's structure so
    # screening composes with every fault axis for free. Robust
    # aggregators (ISSUE 17) extend BOTH with two further outputs —
    # the contributors mask (per-client, sharded) and the replicated
    # [4] aggregation-stats vector — a static config branch, so
    # PR-16 screened configs keep their exact output arity.
    screened_out = (P(), P(), state_spec, state_spec, state_spec,
                    P("clients"), P("clients"), P("clients"),
                    P("clients"))
    if cfg.robust_aggregation:
        screened_out = screened_out + (P("clients"), P())

    def _shard_train_screened(ps_weights, data, mask, err_rows,
                              vel_rows, w_rows, keys, lr, surv, pois,
                              screen):
        return shard_train(ps_weights, data, mask, err_rows, vel_rows,
                           w_rows, keys, lr, surv, None, pois, screen)

    shard_train_screened_mapped = shard_map(
        _shard_train_screened, mesh=mesh,
        in_specs=(P(), P("clients"), P("clients"), P("clients"),
                  P("clients"), P("clients"), P("clients"), P(),
                  P("clients"), P("clients"), P()),
        out_specs=screened_out,
        axis_names=frozenset({"clients"}),
    )

    shard_train_screened_work_mapped = shard_map(
        shard_train, mesh=mesh,
        in_specs=(P(), P("clients"), P("clients"), P("clients"),
                  P("clients"), P("clients"), P("clients"), P(),
                  P("clients"), P("clients"), P("clients"), P()),
        out_specs=screened_out,
        axis_names=frozenset({"clients"}),
    )

    # ---------------- cohort gather / scatter-back -----------------------
    # The participant-row motion lives in two dedicated STATE-MOTION
    # programs OUTSIDE the jitted round (module docstring): the round
    # programs therefore never see a population-shaped operand —
    # graftaudit AU004's hard-error contract — and the only programs
    # touching the sharded RowBlocks move exactly O(cohort) rows each,
    # every row as one contiguous run of whole tiles. Both compile
    # once per config and are cache hits on every later dispatch
    # (tests pin the counts).

    def _gathered(block, ids):
        # the cohort converts to [W, D] in one piece (a tile transpose
        # and the cut of the padding), never row by row: a [W, D]
        # buffer written a row at a time is sublane-strided again
        return tiles_to_rows(
            take_rows(block.tiles, ids, sharded=n_shards > 1), block.D)

    def gather_cohort(clients: ClientState, ids) -> CohortState:
        """Pull the sampled cohort's rows out of the sharded population
        blocks. Untracked blocks yield [W] f32 dummies (distinct
        buffers — the round jit donates the whole CohortState, and XLA
        rejects one buffer donated twice) that keep the shard_map
        operand count static; they are never read."""
        W = ids.shape[0]
        with scope("gather_cohort"):
            return CohortState(
                errors=(_gathered(clients.errors, ids)
                        if _has_errors(cfg) else jnp.zeros((W,))),
                velocities=(_gathered(clients.velocities, ids)
                            if _has_velocities(cfg)
                            else jnp.zeros((W,))),
                weights=(_gathered(clients.weights, ids)
                         if cfg.do_topk_down else jnp.zeros((W,))))

    def scatter_back(clients: ClientState, ids,
                     cohort: CohortState) -> ClientState:
        """Write the round's merged cohort rows back into the sharded
        population blocks, in place on the donated block. The rows
        already encode the dropout contract (round_step merged dropped
        clients' gathered values back), so this is an unconditional
        per-slot write of whole-tile rows; untracked placeholder
        fields pass through."""
        new_clients = clients
        with scope("scatter_back"):
            if _has_errors(cfg):
                new_clients = new_clients._replace(
                    errors=new_clients.errors.set_rows(
                        ids, cohort.errors))
            if _has_velocities(cfg):
                new_clients = new_clients._replace(
                    velocities=new_clients.velocities.set_rows(
                        ids, cohort.velocities))
            if cfg.do_topk_down:
                new_clients = new_clients._replace(
                    weights=new_clients.weights.set_rows(
                        ids, cohort.weights))
        return new_clients

    # ---------------- full train round ----------------------------------
    def round_step(server: ServerState, cohort: CohortState,
                   batch: RoundBatch, lr, key):
        num_workers = batch.client_ids.shape[0]
        if num_workers % n_shards != 0:
            raise ValueError(
                f"num_workers={num_workers} must be divisible by the "
                f"{n_shards}-way clients mesh axis")

        # the gathered participant rows (cohort-gather ran before
        # dispatch; zero population-shaped operands in this program)
        err_rows = cohort.errors
        vel_rows = cohort.velocities
        w_rows = cohort.weights

        round_key = jax.random.fold_in(key, server.round_idx)
        client_keys = jax.vmap(
            lambda i: jax.random.fold_in(round_key, i)
        )(jnp.arange(num_workers))

        surv = batch.survivors
        work = batch.work
        pois = batch.poison
        admitted = contributors = agg_stats = None
        if pois is not None:
            # screened family (ISSUE 16): survivors and the traced
            # screen flag always ride with the poison operand (the
            # host ones-fills / zero-fills whichever is inert) — two
            # programs total, and the per-round screen decision is
            # data, never a retrace (RoundBatch docstring)
            surv = (jnp.ones(num_workers, jnp.float32) if surv is None
                    else surv.astype(jnp.float32))
            pois = pois.astype(jnp.float32)
            screen = (jnp.ones((), jnp.float32)
                      if batch.screen is None
                      else jnp.asarray(batch.screen, jnp.float32))
            if work is not None:
                res = shard_train_screened_work_mapped(
                    server.ps_weights, batch.data, batch.mask,
                    err_rows, vel_rows, w_rows, client_keys, lr, surv,
                    work.astype(jnp.float32), pois, screen)
            else:
                res = shard_train_screened_mapped(
                    server.ps_weights, batch.data, batch.mask,
                    err_rows, vel_rows, w_rows, client_keys, lr, surv,
                    pois, screen)
            (transmit, total, new_err, new_vel, new_w, losses,
             metrics, counts, admitted) = res[:9]
            if cfg.robust_aggregation:
                # robust programs (ISSUE 17) report the contributors
                # mask and the aggregation-stats vector alongside
                contributors, agg_stats = res[9], res[10]
            # a fully-screened round is a zero-survivor round: the
            # whole server update gates off and state comes through
            # bit-untouched
            alive = admitted.sum() > 0
        elif work is not None:
            # stragglers active: the work program always carries a
            # survivor operand too (below-cutoff degradation composes
            # the two), so substitute ones when nothing dropped
            surv = (jnp.ones(num_workers, jnp.float32) if surv is None
                    else surv.astype(jnp.float32))
            (transmit, total, new_err, new_vel, new_w, losses, metrics,
             counts) = shard_train_work_mapped(
                server.ps_weights, batch.data, batch.mask,
                err_rows, vel_rows, w_rows, client_keys, lr, surv,
                work.astype(jnp.float32))
            alive = surv.sum() > 0
        elif surv is None:
            (transmit, total, new_err, new_vel, new_w, losses, metrics,
             counts) = shard_train_mapped(
                server.ps_weights, batch.data, batch.mask,
                err_rows, vel_rows, w_rows, client_keys, lr)
            alive = None
        else:
            surv = surv.astype(jnp.float32)
            (transmit, total, new_err, new_vel, new_w, losses, metrics,
             counts) = shard_train_surv_mapped(
                server.ps_weights, batch.data, batch.mask,
                err_rows, vel_rows, w_rows, client_keys, lr, surv)
            # zero-survivor round -> gate the whole server update off
            # (get_server_update applies it): momentum/error state and
            # ps_weights come through bit-untouched
            alive = surv.sum() > 0

        # mean over the global batch (reference fed_aggregator.py:332):
        # with dropout, `total` already counts survivor examples only,
        # so the mean reweights by survivor count automatically; with
        # stragglers, each transmit was scaled by (and `total` counts)
        # examples ACTUALLY processed, so heterogeneous work fractions
        # normalize out FedNova-style — a half-work client carries
        # half weight, not a half-magnitude bias. A robust aggregator
        # (ISSUE 17) already produced the NORMALIZED location estimate
        # inside shard_train (an order statistic does not distribute
        # over the psum/divide split), so the divide is skipped.
        # compressor post-aggregation hook (ISSUE 19): once per round
        # on the aggregate, before the divide — dp_sketch adds its
        # calibrated Gaussian noise here, on the "dp" domain of the
        # round key; the identity (zero traced ops) for every other
        # plugin, so default programs are byte-unchanged
        with scope("aggregate"):
            transmit = comp.post_aggregate(cfg, transmit, round_key)
            if cfg.robust_aggregation and pois is not None:
                gradient = transmit
            else:
                gradient = transmit / jnp.maximum(total, 1.0)

        # server aggregation + decompression
        upd = fserver.get_server_update(
            gradient, server.Vvelocity, server.Verror, cfg, lr,
            key=jax.random.fold_in(round_key, num_workers),
            alive=alive)

        with scope("server_state"):
            if alive is None:
                new_ps = server.ps_weights - upd.update
            else:
                # `where` (not `- 0.0`) so a dead round is bit-exact
                new_ps = jnp.where(alive, server.ps_weights - upd.update,
                                   server.ps_weights)
            # round_idx advances even on a zero-survivor round: it indexes
            # the PRNG stream (round_key above), and a frozen index would
            # replay the identical dropout draw forever
            new_server = ServerState(new_ps, upd.Vvelocity, upd.Verror,
                                     server.round_idx + 1)

            # merge the updated participant rows with the gathered ones: a
            # dropped client's rows come through as their GATHERED values,
            # i.e. the scatter-back lands them bit-untouched (its error
            # feedback simply waits for the next round it completes). The
            # merged CohortState is this program's carried row output —
            # the scatter-back state-motion program writes it into the
            # population blocks after dispatch.
            # the EFFECTIVE mask: host survivors x device admission —
            # identical to surv outside the screened family, so the three
            # default programs trace byte-identically
            eff = admitted if admitted is not None else surv
            keep = None if eff is None else eff[:, None] > 0
            new_cohort = cohort
            if _has_errors(cfg):
                if keep is not None:
                    new_err = jnp.where(keep, new_err, err_rows)
                new_cohort = new_cohort._replace(errors=new_err)
            if _has_velocities(cfg):
                if upd.velocity_mask is not None:
                    # true_topk momentum factor masking (fixes ref D6)
                    new_vel = new_vel * upd.velocity_mask[None, :]
                if keep is not None:
                    new_vel = jnp.where(keep, new_vel, vel_rows)
                new_cohort = new_cohort._replace(velocities=new_vel)
            if cfg.do_topk_down:
                # persist each participant's post-download weights so its
                # staleness is tracked (the reference computes but never
                # stores these — deliberate fix, see module docstring);
                # a dropped client never received the download, so its
                # stale-weight row is kept too
                if keep is not None:
                    new_w = jnp.where(keep, new_w, w_rows)
                new_cohort = new_cohort._replace(weights=new_w)

        # on-device telemetry (telemetry/metrics.py): pure observation
        # of values already computed — reads the applied delta and the
        # NEW accumulator state, writes nothing back, so the state
        # outputs above are bit-identical with cfg.telemetry off (the
        # zero-size placeholder keeps the treedef stable per config)
        if cfg.telemetry:
            with scope("telemetry"):
                tele = tmetrics.round_vector(
                    losses=losses, counts=counts,
                    delta=new_ps - server.ps_weights,
                    verror=upd.Verror, vvelocity=upd.Vvelocity,
                    survivors=(jnp.float32(num_workers) if eff is None
                               else eff.sum()))
                if cfg.expert_load_layers:
                    tele = jnp.concatenate([tele, *tmetrics.layer_vectors(
                        metrics, cfg.mixer_layers)])
        else:
            tele = tmetrics.empty_vector()

        bits = None
        if cfg.server_in_place:
            from commefficient_tpu.federated.accounting import (
                pack_change_bits_tiled,
            )
            bits = pack_change_bits_tiled(new_ps - server.ps_weights)

        return new_server, new_cohort, RoundMetrics(
            losses, metrics, counts, tele, admitted, contributors,
            agg_stats, bits)

    def round_full(server: ServerState, clients: ClientState,
                   batch: RoundBatch, lr, key):
        """The COMPOSED per-round body — cohort gather, cohort round,
        scatter-back in ONE traced program. This is the scanned span's
        step (client state rides the scan carry, so the gather/scatter
        happen per scanned round exactly as before the split) and the
        bit-identity twin tests compare the three-program dispatch
        against. The cohort crosses an `optimization_barrier` each
        way, so the chip's compiler keeps the three programs three
        fusion regions and cannot fuse the conversion out of (or
        into) the blocks' tile form with the round's arithmetic and
        round that differently than the round program alone does.
        (The CPU's compiler drops barriers before it fuses; there the
        row loop of `take_rows` keeps the cohort out of the round's
        fusions.)"""
        cohort = lax.optimization_barrier(
            gather_cohort(clients, batch.client_ids))
        server, new_cohort, metrics = round_step(
            server, cohort, batch, lr, key)
        clients = scatter_back(clients, batch.client_ids,
                               lax.optimization_barrier(new_cohort))
        return server, clients, metrics

    # explicit output placement for the state-motion programs (the
    # shard-and-gather-fn half of the SNIPPETS.md pattern): gathered
    # rows land sharded over the clients axis — the exact layout the
    # round program's shard_map consumes, so GSPMD never reshards the
    # cohort between the two dispatches — and the scattered population
    # blocks keep their CLIENT_STATE_RULES placement.
    def _cohort_sharding():
        from commefficient_tpu.parallel import multihost as mh

        def spec(tracked):
            return P("clients", None) if tracked else P("clients")
        return mh.shardings(mesh, CohortState(
            spec(_has_errors(cfg)), spec(_has_velocities(cfg)),
            spec(cfg.do_topk_down)))

    def _state_sharding():
        from commefficient_tpu.parallel import multihost as mh

        def spec(tracked):
            # one spec per field: a tracked RowBlock's only leaf is
            # its [rows, T, 128] tiles
            return P("clients", None, None) if tracked else P()
        return mh.shardings(mesh, ClientState(
            spec(_has_errors(cfg)), spec(_has_velocities(cfg)),
            spec(cfg.do_topk_down)))

    # buffer donation (Config.donate_round_state, default on): the
    # dead-after-dispatch state operands are donated so XLA reuses
    # their HBM for the matching outputs in place — at population
    # scale the client rows are the dominant allocation, and an
    # un-donated scatter-back transiently doubles it. The dead sets are
    # the registry constants above; donated operands are INVALID after
    # the call (see TrainRound docstring for the caller contract).
    round_donate = ((ROUND_DEAD_ARGNUMS_IN_PLACE if cfg.server_in_place
                     else ROUND_DEAD_ARGNUMS)
                    if cfg.donate_round_state else ())
    # pipelined TIERED staging (ISSUE 11 + ISSUE 10): span t+1's
    # restore-scatters run against span t's result block while the
    # deferred span-boundary checkpoint still reads it, so the
    # scatter keeps its operand alive — transiently doubled block
    # HBM, bounded by the working set (the same trade the span jit
    # makes below)
    scatter_donate = (SCATTER_DEAD_ARGNUMS
                      if cfg.donate_round_state
                      and not (cfg.pipeline
                               and cfg.state_tier != "device")
                      else ())
    # pipelined spans (Config.pipeline, ISSUE 10) keep their state
    # operands ALIVE: span t+1 dispatches while span t's result state
    # is still needed by the deferred span-boundary checkpoint, so
    # donating it would hand the persistence path deleted buffers —
    # double buffering pays with transiently doubled state HBM instead
    span_donate = (SPAN_DEAD_ARGNUMS
                   if cfg.donate_round_state and not cfg.pipeline
                   else ())
    n_tracked = (int(_has_errors(cfg)) + int(_has_velocities(cfg))
                 + int(cfg.do_topk_down))
    _gather_jit = jax.jit(gather_cohort,
                          out_shardings=_cohort_sharding())
    _scatter_jit = jax.jit(scatter_back, donate_argnums=scatter_donate,
                           out_shardings=_state_sharding())
    _train_round_jit = jax.jit(round_step, donate_argnums=round_donate)

    # ---------------- scanned multi-round driver -------------------------
    def train_rounds(server: ServerState, clients: ClientState,
                     batches: RoundBatch, lrs, key):
        """Run N rounds as ONE device program (`lax.scan` over rounds):
        `batches` is a RoundBatch whose fields carry a leading [N]
        axis, `lrs` is [N]. Amortizes host dispatch — the reference
        pays a full host round-trip (queues + NCCL + shared-memory
        writeback, fed_aggregator.py:303-332) every round by
        construction; here an entire epoch can stay on-device.

        Also returns the per-round packed change bitset of the weight
        update ([N, D/32] uint32) so host-side communication
        accounting can replay the rounds without the weights ever
        leaving the device (see accounting.pack_change_bits).
        """
        from commefficient_tpu.federated.accounting import pack_change_bits

        def body(carry, xs):
            server, clients = carry
            batch, lr = xs
            prev = server.ps_weights
            # the composed body: gather -> cohort round -> scatter all
            # inside the scanned program, client state on the carry —
            # the population blocks never leave the device between
            # rounds, exactly as before the state-motion split
            server, clients, metrics = round_full(
                server, clients, batch, lr, key)
            bits = pack_change_bits(server.ps_weights - prev)
            return (server, clients), (metrics, bits)

        (server, clients), (metrics, bits) = jax.lax.scan(
            body, (server, clients), (batches, lrs))
        return server, clients, metrics, bits

    train_rounds = jax.jit(train_rounds, donate_argnums=span_donate)

    class TrainRound:
        """Callable single-round step; `.train_rounds` runs a whole
        scanned span of rounds in one device program.

        `__call__` brackets the jitted ROUND program with the two
        state-motion programs: cohort-gather before, scatter-back
        after — three dispatches, but the gather/scatter compile once
        per config and the round program is one of exactly three
        treedef variants, so the steady state is three cache-hit
        dispatches with O(cohort) traffic between them.

        Caller contract under donation (Config.donate_round_state, the
        default): `__call__` donates the gathered CohortState into the
        round program and the full ClientState into scatter-back, and
        `.train_rounds` donates BOTH state operands — after a dispatch
        the caller must use the returned state, never the arrays it
        passed in (FedModel reassigns immediately; a timing loop that
        re-dispatches from one retained state object needs
        donate_round_state=False). The registry attributes below are
        graftaudit's trace surface: `round_step` is the un-jitted
        COHORT round body (what the round jit compiles — jax.make_jaxpr
        over it yields the audited ClosedJaxpr), `gather_fn` /
        `scatter_fn` are the raw state-motion bodies, `round_full` the
        composed scan step, and the *_donate_argnums record what the
        built jits actually donate, checked against
        ROUND_DEAD_ARGNUMS / SCATTER_DEAD_ARGNUMS / SPAN_DEAD_ARGNUMS."""

        def __call__(self, server, clients, batch, lr, key):
            # graftscope (ISSUE 13): HOST-side spans around the three
            # dispatches — asynchronous dispatch cost, not device
            # time (that's the device_execute bracket at the
            # dispatch/collect seam). The round/span tags inherit
            # from the caller's enclosing `dispatch` span; nothing
            # here touches the traced programs.
            # `rows`/`bytes`: what has to move, from shapes alone (W
            # rows of D float32 per tracked block, each way) — over
            # the device time of the two programs, the bandwidth the
            # state motion achieves (journal.summarize sums them)
            rows = batch.client_ids.shape[0] * n_tracked
            moved = dict(rows=rows, bytes=rows * cfg.grad_size * 4)
            with TRACE.span("gather", **moved):
                cohort = _gather_jit(clients, batch.client_ids)
            with TRACE.span("round_dispatch"):
                server, new_cohort, metrics = _train_round_jit(
                    server, cohort, batch, lr, key)
            with TRACE.span("scatter", **moved):
                clients = _scatter_jit(clients, batch.client_ids,
                                       new_cohort)
            return server, clients, metrics

    handle = TrainRound()
    handle.train_rounds = train_rounds
    handle.round_step = round_step
    # the gather program's declared cohort placement — the tiered
    # state store (federated/statestore) places its host-built
    # restore rows with exactly these shardings so the restore hits
    # the same compiled scatter program the post-round writeback uses
    handle.cohort_shardings = _cohort_sharding()
    handle.round_full = round_full
    handle.gather = _gather_jit
    handle.scatter = _scatter_jit
    handle.gather_fn = gather_cohort
    handle.scatter_fn = scatter_back
    handle.round_donate_argnums = round_donate
    handle.scatter_donate_argnums = scatter_donate
    handle.span_donate_argnums = span_donate
    handle.cfg = cfg
    return handle


def make_eval_fn(loss_fn: fclient.LossFn, unravel: Callable,
                 cfg: Config, mesh: Mesh):
    """Build the jitted eval function — separate from the train factory
    so a distinct val loss (GPT2's nll/acc/ppl metrics,
    gpt2_train.py:242-253) never builds a throwaway train round.

    Uses the loss-only flat fn: the eval jaxpr contains no backward
    ops (asserted by tests/test_client.py), so eval compiles and runs
    forward-only instead of relying on XLA to DCE an unused grad."""
    flat_loss = fclient.make_flat_loss_fn(
        loss_fn, unravel,
        compute_dtype=jnp.bfloat16 if cfg.do_bf16 else None)

    def shard_eval(ps_weights, data, mask):
        if flat_loss.cohort:
            loss, metrics = flat_loss(ps_weights, data, mask)
            return loss, metrics, mask.sum(axis=1)

        def one_shard(b, m):
            _, loss, metrics, count = fclient.forward_grad(
                flat_loss, ps_weights, b, m, cfg, compute_grad=False)
            return loss, metrics, count
        return jax.vmap(one_shard)(data, mask)

    shard_eval_mapped = shard_map(
        shard_eval, mesh=mesh,
        in_specs=(P(), P("clients"), P("clients")),
        out_specs=(P("clients"), P("clients"), P("clients")),
        # model axis (if present) stays auto — see make_train_fn
        axis_names=frozenset({"clients"}),
    )

    @jax.jit
    def eval_batch(ps_weights, data, mask):
        """data: [S, vb, ...], mask: [S, vb]; S divisible by the mesh.
        Returns per-shard (loss, metrics, count) — the val path of
        reference _call_val (fed_aggregator.py:337-364)."""
        return shard_eval_mapped(ps_weights, data, mask)

    return eval_batch

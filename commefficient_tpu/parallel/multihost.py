"""Multi-host (multi-process) runtime support.

The reference's runtime is a multi-process topology on one box: a PS
process plus N worker processes rendezvousing over
``torch.distributed`` (reference: CommEfficient/fed_aggregator.py:143-164).
Its TPU-native equivalent at the BASELINE v4-32 scale is a multi-HOST
JAX job: one Python process per host, each addressing its local chips,
all running the SAME program over one global mesh (multi-controller
SPMD). This module is everything the rest of the framework needs to
run that way:

  * :func:`initialize` — ``jax.distributed.initialize``.
  * :func:`globalize` — lift a host value every process holds
    identically (PS weights, client ids, LR vectors, PRNG keys) into a
    global array with an explicit sharding on the global mesh.
  * :func:`shard_rows` — per-process batch feeding: each process
    passes ONLY the batch rows its addressable devices own
    (``jax.make_array_from_process_local_data``), so no host ever
    materializes the global batch — the fix for the round-3 gap where
    FedModel ``jnp.asarray``-ed host-global batches.
  * :func:`local_row_slice` — which rows of a ``[num_workers, ...]``
    round batch this process must feed (FedLoader materializes only
    these).
  * :func:`gather_host` — materialize a possibly cross-process-sharded
    metric on every host (``process_allgather``); the identity in
    single-process runs.
  * :func:`is_coordinator` — process-0 guard for logging, checkpoint
    writes, and accounting output.

Design note: everything degrades to a no-op in single-process runs —
``process_count() == 1`` keeps the exact round-3 code paths, so the
single-chip bench and the 8-device CPU test mesh are untouched.
"""
from __future__ import annotations

import functools
from typing import Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from commefficient_tpu.analysis.domains import CLIENTS_AXIS

_initialized = False


def initialize(coordinator_address: Optional[str] = None,
               num_processes: Optional[int] = None,
               process_id: Optional[int] = None,
               local_device_ids: Optional[Sequence[int]] = None,
               connect_timeout_s: float = 300.0,
               connect_retries: int = 3,
               retry_sleep=None) -> None:
    """``jax.distributed.initialize``, before any backend use.

    On TPU pods every argument is auto-detected from the TPU
    environment, so a bare ``initialize()`` suffices; elsewhere
    (CPU/GPU grids, the emulated two-process CPU mode the tests use)
    pass the coordinator and process grid explicitly.

    The coordinator rendezvous is the single most failure-prone moment
    of a preemptible-pod launch (a neighbor host restarting a few
    seconds late looks like a dead coordinator), so the one blocking
    attempt is replaced by a bounded connect policy: each attempt is
    capped at ``connect_timeout_s`` (passed through to jax's
    ``initialization_timeout`` where the installed version supports
    it), and a TRANSIENT failure — connection refused/reset, gRPC
    DEADLINE_EXCEEDED/UNAVAILABLE (utils/retry.is_transient_error) —
    is retried up to ``connect_retries`` more times with exponential
    backoff, each retry logged through utils/logging. Fatal errors
    (bad arguments, mismatched grids) raise immediately.
    ``retry_sleep`` overrides the backoff sleep (tests)."""
    global _initialized
    if _initialized:
        # idempotent: drivers and libraries may both ask for the
        # runtime; the second caller gets the existing one
        return
    kw = {}
    if coordinator_address:
        kw["coordinator_address"] = coordinator_address
    if num_processes is not None:
        kw["num_processes"] = num_processes
    if process_id is not None:
        kw["process_id"] = process_id
    if local_device_ids is not None:
        kw["local_device_ids"] = local_device_ids
    import inspect
    try:
        params = inspect.signature(jax.distributed.initialize).parameters
    except (TypeError, ValueError):  # C-accelerated / wrapped callable
        params = {}
    if "initialization_timeout" in params:
        kw["initialization_timeout"] = int(connect_timeout_s)

    from commefficient_tpu.utils.retry import with_retries

    def attempt():
        try:
            jax.distributed.initialize(**kw)
        except (RuntimeError, ValueError, OSError):
            # The expected rendezvous failure classes: XlaRuntimeError
            # (a RuntimeError) from gRPC timeouts/refusals, ValueError
            # from bad grids, OSError/ConnectionError from the socket
            # layer. jax assigns its global client (and rank 0's
            # coordination service) BEFORE connect(), so a failed
            # connect leaves half-initialized state that would make the
            # next call raise 'initialize should only be called once' —
            # a fatal-looking error masking the real timeout. Tear it
            # down best-effort so the retry is a genuine fresh attempt,
            # then re-raise for with_retries' transient/fatal triage.
            # Anything outside these classes (incl. InjectedFault)
            # propagates untouched, per GL005.
            try:
                jax.distributed.shutdown()
            except (RuntimeError, ValueError, OSError):
                # a half-initialized client may have nothing to shut
                # down; the original connect error is the one to surface
                pass
            raise

    retry_kw = {} if retry_sleep is None else {"sleep": retry_sleep}
    with_retries(attempt,
                 retries=connect_retries,
                 describe="jax.distributed.initialize "
                          f"({coordinator_address or 'auto-detected'})",
                 **retry_kw)
    _initialized = True


def initialize_from_config(cfg) -> None:
    """Driver entry: honor --multihost/--coordinator_address/
    --num_processes/--process_id (config.py flags)."""
    initialize(
        coordinator_address=cfg.coordinator_address or None,
        num_processes=cfg.num_processes if cfg.num_processes > 0 else None,
        process_id=cfg.process_id if cfg.process_id >= 0 else None)


def process_count() -> int:
    return jax.process_count()


def process_index() -> int:
    return jax.process_index()


def is_coordinator() -> bool:
    """True on the process that owns logging/checkpointing/accounting
    output (the reference's rank-0 PS process)."""
    return jax.process_index() == 0


def is_multihost() -> bool:
    return jax.process_count() > 1


# ---------------------------------------------------------------------------
# partition rules (the `match_partition_rules` / shard-and-gather-fn
# pattern of SNIPPETS.md [1], reduced to what the round engine needs)


def match_partition_rules(rules, tree, default: P = P()):
    """Map every leaf of `tree` to a PartitionSpec by regex over its
    tree path (SNIPPETS.md [1] `match_partition_rules`): the first
    `(pattern, spec)` whose pattern searches the leaf's keystr path
    wins. A leaf with fewer dims than the matched spec's length —
    zero-size placeholders, scalars — falls back to `default`, so an
    unused state field never claims a mesh axis it cannot divide.

    Returns a pytree of PartitionSpecs with `tree`'s treedef — feed it
    to `shardings()` for jit in/out_shardings, or zip it with the
    leaves for explicit device_put placement."""
    import re
    flat, treedef = jax.tree_util.tree_flatten_with_path(tree)
    out = []
    for path, leaf in flat:
        name = jax.tree_util.keystr(path)
        spec = default
        for pat, s in rules:
            if re.search(pat, name) and getattr(leaf, "ndim", 0) >= len(s):
                spec = s
                break
        out.append(spec)
    return jax.tree_util.tree_unflatten(treedef, out)


def shardings(mesh: Mesh, specs):
    """A pytree of PartitionSpecs -> the matching NamedShardings on
    `mesh` (the make_shard_and_gather_fns half the jit API needs:
    jit(..., out_shardings=shardings(mesh, specs)))."""
    return jax.tree_util.tree_map(
        lambda s: NamedSharding(mesh, s), specs,
        is_leaf=lambda s: isinstance(s, P))


# ---------------------------------------------------------------------------
# array construction


def globalize(mesh: Mesh, spec: P, value) -> jax.Array:
    """Lift a host value that EVERY process holds identically into a
    global array with sharding ``NamedSharding(mesh, spec)``.

    Single-process: plain ``jax.device_put`` with the sharding (so
    state still lands sharded on the local mesh). Multi-process: each
    process contributes the shards its devices own via
    ``make_array_from_callback`` indexing into the (identical) host
    value — correct for any device→process layout."""
    sharding = NamedSharding(mesh, spec)
    if not is_multihost():
        # EXPLICIT placement (device_put of a host array or an
        # already-device array): the jitted-round transfer-guard
        # contract (analysis/runtime.forbid_transfers) allows explicit
        # transfers only, so the host boundary must never go through an
        # implicit jnp.asarray of host data
        if not isinstance(value, jax.Array):
            value = np.asarray(value)
        return jax.device_put(value, sharding)
    arr = np.asarray(value)
    return jax.make_array_from_callback(
        arr.shape, sharding, lambda idx: arr[idx])


# jitted allocation builders, cached so repeated shapes reuse one jit
# wrapper (a fresh jax.jit(lambda ...) per call would retrace+compile
# every invocation); NamedSharding/np.dtype/tuple keys are hashable
@functools.lru_cache(maxsize=512)
def _jit_zeros(shape: Tuple[int, ...], dtype, sharding):
    return jax.jit(lambda: jnp.zeros(shape, dtype),
                   out_shardings=sharding)


@functools.lru_cache(maxsize=128)
def _jit_broadcast(shape: Tuple[int, ...], sharding):
    return jax.jit(lambda v: jnp.broadcast_to(v, shape),
                   out_shardings=sharding)


@functools.lru_cache(maxsize=128)
def _jit_copy(sharding):
    return jax.jit(lambda x: x.copy(), out_shardings=sharding)


def globalize_owned(mesh: Mesh, spec: P, value) -> jax.Array:
    """globalize + guarantee the result owns an XLA-allocated buffer.

    For values that enter the round engine's DONATION chain — the
    resumed server/client state a checkpoint loader places — a plain
    device_put of a large aligned numpy array may be ZERO-COPY on the
    CPU backend: the "device" buffer aliases numpy-owned heap memory,
    and the donated in-place update chain then writes into (and
    eventually frees) memory XLA does not own — intermittent glibc
    heap corruption (see zeros() below; found by the ISSUE-11 verify
    drive). The jitted copy forces a fresh XLA output allocation;
    values only ever READ by programs don't need this."""
    arr = globalize(mesh, spec, value)
    return _jit_copy(NamedSharding(mesh, spec))(arr)


def shard_rows(mesh: Mesh, local_rows, leading_axes: int = 0) -> jax.Array:
    """Per-process batch feeding: build the global ``[W, ...]`` round
    array from THIS process's rows only.

    ``local_rows``: the rows owned by this process's devices, in mesh
    order — shape ``[W_local, ...]`` (``leading_axes=0``) or with
    ``leading_axes`` unsharded leading dims before the clients axis
    (the scanned multi-round span's ``[N, W_local, ...]``).

    Single-process: device_put of the (already global) rows."""
    spec = P(*([None] * leading_axes), CLIENTS_AXIS,
             *([None] * (np.ndim(local_rows) - leading_axes - 1)))
    sharding = NamedSharding(mesh, spec)
    if not is_multihost():
        # explicit placement — see globalize
        if not isinstance(local_rows, jax.Array):
            local_rows = np.asarray(local_rows)
        return jax.device_put(local_rows, sharding)
    return jax.make_array_from_process_local_data(
        sharding, np.asarray(local_rows))


def local_row_slice(mesh: Mesh, num_rows: int) -> slice:
    """The contiguous block of a ``[num_rows, ...]`` clients-sharded
    array that this process feeds (and therefore the only rows its
    FedLoader must materialize).

    Requires this process's devices to hold a contiguous block of the
    mesh's ``clients`` axis — true for the standard process-major
    device order of ``jax.devices()``; raises otherwise rather than
    feeding rows to the wrong process."""
    axis_devices = _clients_axis_devices(mesh)
    n_shards = len(axis_devices)
    if num_rows % n_shards:
        raise ValueError(f"num_rows={num_rows} not divisible by the "
                         f"{n_shards}-way clients axis")
    rows_per_shard = num_rows // n_shards
    me = jax.process_index()
    mine = [i for i, d in enumerate(axis_devices) if d.process_index == me]
    if not mine:
        return slice(0, 0)
    lo, hi = min(mine), max(mine)
    if mine != list(range(lo, hi + 1)):
        raise ValueError(
            "this process's devices are not a contiguous block of the "
            "clients axis; feed globally with globalize() instead")
    return slice(lo * rows_per_shard, (hi + 1) * rows_per_shard)


def apply_feed_slices(model, train_loader, val_loader,
                      num_train_rows: int, num_val_rows: int) -> None:
    """Driver-side wiring of per-process batch feeding (both trainers
    share it — the invariants are subtle enough to keep in ONE place):
    compute BOTH row slices before assigning either, so a failure can't
    leave one loader local and the other global; on the non-contiguity
    error only, engage the documented globalize() fallback
    (FedModel.feed_global); anything else (e.g. divisibility) is a
    config error and re-raises."""
    try:
        train_sl = local_row_slice(model.mesh, num_train_rows)
        val_sl = local_row_slice(model.mesh, num_val_rows)
    except ValueError as e:
        if "globalize" not in str(e):
            raise
        model.feed_global = True
        if is_coordinator():
            print(f"non-contiguous device layout ({e}); "
                  "feeding batches globally via globalize()")
    else:
        train_loader.feed_slice = train_sl
        val_loader.feed_slice = val_sl


def _clients_axis_devices(mesh: Mesh):
    """Mesh devices along the clients axis (first model-column when a
    model axis exists: the clients coordinate determines the row
    block; every model-column replica of a row must then live in the
    same process for local feeding, which `local_row_slice` verifies
    via contiguity of the flattened list)."""
    axes = list(mesh.axis_names)
    arr = mesh.devices
    if axes == [CLIENTS_AXIS]:
        return list(arr.reshape(-1))
    # move the clients axis first, take the first element of the rest
    k = axes.index(CLIENTS_AXIS)
    arr = np.moveaxis(arr, k, 0)
    return list(arr.reshape(arr.shape[0], -1)[:, 0])


def zeros(mesh: Mesh, spec: P, shape: Tuple[int, ...],
          dtype=jnp.float32) -> jax.Array:
    """Zero-initialized global array. Multi-process path allocates only
    this process's shards (per-shard callback) — the per-client state
    arrays are the framework's memory hazard (SURVEY.md §7.0) and must
    never materialize host-globally."""
    sharding = NamedSharding(mesh, spec)
    if not is_multihost():
        # allocate ON DEVICE (jitted zeros with explicit out_shardings
        # — no transfer at all, so trivially transfer-guard-clean).
        # Deliberately NOT device_put(np.zeros(...)): jax zero-copies
        # large aligned numpy buffers into CPU device arrays, and the
        # round engine DONATES these blocks — the in-place donation
        # chain then writes into (and eventually frees) numpy-owned
        # heap memory for the rest of the run, which intermittently
        # corrupts the allocator on the CPU thunk runtime (glibc
        # "free(): invalid pointer" / "corrupted size vs. prev_size";
        # observed on the scanned local_topk driver, ISSUE 11 verify).
        # A device-native buffer keeps the whole donation chain inside
        # XLA's allocator.
        return _jit_zeros(tuple(shape), np.dtype(dtype), sharding)()
    # multihost: shard-local host staging. A jitted device-side copy
    # (the single-process fix above) is not an option here — the CPU
    # backend cannot run cross-process computations, so the grid
    # emulation would fail before it ever trained — hence the shard
    # buffers are made un-zero-copyable instead, which forces
    # device_put to copy them into XLA-owned memory (same donation
    # hazard as above, same ownership guarantee, per shard)
    return jax.make_array_from_callback(
        tuple(shape), sharding,
        lambda idx: _unaliasable(
            np.zeros(_shard_shape(idx, shape), dtype)))


def tile_rows(mesh: Mesh, vec, rows: int) -> jax.Array:
    """``[rows, *vec.shape]`` global array whose every row is ``vec``,
    sharded over its leading axis — the per-client stale-weights state
    of the download-top-k path (``vec`` is the base row in the
    blocks' tile form, federated/round.rows_to_tiles). Shard-local
    materialization only."""
    host = np.asarray(vec)
    shape = (rows,) + host.shape
    sharding = NamedSharding(
        mesh, P(CLIENTS_AXIS, *([None] * host.ndim)))
    if not is_multihost():
        # materialize the tile ON DEVICE from the (small, explicit)
        # device_put of the base vector: like zeros() above, the
        # resulting block rides the round engine's donation chain, so
        # its buffer must be XLA-allocated, never a zero-copied numpy
        # broadcast
        base = jax.device_put(host, NamedSharding(mesh, P()))
        return _jit_broadcast(shape, sharding)(base)

    def cb(idx):
        # _unaliasable: these rows ride the donation chain — see
        # zeros() above
        return _unaliasable(np.broadcast_to(
            host[idx[1:]], _shard_shape(idx, shape)))

    return jax.make_array_from_callback(shape, sharding, cb)


def _shard_shape(idx: Tuple[slice, ...], shape: Tuple[int, ...]):
    return tuple(len(range(*s.indices(n))) for s, n in zip(idx, shape))


def _unaliasable(arr: np.ndarray) -> np.ndarray:
    """A copy of `arr` whose buffer device_put can NEVER zero-copy:
    the data starts one element into an over-allocated block, so it
    fails XLA's CPU-client alignment check and is always copied into
    an XLA-owned device buffer. Used for host-staged state that rides
    the round engine's donation chain on the multihost path, where
    the jitted on-device allocation of the single-process path is
    unavailable (the CPU backend cannot run cross-process programs).
    If a future backend copies anyway, this is merely one redundant
    host copy at init time."""
    flat = np.empty(arr.size + 1, arr.dtype)
    out = flat[1:].reshape(arr.shape)
    out[...] = arr
    return out


# ---------------------------------------------------------------------------
# result materialization


def gather_host(x) -> np.ndarray:
    """Materialize a (possibly cross-process-sharded) device array on
    every host. An EXPLICIT ``jax.device_get`` when the array is fully
    addressable (so a transfer-guarded round may call this — implicit
    ``np.asarray`` of a device array would trip the guard);
    ``process_allgather`` otherwise."""
    if isinstance(x, np.ndarray) or np.isscalar(x):
        return np.asarray(x)
    if getattr(x, "is_fully_addressable", True) or _fully_replicated(x):
        return jax.device_get(x)
    from jax.experimental import multihost_utils
    return multihost_utils.process_allgather(x, tiled=True)


def async_gather_host(x):
    """Begin the device->host copy of `x` WITHOUT blocking and return
    a zero-arg completer that materializes it (an explicit
    ``gather_host``, so a transfer-guarded caller may invoke it on any
    thread). The tiered client-state spill path (ISSUE 11,
    federated/statestore.py) uses this to move evicted rows off the
    critical path: the copy is started at dispatch time and the
    writer thread blocks on completion instead of the round loop.

    The completer memoizes its result: a pending spill's rows may be
    read back by several restores (plus the writer-thread commit)
    before the entry retires, and each call would otherwise re-run
    the full gather. A concurrent first call may compute twice —
    both produce the identical host array, so the race is benign."""
    try:
        x.copy_to_host_async()
    except AttributeError:
        # host numpy value or an array type without the async API —
        # the completer below is then the whole (cheap) copy
        pass
    memo = []

    def complete():
        if not memo:
            memo.append(gather_host(x))
        return memo[0]

    return complete


def _fully_replicated(x) -> bool:
    try:
        return bool(x.is_fully_replicated)
    except AttributeError:
        return False


def sync_processes(name: str = "barrier") -> None:
    """Cross-process barrier (checkpoint write ordering)."""
    if is_multihost():
        from jax.experimental import multihost_utils
        multihost_utils.sync_global_devices(name)

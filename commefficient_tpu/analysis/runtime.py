"""Runtime sanitizers: the dynamic half of graftlint and graftsync.

The static passes (engine/rules, syncaudit) catch what syntax can
prove; these catch what only execution can — armed by the test suite
so the engine's load-bearing runtime contracts are EXECUTED checks,
not prose:

  * `assert_program_count(n)` — a compilation counter around a block.
    ROADMAP's "exactly three traced round programs" (mask-free,
    dropout, dropout+stragglers) becomes `with
    assert_program_count(3): <run all three configs twice>`: a fourth
    program (an accidental retrace from a new treedef, a weak-type
    flip-flop, a shape leak) fails the block. Counting is a pair of
    jax.monitoring listeners (backend-compile durations + compilation-
    cache requests, max of the two — robust whether the compilation
    cache is enabled, disabled, or hitting its persistent store) — no
    monkeypatching, counts executable builds (tracing-cache hits and
    C++ fast-path dispatches are free, as they must be).
  * `forbid_transfers()` — `jax.transfer_guard("disallow")` around a
    block: any IMPLICIT host<->device transfer (an `np.asarray` of a
    device array, a python-scalar operand materialized at dispatch, a
    stray `float()`) raises. Explicit `jax.device_put`/`device_get`
    stay legal — the framework's host boundaries (multihost.globalize
    / gather_host) are deliberately explicit so a guarded round is
    provably sync-free everywhere else.

  * `LockOrderSanitizer` — graftsync's runtime twin (ISSUE 14).
    Installed, it replaces `threading.Lock`/`threading.RLock` with
    recording proxies: every successful acquisition while other
    instrumented locks are held adds a lock-order edge, and
    `assert_acyclic()` at teardown raises `LockOrderError` naming
    the cycle when two threads ever took instrumented locks in
    opposite orders — the dynamic ABBA check over orders the static
    SY002 graph cannot see (locks reached through aliases, orders
    composed across modules at runtime). Instrumentation is by
    OBJECT, so the RLock re-entrancy idiom adds no self-edges, and
    `queue.Queue`'s internal mutex/conditions are instrumented for
    free (queue looks `threading.Lock` up dynamically).
  * `interleaving_stress()` — deterministic delay injection at the
    writer-queue handoffs (`queue.Queue.put`/`get`): a counter-driven
    (never random — replayable) sub-millisecond stagger that widens
    the producer/drain race windows the bounded-queue writers must
    tolerate. `CCTPU_SYNC_SANITIZE=1` arms both over whatever suites
    pytest then runs (pipeline/statetier/controlplane are the ones
    worth it) via an autouse fixture (tests/conftest.py).

  * `NumericSanitizer` — graftnum's runtime twin (ISSUE 18).
    Installed, it wraps `telemetry.metrics.named` (the ONE host
    boundary every exported round metric crosses) in a post-dispatch
    finite-guard: any NaN/inf reaching an export raises
    `NumericError` naming the metric — the dynamic check behind the
    static NU001 lattice's one assumption (that a `where` guard's
    predicate is semantically sufficient). `replay_drill(fn, *args)`
    dispatches a traced program twice on identical operands and
    asserts bitwise equality leaf by leaf — the executable form of
    the NU004 crash->resume contract. `CCTPU_NUM_SANITIZE=1` arms
    the guard over whatever suites pytest then runs (valuefaults and
    byzantine are the ones worth it) via an autouse fixture
    (tests/conftest.py).

The `sanitize` pytest fixture (tests/conftest.py) hands tests the
program-count/transfer pair; `lock_sanitizer` hands them an
installed LockOrderSanitizer; `num_sanitizer` an installed
NumericSanitizer.
"""
from __future__ import annotations

import contextlib
import itertools
import math
import queue as _queue
import sys
import threading
import time
from typing import Dict, List, Optional, Tuple

import jax

# Two redundant per-program signals, counted independently; the block
# count is their max. Each fires once per distinct executable and
# never on tracing-cache hits or C++ fast-path dispatches:
#   * backend_compile_duration — one per XLA backend compile,
#     unconditionally (fires even with the compilation cache disabled,
#     where the cache-request event below never records);
#   * compile_requests_use_cache — one per compile request when the
#     cache is consulted (covers persistent-cache HITS, where a
#     distinct program loads without a backend compile).
_COMPILE_EVENTS = frozenset({
    "/jax/compilation_cache/compile_requests_use_cache",
})
_COMPILE_DURATION_EVENTS = frozenset({
    "/jax/core/compile/backend_compile_duration",
})

_counter = {"requests": 0, "backend": 0, "installed": False}

# external compile subscribers (telemetry journal): called with
# (event_name, duration_seconds) once per backend compile. Fed from the
# DURATION listener only — it fires unconditionally per executable
# build, while the cache-request event double-counts when both fire.
_compile_subscribers: list = []


def add_compile_listener(cb) -> None:
    """Subscribe `cb(event_name, duration_s)` to backend-compile
    events (the telemetry journal uses this to record every XLA
    compile, and to flag steady-state recompiles). Idempotent per
    callback object."""
    _ensure_listener()
    if cb not in _compile_subscribers:
        _compile_subscribers.append(cb)


def remove_compile_listener(cb) -> None:
    try:
        _compile_subscribers.remove(cb)
    except ValueError:
        pass


def _on_event(event: str, **kw) -> None:
    if event in _COMPILE_EVENTS:
        _counter["requests"] += 1


def _on_event_duration(event: str, duration: float, **kw) -> None:
    if event in _COMPILE_DURATION_EVENTS:
        _counter["backend"] += 1
        for cb in list(_compile_subscribers):
            cb(event, duration)


def _ensure_listener() -> None:
    if not _counter["installed"]:
        jax.monitoring.register_event_listener(_on_event)
        jax.monitoring.register_event_duration_secs_listener(
            _on_event_duration)
        _counter["installed"] = True


class ProgramCount:
    """Result handle of `count_programs`: `.count` is the number of
    programs compiled inside the block (live-updating during it)."""

    def __init__(self, start_requests: int, start_backend: int):
        self._start_requests = start_requests
        self._start_backend = start_backend

    @property
    def count(self) -> int:
        return max(_counter["requests"] - self._start_requests,
                   _counter["backend"] - self._start_backend)


@contextlib.contextmanager
def count_programs():
    """Count XLA executables built inside the block."""
    _ensure_listener()
    yield ProgramCount(_counter["requests"], _counter["backend"])


@contextlib.contextmanager
def assert_program_count(n: int):
    """Assert EXACTLY `n` programs compile inside the block.

    Build every operand (device arrays, keys, lr scalars) BEFORE the
    block: eager jnp ops compile their own tiny programs and would
    inflate the count. A block observing 0 when n > 0 usually means the
    workload was warmed up beforehand — this sanitizer wants the cold
    calls inside."""
    with count_programs() as c:
        yield c
    got = c.count
    if got != n:
        if got > n:
            why = ("an extra program means an accidental retrace (new "
                   "treedef/shape/dtype or weak-type flip) — the "
                   "three-programs contract of federated/round.py caps "
                   "dispatch cost")
        else:
            why = ("fewer means the block was pre-warmed or the "
                   "workload never ran")
        raise AssertionError(
            f"program-count contract violated: expected exactly {n} "
            f"compiled program(s) in this block, observed {got}; {why} "
            "(see analysis/runtime.py)")


@contextlib.contextmanager
def forbid_transfers():
    """Disallow implicit host<->device transfers inside the block
    (explicit jax.device_put / jax.device_get remain legal)."""
    with jax.transfer_guard("disallow"):
        yield


class Sanitizer:
    """What the `sanitize` pytest fixture hands a test."""

    count_programs = staticmethod(count_programs)
    assert_program_count = staticmethod(assert_program_count)
    forbid_transfers = staticmethod(forbid_transfers)


# ---------------------------------------------------------------------------
# LockOrderSanitizer — graftsync's runtime twin (ISSUE 14)


class LockOrderError(AssertionError):
    """The observed lock-acquisition graph contains a cycle: two
    threads took instrumented locks in opposite orders at least once
    — a latent ABBA deadlock that only needs worse timing."""


class _SanitizedLock:
    """Proxy around a real Lock/RLock that reports acquisitions to
    its owning sanitizer. Unknown attributes (RLock's
    `_release_save`/`_acquire_restore`/`_is_owned`, used by
    Condition) delegate to the wrapped lock — Condition then drives
    the REAL lock for its wait dance, which keeps the proxy's held
    bookkeeping aligned with the logical critical section."""

    def __init__(self, san: "LockOrderSanitizer", inner, node: str):
        self._san = san
        self._inner = inner
        self._node = node

    def acquire(self, blocking: bool = True, timeout: float = -1):
        ok = self._inner.acquire(blocking, timeout)
        if ok:
            self._san._note_acquire(self)
        return ok

    def release(self) -> None:
        self._san._note_release(self)
        self._inner.release()

    def locked(self) -> bool:
        return self._inner.locked()

    def __enter__(self):
        self.acquire()
        return self

    def __exit__(self, *exc):
        self.release()
        return False

    def __getattr__(self, name):
        return getattr(self._inner, name)


class LockOrderSanitizer:
    """Record per-thread lock-acquisition edges; assert the global
    graph acyclic at teardown.

    `install()` swaps `threading.Lock`/`threading.RLock` for proxy
    factories (locks created BEFORE install stay uninstrumented —
    the fixture installs before constructing the objects under
    test); `uninstall()` restores the factories and freezes edge
    recording (already-created proxies keep working, they just stop
    reporting). Nodes are per lock OBJECT — `file:line#serial` of
    the creation site — so two queues' mutexes never alias into one
    node (the false-positive class a lockdep-style per-class graph
    would hit), and an RLock re-acquisition adds no self-edge.
    Deterministic given a deterministic schedule: edges carry the
    acquiring thread and site for the report, not timestamps."""

    def __init__(self):
        # real (uninstrumented) lock: the sanitizer must never
        # instrument its own bookkeeping
        self._graph_lock = threading.Lock()
        # (outer node, inner node) -> (thread name, "file:line" of
        # the inner acquisition)
        self._edges: Dict[Tuple[str, str], Tuple[str, str]] = {}
        self._held = threading.local()
        self._serial = itertools.count()
        self._active = False
        self._orig: Optional[tuple] = None

    # ---------------- factory patching --------------------------------
    @staticmethod
    def _site(depth: int = 2) -> str:
        frame = sys._getframe(depth)
        # walk out of this module so the node names the USER's
        # creation/acquisition site, not the proxy internals
        while frame is not None and frame.f_globals.get(
                "__name__") == __name__:
            frame = frame.f_back
        if frame is None:
            return "<unknown>"
        return f"{frame.f_code.co_filename}:{frame.f_lineno}"

    def _make(self, ctor):
        def factory():
            node = f"{self._site()}#{next(self._serial)}"
            return _SanitizedLock(self, ctor(), node)
        return factory

    def install(self) -> None:
        if self._orig is not None:
            return
        self._orig = (threading.Lock, threading.RLock)
        threading.Lock = self._make(self._orig[0])
        threading.RLock = self._make(self._orig[1])
        self._active = True

    def uninstall(self) -> None:
        if self._orig is None:
            return
        threading.Lock, threading.RLock = self._orig
        self._orig = None
        self._active = False

    def __enter__(self):
        self.install()
        return self

    def __exit__(self, *exc):
        self.uninstall()
        return False

    # ---------------- recording ---------------------------------------
    def _stack(self) -> List[_SanitizedLock]:
        stack = getattr(self._held, "stack", None)
        if stack is None:
            stack = self._held.stack = []
        return stack

    def _note_acquire(self, lock: _SanitizedLock) -> None:
        stack = self._stack()
        if self._active:
            for held in stack:
                if held is lock:
                    continue  # RLock re-entrancy: no self-edge
                key = (held._node, lock._node)
                if key not in self._edges:
                    with self._graph_lock:
                        self._edges.setdefault(
                            key, (threading.current_thread().name,
                                  self._site(3)))
        stack.append(lock)

    def _note_release(self, lock: _SanitizedLock) -> None:
        stack = self._stack()
        for i in range(len(stack) - 1, -1, -1):
            if stack[i] is lock:
                del stack[i]
                return

    # ---------------- verdict -----------------------------------------
    def edges(self) -> Dict[Tuple[str, str], Tuple[str, str]]:
        with self._graph_lock:
            return dict(self._edges)

    def find_cycle(self) -> Optional[List[str]]:
        """One cycle in the observed acquisition graph, or None —
        the same cycle definition the static SY002 rule uses
        (engine.find_cycles)."""
        from commefficient_tpu.analysis.engine import (
            edges_to_graph, find_cycles,
        )
        cycles = find_cycles(edges_to_graph(self.edges()))
        return cycles[0] if cycles else None

    def assert_acyclic(self) -> None:
        cyc = self.find_cycle()
        if cyc is None:
            return
        edges = self.edges()
        sites = []
        for a, b in zip(cyc, cyc[1:]):
            thread, site = edges[(a, b)]
            sites.append(f"  {a} -> {b}  (thread {thread!r} at {site})")
        raise LockOrderError(
            "lock-order cycle observed — two threads acquired these "
            "locks in opposite orders at least once (ABBA deadlock "
            "given worse timing):\n" + "\n".join(sites)
            + "\npick ONE global acquisition order (graftsync SY002 "
            "checks the static `with` nesting; this caught an order "
            "composed at runtime)")


# ---------------------------------------------------------------------------
# NumericSanitizer — graftnum's runtime twin (ISSUE 18)


class NumericError(AssertionError):
    """A non-finite value crossed a guarded numeric boundary (an
    exported round metric, a replay-drill mismatch): the static
    graftnum lattice proved the shipped guards are selects, this
    caught a predicate that was not semantically sufficient — or a
    program that did not replay bit-identically."""


class NumericSanitizer:
    """Scoped post-dispatch numeric guard.

    `install()` wraps `telemetry.metrics.named` — the single host
    boundary every exported round-metric vector crosses (the round
    engine, the telemetry writers, and bench all call it by module
    attribute) — so any NaN/inf that survived the on-device guards
    raises `NumericError` at the EXPORT, naming the metric, instead
    of poisoning a CSV three stages later. `uninstall()` restores the
    original; both are idempotent. `.checked` counts guarded vectors
    (a zero after a drill means the guard never saw traffic — arm it
    before the workload, like the program counter).

    `replay_drill(fn, *args, **kwargs)` is the NU004 contract made
    executable: dispatch `fn` twice on the SAME operands and assert
    the results bitwise-identical leaf by leaf (bytes of the
    materialized arrays — NaNs compare equal by representation, so a
    deterministic NaN is replay-clean, as the crash->resume contract
    requires). Returns the first call's result."""

    def __init__(self):
        self._orig = None
        self.checked = 0

    # ---------------- metric finite-guard ------------------------------
    def _guarded(self, orig):
        def named(vec):
            out = orig(vec)
            self.checked += 1
            bad = {k: v for k, v in out.items()
                   if not math.isfinite(v)}
            if bad:
                raise NumericError(
                    "non-finite round metric(s) exported: "
                    + ", ".join(f"{k}={v}" for k, v in
                                sorted(bad.items()))
                    + " — a NaN/inf survived the on-device admission "
                    "guards (graftnum NU001/NU003 prove the guards "
                    "are selects; this predicate was not sufficient "
                    "— see analysis/runtime.py)")
            return out
        return named

    def install(self) -> None:
        from commefficient_tpu.telemetry import metrics as tmetrics
        if self._orig is not None:
            return
        self._orig = tmetrics.named
        tmetrics.named = self._guarded(self._orig)

    def uninstall(self) -> None:
        from commefficient_tpu.telemetry import metrics as tmetrics
        if self._orig is None:
            return
        tmetrics.named = self._orig
        self._orig = None

    def __enter__(self):
        self.install()
        return self

    def __exit__(self, *exc):
        self.uninstall()
        return False

    # ---------------- determinism drill --------------------------------
    @staticmethod
    def assert_finite(tree, where: str = "value") -> None:
        """Raise NumericError if any float leaf of `tree` holds a
        NaN/inf (non-float and zero-size leaves pass)."""
        import numpy as np
        for i, leaf in enumerate(jax.tree.leaves(tree)):
            arr = np.asarray(jax.device_get(leaf))
            if arr.dtype.kind != "f" or not arr.size:
                continue
            if not np.isfinite(arr).all():
                n = int((~np.isfinite(arr)).sum())
                raise NumericError(
                    f"non-finite values at {where} (leaf {i}): "
                    f"{n}/{arr.size} element(s) NaN/inf")

    @staticmethod
    def replay_drill(fn, *args, **kwargs):
        import numpy as np
        first = fn(*args, **kwargs)
        second = fn(*args, **kwargs)
        la = jax.tree.leaves(first)
        lb = jax.tree.leaves(second)
        for i, (a, b) in enumerate(zip(la, lb)):
            ba = np.asarray(jax.device_get(a)).tobytes()
            bb = np.asarray(jax.device_get(b)).tobytes()
            if ba != bb:
                raise NumericError(
                    f"replay divergence: leaf {i} of {len(la)} "
                    "differs bitwise between two dispatches on "
                    "identical operands — the crash->resume "
                    "bit-exactness contract (graftnum NU004) does "
                    "not hold for this program")
        return first


@contextlib.contextmanager
def interleaving_stress(delay: float = 0.0005, period: int = 3):
    """Deterministically stagger writer-queue handoffs: every
    `queue.Queue.put`/`get` sleeps `(i % period) * delay` first, `i`
    a shared counter — so producer/drain interleavings that need an
    unlucky scheduler to collide are collided ON PURPOSE, every run,
    with no randomness (a failure under stress replays). The delays
    are host-side only and orders of magnitude below the drain
    timeouts, so semantics (FIFO order, bounded back-pressure, drain
    completeness) are untouched — only the timing is hostile."""
    counter = itertools.count()
    orig_put, orig_get = _queue.Queue.put, _queue.Queue.get

    def put(self, *args, **kwargs):
        time.sleep((next(counter) % period) * delay)
        return orig_put(self, *args, **kwargs)

    def get(self, *args, **kwargs):
        time.sleep((next(counter) % period) * delay)
        return orig_get(self, *args, **kwargs)

    _queue.Queue.put = put
    _queue.Queue.get = get
    try:
        yield
    finally:
        _queue.Queue.put = orig_put
        _queue.Queue.get = orig_get

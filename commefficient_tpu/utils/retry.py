"""Bounded-retry policy for transient host-side runtime failures.

Preemptible pods fail in two distinct ways and only one of them should
ever be retried: TRANSIENT faults (the coordination service isn't up
yet, a TCP connection reset mid-handshake, a gRPC DEADLINE_EXCEEDED /
UNAVAILABLE from the PJRT client while a neighbor host restarts) heal
themselves within seconds, while FATAL faults (shape errors, config
mistakes, scripted `InjectedFault`s, OOMs) only get louder when
replayed. `with_retries` encodes that split once: classify, retry the
transient class with exponential backoff up to a bound, re-raise
everything else immediately.

Used to guard the two host-side calls whose failure would otherwise
kill a multi-hour pod run for a seconds-long blip:
`parallel/multihost.initialize` (coordinator rendezvous) and the
scanned-span dispatch in `FedModel.run_rounds` (safe to retry because
the scanned round program is functional — server/client state is only
assigned from its RESULT, so a failed dispatch leaves nothing half
mutated).

Buffer-donation caveat (Config.donate_round_state, ISSUE 7): a
donated span dispatch that fails mid-EXECUTION leaves its state
operands deleted, so the retry's second attempt raises a fatal
array-deleted RuntimeError (correctly classified non-transient here)
instead of replaying. Staging-phase failures — where coordination
blips actually occur — still retry. Runs that prioritize the retry
guarantee over the in-place state HBM reuse pass
--no_donate_round_state.
"""
from __future__ import annotations

import time
from typing import Callable, Optional, Tuple, Type, TypeVar

from commefficient_tpu.utils.logging import Logger

T = TypeVar("T")

# lowercase substrings that mark an error message as transient — the
# gRPC status names and socket-level strings the TPU coordination
# service and PJRT surface during neighbor restarts
_TRANSIENT_MARKERS = (
    "deadline_exceeded",
    "deadline exceeded",
    "unavailable",
    "connection refused",
    "connection reset",
    "connection closed",
    "socket closed",
    "failed to connect",
    "broken pipe",
    "temporarily unavailable",
    "transport closed",
    "timed out",
)

_TRANSIENT_TYPES: Tuple[Type[BaseException], ...] = (
    ConnectionError, TimeoutError,
)


def is_transient_error(exc: BaseException) -> bool:
    """Transient (retryable) vs. fatal classification. Scripted
    `InjectedFault`s are ALWAYS fatal — a retry would silently defeat
    the fault-injection tests that rely on them propagating."""
    from commefficient_tpu.utils.faults import InjectedFault
    if isinstance(exc, InjectedFault):
        return False
    if isinstance(exc, _TRANSIENT_TYPES):
        return True
    msg = str(exc).lower()
    return any(marker in msg for marker in _TRANSIENT_MARKERS)


def with_retries(fn: Callable[[], T], *,
                 retries: int = 3,
                 base_delay: float = 0.5,
                 backoff: float = 2.0,
                 max_delay: float = 30.0,
                 classify: Callable[[BaseException], bool]
                 = is_transient_error,
                 describe: str = "operation",
                 sleep: Callable[[float], None] = time.sleep,
                 logger: Optional[Logger] = None,
                 on_retry: Optional[Callable[
                     [int, BaseException, float], None]] = None) -> T:
    """Call `fn()`; on a failure `classify` marks transient, retry up
    to `retries` more times with exponential backoff (base_delay *
    backoff^attempt, capped at max_delay). Fatal failures — and the
    final transient one once the bound is exhausted — re-raise
    unchanged. Each retry is logged through utils/logging.Logger so a
    pod run's recovery attempts are visible in its stdout record;
    `on_retry(attempt, exc, delay)` additionally fires before each
    backoff sleep — the telemetry journal's hook, so a pod run's
    recovery attempts land in its structured record too
    (telemetry/journal.py `retry` events)."""
    logger = logger or Logger()
    delay = base_delay
    for attempt in range(retries + 1):
        try:
            return fn()
        except Exception as exc:
            if attempt >= retries or not classify(exc):
                raise
            if on_retry is not None:
                on_retry(attempt, exc, delay)
            logger.warn(
                f"transient failure in {describe} "
                f"(attempt {attempt + 1}/{retries + 1}): {exc!r}; "
                f"retrying in {delay:.1f}s")
            sleep(delay)
            delay = min(delay * backoff, max_delay)
    raise AssertionError("unreachable")  # pragma: no cover

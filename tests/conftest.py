"""Test configuration: force an 8-device virtual CPU mesh before JAX import.

Mirrors the reference's multi-GPU-only testability gap (SURVEY.md §4): the
reference could only exercise its distributed path on a real multi-GPU box;
here every sharded code path runs on host-emulated devices.
"""
import os

flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (
        flags + " --xla_force_host_platform_device_count=8"
    ).strip()
os.environ["JAX_PLATFORMS"] = "cpu"

import jax  # noqa: E402

# Tests run on the CPU and must never claim a chip.
jax.config.update("jax_platforms", "cpu")
jax.config.update("jax_threefry_partitionable", True)

import pytest  # noqa: E402


@pytest.fixture(scope="session")
def devices():
    return jax.devices()


@pytest.fixture(scope="session")
def mesh():
    from commefficient_tpu.parallel.mesh import make_client_mesh

    return make_client_mesh(len(jax.devices()))


@pytest.fixture
def sanitize():
    """Runtime sanitizers (analysis/runtime): `forbid_transfers()` —
    jax.transfer_guard("disallow") proving a block performs zero
    implicit host transfers — and `assert_program_count(n)` — a
    compilation counter enforcing the round engine's three-programs
    contract. Both are context managers; arm them around the device
    dispatch, build operands (device arrays, jnp lr scalars, keys)
    BEFORE the block, and read results AFTER it."""
    from commefficient_tpu.analysis.runtime import Sanitizer

    return Sanitizer()


@pytest.fixture
def lock_sanitizer():
    """An installed LockOrderSanitizer (analysis/runtime): locks the
    test constructs are instrumented, and the acquisition graph is
    asserted acyclic at teardown — the runtime ABBA check behind
    graftsync SY002. Construct the objects under test INSIDE the
    test (locks created before install are invisible)."""
    from commefficient_tpu.analysis.runtime import LockOrderSanitizer

    san = LockOrderSanitizer()
    san.install()
    try:
        yield san
    finally:
        san.uninstall()
    san.assert_acyclic()


@pytest.fixture
def num_sanitizer():
    """An installed NumericSanitizer (analysis/runtime): every round
    metrics vector exported through telemetry.metrics.named while the
    fixture is live passes a post-dispatch finite guard — a NaN/inf
    in any exported metric raises NumericError naming the metric. Also
    carries the replay drill (`NumericSanitizer.replay_drill(fn, ...)`
    dispatches twice and asserts bitwise-equal results) and the tree
    guard (`NumericSanitizer.assert_finite(tree)`)."""
    from commefficient_tpu.analysis.runtime import NumericSanitizer

    san = NumericSanitizer()
    san.install()
    try:
        yield san
    finally:
        san.uninstall()


@pytest.fixture(autouse=True)
def _num_sanitize(request):
    """CCTPU_NUM_SANITIZE=1 (set it by hand over `pytest -m
    "valuefaults or byzantine"`) runs EVERY test with graftnum's
    runtime twin installed: exported round metrics pass a
    post-dispatch finite guard, so poison that screening or robust
    aggregation should have absorbed but that leaked into telemetry
    raises NumericError with the offending metric named. Off by
    default: the metrics patching is global state no unrelated unit
    test should depend on.

    Tests marked `nonfinite_ok` are exempt (the no_sanitize idiom):
    their SUBJECT is deliberate non-finite propagation — the
    poison->trip->rollback drills run with screening off so NaN
    metrics MUST reach the finite-frontier watchdog to exercise it,
    and the finite guard would preempt the NumericTripError path
    under test."""
    if not os.environ.get("CCTPU_NUM_SANITIZE"):
        yield
        return
    if request.node.get_closest_marker("nonfinite_ok") is not None:
        yield
        return
    from commefficient_tpu.analysis.runtime import NumericSanitizer

    san = NumericSanitizer()
    san.install()
    try:
        yield
    finally:
        san.uninstall()


@pytest.fixture(autouse=True)
def _sync_sanitize():
    """CCTPU_SYNC_SANITIZE=1 (set it by hand over `pytest -m
    "pipeline or statetier or controlplane"`) runs EVERY test under the
    LockOrderSanitizer plus deterministic queue-handoff delay
    injection (analysis/runtime.interleaving_stress), and asserts the
    observed lock graph acyclic at teardown. Off by default: the
    factory patching is global state no unrelated unit test should
    depend on."""
    if not os.environ.get("CCTPU_SYNC_SANITIZE"):
        yield
        return
    from commefficient_tpu.analysis.runtime import (
        LockOrderSanitizer, interleaving_stress,
    )

    san = LockOrderSanitizer()
    san.install()
    try:
        with interleaving_stress():
            yield
    finally:
        san.uninstall()
    san.assert_acyclic()


@pytest.fixture
def ckpt_dir(tmp_path):
    """Isolated checkpoint directory per test: checkpoint/rotation
    tests never see each other's manifests or stamped files."""
    d = tmp_path / "ckpts"
    d.mkdir()
    return str(d)

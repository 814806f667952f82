"""graftlint: trace-safety static analysis + runtime sanitizers.

PRs 1-2 bought the round engine hard guarantees — exactly three traced
round programs (mask-free, dropout, dropout+stragglers), bit-exact
crash->resume, PRNG domain separation between the dropout and straggler
draws — but nothing enforced them except hand-written tests a future
refactor could silently rot. This package makes the invariants
mechanical:

  * `engine` + `rules` — an AST lint pass (``python -m
    commefficient_tpu.analysis <paths>``) with JAX-specific rules
    GL001-GL015: host nondeterminism reachable from traced code, hidden
    host syncs / trace breaks, PRNG key reuse, Python control flow over
    traced values, fault-swallowing broad ``except`` handlers,
    non-atomic file writes, unconstrained shard_map/pjit layouts,
    large exact top-k, PRNG domain tags outside the `domains`
    registry, mesh-axis names outside its MESH_AXES registry,
    wall-clock durations, anonymous threads, float equality on
    traced values (the exact-zero sparsity test stays legal),
    controller wire fields outside its CONTROL_FIELDS registry, and
    strided subscripts in the traced packages (a gather on jax 0.9.0).
    Per-line ``# graftlint: disable=GLxxx`` suppressions and
    a baseline file grandfather justified hits.
  * `audit` + `costmodel` — the SECOND tier (``graftaudit``, ISSUE 7):
    traces the three round programs per config to ClosedJaxprs
    and walks the program itself — forbidden host-interaction
    primitives, f64, large exact sorts, population-scaling buffers
    (with the named client-state inventory), buffer-donation coverage,
    and a static FLOPs/HBM cost report gated against the committed
    ``audit.baseline.json``.
  * `shardaudit` — the THIRD tier (``graftmesh`` / ``graftaudit
    --mesh``, ISSUE 8): traces the round programs + the scanned span
    under explicit multi-device meshes (the real parallel/mesh.py
    constructors on the simulated 8-device host platform) and checks
    the sharding/collective contracts — replication across the
    clients axis, population-scaling collectives, missing shardings,
    link-class placement (one table-sized DCN reduction per round),
    resharding vs the single-device program — plus a deterministic
    per-link ICI/DCN byte report gated against
    ``meshaudit.baseline.json`` (rules AU007-AU011; exit 1 =
    violations, 2 = baseline drift, shared with graftaudit).
  * `syncaudit` — the FOURTH tier (``graftsync``, ISSUE 14): pure-AST
    over the five host packages, checking the shared-state guard
    registry, the static lock-order graph, queue-ownership transfer,
    blocking-under-lock, thread lifecycle, and the named
    happens-before edges in `domains.ORDERING_EDGES` (rules
    SY001-SY006; empty exact-match ``graftsync.baseline.json``).
  * `numaudit` — the FIFTH tier (``graftnum``, ISSUE 18): re-walks
    every registered ClosedJaxpr with a dtype/finiteness dataflow
    lattice — NaN-unsafe mask arithmetic (the PR-16 ``t * mask``
    class), unregistered precision downcasts vs
    `domains.PRECISION_SEAMS` + sub-f32 error-feedback residuals,
    unguarded division/rsqrt/log/sqrt, replay-nondeterministic
    primitives — and prices cross-shard psum reassociation as a
    worst-case ulp bound per program, gated exact-match in
    ``graftnum.baseline.json`` (rules NU001-NU005; empty violations
    baseline).
  * `domains` — the central registries: PRNG-domain tags (dropout /
    straggler / sampler) whose uniqueness GL009 and an import-time
    assert both enforce, the MESH_AXES axis-name registry GL010
    holds the sharding layer to, the SHARED_STATE guard map and
    ORDERING_EDGES happens-before registry graftsync enforces, and
    the PRECISION_SEAMS lossy-cast registry graftnum enforces.
  * `runtime` — sanitizers armed by tests: ``assert_program_count(n)``
    (a compilation counter enforcing the three-programs contract),
    ``forbid_transfers()`` (``jax.transfer_guard`` proving the jitted
    round performs zero implicit host transfers), the
    ``LockOrderSanitizer`` (observed lock-acquisition graph asserted
    acyclic — graftsync's runtime twin), and the
    ``NumericSanitizer`` (post-dispatch finite guard over exported
    round metrics + the bitwise replay drill — graftnum's runtime
    twin).

The lint pass is deliberately jax-free (pure ``ast``) so it runs in
any environment — only `runtime` and `audit`'s tracing functions
import jax (lazily, with JAX_PLATFORMS pinned to cpu in the CLI so
the auditor never claims an accelerator).
"""
from commefficient_tpu.analysis.engine import (  # noqa: F401
    Baseline, LintError, Violation, lint_paths, lint_source,
)
from commefficient_tpu.analysis.rules import ALL_RULES, RULE_DOCS  # noqa: F401

"""graftnum (ISSUE 18): the jaxpr-level numerics & determinism
auditor, its ulp baseline, and the runtime NumericSanitizer.

What is pinned here, in the order the tentpole's claims make it
load-bearing:

  * every rule NU001-NU004 FIRES on a seeded positive control and
    stays QUIET on the matching negative — an auditor whose rules
    stop firing is worse than none (it keeps certifying the tree
    clean);
  * the NU001 positive control re-creates the PR-16 bug CLASS on a
    SCRATCH COPY of the package: swapping one shipped
    `where(admitted > 0, t, 0)` admission guard back to `t * mask`
    turns the audit red, while the shipped `where` form audits clean
    (the tree itself is never mutated);
  * the SHIPPED baseline has EMPTY violations and the tree audits
    clean against its exact-match ulp block — the "apply every real
    finding" satellite, kept honest forever;
  * the report digest is bit-identical across independent runs, and
    the journaled `num_audit_digest` event validates;
  * the NumericSanitizer catches a NaN leaking into an exported
    metrics vector, the replay drill catches a dispatch-to-dispatch
    divergence, and both stay green on finite/deterministic runs.
"""
import json
import os
import shutil
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from commefficient_tpu.analysis.numaudit import (
    NUM_RULE_DOCS, NumBaseline, determinism_findings, lattice_findings,
    precision_findings, report_digest, run_num_audit,
)

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def rules_of(findings):
    return sorted({f.rule for f in findings})


@pytest.fixture(scope="module")
def audit_report():
    """ONE full tree audit (the baseline's program set), shared by the tree-clean / digest / journal gates below.
    ~seconds on CPU: every program the engine registers is traced."""
    cwd = os.getcwd()
    os.chdir(REPO)
    try:
        report, findings = run_num_audit()
    finally:
        os.chdir(cwd)
    return report, findings


# ---------------------------------------------------------------------------
# per-rule positive/negative controls on hand-built programs


def test_nu001_poisoned_value_times_mask_fires():
    """The PR-16 class in miniature: a value that MAY be non-finite
    (a poison `where(flag, inf, t)` injection) multiplied by a 0/1
    admission mask — NaN*0 == NaN, so the masked-out lane leaks."""
    def f(t, flag, admitted):
        poisoned = jnp.where(flag, jnp.inf, t)
        mask = (admitted > 0).astype(jnp.float32)
        return (poisoned * mask).sum()

    closed = jax.make_jaxpr(f)(
        jnp.ones((4,)), jnp.zeros((4,), bool), jnp.ones((4,)))
    assert "NU001" in rules_of(lattice_findings("ctl", closed))


def test_nu001_where_guard_is_quiet():
    """The shipped admission idiom: the same poisoned value routed
    through `where(mask > 0, t, 0)` is finite-by-contract."""
    def f(t, flag, admitted):
        poisoned = jnp.where(flag, jnp.inf, t)
        return jnp.where(admitted > 0, poisoned,
                         jnp.zeros_like(poisoned)).sum()

    closed = jax.make_jaxpr(f)(
        jnp.ones((4,)), jnp.zeros((4,), bool), jnp.ones((4,)))
    assert lattice_findings("ctl", closed) == []


def test_nu001_scalar_enable_flag_is_not_mask_arithmetic():
    """A scalar {0,1} factor (an enable flag, a literal scale) is not
    the per-lane indicator pattern NU001 is about."""
    def f(t, flag, enable):
        poisoned = jnp.where(flag, jnp.inf, t)
        return (poisoned * (enable > 0).astype(jnp.float32)).sum()

    closed = jax.make_jaxpr(f)(
        jnp.ones((4,)), jnp.zeros((4,), bool), jnp.asarray(1.0))
    assert "NU001" not in rules_of(lattice_findings("ctl", closed))


def test_nu001_defensive_nan_select_over_finite_input_is_quiet():
    """jnp.median's internal `where(any(x != x), nan, x)` sentinel:
    over a proven-finite input the predicate folds to False, so the
    NaN literal is dead — the lattice must NOT read it as an
    injection (this is what keeps the shipped nanmedian screening
    clean without baselining)."""
    def f(x, admitted):
        med = jnp.median(x)
        mask = (admitted > 0).astype(jnp.float32)
        return (med * mask).sum()

    closed = jax.make_jaxpr(f)(jnp.ones((8,)), jnp.ones((4,)))
    assert lattice_findings("ctl", closed) == []


def test_nu003_raw_denominator_fires_and_eps_max_is_quiet():
    raw = jax.make_jaxpr(lambda x, n: x / n)(
        jnp.ones((4,)), jnp.ones(()))
    assert "NU003" in rules_of(lattice_findings("ctl", raw))
    guarded = jax.make_jaxpr(lambda x, n: x / jnp.maximum(n, 1.0))(
        jnp.ones((4,)), jnp.ones(()))
    assert lattice_findings("ctl", guarded) == []


def test_nu003_sqrt_needs_nonneg_proof():
    raw = jax.make_jaxpr(jnp.sqrt)(jnp.ones((4,)))
    assert "NU003" in rules_of(lattice_findings("ctl", raw))
    squared = jax.make_jaxpr(lambda x: jnp.sqrt(jnp.sum(x * x)))(
        jnp.ones((4,)))
    assert lattice_findings("ctl", squared) == []


def test_nu003_log_and_rsqrt_need_positive_proof():
    for fn in (jnp.log, jax.lax.rsqrt):
        raw = jax.make_jaxpr(fn)(jnp.ones((4,)))
        assert "NU003" in rules_of(lattice_findings("ctl", raw)), fn
        guarded = jax.make_jaxpr(
            lambda x, fn=fn: fn(jnp.maximum(x * x, 1e-12)))(
            jnp.ones((4,)))
        assert lattice_findings("ctl", guarded) == [], fn


def test_nu002_unregistered_downcast_fires_registered_seam_quiet():
    """float32->float16 is NOT a registered seam; float32->bfloat16 is
    (sketch-wire-bf16, the PR-6 wire-quantization pair)."""
    f16 = jax.make_jaxpr(lambda x: x.astype(jnp.float16))(
        jnp.ones((4,), jnp.float32))
    assert "NU002" in rules_of(
        precision_findings("ctl", f16, ["x"], ["out"]))
    bf16 = jax.make_jaxpr(lambda x: x.astype(jnp.bfloat16))(
        jnp.ones((4,), jnp.float32))
    assert precision_findings("ctl", bf16, ["x"], ["out"]) == []


def test_nu002_error_feedback_residual_must_be_f32_or_wider():
    narrow = jax.make_jaxpr(lambda e: e + 1.0)(
        jnp.zeros((4,), jnp.float16))
    assert "NU002" in rules_of(precision_findings(
        "ctl", narrow, ["clients_error"], ["out_error"]))
    wide = jax.make_jaxpr(lambda e: e + 1.0)(
        jnp.zeros((4,), jnp.float32))
    assert precision_findings(
        "ctl", wide, ["clients_error"], ["out_error"]) == []


def test_nu004_unstable_sort_fires_stable_is_quiet():
    unstable = jax.make_jaxpr(
        lambda x: jax.lax.sort(x, is_stable=False))(jnp.ones((8,)))
    assert "NU004" in rules_of(determinism_findings("ctl", unstable))
    stable = jax.make_jaxpr(
        lambda x: jax.lax.sort(x, is_stable=True))(jnp.ones((8,)))
    assert determinism_findings("ctl", stable) == []


def test_nu004_unpinned_recall_target_fires():
    unpinned = jax.make_jaxpr(
        lambda x: jax.lax.approx_max_k(x, 2, recall_target=0.5))(
        jnp.ones((32,)))
    assert "NU004" in rules_of(determinism_findings("ctl", unpinned))
    pinned = jax.make_jaxpr(
        lambda x: jax.lax.approx_max_k(x, 2, recall_target=0.95))(
        jnp.ones((32,)))
    assert determinism_findings("ctl", pinned) == []


def test_nu004_promise_in_bounds_scatter_fires():
    def promised(x, idx, v):
        return x.at[idx].set(v, mode="promise_in_bounds")

    def defaulted(x, idx, v):
        return x.at[idx].set(v)

    args = (jnp.ones((8,)), jnp.asarray([1, 2]), jnp.ones((2,)))
    assert "NU004" in rules_of(determinism_findings(
        "ctl", jax.make_jaxpr(promised)(*args)))
    assert determinism_findings(
        "ctl", jax.make_jaxpr(defaulted)(*args)) == []


# ---------------------------------------------------------------------------
# the PR-16 red control: shipped `where` guard swapped back to `t * mask`
# on a scratch copy of the package

# the shipped admission guard in federated/round.py (screened local
# aggregation) and its NaN-unsafe PR-16-class rewrite; textual swap so
# the fixture rots loudly if the shipped idiom is refactored
_SHIPPED_WHERE = """\
                        local_sum = jax.tree.map(
                            lambda t: jnp.where(
                                surv_eff.reshape(
                                    surv_eff.shape
                                    + (1,) * (t.ndim - 1)) > 0,
                                t, jnp.zeros_like(t)).sum(axis=0),
                            tx)"""
_MASK_MUL = """\
                        local_sum = jax.tree.map(
                            lambda t: (t * (surv_eff.reshape(
                                surv_eff.shape
                                + (1,) * (t.ndim - 1)) > 0)).sum(axis=0),
                            tx)"""

_RED_DRIVER = """\
import json
import sys

from commefficient_tpu.analysis.audit import (
    audit_configs, build_workload, trace_variant,
)
from commefficient_tpu.analysis.numaudit import lattice_findings

cfg = dict(audit_configs())["sketch-screened"]
handle, server, clients, variants, lr, key = build_workload(cfg)
closed, _, _ = trace_variant(
    handle, server, clients, variants["screened"], lr, key)
findings = lattice_findings("sketch-screened/screened", closed)
print(json.dumps(sorted({f.rule for f in findings})))
"""


@pytest.mark.valuefaults
def test_pr16_mask_multiply_regression_turns_audit_red(tmp_path):
    """The acceptance gate: on a SCRATCH copy of the package, swap the
    shipped screened-aggregation `where(surv_eff > 0, t, 0)` guard
    for the `t * mask` form PR 16 fixed — the NU001 walk over the
    re-traced screened program must fire. The shipped form's
    cleanliness is the tree-clean gate (test_shipped_baseline_...):
    the whole tree audits with zero findings."""
    pkg = tmp_path / "scratch"
    shutil.copytree(
        os.path.join(REPO, "commefficient_tpu"),
        pkg / "commefficient_tpu",
        ignore=shutil.ignore_patterns("__pycache__"))
    round_py = pkg / "commefficient_tpu" / "federated" / "round.py"
    src = round_py.read_text()
    assert src.count(_SHIPPED_WHERE) == 1, (
        "fixture rot: the shipped screened-admission where-guard "
        "moved — update _SHIPPED_WHERE/_MASK_MUL")
    round_py.write_text(src.replace(_SHIPPED_WHERE, _MASK_MUL))

    env = dict(os.environ, PYTHONPATH=str(pkg), JAX_PLATFORMS="cpu")
    # cwd must NOT be the repo root: sys.path[0]='' would shadow the
    # scratch copy with the shipped package
    proc = subprocess.run(
        [sys.executable, "-c", _RED_DRIVER], cwd=tmp_path, env=env,
        capture_output=True, text=True, timeout=600)
    assert proc.returncode == 0, proc.stderr[-2000:]
    fired = json.loads(proc.stdout.strip().splitlines()[-1])
    assert "NU001" in fired, (fired, proc.stderr[-2000:])


# ---------------------------------------------------------------------------
# tree-clean / baseline / digest / journal gates


def test_shipped_baseline_is_empty_and_tree_is_clean(audit_report):
    """The acceptance gate: graftnum exits 0 on the tree with EMPTY
    shipped violations and an exact-match ulp block — every real
    finding was applied, none grandfathered."""
    report, findings = audit_report
    assert findings == [], [f.render() for f in findings]
    assert report["rules"] == {r: 0 for r in NUM_RULE_DOCS}

    with open(os.path.join(REPO, "graftnum.baseline.json")) as f:
        shipped = json.load(f)
    assert shipped["violations"] == []
    baseline = NumBaseline.load(
        os.path.join(REPO, "graftnum.baseline.json"))
    new, stale = baseline.apply_violations(findings)
    drift = baseline.apply_costs(report["ulp"], tolerance=0.0)
    assert new == [] and stale == []
    assert drift == [], [f.render() for f in drift]


def test_ulp_block_prices_the_round_programs(audit_report):
    """Cross-shard psum reassociation is PRICED, not flagged: every
    program gets a non-negative integer bound, and the round programs
    (which psum client updates across the 8-way axis) price > 0."""
    report, _ = audit_report
    assert report["ulp"], "no programs audited"
    for prog, d in report["ulp"].items():
        assert isinstance(d["worst_case_ulp"], int) and \
            d["worst_case_ulp"] >= 0, (prog, d)
    assert any(d["worst_case_ulp"] > 0 for d in report["ulp"].values())
    # the scanned span runs SPAN_LEN rounds: it must price at least
    # one round program's bound
    spans = {p: d["worst_case_ulp"] for p, d in report["ulp"].items()
             if p.endswith("/span")}
    rounds = {p: d["worst_case_ulp"] for p, d in report["ulp"].items()
              if p.endswith("/mask_free")}
    assert spans and rounds
    assert max(spans.values()) >= max(rounds.values())


def test_digest_bit_identical_across_independent_runs(audit_report):
    report, _ = audit_report
    assert len(report["digest"]) == 64
    assert report["digest"] == report_digest(report)
    cwd = os.getcwd()
    os.chdir(REPO)
    try:
        r1, _ = run_num_audit()
        r2, _ = run_num_audit()
    finally:
        os.chdir(cwd)
    assert r1["digest"] == r2["digest"]


def test_nu005_ulp_drift_is_exit_2_material(audit_report):
    """A moved ulp bound (or a new/stale program) is NU005 drift, not
    a rule violation — the regenerate-and-commit workflow."""
    report, _ = audit_report
    drifted = {p: dict(d) for p, d in report["ulp"].items()}
    prog = next(iter(drifted))
    drifted[prog]["worst_case_ulp"] += 1
    baseline = NumBaseline({}, drifted)
    findings = baseline.apply_costs(report["ulp"], tolerance=0.0)
    assert findings and all(f.rule == "NU005" for f in findings)
    exact = NumBaseline({}, report["ulp"])
    assert exact.apply_costs(report["ulp"], tolerance=0.0) == []


def test_journaled_num_digest_validates(audit_report, tmp_path):
    from commefficient_tpu.analysis.numaudit import journal_digest
    from commefficient_tpu.telemetry.journal import (
        summarize, validate_journal,
    )
    report, findings = audit_report
    path = str(tmp_path / "journal.jsonl")
    journal_digest(path, report, len(findings))
    records, problems = validate_journal(path)
    assert problems == []
    assert records[0]["event"] == "num_audit_digest"
    assert records[0]["digest"] == report["digest"]
    s = summarize(records)
    assert s["analysis_digests"]["num_audit_digest"] == \
        report["digest"]
    assert s["num_audit_findings"] == 0
    # and the validator actually checks: corrupt the digest and a ulp
    # entry
    rec = dict(records[0])
    rec["digest"] = "short"
    rec["ulp"] = {"prog": -3}
    with open(path, "w") as f:
        f.write(json.dumps(rec) + "\n")
    _, problems = validate_journal(path)
    assert any("64-char" in p for p in problems)
    assert any("ulp" in p for p in problems)


# ---------------------------------------------------------------------------
# the runtime twin: NumericSanitizer


@pytest.mark.nonfinite_ok  # deliberately exports NaN after uninstall
def test_sanitizer_catches_nan_in_exported_metrics():
    from commefficient_tpu.analysis.runtime import (
        NumericError, NumericSanitizer,
    )
    from commefficient_tpu.telemetry import metrics as tmetrics
    vec = jnp.arange(float(tmetrics.NUM_METRICS))
    bad = vec.at[2].set(jnp.nan)
    san = NumericSanitizer()
    san.install()
    try:
        assert tmetrics.named(vec)["update_l2"] == 1.0
        assert san.checked == 1
        with pytest.raises(NumericError, match="error_l2"):
            tmetrics.named(bad)
    finally:
        san.uninstall()
    # uninstalled: the raw export is back (no guard, no raise)
    assert tmetrics.named(bad)
    assert san.checked >= 2


def test_sanitizer_fixture_is_scoped(num_sanitizer):
    from commefficient_tpu.telemetry import metrics as tmetrics
    tmetrics.named(jnp.zeros((tmetrics.NUM_METRICS,)))
    assert num_sanitizer.checked == 1


def test_assert_finite_walks_trees():
    from commefficient_tpu.analysis.runtime import (
        NumericError, NumericSanitizer,
    )
    NumericSanitizer.assert_finite(
        {"w": jnp.ones((3,)), "n": np.arange(4)}, where="ok tree")
    with pytest.raises(NumericError, match="poisoned"):
        NumericSanitizer.assert_finite(
            {"w": jnp.asarray([1.0, jnp.inf])}, where="poisoned")


def test_replay_drill_passes_deterministic_dispatch():
    from commefficient_tpu.analysis.runtime import NumericSanitizer

    @jax.jit
    def step(x):
        return {"y": jnp.cumsum(x) / jnp.maximum(x.sum(), 1.0)}

    out = NumericSanitizer.replay_drill(step, jnp.arange(8.0))
    np.testing.assert_allclose(
        np.asarray(out["y"])[-1], 1.0, rtol=1e-6)


def test_replay_drill_catches_dispatch_divergence():
    from commefficient_tpu.analysis.runtime import (
        NumericError, NumericSanitizer,
    )
    calls = []

    def flaky(x):
        calls.append(None)
        return x + float(len(calls))

    with pytest.raises(NumericError, match="bitwise"):
        NumericSanitizer.replay_drill(flaky, jnp.ones((4,)))


@pytest.mark.valuefaults
def test_replay_drill_on_a_real_round_program():
    """The determinism drill the tentpole promises: dispatch a traced
    round program twice on identical operands and assert bitwise
    equality — run on the real sketch round step at audit geometry."""
    from commefficient_tpu.analysis.audit import (
        audit_configs, build_workload,
    )
    from commefficient_tpu.analysis.runtime import NumericSanitizer
    cfg = dict(audit_configs())["sketch"]
    handle, server, clients, variants, lr, key = build_workload(cfg)
    batch = variants["mask_free"]
    cohort = handle.gather_fn(clients, batch.client_ids)
    out = NumericSanitizer.replay_drill(
        handle.round_step, server, cohort, batch, lr, key)
    assert out is not None
    NumericSanitizer.assert_finite(out, where="sketch round output")

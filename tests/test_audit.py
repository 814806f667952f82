"""graftaudit (analysis/audit + analysis/costmodel): the jaxpr-level
program auditor. Three concerns, mirroring test_graftlint's shape for
the second analysis tier:

  * the TREE audits clean against the SHIPPED baseline — the
    committed `audit.baseline.json` must match what the auditor finds
    and prices right now (the CI gate, run here so `pytest` alone
    catches a drifted baseline before scripts/audit.sh does);
  * seeded POSITIVE CONTROLS — each violation class (forbidden
    primitive, f64, large exact top-k/sort, population-shaped
    intermediate, undonated dead input, cost drift) must fire with
    the right rule id, so the auditor itself can't silently rot;
  * the DONATION finding applied (ISSUE 7 satellite): donation on vs
    off is bit-identical, including across a save/restore boundary,
    and the donated configuration still satisfies the three-programs
    and zero-implicit-transfer sanitizer contracts.
"""
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from commefficient_tpu.analysis import audit as A
from commefficient_tpu.analysis.costmodel import jaxpr_cost
from commefficient_tpu.config import Config
from commefficient_tpu.federated.round import (
    PROGRAM_VARIANTS, ROUND_DEAD_ARGNUMS, SPAN_DEAD_ARGNUMS,
    RoundBatch, init_client_state, init_server_state, make_train_fn,
    program_variant, program_variants_for,
)
from commefficient_tpu.ops.flat import flatten_params

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BASELINE = os.path.join(REPO, "audit.baseline.json")


@pytest.fixture(scope="module")
def full_audit():
    """One shared full audit (9 traced programs) for every test that
    only reads the result."""
    return A.run_audit()


# ---------------------------------------------------------------------------
# the tree is clean against the shipped baseline


def test_tree_audits_clean_against_shipped_baseline(full_audit):
    report, findings = full_audit
    assert findings == [], [f.render() for f in findings]
    baseline = A.AuditBaseline.load(BASELINE)
    new, stale = baseline.apply_violations(findings)
    assert new == [] and stale == []
    assert baseline.apply_costs(report["costs"], tolerance=0.0) == []


def test_shipped_baseline_has_no_unjustified_violations():
    """Acceptance contract: the committed baseline is empty or carries
    justified entries only — a TODO justification is a violation that
    was grandfathered without thought."""
    baseline = A.AuditBaseline.load(BASELINE)
    for (program, rule), (count, justification) in sorted(
            baseline.violations.items()):
        assert justification and "TODO" not in justification, (
            f"unjustified baseline entry: {program} {rule} x{count}")


def test_audit_covers_programs(full_audit):
    report, _ = full_audit
    for cfg_name, _cfg in A.audit_configs():
        # per-config program family (ISSUE 16): sketch-screened traces
        # the two screened variants, every other config the defaults
        for variant in program_variants_for(_cfg):
            assert f"{cfg_name}/{variant}" in report["programs"]


def test_population_inventory_names_the_client_state(full_audit):
    """The AU004 inventory is the million-client refactor's shopping
    list: all three dense per-client blocks, named, with population-
    scaled shapes, on both the input and carried-output side."""
    report, _ = full_audit
    # ISSUE 9: the ROUND programs are population-free — empty
    # inventory on the jitted-round side for every audit config (the
    # refactor's mechanical definition of done)
    for cfg_name in ("client-state", "sketch"):
        for variant in ("mask_free", "dropout", "dropout_stragglers"):
            inv = report["programs"][f"{cfg_name}/{variant}"][
                "population_inventory"]
            assert inv["inputs"] == [] and inv["outputs"] == [], (
                cfg_name, variant)
    # the named client-state map now lives on the two state-motion
    # programs: gather reads all three dense blocks, scatter carries
    # them in AND out
    names = {"clients.errors.tiles", "clients.velocities.tiles",
             "clients.weights.tiles"}
    g = report["programs"]["client-state/gather"][
        "population_inventory"]
    assert {e["name"] for e in g["inputs"]} == names
    s = report["programs"]["client-state/scatter"][
        "population_inventory"]
    assert {e["name"] for e in s["inputs"]} == names
    assert {e["name"] for e in s["outputs"]} == names
    for e in g["inputs"] + s["inputs"] + s["outputs"]:
        assert e["shape"][0] == A.AUDIT_POPULATION
    # the stateless sketch configs' state-motion programs move nothing
    sk = report["programs"]["sketch/gather"][
        "population_inventory"]
    assert sk["inputs"] == [] and sk["outputs"] == []


def test_cost_report_bit_identical_across_runs():
    """Acceptance: the journaled cost report reproduces bit-identically
    — two fully independent audits must agree on the digest."""
    r1, _ = A.run_audit()
    r2, _ = A.run_audit()
    assert r1["digest"] == r2["digest"]
    assert r1["costs"] == r2["costs"]


def test_au003_threshold_matches_gl008():
    from commefficient_tpu.analysis.rules import GL008_MIN_K
    assert A.TOPK_MIN_K == GL008_MIN_K


# ---------------------------------------------------------------------------
# seeded positive controls: every rule must fire on its violation class


def test_au001_host_callback_fires():
    def f(x):
        jax.debug.print("x={x}", x=x)
        return x * 2.0

    closed = jax.make_jaxpr(f)(jnp.ones(4))
    rules = {v.rule for v in
             A.forbidden_primitive_findings("p", closed)}
    assert "AU001" in rules


def test_au002_f64_fires():
    with jax.enable_x64(True):
        closed = jax.make_jaxpr(
            lambda x: x.astype(jnp.float64).sum())(
            jnp.ones(4, jnp.float32))
    rules = {v.rule for v in
             A.forbidden_primitive_findings("p", closed)}
    assert "AU002" in rules


def test_au003_large_exact_topk_and_sort_fire():
    closed = jax.make_jaxpr(
        lambda v: jax.lax.top_k(v, A.TOPK_MIN_K))(
        jnp.ones(4 * A.TOPK_MIN_K))
    assert "AU003" in {v.rule for v in
                       A.forbidden_primitive_findings("p", closed)}
    closed = jax.make_jaxpr(lambda v: jnp.sort(v))(
        jnp.ones(A.SORT_MIN_N))
    assert "AU003" in {v.rule for v in
                       A.forbidden_primitive_findings("p", closed)}
    # below both thresholds: quiet (approx_max_k's small exact tail,
    # the audit geometry's own tiny sorts)
    closed = jax.make_jaxpr(
        lambda v: jax.lax.top_k(jnp.sort(v), 16))(jnp.ones(1024))
    assert A.forbidden_primitive_findings("p", closed) == []
    # the sketch median's r-wide LANE sort over a huge table sorts a
    # short dimension — wide operand, cheap sort, must stay quiet
    # (the false positive the flagship-geometry trace exposed)
    closed = jax.make_jaxpr(
        lambda t: jnp.median(t, axis=0))(
        jnp.ones((5, A.SORT_MIN_N)))
    assert A.forbidden_primitive_findings("p", closed) == []


def test_au004_population_intermediate_fires():
    P = A.AUDIT_POPULATION

    def leaky(rows, ids):
        # a population-sized INTERMEDIATE: scaling all rows before the
        # cohort gather materializes a [P, 4] temp per dispatch
        scaled = rows * 2.0
        return scaled[ids].sum()

    rows = jnp.ones((P, 4))
    ids = jnp.arange(3)
    closed, shape = jax.make_jaxpr(leaky, return_shape=True)(rows, ids)
    inventory, findings = A.population_scan(
        "p", closed, P, ["rows", "ids"], ["out"])
    assert {v.rule for v in findings} == {"AU004"}
    assert [e["name"] for e in inventory["inputs"]] == ["rows"]

    def leaky_twice(rows, ids):
        # TWO distinct equations with identical findings (same
        # primitive, same shape) must yield TWO findings — a set-dedup
        # here would let the second occurrence hide behind a count=1
        # baseline entry
        a = rows * 2.0
        b = rows * 3.0
        c = b * (1.0 / 3.0)
        return a[ids].sum() + c[ids].sum()

    closed, _ = jax.make_jaxpr(leaky_twice, return_shape=True)(rows, ids)
    _, findings = A.population_scan(
        "p", closed, P, ["rows", "ids"], ["out"])
    assert len([v for v in findings if v.rule == "AU004"]) >= 2

    def clean(rows, ids):
        # gather -> cohort-sized compute -> scatter back: the carried-
        # state pattern the round engine uses; no intermediate scales
        # with the population
        got = rows[ids] * 2.0
        return rows.at[ids].set(got)

    closed, shape = jax.make_jaxpr(clean, return_shape=True)(rows, ids)
    _, findings = A.population_scan(
        "p", closed, P, ["rows", "ids"], ["out"])
    assert findings == []


def test_au005_undonated_dead_inputs_fire():
    cfg = dict(A.audit_configs())["sketch"]
    handle, *_ = A.build_workload(
        cfg.replace(donate_round_state=False))
    findings = A.donation_findings("sketch", handle)
    assert {v.rule for v in findings} == {"AU005"}
    # per-round cohort + scatter-back clients + scanned server +
    # scanned clients
    from commefficient_tpu.federated.round import SCATTER_DEAD_ARGNUMS
    assert len(findings) == (len(ROUND_DEAD_ARGNUMS)
                             + len(SCATTER_DEAD_ARGNUMS)
                             + len(SPAN_DEAD_ARGNUMS))
    # with donation wired (the default) the same config is clean
    handle_on, *_ = A.build_workload(cfg)
    assert A.donation_findings("sketch", handle_on) == []


def test_au006_cost_drift_new_and_stale_fire(full_audit):
    report, _ = full_audit
    costs = dict(report["costs"])
    some_prog = sorted(costs)[0]
    baseline = A.AuditBaseline(costs={
        p: dict(c) for p, c in costs.items()})
    # exact match: clean
    assert baseline.apply_costs(costs, tolerance=0.0) == []
    # +7% flops drift: beyond 5% tolerance -> AU006; within 10% -> ok
    drifted = {p: dict(c) for p, c in costs.items()}
    drifted[some_prog]["flops"] = int(
        drifted[some_prog]["flops"] * 1.07)
    hits = baseline.apply_costs(drifted, tolerance=0.05)
    assert {v.rule for v in hits} == {"AU006"}
    assert any(some_prog == v.program for v in hits)
    assert baseline.apply_costs(drifted, tolerance=0.10) == []
    # a program with no baseline entry is NEW -> AU006
    extra = dict(costs)
    extra["novel/program"] = {"flops": 1, "hbm_bytes": 1}
    assert any(v.program == "novel/program" and v.rule == "AU006"
               for v in baseline.apply_costs(extra, tolerance=0.0))
    # a baseline entry with no traced program is STALE -> AU006
    missing = {p: c for p, c in costs.items() if p != some_prog}
    assert any(v.program == some_prog and "stale" in v.message
               for v in baseline.apply_costs(missing, tolerance=0.0))


def test_exit_code_split_violations_vs_drift():
    """ISSUE 8 satellite: graftaudit shares graftmesh's exit-code
    contract — rule violations exit 1, baseline drift (AU006 / stale
    entries) exits 2 — so CI can route 'program broke a contract' and
    're-commit the baseline' differently."""
    from commefficient_tpu.analysis.shardaudit import (
        exit_code, split_findings,
    )

    rule_hit = A.AuditFinding("p/x", "AU002", "f64")
    drift_hit = A.AuditFinding("p/x", "AU006", "cost moved")
    assert split_findings([rule_hit, drift_hit]) == ([rule_hit],
                                                     [drift_hit])
    assert exit_code([rule_hit], [drift_hit], []) == 1
    assert exit_code([], [drift_hit], []) == 2
    assert exit_code([], [], ["stale entry"]) == 2
    assert exit_code([], [], []) == 0


def test_audit_digest_journal_schema(full_audit, tmp_path):
    from commefficient_tpu.telemetry.journal import (
        append_event, validate_journal,
    )
    report, findings = full_audit
    path = str(tmp_path / "audit.jsonl")
    rec = A.journal_digest(path, report, len(findings))
    assert rec["event"] == "audit_digest"
    records, problems = validate_journal(path)
    assert problems == []
    assert records[0]["digest"] == report["digest"]
    # corrupted digests fail validation (the schema the ISSUE adds)
    bad = str(tmp_path / "bad.jsonl")
    append_event(bad, "audit_digest", digest="",
                 programs={"p": {"flops": -1, "hbm_bytes": 2}})
    _, problems = validate_journal(bad)
    assert any("digest" in p for p in problems)
    assert any("flops" in p for p in problems)


# ---------------------------------------------------------------------------
# cost model units


def test_costmodel_prices_dot_general_exactly():
    closed = jax.make_jaxpr(
        lambda a, b: a @ b)(jnp.ones((3, 5)), jnp.ones((5, 7)))
    cost = jaxpr_cost(closed).as_dict()
    assert cost["by_primitive"]["dot_general"]["flops"] == 2 * 3 * 5 * 7


def test_costmodel_scan_multiplies_by_trip_count():
    def body(c, x):
        return c + x * x, c

    def f(xs):
        return jax.lax.scan(body, jnp.float32(0.0), xs)

    c10 = jaxpr_cost(jax.make_jaxpr(f)(jnp.ones(10))).as_dict()
    c40 = jaxpr_cost(jax.make_jaxpr(f)(jnp.ones(40))).as_dict()
    assert c40["flops"] == 4 * c10["flops"]


# ---------------------------------------------------------------------------
# the applied donation finding: bit-exactness + sanitizer contracts


D = 8


def _loss_fn(params, batch, mask):
    x, y = batch
    pred = x @ params["w"]
    per_ex = 0.5 * (pred - y) ** 2
    denom = jnp.maximum(mask.sum(), 1.0)
    loss = (per_ex * mask).sum() / denom
    return loss, (loss,)


def _mini(mesh, donate: bool, num_clients: int = 16):
    params = {"w": jnp.zeros(D)}
    vec, unravel = flatten_params(params)
    cfg = Config(mode="local_topk", error_type="local",
                 local_momentum=0.9, do_topk_down=True, k=4, down_k=2,
                 grad_size=D, weight_decay=0.0, num_workers=8,
                 microbatch_size=-1, num_clients=num_clients,
                 donate_round_state=donate).validate()
    handle = make_train_fn(_loss_fn, unravel, cfg, mesh)
    server = init_server_state(cfg, vec, mesh=mesh)
    # mesh-placed, the production pattern: the scatter-back jit pins
    # P('clients', None, None) out_shardings, and donation only aliases when
    # the input already lives in that layout
    clients = init_client_state(cfg, num_clients, vec, mesh=mesh)
    rng = np.random.RandomState(7)
    x = jnp.asarray(rng.randn(8, 4, D).astype(np.float32))
    y = jnp.asarray(rng.randn(8, 4).astype(np.float32))
    batch = RoundBatch(jnp.arange(8, dtype=jnp.int32), (x, y),
                       jnp.ones((8, 4)))
    return handle, server, clients, batch


def _run(handle, server, clients, batch, rounds, key):
    for _ in range(rounds):
        server, clients, _ = handle(server, clients, batch, 0.1, key)
    return server, clients


def _state_bytes(tree):
    return [np.asarray(leaf).tobytes() for leaf in jax.tree.leaves(tree)]


def test_donation_is_bit_identical(mesh):
    """Donation is aliasing, not math: N rounds donated == N rounds
    undonated, bit for bit, across server AND client state."""
    key = jax.random.PRNGKey(3)
    h_on, s_on, c_on, b_on = _mini(mesh, donate=True)
    h_off, s_off, c_off, b_off = _mini(mesh, donate=False)
    s_on, c_on = _run(h_on, s_on, c_on, b_on, 5, key)
    s_off, c_off = _run(h_off, s_off, c_off, b_off, 5, key)
    assert _state_bytes(s_on) == _state_bytes(s_off)
    assert _state_bytes(c_on) == _state_bytes(c_off)


def test_donation_resume_bit_exact(mesh):
    """The ISSUE's resume proof: a straight 6-round donated run ==
    3 rounds + host save/restore + 3 rounds, bit for bit. Donation
    must not leak state identity across the checkpoint boundary (the
    restore path rebuilds arrays from host copies exactly like
    utils/checkpoint + FedModel.load_state do)."""
    key = jax.random.PRNGKey(5)
    h, s, c, b = _mini(mesh, donate=True)
    s_straight, c_straight = _run(h, s, c, b, 6, key)

    from jax.sharding import PartitionSpec as P

    from commefficient_tpu.federated.round import (
        RowBlock, client_state_specs,
    )
    from commefficient_tpu.parallel import multihost as mh

    h2, s2, c2, b2 = _mini(mesh, donate=True)
    s2, c2 = _run(h2, s2, c2, b2, 3, key)
    saved_server = [np.asarray(f) for f in s2]
    saved_clients = [np.asarray(f) for f in c2]
    # restore with the PRODUCTION placement (FedModel.load_state:
    # globalize onto the mesh under the CLIENT_STATE_RULES specs) —
    # a default-placed restore would silently defeat the scatter-back
    # donation aliasing
    s3 = type(s2)(*[mh.globalize(mesh, P(), f) for f in saved_server])
    # the host copies are [rows, D], as a checkpoint holds them; the
    # device's form is the RowBlock's whole tiles
    restored = type(c2)(*[RowBlock.from_rows(f) if f.ndim == 2 else f
                          for f in saved_clients])
    c3 = jax.tree.map(lambda leaf, spec: mh.globalize(mesh, spec, leaf),
                      restored, client_state_specs(restored))
    s3, c3 = _run(h2, s3, c3, b2, 3, key)
    assert _state_bytes(s_straight) == _state_bytes(s3)
    assert _state_bytes(c_straight) == _state_bytes(c3)


def test_donated_dispatch_three_programs_and_no_transfers(
        mesh, sanitize):
    """The donated twins of test_round's sanitizer proofs (those run
    with donation off because they re-dispatch from retained state):
    with state THREADED — the production access pattern — the donated
    config still compiles exactly three programs and performs zero
    implicit transfers in steady state."""
    from jax.sharding import PartitionSpec as P

    from commefficient_tpu.federated.round import client_state_specs
    from commefficient_tpu.parallel import multihost as mh

    h, server, clients, batch = _mini(mesh, donate=True)
    server = jax.tree.map(
        lambda a: mh.globalize(mesh, P(), np.asarray(a)), server)
    clients = jax.tree.map(
        lambda a, spec: mh.globalize(mesh, spec, np.asarray(a)),
        clients, client_state_specs(clients))
    ids = mh.globalize(mesh, P(), np.arange(8, dtype=np.int32))
    data = tuple(mh.shard_rows(mesh, np.asarray(d))
                 for d in batch.data)
    maskv = mh.shard_rows(mesh, np.ones((8, 4), np.float32))
    surv = mh.globalize(mesh, P(),
                        np.ones(8, np.float32))
    work = mh.globalize(mesh, P(),
                        np.full(8, 0.5, np.float32))
    batches = [RoundBatch(ids, data, maskv),
               RoundBatch(ids, data, maskv, survivors=surv),
               RoundBatch(ids, data, maskv, survivors=surv,
                          work=work)]
    assert [program_variant(b) for b in batches] == list(
        PROGRAM_VARIANTS)
    lr = mh.globalize(mesh, P(), np.float32(0.1))
    key = mh.globalize(mesh, P(), jax.random.PRNGKey(0))

    with sanitize.assert_program_count(2):
        # the state-motion pair compiles once (shared by all variants)
        cohort = h.gather(clients, ids)
        clients = h.scatter(clients, ids, cohort)
    with sanitize.assert_program_count(3):
        for b in batches * 2:  # second sweep: all cache hits
            server, clients, _ = h(server, clients, b, lr, key)
    with sanitize.forbid_transfers():
        for b in batches:
            server, clients, m = h(server, clients, b, lr, key)
    assert np.all(np.isfinite(np.asarray(server.ps_weights)))
    assert np.all(np.isfinite(np.asarray(m.losses)))


def test_donated_operands_are_consumed(mesh):
    """The donation is REAL on this backend: after a dispatch the
    donated ClientState buffers are deleted (reuse raises), while the
    undonated ServerState stays readable — exactly the per-round dead
    set ROUND_DEAD_ARGNUMS declares."""
    h, server, clients, batch = _mini(mesh, donate=True)
    s2, c2, _ = h(server, clients, batch, 0.1, jax.random.PRNGKey(0))
    assert np.all(np.isfinite(np.asarray(server.ps_weights)))
    assert clients.errors.is_deleted()
    with pytest.raises(RuntimeError):
        np.asarray(clients.errors)


def test_fedmodel_trace_hook_returns_three_programs():
    """FedModel.trace_round_programs — the registry hook graftaudit
    uses to audit a REAL workload — yields the three variants' jaxprs
    without executing anything."""
    from commefficient_tpu.federated.api import FedModel

    params = {"w": jnp.zeros(D)}
    cfg = Config(mode="uncompressed", error_type="none",
                 local_momentum=0.0, virtual_momentum=0.0,
                 weight_decay=0.0, num_workers=8, microbatch_size=-1,
                 num_clients=8)
    model = FedModel(None, _loss_fn, cfg, params=params)
    rng = np.random.RandomState(0)
    batch = (np.arange(8, dtype=np.int32),
             (rng.randn(8, 4, D).astype(np.float32),
              rng.randn(8, 4).astype(np.float32)),
             np.ones((8, 4), np.float32))
    jaxprs = model.trace_round_programs(batch)
    assert set(jaxprs) == set(PROGRAM_VARIANTS)
    for closed in jaxprs.values():
        assert jaxpr_cost(closed).as_dict()["flops"] > 0
        assert A.forbidden_primitive_findings("m", closed) == []

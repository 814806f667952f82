"""graftmesh (analysis/shardaudit + the costmodel collective
extension): the mesh-aware third analysis tier, on the 8-device
simulated CPU mesh. Four concerns, mirroring test_audit's shape:

  * the TREE audits clean against the SHIPPED meshaudit.baseline.json
    across all three registered mesh shapes, and the per-link report
    digest is bit-identical across independent runs;
  * seeded POSITIVE CONTROLS for every rule AU007-AU011, so the
    auditor itself can't silently rot;
  * SHARDED-VS-SINGLE-DEVICE round identity: the 8-shard round is
    BIT-identical across mesh placements (flat vs slice-major
    permuted — the placement-invariance the multihost layout depends
    on), per-client state rows are bit-identical even across SHARD
    COUNTS (each row is a per-client computation), and the
    cross-client reductions agree with the single-device program to
    float-association tolerance (psum order across shards is the one
    thing that legitimately reassociates);
  * the exit-code contract (0 clean / 1 violations / 2 baseline
    drift) and the `mesh_audit_digest` journal schema.
"""
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from commefficient_tpu.analysis import shardaudit as M
from commefficient_tpu.analysis.costmodel import (
    MeshLinkModel, collective_cost,
)
from commefficient_tpu.config import Config
from commefficient_tpu.federated.round import (
    RoundBatch, init_client_state, init_server_state, make_train_fn,
)
from commefficient_tpu.ops.flat import flatten_params
from commefficient_tpu.parallel.mesh import (
    make_client_mesh, make_multihost_client_mesh,
)
from commefficient_tpu.telemetry.journal import validate_journal

pytestmark = pytest.mark.mesh

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BASELINE = os.path.join(REPO, "meshaudit.baseline.json")

D, W, B = 1024, 8, 4


@pytest.fixture(scope="module")
def full_mesh_audit():
    """One shared full mesh audit (36 traced programs) for every test
    that only reads the result."""
    return M.run_mesh_audit()


# ---------------------------------------------------------------------------
# tree clean + determinism


def test_tree_audits_clean_against_shipped_baseline(full_mesh_audit):
    report, findings = full_mesh_audit
    assert findings == [], [f.render() for f in findings]
    baseline = M.MeshBaseline.load(BASELINE)
    new, stale = baseline.apply_violations(findings)
    assert new == [] and stale == []
    assert baseline.apply_costs(report["links"], tolerance=0.0) == []


def test_report_covers_programs_meshes(full_mesh_audit):
    report, _ = full_mesh_audit
    assert set(report["meshes"]) == {"clients8", "clients4_model2",
                                     "multislice2"}
    for cfg_name, _cfg in M.mesh_configs():
        for mesh_name in report["meshes"]:
            # per-config program family (ISSUE 16): sketch-screened
            # traces the screened variants plus motion/span
            for program in M.mesh_programs_for(_cfg):
                key = f"{cfg_name}/{program}@{mesh_name}"
                assert key in report["programs"], key


def test_digest_bit_identical_across_runs(full_mesh_audit):
    report, _ = full_mesh_audit
    report2, _ = M.run_mesh_audit()
    assert report["digest"] == report2["digest"]
    assert report["links"] == report2["links"]


def test_multislice_report_splits_traffic(full_mesh_audit):
    """The link model's raison d'etre: the SAME program prices pure
    ICI on the flat mesh and a DCN component on the slice-major one —
    with exactly one table-sized DCN reduction per round."""
    report, _ = full_mesh_audit
    flat = report["links"]["sketch/mask_free@clients8"]
    ms = report["links"]["sketch/mask_free@multislice2"]
    assert flat["dcn_bytes"] == 0 and flat["dcn_collectives"] == 0
    assert ms["dcn_bytes"] > 0 and ms["dcn_collectives"] > 0
    # the span prices SPAN_LEN rounds of the same collectives
    span = report["links"]["sketch/span@multislice2"]
    assert span["dcn_bytes"] == M.SPAN_LEN * ms["dcn_bytes"]


def test_link_model_slice_detection():
    meshes = M.build_meshes()
    ms = meshes["multislice2"]["link"]
    assert dict(ms.axis_slices)["clients"] == 2
    flat = meshes["clients8"]["link"]
    assert dict(flat.axis_slices)["clients"] == 1
    two_d = meshes["clients4_model2"]["link"]
    assert dict(two_d.axis_sizes) == {"clients": 4, "model": 2}
    assert dict(two_d.axis_slices) == {"clients": 1, "model": 1}


def test_collective_cost_hierarchical_ring_math():
    """Hand-checkable formula unit: an all-reduce of a [3, 256] f32
    table (3072 B) over an 8-way clients axis spanning 2 slices
    prices 2*(4-1)*3072*2 ICI bytes + 2*(2-1)*3072 DCN bytes."""
    mesh = make_client_mesh(8)
    table = jnp.zeros((3, 256), jnp.float32)

    from jax import shard_map
    from jax.sharding import PartitionSpec as P

    fn = shard_map(lambda t: jax.lax.psum(t, "clients"), mesh=mesh,
                   in_specs=(P(),), out_specs=P(),
                   axis_names=frozenset({"clients"}))
    closed = jax.make_jaxpr(fn)(table)
    link = MeshLinkModel("ms", (("clients", 8),), (("clients", 2),))
    cost = collective_cost(closed, link)
    assert cost.ici_bytes == 2 * 3 * 3072 * 2
    assert cost.dcn_bytes == 2 * 1 * 3072
    assert cost.dcn_collectives == 1
    flat = MeshLinkModel("flat", (("clients", 8),), (("clients", 1),))
    cost_flat = collective_cost(closed, flat)
    assert cost_flat.ici_bytes == 2 * 7 * 3072
    assert cost_flat.dcn_bytes == 0


# ---------------------------------------------------------------------------
# seeded positive controls, one per rule


def test_au007_replicated_client_rows_fire():
    """A deliberately replicated error-feedback row block — the exact
    million-client failure mode — fires AU007; the production sharded
    placement stays quiet."""
    from jax.sharding import NamedSharding, PartitionSpec as P

    mesh = make_client_mesh(8)
    big = (M.MESH_POPULATION, 2048)          # 1.5 MiB > 1 MiB default
    replicated = jax.device_put(np.zeros(big, np.float32),
                                NamedSharding(mesh, P()))
    sharded = jax.device_put(np.zeros(big, np.float32),
                             NamedSharding(mesh, P("clients", None)))
    fs = M.replication_findings(
        "ctl", [("clients.errors", replicated)], mesh, 1 << 20)
    assert [f.rule for f in fs] == ["AU007"]
    assert "replicated" in fs[0].message
    assert M.replication_findings(
        "ctl", [("clients.errors", sharded)], mesh, 1 << 20) == []


def test_au008_population_length_psum_fires():
    """A psum whose payload carries the population sentinel — wire
    cost scaling with num_clients — fires AU008."""
    from jax import shard_map
    from jax.sharding import PartitionSpec as P

    mesh = make_client_mesh(8)
    pop_vec = jnp.zeros((M.MESH_POPULATION,), jnp.float32)
    fn = shard_map(lambda v: jax.lax.psum(v, "clients"), mesh=mesh,
                   in_specs=(P(),), out_specs=P(),
                   axis_names=frozenset({"clients"}))
    closed = jax.make_jaxpr(fn)(pop_vec)
    link = M.build_meshes(["clients8"])["clients8"]["link"]
    cost = collective_cost(closed, link)
    fs = M.collective_findings("ctl", cost, M.MESH_POPULATION,
                               table_bytes=1024, rounds_per_program=1)
    assert "AU008" in {f.rule for f in fs}
    # a cohort-sized psum of the same kind stays quiet
    cohort = jnp.zeros((W,), jnp.float32)
    closed2 = jax.make_jaxpr(fn)(cohort)
    cost2 = collective_cost(closed2, link)
    assert M.collective_findings("ctl", cost2, M.MESH_POPULATION,
                                 1024, 1) == []


def test_au009_default_placement_fires():
    mesh = make_client_mesh(8)
    default_placed = jnp.zeros((W, B), jnp.float32)  # SingleDevice
    fs = M.replication_findings("ctl", [("batch.mask", default_placed)],
                                mesh, 1 << 20)
    assert [f.rule for f in fs] == ["AU009"]
    # a bare host array (no .sharding at all) is the most-unplaced
    # case and must fire too, not be skipped
    fs2 = M.replication_findings(
        "ctl", [("batch.mask", np.zeros((W, B), np.float32))],
        mesh, 1 << 20)
    assert [f.rule for f in fs2] == ["AU009"]
    assert "no placement" in fs2[0].message


def test_au010_model_axis_dcn_and_double_reduction_fire():
    from commefficient_tpu.analysis.costmodel import CollectiveRecord

    def rec(kind, axes, payload, crosses):
        return CollectiveRecord(kind=kind, axes=axes,
                                payload_bytes=payload,
                                operand_shapes=((payload // 4,),),
                                mult=1, ici_bytes=0,
                                dcn_bytes=payload if crosses else 0,
                                crosses_dcn=crosses)

    from commefficient_tpu.analysis.costmodel import CollectiveCost
    # (a) model-axis collective over DCN
    cost = CollectiveCost()
    cost.add(rec("psum", ("model",), 4096, True))
    fs = M.collective_findings("ctl", cost, M.MESH_POPULATION, 1024, 1)
    assert "AU010" in {f.rule for f in fs}
    # (b) two table-sized DCN reductions in one round
    cost2 = CollectiveCost()
    cost2.add(rec("psum", ("clients",), 4096, True))
    cost2.add(rec("psum", ("clients",), 4096, True))
    fs2 = M.collective_findings("ctl", cost2, M.MESH_POPULATION,
                                1024, 1)
    assert [f.rule for f in fs2] == ["AU010"]
    assert "ONE compressed all-reduce" in fs2[0].message
    # one table reduction + one small scalar reduction is the
    # sanctioned round shape
    cost3 = CollectiveCost()
    cost3.add(rec("psum", ("clients",), 4096, True))
    cost3.add(rec("psum", ("clients",), 4, True))
    assert M.collective_findings("ctl", cost3, M.MESH_POPULATION,
                                 1024, 1) == []


def test_au011_conflicting_constraints_fire():
    from jax.sharding import PartitionSpec as P

    mesh = make_client_mesh(8)

    def reshardy(x):
        y = jax.lax.with_sharding_constraint(
            x, jax.sharding.NamedSharding(mesh, P("clients", None)))
        z = jax.lax.with_sharding_constraint(
            y * 2.0, jax.sharding.NamedSharding(mesh, P()))
        # the SAME value re-pinned to a different layout: a genuine
        # mid-program reshard
        return jax.lax.with_sharding_constraint(
            y, jax.sharding.NamedSharding(mesh, P())), z

    closed = jax.make_jaxpr(reshardy)(jnp.zeros((8, 4)))
    fs = M.reshard_findings("ctl", closed, baseline_count=None)
    assert "AU011" in {f.rule for f in fs}

    # the count-diff detector: any reshard eqns beyond the
    # single-device trace's count fire
    fs2 = M.reshard_findings("ctl", closed, baseline_count=0)
    assert sum(1 for f in fs2 if "single-device" in f.message) == 1


def test_exit_code_contract():
    from commefficient_tpu.analysis.audit import AuditFinding

    v = AuditFinding("p", "AU008", "x")
    d = AuditFinding("p", "MAU006", "x")
    assert M.split_findings([v, d]) == ([v], [d])
    assert M.exit_code([], [], []) == 0
    assert M.exit_code([v], [d], []) == 1
    assert M.exit_code([], [d], []) == 2
    assert M.exit_code([], [], ["stale"]) == 2


def test_cli_exit_codes(tmp_path):
    """End-to-end: clean against the shipped baseline -> 0; a
    perturbed baseline -> 2 (drift, not violation)."""
    rc = M.main(["--meshes", "clients8",
                 "--write-baseline", "--baseline",
                 str(tmp_path / "b.json")])
    assert rc == 0
    rc = M.main(["--meshes", "clients8",
                 "--baseline", str(tmp_path / "b.json")])
    assert rc == 0
    doc = json.loads((tmp_path / "b.json").read_text())
    key = next(iter(doc["links"]))
    doc["links"][key]["ici_bytes"] += 1
    (tmp_path / "b.json").write_text(json.dumps(doc))
    rc = M.main(["--meshes", "clients8",
                 "--baseline", str(tmp_path / "b.json")])
    assert rc == 2


def test_mesh_audit_digest_journal_schema(full_mesh_audit, tmp_path):
    report, findings = full_mesh_audit
    path = str(tmp_path / "journal.jsonl")
    rec = M.journal_digest(path, report, len(findings))
    assert rec["digest"] == report["digest"]
    records, problems = validate_journal(path)
    assert problems == [], problems
    assert records[-1]["event"] == "mesh_audit_digest"
    assert records[-1]["programs"] == report["links"]


# ---------------------------------------------------------------------------
# sharded-vs-single-device round identity


def loss_fn(params, batch, mask):
    x, y = batch
    pred = x @ params["w"]
    per_ex = 0.5 * (pred - y) ** 2
    denom = jnp.maximum(mask.sum(), 1.0)
    loss = (per_ex * mask).sum() / denom
    return loss, (loss,)


MODE_CFGS = {
    "sketch": dict(mode="sketch", error_type="virtual",
                   virtual_momentum=0.9, local_momentum=0.0, k=16,
                   num_rows=3, num_cols=64, num_blocks=1),
    "true_topk": dict(mode="true_topk", error_type="virtual",
                      virtual_momentum=0.9, local_momentum=0.0, k=16),
    "fedavg": dict(mode="fedavg", error_type="none",
                   virtual_momentum=0.0, local_momentum=0.0,
                   num_fedavg_epochs=1, local_batch_size=-1),
}


def _run_round(cfg, mesh, pop=16):
    params = {"w": jnp.zeros(D, jnp.float32)}
    vec, unravel = flatten_params(params)
    handle = make_train_fn(loss_fn, unravel, cfg, mesh)
    server = init_server_state(cfg, vec, mesh=mesh)
    clients = init_client_state(cfg, pop, vec, mesh=mesh)
    rng = np.random.RandomState(0)
    batch = RoundBatch(
        jnp.arange(W, dtype=jnp.int32),
        (jnp.asarray(rng.randn(W, B, D).astype(np.float32)),
         jnp.asarray(rng.randn(W, B).astype(np.float32))),
        jnp.ones((W, B), jnp.float32))
    server, clients, _ = handle(server, clients, batch,
                                jnp.float32(0.1), jax.random.PRNGKey(0))
    return (np.asarray(server.ps_weights),
            [np.asarray(f) for f in clients])


@pytest.mark.parametrize("mode", sorted(MODE_CFGS))
def test_sharded_round_placement_bit_identity(mode):
    """The 8-shard round on the flat clients mesh and on the emulated
    slice-major 2-slice mesh (a REAL device permutation —
    test_mesh.test_multihost_mesh_is_a_real_permutation) produces
    BIT-identical server weights and client rows: the round is
    placement-invariant, which is what makes the multihost slice
    layout a pure transport decision."""
    cfg = Config(weight_decay=0.0, num_workers=W, microbatch_size=-1,
                 grad_size=D, num_clients=16, seed=0,
                 **MODE_CFGS[mode]).validate()
    w_flat, rows_flat = _run_round(cfg, make_client_mesh(8))
    w_ms, rows_ms = _run_round(
        cfg, make_multihost_client_mesh(num_slices=2))
    assert np.array_equal(w_flat, w_ms)
    for a, b in zip(rows_flat, rows_ms):
        assert np.array_equal(a, b)


def test_fedmodel_trace_hook_includes_span():
    """The real-workload trace surface grows the scanned-span entry:
    four programs, the span one containing a scan of trip count
    span_len (what graftmesh prices per-link)."""
    from commefficient_tpu.analysis.costmodel import collective_cost
    from commefficient_tpu.federated.api import FedModel

    cfg = Config(weight_decay=0.0, num_workers=W, microbatch_size=-1,
                 grad_size=D, num_clients=16, seed=0,
                 **MODE_CFGS["sketch"]).validate()
    model = FedModel(None, loss_fn, cfg,
                     params={"w": jnp.zeros(D)}, num_clients=16)
    rng = np.random.RandomState(0)
    batch = (np.arange(W, dtype=np.int32),
             (rng.randn(W, B, D).astype(np.float32),
              rng.randn(W, B).astype(np.float32)),
             np.ones((W, B), np.float32))
    programs = model.trace_round_programs(batch, include_span=True,
                                          span_len=3)
    assert set(programs) == {"mask_free", "dropout",
                             "dropout_stragglers", "span"}
    link = MeshLinkModel(
        "m", tuple((a, int(n)) for a, n in model.mesh.shape.items()),
        tuple((a, 1) for a in model.mesh.axis_names))
    per_round = collective_cost(programs["mask_free"], link)
    span = collective_cost(programs["span"], link)
    assert span.ici_bytes == 3 * per_round.ici_bytes


@pytest.mark.parametrize("mode", sorted(MODE_CFGS))
def test_sharded_round_matches_single_device(mode):
    """8-shard vs 1-device: per-client state rows are BIT-identical
    (each row is a pure per-client computation — sharding cannot touch
    it), and the cross-client aggregates agree to float-association
    tolerance (the psum across 8 shards legitimately reassociates the
    sum a single device performs in one reduction; ~1e-8 relative at
    this geometry, and the ONLY divergence sharding introduces)."""
    cfg = Config(weight_decay=0.0, num_workers=W, microbatch_size=-1,
                 grad_size=D, num_clients=16, seed=0,
                 **MODE_CFGS[mode]).validate()
    w_1, rows_1 = _run_round(cfg, make_client_mesh(1))
    w_8, rows_8 = _run_round(cfg, make_client_mesh(8))
    for a, b in zip(rows_1, rows_8):
        assert np.array_equal(a, b)
    np.testing.assert_allclose(w_1, w_8, rtol=0, atol=5e-7)

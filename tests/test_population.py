"""ISSUE 9 — O(active) client state at million-client populations.

The tentpole's executable claims:

  * the three-program dispatch (cohort-gather -> round -> scatter-back)
    is bit-identical to the composed single-program body for the
    default (client-state-free) sketch config, and placement-identical
    (rows bit-exact, aggregates within the PR-8 psum-reassociation
    tolerance) between the dense 1-device path and the 8-way sharded
    path for sketch / true_topk / local_topk;
  * checkpoints are O(cohort): a 1e6-population save with a 64-client
    cohort lands within a small constant of the 1e3-population save;
  * sparse (crows_*) checkpoints resume BIT-exactly;
  * the alias-method sampler draws the same distribution as the exact
    `gen.choice(p=weights(alive))` it replaced (statistical bound),
    and its rebuild counter / table snapshot resume bit-exactly;
  * AU004's strict mode hard-errors population-shaped round-program
    inputs/outputs (positive control) while the inventory path
    survives for opted-out configs.
"""
import os

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from commefficient_tpu.config import Config
from commefficient_tpu.federated import round as fround
from commefficient_tpu.federated.api import FedModel, FedOptimizer
from commefficient_tpu.ops.flat import flatten_params
from commefficient_tpu.parallel import multihost as mh
from commefficient_tpu.parallel.mesh import make_client_mesh
from commefficient_tpu.scheduler.policy import (
    AliasTable, ThroughputAwareSampler,
)
from commefficient_tpu.telemetry.clients import ClientThroughputTracker

D = 16
W = 8
B = 4


def _loss_fn(params, batch, mask):
    x, y = batch
    pred = x @ params["w"]
    per_ex = 0.5 * (pred - y) ** 2
    loss = (per_ex * mask).sum() / jnp.maximum(mask.sum(), 1.0)
    return loss, (loss,)


def _mode_cfg(mode, **kw):
    base = dict(weight_decay=0.0, num_workers=W, microbatch_size=-1,
                grad_size=D, seed=0)
    if mode == "sketch":
        base.update(error_type="virtual", virtual_momentum=0.9,
                    local_momentum=0.0, k=8, num_rows=3, num_cols=32,
                    num_blocks=1)
    elif mode == "true_topk":
        base.update(error_type="virtual", local_momentum=0.9, k=8)
    elif mode == "local_topk":
        base.update(error_type="local", local_momentum=0.9,
                    do_topk_down=True, k=8, down_k=16)
    base.update(kw)
    return Config(mode=mode, **base).validate()


def _problem(seed=0, w=W):
    rng = np.random.RandomState(seed)
    x = rng.randn(w, B, D).astype(np.float32)
    y = rng.randn(w, B).astype(np.float32)
    return x, y, np.ones((w, B), np.float32)


# ---------------------------------------------------------------------------
# sharded-gather vs dense-path identity


def test_split_dispatch_bit_identical_to_composed_default_sketch():
    """The default-shaped (client-state-free) sketch config: the
    three-program dispatch == one jit of the composed body (which IS
    the pre-refactor round program: gather, compute, scatter in one
    traced fn) — bit for bit over several rounds. The 'default
    uniform-sampler single-device run stays bit-identical to the
    pre-refactor program' acceptance, executable."""
    cfg = _mode_cfg("sketch", num_clients=23,
                    donate_round_state=False)
    params = {"w": jnp.zeros(D, jnp.float32)}
    vec, unravel = flatten_params(params)
    mesh = make_client_mesh(1)
    tr = fround.make_train_fn(_loss_fn, unravel, cfg, mesh)
    composed = jax.jit(tr.round_full)
    x, y, mask = _problem()
    key = jax.random.PRNGKey(0)
    sA = fround.init_server_state(cfg, vec)
    cA = fround.init_client_state(cfg, 23, vec)
    sB = fround.init_server_state(cfg, vec)
    cB = fround.init_client_state(cfg, 23, vec)
    rng = np.random.RandomState(3)
    for _ in range(4):
        ids = jnp.asarray(rng.choice(23, W, replace=False)
                          .astype(np.int32))
        b = fround.RoundBatch(ids, (jnp.asarray(x), jnp.asarray(y)),
                              jnp.asarray(mask))
        sA, cA, _ = tr(sA, cA, b, 0.1, key)
        sB, cB, _ = composed(sB, cB, b, 0.1, key)
    for name, a, bb in [("ps", sA.ps_weights, sB.ps_weights),
                        ("Vv", sA.Vvelocity, sB.Vvelocity),
                        ("Ve", sA.Verror, sB.Verror)]:
        assert np.array_equal(np.asarray(a), np.asarray(bb)), name


@pytest.mark.parametrize("mode", ["sketch", "true_topk", "local_topk"])
def test_sharded_gather_matches_dense_path(mode):
    """Placement identity across the gather path: the same round on
    the dense 1-device layout and on the 8-way clients-sharded layout.
    Per-client state ROWS are bit-identical (row math is client-local;
    the sharded gather/scatter move them exactly), cross-client
    aggregates agree within the PR-8 psum-reassociation tolerance
    (the one legitimate divergence — an 8-way lax.psum reassociates
    the sum a single device folds linearly)."""
    from jax.sharding import PartitionSpec as P

    cfg = _mode_cfg(mode, num_clients=24, donate_round_state=False)
    params = {"w": jnp.zeros(D, jnp.float32)}
    vec, unravel = flatten_params(params)
    x, y, mask = _problem(seed=5)
    key_h = np.asarray(jax.random.PRNGKey(0))
    out = {}
    for nd in (1, 8):
        mesh = make_client_mesh(nd)
        tr = fround.make_train_fn(_loss_fn, unravel, cfg, mesh)
        s = fround.init_server_state(cfg, vec, mesh=mesh)
        c = fround.init_client_state(cfg, 24, vec, mesh=mesh)
        key = mh.globalize(mesh, P(), key_h)
        lr = mh.globalize(mesh, P(), np.float32(0.1))
        ids = mh.globalize(mesh, P(),
                           np.arange(W, dtype=np.int32) * 3)
        b = fround.RoundBatch(ids,
                              (mh.shard_rows(mesh, x),
                               mh.shard_rows(mesh, y)),
                              mh.shard_rows(mesh, mask))
        s, c, _ = tr(s, c, b, lr, key)
        out[nd] = (jax.device_get(s.ps_weights),
                   [jax.device_get(f) for f in c])
    ps1, rows1 = out[1]
    ps8, rows8 = out[8]
    np.testing.assert_allclose(ps1, ps8, atol=5e-7)
    for name, a, bb in zip(("errors", "velocities", "weights"),
                           rows1, rows8):
        if a.ndim == 2:
            assert np.array_equal(a, bb), (
                f"{name} rows diverged across placements")


# ---------------------------------------------------------------------------
# O(cohort) checkpoints


def _fed_model(cfg, num_clients):
    params = {"w": jnp.zeros(D, jnp.float32)}
    model = FedModel(None, _loss_fn, cfg, params=params,
                     num_clients=num_clients)
    opt = FedOptimizer(model, cfg)
    opt.param_groups[0]["lr"] = 0.1
    return model, opt


def _drive(model, rounds, num_clients, seed=9, start=0):
    x, y, mask = _problem(seed=7, w=model.cfg.num_workers)
    rng = np.random.RandomState(seed)
    all_ids = [rng.choice(num_clients, model.cfg.num_workers,
                          replace=False).astype(np.int32)
               for _ in range(start + rounds)]
    for ids in all_ids[start:]:
        model((ids, (x, y), mask))


def test_checkpoint_bytes_flat_in_population(tmp_path):
    """The headline regression gate: a checkpoint written at a
    1e6-client population with a 64-slot cohort must land within a
    small constant of the 1e3-population checkpoint — O(cohort), not
    O(population). (Before ISSUE 9 the 1e6 save carried three dense
    [1e6, D] blocks: ~200 MB at D=16 vs a few KB.)"""
    from commefficient_tpu.utils.checkpoint import save_checkpoint

    sizes = {}
    for pop in (1_000, 1_000_000):
        cfg = _mode_cfg("local_topk", num_workers=64,
                        num_clients=pop)
        model, _ = _fed_model(cfg, pop)
        _drive(model, 2, pop)
        path = str(tmp_path / f"pop{pop}.npz")
        save_checkpoint(path, model.server, model.clients,
                        fingerprint=model.checkpoint_fingerprint,
                        throughput=model.throughput.state_dict(),
                        client_rows=model.client_rows_payload())
        sizes[pop] = os.path.getsize(path)
        del model
    # identical cohort work -> near-identical checkpoints; 64 KiB of
    # slack absorbs id-array/metadata differences
    assert sizes[1_000_000] <= sizes[1_000] + 65536, sizes
    # and the big one is nowhere near the dense O(population) bytes
    dense_bytes = 1_000_000 * D * 4 * 3
    assert sizes[1_000_000] < dense_bytes / 100, sizes


def test_sparse_checkpoint_resume_bit_exact(tmp_path):
    """crows_* checkpoints restore the exact client state: straight
    6-round run == 3 rounds + sparse save/load + 3 rounds, bit for
    bit, with all three state blocks live (local_topk + momentum +
    topk_down)."""
    from commefficient_tpu.utils.checkpoint import (
        load_checkpoint, save_checkpoint,
    )

    pop = 64
    cfg = _mode_cfg("local_topk", num_clients=pop)
    model_a, _ = _fed_model(cfg, pop)
    _drive(model_a, 6, pop)

    model_b, _ = _fed_model(cfg, pop)
    _drive(model_b, 3, pop)
    path = str(tmp_path / "sparse.npz")
    save_checkpoint(path, model_b.server, model_b.clients,
                    fingerprint=model_b.checkpoint_fingerprint,
                    client_rows=model_b.client_rows_payload())

    # the file really is the sparse format (and not the dense blocks)
    z = np.load(path)
    assert "crows_ids" in z.files
    assert "client_errors" not in z.files

    model_c, _ = _fed_model(cfg, pop)
    ckpt = load_checkpoint(
        path, expect_fingerprint=model_c.checkpoint_fingerprint)
    assert ckpt.client_rows is not None and ckpt.clients is None
    model_c.load_state(ckpt)
    # restored rows == the saver's full state, bit for bit
    for name in ("errors", "velocities", "weights"):
        np.testing.assert_array_equal(
            np.asarray(getattr(model_c.clients, name)),
            np.asarray(getattr(model_b.clients, name)),
            err_msg=name)
    _drive(model_c, 3, pop, start=3)
    np.testing.assert_array_equal(
        np.asarray(model_c.server.ps_weights),
        np.asarray(model_a.server.ps_weights))
    for name in ("errors", "velocities", "weights"):
        np.testing.assert_array_equal(
            np.asarray(getattr(model_c.clients, name)),
            np.asarray(getattr(model_a.clients, name)),
            err_msg=name)


def test_legacy_dense_checkpoint_still_loads(tmp_path):
    """A pre-ISSUE-9 dense checkpoint (client_* blocks) still resumes
    — and the resumed model falls back to DENSE saves (the touched-row
    set is unrecoverable, so a sparse save would silently drop
    pre-resume rows)."""
    from commefficient_tpu.utils.checkpoint import (
        load_checkpoint, save_checkpoint,
    )

    pop = 32
    cfg = _mode_cfg("local_topk", num_clients=pop)
    model_a, _ = _fed_model(cfg, pop)
    _drive(model_a, 3, pop)
    path = str(tmp_path / "dense.npz")
    # legacy format: dense blocks, no client_rows payload
    save_checkpoint(path, model_a.server, model_a.clients,
                    fingerprint=model_a.checkpoint_fingerprint)
    z = np.load(path)
    assert "client_errors" in z.files

    model_b, _ = _fed_model(cfg, pop)
    ckpt = load_checkpoint(
        path, expect_fingerprint=model_b.checkpoint_fingerprint)
    assert ckpt.clients is not None and ckpt.client_rows is None
    model_b.load_state(ckpt)
    for name in ("errors", "velocities", "weights"):
        np.testing.assert_array_equal(
            np.asarray(getattr(model_b.clients, name)),
            np.asarray(getattr(model_a.clients, name)), err_msg=name)
    assert model_b.client_rows_payload() is None


# ---------------------------------------------------------------------------
# alias-method sampling


def test_alias_table_matches_weights():
    """Unit: the alias table realizes its weight distribution — the
    empirical draw frequency converges to w / w.sum()."""
    rng = np.random.default_rng(0)
    ids = np.array([3, 11, 42, 7, 19], np.int64)
    w = np.array([1.0, 4.0, 0.5, 2.0, 2.5])
    table = AliasTable(ids, w)
    n = 40_000
    counts = {int(c): 0 for c in ids}
    for _ in range(n):
        counts[table.draw(rng)] += 1
    want = w / w.sum()
    got = np.array([counts[int(c)] / n for c in ids])
    np.testing.assert_allclose(got, want, atol=0.01)


def test_alias_sampler_distribution_matches_exact_choice():
    """The O(1)-per-draw path draws the SAME distribution as the
    exact `gen.choice(p=weights(alive))` it replaced: empirical
    per-client inclusion frequencies over many rounds agree within a
    statistical bound, with measured, unmeasured, and not-alive
    clients all present."""
    N, slots = 30, 5
    tracker = ClientThroughputTracker(N)
    rates = np.zeros(N, np.float32)
    rates[:18] = np.linspace(1.0, 9.0, 18)  # measured; 18..29 unmeasured
    tracker.force(np.arange(N), rate=rates,
                  completions=(rates > 0).astype(np.int64))
    sampler = ThroughputAwareSampler(0, tracker, explore_floor=0.15)
    alive = np.delete(np.arange(N), [2, 25])  # some clients exhausted
    p = sampler.weights(alive)

    R = 4000
    counts_alias = np.zeros(N)
    for r in range(R):
        counts_alias[sampler.select(alive, slots, None, r)] += 1
    gen = np.random.default_rng(123)
    counts_exact = np.zeros(N)
    for _ in range(R):
        counts_exact[gen.choice(alive, size=slots, replace=False,
                                p=p)] += 1
    incl_alias = counts_alias / R
    incl_exact = counts_exact / R
    # never-alive clients are never drawn by either path
    assert counts_alias[2] == counts_alias[25] == 0
    # inclusion frequencies agree within sampling noise (std of a
    # binomial mean at R=4000 is < 0.008; 0.03 is > 3 sigma)
    np.testing.assert_allclose(incl_alias[alive], incl_exact[alive],
                               atol=0.03)


def test_alias_sampler_is_o_seen_not_o_population():
    """The sampler touches O(clients-ever-seen) state, never the
    population: selection over a 1e6-strong alive set with 50 measured
    clients builds a 50-row table and materializes no
    population-length weight vector (weights() is never called on the
    alias path — monkeypatch-free check via the table size)."""
    pop = 1_000_000
    tracker = ClientThroughputTracker(pop)
    seen = np.arange(0, 5000, 100, dtype=np.int64)  # 50 clients
    tracker.force(seen, rate=np.linspace(1, 5, len(seen)),
                  completions=np.ones(len(seen)))
    sampler = ThroughputAwareSampler(0, tracker, explore_floor=0.1)
    alive = np.arange(pop)
    chosen = sampler.select(alive, 64, None, round_idx=7)
    assert len(chosen) == 64 and len(set(chosen)) == 64
    assert sampler._table is not None and sampler._table.n == len(seen)
    # deterministic: the same (seed, round, state) replays identically
    again = sampler.select(alive, 64, None, round_idx=7)
    np.testing.assert_array_equal(chosen, again)


def test_alias_rebuild_only_on_material_change():
    """The table rebuilds when EMAs move materially (> rebuild_tol
    relative) or a new client is measured — and NOT on sub-threshold
    jitter."""
    tracker = ClientThroughputTracker(16)
    tracker.force(np.arange(8), rate=np.full(8, 4.0),
                  completions=np.ones(8))
    sampler = ThroughputAwareSampler(0, tracker, explore_floor=0.1,
                                     rebuild_tol=0.05)
    alive = np.arange(16)
    sampler.select(alive, 4, None, 0)
    assert sampler.rebuilds == 1
    # sub-threshold jitter: no rebuild
    tracker.force(np.arange(8), rate=np.full(8, 4.1))
    sampler.select(alive, 4, None, 1)
    assert sampler.rebuilds == 1
    # material move: rebuild
    tracker.force(np.arange(8), rate=np.full(8, 6.0))
    sampler.select(alive, 4, None, 2)
    assert sampler.rebuilds == 2
    # new measured client: rebuild
    tracker.force([12], rate=[2.0], completions=[1])
    sampler.select(alive, 4, None, 3)
    assert sampler.rebuilds == 3


def test_alias_rebuild_counter_and_stream_resume_bit_exact():
    """The satellite's resume proof: checkpoint the sampler's alias
    state (rebuild counter + snapshot) mid-run, restore into a fresh
    sampler over the restored tracker, and the post-resume selection
    STREAM — including rebuild decisions — is bit-exact vs the
    uninterrupted run."""
    def fresh():
        tracker = ClientThroughputTracker(64)
        return tracker, ThroughputAwareSampler(0, tracker,
                                               explore_floor=0.1)

    def step(tracker, sampler, r):
        # evolving rates: some rounds move the EMAs materially
        if r % 3 == 0:
            tracker.force(np.arange(16),
                          rate=np.linspace(1.0, 4.0, 16) * (1 + r),
                          completions=np.ones(16))
        return sampler.select(np.arange(64), 8, None, r)

    tr_a, smp_a = fresh()
    picks_a = [step(tr_a, smp_a, r) for r in range(10)]

    tr_b, smp_b = fresh()
    for r in range(5):
        step(tr_b, smp_b, r)
    thr_state = tr_b.state_dict()
    smp_state = smp_b.state_dict()
    assert int(smp_state["alias_rebuilds"]) == smp_b.rebuilds

    tr_c, smp_c = fresh()
    tr_c.load_state_dict(thr_state)
    smp_c.load_state_dict(smp_state)
    assert smp_c.rebuilds == smp_b.rebuilds
    picks_c = [step(tr_c, smp_c, r) for r in range(5, 10)]
    for want, got in zip(picks_a[5:], picks_c):
        np.testing.assert_array_equal(want, got)
    assert smp_c.rebuilds == smp_a.rebuilds


# ---------------------------------------------------------------------------
# AU004 strict mode (the flipped rule)


def test_au004_strict_errors_population_round_operands():
    """Positive control for the flipped rule: a 'round program' whose
    input/output carry the population sentinel is an AU004 ERROR under
    strict mode, while inventory mode (the state-motion programs /
    opted-out configs) reports it as inventory only."""
    from commefficient_tpu.analysis import audit as A

    P = A.AUDIT_POPULATION

    def leaky_round(rows, ids):
        got = rows[ids] * 2.0
        return rows.at[ids].set(got)

    rows = jnp.ones((P, 4))
    ids = jnp.arange(3)
    closed = jax.make_jaxpr(leaky_round)(rows, ids)
    inv, strict_hits = A.population_scan(
        "p", closed, P, ["rows", "ids"], ["rows_out"], strict=True)
    assert {v.rule for v in strict_hits} == {"AU004"}
    # one for the population input, one for the population output
    assert len(strict_hits) == 2
    assert any("INPUT" in v.message for v in strict_hits)
    assert any("OUTPUT" in v.message for v in strict_hits)
    # inventory mode: same program, no findings, named inventory
    inv2, legacy_hits = A.population_scan(
        "p", closed, P, ["rows", "ids"], ["rows_out"], strict=False)
    assert legacy_hits == []
    assert [e["name"] for e in inv2["inputs"]] == ["rows"]
    assert [e["name"] for e in inv2["outputs"]] == ["rows_out"]
    # the inventory block is emitted either way (strict mode's must
    # match — the audit report schema is unchanged)
    assert inv == inv2


def test_run_audit_inventory_opt_out():
    """`population_inventory_configs` keeps the pre-ISSUE-9 semantics
    for named configs: run_audit with every config opted out still
    audits clean (nothing in the tree violates either mode), and the
    strict default equals the opt-out on today's population-free round
    programs — the flag only matters for workloads that keep dense
    in-round state."""
    from commefficient_tpu.analysis import audit as A

    report, findings = A.run_audit(
        inventory_configs=["sketch", "client-state"])
    assert findings == []
    strict_report, strict_findings = A.run_audit()
    assert strict_findings == []
    assert report["costs"] == strict_report["costs"]

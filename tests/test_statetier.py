"""ISSUE 11 — tiered cold client state: a bounded device-HBM working
set over a host-spilled long tail.

The tentpole's executable claims:

  * `state_tier=host` training is BIT-identical to `state_tier=device`
    on the per-round path (the round program is trace-identical
    between tiers; f32 rows round-trip the host exactly), with spills
    and restores live. The scanned span traces a DIFFERENT program
    under the tier (block shape on the carry), so the scanned
    comparison below is exact at this geometry but is the
    cross-program class in general (PR 9's caveat);
  * the gather/scatter pair stays the ONLY state-motion program pair:
    spills ride the compiled gather, restores the compiled scatter
    (host-built rows placed with the gather's own cohort shardings),
    so the steady state is zero new compiles even while rows migrate,
    and dispatch is transfer-guard-clean including host-tier restores;
  * crash->resume is bit-exact with rows resident in EVERY tier
    combination — hot (working set), host-spilled, and mid-spill with
    a live writer queue (the PR-10 drain contract) — and the LRU
    recency/slot map rides in crows_* so the resumed run replays the
    exact eviction stream;
  * checkpoints stay O(working set) on the device side: evicted rows
    serialize straight from the host tail with no device gather
    (satellite fix);
  * the journal's `state_tier` events validate and surface the hit
    rate; config validation rejects the unsupported combinations.
"""
import json
import os
import threading
import time

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from commefficient_tpu.config import Config
from commefficient_tpu.federated import round as fround
from commefficient_tpu.federated.api import FedModel, FedOptimizer
from commefficient_tpu.federated.statestore import TieredStateStore
from commefficient_tpu.ops.flat import flatten_params
from commefficient_tpu.parallel.mesh import make_client_mesh
from commefficient_tpu.utils.checkpoint import (
    load_checkpoint, save_checkpoint,
)
from commefficient_tpu.utils.faults import FaultSchedule, InjectedFault

# a suite worth re-running under the LockOrderSanitizer +
# interleaving stress (CCTPU_SYNC_SANITIZE=1) — the spill writer is
# the lock-richest path in the tree
pytestmark = pytest.mark.statetier

D = 16
W = 8
B = 4
POP = 64


def _loss_fn(params, batch, mask):
    x, y = batch
    pred = x @ params["w"]
    per_ex = 0.5 * (pred - y) ** 2
    loss = (per_ex * mask).sum() / jnp.maximum(mask.sum(), 1.0)
    return loss, (loss,)


def _cfg(**kw):
    base = dict(mode="local_topk", error_type="local",
                local_momentum=0.9, do_topk_down=True, k=8, down_k=16,
                weight_decay=0.0, num_workers=W, microbatch_size=-1,
                grad_size=D, seed=0, num_clients=POP)
    base.update(kw)
    return Config(**base).validate()


def _model(**kw):
    model = FedModel(None, _loss_fn, _cfg(**kw),
                     params={"w": jnp.zeros(D, jnp.float32)},
                     num_clients=POP)
    opt = FedOptimizer(model)
    opt.param_groups[0]["lr"] = 0.1
    return model


def _problem(seed=0):
    rng = np.random.RandomState(seed)
    return (rng.randn(W, B, D).astype(np.float32),
            rng.randn(W, B).astype(np.float32),
            np.ones((W, B), np.float32))


def _ids_stream(rounds, seed=3):
    rng = np.random.RandomState(seed)
    return [rng.choice(POP, W, replace=False).astype(np.int32)
            for _ in range(rounds)]


def _full_rows(model):
    """[POP, D] per tracked block, reconstructed the same way for both
    tiers: device tier reads the population block, tiered models
    rebuild init + the crows payload."""
    out = {}
    if model.state_store is None:
        for name in ("errors", "velocities", "weights"):
            out[name] = np.asarray(getattr(model.clients, name))[:POP]
        return out
    payload = model.client_rows_payload()
    base_w = payload["base_weights"]
    for name in ("errors", "velocities", "weights"):
        full = (np.broadcast_to(base_w, (POP, D)).copy()
                if name == "weights" else np.zeros((POP, D), np.float32))
        if len(payload["ids"]):
            full[payload["ids"]] = payload[name]
        out[name] = full
    return out


def _assert_same_state(model_a, model_b):
    np.testing.assert_array_equal(
        np.asarray(model_a.server.ps_weights),
        np.asarray(model_b.server.ps_weights))
    rows_a, rows_b = _full_rows(model_a), _full_rows(model_b)
    for name in ("errors", "velocities", "weights"):
        np.testing.assert_array_equal(rows_a[name], rows_b[name],
                                      err_msg=name)


# ---------------------------------------------------------------------------
# tier identity


def test_host_tier_bit_identical_to_device_per_round():
    """The headline contract: local_topk (all three state blocks live)
    under a 16-slot working set over a 64-client population — spills
    and restores every round — produces BIT-identical server weights
    and client rows vs the default device tier."""
    x, y, mask = _problem()
    dev = _model()
    host = _model(state_tier="host", state_working_set=16)
    for ids in _ids_stream(10):
        dev((ids, (x, y), mask))
        host((ids, (x, y), mask))
    assert host.state_store.spills > 0, "working set never spilled"
    _assert_same_state(dev, host)
    host.close_persistence()


def test_host_tier_bit_identical_scanned_span():
    """Same claim on the scanned path: the span executes with the
    working-set block on the scan carry, all restores prefetched
    before dispatch."""
    x, y, mask = _problem(seed=5)
    dev = _model()
    host = _model(state_tier="host", state_working_set=24)
    ids_all = _ids_stream(9, seed=7)
    for lo in range(0, 9, 3):
        ids = np.stack(ids_all[lo:lo + 3])
        data = (np.broadcast_to(x, (3,) + x.shape),
                np.broadcast_to(y, (3,) + y.shape))
        mk = np.broadcast_to(mask, (3,) + mask.shape)
        lrs = np.full(3, 0.1, np.float32)
        dev.run_rounds(ids, data, mk, lrs)
        host.run_rounds(ids, data, mk, lrs)
    assert host.state_store.spills > 0
    _assert_same_state(dev, host)
    host.close_persistence()


def test_disk_spill_dir_backs_the_tail(tmp_path):
    """--state_spill_dir: the cold tail lives in sparse per-block
    memmaps; results stay bit-identical and the files exist."""
    x, y, mask = _problem()
    dev = _model()
    disk = _model(state_tier="host", state_working_set=16,
                  state_spill_dir=str(tmp_path / "tail"))
    for ids in _ids_stream(8):
        dev((ids, (x, y), mask))
        disk((ids, (x, y), mask))
    disk.state_store.flush()
    assert disk.state_store.spills > 0
    for name in ("errors", "velocities", "weights"):
        assert (tmp_path / "tail" / f"tail_{name}.npy").exists()
    _assert_same_state(dev, disk)
    disk.close_persistence()


# ---------------------------------------------------------------------------
# program contracts


def test_gather_scatter_stay_the_only_state_motion_programs(sanitize):
    """Handle-level compile accounting: the first tiered round
    compiles exactly gather + scatter + the mask-free round (3); every
    later round — misses, restores, evictions and all — is a cache
    hit (0 compiles), because spills ride the compiled gather and
    restores the compiled scatter at the gather's own cohort
    placement."""
    cfg = _cfg(state_tier="host", state_working_set=16)
    params = {"w": jnp.zeros(D, jnp.float32)}
    vec, unravel = flatten_params(params)
    mesh = make_client_mesh(len(jax.devices()))
    tr = fround.make_train_fn(_loss_fn, unravel, cfg, mesh)
    server = fround.init_server_state(cfg, vec, mesh=mesh)
    block = fround.init_client_state(
        cfg, fround.client_state_rows(cfg, POP), vec, mesh=mesh)
    store = TieredStateStore(cfg, mesh, tr, vec, POP)
    x, y, mask = _problem()
    from commefficient_tpu.parallel import multihost as mh
    from jax.sharding import PartitionSpec as P
    key = mh.globalize(mesh, P(), np.asarray(jax.random.PRNGKey(0)))
    lr = mh.globalize(mesh, P(), np.float32(0.1))
    data = (mh.shard_rows(mesh, x), mh.shard_rows(mesh, y))
    mk = mh.shard_rows(mesh, mask)
    ids_all = _ids_stream(8, seed=11)

    def one_round(server, block, ids):
        plan = store.plan_round(ids)
        block = store.execute(block, plan)
        b = fround.RoundBatch(
            mh.globalize(mesh, P(), plan.slots), data, mk)
        return tr(server, block, b, lr, key)

    with sanitize.assert_program_count(3):
        server, block, _ = one_round(server, block, ids_all[0])
    with sanitize.assert_program_count(0):
        for ids in ids_all[1:]:
            server, block, _ = one_round(server, block, ids)
    assert store.spills > 0
    store.close()


def test_tiered_dispatch_transfer_guard_clean(sanitize):
    """Host-tier restores and async spills are EXPLICIT transfers
    only: a fully-armed transfer guard around steady-state tiered
    rounds sees zero implicit host<->device transfers."""
    x, y, mask = _problem()
    host = _model(state_tier="host", state_working_set=16)
    ids_all = _ids_stream(6, seed=13)
    for ids in ids_all[:2]:
        host((ids, (x, y), mask))
    with sanitize.forbid_transfers():
        for ids in ids_all[2:]:
            host((ids, (x, y), mask))
    assert host.state_store.spills > 0
    host.close_persistence()


def test_default_tier_constructs_no_store():
    """state_tier=device builds no store, keeps the population-sized
    blocks, and stages global client ids — the pre-feature program,
    machinery never constructed."""
    dev = _model()
    assert dev.state_store is None
    assert np.asarray(dev.clients.errors).shape[0] >= POP


def test_working_set_too_small_for_span_raises():
    """A span whose distinct clients exceed the working set fails
    loud with the actionable knob names, instead of silently evicting
    rows the span still needs."""
    host = _model(state_tier="host", state_working_set=8)
    x, y, mask = _problem()
    ids = np.stack([np.arange(W, dtype=np.int32),
                    np.arange(W, 2 * W, dtype=np.int32)])
    data = (np.broadcast_to(x, (2,) + x.shape),
            np.broadcast_to(y, (2,) + y.shape))
    mk = np.broadcast_to(mask, (2,) + mask.shape)
    with pytest.raises(ValueError, match="state_working_set"):
        host.run_rounds(ids, data, mk, np.full(2, 0.1, np.float32))
    host.close_persistence()


def test_config_validation():
    with pytest.raises(ValueError, match="state_working_set"):
        _cfg(state_tier="host")
    with pytest.raises(ValueError, match="cohort"):
        _cfg(state_tier="host", state_working_set=4)
    with pytest.raises(ValueError, match="state_spill_dir"):
        _cfg(state_spill_dir="/tmp/x")
    with pytest.raises(ValueError, match="unknown state_tier"):
        _cfg(state_tier="hbm")
    with pytest.raises(ValueError, match="single-controller"):
        _cfg(state_tier="host", state_working_set=16, multihost=True)


# ---------------------------------------------------------------------------
# crash -> resume, every tier combination


def _drive(model, ids_all, start=0):
    x, y, mask = _problem(seed=2)
    for ids in ids_all[start:]:
        model((ids, (x, y), mask))


def _save(model, path):
    save_checkpoint(path, model.server, model.clients,
                    fingerprint=model.checkpoint_fingerprint,
                    throughput=model.throughput.state_dict(),
                    client_rows=model.client_rows_payload())


def test_resume_bit_exact_with_all_tier_combinations(tmp_path):
    """Straight 12-round tiered run == 6 rounds + crows_* save/load +
    6 rounds, bit for bit, with rows resident in every combination at
    save time: hot (working set), host-spilled (tail), and MID-SPILL
    — a live writer queue deliberately stalled so spills are still in
    flight when the payload drains it (the PR-10 drain contract)."""
    ids_all = _ids_stream(12, seed=17)
    a = _model(state_tier="host", state_working_set=16)
    _drive(a, ids_all)

    b = _model(state_tier="host", state_working_set=16)
    _drive(b, ids_all[:6])
    # stall the spill writer so the next round's evictions are STILL
    # QUEUED when checkpoint_rows runs — its flush must drain them
    # into the tail before serializing
    gate = threading.Event()
    b.state_store._writer.submit(lambda: gate.wait(timeout=10) or None)
    gate_released = [False]

    def release():
        time.sleep(0.05)
        gate_released[0] = True
        gate.set()
    threading.Thread(target=release, daemon=True).start()
    path = str(tmp_path / "tier.npz")
    _save(b, path)
    assert gate_released[0], "payload did not wait for the live queue"

    z = np.load(path)
    assert "crows_lru_ids" in z.files and "crows_lru_slots" in z.files

    c = _model(state_tier="host", state_working_set=16)
    ckpt = load_checkpoint(
        path, expect_fingerprint=c.checkpoint_fingerprint)
    c.load_state(ckpt)
    # the eviction stream replays: LRU recency + slots restored
    snap_b = b.state_store.snapshot_tier()
    snap_c = c.state_store.snapshot_tier()
    np.testing.assert_array_equal(snap_b["lru_ids"], snap_c["lru_ids"])
    np.testing.assert_array_equal(snap_b["lru_slots"],
                                  snap_c["lru_slots"])
    _drive(c, ids_all, start=6)
    _assert_same_state(a, c)
    for m in (a, b, c):
        m.close_persistence()


def test_lru_determinism_resume_replays_eviction_stream(tmp_path):
    """Beyond value bit-exactness: the post-resume hit/miss/spill
    COUNTS equal the uninterrupted run's (the eviction stream itself
    replays, so tier telemetry and spill traffic are reproducible)."""
    ids_all = _ids_stream(12, seed=19)
    a = _model(state_tier="host", state_working_set=16)
    _drive(a, ids_all[:6])
    mid = (a.state_store.hits, a.state_store.misses,
           a.state_store.spills)
    path = str(tmp_path / "lru.npz")
    _save(a, path)
    _drive(a, ids_all, start=6)
    tail_counts = (a.state_store.hits - mid[0],
                   a.state_store.misses - mid[1],
                   a.state_store.spills - mid[2])

    c = _model(state_tier="host", state_working_set=16)
    c.load_state(load_checkpoint(path))
    _drive(c, ids_all, start=6)
    assert (c.state_store.hits, c.state_store.misses,
            c.state_store.spills) == tail_counts
    np.testing.assert_array_equal(
        a.state_store.snapshot_tier()["lru_ids"],
        c.state_store.snapshot_tier()["lru_ids"])
    for m in (a, c):
        m.close_persistence()


def test_injected_crash_then_resume_bit_exact(tmp_path):
    """The chaos-drill shape: InjectedFault at a round boundary with
    spills in flight; the post-crash save (drivers' finally path)
    drains the spill queue, and resume from it is bit-exact."""
    ids_all = _ids_stream(10, seed=23)
    a = _model(state_tier="host", state_working_set=16)
    _drive(a, ids_all)

    b = _model(state_tier="host", state_working_set=16)
    b.set_fault_schedule(FaultSchedule(crash_after=4))
    with pytest.raises(InjectedFault):
        _drive(b, ids_all)
    b.set_fault_schedule(None)
    path = str(tmp_path / "crash.npz")
    _save(b, path)

    c = _model(state_tier="host", state_working_set=16)
    c.load_state(load_checkpoint(path))
    _drive(c, ids_all, start=5)
    _assert_same_state(a, c)
    for m in (a, b, c):
        m.close_persistence()


def test_cross_tier_checkpoints_interchange(tmp_path):
    """crows_* checkpoints are tier-portable both ways: a device-tier
    save resumes into a host-tier model (cold working set — no lru
    keys) and a host-tier save resumes into a device-tier model
    (lru keys ignored), bit-exact in both directions."""
    ids_all = _ids_stream(10, seed=29)
    dev = _model()
    _drive(dev, ids_all[:5])
    dev_path = str(tmp_path / "dev.npz")
    _save(dev, dev_path)

    host = _model(state_tier="host", state_working_set=16)
    _drive(host, ids_all[:5])
    host_path = str(tmp_path / "host.npz")
    _save(host, host_path)

    # device save -> host model
    h2 = _model(state_tier="host", state_working_set=16)
    h2.load_state(load_checkpoint(dev_path))
    # host save -> device model
    d2 = _model()
    d2.load_state(load_checkpoint(host_path))

    _drive(dev, ids_all, start=5)
    _drive(host, ids_all, start=5)
    _drive(h2, ids_all, start=5)
    _drive(d2, ids_all, start=5)
    _assert_same_state(dev, h2)
    _assert_same_state(dev, d2)
    _assert_same_state(dev, host)
    for m in (host, h2):
        m.close_persistence()


def test_legacy_dense_checkpoint_into_host_tier(tmp_path):
    """A pre-ISSUE-9 dense checkpoint resumes into a tiered model:
    the vectorized diff against init recovers the touched set, rows
    land in the host tail, and — unlike the device-tier fallback —
    the tiered model KEEPS sparse saves."""
    ids_all = _ids_stream(8, seed=31)
    dev = _model()
    _drive(dev, ids_all[:4])
    path = str(tmp_path / "dense.npz")
    save_checkpoint(path, dev.server, dev.clients,
                    fingerprint=dev.checkpoint_fingerprint)
    assert "client_errors" in np.load(path).files

    host = _model(state_tier="host", state_working_set=16)
    host.load_state(load_checkpoint(path))
    assert host.client_rows_payload() is not None
    _drive(dev, ids_all, start=4)
    _drive(host, ids_all, start=4)
    _assert_same_state(dev, host)
    host.close_persistence()


# ---------------------------------------------------------------------------
# O(working set) checkpoints (satellite)


def test_checkpoint_device_gather_is_o_working_set(monkeypatch):
    """Evicted rows serialize from the host tail with NO device
    gather: the payload's only device reads are the resident rows —
    a padded-256 slot gather bounded by the working set — however
    many clients were ever touched."""
    from commefficient_tpu.federated import statestore as ss

    host = _model(state_tier="host", state_working_set=16)
    _drive(host, _ids_stream(12, seed=37))
    store = host.state_store
    touched = len(store.touched_ids())
    assert touched > 2 * store.slots, "not enough cold clients"

    gathered_rows = [0]
    real = ss.mh.gather_host

    def counting(x):
        out = real(x)
        if getattr(out, "ndim", 0) == 2:
            gathered_rows[0] += out.shape[0]
        return out
    monkeypatch.setattr(ss.mh, "gather_host", counting)
    payload = host.client_rows_payload()
    assert len(payload["ids"]) == touched
    # 3 tracked blocks x one padded-256 slot gather each; never the
    # touched population
    assert gathered_rows[0] <= 3 * (store.slots + 255)
    host.close_persistence()


def test_prefetch_is_lru_neutral_and_bit_neutral():
    """The scheduler's working-set prefetch hook warms host rows only:
    interleaving aggressive prefetches of future cohorts changes
    neither the eviction stream (hit/miss/spill counts) nor a single
    bit of the results."""
    x, y, mask = _problem()
    plain = _model(state_tier="host", state_working_set=16)
    warm = _model(state_tier="host", state_working_set=16)
    ids_all = _ids_stream(10, seed=47)
    for r, ids in enumerate(ids_all):
        if r + 1 < len(ids_all):
            warm.state_store.prefetch_host_rows(ids_all[r + 1])
        plain((ids, (x, y), mask))
        warm((ids, (x, y), mask))
    assert (plain.state_store.hits, plain.state_store.misses,
            plain.state_store.spills) == (
        warm.state_store.hits, warm.state_store.misses,
        warm.state_store.spills)
    _assert_same_state(plain, warm)
    for m in (plain, warm):
        m.close_persistence()


# ---------------------------------------------------------------------------
# telemetry


def test_state_tier_journal_events_validate(tmp_path):
    from commefficient_tpu.telemetry import TelemetrySession
    from commefficient_tpu.telemetry.journal import (
        RunJournal, summarize, validate_journal,
    )

    jpath = str(tmp_path / "journal.jsonl")
    host = _model(state_tier="host", state_working_set=16)
    tele = TelemetrySession(journal=RunJournal(jpath, run_id="t"))
    host.attach_telemetry(tele)
    _drive(host, _ids_stream(8, seed=41))
    tele.close(ok=True)
    records, problems = validate_journal(jpath)
    assert problems == []
    tier_recs = [r for r in records if r["event"] == "state_tier"]
    assert tier_recs and sum(r["spills"] for r in tier_recs) > 0
    summary = summarize(records)
    assert 0.0 <= summary["state_hit_rate"] <= 1.0
    assert summary["state_spills"] > 0
    host.close_persistence()


def test_state_tier_journal_schema_negative(tmp_path):
    """validate_journal rejects a malformed state_tier record (the
    schema cannot silently rot)."""
    from commefficient_tpu.telemetry.journal import validate_journal

    jpath = str(tmp_path / "bad.jsonl")
    with open(jpath, "w") as f:
        f.write(json.dumps({"v": 1, "event": "state_tier", "ts": 1.0,
                            "hits": -1, "misses": 0, "spills": 0,
                            "restores": "many"}) + "\n")
    _, problems = validate_journal(jpath)
    assert any("hits" in p for p in problems)
    assert any("restores" in p for p in problems)


# ---------------------------------------------------------------------------
# pipelined staging loop


def test_pipelined_tiered_span_loop_bit_identical(tmp_path):
    """training/scanloop with pipeline=True over a tiered model: the
    double-buffered loop (span t+1's restores staged while span t
    executes) matches the synchronous tiered loop bit for bit, and
    the one-span-late boundary checkpoint — built from the snapshot's
    tier bookkeeping — resumes bit-exactly."""
    from commefficient_tpu.training.scanloop import (
        make_span_checkpoint, run_scanned_rounds,
    )
    from commefficient_tpu.utils.schedules import LambdaLR

    x, y, mask = _problem(seed=43)
    ids_all = _ids_stream(8, seed=43)
    stream = [(r, ids_all[r], (x, y), mask, 0.1) for r in range(8)]

    def run(pipeline, workdir):
        model = _model(state_tier="host", state_working_set=24,
                       checkpoint_every=1, ckpt_every_spans=2,
                       pipeline=pipeline)
        sch = LambdaLR(model._optimizer, lr_lambda=lambda s: 1.0)
        model._optimizer.param_groups[0]["lr"] = 0.1
        hook = make_span_checkpoint(
            os.path.join(workdir, "ck"), model, model.cfg, sch)
        ok = run_scanned_rounds(model, iter(stream), 2,
                                lambda *a: True, checkpoint=hook,
                                pipeline=pipeline)
        assert ok
        model.drain_persistence()
        return model

    sync = run(False, str(tmp_path / "s"))
    pipe = run(True, str(tmp_path / "p"))
    assert pipe.state_store.spills > 0
    _assert_same_state(sync, pipe)

    # resume from the pipelined run's MID-RUN boundary checkpoint
    # (ckpt_every_spans=2 -> the round-4 stamped save, written one
    # span late from the snapshot's tier bookkeeping) and replay the
    # remaining stream: bit-exact vs the straight run
    from commefficient_tpu.utils.checkpoint import load_checkpoint
    mid = os.path.join(str(tmp_path / "p"), "ck-r00000004.npz")
    assert os.path.exists(mid)
    ckpt = load_checkpoint(mid)
    assert ckpt.client_rows is not None
    resumed = _model(state_tier="host", state_working_set=24)
    resumed.load_state(ckpt)
    first = int(np.asarray(ckpt.server.round_idx))
    assert 0 < first < 8
    # replay on the SAME scanned cadence the original ran (the
    # composed span program differs from the per-round split at ~1
    # ULP — the PR-9 codegen caveat — so bit-exact resume means
    # same-program resume)
    xh, yh, mh_ = _problem(seed=43)
    for lo in range(first, 8, 2):
        ids = np.stack(ids_all[lo:lo + 2])
        n = ids.shape[0]
        resumed.run_rounds(
            ids,
            (np.broadcast_to(xh, (n,) + xh.shape),
             np.broadcast_to(yh, (n,) + yh.shape)),
            np.broadcast_to(mh_, (n,) + mh_.shape),
            np.full(n, 0.1, np.float32))
    _assert_same_state(sync, resumed)
    for m in (sync, pipe, resumed):
        m.close_persistence()

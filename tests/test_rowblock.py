"""The storage format of the per-client blocks (federated/round
RowBlock: a client's row as whole (8, 128) tiles) and the two
state-motion programs that are the only device code reading it.

Bit-identity cases run the jitted cohort-gather and scatter-back of
the real round factory against a numpy `[rows, D]` reference, on one
device and on the 8-device mesh, for D below 1,024, D not a multiple
of 1,024 and D a multiple; the structural cases are the ones that
would have caught the programs this format replaced (XLA's `gather`
op over the block, a block that is not donated, a block gathered to
one device).
"""
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from commefficient_tpu import Config
from commefficient_tpu.analysis.costmodel import sub_jaxprs
from commefficient_tpu.federated import round as fround
from commefficient_tpu.federated.api import FedModel, FedOptimizer
from commefficient_tpu.federated.round import (
    CohortState, RowBlock, lane_rows, rows_to_tiles, tiles_to_rows,
)
from commefficient_tpu.ops.flat import flatten_params
from commefficient_tpu.parallel.mesh import make_client_mesh
from commefficient_tpu.telemetry.journal import summarize
from commefficient_tpu.telemetry.trace import TRACE

W, POP, B = 8, 23, 4
WIDTHS = [300, 1500, 2048]      # below 1,024; not a multiple; a multiple


def _loss_fn(params, batch, mask):
    x, y = batch
    per_ex = 0.5 * (x @ params["w"] - y) ** 2
    loss = (per_ex * mask).sum() / jnp.maximum(mask.sum(), 1.0)
    return loss, (loss,)


def _cfg(D, **kw):
    base = dict(mode="local_topk", error_type="local",
                local_momentum=0.9, do_topk_down=True, k=16, down_k=8,
                weight_decay=0.0, num_workers=W, microbatch_size=-1,
                grad_size=D, num_clients=POP, seed=0)
    base.update(kw)
    return Config(**base).validate()


def _build(D, n_devices, **kw):
    cfg = _cfg(D, **kw)
    vec, unravel = flatten_params(
        {"w": jnp.arange(D, dtype=jnp.float32) / D})
    mesh = make_client_mesh(n_devices)
    handle = fround.make_train_fn(_loss_fn, unravel, cfg, mesh)
    clients = fround.init_client_state(cfg, POP, vec, mesh=mesh)
    return cfg, handle, clients, vec, mesh


# ---------------------------------------------------------------------------
# the format itself


@pytest.mark.parametrize("D", [1, 300, 1024, 1500, 6_568_640])
def test_lane_rows_is_a_whole_number_of_tiles(D):
    """Whole (8, 128) tiles from D = 1,024 up; below it whole lanes
    only (no eightfold sublane padding for tiny models)."""
    T = lane_rows(D)
    step = 8 if D >= 1024 else 1
    assert T % step == 0 and T * 128 >= D and (T - step) * 128 < D


@pytest.mark.parametrize("D", WIDTHS)
@pytest.mark.parametrize("xp", [np, jnp], ids=["numpy", "jax"])
def test_rows_tiles_roundtrip_and_zero_padding(D, xp):
    rows = np.random.default_rng(D).normal(size=(5, D)).astype(np.float32)
    tiles = rows_to_tiles(xp.asarray(rows))
    assert isinstance(tiles, np.ndarray) == (xp is np)
    assert tiles.shape == (5, lane_rows(D), 128)
    flat = np.asarray(tiles).reshape(5, -1)
    np.testing.assert_array_equal(flat[:, :D], rows)
    assert not flat[:, D:].any()
    np.testing.assert_array_equal(np.asarray(tiles_to_rows(tiles, D)), rows)


def test_rowblock_reads_as_the_rows_it_holds():
    D = 300
    rows = np.random.default_rng(1).normal(size=(6, D)).astype(np.float32)
    block = RowBlock.from_rows(jnp.asarray(rows))
    assert block.shape == (6, D) and block.ndim == 2
    assert block.dtype == jnp.float32
    np.testing.assert_array_equal(np.asarray(block), rows)
    np.testing.assert_array_equal(np.asarray(block[2]), rows[2])
    np.testing.assert_array_equal(np.asarray(block[1:4]), rows[1:4])
    ids = np.array([5, 0, 3], np.int32)
    np.testing.assert_array_equal(np.asarray(block[ids]), rows[ids])
    new = np.ones((3, D), np.float32)
    rows[ids] = new
    np.testing.assert_array_equal(
        np.asarray(block.set_rows(jnp.asarray(ids), new)), rows)
    # one leaf, and the width rides the treedef
    leaves, treedef = jax.tree.flatten(block)
    assert [l.shape for l in leaves] == [(6, lane_rows(D), 128)]
    assert jax.tree.unflatten(treedef, leaves).D == D


@pytest.mark.parametrize("idx", [np.int64(2), np.int32(2),
                                 jnp.asarray(2, jnp.int32)],
                         ids=["np.int64", "np.int32", "jax scalar"])
def test_rowblock_reads_one_row_by_any_integer_scalar(idx):
    rows = np.random.default_rng(2).normal(size=(6, 300)).astype(np.float32)
    block = RowBlock.from_rows(jnp.asarray(rows))
    np.testing.assert_array_equal(np.asarray(block[idx]), rows[2])


@pytest.mark.parametrize("idx", [np.array([True, False] * 3), 1.5,
                                 np.zeros((2, 2), np.int32)],
                         ids=["bool mask", "float", "2-D ids"])
def test_rowblock_refuses_an_index_it_cannot_read(idx):
    block = RowBlock.from_rows(jnp.zeros((6, 300), jnp.float32))
    with pytest.raises(TypeError):
        block[idx]


# ---------------------------------------------------------------------------
# gather then scatter against a numpy [rows, D] reference


@pytest.mark.parametrize("n_devices", [1, 8])
@pytest.mark.parametrize("D", WIDTHS)
def test_gather_scatter_bit_identical_to_numpy_rows(D, n_devices):
    cfg, h, clients, vec, _ = _build(D, n_devices)
    rows = clients.errors.shape[0]
    assert rows % n_devices == 0 and rows >= POP
    ref = {f: np.asarray(getattr(clients, f)).copy()
           for f in clients._fields}
    np.testing.assert_array_equal(
        ref["weights"], np.tile(np.asarray(vec), (rows, 1)))
    rng = np.random.default_rng(D + n_devices)
    for _ in range(3):
        ids = rng.permutation(POP)[:W].astype(np.int32)   # unsorted
        assert (np.diff(ids) < 0).any()
        cohort = h.gather(clients, jnp.asarray(ids))
        for f in clients._fields:
            got = getattr(cohort, f)
            assert got.shape == (W, D)
            np.testing.assert_array_equal(np.asarray(got), ref[f][ids])
        new = jax.device_put(CohortState(*[
            rng.normal(size=(W, D)).astype(np.float32)
            for _ in clients._fields]), h.cohort_shardings)
        for f in clients._fields:
            ref[f][ids] = np.asarray(getattr(new, f))
        clients = h.scatter(clients, jnp.asarray(ids), new)
        for f in clients._fields:
            block = getattr(clients, f)
            np.testing.assert_array_equal(np.asarray(block), ref[f])
            # the padding was written as zeros once and stays zero
            flat = np.asarray(block.tiles).reshape(rows, -1)
            assert not flat[:, D:].any()


@pytest.mark.parametrize("n_devices", [1, 8])
def test_dropped_client_row_comes_back_bit_untouched(n_devices):
    """Through a whole dispatch (gather, round with a survivor mask,
    scatter): a dropped client's rows, tiles and padding, are the
    bytes they were; a survivor's moved."""
    D = 300
    cfg, h, clients, vec, mesh = _build(D, n_devices,
                                        do_topk_down=False)
    server = fround.init_server_state(cfg, vec, mesh=mesh)
    rng = np.random.default_rng(3)
    x = jnp.asarray(rng.normal(size=(W, B, D)).astype(np.float32))
    y = jnp.asarray(rng.normal(size=(W, B)).astype(np.float32))
    ids = rng.permutation(POP)[:W].astype(np.int32)
    key = jax.random.PRNGKey(0)
    batch = fround.RoundBatch(jnp.asarray(ids), (x, y),
                              jnp.ones((W, B), jnp.float32))
    # a first round so the rows hold something
    server, clients, _ = h(server, clients, batch, 0.1, key)
    before = [np.asarray(l).copy() for l in jax.tree.leaves(clients)]
    surv = np.ones(W, np.float32)
    surv[[1, 4]] = 0.0
    server, clients, _ = h(
        server, clients, batch._replace(survivors=jnp.asarray(surv)),
        0.1, key)
    for was, now in zip(before, jax.tree.leaves(clients)):
        now = np.asarray(now)
        if now.ndim != 3:
            continue
        for slot, cid in enumerate(ids):
            same = was[cid].tobytes() == now[cid].tobytes()
            assert same == (surv[slot] == 0.0), (slot, cid)
        untouched = np.setdiff1d(np.arange(now.shape[0]), ids)
        assert was[untouched].tobytes() == now[untouched].tobytes()


# ---------------------------------------------------------------------------
# the programs' structure


def _primitives_over(jaxpr, pop):
    """(primitive name, operand shapes) of every equation, sub-jaxprs
    included, one of whose operands carries the population dim."""
    out = []
    for eqn in jaxpr.eqns:
        shapes = [tuple(getattr(v.aval, "shape", ())) for v in eqn.invars]
        if any(pop in s for s in shapes):
            out.append((eqn.primitive.name, shapes))
        for v in eqn.params.values():
            for sub in sub_jaxprs(v):
                out.extend(_primitives_over(sub, pop))
    return out


@pytest.mark.parametrize("D", [300, 1500])
def test_one_device_programs_hold_no_gather_over_the_block(D):
    """What the TPU compiler makes hundreds of strided pieces of: on
    one device the rows leave the block by `dynamic_slice`, never by
    the `gather` primitive (and so never through a [rows, D] view)."""
    cfg, h, clients, _, _ = _build(D, 1)
    ids = jnp.arange(W, dtype=jnp.int32)
    cohort = jax.eval_shape(h.gather_fn, clients, ids)
    g = jax.make_jaxpr(h.gather_fn)(clients, ids)
    s = jax.make_jaxpr(h.scatter_fn)(clients, ids, cohort)
    rows = clients.errors.shape[0]
    over_g = _primitives_over(g.jaxpr, rows)
    over_s = _primitives_over(s.jaxpr, rows)
    assert over_g and over_s
    assert "gather" not in {name for name, _ in over_g + over_s}
    # the row loop, and inside it the one-row copy
    assert {name for name, _ in over_g} == {"scan", "dynamic_slice"}
    # every population-shaped operand is in the tile form
    for _, shapes in over_g + over_s:
        for shp in shapes:
            if rows in shp:
                assert shp == (rows, lane_rows(D), 128)


@pytest.mark.parametrize("n_devices", [1, 8])
def test_scatter_block_is_donated_and_aliased(n_devices):
    cfg, h, clients, _, _ = _build(300, n_devices)
    assert h.scatter_donate_argnums == fround.SCATTER_DEAD_ARGNUMS
    ids = jnp.arange(W, dtype=jnp.int32)
    cohort = h.gather(clients, ids)
    compiled = h.scatter.lower(clients, ids, cohort).compile()
    # every tracked block's buffer comes back as the result's
    text = compiled.as_text()
    header = text[text.index("input_output_alias"):].split("\n")[0]
    assert header.count("may-alias") + header.count("must-alias") == 3
    out = h.scatter(clients, ids, cohort)
    assert all(l.is_deleted() for l in jax.tree.leaves(clients))
    assert not any(l.is_deleted() for l in jax.tree.leaves(out))


def test_mesh_programs_take_the_blocks_share_not_the_block():
    """On the 8-device mesh each device is handed an eighth of every
    block, and no collective moves anything block-sized: the cohort
    crosses devices, the block never does."""
    D, n = 1500, 8
    cfg, h, clients, _, _ = _build(D, n)
    ids = jnp.arange(W, dtype=jnp.int32)
    cohort = h.gather(clients, ids)
    rows = clients.errors.shape[0]
    share = 3 * (rows // n) * lane_rows(D) * 128 * 4
    cohort_bytes = 3 * W * lane_rows(D) * 128 * 4
    for compiled, extra in (
            (h.gather.lower(clients, ids).compile(), W * 4),
            (h.scatter.lower(clients, ids, cohort).compile(),
             W * 4 + 3 * (W // n) * D * 4)):
        args = compiled.memory_analysis().argument_size_in_bytes
        assert share <= args <= share + extra + 1024, (args, share)
        for shape in re.findall(
                r"= \(?f32\[([\d,]+)\][^=]*? (?:all-gather|all-reduce|"
                r"all-to-all|collective-permute|reduce-scatter)",
                compiled.as_text()):
            size = 4 * int(np.prod([int(d) for d in shape.split(",")]))
            assert size <= cohort_bytes, shape


# ---------------------------------------------------------------------------
# checkpoints and resume through the format


def _fed_model(cfg):
    model = FedModel(None, _loss_fn, cfg,
                     params={"w": jnp.zeros(cfg.grad_size, jnp.float32)},
                     num_clients=POP)
    opt = FedOptimizer(model, cfg)
    opt.param_groups[0]["lr"] = 0.1
    return model


def _drive(model, rounds, start=0, seed=9):
    D = model.cfg.grad_size
    rng = np.random.RandomState(7)
    x = rng.randn(W, B, D).astype(np.float32)
    y = rng.randn(W, B).astype(np.float32)
    mask = np.ones((W, B), np.float32)
    rng = np.random.RandomState(seed)
    all_ids = [rng.choice(POP, W, replace=False).astype(np.int32)
               for _ in range(start + rounds)]
    for ids in all_ids[start:]:
        model((ids, (x, y), mask))


@pytest.mark.parametrize("sparse", [False, True],
                         ids=["dense", "touched_rows"])
def test_save_kill_resume_bit_exact_through_the_format(tmp_path, sparse):
    """6 straight rounds == 3 rounds, save, a fresh model, load, 3
    rounds, bit for bit; and the file holds [rows, D] arrays whichever
    way it was written, as files from before the tile form do."""
    from commefficient_tpu.utils.checkpoint import (
        load_checkpoint, save_checkpoint,
    )

    cfg = _cfg(300)
    straight = _fed_model(cfg)
    _drive(straight, 6)

    first = _fed_model(cfg)
    _drive(first, 3)
    path = str(tmp_path / "ck.npz")
    save_checkpoint(
        path, first.server, first.clients,
        fingerprint=first.checkpoint_fingerprint,
        accountant=first.accountant,
        prev_change_words=first._prev_change_words,
        client_rows=first.client_rows_payload() if sparse else None)
    z = np.load(path)
    key = "crows_errors" if sparse else "client_errors"
    assert z[key].ndim == 2 and z[key].shape[1] == cfg.grad_size
    del first

    second = _fed_model(cfg)
    second.load_state(load_checkpoint(
        path, expect_fingerprint=second.checkpoint_fingerprint))
    assert isinstance(second.clients.errors, RowBlock)
    _drive(second, 3, start=3)
    np.testing.assert_array_equal(np.asarray(second.server.ps_weights),
                                  np.asarray(straight.server.ps_weights))
    for a, b in zip(jax.tree.leaves(second.clients),
                    jax.tree.leaves(straight.clients)):
        assert np.asarray(a).tobytes() == np.asarray(b).tobytes()


def test_checkpoint_written_in_the_old_format_loads(tmp_path):
    """A file as the [rows, D] blocks wrote it (plain 2-D client_*
    arrays) loads into RowBlocks holding those rows."""
    from commefficient_tpu.utils.checkpoint import load_checkpoint

    D, rows = 300, 24
    rng = np.random.default_rng(5)
    blocks = {k: rng.normal(size=(rows, D)).astype(np.float32)
              for k in ("client_errors", "client_velocities")}
    path = str(tmp_path / "old.npz")
    np.savez(path, ps_weights=np.zeros(D, np.float32),
             Vvelocity=np.zeros(D, np.float32),
             Verror=np.zeros(D, np.float32), round_idx=np.int32(4),
             scheduler_step=np.asarray(4),
             client_weights=np.zeros((0,), np.float32), **blocks)
    ckpt = load_checkpoint(path)
    assert isinstance(ckpt.clients.errors, RowBlock)
    assert ckpt.clients.errors.tiles.shape == (rows, lane_rows(D), 128)
    assert ckpt.clients.weights.shape == (0,)
    np.testing.assert_array_equal(np.asarray(ckpt.clients.errors),
                                  blocks["client_errors"])
    np.testing.assert_array_equal(np.asarray(ckpt.clients.velocities),
                                  blocks["client_velocities"])


# ---------------------------------------------------------------------------
# the counter: what the two programs have to move


def test_gather_and_scatter_spans_carry_rows_and_bytes():
    D = 300
    cfg, h, clients, vec, mesh = _build(D, 1, do_topk_down=False)
    server = fround.init_server_state(cfg, vec, mesh=mesh)
    batch = fround.RoundBatch(
        jnp.arange(W, dtype=jnp.int32),
        (jnp.zeros((W, B, D)), jnp.zeros((W, B))), jnp.ones((W, B)))
    TRACE.enable(controller=0)
    try:
        for _ in range(2):
            server, clients, _ = h(server, clients, batch, 0.1,
                                   jax.random.PRNGKey(0))
        spans, _ = TRACE.drain()
    finally:
        TRACE.disable()
    moved = [sp for sp in spans if sp["name"] in ("gather", "scatter")]
    assert len(moved) == 4
    for sp in moved:        # two tracked blocks of W rows of D float32
        assert sp["rows"] == 2 * W and sp["bytes"] == 2 * W * D * 4
    digest = summarize([{"v": 1, "event": "trace", "ts": 0.0,
                         "mono": 0.0, "spans": spans}])
    assert digest["state_motion_rows_per_round"] == 4 * W
    assert digest["state_motion_bytes_per_round"] == 4 * W * D * 4

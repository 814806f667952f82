"""The download accountant at large D: nested dense change bitsets
are skipped by the OR walk (and give their memory back) with every
byte count unchanged, and the round program's own tiled packing of
the change bits counts what the classic packing counts."""
import jax.numpy as jnp
import numpy as np
import pytest

from commefficient_tpu import Config
from commefficient_tpu.federated import accounting as acc


def accountant(d, **kw):
    cfg = Config(mode="uncompressed", error_type="none",
                 local_momentum=0.0, num_workers=2, num_clients=16,
                 local_batch_size=1, num_epochs=5, **kw) \
        .replace(grad_size=d).validate()
    return acc.CommAccountant(cfg, 16)


def bitsets(kind, rounds, n_words, rng):
    """Change bitsets a run could produce: `nested` grows like dense
    momentum SGD's (a coordinate that moved keeps moving), `sparse`
    are unrelated top-k supports, `mixed` alternates."""
    out, cur = [], np.zeros(n_words, np.uint32)
    for r in range(rounds):
        fresh = rng.randint(0, 2 ** 32, n_words, dtype=np.uint64) \
            .astype(np.uint32)
        if kind == "nested" or (kind == "mixed" and r % 3):
            cur = cur | fresh | np.uint32(0xF0F0F0F0)
            out.append(cur.copy())
        else:
            out.append(fresh & rng.randint(0, 2 ** 32, n_words,
                                           dtype=np.uint64)
                       .astype(np.uint32) & np.uint32(0x01010101))
    return out


@pytest.mark.parametrize("kind", ["nested", "sparse", "mixed"])
@pytest.mark.parametrize("native", [True, False])
def test_skipping_covered_bitsets_changes_no_byte(kind, native,
                                                  monkeypatch):
    """Against the walk over every bitset (the parent's): the same
    download and upload bytes for every client of every round."""
    if not native:
        monkeypatch.setattr(acc, "_native", None)
    elif acc._native is None:
        pytest.skip("native accounting not built")
    d = 32 * 64
    rng = np.random.RandomState(0)
    rounds = bitsets(kind, 24, 64, rng)
    a = accountant(d)
    plain = accountant(d)
    # the parent's accountant: nothing is ever marked covered
    monkeypatch.setattr(
        plain, "_append",
        lambda w: (plain.changes.append(w),
                   plain._covered.append(False)))
    prev = None
    for r, words in enumerate(rounds):
        ids = rng.choice(16, size=2, replace=False)
        got = a.record_round(ids, prev)
        want = plain.record_round(ids, prev)
        np.testing.assert_array_equal(got[0], want[0])
        np.testing.assert_array_equal(got[1], want[1])
        prev = words
    if kind == "nested":
        assert sum(a._covered) == len(a.changes) - 1
        assert all(c is a._empty for c in list(a.changes)[:-1])
    if kind == "sparse":
        assert not any(a._covered)
    # a checkpoint round trip keeps the counts too
    b = accountant(d)
    b.load_state_dict(a.state_dict())
    ids = np.array([3, 11])
    np.testing.assert_array_equal(a.record_round(ids, prev)[0],
                                  b.record_round(ids, prev)[0])


@pytest.mark.parametrize("d", [4096, 5000, 3 * 4096 + 17, 40])
def test_tiled_packing_counts_what_the_classic_packing_counts(d):
    rng = np.random.RandomState(d)
    update = rng.randn(d).astype(np.float32) * (rng.rand(d) < 0.3)
    tiled = np.asarray(acc.pack_change_bits_tiled(jnp.asarray(update)))
    classic = np.asarray(acc.pack_change_bits(jnp.asarray(update)))
    assert tiled.shape == (acc.tiled_words(d),)
    assert acc._popcount(tiled) == acc._popcount(classic) \
        == int((update != 0).sum())
    # bit j of word (row, lane) is coordinate row*4096 + j*128 + lane
    bits = np.zeros(acc.tiled_words(d) * 32, bool)
    nz = np.flatnonzero(update)
    row, rest = nz // 4096, nz % 4096
    bits[(row * 128 + rest % 128) * 32 + rest // 128] = True
    want = np.packbits(bits.reshape(-1, 32)[:, ::-1], axis=1) \
        .view(">u4").astype(np.uint32).reshape(-1)
    np.testing.assert_array_equal(tiled, want)


# ---------------- the in-place server update, end to end ------------------

D = 200


def _loss(params, batch, mask):
    x, y = batch
    per_ex = 0.5 * (x @ params["w"] - y) ** 2
    loss = (per_ex * mask).sum() / jnp.maximum(mask.sum(), 1.0)
    return loss, (loss,)


def _run(rounds, mode, **kw):
    from commefficient_tpu.federated.api import FedModel, FedOptimizer
    cfg = Config(mode=mode, weight_decay=0.0, num_workers=4,
                 local_momentum=0.0, virtual_momentum=0.9,
                 microbatch_size=-1, num_clients=16, local_batch_size=2,
                 num_epochs=4, **kw)
    model = FedModel(None, _loss, cfg,
                     params={"w": jnp.zeros((D,), jnp.float32)},
                     num_clients=16)
    opt = FedOptimizer(model)
    opt.param_groups[0]["lr"] = 0.05
    rng = np.random.RandomState(1)
    w_true = rng.randn(D).astype(np.float32) * (rng.rand(D) < 0.5)
    downs, ups = [], []
    for _ in range(rounds):
        ids = rng.choice(16, size=4, replace=False).astype(np.int32)
        x = rng.randn(4, 2, D).astype(np.float32)
        # half the coordinates never get a gradient: their bits stay 0
        x = x * (np.arange(D) % 2 == 0)
        y = x @ w_true
        *_, down, up = model((ids, (x, y), np.ones((4, 2), np.float32)))
        opt.step()
        downs.append(np.asarray(down))
        ups.append(np.asarray(up))
    return model, np.array(downs), np.array(ups)


@pytest.mark.parametrize("mode,kw", [
    ("uncompressed", dict(error_type="none")),
    ("true_topk", dict(error_type="virtual", k=20)),
])
def test_in_place_server_reports_the_parents_bytes(mode, kw, monkeypatch):
    """A tiny model through the large-D path (ServerState donated,
    change bits packed by the round program in tiled order, the
    unused error a placeholder) against the default path: the same
    weights, the same download and upload bytes every round."""
    from commefficient_tpu import config as config_mod
    base, down0, up0 = _run(8, mode, **kw)
    assert not base.cfg.server_in_place
    monkeypatch.setattr(config_mod, "IN_PLACE_MIN_D", 1)
    big, down1, up1 = _run(8, mode, **kw)
    assert big.cfg.server_in_place
    np.testing.assert_array_equal(down0, down1)
    np.testing.assert_array_equal(up0, up1)
    assert down0[-1].sum() > 0
    np.testing.assert_array_equal(np.asarray(base.server.ps_weights),
                                  np.asarray(big.server.ps_weights))
    np.testing.assert_array_equal(np.asarray(base.server.Vvelocity),
                                  np.asarray(big.server.Vvelocity))
    want = (1,) if mode == "uncompressed" else (D,)
    assert big.server.Verror.shape == want
    assert base.server.Verror.shape == (D,)

"""The sketch table's wire dtype (`--sketch_table_dtype`): round-trip
error bounds, what quantization does to linearity, the bytes billed,
and the engine under a quantized wire: convergence through the
virtual error accumulator and crash->resume bit-exactness.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from commefficient_tpu.config import Config
from commefficient_tpu.federated.round import (
    RoundBatch, init_client_state, init_server_state, make_round_fns,
)
from commefficient_tpu.ops.flat import flatten_params
from commefficient_tpu.ops.quant import table_elem_bytes, wire_roundtrip
from commefficient_tpu.ops.sketch import CSVec

# ---------------------------------------------------------------------------
# linearity (the load-bearing FetchSGD property)


@pytest.mark.parametrize("route", ["static", "scan"])
def test_linearity_exact_f32(route, monkeypatch):
    # the f32 wire is exact on either encode route; the quantized
    # pair below says what bf16/int8 cost
    if route == "scan":
        import commefficient_tpu.ops.sketch as sketch_mod
        monkeypatch.setattr(sketch_mod, "STATIC_UNROLL_LIMIT", 0)
    s = CSVec(d=1000, c=200, r=5, num_blocks=3)
    assert s._static_path == (route == "static")
    rng = np.random.RandomState(4)
    a = jnp.asarray(rng.randn(s.d).astype(np.float32))
    b = jnp.asarray(rng.randn(s.d).astype(np.float32))
    np.testing.assert_allclose(np.asarray(s.encode(a) + s.encode(b)),
                               np.asarray(s.encode(a + b)),
                               rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("dtype", ["bf16", "int8"])
def test_linearity_quantized_tolerance(dtype):
    # the wire round-trip breaks exact linearity by at most the
    # quantization step per term: |Q(T(a+b)) - (Q(T(a)) + Q(T(b)))|
    # <= 3 quantization errors, each bounded by the row absmax times
    # the dtype's relative step
    s = CSVec(d=1000, c=200, r=5, num_blocks=3)
    rng = np.random.RandomState(5)
    a = jnp.asarray(rng.randn(s.d).astype(np.float32))
    b = jnp.asarray(rng.randn(s.d).astype(np.float32))
    ta, tb, tab = s.encode(a), s.encode(b), s.encode(a + b)
    qa = np.asarray(wire_roundtrip(ta, dtype))
    qb = np.asarray(wire_roundtrip(tb, dtype))
    qab = np.asarray(wire_roundtrip(tab, dtype))
    step = {"bf16": 2.0 ** -8, "int8": 1.0 / 127.0}[dtype]
    bound = 3.0 * step * max(float(jnp.abs(t).max())
                             for t in (ta, tb, tab))
    assert np.abs(qab - (qa + qb)).max() <= bound


# ---------------------------------------------------------------------------
# quantized wire transport properties


def test_wire_roundtrip_f32_is_identity():
    t = jnp.ones((3, 8))
    assert wire_roundtrip(t, "f32") is t  # not equal — the SAME array


@pytest.mark.parametrize("dtype,rel", [("bf16", 2.0 ** -8),
                                       ("int8", 1.0 / 127.0)])
def test_wire_roundtrip_error_bound(dtype, rel):
    rng = np.random.RandomState(6)
    t = jnp.asarray(rng.randn(5, 333).astype(np.float32)) * 7.3
    rt = np.asarray(wire_roundtrip(t, dtype))
    # bf16 error is relative per element; int8 is absolute per row
    # (scale = row absmax / 127) — both bounded by absmax * rel
    per_row_bound = np.max(np.abs(np.asarray(t)), axis=1,
                           keepdims=True) * rel
    assert np.all(np.abs(rt - np.asarray(t)) <= per_row_bound + 1e-7)


def test_wire_roundtrip_zero_rows_exact_and_deterministic():
    t = jnp.zeros((4, 64)).at[1, 3].set(2.5)
    for dtype in ("bf16", "int8"):
        rt1 = np.asarray(wire_roundtrip(t, dtype))
        rt2 = np.asarray(wire_roundtrip(t, dtype))
        np.testing.assert_array_equal(rt1, rt2)  # round-to-nearest,
        # no stochastic rounding: resume replays identical tables
        assert np.all(rt1[0] == 0) and np.all(rt1[2:] == 0)
        # a row's absmax is representable exactly in both dtypes
        assert rt1[1, 3] == 2.5
    assert table_elem_bytes("f32") == 4
    assert table_elem_bytes("bf16") == 2
    assert table_elem_bytes("int8") == 1


# ---------------------------------------------------------------------------
# the round engine under a quantized wire

D = 8


def loss_fn(params, batch, mask):
    x, y = batch
    pred = x @ params["w"]
    per_ex = 0.5 * (pred - y) ** 2
    denom = jnp.maximum(mask.sum(), 1.0)
    loss = (per_ex * mask).sum() / denom
    acc = ((jnp.abs(pred - y) < 0.5) * mask).sum() / denom
    return loss, (acc,)


def _sketch_cfg(**kw):
    base = dict(mode="sketch", grad_size=D, weight_decay=0.0,
                num_workers=8, local_momentum=0.0, virtual_momentum=0.9,
                error_type="virtual", microbatch_size=-1, num_clients=8,
                k=D, num_rows=5, num_cols=64, num_blocks=1)
    base.update(kw)
    return Config(**base).validate()


def _problem(seed=0):
    rng = np.random.RandomState(seed)
    w_true = rng.randn(D).astype(np.float32)
    x = rng.randn(8, 4, D).astype(np.float32)
    y = np.einsum("wbd,d->wb", x, w_true).astype(np.float32)
    return jnp.asarray(x), jnp.asarray(y)


def _round_setup(mesh, cfg):
    params = {"w": jnp.zeros(D)}
    vec, unravel = flatten_params(params)
    train_round, _ = make_round_fns(loss_fn, unravel, cfg, mesh)
    server = init_server_state(cfg, vec)
    clients = init_client_state(cfg, cfg.num_clients, vec)
    return train_round, server, clients


@pytest.mark.faults
def test_quantized_resume_bit_exact(mesh):
    """crash->resume bit-exactness on the quantized-transport config:
    2 rounds + state round-trip through host numpy (what a checkpoint
    serializes) + 2 rounds == 4 straight rounds, bit for bit.
    Round-to-nearest quantization makes the replay exact."""
    from commefficient_tpu.federated.round import ServerState

    # the straight and resumed runs both start from ONE initial state
    # object; donation would delete it after the first run's dispatch
    cfg = _sketch_cfg(sketch_table_dtype="int8",
                      donate_round_state=False)
    x, y = _problem()
    batch = RoundBatch(jnp.arange(8, dtype=jnp.int32), (x, y),
                       jnp.ones((8, 4)))
    key = jax.random.PRNGKey(0)

    train_round, server, clients = _round_setup(mesh, cfg)
    s_straight, c_straight = server, clients
    for _ in range(4):
        s_straight, c_straight, _ = train_round(
            s_straight, c_straight, batch, 0.1, key)

    s_mid, c_mid = server, clients
    for _ in range(2):
        s_mid, c_mid, _ = train_round(s_mid, c_mid, batch, 0.1, key)
    # host round-trip + a FRESH trace (new round fns), as resume does
    s_mid = ServerState(*[jnp.asarray(np.asarray(f)) for f in s_mid])
    train_round2, _, _ = _round_setup(mesh, cfg)
    for _ in range(2):
        s_mid, c_mid, _ = train_round2(s_mid, c_mid, batch, 0.1, key)

    for a, b in zip(s_straight, s_mid):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_quantized_round_error_feedback_absorbs_noise(mesh):
    """The FetchSGD extension the quantized transport rides on: an
    int8 wire table must not stop the sketch round from converging on
    the closed-form problem — the rounding noise stays in the virtual
    error accumulator and retransmits, like any compression noise."""
    x, y = _problem()
    batch = RoundBatch(jnp.arange(8, dtype=jnp.int32), (x, y),
                       jnp.ones((8, 4)))
    key = jax.random.PRNGKey(0)
    losses = {}
    for dtype in ("f32", "int8"):
        cfg = _sketch_cfg(sketch_table_dtype=dtype, num_cols=256)
        train_round, server, clients = _round_setup(mesh, cfg)
        for _ in range(150):
            server, clients, m = train_round(server, clients, batch,
                                             0.1, key)
        losses[dtype] = float(np.mean(np.asarray(m.losses)))
    assert losses["f32"] < 0.02, losses
    assert losses["int8"] < 0.05, losses


# ---------------------------------------------------------------------------
# config surface


def test_config_validates_wire_dtype_flag(capsys):
    # the removed flag and field fail loudly, not swallowed
    from commefficient_tpu.config import parse_args
    with pytest.raises(SystemExit):
        parse_args(argv=["--kernel_backend", "xla"])
    assert ("unrecognized arguments: --kernel_backend"
            in capsys.readouterr().err)
    with pytest.raises(TypeError, match="kernel_backend"):
        Config(mode="uncompressed", kernel_backend="xla")
    with pytest.raises(ValueError, match="sketch_table_dtype"):
        Config(mode="sketch", local_momentum=0.0,
               sketch_table_dtype="fp8").validate()
    with pytest.raises(ValueError, match="requires --mode sketch"):
        Config(mode="uncompressed", error_type="none",
               sketch_table_dtype="bf16").validate()


def test_upload_bytes_wire_dtype():
    base = dict(mode="sketch", error_type="virtual", local_momentum=0.0,
                num_rows=3, num_cols=100, grad_size=64)
    assert Config(**base).upload_bytes == 4 * 300
    assert Config(**base, sketch_table_dtype="bf16").upload_bytes == 2 * 300
    # int8 ships the per-row f32 dequantization scales
    assert Config(**base, sketch_table_dtype="int8").upload_bytes == 300 + 12

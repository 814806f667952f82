"""Blockwise attention for long sequences: the flash backward inside
the round's `shard_map` (ROADMAP A1), and grouped-query, windowed
`blockwise_attention` against dense masked attention, forward and
backward, inside `shard_map` too. Its TPU kernels run here in
interpret mode: against the XLA path with float32 products (which
they must then match to rounding) and against dense attention with
the chip's bfloat16 product operands."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax import shard_map
from jax.experimental.pallas import tpu as pltpu
from jax.sharding import PartitionSpec as P

from commefficient_tpu.ops import attention as A


def rand(shape, seed):
    return jnp.asarray(np.random.RandomState(seed).randn(*shape)
                       .astype(np.float32))


def sharded(fn, mesh, n_args):
    """`fn` over the leading axis of its operands, one shard a device
    of the `clients` mesh, `check_vma` on: as the round program runs
    a client's forward and backward."""
    return jax.jit(shard_map(fn, mesh=mesh,
                             in_specs=(P("clients"),) * n_args,
                             out_specs=P("clients")))


def test_flash_backward_traces_inside_shard_map_at_256(mesh):
    """ROADMAP A1: the scan carries of the flash forward and backward
    are varying, so the VJP traces under `check_vma` at L >= 256."""
    n = mesh.shape["clients"]
    q, k, v = (rand((n, 2, 256, 32), s) for s in (0, 1, 2))

    def loss(q, k, v):
        return (A.flash_attention(q, k, v) ** 2).sum(axis=(1, 2, 3))

    def ref_loss(q, k, v):
        return (A.reference_attention(q, k, v) ** 2).sum()

    grads = sharded(lambda *a: jax.grad(
        lambda *b: loss(*b).sum(), argnums=(0, 1, 2))(*a)[0], mesh, 3)
    got = grads(q, k, v)
    want = jax.grad(ref_loss)(q, k, v)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-4, atol=2e-5)


CASES = [
    # (L, Hq, Hkv, window, block)
    (32, 4, 2, None, 8),
    (32, 4, 2, 8, 8),
    (32, 6, 2, 12, 8),      # a window that is no block multiple
    (29, 4, 1, 8, 8),       # a length that is no block multiple
    (32, 2, 2, 8, 512),     # one block holds the whole sequence
    (64, 7, 1, 16, 16),
]


@pytest.mark.parametrize("L,Hq,Hkv,window,block", CASES)
def test_blockwise_matches_dense_forward_backward(L, Hq, Hkv, window,
                                                  block):
    q = rand((2, L, Hq, 16), 0)
    k, v = rand((2, L, Hkv, 16), 1), rand((2, L, Hkv, 16), 2)
    w = rand((2, L, Hq, 16), 3)

    def f(fn):
        return lambda q, k, v: (fn(q, k, v) * w).sum()

    mine = lambda q, k, v: A.blockwise_attention(q, k, v, window, block)
    dense = lambda q, k, v: A.reference_windowed_attention(q, k, v, window)
    np.testing.assert_allclose(np.asarray(mine(q, k, v)),
                               np.asarray(dense(q, k, v)),
                               rtol=2e-5, atol=2e-6)
    got = jax.grad(f(mine), argnums=(0, 1, 2))(q, k, v)
    want = jax.grad(f(dense), argnums=(0, 1, 2))(q, k, v)
    for g, r in zip(got, want):
        np.testing.assert_allclose(np.asarray(g), np.asarray(r),
                                   rtol=2e-4, atol=2e-5)


@pytest.mark.parametrize("window", [None, 8])
def test_blockwise_inside_shard_map(mesh, window):
    """Forward and backward under `check_vma`, K and V at their own
    head count (nothing repeated)."""
    n = mesh.shape["clients"]
    q = rand((n, 32, 4, 16), 0)
    k, v = rand((n, 32, 2, 16), 1), rand((n, 32, 2, 16), 2)

    def loss(fn):
        return lambda q, k, v: (fn(q, k, v) ** 2).sum()

    mine = lambda q, k, v: A.blockwise_attention(q, k, v, window, 8)
    dense = lambda q, k, v: A.reference_windowed_attention(q, k, v, window)
    for i in range(3):
        got = sharded(lambda *a: jax.grad(loss(mine), argnums=i)(*a),
                      mesh, 3)(q, k, v)
        want = jax.grad(loss(dense), argnums=i)(q, k, v)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=2e-4, atol=2e-5)


def test_blockwise_skips_blocks_outside_the_window():
    """The inner loop's bounds: with a window of two blocks a query
    block visits at most three key blocks, whatever the length."""
    a = jnp.arange(16)
    first = A._first_block(a, 16, 8)
    assert list(np.asarray(a + 1 - first)) == [1, 2] + [3] * 14
    assert list(np.asarray(A._first_block(a, None, 8))) == [0] * 16


# ---------------- the pair table ------------------------------------------

def mask_pairs(L, window, block):
    """The (query block, key block) pairs in which the mask keeps a
    position, read off the dense [L, L] mask."""
    pos = np.arange(L)
    ok = pos[:, None] >= pos[None, :]
    if window is not None:
        ok &= pos[None, :] > pos[:, None] - window
    n = L // block
    return set(zip(*np.nonzero(ok.reshape(n, block, n, block)
                               .any(axis=(1, 3)))))


@pytest.mark.parametrize("window,count", [(None, 136), (2048, 70),
                                          (4096, 108)],
                         ids=["full", "window2048", "window4096"])
def test_pair_table_at_the_cells_shapes(window, count):
    """8,192 positions in blocks of 512: the grid the kernels run in
    a full, a 2,048-window and a 4,096-window layer."""
    qi, kj = A.pair_table(8192 // 512, window, 512)
    assert len(qi) == count
    assert set(zip(qi, kj)) == mask_pairs(8192, window, 512)


@pytest.mark.parametrize("window", [1, 7, 8, 9, 17, 24, 100])
def test_pair_table_is_the_mask_s_nonempty_pairs(window):
    """Windows one over, one under and on a block multiple; query
    blocks ascending, each one's key blocks ascending and ending on the
    diagonal, as the kernels' first and last pairs assume."""
    qi, kj = A.pair_table(12, window, 8)
    assert set(zip(qi, kj)) == mask_pairs(96, window, 8)
    pairs = list(zip(qi.tolist(), kj.tolist()))
    assert pairs == sorted(pairs)
    for a in range(12):
        js = [j for b, j in pairs if b == a]
        assert js[0] == int(A._first_block(a, window, 8)) and js[-1] == a


# ---------------- the kernels, in interpret mode ----------------------------

KERNEL_CASES = [
    # (L, G, window): block 8, two key-value heads, Dh 16
    (48, 7, None), (48, 8, None), (48, 7, 16), (48, 8, 16), (48, 7, 32),
    (48, 8, 32), (45, 7, None), (45, 8, 16), (45, 8, 32),
    # Nemotron-H's 32 query heads over 2 key-value heads
    (45, 16, 16), (48, 16, None),
]


def kernel_inputs(L, G, seed=0):
    Hkv = 2
    q, do = rand((2, L, Hkv * G, 16), seed), rand((2, L, Hkv * G, 16),
                                                 seed + 3)
    k, v = rand((2, L, Hkv, 16), seed + 1), rand((2, L, Hkv, 16), seed + 2)
    return q, k, v, do


def run_kernels(q, k, v, do, window, block=8):
    """o, lse (real rows) and the three gradients from the kernels."""
    L = q.shape[1]
    block, Lp = A._bwa_geometry(L, block)
    pad = lambda x: A._bwa_pad(x, Lp)
    o, lse = A._bw_fwd_pallas(pad(q), pad(k), pad(v), window, block,
                              interpret=True)
    lse = jnp.where(jnp.arange(Lp) < L, lse, A.LSE_PAD)
    grads = A._bw_bwd_pallas(pad(q), pad(k), pad(v), pad(o[:, :L]), pad(do),
                             lse, window, block, interpret=True)
    return (o[:, :L], lse[..., :L]) + tuple(g[:, :L] for g in grads)


def run_xla(q, k, v, do, window, block=8):
    L = q.shape[1]
    block, Lp = A._bwa_geometry(L, block)
    pad = lambda x: A._bwa_pad(x, Lp)
    o, lse = A._bw_fwd_xla(pad(q), pad(k), pad(v), window, block)
    lse = jnp.where(jnp.arange(Lp) < L, lse, A.LSE_PAD)
    grads = A._bw_bwd_xla(pad(q), pad(k), pad(v), pad(o[:, :L]), pad(do),
                          lse, window, block)
    return (o[:, :L], lse[..., :L]) + tuple(g[:, :L] for g in grads)


def dense_lse(q, k, window):
    """[B, Hkv, G, L] logsumexp of the masked, scaled scores."""
    B, L, Hq, Dh = q.shape
    Hkv = k.shape[2]
    qg = q.reshape(B, L, Hkv, Hq // Hkv, Dh)
    s = jnp.einsum("bqhgd,bkhd->bhgqk", qg, k) / np.sqrt(Dh)
    pos = jnp.arange(L)
    s = jnp.where(A._allowed(pos, pos, window), s, -jnp.inf)
    return jax.nn.logsumexp(s, axis=-1)


@pytest.mark.parametrize("L,G,window", KERNEL_CASES)
def test_kernels_match_the_xla_path(monkeypatch, L, G, window):
    """With float32 products, as the XLA path's on a CPU: o and lse
    to the last bits, dq, dk and dv to float32 rounding."""
    monkeypatch.setattr(A, "_MXU_OPERAND", jnp.float32)
    q, k, v, do = kernel_inputs(L, G)
    got = run_kernels(q, k, v, do, window)
    want = run_xla(q, k, v, do, window)
    for name, g, w in zip(("o", "lse", "dq", "dk", "dv"), got, want):
        np.testing.assert_allclose(np.asarray(g), np.asarray(w),
                                   rtol=1e-5, atol=5e-6, err_msg=name)


@pytest.mark.parametrize("L,G,window", KERNEL_CASES[::2])
def test_kernels_match_dense_attention(L, G, window):
    """With the chip's bfloat16 product operands: every result within
    a bfloat16 rounding's reach of dense float32 attention (a pair
    missed or masked wrong errs by its whole share)."""
    q, k, v, do = kernel_inputs(L, G, seed=10)
    o, lse, dq, dk, dv = run_kernels(q, k, v, do, window)
    ref_o, vjp = jax.vjp(lambda q, k, v: A.reference_windowed_attention(
        q, k, v, window), q, k, v)
    for name, g, w in zip(("o", "lse", "dq", "dk", "dv"),
                          (o, lse, dq, dk, dv),
                          (ref_o, dense_lse(q, k, window)) + vjp(do)):
        rel = float(jnp.linalg.norm(g - w) / jnp.linalg.norm(w))
        assert rel < 1e-2, (name, rel)


@pytest.mark.parametrize("window", [None, 16])
def test_kernels_inside_shard_map(mesh, monkeypatch, window):
    """`blockwise_attention` by its kernels (here interpreted, in
    blocks of 8 positions where a TPU takes 128 or more), forward and
    backward under `check_vma`, a client's sequence a device: matches
    the XLA path's results."""
    monkeypatch.setattr(A, "_MXU_OPERAND", jnp.float32)
    n = mesh.shape["clients"]
    q, k, v, do = kernel_inputs(40, 4)
    q, k, v, do = (jnp.concatenate([x] * (n // 2)) for x in (q, k, v, do))

    def grads(q, k, v):
        o, vjp = jax.vjp(lambda q, k, v: A.blockwise_attention(
            q, k, v, window, 8), q, k, v)
        return (o,) + vjp(do)

    def grads_sharded(q, k, v, do_):
        o, vjp = jax.vjp(lambda q, k, v: A.blockwise_attention(
            q, k, v, window, 8), q, k, v)
        return (o,) + vjp(do_)

    want = grads(q, k, v)
    monkeypatch.setattr(A, "_bwa_path", lambda L, block: (
        A._bw_fwd_pallas, A._bw_bwd_pallas, *A._bwa_geometry(L, block)))
    fn = jax.jit(shard_map(grads_sharded, mesh=mesh,
                           in_specs=(P("clients"),) * 4,
                           out_specs=(P("clients"),) * 4))
    with pltpu.force_tpu_interpret_mode():
        got = fn(q, k, v, do)
    for g, w in zip(got, want):
        np.testing.assert_allclose(np.asarray(g), np.asarray(w),
                                   rtol=1e-5, atol=5e-6)


@pytest.mark.parametrize("L,block,lanes,want", [
    (8192, 512, 128, (512, 8192)), (8192, 512, 8, (512, 8192)),
    (300, 512, 128, (384, 384)), (64, 8, 128, (128, 128)),
    (29, 8, 8, (8, 32)), (29, 512, 8, (32, 32))])
def test_block_geometry(L, block, lanes, want):
    """(block, padded length): the kernels take blocks of whole
    128-position lanes, the XLA path whole 8-position sublanes."""
    assert A._bwa_geometry(L, block, lanes) == want


def test_kernels_take_bfloat16_operands():
    """The bfloat16 control's q, k, v, o and do (Config.do_bf16), at
    Nemotron-H's 16 query heads a key-value head: both kernels agree
    with their float32 run to bfloat16's reach. (What the chip's
    compiler refuses of such operands, interpret mode does not:
    `tests/test_tpu_compile.py` compiles the backward for them.)"""
    q, k, v, do = kernel_inputs(48, 16, seed=20)
    low = run_kernels(*(x.astype(jnp.bfloat16) for x in (q, k, v, do)),
                      None)
    want = run_kernels(q, k, v, do, None)
    for name, g, w in zip(("o", "lse", "dq", "dk", "dv"), low, want):
        g = g.astype(jnp.float32)
        rel = float(jnp.linalg.norm(g - w) / jnp.linalg.norm(w))
        assert rel < 2e-2, (name, rel)

"""Blockwise attention for long sequences: the flash backward inside
the round's `shard_map` (ROADMAP A1), and grouped-query, windowed
`blockwise_attention` against dense masked attention, forward and
backward, inside `shard_map` too."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax import shard_map
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
    q = rand((2, Hq, L, 16), 0)
    k, v = rand((2, Hkv, L, 16), 1), rand((2, Hkv, L, 16), 2)
    w = rand((2, Hq, L, 16), 3)

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
    q = rand((n, 4, 32, 16), 0)
    k, v = rand((n, 2, 32, 16), 1), rand((n, 2, 32, 16), 2)

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

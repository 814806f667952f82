"""Ring attention (sequence parallelism): the sharded ring computation
must equal single-device causal attention on the full sequence, and
its gradients must flow (the long-context training path)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, PartitionSpec as P

from commefficient_tpu.ops.attention import reference_attention
from commefficient_tpu.parallel.ring import ring_attention

S = 8  # seq shards = the full CPU test mesh


def full_and_sharded(L=128, B=2, H=2, Dh=16, seed=0):
    rng = np.random.RandomState(seed)
    mk = lambda: jnp.asarray(rng.randn(B, H, L, Dh).astype(np.float32))
    return mk(), mk(), mk()


def make_ring_fn(mesh):
    def shard_fn(q, k, v):
        return ring_attention(q, k, v, axis_name="seq")

    from jax import shard_map

    # sequence axis (dim 2) sharded over the mesh
    return jax.jit(shard_map(
        shard_fn, mesh=mesh,
        in_specs=(P(None, None, "seq", None),) * 3,
        out_specs=P(None, None, "seq", None)))


def test_ring_matches_full_attention():
    if len(jax.devices()) < S:
        pytest.skip("needs the 8-device CPU mesh")
    mesh = Mesh(np.asarray(jax.devices()[:S]), axis_names=("seq",))
    q, k, v = full_and_sharded()
    out = make_ring_fn(mesh)(q, k, v)
    ref = reference_attention(q, k, v)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-5, atol=2e-6)


def test_ring_gradients_flow():
    if len(jax.devices()) < S:
        pytest.skip("needs the 8-device CPU mesh")
    mesh = Mesh(np.asarray(jax.devices()[:S]), axis_names=("seq",))
    q, k, v = full_and_sharded(L=64)

    ring = make_ring_fn(mesh)

    def loss_ring(q, k, v):
        return (ring(q, k, v) ** 2).sum()

    def loss_ref(q, k, v):
        return (reference_attention(q, k, v) ** 2).sum()

    g1 = jax.grad(loss_ring, argnums=(0, 1, 2))(q, k, v)
    g2 = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(g1, g2):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=2e-4, atol=2e-5)

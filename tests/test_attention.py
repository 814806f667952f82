"""Flash attention: XLA path, Pallas kernel (interpret mode), and the
tiled custom-VJP backward, all against the O(L^2) einsum reference."""
import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from commefficient_tpu.ops import attention as A


def qkv(B=2, H=2, L=256, Dh=32, seed=0):
    rng = np.random.RandomState(seed)
    mk = lambda: jnp.asarray(rng.randn(B, H, L, Dh).astype(np.float32))
    return mk(), mk(), mk()


def test_xla_forward_matches_reference():
    q, k, v = qkv()
    out = A.flash_attention(q, k, v)
    ref = A.reference_attention(q, k, v)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-5, atol=2e-6)


def test_pallas_kernel_matches_reference_interpret():
    """The kernel itself, run through the Pallas interpreter on CPU."""
    q, k, v = qkv(L=256, Dh=64)
    scale = 1.0 / math.sqrt(q.shape[-1])
    o, lse = A._flash_fwd_pallas(q, k, v, scale, 128, 128,
                                 interpret=True)
    ref = A.reference_attention(q, k, v)
    np.testing.assert_allclose(np.asarray(o), np.asarray(ref),
                               rtol=2e-5, atol=2e-6)
    # lse sanity: logsumexp of the masked scores
    _, lse_ref = A._flash_fwd_xla(q, k, v, scale, 128)
    np.testing.assert_allclose(np.asarray(lse), np.asarray(lse_ref),
                               rtol=1e-5, atol=1e-5)


def _kernel(q, k, v):
    return A._flash_fwd_pallas(q, k, v, 1.0 / math.sqrt(q.shape[-1]),
                               128, 128, interpret=True)


def test_pallas_kernel_lse_layout_is_the_one_mosaic_accepts():
    """lse leaves the kernel as [B*H, L, 1] in (1, block_q, 1) blocks
    (a (1, block_q) block of [B*H, L] is refused by the TPU lowering;
    tests/test_tpu_compile.py asks the compiler) and reaches the
    caller as [B, H, L], numbers unchanged."""
    q, k, v = qkv(L=256, Dh=64)
    jaxpr = jax.make_jaxpr(_kernel)(q, k, v)
    (call,) = [e for e in jaxpr.eqns if e.primitive.name == "pallas_call"]
    assert [o.aval.shape for o in call.outvars] == [(4, 256, 64),
                                                    (4, 256, 1)]
    o, lse = _kernel(q, k, v)
    assert lse.shape == (2, 2, 256)
    np.testing.assert_allclose(
        np.asarray(o), np.asarray(A.reference_attention(q, k, v)),
        rtol=2e-5, atol=2e-6)


def test_pallas_kernel_inside_shard_map(mesh):
    """The context the chip compiles and the CPU route never reaches:
    the round's shard_map over `clients`. Under check_vma the kernel's
    outputs must carry the operands' varying axes (a bare out_shape is
    rejected), also when some operands are replicated. jax 0.9.0's
    Pallas interpreter cannot EVALUATE there (it binds the kernel's
    primitives with program ids that vary over nothing), so the typed
    trace is checked with the check on and the numbers with it off."""
    from jax import shard_map
    from jax.sharding import PartitionSpec as P

    q, k, v = qkv(B=mesh.shape["clients"], L=256, Dh=64)
    for in_specs in (P("clients"), (P("clients"), P(), P())):
        mapped = shard_map(_kernel, mesh=mesh, in_specs=in_specs,
                           out_specs=P("clients"))
        if in_specs != P("clients"):
            k, v = k[:1], v[:1]
        o, lse = jax.eval_shape(mapped, q, k, v)
        assert o.shape == q.shape and lse.shape == q.shape[:3]
    q, k, v = qkv(B=mesh.shape["clients"], L=256, Dh=64)
    o, _ = shard_map(_kernel, mesh=mesh, in_specs=P("clients"),
                     out_specs=P("clients"), check_vma=False)(q, k, v)
    np.testing.assert_allclose(
        np.asarray(o), np.asarray(A.reference_attention(q, k, v)),
        rtol=2e-5, atol=2e-6)


def test_grad_matches_reference():
    q, k, v = qkv(L=128, Dh=16)

    def loss_flash(q, k, v):
        return (A.flash_attention(q, k, v) ** 2).sum()

    def loss_ref(q, k, v):
        return (A.reference_attention(q, k, v) ** 2).sum()

    g1 = jax.grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
    g2 = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(g1, g2):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=2e-4, atol=2e-5)


def test_odd_lengths_are_padded_internally():
    """Any L works: the op pads to a block multiple and slices back
    (causality keeps tail padding invisible to real queries); the
    backward's poisoned pad logsumexp keeps pad grads at exactly 0."""
    for L in (96, 257, 300):
        q, k, v = qkv(L=L, Dh=16, seed=L)
        out = A.flash_attention(q, k, v)
        ref = A.reference_attention(q, k, v)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   rtol=2e-5, atol=2e-6, err_msg=str(L))

    q, k, v = qkv(L=257, Dh=16, seed=9)
    g1 = jax.grad(lambda *a: (A.flash_attention(*a) ** 2).sum(),
                  argnums=(0, 1, 2))(q, k, v)
    g2 = jax.grad(lambda *a: (A.reference_attention(*a) ** 2).sum(),
                  argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(g1, g2):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=2e-4, atol=2e-5)


def test_explicit_zero_scale_respected():
    # sm_scale=0.0 must not fall back to the default 1/sqrt(Dh):
    # zero scale makes attention uniform over the causal prefix
    q, k, v = qkv(L=64, Dh=16)
    out = A.flash_attention(q, k, v, 0.0)
    ref = A.reference_attention(q, k, v, 0.0)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-5, atol=2e-6)
    L = 64
    causal_mean = jnp.cumsum(v.astype(jnp.float32), axis=2) / (
        jnp.arange(1, L + 1, dtype=jnp.float32)[None, None, :, None])
    np.testing.assert_allclose(np.asarray(out), np.asarray(causal_mean),
                               rtol=2e-5, atol=2e-6)

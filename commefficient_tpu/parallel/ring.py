"""Ring attention: causal attention with the SEQUENCE sharded over a
mesh axis — the long-context scaling primitive.

Each shard of the `seq` axis holds one contiguous chunk of the
sequence ([B, H, Lc, Dh] of queries, keys and values). K/V chunks
rotate around the ring via `lax.ppermute` (neighbor exchange — rides
ICI, never DCN on a sane mesh layout), and every shard folds each
arriving chunk into the same online-softmax state the flash kernel
uses (ops/attention.py), so no shard ever materializes more than
[B, H, Lc, Lc] scores. After `S` rotations every (query, key) pair has
met exactly once; causality falls out of comparing GLOBAL positions,
so off-diagonal chunks need no special cases.

This is an extension beyond the reference (which has no sequence
parallelism of any kind); it composes with the framework's mesh axes
the same way tensor parallelism does — `clients` outer, `seq` inner:

    mesh = Mesh(devices.reshape(C, S), ("clients", "seq"))
    shard_map(..., in_specs=P("clients", None, None, "seq", None))

Verified equivalent to single-device attention in tests/test_ring.py.
"""
from __future__ import annotations

import math
from functools import partial
from typing import Optional

import jax
import jax.numpy as jnp

from commefficient_tpu.ops.attention import NEG_INF, online_softmax_fold


def ring_attention(q: jax.Array, k: jax.Array, v: jax.Array,
                   axis_name: str,
                   sm_scale: Optional[float] = None) -> jax.Array:
    """Causal attention over a sequence sharded on `axis_name`.

    q, k, v: [B, H, Lc, Dh] — this shard's chunk (global sequence
    length = Lc * axis_size, chunk i holding positions
    [i*Lc, (i+1)*Lc)). Returns this shard's [B, H, Lc, Dh] output.
    Call INSIDE shard_map/psum context where `axis_name` is manual.
    """
    B, H, Lc, Dh = q.shape
    n = jax.lax.axis_size(axis_name)   # static under shard_map
    my = jax.lax.axis_index(axis_name)
    scale = sm_scale if sm_scale is not None else 1.0 / math.sqrt(Dh)

    qs = q.astype(jnp.float32) * scale
    q_pos = my * Lc + jnp.arange(Lc)                     # global positions

    def fold(state, kv_src):
        kt, vt, src = kv_src
        # the same online-softmax fold the flash kernel uses
        # (ops/attention.py) — one copy of the rescaling math
        k_pos = src * Lc + jnp.arange(Lc)
        return online_softmax_fold(state, qs, kt, vt, q_pos, k_pos)

    m = jnp.full((B, H, Lc), NEG_INF, jnp.float32)
    l = jnp.zeros((B, H, Lc), jnp.float32)
    acc = jnp.zeros((B, H, Lc, Dh), jnp.float32)

    # static ring schedule: at step t this shard holds chunk (my - t);
    # rotate kv to the next shard after each fold so communication
    # overlaps the matmul of the following step under XLA's scheduler
    kt, vt = k, v
    ring = [(i, (i + 1) % n) for i in range(n)]
    for t in range(n):
        src = (my - t) % n
        m, l, acc = fold((m, l, acc), (kt, vt, src))
        if t + 1 < n:
            kt = jax.lax.ppermute(kt, axis_name, ring)
            vt = jax.lax.ppermute(vt, axis_name, ring)

    l_safe = jnp.maximum(l, 1e-30)
    return (acc / l_safe[..., None]).astype(q.dtype)

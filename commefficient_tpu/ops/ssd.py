"""The Mamba-2 mixer's own operations (arXiv:2405.21060), in plain
XLA: the causal depthwise convolution, the chunked state-space scan
(SSD) and the gated group RMSNorm.

The scan. Head h of Hh reads state group h // (Hh / G); at position
t, with a_t = dt_t A_h (dt_t > 0, A_h < 0):

    S_t = exp(a_t) S_{t-1} + dt_t x_t B_t^T          S [P, N]
    y_t = S_t C_t

Over chunks of Q positions, cs_t the cumulative sum of a within its
chunk and S_{c-1} the state that enters chunk c:

    1. within the chunk  y_t = sum_{u<=t} (C_t.B_u) exp(cs_t - cs_u) dt_u x_u
    2. its end state     E_c = sum_u exp(cs_Q - cs_u) dt_u x_u B_u^T
    3. across chunks     S_c = exp(cs_Q) S_{c-1} + E_c, scanned over c
    4. from the state    y_t += exp(cs_t) S_{c-1} C_t

`exp(cs_Q)` of a (head, chunk) is the share of the state that
reaches the next chunk (`ssd_scan`'s second result). The decays of
step 1 are masked before `exp` (-inf above the diagonal), so that no
masked entry is an overflow times zero in the backward pass. Products
run at the caller's matmul precision, decays and states in float32.
Inside `shard_map` the scan's fresh carry varies as its inputs do
(`ops.attention.vary_like`).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from commefficient_tpu.ops.attention import vary_like


def causal_conv(x, w, b):
    """Depthwise causal convolution: x [N, L, C], w [K, C], b [C] ->
    y [N, L, C], y_t = b + sum_i w_i x_{t - K + 1 + i} (x before the
    sequence reads as zero)."""
    K, L = w.shape[0], x.shape[1]
    xp = jnp.pad(x, ((0, 0), (K - 1, 0), (0, 0)))
    y = b.astype(x.dtype) + xp[:, :L] * w[0].astype(x.dtype)
    for i in range(1, K):
        y = y + xp[:, i:i + L] * w[i].astype(x.dtype)
    return y


def gated_rmsnorm(y, z, g, group: int, eps: float):
    """RMSNorm over each `group` channels of y * silu(z), times g:
    y, z [..., I], g [I]; the gate is applied before the norm."""
    v = y.astype(jnp.float32) * jax.nn.silu(z.astype(jnp.float32))
    vg = v.reshape(*v.shape[:-1], -1, group)
    var = (vg * vg).mean(-1, keepdims=True)
    return ((vg / jnp.sqrt(var + eps)).reshape(v.shape)
            * g.astype(jnp.float32)).astype(y.dtype)


def ssd_scan(x, dt, A, B, C, chunk: int):
    """The scan above, without the D skip: x [N, L, Hh, P], dt
    [N, L, Hh], A [Hh], B and C [N, L, G, S] -> (y [N, L, Hh, P],
    carry [N, chunks, Hh] float32: exp of the sum of dt A over each
    chunk). Any L: it is padded to a chunk multiple inside, with dt 0
    (a pad position neither decays the state nor adds to it)."""
    L0 = x.shape[1]
    pad = -L0 % chunk
    if pad:
        x, dt, B, C = (
            jnp.pad(v, [(0, 0), (0, pad)] + [(0, 0)] * (v.ndim - 2))
            for v in (x, dt, B, C))
    N, L, Hh, P = x.shape
    G, S = B.shape[2:]
    R, Q, c = Hh // G, chunk, L // chunk
    f32 = jnp.float32
    # the cumulative decay within each chunk, positions last
    a = (dt.astype(f32) * A.astype(f32)).reshape(N, c, Q, G, R)
    cs = jnp.cumsum(jnp.moveaxis(a, 2, -1), axis=-1)       # [N,c,G,R,Q]
    xdt = (x * dt[..., None].astype(x.dtype)).reshape(N, c, Q, G, R, P)
    Bc, Cc = B.reshape(N, c, Q, G, S), C.reshape(N, c, Q, G, S)

    def by_position(v):
        """[N, c, G, R, Q] -> [N, c, Q, G, R, 1]."""
        return jnp.moveaxis(v, -1, 2)[..., None]

    # 1. within the chunk: (C_t . B_u) exp(cs_t - cs_u), u <= t
    t = jnp.arange(Q)
    seg = jnp.where(t[:, None] >= t[None, :],
                    cs[..., :, None] - cs[..., None, :], -jnp.inf)
    cb = jnp.einsum("nctgs,ncugs->ncgtu", Cc, Bc)
    y = jnp.einsum("ncgrtu,ncugrp->nctgrp", cb[:, :, :, None]
                   * jnp.exp(seg), xdt)
    # 2. each chunk's end state
    states = jnp.einsum("ncugs,ncugrp->ncgrps", Bc,
                        xdt * by_position(jnp.exp(cs[..., -1:] - cs)))
    # 3. the state entering each chunk
    carry = jnp.exp(cs[..., -1])                            # [N,c,G,R]

    def step(s, xs):
        share, e = xs
        return share[..., None, None] * s + e, s

    s0 = vary_like(jnp.zeros((N, G, R, P, S), f32), states, carry)
    _, entering = jax.lax.scan(
        step, s0, (jnp.moveaxis(carry, 1, 0),
                   jnp.moveaxis(states.astype(f32), 1, 0)))
    # 4. what each position reads of it
    y = y + jnp.einsum("nctgs,ncgrps->nctgrp", Cc,
                       jnp.moveaxis(entering, 0, 1)) \
        * by_position(jnp.exp(cs))
    y = y.reshape(N, L, Hh, P)[:, :L0]
    return y.astype(x.dtype), carry.reshape(N, c, Hh)


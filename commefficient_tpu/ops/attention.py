"""Causal attention for long sequences, two ops, each a Pallas kernel
on a TPU and the same mathematics in XLA elsewhere.

`flash_attention` (GPT2, models/gpt2.py at L >= 256): q, k, v
[B, H, L, Dh], any L, returns [B, H, L, Dh]. The reference's GPT2 path
materializes the full [B, H, L, L] score matrix inside
pytorch_transformers (and our baseline einsum path does the same —
models/gpt2.py SelfAttention), which is fine at PersonaChat lengths
but quadratic-memory at long context.

  * forward: a hand-written Pallas kernel (`_flash_fwd_kernel`) — grid
    (batch*head, q-block, k-block) with the online-softmax state
    (running max, denominator, accumulator) carried across k-block
    grid steps in VMEM scratch, so per-program VMEM holds one q block
    and one k/v block (O(block * Dh)), never a full [L, Dh] row or an
    [L, L] score tile. Blocks strictly above the causal diagonal skip
    their compute via `pl.when` (their DMAs still stream — the cost of
    the dense-grid schedule, bounded at 2x bandwidth).
  * backward: flash-style recomputation from the saved output and
    per-row logsumexp, tiled as a `lax.scan` over k-blocks so the
    backward also never materializes [L, L].
  * `flash_attention` wraps both in a `jax.custom_vjp`, padding any
    sequence length up to a block multiple internally (causality keeps
    tail padding invisible to real queries; pad rows of the saved
    logsumexp are poisoned to +big so the backward's recomputed
    probabilities vanish there). On non-TPU backends (the CPU test
    mesh) the forward runs the same online-softmax math as a scan
    (`_flash_fwd_xla`); the Pallas kernel itself is covered by
    interpret-mode tests (tests/test_attention.py).

The online-softmax block fold is shared (`online_softmax_fold`)
between the XLA forward and `parallel/ring.py`'s ring attention — one
copy of the numerically delicate rescaling.

`blockwise_attention` (models/smallthinker.py, models/afmoe.py):
grouped query heads, full or sliding-window causal masks, q, k, v and
the result in the projections' layout [B, L, heads, Dh]. On a TPU one
Pallas kernel a direction (`bw_attn_fwd`, `bw_attn_bwd`) whose grid
holds only the (query block, key block) pairs the mask leaves
non-empty (`pair_table`), one program per pair holding a group's
query heads; elsewhere XLA loops over the same pairs (`_bw_fwd`,
`_bw_bwd`). Its section below says how.
"""
from __future__ import annotations

import functools
import math
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

DEFAULT_BLOCK = 128
NEG_INF = -1e30
# pad rows of the saved logsumexp carry this so exp(s - lse) == 0
LSE_PAD = 1e30


def _resolve_scale(sm_scale: Optional[float], dh: int) -> float:
    return sm_scale if sm_scale is not None else 1.0 / math.sqrt(dh)


def _pad_len(L: int, block: int) -> int:
    return -(-L // block) * block


def _pad_seq(x, Lp):
    pad = Lp - x.shape[2]
    if pad == 0:
        return x
    return jnp.pad(x, ((0, 0), (0, 0), (0, pad), (0, 0)))


# ---------------- shared online-softmax fold ----------------------------

def online_softmax_fold(state, qs, kt, vt, q_pos, k_pos):
    """One flash block fold: fold keys `kt`/values `vt` (global
    positions `k_pos`) into the running (m, l, acc) softmax state of
    queries `qs` (already scaled; global positions `q_pos`). Shapes:
    qs [..., Lq, Dh], kt/vt [..., Lk, Dh], state m/l [..., Lq],
    acc [..., Lq, Dh]. Causal: k > q masked."""
    m, l, acc = state
    s = jnp.einsum("...qd,...kd->...qk", qs, kt.astype(jnp.float32),
                   preferred_element_type=jnp.float32)
    s = jnp.where(q_pos[:, None] >= k_pos[None, :], s, NEG_INF)
    m_new = jnp.maximum(m, s.max(axis=-1))
    p = jnp.exp(s - m_new[..., None])
    rescale = jnp.exp(m - m_new)
    l = l * rescale + p.sum(axis=-1)
    acc = acc * rescale[..., None] + jnp.einsum(
        "...qk,...kd->...qd", p, vt.astype(jnp.float32),
        preferred_element_type=jnp.float32)
    return m_new, l, acc


# ---------------- Pallas forward kernel ---------------------------------

def _flash_fwd_kernel(q_ref, k_ref, v_ref, o_ref, lse_ref,
                      m_scr, l_scr, acc_scr, *,
                      sm_scale: float, block_q: int, block_k: int):
    """Grid (B*H, n_q, n_k), k innermost: scratch carries the online
    state across k steps of one q block. Compute is skipped above the
    causal diagonal."""
    qi = pl.program_id(1)
    kj = pl.program_id(2)
    n_k = pl.num_programs(2)

    @pl.when(kj == 0)
    def _init():
        m_scr[:] = jnp.full_like(m_scr, NEG_INF)
        l_scr[:] = jnp.zeros_like(l_scr)
        acc_scr[:] = jnp.zeros_like(acc_scr)

    # blocks strictly above the diagonal contribute nothing
    @pl.when(kj * block_k <= qi * block_q + (block_q - 1))
    def _fold():
        q = q_ref[0].astype(jnp.float32) * sm_scale     # [bq, Dh]
        k = k_ref[0].astype(jnp.float32)                # [bk, Dh]
        v = v_ref[0].astype(jnp.float32)
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)         # [bq, bk]
        q_pos = qi * block_q + jax.lax.broadcasted_iota(
            jnp.int32, (block_q, block_k), 0)
        k_pos = kj * block_k + jax.lax.broadcasted_iota(
            jnp.int32, (block_q, block_k), 1)
        s = jnp.where(q_pos >= k_pos, s, NEG_INF)

        m = m_scr[:, 0]                                  # [bq]
        l = l_scr[:, 0]
        m_new = jnp.maximum(m, s.max(axis=1))
        p = jnp.exp(s - m_new[:, None])
        rescale = jnp.exp(m - m_new)
        l_new = l * rescale + p.sum(axis=1)
        acc_scr[:] = acc_scr[:] * rescale[:, None] + jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        m_scr[:] = jnp.broadcast_to(m_new[:, None], m_scr.shape)
        l_scr[:] = jnp.broadcast_to(l_new[:, None], l_scr.shape)

    @pl.when(kj == n_k - 1)
    def _finalize():
        l_safe = jnp.maximum(l_scr[:, 0], 1e-30)
        o_ref[0] = (acc_scr[:] / l_safe[:, None]).astype(o_ref.dtype)
        lse_ref[0] = (m_scr[:, :1] + jnp.log(l_safe)[:, None]).astype(
            jnp.float32)                                # [bq, 1]


def vary_like(tree, *refs):
    """`tree` with every leaf pcast to vary over the axes any of
    `refs` varies over: what a loop's fresh initial carry needs under
    `shard_map`'s `check_vma`, where the carry's type may not change
    between iterations. Outside `shard_map` nothing changes."""
    vma = frozenset().union(*(jax.typeof(r).vma for r in refs))

    def lift(x):
        missing = tuple(sorted(vma - jax.typeof(x).vma))
        return jax.lax.pcast(x, missing, to="varying") if missing else x

    return jax.tree.map(lift, tree)


def vary_together(*operands):
    """(vma, operands) with every operand pcast to the union `vma` of
    their varying-axes sets. Under `shard_map`'s `check_vma` a
    `pallas_call` does not infer that set for its outputs (a bare
    `ShapeDtypeStruct` is rejected), and its body refuses to mix
    operands whose sets differ: both are settled before the call.
    Outside `shard_map` the union is empty and nothing changes."""
    vma = frozenset().union(*(jax.typeof(x).vma for x in operands))
    return vma, vary_like(operands, *operands)


def _flash_fwd_pallas(q, k, v, sm_scale, block_q, block_k,
                      interpret=False):
    B, H, L, Dh = q.shape
    assert L % block_q == 0 and L % block_k == 0
    # inside shard_map (check_vma) the outputs must say over which
    # manual axes they vary: wherever any operand does
    vma, (qf, kf, vf) = vary_together(
        q.reshape(B * H, L, Dh), k.reshape(B * H, L, Dh),
        v.reshape(B * H, L, Dh))

    kernel = functools.partial(_flash_fwd_kernel, sm_scale=sm_scale,
                               block_q=block_q, block_k=block_k)
    # lse is laid out [B*H, L, 1] with block (1, block_q, 1): Mosaic
    # wants a block's last two dims (8, 128)-divisible or equal to the
    # array's, which a (1, block_q) block of [B*H, L] is not
    o, lse = pl.pallas_call(
        kernel,
        name="flash_fwd",
        grid=(B * H, L // block_q, L // block_k),
        in_specs=[
            pl.BlockSpec((1, block_q, Dh), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, block_k, Dh), lambda b, i, j: (b, j, 0)),
            pl.BlockSpec((1, block_k, Dh), lambda b, i, j: (b, j, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, block_q, Dh), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, block_q, 1), lambda b, i, j: (b, i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((B * H, L, Dh), q.dtype, vma=vma),
            jax.ShapeDtypeStruct((B * H, L, 1), jnp.float32, vma=vma),
        ],
        scratch_shapes=[
            pltpu.VMEM((block_q, 128), jnp.float32),   # running max
            pltpu.VMEM((block_q, 128), jnp.float32),   # denominator
            pltpu.VMEM((block_q, Dh), jnp.float32),    # accumulator
        ],
        interpret=interpret,
    )(qf, kf, vf)
    return o.reshape(B, H, L, Dh), lse.reshape(B, H, L)


# ---------------- XLA forward (same math, scan-tiled) -------------------

def _flash_fwd_xla(q, k, v, sm_scale, block_k) -> Tuple[jax.Array, jax.Array]:
    """Online-softmax forward as a lax.scan over k blocks — identical
    semantics to the kernel, runs on any backend, O(L * block) live."""
    B, H, L, Dh = q.shape
    qs = q.astype(jnp.float32) * sm_scale
    n_blocks = L // block_k
    kb = k.reshape(B, H, n_blocks, block_k, Dh)
    vb = v.reshape(B, H, n_blocks, block_k, Dh)
    q_pos = jnp.arange(L)

    def body(carry, xs):
        kj, vj, j = xs
        k_pos = j * block_k + jnp.arange(block_k)
        return online_softmax_fold(carry, qs, kj, vj, q_pos, k_pos), None

    # inside shard_map (check_vma) a scan's carry keeps one type: the
    # fresh constants vary wherever the operands do before they enter
    m0, l0, acc0 = vary_like(
        (jnp.full((B, H, L), NEG_INF, jnp.float32),
         jnp.zeros((B, H, L), jnp.float32),
         jnp.zeros((B, H, L, Dh), jnp.float32)), q, k, v)
    (m, l, acc), _ = jax.lax.scan(
        body, (m0, l0, acc0),
        (kb.transpose(2, 0, 1, 3, 4), vb.transpose(2, 0, 1, 3, 4),
         jnp.arange(n_blocks)))
    l_safe = jnp.maximum(l, 1e-30)
    o = (acc / l_safe[..., None]).astype(q.dtype)
    return o, m + jnp.log(l_safe)


# ---------------- tiled backward (shared) -------------------------------

def _flash_bwd_xla(q, k, v, o, lse, do, sm_scale, block_k):
    """Flash-style backward from saved (o, lse): recompute p per
    k-block, never materializing [L, L]. Zero-padded `do` and
    LSE_PAD-poisoned `lse` rows make sequence padding contribute
    exactly zero to every gradient."""
    B, H, L, Dh = q.shape
    qs = q.astype(jnp.float32)
    do_f = do.astype(jnp.float32)
    o_f = o.astype(jnp.float32)
    delta = (do_f * o_f).sum(axis=-1)                   # [B, H, L]
    n_blocks = L // block_k
    kb = k.reshape(B, H, n_blocks, block_k, Dh).astype(jnp.float32)
    vb = v.reshape(B, H, n_blocks, block_k, Dh).astype(jnp.float32)
    q_pos = jnp.arange(L)

    def body(dq, xs):
        kj, vj, j = xs
        s = jnp.einsum("bhqd,bhkd->bhqk", qs * sm_scale, kj,
                       preferred_element_type=jnp.float32)
        k_pos = j * block_k + jnp.arange(block_k)
        s = jnp.where(q_pos[:, None] >= k_pos[None, :], s, NEG_INF)
        p = jnp.exp(s - lse[..., None])                 # [B,H,L,bk]
        dv_j = jnp.einsum("bhqk,bhqd->bhkd", p, do_f,
                          preferred_element_type=jnp.float32)
        dp = jnp.einsum("bhqd,bhkd->bhqk", do_f, vj,
                        preferred_element_type=jnp.float32)
        ds = p * (dp - delta[..., None])                # [B,H,L,bk]
        dq = dq + sm_scale * jnp.einsum(
            "bhqk,bhkd->bhqd", ds, kj,
            preferred_element_type=jnp.float32)
        dk_j = sm_scale * jnp.einsum(
            "bhqk,bhqd->bhkd", ds, qs,
            preferred_element_type=jnp.float32)
        return dq, (dk_j, dv_j)

    dq0 = vary_like(jnp.zeros((B, H, L, Dh), jnp.float32),
                    q, k, v, o, lse, do)
    dq, (dk_b, dv_b) = jax.lax.scan(
        body, dq0,
        (kb.transpose(2, 0, 1, 3, 4), vb.transpose(2, 0, 1, 3, 4),
         jnp.arange(n_blocks)))
    dk = dk_b.transpose(1, 2, 0, 3, 4).reshape(B, H, L, Dh)
    dv = dv_b.transpose(1, 2, 0, 3, 4).reshape(B, H, L, Dh)
    return dq.astype(q.dtype), dk.astype(k.dtype), dv.astype(v.dtype)


# ---------------- public op ---------------------------------------------

def _on_tpu() -> bool:
    return jax.devices()[0].platform == "tpu"


@functools.partial(jax.custom_vjp, nondiff_argnums=(3,))
def flash_attention(q, k, v, sm_scale: Optional[float] = None):
    """Causal flash attention, [B, H, L, Dh] -> [B, H, L, Dh]."""
    o, _ = _fa_fwd_impl(q, k, v, sm_scale)
    return o


def _fa_fwd_impl(q, k, v, sm_scale):
    L = q.shape[2]
    scale = _resolve_scale(sm_scale, q.shape[-1])
    block = min(DEFAULT_BLOCK, L)
    Lp = _pad_len(L, block)
    qp, kp, vp = (_pad_seq(x, Lp) for x in (q, k, v))
    # tail padding is invisible to real queries under the causal mask
    # (pad positions are strictly later), so outputs [:L] are exact
    if _on_tpu():
        o, lse = _flash_fwd_pallas(qp, kp, vp, scale, block, block)
    else:
        o, lse = _flash_fwd_xla(qp, kp, vp, scale, block)
    return o[:, :, :L], lse[:, :, :L]


def _fa_fwd(q, k, v, sm_scale):
    o, lse = _fa_fwd_impl(q, k, v, sm_scale)
    return o, (q, k, v, o, lse)


def _fa_bwd(sm_scale, res, do):
    q, k, v, o, lse = res
    L = q.shape[2]
    scale = _resolve_scale(sm_scale, q.shape[-1])
    block = min(DEFAULT_BLOCK, L)
    Lp = _pad_len(L, block)
    qp, kp, vp, op, dop = (_pad_seq(x, Lp) for x in (q, k, v, o, do))
    pad = Lp - L
    lsep = (jnp.pad(lse, ((0, 0), (0, 0), (0, pad)),
                    constant_values=LSE_PAD) if pad else lse)
    dq, dk, dv = _flash_bwd_xla(qp, kp, vp, op, lsep, dop, scale, block)
    return dq[:, :, :L], dk[:, :, :L], dv[:, :, :L]


flash_attention.defvjp(_fa_fwd, _fa_bwd)


def reference_attention(q, k, v, sm_scale: Optional[float] = None):
    """O(L^2)-memory einsum attention (the models/gpt2.py baseline
    path), for equivalence tests."""
    scale = _resolve_scale(sm_scale, q.shape[-1])
    L = q.shape[2]
    s = jnp.einsum("bhqd,bhkd->bhqk", q.astype(jnp.float32) * scale,
                   k.astype(jnp.float32),
                   preferred_element_type=jnp.float32)
    causal = jnp.tril(jnp.ones((L, L), bool))
    s = jnp.where(causal[None, None], s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    return jnp.einsum("bhqk,bhkd->bhqd", p,
                      v.astype(jnp.float32)).astype(q.dtype)


# ---------------- grouped-query, windowed, blockwise ---------------------
#
# The long-sequence path of models that mix full and sliding-window
# causal attention and share each key-value head among several query
# heads (models/smallthinker.py, models/afmoe.py). q, k, v and o come
# and go as [B, L, heads, Dh], the reshape of what `h @ W_q` gives and
# what `W_o` takes.
#
# On a TPU, one Pallas kernel a direction. The grid is (sequence,
# key-value head, visible block pair): a static table of the (query
# block, key block) pairs the mask leaves non-empty, built at trace
# time from (L, window, block) and handed to the kernel by scalar
# prefetch, so a pair the mask rules out whole is neither fetched nor
# computed. One program holds a group's G query heads, so a k and v
# tile is read once for all of them. Scores, probabilities, dp and ds
# live in VMEM; the forward's online-softmax state is VMEM scratch and
# its output block stays resident across a query block's key blocks;
# the backward runs query blocks outer and key blocks inner, dq
# resident across a query block's key blocks and dk, dv of the whole
# (padded) sequence of one key-value head resident across the head's
# pairs (2 x 4 MiB f32 at 8,192 positions, 128 wide).
#
# Elsewhere the same mathematics in plain XLA (`_bw_fwd`, `_bw_bwd`):
# an outer loop over query blocks, an inner loop over the key blocks a
# query block can see, in a [B, Hkv, G, L, Dh] layout of their own.
#
# Both round the same operands: every product takes bfloat16 operands
# into a float32 accumulator, as the chip's default precision does
# with the float32 einsums of the XLA path (q.k^T and p.v; do.v^T,
# p^T.do, ds.k and ds^T.q), scores are scaled after the product and
# p is exp(s - lse); m, l, lse, o and the gradients are float32.

BLOCKWISE_BLOCK = 512


def _visible(q_pos, k_pos, window: Optional[int]):
    """The mask, elementwise over broadcast positions."""
    ok = q_pos >= k_pos
    if window is not None:
        ok = ok & (k_pos > q_pos - window)
    return ok


def _allowed(q_pos, k_pos, window: Optional[int]):
    return _visible(q_pos[:, None], k_pos[None, :], window)


def _span(window: Optional[int], block: int) -> Optional[int]:
    """How many key blocks before its own a query block sees: the pair
    (a, j) holds a visible position iff 0 <= a - j <= span (None: no
    window). Its nearest positions lie (a - j - 1) * block + 1 apart,
    and the window takes distances up to window - 1."""
    if window is None:
        return None
    return -(-(window - 1) // block)


def _first_block(a, window: Optional[int], block: int):
    """The first key block that query block `a` can see."""
    span = _span(window, block)
    if span is None:
        return jnp.zeros_like(a)
    return jnp.maximum(a - span, 0)


def pair_table(n_blocks: int, window: Optional[int], block: int):
    """(query block, key block) int32 arrays of the pairs the mask
    leaves non-empty, query blocks ascending and each one's key blocks
    ascending: the kernels' grid."""
    span = _span(window, block)
    pairs = [(a, j) for a in range(n_blocks)
             for j in range(0 if span is None else max(a - span, 0), a + 1)]
    qi, kj = np.array(pairs, np.int32).T
    return qi, kj


def _blocked(x, block: int):
    """[..., L, Dh] -> [L // block, ..., block, Dh]."""
    *lead, L, Dh = x.shape
    x = x.reshape(*lead, L // block, block, Dh)
    return jnp.moveaxis(x, len(lead), 0)


def _unblocked(xb):
    """Inverse of `_blocked`."""
    n, *lead, block, Dh = xb.shape
    return jnp.moveaxis(xb, 0, len(lead)).reshape(*lead, n * block, Dh)


def _bw_scores(qa, kj, a, j, scale, window, block):
    s = jnp.einsum("bhgqd,bhkd->bhgqk", qa, kj,
                   preferred_element_type=jnp.float32) * scale
    ok = _allowed(a * block + jnp.arange(block),
                  j * block + jnp.arange(block), window)
    return s, ok


def _bw_fwd(q, k, v, window, block):
    """q [B, Hkv, G, L, Dh], k and v [B, Hkv, L, Dh], L a multiple of
    `block` -> (o like q, lse [B, Hkv, G, L] f32)."""
    B, Hkv, G, L, Dh = q.shape
    scale = 1.0 / math.sqrt(Dh)
    kb, vb = _blocked(k, block), _blocked(v, block)

    def q_block(xs):
        a, qa = xs

        def fold(j, state):
            m, l, acc = state
            s, ok = _bw_scores(qa, kb[j], a, j, scale, window, block)
            s = jnp.where(ok, s, NEG_INF)
            m_new = jnp.maximum(m, s.max(axis=-1))
            # a row whose every key in this block is masked keeps
            # m = NEG_INF: its exp(0) must not count
            p = jnp.where(ok, jnp.exp(s - m_new[..., None]), 0.0)
            rescale = jnp.exp(m - m_new)
            l = l * rescale + p.sum(axis=-1)
            acc = acc * rescale[..., None] + jnp.einsum(
                "bhgqk,bhkd->bhgqd", p.astype(v.dtype), vb[j],
                preferred_element_type=jnp.float32)
            return m_new, l, acc

        state = vary_like(
            (jnp.full((B, Hkv, G, block), NEG_INF, jnp.float32),
             jnp.zeros((B, Hkv, G, block), jnp.float32),
             jnp.zeros((B, Hkv, G, block, Dh), jnp.float32)), q, k, v)
        m, l, acc = jax.lax.fori_loop(
            _first_block(a, window, block), a + 1, fold, state)
        return (acc / l[..., None]).astype(q.dtype), m + jnp.log(l)

    n = L // block
    ob, lseb = jax.lax.map(q_block, (jnp.arange(n), _blocked(q, block)))
    return _unblocked(ob), jnp.moveaxis(lseb, 0, 3).reshape(B, Hkv, G, L)


def _bw_bwd(q, k, v, o, lse, do, window, block):
    """One pass over the visible (query block, key block) pairs: dq of
    the query block rides the inner loop, dk and dv of all blocks the
    outer one (33 MB at 8,192 positions), updated in place."""
    B, Hkv, G, L, Dh = q.shape
    scale = 1.0 / math.sqrt(Dh)
    n = L // block
    kb, vb = _blocked(k, block), _blocked(v, block)
    delta = (do.astype(jnp.float32) * o.astype(jnp.float32)).sum(-1)
    lseb = jnp.moveaxis(lse.reshape(B, Hkv, G, n, block), 3, 0)
    deltab = jnp.moveaxis(delta.reshape(B, Hkv, G, n, block), 3, 0)

    def q_block(carry, xs):
        a, qa, doa, lsea, deltaa = xs

        def fold(j, state):
            dqa, dkb, dvb = state
            s, ok = _bw_scores(qa, kb[j], a, j, scale, window, block)
            p = jnp.where(ok, jnp.exp(s - lsea[..., None]), 0.0)
            dv_j = jnp.einsum("bhgqk,bhgqd->bhkd", p.astype(do.dtype),
                              doa, preferred_element_type=jnp.float32)
            dp = jnp.einsum("bhgqd,bhkd->bhgqk", doa, vb[j],
                            preferred_element_type=jnp.float32)
            ds = (p * (dp - deltaa[..., None]) * scale).astype(q.dtype)
            dqa = dqa + jnp.einsum("bhgqk,bhkd->bhgqd", ds, kb[j],
                                   preferred_element_type=jnp.float32)
            dk_j = jnp.einsum("bhgqk,bhgqd->bhkd", ds, qa,
                              preferred_element_type=jnp.float32)
            return (dqa, dkb.at[j].add(dk_j), dvb.at[j].add(dv_j))

        dqa = vary_like(jnp.zeros((B, Hkv, G, block, Dh), jnp.float32),
                        q, k, v, do)
        dqa, dkb, dvb = jax.lax.fori_loop(
            _first_block(a, window, block), a + 1, fold, (dqa, *carry))
        return (dkb, dvb), dqa.astype(q.dtype)

    zeros = vary_like(jnp.zeros((n, B, Hkv, block, Dh), jnp.float32),
                      q, k, v, do)
    (dkb, dvb), dqb = jax.lax.scan(
        q_block, (zeros, zeros),
        (jnp.arange(n), _blocked(q, block), _blocked(do, block),
         lseb, deltab))
    return (_unblocked(dqb), _unblocked(dkb).astype(k.dtype),
            _unblocked(dvb).astype(v.dtype))


def _heads_first(x, Hkv):
    """[B, L, H, Dh] -> [B, Hkv, H // Hkv, L, Dh]."""
    B, L, H, Dh = x.shape
    return x.reshape(B, L, Hkv, H // Hkv, Dh).transpose(0, 2, 3, 1, 4)


def _heads_last(x):
    """Inverse of `_heads_first`."""
    B, Hkv, G, L, Dh = x.shape
    return x.transpose(0, 3, 1, 2, 4).reshape(B, L, Hkv * G, Dh)


def _bw_fwd_xla(q, k, v, window, block):
    """`_bw_fwd` in the projections' layout."""
    Hkv = k.shape[2]
    o, lse = _bw_fwd(_heads_first(q, Hkv), _heads_first(k, Hkv)[:, :, 0],
                     _heads_first(v, Hkv)[:, :, 0], window, block)
    return _heads_last(o), lse


def _bw_bwd_xla(q, k, v, o, do, lse, window, block):
    Hkv = k.shape[2]
    dq, dk, dv = _bw_bwd(
        _heads_first(q, Hkv), _heads_first(k, Hkv)[:, :, 0],
        _heads_first(v, Hkv)[:, :, 0], _heads_first(o, Hkv),
        lse, _heads_first(do, Hkv), window, block)
    return (_heads_last(dq), _heads_last(dk[:, :, None]),
            _heads_last(dv[:, :, None]))


# ---------------- the kernels ----------------------------------------------
#
# The kernels read and write q, k, v, o and the gradients as [B, heads
# * Dh, L], positions in lanes: that is how the chip's compiler lays
# out the projections' products and the gradients the weights'
# products take (position-minor), so the transposes around the calls
# are free (read as [B, L, heads * Dh], the same arrays made it copy
# each one, 268 MB a q of Trinity's). Inside, scores are [key, query],
# each query's softmax state and lse a lane-dense row, and the only
# transposes are of a pair's k and v tiles.

# Scoped VMEM the kernels may take: the backward's resident dk and dv
# blocks (double-buffered, 16 MiB at 8,192 positions and Dh 128) put it
# over the default 16 MiB; a v5e core has 128 MiB.
_BW_VMEM_BYTES = 64 * 2 ** 20
# What the chip's default precision rounds the operands of a float32
# product to. On a CPU, whose products do not round, a test sets it to
# float32 to hold the kernels to the XLA path's results exactly.
_MXU_OPERAND = jnp.bfloat16


def _pair_mask(a, j, block: int, window: Optional[int]):
    """[key, query] of the pair (a, j): which the mask keeps."""
    shape = (block, block)
    k_pos = j * block + jax.lax.broadcasted_iota(jnp.int32, shape, 0)
    q_pos = a * block + jax.lax.broadcasted_iota(jnp.int32, shape, 1)
    return _visible(q_pos, k_pos, window)


def _tile_scores(kt, q, ok, scale):
    """Masked, scaled [key, query] scores of one head: kt [bk, Dh],
    q [Dh, bq]."""
    s = jnp.dot(kt, q.astype(_MXU_OPERAND),
                preferred_element_type=jnp.float32) * scale
    return jnp.where(ok, s, NEG_INF)


def _nt(a, b):
    """a [m, n] times b [k, n] transposed -> [m, k], f32."""
    return jax.lax.dot_general(a, b, (((1,), (1,)), ((), ())),
                               preferred_element_type=jnp.float32)


def _bw_fwd_kernel(qi_ref, kj_ref, q_ref, k_ref, v_ref, o_ref, lse_ref,
                   m_scr, l_scr, *, groups, head_dim, block, window):
    """One visible pair of one (sequence, key-value head): fold key
    block j into query block a's online-softmax state, all G query
    heads. The state is [G, 1, block] scratch; the output block
    accumulates in place until the pair a == j ends it and writes the
    G rows of lse."""
    p = pl.program_id(2)
    a, j = qi_ref[p], kj_ref[p]
    scale = 1.0 / math.sqrt(head_dim)

    @pl.when(j == _first_block(a, window, block))
    def _init():
        m_scr[...] = jnp.full(m_scr.shape, NEG_INF, jnp.float32)
        l_scr[...] = jnp.zeros(l_scr.shape, jnp.float32)
        o_ref[...] = jnp.zeros(o_ref.shape, jnp.float32)

    kt = k_ref[0].T.astype(_MXU_OPERAND)
    v = v_ref[0].astype(_MXU_OPERAND)
    ok = _pair_mask(a, j, block, window)
    for g in range(groups):
        rows = slice(g * head_dim, (g + 1) * head_dim)
        s = _tile_scores(kt, q_ref[0, rows, :], ok, scale)
        m_prev = m_scr[g]
        m_new = jnp.maximum(m_prev, s.max(axis=0, keepdims=True))
        # a query with no visible key yet keeps m = NEG_INF and counts
        # exp(0) here; the first visible key's rescale exp(NEG_INF - m)
        # zeroes that, and every query sees its own position
        pr = jnp.exp(s - m_new)
        alpha = jnp.exp(m_prev - m_new)
        l_scr[g] = alpha * l_scr[g] + pr.sum(axis=0, keepdims=True)
        o_ref[0, rows, :] = o_ref[0, rows, :] * alpha + jnp.dot(
            v, pr.astype(_MXU_OPERAND), preferred_element_type=jnp.float32)
        m_scr[g] = m_new

    @pl.when(j == a)
    def _finish():
        for g in range(groups):
            rows = slice(g * head_dim, (g + 1) * head_dim)
            o_ref[0, rows, :] = o_ref[0, rows, :] / l_scr[g]
            lse_ref[0, 0, g:g + 1, :] = m_scr[g] + jnp.log(l_scr[g])


def _bw_bwd_kernel(qi_ref, kj_ref, q_ref, k_ref, v_ref, do_ref, o_ref,
                   lse_ref, dq_ref, dk_ref, dv_ref, delta_scr, *, groups,
                   head_dim, block, window):
    """One visible pair of one (sequence, key-value head): the pair's
    share of dq (resident across query block a's pairs) and of dk, dv
    (the head's whole sequence, resident across all its pairs). A
    query block's first pair takes its rows delta = colsum(do * o)."""
    p = pl.program_id(2)
    a, j = qi_ref[p], kj_ref[p]
    scale = 1.0 / math.sqrt(head_dim)

    @pl.when(p == 0)
    def _init_kv():
        dk_ref[...] = jnp.zeros(dk_ref.shape, jnp.float32)
        dv_ref[...] = jnp.zeros(dv_ref.shape, jnp.float32)

    @pl.when(j == _first_block(a, window, block))
    def _init_q():
        dq_ref[...] = jnp.zeros(dq_ref.shape, jnp.float32)
        for g in range(groups):
            rows = slice(g * head_dim, (g + 1) * head_dim)
            # float32 whatever the operands' dtype (the bfloat16
            # control's do and o)
            delta_scr[g] = (do_ref[0, rows, :].astype(jnp.float32)
                            * o_ref[0, rows, :].astype(jnp.float32)).sum(
                axis=0, keepdims=True)

    kt = k_ref[0].T.astype(_MXU_OPERAND)
    vt = v_ref[0].T.astype(_MXU_OPERAND)
    k = k_ref[0].astype(_MXU_OPERAND)
    ok = _pair_mask(a, j, block, window)
    dk = jnp.zeros((head_dim, block), jnp.float32)
    dv = jnp.zeros((head_dim, block), jnp.float32)
    for g in range(groups):
        rows = slice(g * head_dim, (g + 1) * head_dim)
        q = q_ref[0, rows, :].astype(_MXU_OPERAND)
        do = do_ref[0, rows, :].astype(_MXU_OPERAND)
        # masked scores give exp(NEG_INF - lse) = 0, pad queries
        # (lse = LSE_PAD) exp(s - LSE_PAD) = 0
        pr = jnp.exp(_tile_scores(kt, q, ok, scale)
                     - lse_ref[0, 0, g:g + 1, :])
        dv += _nt(do, pr.astype(_MXU_OPERAND))
        dp = jnp.dot(vt, do, preferred_element_type=jnp.float32)
        ds = (pr * (dp - delta_scr[g]) * scale).astype(_MXU_OPERAND)
        dq_ref[0, rows, :] += jnp.dot(k, ds,
                                      preferred_element_type=jnp.float32)
        dk += _nt(q, ds)
    cols = pl.ds(pl.multiple_of(j * block, block), block)
    dk_ref[0, :, cols] += dk
    dv_ref[0, :, cols] += dv


def _bw_specs(groups: int, head_dim: int, block: int, Lp: int):
    """BlockSpecs over the grid (sequence, key-value head, pair) of
    [B, heads * Dh, Lp] operands: a query block's G heads, a key
    block's one head, the G rows of lse of a query block ([B, Hkv, G,
    Lp]), the head's whole sequence."""
    return dict(
        q=pl.BlockSpec((1, groups * head_dim, block),
                       lambda b, h, p, qi, kj: (b, h, qi[p])),
        kv=pl.BlockSpec((1, head_dim, block),
                        lambda b, h, p, qi, kj: (b, h, kj[p])),
        row=pl.BlockSpec((1, 1, groups, block),
                         lambda b, h, p, qi, kj: (b, h, 0, qi[p])),
        seq=pl.BlockSpec((1, head_dim, Lp),
                         lambda b, h, p, qi, kj: (b, h, 0)))


def _bw_call(kernel, name, operands, in_specs, out_shapes, out_specs,
             scratch, grid, interpret):
    return pl.pallas_call(
        kernel, name=name, out_shape=out_shapes,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2, grid=grid, in_specs=in_specs,
            out_specs=out_specs, scratch_shapes=scratch),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
            vmem_limit_bytes=_BW_VMEM_BYTES),
        interpret=interpret)(*operands)


def _lanes(x):
    """[B, L, H, Dh] -> [B, H * Dh, L]."""
    B, L, H, Dh = x.shape
    return x.reshape(B, L, H * Dh).transpose(0, 2, 1)


def _unlanes(x, heads: int):
    """[B, H * Dh, L] -> [B, L, H, Dh]."""
    B, C, L = x.shape
    return x.transpose(0, 2, 1).reshape(B, L, heads, C // heads)


def _bw_fwd_pallas(q, k, v, window, block, interpret=False):
    """q [B, Lp, Hq, Dh], k and v [B, Lp, Hkv, Dh], Lp a multiple of
    `block` -> (o [B, Lp, Hq, Dh] f32, lse [B, Hkv, G, Lp] f32)."""
    B, Lp, Hq, Dh = q.shape
    Hkv = k.shape[2]
    G = Hq // Hkv
    qi, kj = pair_table(Lp // block, window, block)
    vma, operands = vary_together(jnp.asarray(qi), jnp.asarray(kj),
                                  _lanes(q), _lanes(k), _lanes(v))
    spec = _bw_specs(G, Dh, block, Lp)
    o, lse = _bw_call(
        functools.partial(_bw_fwd_kernel, groups=G, head_dim=Dh,
                          block=block, window=window),
        "bw_attn_fwd", operands, [spec["q"], spec["kv"], spec["kv"]],
        [jax.ShapeDtypeStruct((B, Hq * Dh, Lp), jnp.float32, vma=vma),
         jax.ShapeDtypeStruct((B, Hkv, G, Lp), jnp.float32, vma=vma)],
        [spec["q"], spec["row"]],
        [pltpu.VMEM((G, 1, block), jnp.float32)] * 2,
        (B, Hkv, len(qi)), interpret)
    return _unlanes(o, Hq), lse


def _bw_bwd_pallas(q, k, v, o, do, lse, window, block, interpret=False):
    """The gradients of `_bw_fwd_pallas`'s o, all f32 in the layout of
    q, k and v."""
    B, Lp, Hq, Dh = q.shape
    Hkv = k.shape[2]
    G = Hq // Hkv
    qi, kj = pair_table(Lp // block, window, block)
    vma, operands = vary_together(
        jnp.asarray(qi), jnp.asarray(kj), _lanes(q), _lanes(k), _lanes(v),
        _lanes(do), _lanes(o), lse)
    spec = _bw_specs(G, Dh, block, Lp)
    kv = jax.ShapeDtypeStruct((B, Hkv * Dh, Lp), jnp.float32, vma=vma)
    dq, dk, dv = _bw_call(
        functools.partial(_bw_bwd_kernel, groups=G, head_dim=Dh,
                          block=block, window=window),
        "bw_attn_bwd", operands,
        [spec["q"], spec["kv"], spec["kv"], spec["q"], spec["q"],
         spec["row"]],
        [jax.ShapeDtypeStruct((B, Hq * Dh, Lp), jnp.float32, vma=vma),
         kv, kv],
        [spec["q"], spec["seq"], spec["seq"]],
        [pltpu.VMEM((G, 1, block), jnp.float32)],
        (B, Hkv, len(qi)), interpret)
    return _unlanes(dq, Hq), _unlanes(dk, Hkv), _unlanes(dv, Hkv)


# ---------------- the public op ---------------------------------------------

@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4))
def blockwise_attention(q, k, v, window: Optional[int] = None,
                        block: int = BLOCKWISE_BLOCK):
    """Causal attention of q [B, L, Hq, Dh] over k, v [B, L, Hkv, Dh],
    Hq a multiple of Hkv (query head i reads key-value head
    i // (Hq // Hkv)); with `window`, position i sees only positions
    j > i - window. Any L: it is padded to a block multiple inside
    (pad keys lie after every real query, pad queries are cut off).
    Returns [B, L, Hq, Dh]: `.reshape(B, L, Hq * Dh)` is what an
    output projection takes."""
    o, _ = _bwa_fwd_impl(q, k, v, window, block)
    return o


def _bwa_pad(x, Lp):
    pad = Lp - x.shape[1]
    if pad == 0:
        return x
    return jnp.pad(x, [(0, 0), (0, pad)] + [(0, 0)] * (x.ndim - 2))


def _bwa_geometry(L: int, block: int, lanes: int = 8):
    """(block, padded length): a short sequence is one block, a block a
    multiple of `lanes` (the kernels' 128: positions are their lanes)."""
    block = _pad_len(min(block, _pad_len(L, lanes)), lanes)
    return block, _pad_len(L, block)


def _bwa_path(L: int, block: int):
    """(forward, backward, block, padded length) of this backend."""
    if _on_tpu():
        return (_bw_fwd_pallas, _bw_bwd_pallas,
                *_bwa_geometry(L, block, 128))
    return _bw_fwd_xla, _bw_bwd_xla, *_bwa_geometry(L, block)


def _bwa_fwd_impl(q, k, v, window, block):
    """(o [B, L, Hq, Dh], lse [B, Hkv, G, Lp] f32), lse's pad rows
    LSE_PAD: their p vanishes in the backward pass."""
    L = q.shape[1]
    fwd, _, block, Lp = _bwa_path(L, block)
    o, lse = fwd(*(_bwa_pad(x, Lp) for x in (q, k, v)), window, block)
    if Lp > L:
        lse = jnp.where(jnp.arange(Lp) < L, lse, LSE_PAD)
    return o[:, :L].astype(q.dtype), lse


def _bwa_fwd(q, k, v, window, block):
    o, lse = _bwa_fwd_impl(q, k, v, window, block)
    return o, (q, k, v, o, lse)


def _bwa_bwd(window, block, res, do):
    q, k, v, o, lse = res
    L = q.shape[1]
    _, bwd, block, Lp = _bwa_path(L, block)
    # pad queries: zero do, and lse's LSE_PAD rows
    dq, dk, dv = bwd(*(_bwa_pad(x, Lp) for x in (q, k, v, o, do)), lse,
                     window, block)
    return (dq[:, :L].astype(q.dtype), dk[:, :L].astype(k.dtype),
            dv[:, :L].astype(v.dtype))


blockwise_attention.defvjp(_bwa_fwd, _bwa_bwd)


def reference_windowed_attention(q, k, v, window: Optional[int] = None):
    """Dense masked grouped-query attention (O(L^2) memory), for
    equivalence tests of `blockwise_attention`: q [B, L, Hq, Dh], k
    and v [B, L, Hkv, Dh] -> [B, L, Hq, Dh]."""
    B, L, Hq, Dh = q.shape
    Hkv = k.shape[2]
    qg = q.reshape(B, L, Hkv, Hq // Hkv, Dh).astype(jnp.float32)
    s = jnp.einsum("bqhgd,bkhd->bhgqk", qg, k.astype(jnp.float32)) \
        / math.sqrt(Dh)
    pos = jnp.arange(L)
    s = jnp.where(_allowed(pos, pos, window), s, -jnp.inf)
    p = jax.nn.softmax(s, axis=-1)
    return jnp.einsum("bhgqk,bkhd->bqhgd", p, v.astype(jnp.float32)) \
        .reshape(B, L, Hq, Dh).astype(q.dtype)

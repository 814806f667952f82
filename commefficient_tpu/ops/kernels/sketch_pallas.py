"""Fused Pallas TPU kernels for the rotation-hash count-sketch.

The XLA path (ops/sketch.py) runs the sketch as r * B separate
rotate-multiply-add stages: below STATIC_UNROLL_LIMIT it unrolls them
into `jnp.roll` calls XLA fuses well; above it, a `lax.scan` whose
traced-offset `dynamic_slice` defeats fusion (~4x slower per element
— the PERF.md scan-fallback cost). These kernels replace both with
ONE `pallas_call` per operation, the hot path of PERF.md's remaining
sketch overhead:

  * `pallas_encode` — grid (r, B): row j's accumulator lives in VMEM
    across all B chunk steps; each step is multiply (eps row * chunk
    * delta scalar) + one hardware dynamic rotate (`pltpu.roll`, the
    TPU lane-rotate the XLA scan path cannot reach with traced
    shifts) + add. One pass over the vector per row, no HBM
    round-trips between chunks, compile time flat in r * B.
  * `pallas_estimate_all` — grid (B, r): the r un-rotated signed rows
    of one chunk collect in VMEM scratch; the last row step computes
    the median in-register (a compare-exchange sorting network over
    the r rows — branch-free min/max, exactly `jnp.median`'s
    sort-then-middle semantics for finite values) and writes the
    chunk's [c] estimates once. The [r, c] rotated intermediate the
    XLA path materializes per chunk never exists.
  * `pallas_threshold_decode` — the fused estimate+threshold
    selection for the large-d decode route (THRESHOLD_DECODE_MIN_D):
    pass 1 re-derives chunk estimates in VMEM and emits only a
    strided ~1M-element sample; the k-th-largest-square threshold
    comes from one cheap `approx_max_k` over that sample; pass 2
    re-derives the estimates again and writes the thresholded
    k-sparse update directly. The full [D] estimate vector is never
    materialized in HBM — estimates are recomputed (cheap: r rotates
    + multiplies per element) instead of stored, trading ~2x VMEM
    compute for d-sized HBM traffic, the same trade flash attention
    makes with attention scores.

Sampling note: the XLA route samples the flat estimate at one global
stride; the fused route samples each chunk at the same stride
truncated to `c // stride` positions (a ragged tail cannot leave a
static kernel). Both are ~1M-point estimators of the same k-th
largest square, so selection counts agree to the documented ~1%
sampling noise (tests/test_kernels.py bounds it); exact-k small
geometries never reach this route.

Interpret mode: every `pallas_call` here takes `interpret=True` off
TPU (trace-time backend consult, same caveat class as
`CSVec.encode_k_sparse`), so the tier-1 CPU suite runs the identical
kernel bodies — the ISSUE-6 testing contract. On a TPU nothing is
interpreted.

Compile status (PR 21, tests/test_tpu_compile.py): the chip's
compiler has never accepted one of these kernels at a real shape. At
the flagship 5 x 500,000 table the (1, c) blocks of [B, c] / [r, c]
are refused (a block's last two dimensions must be divisible by 8 and
128 or equal the array's); with (1, 1, c) blocks on [B, 1, c] the
traced `pltpu.roll` of a [1, 500000] row is an unaligned
`tpu.dynamic_rotate`, and at an aligned c = 524,288 a one-row block
pads to eight sublanes and is double buffered to 29.9 MB of scoped
VMEM against a 16 MB limit. They need a re-tiling (ROADMAP D1/S6).
On the chip `kernel_backend="pallas"` raises the compiler's error.

VMEM sizing: `pallas_fits` refuses a geometry whose rows cannot fit:
3 rows of c f32 for encode and (r + 3) rows for the estimate/decode
kernels (the scratch holds all r rotated rows of a chunk), each row
counted as the compiler counts it — padded to eight sublanes. A
geometry that does not fit RAISES when `kernel_backend="pallas"` was
asked for: a user who asks for a backend gets it or is told.
"""
from __future__ import annotations

import functools
from typing import Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from commefficient_tpu.ops.kernels.vma import vary_together

# The compiler's scoped-VMEM limit for one kernel (bytes), and the
# sublanes a one-row f32 block is padded to.
PALLAS_VMEM_BUDGET = 16 * 1024 * 1024
_SUBLANES = 8

# Strided-sample size target for the fused threshold decode — same
# ~1M-point quantile estimator as ops/flat._TOPK_SAMPLE.
_SAMPLE_TARGET = 1024 * 1024


def _interpret() -> bool:
    """Trace-time backend consult: compiled Mosaic on TPU, the Pallas
    interpreter (plain jax ops, identical math) everywhere else."""
    return jax.default_backend() != "tpu"


def pallas_vmem_bytes(sk, kind: str) -> int:
    """VMEM the `kind` ('encode' | 'estimate') kernel holds at this
    geometry, rows padded to the sublanes the compiler pads them to."""
    rows = 3 if kind == "encode" else sk.r + 3
    return rows * _SUBLANES * sk.c * 4


def pallas_fits(sk, kind: str) -> bool:
    """Whether `kind` fits the VMEM budget at this geometry."""
    return pallas_vmem_bytes(sk, kind) <= PALLAS_VMEM_BUDGET


# ---------------------------------------------------------------------------
# shared in-kernel helpers


def _median_rows(rows):
    """Median over a static list of equal-shape arrays via a
    compare-exchange (bubble) sorting network: branch-free
    jnp.minimum/maximum only, so it lowers on the VPU and in the
    interpreter alike. Matches jnp.median for finite inputs (middle
    element for odd r, mean of the two middles for even)."""
    rows = list(rows)
    r = len(rows)
    for i in range(r):
        for j in range(r - 1 - i):
            lo = jnp.minimum(rows[j], rows[j + 1])
            hi = jnp.maximum(rows[j], rows[j + 1])
            rows[j], rows[j + 1] = lo, hi
    if r % 2:
        return rows[r // 2]
    return 0.5 * (rows[r // 2 - 1] + rows[r // 2])


def _chunk_estimate_rows(b, j, off_ref, delta_ref, table_ref, eps_ref,
                         rows_scr, *, c: int):
    """One (b, j) step of the estimate-family kernels: un-rotate row j
    of the table for chunk b (out[p] = table[j, (p + off) % c], i.e. a
    left-rotate by off — implemented as a right-rotate by c - off so
    the traced shift stays non-negative), apply the factored signs,
    and park the row in VMEM scratch. `b`/`j` are the grid ids, read
    once at the kernel top (program_id is unavailable inside pl.when
    bodies under the interpreter — same hoisting as ops/attention).
    The % c canonicalizes the off == 0 boundary (c - 0 == c): the
    interpreter's jnp.roll is modular but Mosaic's dynamic_rotate is
    not guaranteed to be at shift == axis size."""
    shift = (c - off_ref[j, b]) % c
    unrot = pltpu.roll(table_ref[...], shift, axis=1)
    rows_scr[j, :] = unrot[0] * eps_ref[0] * delta_ref[j, b]


def _masked_est(b, rows_scr, *, r: int, c: int, d: int):
    """Median over the collected scratch rows with the padding tail
    (global index >= d) zeroed — the final chunk's contract, shared by
    all three estimate-family kernels."""
    est = _median_rows([rows_scr[jj, :] for jj in range(r)])  # [c]
    gidx = b * c + jax.lax.broadcasted_iota(jnp.int32, (1, c), 1)
    return jnp.where(gidx < d, est[None, :], 0.0)             # [1, c]


# ---------------------------------------------------------------------------
# fused encode


def _encode_kernel(off_ref, delta_ref, chunk_ref, eps_ref, out_ref,
                   *, c: int):
    """Grid (r, B), chunks innermost: row j's [c] accumulator stays
    resident in VMEM across every chunk step — the 'one VMEM pass'
    of the ISSUE-6 tentpole. Each step: sign-multiply, one hardware
    dynamic rotate, add."""
    b = pl.program_id(1)

    @pl.when(b == 0)
    def _init():
        out_ref[...] = jnp.zeros_like(out_ref)

    j = pl.program_id(0)
    signed = eps_ref[...] * chunk_ref[...] * delta_ref[j, b]
    out_ref[...] += pltpu.roll(signed, off_ref[j, b], axis=1)


def pallas_encode(sk, vec: jax.Array) -> jax.Array:
    """Sketch a dense [d] vector into the [r, c] table with the fused
    accumulate kernel. Bit-for-bit the same sum ORDER as the XLA
    static path (chunks accumulate in ascending order per row), so
    equivalence tests can demand tight tolerances."""
    B = sk.n_chunks
    vma, operands = vary_together(
        jnp.asarray(sk._offsets), jnp.asarray(sk._delta),
        sk._padded_chunks(vec.astype(jnp.float32)),           # [B, c]
        jnp.asarray(sk._eps))
    kernel = functools.partial(_encode_kernel, c=sk.c)
    return pl.pallas_call(
        kernel,
        name="sketch_encode",
        grid=(sk.r, B),
        in_specs=[
            pl.BlockSpec(memory_space=pltpu.SMEM),            # offsets
            pl.BlockSpec(memory_space=pltpu.SMEM),            # delta
            pl.BlockSpec((1, sk.c), lambda j, b: (b, 0)),     # chunk
            pl.BlockSpec((1, sk.c), lambda j, b: (j, 0)),     # eps row
        ],
        out_specs=pl.BlockSpec((1, sk.c), lambda j, b: (j, 0)),
        out_shape=jax.ShapeDtypeStruct((sk.r, sk.c), jnp.float32,
                                       vma=vma),
        interpret=_interpret(),
    )(*operands)


# ---------------------------------------------------------------------------
# fused estimate-all


def _estimate_kernel(off_ref, delta_ref, table_ref, eps_ref, out_ref,
                     rows_scr, *, r: int, c: int, d: int):
    """Grid (B, r), rows innermost: collect the chunk's r un-rotated
    signed rows in scratch, emit the median once at the last row."""
    b = pl.program_id(0)
    j = pl.program_id(1)
    _chunk_estimate_rows(b, j, off_ref, delta_ref, table_ref, eps_ref,
                         rows_scr, c=c)

    @pl.when(j == r - 1)
    def _emit():
        out_ref[...] = _masked_est(b, rows_scr, r=r, c=c, d=d)


def pallas_estimate_all(sk, table: jax.Array) -> jax.Array:
    """[B, c] median-of-rows estimates (the padding tail zeroed — a
    superset of the XLA estimate_all contract, whose callers zero the
    tail themselves; zeros-for-zeros either way)."""
    B = sk.n_chunks
    vma, operands = vary_together(
        jnp.asarray(sk._offsets), jnp.asarray(sk._delta),
        table.astype(jnp.float32), jnp.asarray(sk._eps))
    kernel = functools.partial(_estimate_kernel, r=sk.r, c=sk.c, d=sk.d)
    return pl.pallas_call(
        kernel,
        name="sketch_estimate_all",
        grid=(B, sk.r),
        in_specs=[
            pl.BlockSpec(memory_space=pltpu.SMEM),            # offsets
            pl.BlockSpec(memory_space=pltpu.SMEM),            # delta
            pl.BlockSpec((1, sk.c), lambda b, j: (j, 0)),     # table row
            pl.BlockSpec((1, sk.c), lambda b, j: (j, 0)),     # eps row
        ],
        out_specs=pl.BlockSpec((1, sk.c), lambda b, j: (b, 0)),
        out_shape=jax.ShapeDtypeStruct((B, sk.c), jnp.float32, vma=vma),
        scratch_shapes=[pltpu.VMEM((sk.r, sk.c), jnp.float32)],
        interpret=_interpret(),
    )(*operands)


# ---------------------------------------------------------------------------
# fused estimate + threshold selection (large-d decode)


def _sample_kernel(off_ref, delta_ref, table_ref, eps_ref, samp_ref,
                   rows_scr, *, r: int, c: int, d: int, stride: int,
                   ns: int):
    """Pass 1: per chunk, emit estimates at positions 0, stride, ...,
    (ns-1)*stride — the strided quantile sample — without writing the
    estimates themselves anywhere."""
    b = pl.program_id(0)
    j = pl.program_id(1)
    _chunk_estimate_rows(b, j, off_ref, delta_ref, table_ref, eps_ref,
                         rows_scr, c=c)

    @pl.when(j == r - 1)
    def _emit():
        est = _masked_est(b, rows_scr, r=r, c=c, d=d)         # [1, c]
        strided = est[:, : ns * stride].reshape((ns, stride))[:, :1]
        samp_ref[...] = strided.reshape((1, ns))


def _mask_kernel(off_ref, delta_ref, thr_ref, table_ref, eps_ref,
                 out_ref, rows_scr, *, r: int, c: int, d: int):
    """Pass 2: re-derive the chunk estimates and write the thresholded
    selection (>= keeps threshold ties, matching
    ops/flat.sampled_threshold_mask and its documented tie caveat)."""
    b = pl.program_id(0)
    j = pl.program_id(1)
    _chunk_estimate_rows(b, j, off_ref, delta_ref, table_ref, eps_ref,
                         rows_scr, c=c)

    @pl.when(j == r - 1)
    def _emit():
        est = _masked_est(b, rows_scr, r=r, c=c, d=d)
        out_ref[...] = jnp.where(est * est >= thr_ref[0], est, 0.0)


def threshold_sample_geometry(sk) -> Tuple[int, int]:
    """(stride, per-chunk sample count) for the fused decode's
    quantile sample — the per-chunk restriction of the XLA route's
    global stride (module docstring 'Sampling note'). The stride is
    clamped to c so ns * stride <= c always holds (a chunk narrower
    than the global stride still contributes its position-0 element;
    without the clamp the sample kernel's reshape would receive c !=
    ns * stride elements and fail at trace time)."""
    padded = sk.n_chunks * sk.c
    stride = min(max(1, padded // _SAMPLE_TARGET), sk.c)
    return stride, sk.c // stride


def pallas_threshold_decode(sk, table: jax.Array, k: int) -> jax.Array:
    """Dense [d] k-sparse update: estimates >= the sampled k-th
    largest magnitude, computed without materializing the full [D]
    estimate (two fused estimate passes; module docstring)."""
    B = sk.n_chunks
    stride, ns = threshold_sample_geometry(sk)
    common = dict(r=sk.r, c=sk.c, d=sk.d)
    vma, (offsets, delta, table, eps) = vary_together(
        jnp.asarray(sk._offsets), jnp.asarray(sk._delta),
        table.astype(jnp.float32), jnp.asarray(sk._eps))

    sample = pl.pallas_call(
        functools.partial(_sample_kernel, stride=stride, ns=ns,
                          **common),
        name="sketch_threshold_sample",
        grid=(B, sk.r),
        in_specs=[
            pl.BlockSpec(memory_space=pltpu.SMEM),
            pl.BlockSpec(memory_space=pltpu.SMEM),
            pl.BlockSpec((1, sk.c), lambda b, j: (j, 0)),
            pl.BlockSpec((1, sk.c), lambda b, j: (j, 0)),
        ],
        out_specs=pl.BlockSpec((1, ns), lambda b, j: (b, 0)),
        out_shape=jax.ShapeDtypeStruct((B, ns), jnp.float32, vma=vma),
        scratch_shapes=[pltpu.VMEM((sk.r, sk.c), jnp.float32)],
        interpret=_interpret(),
    )(offsets, delta, table, eps)

    # threshold from the sample: THE shared quantile math
    # (ops/flat.threshold_from_sq_sample — one copy for both routes),
    # with the sample drawn per chunk
    from commefficient_tpu.ops.flat import threshold_from_sq_sample
    sq = (sample * sample).reshape(-1)
    thr = threshold_from_sq_sample(sq, k, B * sk.c)

    masked = pl.pallas_call(
        functools.partial(_mask_kernel, **common),
        name="sketch_threshold_mask",
        grid=(B, sk.r),
        in_specs=[
            pl.BlockSpec(memory_space=pltpu.SMEM),
            pl.BlockSpec(memory_space=pltpu.SMEM),
            pl.BlockSpec(memory_space=pltpu.SMEM),            # thr
            pl.BlockSpec((1, sk.c), lambda b, j: (j, 0)),
            pl.BlockSpec((1, sk.c), lambda b, j: (j, 0)),
        ],
        out_specs=pl.BlockSpec((1, sk.c), lambda b, j: (b, 0)),
        out_shape=jax.ShapeDtypeStruct((B, sk.c), jnp.float32, vma=vma),
        scratch_shapes=[pltpu.VMEM((sk.r, sk.c), jnp.float32)],
        interpret=_interpret(),
    )(offsets, delta, thr.reshape(1), table, eps)
    return masked.reshape(-1)[: sk.d]

"""Flat-parameter-vector substrate.

The whole framework, like the reference, operates on a single flattened
fp32 vector of all trainable parameters (reference:
CommEfficient/utils.py:232-313 — `_topk`, `get_param_vec`,
`set_param_vec`, `get_grad`, `clip_grad`). Here flattening is
`jax.flatten_util.ravel_pytree` (one fused reshape/concat under jit, no
per-parameter Python loop), and every op is a pure function usable
inside `jit`/`shard_map`.
"""
from __future__ import annotations

from typing import Callable, Tuple

import jax
import jax.numpy as jnp
from jax.flatten_util import ravel_pytree


def flatten_params(params) -> Tuple[jax.Array, Callable]:
    """Flatten a parameter pytree to one fp32 vector.

    Returns (vec, unravel) where unravel(vec) rebuilds the pytree
    (replaces reference get_param_vec/set_param_vec,
    utils.py:281-297).
    """
    vec, unravel = ravel_pytree(params)
    return vec.astype(jnp.float32), unravel


# Above this d, masked_topk selects by SAMPLED THRESHOLD instead of
# index top-k. Same motivation and regime as the sketch decoder's
# THRESHOLD_DECODE_MIN_D (ops/sketch.py): ApproxTopK's partial reduce
# shrinks the input only 4x at the reference's k/d ~ 1/130 ratio
# (approx_top_k_reduction_output_size: d=5.25M, k=40402 -> a
# 1.31M-element exact sort PER CLIENT at BASELINE config #3), where
# the threshold route is one ~1M-sample approx_max_k plus an
# elementwise mask. Selected count is k within ~1% sampling noise
# rather than exactly k; every caller (true_topk/local_topk error
# accumulation, topk_down staleness tracking) runs under error
# feedback, which retransmits anything a high threshold briefly
# excludes. Small geometries — all closed-form tests — keep exact-k
# semantics. d-based, not backend-based, so a geometry has one
# semantics everywhere.
TOPK_THRESHOLD_MIN_D = 4 * 1024 * 1024

_TOPK_SAMPLE = 1024 * 1024


def masked_topk(vec: jax.Array, k: int) -> jax.Array:
    """Dense vector equal to `vec` at its ~k largest-magnitude entries
    and zero elsewhere (reference `_topk`, utils.py:232-252).

    Works on 1-D [d] and batched 2-D [b, d] input (top-k taken per
    row), like the reference.

    Below TOPK_THRESHOLD_MIN_D, selection is `jax.lax.approx_max_k`:
    on TPU the native partial-reduce kernel (exact `lax.top_k` sorts
    the full vector — ~9 ms at d=6.6M, k=50k on a v5e) recovering
    ~95% of the true top-k; missed coordinates stay in the error
    accumulator and transmit on later rounds. On CPU — where the
    golden tests run — approx_max_k is exact. Above the gate, the
    sampled-threshold route (constant's docstring) replaces the index
    select entirely.
    """
    d = vec.shape[-1]
    one = (_topk_threshold_1d if d > TOPK_THRESHOLD_MIN_D
           else _topk_exact_1d)

    def _topk_1d(v):
        return one(v, k)

    if vec.ndim == 1:
        return _topk_1d(vec)
    elif vec.ndim == 2:
        return jax.vmap(_topk_1d)(vec)
    raise ValueError(f"masked_topk supports 1-D/2-D input, got {vec.ndim}-D")


def _topk_exact_1d(v: jax.Array, k: int) -> jax.Array:
    _, idx = jax.lax.approx_max_k(v * v, k)
    mask = jnp.zeros_like(v).at[idx].set(1.0)
    return v * mask


def _topk_threshold_1d(v: jax.Array, k: int) -> jax.Array:
    return sampled_threshold_mask(v, k)


def threshold_from_sq_sample(sq_sample: jax.Array, k: int,
                             total: int) -> jax.Array:
    """THE k-th-largest-square threshold estimate from a sample of
    squared magnitudes — one copy of the quantile math (ks clamp,
    approx_max_k, tiny floor), kept apart from the sampling in
    sampled_threshold_mask below.

    sq_sample: [n] squared values sampled ~uniformly from a vector of
    `total` squared values; returns the scalar threshold: a vector
    with fewer than k nonzeros floors the threshold at f32-tiny so
    callers' `sq >= thr` select exactly the nonzeros, not everything."""
    n = sq_sample.shape[0]
    ks = max(1, min(int(round(k * n / total)), n))
    vals, _ = jax.lax.approx_max_k(sq_sample, ks)
    return jnp.maximum(vals[-1], jnp.finfo(jnp.float32).tiny)


def sampled_threshold_mask(v: jax.Array, k: int) -> jax.Array:
    """THE sampled-threshold selection (one algorithm, shared by
    masked_topk's large-d route and CSVec.decode_topk_dense): estimate
    the k-th largest v^2 from a ~_TOPK_SAMPLE strided sample, then
    keep every coordinate at or above it. Coordinates the caller wants
    excluded (e.g. a padding tail) must already be zero — zeros sort
    last, so they dilute the sample and the selection identically and
    the quantile math stays exact.

    TIE CAVEAT: the `sq >= thr` select keeps EVERY coordinate whose
    squared magnitude ties the estimated threshold, so the realized
    count can exceed k by the tie multiplicity on top of the ~1%
    sampling noise. Real gradients have measure-zero ties, but
    structured inputs (quantized values, repeated embeddings, adv
    synthetic tests) can tie arbitrarily many coordinates — a
    degenerate vector with one repeated magnitude selects ALL its
    nonzeros. Error feedback keeps the math correct either way (the
    selection is a superset of intent), but the WIRE cost grows with
    the realized support, which is why local_topk accounting records
    the realized nonzero count next to the analytic k
    (federated/accounting.CommAccountant.realized_nonzeros) — a tie
    blowout shows up there instead of silently under-billing.

    The sample is a `jax.lax.slice`, squared after it is taken: on
    jax 0.9.0 `sq[::stride]` traces to iota -> gather, which under the
    round's per-client vmap the chip's compiler runs as one index
    lookup per sampled coordinate (17.6 ms of the local top-k cell's
    round at [16, 6568640]; PERF.md section 6, PR 34). `lax.slice`
    compiles to one strided slice instruction over the same
    coordinates, so the values are the same to the bit."""
    d = v.shape[0]
    k = min(k, d)
    stride = max(1, d // _TOPK_SAMPLE)
    sample = jax.lax.slice(v, (0,), (d,), (stride,))
    thr = threshold_from_sq_sample(sample * sample, k, d)
    return jnp.where(v * v >= thr, v, 0.0)


def clip_to_l2(vec: jax.Array, clip: float) -> jax.Array:
    """Scale `vec` down to L2 norm `clip` if it exceeds it; identity
    otherwise (reference `clip_grad`, utils.py:305-313). Unlike the
    reference this is branch-free (jnp.where) so it traces under jit.
    """
    norm = jnp.linalg.norm(vec)
    scale = jnp.where(norm > clip, clip / jnp.maximum(norm, 1e-30), 1.0)
    return vec * scale


def clip_table_to_l2(table: jax.Array, l2_est: jax.Array, clip: float) -> jax.Array:
    """Clip a sketch table by an externally-supplied L2 estimate
    (reference clips sketches via CSVec.l2estimate(),
    utils.py:307-309)."""
    scale = jnp.where(l2_est > clip, clip / jnp.maximum(l2_est, 1e-30), 1.0)
    return table * scale


def global_norm_clip(vec: jax.Array, max_norm: float) -> jax.Array:
    """torch.nn.utils.clip_grad_norm_ semantics: multiply by
    max_norm/(norm+1e-6) when norm exceeds max_norm (reference use:
    fed_worker.py:290-292)."""
    norm = jnp.linalg.norm(vec)
    scale = jnp.where(norm > max_norm, max_norm / (norm + 1e-6), 1.0)
    return vec * scale


def dp_noise(key: jax.Array, shape, noise_multiplier: float,
             scale: float = 1.0) -> jax.Array:
    """Gaussian DP noise N(0, noise_multiplier) * scale (reference:
    fed_worker.py:304-309 worker-side — scale=sqrt(num_workers);
    fed_aggregator.py:505-508 server-side — scale=1)."""
    return jax.random.normal(key, shape) * (noise_multiplier * scale)

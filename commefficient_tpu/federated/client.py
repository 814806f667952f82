"""Client-side computation: one simulated federated client's round.

Functional re-design of the reference worker runtime's per-client math
(reference: CommEfficient/fed_worker.py:140-335 — `process_batch`,
`local_step`, `forward_grad` — and the fedavg local-SGD branch at
:61-113). The reference runs this as a Python loop inside one process
per GPU; here it is a pure function over static-shape arrays, designed
to be `vmap`ed over the clients owned by a mesh shard and `shard_map`ed
over the `clients` axis.

Static-shape discipline (SURVEY.md §7.3 hard part #2): client batches
are padded to [B] with a validity mask; microbatching is a `lax.scan`
over a [n_mb, mb, ...] reshape; all means are masked means; the
transmitted quantity is scaled by the *valid* example count, matching
the reference's g *= batch_size (fed_worker.py:190) so the server's
divide-by-total-batch-size (fed_aggregator.py:332) is exact.
"""
from __future__ import annotations

from functools import partial
from typing import Callable, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from commefficient_tpu import compress
from commefficient_tpu.config import Config
from commefficient_tpu.ops.flat import clip_to_l2, dp_noise, global_norm_clip
from commefficient_tpu.scopes import scope

# loss_fn contract (the workload callback, analogous to the reference's
# compute_loss(model, batch, args) -> (loss, *metrics) at
# cv_train.py:67-83 / gpt2_train.py:77-99, extended with a validity
# mask): loss_fn(params_pytree, batch_tuple, mask) ->
#   (masked-mean loss, tuple of masked-mean metrics)
LossFn = Callable[[object, Tuple[jax.Array, ...], jax.Array],
                  Tuple[jax.Array, Tuple[jax.Array, ...]]]


class ClientResult(NamedTuple):
    transmit: jax.Array          # [D] vector or [r, c] sketch table
    error: jax.Array             # updated local error state (or dummy)
    velocity: jax.Array          # updated local velocity state (or dummy)
    loss: jax.Array              # masked-mean loss over this client's batch
    metrics: Tuple[jax.Array, ...]
    num_examples: jax.Array      # valid example count (f32)


def _cast_tree(tree, dtype):
    """Cast every inexact leaf to `dtype` (ints/bools untouched)."""
    return jax.tree.map(
        lambda l: l.astype(dtype)
        if jnp.issubdtype(l.dtype, jnp.inexact) else l, tree)


def make_flat_grad_fn(loss_fn: LossFn, unravel: Callable,
                      compute_dtype=None):
    """Lift a pytree loss into flat-vector space: the substrate every
    compression op works in (replaces get_grad/get_grad_vec,
    reference utils.py:254-273).

    compute_dtype=jnp.bfloat16 runs the client forward/backward on the
    MXU's fast path: master weights stay f32 (the [D] vector, all
    server/compression state), the model body computes in bf16, and
    the grad returns to f32 at the cast boundary. The bf16 rounding
    noise lands inside the same error-feedback loop that already
    absorbs compression error. Opt-in via --bf16 (a capability the
    reference's fp32-only CUDA path doesn't have)."""
    def flat_grad(weights_vec, batch, mask):
        def scalar_loss(vec):
            params = unravel(vec)
            b = batch
            if compute_dtype is not None:
                params = _cast_tree(params, compute_dtype)
                b = _cast_tree(b, compute_dtype)
            loss, metrics = loss_fn(params, b, mask)
            return loss.astype(jnp.float32), _cast_tree(
                metrics, jnp.float32)
        (loss, metrics), grad = jax.value_and_grad(
            scalar_loss, has_aux=True)(weights_vec)
        return loss, metrics, grad
    return flat_grad


def make_flat_loss_fn(loss_fn: LossFn, unravel: Callable,
                      compute_dtype=None):
    """Loss-only counterpart of make_flat_grad_fn for the eval path:
    no value_and_grad, so eval jaxprs carry no backward ops at all —
    eval cost and compile time are forward-only by construction, not by
    hoping XLA DCEs an unused gradient (this matters at GPT2 size)."""
    def flat_loss(weights_vec, batch, mask):
        params = unravel(weights_vec)
        if compute_dtype is not None:
            params = _cast_tree(params, compute_dtype)
            batch = _cast_tree(batch, compute_dtype)
        loss, metrics = loss_fn(params, batch, mask)
        return loss.astype(jnp.float32), _cast_tree(metrics, jnp.float32)
    flat_loss.cohort = is_cohort_loss(loss_fn)
    return flat_loss


def is_cohort_loss(loss_fn) -> bool:
    """A loss that takes a whole shard of clients at once: batch
    leaves [W, B, ...] and mask [W, B] in, (losses [W], metrics with a
    leading [W]) out, each client's loss the mean over its own valid
    examples. A model whose layers batch over every position they are
    given and cannot be written per client and then vmapped (a sorted,
    grouped expert layer: models/smallthinker.py) marks its loss
    `cohort = True`; the engine then calls it once where it would
    vmap a per-client loss. Only the fused backward and the eval path
    take such a loss."""
    return bool(getattr(loss_fn, "cohort", False))


def _microbatch_shape(batch_size: int, microbatch_size: int) -> Tuple[int, int]:
    mb = batch_size if microbatch_size <= 0 else min(microbatch_size, batch_size)
    n_mb = -(-batch_size // mb)
    return n_mb, mb


def _reshape_microbatches(tree, mask, n_mb: int, mb: int):
    """Pad [B, ...] arrays to n_mb*mb and fold into [n_mb, mb, ...]."""
    B = mask.shape[0]
    pad = n_mb * mb - B

    def fold(x):
        if pad:
            x = jnp.concatenate(
                [x, jnp.zeros((pad,) + x.shape[1:], x.dtype)], axis=0)
        return x.reshape((n_mb, mb) + x.shape[1:])

    mask = jnp.concatenate([mask, jnp.zeros((pad,), mask.dtype)]) if pad else mask
    return jax.tree.map(fold, tree), mask.reshape(n_mb, mb)


def forward_grad(flat_grad_fn, weights: jax.Array, batch, mask: jax.Array,
                 cfg: Config, key: Optional[jax.Array] = None,
                 compute_grad: bool = True,
                 grad_mask: Optional[jax.Array] = None):
    """Microbatched forward(/backward) over one client's padded batch
    (reference forward_grad, fed_worker.py:249-335).

    Returns (g, loss, metrics, count): g is the per-mode compressed
    mean-gradient ([D] vector, or [r, c] table for sketch); loss and
    metrics are masked means over the batch; count is the number of
    valid examples. When compute_grad=False (eval path,
    fed_worker.py:300-301) g is None and `flat_grad_fn` must be a
    loss-only callable returning (loss, metrics) — see
    make_flat_loss_fn — so the traced program has no backward pass.
    """
    with scope("fwdbwd"):
        grad, loss, metrics, total = _mean_grad(
            flat_grad_fn, weights, batch, mask, cfg, key, compute_grad,
            grad_mask)
    if not compute_grad:
        return None, loss, metrics, total

    # per-mode compression (reference fed_worker.py:311-335), delegated
    # to the mode's Compressor plugin (ISSUE 19): the sketch-like
    # plugins encode the [r, c] table here; dense plugins pass the
    # gradient through untouched (sparsification happens later —
    # server for true_topk, the residual seam for local_topk/powersgd)
    with scope("encode"):
        g = compress.get_compressor(cfg.mode).encode(cfg, grad, key)

    return g, loss, metrics, total


def _mean_grad(flat_grad_fn, weights, batch, mask, cfg: Config, key,
               compute_grad: bool, grad_mask):
    """forward_grad up to the compression seam: the masked-mean
    gradient with frozen-coordinate masking, clipping, weight decay
    and worker-side DP folded in (None without compute_grad)."""
    B = mask.shape[0]
    n_mb, mb = _microbatch_shape(B, cfg.microbatch_size)
    mbatch, mmask = _reshape_microbatches(batch, mask, n_mb, mb)

    def body(carry, xs):
        accum_grad, accum_loss, accum_metrics = carry
        b, m = xs
        count = m.sum()
        if compute_grad:
            loss, metrics, grad = flat_grad_fn(weights, b, m)
            accum_grad = accum_grad + grad * count
        else:
            loss, metrics = flat_grad_fn(weights, b, m)
        accum_loss = accum_loss + loss * count
        accum_metrics = jax.tree.map(
            lambda a, v: a + v * count, accum_metrics, metrics)
        return (accum_grad, accum_loss, accum_metrics), None

    # metric structure probe (abstract eval: shapes only, no FLOPs)
    probe = jax.eval_shape(
        flat_grad_fn, weights,
        jax.tree.map(lambda x: x[0], mbatch), mmask[0])
    metrics_shape = probe[1]
    # scan carries seeded from `mask` (not fresh constants) so that
    # under shard_map they inherit the data's varying-axes type
    zero = jnp.zeros_like(mask, shape=())
    metrics_proto = jax.tree.map(
        lambda s: jnp.zeros(s.shape, s.dtype) + zero.astype(s.dtype),
        metrics_shape)
    init = (jnp.zeros_like(weights) + zero, zero, metrics_proto)
    (grad_sum, loss_sum, metric_sums), _ = jax.lax.scan(
        body, init, (mbatch, mmask))

    total = mask.sum()
    denom = jnp.maximum(total, 1.0)
    loss = loss_sum / denom
    metrics = jax.tree.map(lambda m: m / denom, metric_sums)

    if not compute_grad:
        return None, loss, metrics, total

    # weighted mean over valid examples: gradient scale is invariant to
    # microbatch_size. (Deliberate divergence: the reference sums
    # microbatch-mean grads, making scale depend on the microbatch
    # count, and compensates by scaling the clip threshold by
    # num_iters — fed_worker.py:286-292.)
    grad = grad_sum / denom

    # frozen-coordinate masking FIRST: frozen coordinates contribute
    # nothing — no gradient, no weight decay, no share of any clipping
    # norm, no compression budget. The reference gets all of this for
    # free because requires_grad=False params never enter the flat
    # vector; here they stay in the vector, so every term below must
    # exclude them explicitly.
    if grad_mask is not None:
        grad = grad * grad_mask

    # gradient clipping for non-sketch modes (reference
    # fed_worker.py:290-292; unscaled here per the note above)
    if cfg.max_grad_norm is not None and cfg.mode != "sketch":
        grad = global_norm_clip(grad, cfg.max_grad_norm)

    # weight decay folded into the gradient, divided by num_workers so
    # the summed transmission applies it once (reference utils.py:254-259)
    if cfg.weight_decay != 0:
        wd_term = (cfg.weight_decay / cfg.num_workers) * weights
        if grad_mask is not None:
            wd_term = wd_term * grad_mask
        grad = grad + wd_term

    # differential privacy at the worker (reference fed_worker.py:304-309)
    if cfg.do_dp:
        grad = clip_to_l2(grad, cfg.l2_norm_clip)
        if cfg.dp_mode == "worker":
            grad = grad + dp_noise(key, grad.shape, cfg.noise_multiplier,
                                   scale=float(np.sqrt(cfg.num_workers)))
        if grad_mask is not None:
            grad = grad * grad_mask  # DP noise lands only on live coords

    return grad, loss, metrics, total


def fused_shard_grads(flat_loss_fn, weights, batch, mask,
                      cfg: Config,
                      grad_mask: Optional[jax.Array] = None,
                      survivors: Optional[jax.Array] = None):
    """One backward pass for a whole shard of clients
    (Config.fused_client_backward's gate guarantees this equals the
    sum of per-client local_step transmits):

        sum_c transmit_c = sum_c count_c * mean_grad_c
                         = d/dw [ sum_c count_c * mean_loss_c ]

    plus the weight-decay term, which every client adds as
    (wd/num_workers) * w before the count scaling, so the shard sum
    contributes (wd/num_workers) * w * total_count (reference
    utils.py:254-259 semantics preserved).

    survivors: optional [W_shard] f32 {0,1} dropout mask. Each
    client's term of the fused objective (and its weight-decay
    contribution) is scaled by its survivor bit, so a dropped client
    contributes exactly nothing to the shard gradient — the same
    linearity that lets the fusion exist at all. Returned counts are
    survivor-weighted; losses/metrics stay per-client diagnostics.

    batch/mask are the shard's [W_shard, B, ...] arrays. Returns
    (grad_sum [D], losses [W_shard], metrics, counts [W_shard]) where
    losses/metrics are per-client masked means — the same reporting
    contract as the vmapped path.
    """
    def objective(vec):
        def one(d, m):
            loss, metrics = flat_loss_fn(vec, d, m)
            return loss, metrics, m.sum()
        if is_cohort_loss(flat_loss_fn):
            losses, metrics = flat_loss_fn(vec, batch, mask)
            counts = mask.sum(axis=1)
        else:
            losses, metrics, counts = jax.vmap(one)(batch, mask)
        if survivors is not None:
            counts = counts * survivors
        total = (losses * counts).sum()
        return total, (losses, metrics, counts)

    with scope("fwdbwd"):
        (_, (losses, metrics, counts)), grad_sum = jax.value_and_grad(
            objective, has_aux=True)(weights)

        if grad_mask is not None:
            grad_sum = grad_sum * grad_mask
        if cfg.weight_decay != 0:
            wd_term = (cfg.weight_decay / cfg.num_workers) * weights \
                * counts.sum()
            if grad_mask is not None:
                wd_term = wd_term * grad_mask
            grad_sum = grad_sum + wd_term
    return grad_sum, losses, metrics, counts


def local_step(flat_grad_fn, weights, batch, mask, error, velocity,
               cfg: Config, key=None,
               grad_mask: Optional[jax.Array] = None) -> ClientResult:
    """One client's single local step + compression bookkeeping
    (reference local_step, fed_worker.py:184-230)."""
    g, loss, metrics, count = forward_grad(
        flat_grad_fn, weights, batch, mask, cfg, key, grad_mask=grad_mask)

    with scope("residual"):
        # transmit sums over examples; server divides by the global
        # batch size (reference fed_worker.py:190)
        g = g * count

        if cfg.local_momentum > 0:
            velocity = g + cfg.local_momentum * velocity

        if cfg.error_type == "local":
            error = error + (velocity if cfg.local_momentum > 0 else g)
            to_transmit = error
        else:
            to_transmit = velocity if cfg.local_momentum > 0 else g

        # residual seam (ISSUE 19): the plugin turns the accumulated
        # quantity into the final wire payload plus new error/velocity
        # carries — local_topk's sparsify-and-mask, powersgd's
        # low-rank factorization, dp_sketch's sensitivity clip;
        # identity elsewhere
        to_transmit, error, velocity = compress.get_compressor(
            cfg.mode).residual(cfg, to_transmit, error, velocity, key)

    return ClientResult(to_transmit, error, velocity, loss, metrics, count)


def fedavg_step(flat_grad_fn, weights, batch, mask, cfg: Config,
                lr, key=None,
                grad_mask: Optional[jax.Array] = None,
                work: Optional[jax.Array] = None) -> ClientResult:
    """FedAvg: full local SGD over the client's dataset, transmitting
    the dataset-size-weighted weight delta (reference worker_loop
    fedavg branch, fed_worker.py:61-113).

    `batch` holds the client's entire local dataset padded to a static
    size; it is split into fedavg_batch_size local batches and scanned
    num_fedavg_epochs times with per-step lr decay fedavg_lr_decay**step.

    `lr` may be a scalar or a per-parameter [D] vector (finetune
    freezing / Fixup param-group LRs applied to the LOCAL steps, since
    fedavg's server update runs at lr=1); `grad_mask` zeroes frozen
    coordinates' local gradients so they neither move nor accrue
    weight decay.

    `work`: optional traced scalar work fraction in (0, 1] — a
    straggler's COMPLETED-STEPS budget (Config.straggler_*). The
    client applies only its first ceil(work * steps) local SGD steps
    (the round deadline lands mid-local-training); later steps still
    trace (static shapes) but their updates are gated off. The
    transmitted delta is weighted by examples actually processed —
    dataset size scaled by completed/total steps — the FedNova-style
    normalization that keeps heterogeneous work from biasing the
    average. Loss/metrics are means over completed steps only. None
    traces the original work-free program.
    """
    B = mask.shape[0]
    inner = B if cfg.fedavg_batch_size == -1 else min(cfg.fedavg_batch_size, B)
    n_batches = -(-B // inner)
    lbatch, lmask = _reshape_microbatches(batch, mask, n_batches, inner)

    # one scan over epochs * n_batches steps
    steps = cfg.num_fedavg_epochs * n_batches
    step_batch = jax.tree.map(
        lambda x: jnp.tile(x, (cfg.num_fedavg_epochs,) + (1,) * (x.ndim - 1)),
        lbatch)
    step_mask = jnp.tile(lmask, (cfg.num_fedavg_epochs, 1))
    if work is not None:
        # ceil keeps a surviving straggler on >= 1 step; work=1.0 is
        # exactly `steps` (below-cutoff fractions never reach here —
        # the host degraded them to dropout)
        live_steps = jnp.ceil(work * steps)

    def body(carry, xs):
        w, step = carry
        b, m = xs
        loss, metrics, grad = flat_grad_fn(w, b, m)
        # reference computes sum-grad then divides by batch size
        # (fed_worker.py:96-98); our flat_grad_fn already returns the
        # masked-mean gradient, but weight decay must still be added
        if cfg.weight_decay != 0:
            grad = grad + (cfg.weight_decay / cfg.num_workers) * w
        if grad_mask is not None:
            grad = grad * grad_mask
        decay = cfg.fedavg_lr_decay ** step
        if work is None:
            w = w - grad * lr * decay
            return (w, step + 1.0), (loss, metrics)
        live = (step < live_steps).astype(w.dtype)
        w = w - grad * lr * decay * live
        return (w, step + 1.0), (loss, metrics, live)

    zero = jnp.zeros_like(mask, shape=())
    with scope("fwdbwd"):
        (w_final, _), outs = jax.lax.scan(
            body, (weights + zero, zero), (step_batch, step_mask))

    if work is None:
        losses, metrics_seq = outs
        # metrics averaged over local steps (reference fed_worker.py:102-103)
        loss = losses.mean()
        metrics = jax.tree.map(lambda m: m.mean(), metrics_seq)
        count = mask.sum()
    else:
        losses, metrics_seq, lives = outs
        done = lives.sum()
        denom = jnp.maximum(done, 1.0)
        loss = (losses * lives).sum() / denom
        metrics = jax.tree.map(lambda m: (m * lives).sum() / denom,
                               metrics_seq)
        # examples actually processed: dataset size scaled by the
        # completed-step fraction (FedNova-style delta weighting)
        count = mask.sum() * (done / steps)
    delta = (weights - w_final) * count  # dataset-size weighting (:104-108)
    dummy = jnp.zeros_like(mask, shape=())
    return ClientResult(delta, dummy, dummy, loss, metrics, count)

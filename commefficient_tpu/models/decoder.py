"""Pieces the mixture-of-experts language models share (`models/
smallthinker.py`, `models/afmoe.py`) beside their expert layer
(`models/experts.py`): RMSNorm, rotate-half RoPE, and the next-token
loss over a whole cohort, with the head and the softmax in blocks of
positions."""
from __future__ import annotations

import jax
import jax.numpy as jnp

from commefficient_tpu.models.experts import block_of, merge_loads
from commefficient_tpu.scopes import scope


def rmsnorm(x, g, eps: float):
    x32 = x.astype(jnp.float32)
    var = (x32 * x32).mean(-1, keepdims=True)
    return (x32 / jnp.sqrt(var + eps) * g.astype(jnp.float32)) \
        .astype(x.dtype)


def rope(x, theta: float):
    """Rotate-half RoPE over the whole head: x [N, L, heads, Dh]."""
    L, Dh = x.shape[1], x.shape[-1]
    inv = 1.0 / (theta ** (jnp.arange(0, Dh, 2, dtype=jnp.float32) / Dh))
    ang = jnp.arange(L, dtype=jnp.float32)[:, None] * inv[None, :]
    ang = jnp.concatenate([ang, ang], axis=-1)[None, :, None, :]
    x32 = x.astype(jnp.float32)
    x1, x2 = x32[..., :Dh // 2], x32[..., Dh // 2:]
    rotated = jnp.concatenate([-x2, x1], axis=-1)
    return (x32 * jnp.cos(ang) + rotated * jnp.sin(ang)).astype(x.dtype)


def next_token_nll(cfg, head, h, labels, valid):
    """Sum of the next-token losses of each sequence: h [N, L, H]
    (final, normed), labels [N, L] (the token that follows each
    position), valid [N, L] f32 -> [N]. The head and the softmax run
    over `cfg.loss_block` positions at a time."""
    N, L, H = h.shape
    b = block_of(L, cfg.loss_block)

    def block(hb, yb, vb):
        z = (hb @ head).astype(jnp.float32)
        logp = z - jax.scipy.special.logsumexp(z, axis=-1, keepdims=True)
        nll = -jnp.take_along_axis(logp, yb[:, None], axis=-1)[:, 0]
        return (nll * vb).sum()

    if cfg.remat:
        block = jax.checkpoint(block)
    sums = jax.lax.map(
        lambda xs: block(*xs),
        (h.reshape(N * L // b, b, H), labels.reshape(N * L // b, b),
         valid.reshape(N * L // b, b)))
    return sums.reshape(N, L // b).sum(1)


def make_lm_loss(cfg, pad_id: int, hidden):
    """Next-token loss over every real token of a PersonaChat
    sequence (`data/persona.py`'s batch: input_ids [.., B, C, L]
    first), for a whole cohort at once: batch leaves [W, B, ...],
    mask [W, B] -> (losses [W], (load [W, layers, held + 4],)), where
    `hidden(cfg, params, ids)` gives the final normed hidden states
    and the expert layers' loads; the round's telemetry turns the
    loads into its per-layer counters
    (telemetry/metrics.expert_load_vector). A client's loss is the
    mean over the real next tokens of its valid examples."""

    def compute_loss(params, batch, mask):
        ids = batch[0]
        W, B, C, L = ids.shape
        ids = ids.reshape(W * B * C, L)
        labels = jnp.concatenate(
            [ids[:, 1:], jnp.full((ids.shape[0], 1), pad_id, ids.dtype)],
            axis=1)
        valid = ((labels != pad_id).reshape(W, B, C, L)
                 * mask[:, :, None, None]).astype(jnp.float32) \
            .reshape(W * B * C, L)
        h, load = hidden(cfg, params, ids)
        with scope("head_loss"):
            nll = next_token_nll(cfg, params["head"], h, labels, valid)
        per_client = nll.reshape(W, B * C).sum(1) / jnp.maximum(
            valid.reshape(W, -1).sum(1), 1.0)
        return per_client, (
            merge_loads(load.reshape(W, B * C, *load.shape[1:]), 1),)

    compute_loss.cohort = True
    return compute_loss

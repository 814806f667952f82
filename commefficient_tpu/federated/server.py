"""Server-side aggregation: the five update/decompression algorithms.

Pure-functional re-design of the reference's `get_server_update` +
`_server_helper_*` family (reference: CommEfficient/fed_aggregator.py:
469-613). The reference's helper signature
`(gradient, Vvelocity, Verror, args, lr) -> (update, Vvelocity, Verror)`
was already functional; we keep it, add an explicit PRNG key (server-side
DP noise), and return an explicit `velocity_mask` so that momentum
factor masking of *client* velocities (true_topk) is data flow instead
of a global-variable side channel. (The reference's version of that is
broken: `g_participating_clients` is assigned as a local and never set
globally — SURVEY.md §7.4 D6 — so we fix rather than replicate.)

All helpers run under jit; branch-free masking replaces the reference's
`tensor[nz] = 0` in-place scatter idiom.
"""
from __future__ import annotations

from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp

from commefficient_tpu.config import Config
from commefficient_tpu.ops.flat import dp_noise, masked_topk
from commefficient_tpu.ops.sketch import CSVec
from commefficient_tpu.scopes import scope


class ServerUpdate(NamedTuple):
    """Result of one server aggregation step.

    update:        dense [D] weight update; PS applies w -= update.
    Vvelocity:     new server (virtual) momentum state.
    Verror:        new server (virtual) error-feedback state.
    velocity_mask: [D] multiplicative mask (0 at freshly-transmitted
                   coordinates) to apply to participating clients'
                   local velocities — momentum factor masking for
                   true_topk (reference intent at
                   fed_aggregator.py:525-533). None when inapplicable.
    """
    update: jax.Array
    Vvelocity: jax.Array
    Verror: jax.Array
    velocity_mask: Optional[jax.Array]


def args2sketch(cfg: Config) -> CSVec:
    """Sketch geometry from config (reference fed_aggregator.py:464-467)."""
    return CSVec(d=cfg.grad_size, c=cfg.num_cols, r=cfg.num_rows,
                 num_blocks=cfg.num_blocks, seed=42)


def get_server_update(gradient: jax.Array, Vvelocity: jax.Array,
                      Verror: jax.Array, cfg: Config, lr,
                      key: Optional[jax.Array] = None,
                      alive: Optional[jax.Array] = None) -> ServerUpdate:
    """Dispatch on cfg.mode (reference fed_aggregator.py:469-481).
    `lr` may be a scalar or a per-parameter [D] vector (param-group
    LRs for Fixup nets, reference fed_aggregator.py:411-427).

    `alive`: optional traced boolean — False means NO client survived
    the round (client dropout, round.RoundBatch.survivors). The
    helper still runs (jit has no cheap dynamic skip), but its result
    is gated to a no-op: zero weight update and Vvelocity/Verror
    passed through bit-exactly. Without the gate a zero gradient
    would still decay momentum (rho * V) and fold V into the error
    accumulator — state drift from a round in which no information
    arrived."""
    # dispatch through the mode's Compressor plugin (ISSUE 19); the
    # five classic plugins delegate straight back to the helpers below
    from commefficient_tpu import compress
    upd = compress.get_compressor(cfg.mode).decode(
        cfg, gradient, Vvelocity, Verror, lr, key)
    if alive is None:
        return upd
    with scope("server_state"):
        return ServerUpdate(
            update=jnp.where(alive, upd.update,
                             jnp.zeros_like(upd.update)),
            Vvelocity=jnp.where(alive, upd.Vvelocity, Vvelocity),
            Verror=jnp.where(alive, upd.Verror, Verror),
            # a dead round transmits nothing, so no client velocity
            # coordinate may be factor-masked either
            velocity_mask=(None if upd.velocity_mask is None
                           else jnp.where(
                               alive, upd.velocity_mask,
                               jnp.ones_like(upd.velocity_mask))),
        )


def _fedavg(avg_update, Vvelocity, Verror, cfg: Config, lr, key) -> ServerUpdate:
    # (reference fed_aggregator.py:483-495) — lr is forced to 1 by the
    # optimizer for fedavg; clients already applied the real LR locally.
    rho = cfg.virtual_momentum
    with scope("server_state"):
        Vvelocity = avg_update + rho * Vvelocity
    return ServerUpdate(Vvelocity, Vvelocity, Verror, None)


def _uncompressed(gradient, Vvelocity, Verror, cfg: Config, lr, key) -> ServerUpdate:
    # (reference fed_aggregator.py:497-509)
    rho = cfg.virtual_momentum
    with scope("server_state"):
        Vvelocity = gradient + rho * Vvelocity
        grad = Vvelocity
        if cfg.do_dp and cfg.dp_mode == "server":
            grad = grad + dp_noise(key, grad.shape, cfg.noise_multiplier)
        return ServerUpdate(grad * lr, Vvelocity, Verror, None)


def _true_topk(gradient, Vvelocity, Verror, cfg: Config, lr, key) -> ServerUpdate:
    # (reference fed_aggregator.py:511-542)
    rho = cfg.virtual_momentum
    with scope("server_state"):
        Vvelocity = gradient + rho * Vvelocity
        Verror = Verror + Vvelocity

    with scope("select"):
        update = masked_topk(Verror, k=cfg.k)

    with scope("server_state"):
        not_sent = (update == 0).astype(Verror.dtype)

        # error feedback + momentum factor masking at transmitted
        # coords
        Verror = Verror * not_sent
        Vvelocity = Vvelocity * not_sent

        # clients' local velocities are masked at the same coords; the
        # round engine applies this to participating rows only.
        vel_mask = not_sent if cfg.local_momentum > 0 else None
        return ServerUpdate(update * lr, Vvelocity, Verror, vel_mask)


def _local_topk(local_topk_grad, Vvelocity, Verror, cfg: Config, lr, key) -> ServerUpdate:
    # (reference fed_aggregator.py:544-566): virtual momentum over the
    # *already sparsified* summed gradient; no virtual error possible.
    rho = cfg.virtual_momentum
    with scope("server_state"):
        Vvelocity = local_topk_grad + rho * Vvelocity
        return ServerUpdate(Vvelocity * lr, Vvelocity, Verror, None)


def _sketched(sketched_grad, Vvelocity, Verror, cfg: Config, lr, key) -> ServerUpdate:
    # (reference fed_aggregator.py:568-613). State lives in sketch
    # space: Vvelocity/Verror are [r, c] tables; linearity makes
    # momentum/error accumulation in table space exact.
    rho = cfg.virtual_momentum
    sketch = args2sketch(cfg)

    # error_type is "virtual" or "none" here: Config.validate()
    # rejects sketch+local outright, as the reference's own workers do
    # (fed_worker.py:221-222 asserts it away — the server-side alias at
    # fed_aggregator.py:579-580 is unreachable there too, so there is
    # no local-error branch to carry).
    with scope("server_state"):
        Vvelocity = sketched_grad + rho * Vvelocity
        if cfg.error_type == "virtual":
            Verror = Verror + Vvelocity
            decode_table = Verror
        else:  # "none": decode straight from the momentum table.
            # (the reference would unsketch an all-zero Verror here
            # and silently produce a zero update — drift note D-class,
            # not replicated)
            decode_table = Vvelocity

    # large-d route: sampled-threshold heavy-hitter recovery (one
    # mask, no big sort/gather/scatter — ops/sketch.py docs) and a
    # contiguous dense re-encode; otherwise the sparse top-k
    with scope("select"):
        if sketch._threshold_decode:
            update = sketch.decode_topk_dense(decode_table, k=cfg.k)
        else:
            idx, vals = sketch.decode_topk_sparse(decode_table, k=cfg.k)

    with scope("server_state"):
        if sketch._threshold_decode:
            sketched_update = sketch.encode(update)
        else:
            update = jnp.zeros(cfg.grad_size, jnp.float32).at[idx].set(
                vals, mode="drop")
            # encode_k_sparse picks the faster of the scatter-add /
            # dense-rotation routes per geometry and backend (CSVec
            # owns that heuristic)
            sketched_update = sketch.encode_k_sparse(idx, vals,
                                                     dense=update)

        # virtual error feedback: re-sketch the k-sparse update and
        # zero the error/momentum tables wherever the re-sketch landed
        # (reference fed_aggregator.py:593-611; note the reference
        # deliberately zeroes rather than subtracts — subtracting
        # diverges per its own comment at :596-599).
        not_sent = (sketched_update == 0).astype(Vvelocity.dtype)
        if cfg.error_type == "virtual":
            Verror = Verror * not_sent
        Vvelocity = Vvelocity * not_sent

        return ServerUpdate(update * lr, Vvelocity, Verror, None)

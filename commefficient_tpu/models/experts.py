"""The held-expert layer both mixture-of-experts families call
(`models/smallthinker.py`, `models/afmoe.py`): one chip's share of an
expert-parallel deployment, run without its exchange.

The layer is told which experts it holds (`held_experts` = (first,
count)): it routes over all `num_experts`, computes its own experts'
part of the result, and leaves out what the absent experts would add.
No token is dropped at any imbalance: the picks are sorted by expert,
the held ones are a prefix of the sorted rows, and
`jax.lax.ragged_dot` runs each expert over its own stretch, however
long (on the TPU a grouped-matmul kernel that visits only the row
tiles in use). Where only some experts are held, only that prefix is
carried: each chunk of positions counts its held picks and runs in the
smallest of the static capacities that holds them (twice and four
times a uniform router's share, then every pick: `compact_capacities`),
gathering those rows from the positions and adding the weighted
results back by position. Where every expert is held there is nothing
to choose, and every pick's row is carried (`full_width`).

`cfg`, everywhere here, is the model's frozen configuration. What the
layer reads of it: `num_experts` (the router's width),
`experts_per_token`, `held_experts`, `moe_chunk`, `remat`, and two
methods that are the family's own:

    cfg.route(r, p)     router logits r [T, E] (and the layer's
                        parameters p) -> (idx [T, k] over all E
                        experts, weight [T, k] of each pick)
    cfg.expert_act(x)   the gate's activation: y = (act(x G) * (x U)) D

`p` holds each held expert's `gate`, `up` [held, H, F] and `down`
[held, F, H].
"""
from __future__ import annotations

import functools
import math
from typing import Tuple

import jax
import jax.numpy as jnp

from commefficient_tpu.scopes import scope


def block_of(L: int, at_most: int) -> int:
    """The largest divisor of L that is at most `at_most`."""
    b = min(at_most, L)
    while L % b:
        b -= 1
    return b


@jax.custom_vjp
def _permute(x, perm, inverse):
    """x[perm] for a permutation `perm` of the rows: its transpose is
    a gather by the inverse, not a scatter."""
    return x[perm]


_permute.defvjp(lambda x, perm, inverse: (x[perm], (perm, inverse)),
                lambda res, g: (g[res[1]], None, None))


def sorted_picks(cfg, r, p=None):
    """Router logits r [T, E] -> (weight [T, k], order [T*k]: the
    picks sorted by held expert, absent experts' picks last as one
    group, sizes [held]: picks on each held expert)."""
    first, held = cfg.held_experts
    with scope("moe_route"):
        idx, prob = cfg.route(r, p)
        local = idx - first
        # absent experts' picks form one group after the held ones
        group = jnp.where((local >= 0) & (local < held), local,
                          held).reshape(-1)
        order = jnp.argsort(group, stable=True)
        sizes = (group[:, None] == jnp.arange(held)[None, :]) \
            .sum(0).astype(jnp.int32)
    return prob, order, sizes


def _cut_to(live):
    """Rows past the held groups belong to no expert here; the grouped
    product leaves its result there unwritten (whatever the buffer
    held), forward and transposed alike, so every operand and result
    is cut to the rows in use."""
    return lambda x: jnp.where(live, x, jnp.zeros((), x.dtype))


def _grouped_ffn(cfg, p: dict, rows, sizes, cut):
    g = cut(jax.lax.ragged_dot(rows, p["gate"], sizes))
    u = cut(jax.lax.ragged_dot(rows, p["up"], sizes))
    return cut(jax.lax.ragged_dot(cfg.expert_act(g) * u, p["down"],
                                  sizes))


def full_width(cfg, p: dict, h2, prob, order, sizes):
    """Every pick's row carried through the grouped products, in
    expert order: the chunk where every expert is held. -> y [T, H]."""
    T, H = h2.shape
    k = cfg.experts_per_token
    with scope("moe_route"):
        inverse = jnp.argsort(order)
        rows = _permute(jnp.repeat(h2, k, axis=0), order, inverse)
        cut = _cut_to((jnp.arange(T * k) < sizes.sum())[:, None])
        rows = cut(rows)
    with scope("expert_ffn"):
        out = _grouped_ffn(cfg, p, rows, sizes, cut)
        picks = _permute(out, inverse, order).reshape(T, k, H)
        return (picks * prob[..., None].astype(picks.dtype)).sum(1)


# The two ways between positions and the C carried rows. `tok` [C] is
# the position of each carried row; `slot` [T, k] is the carried row
# of each pick, C for a pick that is not carried. Each is the other's
# transpose, so neither direction falls to a scatter.

@jax.custom_vjp
def _take(x, tok, slot):
    """x [T, H] -> x[tok] [C, H]."""
    return x[tok]


@jax.custom_vjp
def _spread(x, tok, slot):
    """x [C, H] -> [T, H]: each position's carried rows, added up."""
    table = jnp.concatenate([x, jnp.zeros((1, x.shape[1]), x.dtype)])
    y = table[slot[:, 0]]
    for j in range(1, slot.shape[1]):
        y = y + table[slot[:, j]]
    return y


_take.defvjp(lambda x, tok, slot: (x[tok], (tok, slot)),
             lambda res, g: (_spread(g, *res), None, None))
_spread.defvjp(lambda x, tok, slot: (_spread(x, tok, slot), (tok, slot)),
               lambda res, g: (_take(g, *res), None, None))


def compacted(cfg, C: int, p: dict, h2, prob, order, sizes):
    """`full_width` for a chunk whose held picks number at most C:
    only the first C sorted rows exist. -> y [T, H]."""
    T, k = h2.shape[0], cfg.experts_per_token
    with scope("moe_route"):
        carried = order[:C]
        tok = carried // k
        slot = jnp.minimum(jnp.argsort(order), C).reshape(T, k)
        cut = _cut_to((jnp.arange(C) < sizes.sum())[:, None])
        rows = cut(_take(h2, tok, slot))
    with scope("expert_ffn"):
        out = _grouped_ffn(cfg, p, rows, sizes, cut)
        w = prob.reshape(-1)[carried].astype(out.dtype)
        return _spread(out * w[:, None], tok, slot)


ROW_TILE = 8      # rows of a float32 (8, 128) tile


def compact_capacities(cfg, T: int) -> Tuple[int, ...]:
    """The static row counts a chunk of T positions may be compacted
    to: twice and four times the held picks of a uniform router, in
    whole row tiles, where that is under the T * k picks in all. None
    where every expert is held."""
    picks = T * cfg.experts_per_token
    uniform = picks * cfg.held_experts[1] / cfg.num_experts
    caps = {math.ceil(f * uniform / ROW_TILE) * ROW_TILE for f in (2, 4)}
    return tuple(sorted(c for c in caps if c < picks))


def _branches(cfg, T: int):
    """One per capacity, then one that carries every pick: exact at
    any imbalance."""
    return [functools.partial(compacted, cfg, C)
            for C in compact_capacities(cfg, T)
            + (T * cfg.experts_per_token,)]


# Differentiated as written, a `switch` makes every branch hand back
# zeros in place of the other branches' residuals: arrays of every
# pick's row out of the smaller branches. So the backward pass makes
# the choice again, each branch from its inputs. Not as a `switch`:
# with a `conditional` in the backward pass the chip's compiler copies
# the old weights of the round (1.48 GB in the benchmark's cell) on
# entry, where the copy lives through the program's peak. As loops of
# one trip or none it does not.

def _once(pred, fn, otherwise, *args):
    """fn(*args) where `pred`, else `otherwise`, as a loop of one trip
    or none. The arguments pass a barrier together with the counter,
    or the compiler would lift the whole body out of the loop and run
    it whether chosen or not."""
    def trip(i, _):
        _, held = jax.lax.optimization_barrier((i, args))
        return fn(*held)

    return jax.lax.fori_loop(0, pred.astype(jnp.int32), trip, otherwise)


@functools.partial(jax.custom_vjp, nondiff_argnums=(0,))
def _fitting_branch(cfg, which, p, h2, prob, order, sizes):
    return jax.lax.switch(which, _branches(cfg, h2.shape[0]), p, h2, prob,
                          order, sizes)


def _fitting_fwd(cfg, which, p, h2, prob, order, sizes):
    args = (which, p, h2, prob, order, sizes)
    return _fitting_branch(cfg, *args), args


def _fitting_bwd(cfg, args, dy):
    which, p, h2, prob, order, sizes = args

    def grads(branch, p, h2, prob, order, sizes, dy):
        return jax.vjp(lambda *a: branch(*a, order, sizes),
                       p, h2, prob)[1](dy)

    out = jax.tree.map(jnp.zeros_like, (p, h2, prob))
    for i, branch in enumerate(_branches(cfg, h2.shape[0])):
        out = _once(which == i, functools.partial(grads, branch), out,
                    p, h2, prob, order, sizes, dy)
    return (None, *out, None, None)


_fitting_branch.defvjp(_fitting_fwd, _fitting_bwd)


def expert_chunk(cfg, p: dict, h2, r):
    """The held experts' part of the expert layer for one chunk of
    positions: h2 [T, H] (normed), r [T, E] -> (y [T, H],
    load [held + 3]: picks that fell on each held expert, then the
    chunk's picks in all, those of them that ran compacted (all or
    none), and the held picks' share of all).

    Where only some experts are held, the chunk runs in the smallest
    of `compact_capacities` that holds its held picks, and with every
    pick's row where none does."""
    T = h2.shape[0]
    picks = T * cfg.experts_per_token
    prob, order, sizes = sorted_picks(cfg, r, p)
    caps = compact_capacities(cfg, T)
    live = sizes.sum()
    if caps:
        which = (live > jnp.asarray(caps, jnp.int32)).sum()
        y = _fitting_branch(
            cfg, which, {name: p[name] for name in ("gate", "up", "down")},
            h2, prob, order, sizes)
        compact = jnp.where(which < len(caps), picks, 0)
    else:
        y = full_width(cfg, p, h2, prob, order, sizes)
        compact = 0
    load = jnp.concatenate([
        sizes.astype(jnp.float32),
        jnp.stack([picks, compact, live / picks]).astype(jnp.float32)])
    return y.astype(h2.dtype), load


def merge_loads(load, axis: int):
    """Loads of several chunks as one: counts add up, the last entry
    (the held picks' share) keeps its largest."""
    return jnp.concatenate([load[..., :-1].sum(axis),
                            load[..., -1:].max(axis)], axis=-1)


def expert_layer(cfg, p: dict, h2, r):
    """h2 [N, L, H], r [N, L, E] -> (y [N, L, H], load [N, held + 3]),
    a chunk of positions at a time."""
    N, L, H = h2.shape
    c = block_of(L, cfg.moe_chunk)
    chunk = functools.partial(expert_chunk, cfg, p)
    if cfg.remat:
        chunk = jax.checkpoint(chunk)
    y, load = jax.lax.map(
        lambda xs: chunk(*xs),
        (h2.reshape(N * L // c, c, H), r.reshape(N * L // c, c, -1)))
    return (y.reshape(N, L, H),
            merge_loads(load.reshape(N, L // c, -1), 1))

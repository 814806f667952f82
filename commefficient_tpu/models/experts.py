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

The layer runs a chunk of `moe_chunk` positions at a time and has its
own backward pass: each chunk's forward again from its inputs, its
weight gradient added into one accumulator a weight for the whole
layer (`_chunks`).

`cfg`, everywhere here, is the model's frozen configuration. What the
layer reads of it: `num_experts` (the router's width),
`experts_per_token`, `held_experts`, `moe_chunk`, and two methods that
are the family's own:

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

WEIGHTS = ("gate", "up", "down")     # the held experts' own


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


# The weight's gradient of ragged_dot(x, W, sizes): each group's rows
# of x^T dy [held, K, N], as JAX's transpose rule forms it.
_BY_GROUP = jax.lax.RaggedDotDimensionNumbers(
    dot_dimension_numbers=(([0], [0]), ([], [])),
    lhs_ragged_dimensions=[0], rhs_group_dimensions=[])


@functools.partial(jax.custom_vjp, nondiff_argnums=(0,))
def _ffn(cfg, w: dict, wt, rows, sizes, live):
    """The held experts' three grouped products on rows sorted by
    expert, cut to the `live` rows."""
    return _ffn_fwd(cfg, w, wt, rows, sizes, live)[0]


def _ffn_fwd(cfg, w, wt, rows, sizes, live):
    cut = _cut_to(live)
    g = cut(jax.lax.ragged_dot(rows, w["gate"], sizes))
    u = cut(jax.lax.ragged_dot(rows, w["up"], sizes))
    out = cut(jax.lax.ragged_dot(cfg.expert_act(g) * u, w["down"], sizes))
    return out, (w, wt, rows, sizes, live, g, u)


def _ffn_bwd(cfg, res, dout):
    """JAX's own transposes of the three products, the rows' gradient
    reading the weights from `wt`, already `transposed`, where given."""
    w, wt, rows, sizes, live, g, u = res
    if wt is None:
        wt = transposed(w)
    cut = _cut_to(live)
    dout = cut(dout)
    a, act_back = jax.vjp(lambda g, u: cfg.expert_act(g) * u, g, u)
    dg, du = map(cut, act_back(
        jax.lax.ragged_dot(dout, wt["down"], sizes)))
    drows = (jax.lax.ragged_dot(dg, wt["gate"], sizes)
             + jax.lax.ragged_dot(du, wt["up"], sizes))
    dw = {name: jax.lax.ragged_dot_general(x, dy, sizes, _BY_GROUP)
          for name, x, dy in (("gate", rows, dg), ("up", rows, du),
                              ("down", a, dout))}
    return dw, None, drows, None, None


_ffn.defvjp(_ffn_fwd, _ffn_bwd)


def transposed(w: dict) -> dict:
    """Each weight [held, K, N] as [held, N, K], the layout in which the
    rows' gradient reads it."""
    return {name: jnp.swapaxes(x, 1, 2) for name, x in w.items()}


def _grouped_ffn(cfg, p: dict, rows, sizes, live, wt=None):
    """`_ffn` on `p`'s weights; `wt`, where given, holds them
    `transposed` for the backward pass."""
    return _ffn(cfg, {name: p[name] for name in WEIGHTS}, wt, rows, sizes,
                live)


def full_width(cfg, p: dict, h2, prob, order, sizes, wt=None):
    """Every pick's row carried through the grouped products, in
    expert order: the chunk where every expert is held. -> y [T, H]."""
    T, H = h2.shape
    k = cfg.experts_per_token
    with scope("moe_route"):
        inverse = jnp.argsort(order)
        rows = _permute(jnp.repeat(h2, k, axis=0), order, inverse)
        live = (jnp.arange(T * k) < sizes.sum())[:, None]
        rows = _cut_to(live)(rows)
    with scope("expert_ffn"):
        out = _grouped_ffn(cfg, p, rows, sizes, live, wt)
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


def compacted(cfg, C: int, p: dict, h2, prob, order, sizes, wt=None):
    """`full_width` for a chunk whose held picks number at most C:
    only the first C sorted rows exist. -> y [T, H]."""
    T, k = h2.shape[0], cfg.experts_per_token
    with scope("moe_route"):
        carried = order[:C]
        tok = carried // k
        slot = jnp.minimum(jnp.argsort(order), C).reshape(T, k)
        live = (jnp.arange(C) < sizes.sum())[:, None]
        rows = _cut_to(live)(_take(h2, tok, slot))
    with scope("expert_ffn"):
        out = _grouped_ffn(cfg, p, rows, sizes, live, wt)
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


def _branch_of(caps, sizes):
    """The branch a chunk takes: the first capacity that holds its held
    picks, len(caps) (every pick) where none does."""
    return (sizes.sum() > jnp.asarray(caps, jnp.int32)).sum()


def expert_chunk(cfg, p: dict, h2, r):
    """The held experts' part of the expert layer for one chunk of
    positions: h2 [T, H] (normed), r [T, E] -> (y [T, H],
    load [held + 3]: picks that fell on each held expert, then the
    chunk's picks in all, those of them that ran compacted (all or
    none), and the held picks' share of all).

    Where only some experts are held, the chunk runs in the smallest
    of `compact_capacities` that holds its held picks, and with every
    pick's row where none does. Differentiated by itself, its choice
    is a `switch` that hands back every branch's residuals;
    `expert_layer` gives its loop of chunks a backward pass of its
    own."""
    T = h2.shape[0]
    picks = T * cfg.experts_per_token
    prob, order, sizes = sorted_picks(cfg, r, p)
    caps = compact_capacities(cfg, T)
    live = sizes.sum()
    if caps:
        which = _branch_of(caps, sizes)
        y = jax.lax.switch(which, _branches(cfg, T),
                           {name: p[name] for name in WEIGHTS}, h2, prob,
                           order, sizes)
        compact = jnp.where(which < len(caps), picks, 0)
    else:
        y = full_width(cfg, p, h2, prob, order, sizes)
        compact = 0
    load = jnp.concatenate([
        sizes.astype(jnp.float32),
        jnp.stack([picks, compact, live / picks]).astype(jnp.float32)])
    return y.astype(h2.dtype), load


# The backward pass makes each chunk's choice of branch again, from its
# inputs. Not as a `switch`: with a `conditional` in the backward pass
# the chip's compiler copies the old weights of the round (1.48 GB in
# the SmallThinker cell) on entry, where the copy lives through the
# program's peak. As loops of one trip or none it does not.

def _once(pred, fn, carry, *args):
    """fn(carry, *args) where `pred`, else `carry` untouched, as a loop
    of one trip or none. The arguments pass a barrier together with the
    counter, or the compiler would lift the work on them out of the loop
    and run it whether chosen or not. The carry (the layer's gradient
    accumulators) and what `fn` closes over (the weights and their
    transposes) stay out of it."""
    def trip(i, carry):
        _, held = jax.lax.optimization_barrier((i, args))
        return fn(carry, *held)

    return jax.lax.fori_loop(0, pred.astype(jnp.int32), trip, carry)


def _add_grads(cfg, w: dict, wt: dict, carry, at, h2, prob, order, sizes,
               dy):
    """One chunk's backward pass, its forward computed again from its
    inputs and routing (`wt`: the weights transposed): carry (acc,
    dh2 [n, T, H]) -> (acc with the chunk's weight gradient added, dh2
    with the chunk's written at row `at`, dprob [T, k])."""
    T = h2.shape[0]

    def grads(branch, carry, h2, prob, order, sizes, dy):
        acc, dh2, _ = carry
        y, back = jax.vjp(
            lambda w, h2, prob: branch(w, h2, prob, order, sizes, wt),
            w, h2, prob)
        dw, dx, dprob = back(dy.astype(y.dtype))
        return (jax.tree.map(jnp.add, acc, dw),
                jax.lax.dynamic_update_index_in_dim(dh2, dx, at, 0), dprob)

    carry = (*carry, jnp.zeros_like(prob))
    caps = compact_capacities(cfg, T)
    if not caps:
        return grads(functools.partial(full_width, cfg), carry, h2, prob,
                     order, sizes, dy)
    which = _branch_of(caps, sizes)
    for i, branch in enumerate(_branches(cfg, T)):
        carry = _once(which == i, functools.partial(grads, branch), carry,
                      h2, prob, order, sizes, dy)
    return carry


@functools.partial(jax.custom_vjp, nondiff_argnums=(0,))
def _chunks(cfg, p: dict, h2, r):
    """`expert_chunk` over chunks h2 [n, T, H], r [n, T, E] ->
    (y [n, T, H], load [n, held + 4]: `expert_chunk`'s with a 1 before
    its last entry, the chunk counted)."""
    y, load = jax.lax.map(lambda xs: expert_chunk(cfg, p, *xs), (h2, r))
    return y, jnp.concatenate(
        [load[:, :-1], jnp.ones_like(load[:, -1:]), load[:, -1:]], axis=1)


def _chunks_fwd(cfg, p, h2, r):
    return _chunks(cfg, p, h2, r), (p, h2, r)


def _chunks_bwd(cfg, res, cts):
    """The chunks in turn, each forward again from (h2, r) as a
    rematerialised chunk would be, its rows' gradients written into the
    layer's and its weight gradient added into ONE float32 accumulator
    a weight, carried by every chunk and every branch: a chunk makes no
    weight-sized zeros or copy, and adds its products once. The
    router's own parameters (Trinity's expert bias) only choose the
    picks: no gradient reaches them here."""
    p, h2, r = res
    dy = cts[0]
    w = {name: p[name] for name in WEIGHTS}
    # once a layer: transposed inside the loops, they were copied in
    # every trip
    wt = transposed(w)

    def route(r):
        prob, order, sizes = sorted_picks(cfg, r, p)
        return prob, (order, sizes)

    # chunk i's rows' gradients are written over its inputs, which no
    # later chunk reads: the layer's gradients need no buffers of their own
    def chunk(i, carry):
        acc, h2, r = carry
        prob, back, (order, sizes) = jax.vjp(route, r[i], has_aux=True)
        acc, h2, dprob = _add_grads(cfg, w, wt, (acc, h2), i, h2[i],
                                    prob, order, sizes, dy[i])
        return acc, h2, r.at[i].set(back(dprob)[0])

    acc = {k: jnp.zeros_like(x, jnp.float32) for k, x in w.items()}
    acc, dh2, dr = jax.lax.fori_loop(0, h2.shape[0], chunk, (acc, h2, r))
    dp = {k: acc[k].astype(x.dtype) if k in acc else None
          for k, x in p.items()}
    return dp, dh2, dr


_chunks.defvjp(_chunks_fwd, _chunks_bwd)


def merge_loads(load, axis: int):
    """Loads of several chunks as one: counts add up, the last entry
    (the held picks' share) keeps its largest."""
    return jnp.concatenate([load[..., :-1].sum(axis),
                            load[..., -1:].max(axis)], axis=-1)


def expert_layer(cfg, p: dict, h2, r):
    """h2 [N, L, H], r [N, L, E] -> (y [N, L, H], load [N, held + 4]:
    `expert_chunk`'s load with the chunks counted before its last
    entry), a chunk of positions at a time (`_chunks`)."""
    N, L, H = h2.shape
    c = block_of(L, cfg.moe_chunk)
    y, load = _chunks(cfg, p, h2.reshape(N * L // c, c, H),
                      r.reshape(N * L // c, c, -1))
    return (y.reshape(N, L, H),
            merge_loads(load.reshape(N, L // c, -1), 1))

"""SmallThinker (PowerInfer, 2025): a decoder whose every layer is a
mixture of experts read by a router that sits BEFORE attention, with
full-attention layers that carry no position encoding mixed 1:3 with
RoPE layers that see a sliding window.

One layer, on `x` [T, H] (float32; the configuration's `rope_layout`
and `sliding_window_layout` say which kind layer `l` is):

    r  = x @ W_r                      router logits, from the layer's
                                      input: before the norm, before
                                      attention
    h  = rmsnorm(x, g1)
    q, k, v = h @ W_q, h @ W_k, h @ W_v        no biases, no q/k norm
    q, k = rope(q), rope(k)           rotate-half, only where the
                                      layout says so
    a  = softmax(mask(q k^T / sqrt(Dh))) v      query head i reads
                                      key-value head i // group;
                                      j <= i, and j > i - window in a
                                      window layer
    x1 = x + a @ W_o
    h2 = rmsnorm(x1, g2)
    idx = top_k(r);  p = softmax(r[idx])
    y  = sum_{e in idx} p_e (relu(h2 G_e) * (h2 U_e)) D_e
    x2 = x1 + y

then a final RMSNorm and an untied head.

**The expert layer is told which experts it holds** (`held_experts` =
(first, count)): it routes over all `num_experts`, computes its own
experts' part of `y`, and leaves out what the absent experts would
add — one chip's share of an expert-parallel deployment, run without
its exchange. No token is dropped at any imbalance: the picks are
sorted by expert, the held ones are a prefix of the sorted rows, and
`jax.lax.ragged_dot` runs each expert over its own stretch, however
long (on the TPU a grouped-matmul kernel that visits only the row
tiles in use). Where only some experts are held, only that prefix is
carried: each chunk of positions counts its held picks and runs in
the smallest of the static capacities that holds them (twice and four
times a uniform router's share, then every pick: `compact_capacities`),
gathering those rows from the positions and adding the weighted
results back by position. Where every expert is held there is nothing
to choose, and every pick's row is carried (`full_width`).

Everything here is a function of a plain parameter tree (no Flax
module): the tree is the one the benchmark's plain reference builds
(`fedbench/configs/smallthinker.py`), flattened in `jax.tree_util`
order. The loss takes a whole cohort at once (`cohort = True`, see
`federated/client.fused_shard_grads`): the expert layer's sort and
grouped products are not written per client and then vmapped.

Long sequences: attention is `ops.attention.blockwise_attention`
(scores exist a block at a time); each layer is rematerialised in the
backward pass; the expert layer runs in chunks of `moe_chunk`
positions, each rematerialised, so the sorted copies of the picks
(the held picks' rows up to the chunk's capacity; six rows a position
only where the held picks outnumber four times a uniform router's, or
every expert is held) never exist for a whole batch; the
head and the loss run in blocks of `loss_block` positions, so logits
over the vocabulary never exist for a whole batch either.
"""
from __future__ import annotations

import dataclasses
import functools
import math
from typing import Tuple

import jax
import jax.numpy as jnp

from commefficient_tpu.ops.attention import blockwise_attention
from commefficient_tpu.scopes import scope


@dataclasses.dataclass(frozen=True)
class SmallThinkerConfig:
    vocab_size: int = 151936
    hidden_size: int = 2560
    num_layers: int = 52
    num_heads: int = 28
    num_kv_heads: int = 4
    head_dim: int = 128
    expert_width: int = 768
    num_experts: int = 64            # the router's width
    experts_per_token: int = 6
    held_experts: Tuple[int, int] = (0, 64)   # (first, count) held here
    rope_layout: Tuple[int, ...] = (0, 1, 1, 1) * 13
    window_layout: Tuple[int, ...] = (0, 1, 1, 1) * 13
    window_size: int = 4096
    rope_theta: float = 1.5e6
    rms_eps: float = 1e-6
    initializer_range: float = 0.02
    remat: bool = True
    moe_chunk: int = 4096
    loss_block: int = 2048
    attn_block: int = 512

    def replace(self, **kw) -> "SmallThinkerConfig":
        return dataclasses.replace(self, **kw)

    @classmethod
    def from_published(cls, c: dict, **kw) -> "SmallThinkerConfig":
        """From the keys of the model's public `config.json`."""
        n = int(c["num_hidden_layers"])
        return cls(
            vocab_size=int(c["vocab_size"]),
            hidden_size=int(c["hidden_size"]), num_layers=n,
            num_heads=int(c["num_attention_heads"]),
            num_kv_heads=int(c["num_key_value_heads"]),
            head_dim=int(c["head_dim"]),
            expert_width=int(c["moe_ffn_hidden_size"]),
            experts_per_token=int(c["moe_num_active_primary_experts"]),
            rope_layout=tuple(int(x) for x in c["rope_layout"][:n]),
            window_layout=tuple(
                int(x) for x in c["sliding_window_layout"][:n]),
            window_size=int(c["sliding_window_size"]),
            rope_theta=float(c["rope_theta"]),
            rms_eps=float(c["rms_norm_eps"]), **kw)


# the test-suite size: two periods, every mechanism present
TINY = SmallThinkerConfig(
    vocab_size=96, hidden_size=64, num_layers=8, num_heads=4,
    num_kv_heads=2, head_dim=16, expert_width=32, num_experts=8,
    experts_per_token=2, held_experts=(0, 8),
    rope_layout=(0, 1, 1, 1) * 2, window_layout=(0, 1, 1, 1) * 2,
    window_size=8, moe_chunk=16, loss_block=16, attn_block=8)


def param_shapes(cfg: SmallThinkerConfig) -> dict:
    H, F = cfg.hidden_size, cfg.expert_width
    q = cfg.num_heads * cfg.head_dim
    kv = cfg.num_kv_heads * cfg.head_dim
    n = cfg.held_experts[1]
    layer = {"router": (H, cfg.num_experts), "norm1": (H,),
             "wq": (H, q), "wk": (H, kv), "wv": (H, kv), "wo": (q, H),
             "norm2": (H,), "gate": (n, H, F), "up": (n, H, F),
             "down": (n, F, H)}
    tree = {"embed": (cfg.vocab_size, H), "final_norm": (H,),
            "head": (H, cfg.vocab_size)}
    for i in range(cfg.num_layers):
        tree[f"layer_{i}"] = dict(layer)
    return tree


def init_params(cfg: SmallThinkerConfig, key) -> dict:
    """N(0, initializer_range) matrices and unit norm scales, every
    leaf from its own fold of `key`, in one jitted call."""
    leaves, treedef = jax.tree_util.tree_flatten(
        param_shapes(cfg), is_leaf=lambda x: isinstance(x, tuple))

    @jax.jit
    def make(key):
        return jax.tree_util.tree_unflatten(treedef, [
            jnp.ones(shape, jnp.float32) if len(shape) == 1
            else jax.random.normal(jax.random.fold_in(key, i), shape,
                                   jnp.float32) * cfg.initializer_range
            for i, shape in enumerate(leaves)])

    return make(key)


def num_params(cfg: SmallThinkerConfig) -> int:
    return sum(math.prod(shape) for shape in jax.tree_util.tree_leaves(
        param_shapes(cfg), is_leaf=lambda x: isinstance(x, tuple)))


def _block_of(L: int, at_most: int) -> int:
    """The largest divisor of L that is at most `at_most`."""
    b = min(at_most, L)
    while L % b:
        b -= 1
    return b


# ---------------- the layer's pieces --------------------------------------

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


def attention(cfg: SmallThinkerConfig, p: dict, h, rope_on: bool,
              window_on: bool):
    """h [N, L, H] (normed) -> [N, L, num_heads * head_dim]."""
    N, L, _ = h.shape
    q = (h @ p["wq"]).reshape(N, L, cfg.num_heads, cfg.head_dim)
    k = (h @ p["wk"]).reshape(N, L, cfg.num_kv_heads, cfg.head_dim)
    v = (h @ p["wv"]).reshape(N, L, cfg.num_kv_heads, cfg.head_dim)
    with scope("attention_window" if window_on else "attention_full"):
        if rope_on:
            q, k = rope(q, cfg.rope_theta), rope(k, cfg.rope_theta)
        a = blockwise_attention(
            q.transpose(0, 2, 1, 3), k.transpose(0, 2, 1, 3),
            v.transpose(0, 2, 1, 3),
            cfg.window_size if window_on else None, cfg.attn_block)
    return a.transpose(0, 2, 1, 3).reshape(N, L, -1)


@jax.custom_vjp
def _permute(x, perm, inverse):
    """x[perm] for a permutation `perm` of the rows: its transpose is
    a gather by the inverse, not a scatter."""
    return x[perm]


_permute.defvjp(lambda x, perm, inverse: (x[perm], (perm, inverse)),
                lambda res, g: (g[res[1]], None, None))


def route(cfg: SmallThinkerConfig, r):
    """Router logits r [T, E] -> (idx [T, k] over all E experts,
    p [T, k]: softmax over the k selected logits, which is the
    softmax over all E renormalised over the k)."""
    top, idx = jax.lax.top_k(r.astype(jnp.float32), cfg.experts_per_token)
    return idx, jax.nn.softmax(top, axis=-1)


def sorted_picks(cfg: SmallThinkerConfig, r):
    """Router logits r [T, E] -> (prob [T, k], order [T*k]: the picks
    sorted by held expert, absent experts' picks last as one group,
    sizes [held]: picks on each held expert)."""
    first, held = cfg.held_experts
    with scope("moe_route"):
        idx, prob = route(cfg, r)
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


def _grouped_ffn(p: dict, rows, sizes, cut):
    g = cut(jax.lax.ragged_dot(rows, p["gate"], sizes))
    u = cut(jax.lax.ragged_dot(rows, p["up"], sizes))
    return cut(jax.lax.ragged_dot(jax.nn.relu(g) * u, p["down"], sizes))


def full_width(cfg: SmallThinkerConfig, p: dict, h2, prob, order, sizes):
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
        out = _grouped_ffn(p, rows, sizes, cut)
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


def compacted(cfg: SmallThinkerConfig, C: int, p: dict, h2, prob, order,
              sizes):
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
        out = _grouped_ffn(p, rows, sizes, cut)
        w = prob.reshape(-1)[carried].astype(out.dtype)
        return _spread(out * w[:, None], tok, slot)


ROW_TILE = 8      # rows of a float32 (8, 128) tile


def compact_capacities(cfg: SmallThinkerConfig, T: int) -> Tuple[int, ...]:
    """The static row counts a chunk of T positions may be compacted
    to: twice and four times the held picks of a uniform router, in
    whole row tiles, where that is under the T * k picks in all. None
    where every expert is held."""
    picks = T * cfg.experts_per_token
    uniform = picks * cfg.held_experts[1] / cfg.num_experts
    caps = {math.ceil(f * uniform / ROW_TILE) * ROW_TILE for f in (2, 4)}
    return tuple(sorted(c for c in caps if c < picks))


def _branches(cfg: SmallThinkerConfig, T: int):
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


def expert_chunk(cfg: SmallThinkerConfig, p: dict, h2, r):
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
    prob, order, sizes = sorted_picks(cfg, r)
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


def expert_layer(cfg: SmallThinkerConfig, p: dict, h2, r):
    """h2 [N, L, H], r [N, L, E] -> (y [N, L, H], load [N, held + 3]),
    a chunk of positions at a time."""
    N, L, H = h2.shape
    c = _block_of(L, cfg.moe_chunk)
    chunk = functools.partial(expert_chunk, cfg, p)
    if cfg.remat:
        chunk = jax.checkpoint(chunk)
    y, load = jax.lax.map(
        lambda xs: chunk(*xs),
        (h2.reshape(N * L // c, c, H), r.reshape(N * L // c, c, -1)))
    return (y.reshape(N, L, H),
            merge_loads(load.reshape(N, L // c, -1), 1))


def layer(cfg: SmallThinkerConfig, rope_on: bool, window_on: bool,
          p: dict, x):
    with scope("moe_route"):
        r = x @ p["router"]
    a = attention(cfg, p, rmsnorm(x, p["norm1"], cfg.rms_eps), rope_on,
                  window_on)
    x1 = x + a @ p["wo"]
    y, load = expert_layer(
        cfg, p, rmsnorm(x1, p["norm2"], cfg.rms_eps), r)
    return x1 + y, load


def hidden(cfg: SmallThinkerConfig, params: dict, input_ids):
    """input_ids [N, L] -> (final normed hidden [N, L, H],
    load [N, layers, held + 3])."""
    x = params["embed"][input_ids]
    loads = []
    for i in range(cfg.num_layers):
        f = functools.partial(layer, cfg, bool(cfg.rope_layout[i]),
                              bool(cfg.window_layout[i]))
        if cfg.remat:
            f = jax.checkpoint(f)
        x, load = f(params[f"layer_{i}"], x)
        loads.append(load)
    return (rmsnorm(x, params["final_norm"], cfg.rms_eps),
            jnp.stack(loads, axis=1))


def logits(cfg: SmallThinkerConfig, params: dict, input_ids):
    """[N, L] -> [N, L, V] float32, all at once (tests, small sizes)."""
    h, _ = hidden(cfg, params, input_ids)
    return (h @ params["head"]).astype(jnp.float32)


def next_token_nll(cfg: SmallThinkerConfig, head, h, labels, valid):
    """Sum of the next-token losses of each sequence: h [N, L, H]
    (final, normed), labels [N, L] (the token that follows each
    position), valid [N, L] f32 -> [N]. The head and the softmax run
    over `loss_block` positions at a time."""
    N, L, H = h.shape
    b = _block_of(L, cfg.loss_block)

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


# ---------------- the loss the drivers hand to FedModel -------------------

def make_lm_loss(cfg: SmallThinkerConfig, pad_id: int):
    """Next-token loss over every real token of a PersonaChat
    sequence (`data/persona.py`'s batch: input_ids [.., B, C, L]
    first), for a whole cohort at once: batch leaves [W, B, ...],
    mask [W, B] -> (losses [W], (load [W, layers, held + 3],)); the
    round's telemetry turns the loads into its per-layer counters
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
        nll = next_token_nll(cfg, params["head"], h, labels, valid)
        per_client = nll.reshape(W, B * C).sum(1) / jnp.maximum(
            valid.reshape(W, -1).sum(1), 1.0)
        return per_client, (
            merge_loads(load.reshape(W, B * C, *load.shape[1:]), 1),)

    compute_loss.cohort = True
    return compute_loss

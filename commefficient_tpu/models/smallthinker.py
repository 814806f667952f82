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
(first, count)): one chip's share of an expert-parallel deployment,
run without its exchange (`models/experts.py`, which Trinity's layer
calls too; this model gives it the softmax router and `relu`).

Everything here is a function of a plain parameter tree (no Flax
module): the tree is the one the benchmark's plain reference builds
(`fedbench/configs/smallthinker.py`), flattened in `jax.tree_util`
order. The loss takes a whole cohort at once (`cohort = True`, see
`federated/client.fused_shard_grads`): the expert layer's sort and
grouped products are not written per client and then vmapped.

Long sequences: attention is `ops.attention.blockwise_attention`
(scores exist a block at a time, on a TPU in its kernels' VMEM; q, k,
v and its result in the projections' layout); each layer is
rematerialised in the backward pass; the expert layer runs in chunks
of `moe_chunk` positions, each computed again in the layer's own
backward pass (`models/experts.py`), so the sorted copies
of the picks (the held picks' rows up to the chunk's capacity; six
rows a position only where the held picks outnumber four times a
uniform router's, or every expert is held) never exist for a whole
batch; the head and the loss run in blocks of `loss_block` positions,
so logits over the vocabulary never exist for a whole batch either.
"""
from __future__ import annotations

import dataclasses
import functools
import math
from typing import Tuple

import jax
import jax.numpy as jnp

from commefficient_tpu.models import decoder
from commefficient_tpu.models.decoder import (  # noqa: F401
    next_token_nll, rmsnorm, rope,
)
from commefficient_tpu.models.experts import (  # noqa: F401
    compact_capacities, compacted, expert_chunk, expert_layer, full_width,
    merge_loads, sorted_picks,
)
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

    @property
    def expert_layers(self) -> int:
        return self.num_layers

    def route(self, r, p=None):
        """Router logits r [T, E] -> (idx [T, k] over all E experts,
        p [T, k]: softmax over the k selected logits, which is the
        softmax over all E renormalised over the k)."""
        top, idx = jax.lax.top_k(r.astype(jnp.float32),
                                 self.experts_per_token)
        return idx, jax.nn.softmax(top, axis=-1)

    def expert_act(self, x):
        return jax.nn.relu(x)

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


# ---------------- the layer's pieces --------------------------------------

def attention(cfg: SmallThinkerConfig, p: dict, h, rope_on: bool,
              window_on: bool):
    """h [N, L, H] (normed) -> [N, L, num_heads * head_dim]."""
    N, L, _ = h.shape
    with scope("attn_proj"):
        q = (h @ p["wq"]).reshape(N, L, cfg.num_heads, cfg.head_dim)
        k = (h @ p["wk"]).reshape(N, L, cfg.num_kv_heads, cfg.head_dim)
        v = (h @ p["wv"]).reshape(N, L, cfg.num_kv_heads, cfg.head_dim)
    with scope("attention_window" if window_on else "attention_full"):
        if rope_on:
            q, k = rope(q, cfg.rope_theta), rope(k, cfg.rope_theta)
        a = blockwise_attention(q, k, v,
                                cfg.window_size if window_on else None,
                                cfg.attn_block)
    return a.reshape(N, L, -1)


def layer(cfg: SmallThinkerConfig, rope_on: bool, window_on: bool,
          p: dict, x):
    with scope("moe_route"):
        r = x @ p["router"]
    with scope("norm"):
        h = rmsnorm(x, p["norm1"], cfg.rms_eps)
    a = attention(cfg, p, h, rope_on, window_on)
    with scope("attn_proj"):
        o = a @ p["wo"]
    with scope("norm"):
        x1 = x + o
        h2 = rmsnorm(x1, p["norm2"], cfg.rms_eps)
    y, load = expert_layer(cfg, p, h2, r)
    with scope("norm"):
        return x1 + y, load


def hidden(cfg: SmallThinkerConfig, params: dict, input_ids):
    """input_ids [N, L] -> (final normed hidden [N, L, H],
    load [N, layers, held + 4])."""
    x = params["embed"][input_ids]
    loads = []
    for i in range(cfg.num_layers):
        f = functools.partial(layer, cfg, bool(cfg.rope_layout[i]),
                              bool(cfg.window_layout[i]))
        if cfg.remat:
            f = jax.checkpoint(f)
        x, load = f(params[f"layer_{i}"], x)
        loads.append(load)
    with scope("norm"):
        x = rmsnorm(x, params["final_norm"], cfg.rms_eps)
    return x, jnp.stack(loads, axis=1)


def logits(cfg: SmallThinkerConfig, params: dict, input_ids):
    """[N, L] -> [N, L, V] float32, all at once (tests, small sizes)."""
    h, _ = hidden(cfg, params, input_ids)
    return (h @ params["head"]).astype(jnp.float32)


# ---------------- the loss the drivers hand to FedModel -------------------

def make_lm_loss(cfg: SmallThinkerConfig, pad_id: int):
    """`decoder.make_lm_loss` over this model's layers:
    losses [W], (load [W, layers, held + 4],)."""
    return decoder.make_lm_loss(cfg, pad_id, hidden)

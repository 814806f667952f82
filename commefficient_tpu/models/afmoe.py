"""AFMoE (Arcee's Trinity family, 2025): a decoder whose leading
layers are dense and whose other layers are a mixture of experts read
by a sigmoid router with an expert bias, beside one shared expert;
gated attention with q/k RMSNorm, sliding-window layers with RoPE
mixed 3:1 with full layers that carry no position encoding, and a norm
after each sublayer as well as before it.

On x [T, H] (float32; `window_layout[l]` says which kind layer l is):

    x0 = embed[ids] * sqrt(H)                      (mup_enabled)
    h  = rmsnorm(x, g_in)
    q  = rmsnorm_head(h W_q, g_q);  k = rmsnorm_head(h W_k, g_k)
    v  = h W_v                                     (per-head norms)
    q, k = rope(q), rope(k)          window layers only; full: NoPE
    a  = softmax(mask(q k^T / sqrt(Dh))) v         query head i reads
                                     key-value head i // group;
                                     j <= i, and j > i - window in a
                                     window layer
    a  = a * sigmoid(h W_g)                        the output gate
    x1 = x + rmsnorm(a W_o, g_post_attn)
    h2 = rmsnorm(x1, g_pre_mlp)
    dense layer:  f = (silu(h2 G) * (h2 U)) D
    expert layer: s = sigmoid(h2 W_r) [E];  idx = top_k(s + b)
                  w = s[idx] / sum(s[idx]) * route_scale
                  f = SwiGLU_shared(h2) + sum_{e in idx, held} w_e SwiGLU_e(h2)
    x2 = x1 + rmsnorm(f, g_post_mlp)

then a final RMSNorm and an untied head. `b` (`expert_bias`, one
[E] leaf a layer) decides the selection and weighs nothing: its
gradient is zero by construction (the load-balancing update that
moves it in training is not run). `route_norm` divides by the sum over
all k picks, held or not.

The routed experts are `models/experts.py`'s held-expert layer, the
one SmallThinker's layer calls: this model gives it the sigmoid router
(`route`) and `silu`. The shared expert runs for every position under
the scope `shared_expert`, the dense layers' SwiGLU under `dense_mlp`.
Everything is a function of a plain parameter tree, the one the
benchmark's plain reference builds (`fedbench/configs/afmoe.py`),
flattened in `jax.tree_util` order.
Long sequences as in SmallThinker: blockwise attention, each layer
rematerialised, the expert layer in chunks, head and loss in blocks.
"""
from __future__ import annotations

import dataclasses
import functools
import math
from typing import Tuple

import jax
import jax.numpy as jnp
from jax.ad_checkpoint import checkpoint_name

from commefficient_tpu.models import decoder
from commefficient_tpu.models.decoder import rmsnorm, rope
from commefficient_tpu.models.experts import expert_layer, sigmoid_route
from commefficient_tpu.ops.attention import blockwise_attention
from commefficient_tpu.scopes import scope


@dataclasses.dataclass(frozen=True)
class AfmoeConfig:
    vocab_size: int = 200192
    hidden_size: int = 2048
    num_layers: int = 32
    num_dense_layers: int = 2
    num_heads: int = 32
    num_kv_heads: int = 4
    head_dim: int = 128
    dense_width: int = 6144
    expert_width: int = 1024
    num_experts: int = 128           # the router's width
    experts_per_token: int = 8
    held_experts: Tuple[int, int] = (0, 128)   # (first, count) held here
    num_shared_experts: int = 1
    route_scale: float = 2.826
    route_norm: bool = True
    window_layout: Tuple[int, ...] = (1, 1, 1, 0) * 8
    window_size: int = 2048
    rope_theta: float = 1e4
    rms_eps: float = 1e-5
    mup: bool = True
    initializer_range: float = 0.02
    remat: bool = True
    # 2,048 positions carry 16,384 picks at top-8 (SmallThinker's 4,096
    # carry 24,576): at 4,096 the benchmark cell's round program asks
    # the chip's compiler for 16.35 GiB, at 2,048 for 12.0
    moe_chunk: int = 2048
    loss_block: int = 2048
    attn_block: int = 512

    def replace(self, **kw) -> "AfmoeConfig":
        return dataclasses.replace(self, **kw)

    @property
    def expert_layers(self) -> int:
        return self.num_layers - self.num_dense_layers

    def route(self, r, p):
        """`experts.sigmoid_route` with the layer's expert bias."""
        return sigmoid_route(r, p["expert_bias"], self.experts_per_token,
                             self.route_norm, self.route_scale)

    def expert_act(self, x):
        return jax.nn.silu(x)

    @classmethod
    def from_published(cls, c: dict, **kw) -> "AfmoeConfig":
        """From the keys of the model's public `config.json`; the
        layers are those from `first_layer` (0 unless the file says)
        on."""
        if c.get("score_func", "sigmoid") != "sigmoid":
            raise ValueError(f"AFMoE: score_func {c['score_func']!r}, "
                             f"only 'sigmoid' is written")
        n = int(c["num_hidden_layers"])
        first = int(c.get("first_layer", 0))
        return cls(
            vocab_size=int(c["vocab_size"]),
            hidden_size=int(c["hidden_size"]), num_layers=n,
            num_dense_layers=int(c["num_dense_layers"]),
            num_heads=int(c["num_attention_heads"]),
            num_kv_heads=int(c["num_key_value_heads"]),
            head_dim=int(c["head_dim"]),
            dense_width=int(c["intermediate_size"]),
            expert_width=int(c["moe_intermediate_size"]),
            experts_per_token=int(c["num_experts_per_tok"]),
            num_shared_experts=int(c["num_shared_experts"]),
            route_scale=float(c["route_scale"]),
            route_norm=bool(c["route_norm"]),
            window_layout=tuple(
                int(t == "sliding_attention")
                for t in c["layer_types"][first:first + n]),
            window_size=int(c["sliding_window"]),
            rope_theta=float(c["rope_theta"]),
            rms_eps=float(c["rms_norm_eps"]),
            mup=bool(c["mup_enabled"]), **kw)


# the test-suite size: a dense layer, then a whole period of expert
# layers (window, window, full, window), every mechanism present
TINY = AfmoeConfig(
    vocab_size=96, hidden_size=64, num_layers=5, num_dense_layers=1,
    num_heads=4, num_kv_heads=2, head_dim=16, dense_width=96,
    expert_width=32, num_experts=16, experts_per_token=4,
    held_experts=(0, 16), window_layout=(1, 1, 0, 1, 1), window_size=8,
    moe_chunk=16, loss_block=16, attn_block=8)


def param_shapes(cfg: AfmoeConfig) -> dict:
    H, D = cfg.hidden_size, cfg.head_dim
    q, kv = cfg.num_heads * D, cfg.num_kv_heads * D
    F, n = cfg.expert_width, cfg.held_experts[1]
    S = F * cfg.num_shared_experts
    attn = {"norm_in": (H,), "wq": (H, q), "wk": (H, kv), "wv": (H, kv),
            "wg": (H, q), "wo": (q, H), "q_norm": (D,), "k_norm": (D,),
            "norm_post_attn": (H,), "norm_pre_mlp": (H,),
            "norm_post_mlp": (H,)}
    dense = {"mlp_gate": (H, cfg.dense_width), "mlp_up": (H, cfg.dense_width),
             "mlp_down": (cfg.dense_width, H)}
    moe = {"router": (H, cfg.num_experts),
           "expert_bias": (cfg.num_experts,),
           "gate": (n, H, F), "up": (n, H, F), "down": (n, F, H),
           "shared_gate": (H, S), "shared_up": (H, S), "shared_down": (S, H)}
    tree = {"embed": (cfg.vocab_size, H), "final_norm": (H,),
            "head": (H, cfg.vocab_size)}
    for i in range(cfg.num_layers):
        tree[f"layer_{i}"] = {
            **attn, **(dense if i < cfg.num_dense_layers else moe)}
    return tree


def init_params(cfg: AfmoeConfig, key) -> dict:
    """N(0, initializer_range) matrices, unit norm scales and a zero
    expert bias (a fresh model's: training moves it), every matrix
    from its own fold of `key`, in one jitted call."""
    leaves, treedef = jax.tree_util.tree_flatten_with_path(
        param_shapes(cfg), is_leaf=lambda x: isinstance(x, tuple))

    def leaf(i, path, shape):
        if len(shape) > 1:
            return jax.random.normal(jax.random.fold_in(key, i), shape,
                                     jnp.float32) * cfg.initializer_range
        fill = 0.0 if path[-1].key == "expert_bias" else 1.0
        return jnp.full(shape, fill, jnp.float32)

    @jax.jit
    def make(key):
        return jax.tree_util.tree_unflatten(treedef, [
            leaf(i, path, shape) for i, (path, shape) in enumerate(leaves)])

    return make(key)


def num_params(cfg: AfmoeConfig) -> int:
    return sum(math.prod(shape) for shape in jax.tree_util.tree_leaves(
        param_shapes(cfg), is_leaf=lambda x: isinstance(x, tuple)))


# ---------------- the layer's pieces --------------------------------------

# What a rematerialised layer keeps from its first forward pass: the
# routed experts' sum [N, L, H] (134 MB a layer in the benchmark's
# cell). The norm after the MLP needs its value in the backward pass;
# recomputed, the expert layer's forward (routing, sort, grouped
# products) would run a second time there.
KEEP_ROUTED = jax.checkpoint_policies.save_only_these_names("routed_experts")


def swiglu(h, gate, up, down):
    return (jax.nn.silu(h @ gate) * (h @ up)) @ down


def attention(cfg: AfmoeConfig, p: dict, h, window_on: bool):
    """h [N, L, H] (normed) -> [N, L, num_heads * head_dim], gated."""
    N, L, _ = h.shape
    with scope("attn_proj"):
        q = (h @ p["wq"]).reshape(N, L, cfg.num_heads, cfg.head_dim)
        k = (h @ p["wk"]).reshape(N, L, cfg.num_kv_heads, cfg.head_dim)
        v = (h @ p["wv"]).reshape(N, L, cfg.num_kv_heads, cfg.head_dim)
    with scope("attention_window" if window_on else "attention_full"):
        q = rmsnorm(q, p["q_norm"], cfg.rms_eps)
        k = rmsnorm(k, p["k_norm"], cfg.rms_eps)
        if window_on:
            q, k = rope(q, cfg.rope_theta), rope(k, cfg.rope_theta)
        a = blockwise_attention(q, k, v,
                                cfg.window_size if window_on else None,
                                cfg.attn_block)
    with scope("attn_proj"):
        return a.reshape(N, L, -1) * jax.nn.sigmoid(h @ p["wg"])


def mixture(cfg: AfmoeConfig, p: dict, h2):
    """The expert layer: h2 [N, L, H] (normed) -> (shared expert plus
    the held experts' part [N, L, H], load [N, held + 4])."""
    with scope("moe_route"):
        r = h2 @ p["router"]
    with scope("shared_expert"):
        shared = swiglu(h2, p["shared_gate"], p["shared_up"],
                        p["shared_down"])
    y, load = expert_layer(cfg, p, h2, r)
    return shared + checkpoint_name(y, "routed_experts"), load


def layer(cfg: AfmoeConfig, dense: bool, window_on: bool, p: dict, x):
    eps = cfg.rms_eps
    with scope("norm"):
        h = rmsnorm(x, p["norm_in"], eps)
    a = attention(cfg, p, h, window_on)
    with scope("attn_proj"):
        o = a @ p["wo"]
    with scope("norm"):
        x1 = x + rmsnorm(o, p["norm_post_attn"], eps)
        h2 = rmsnorm(x1, p["norm_pre_mlp"], eps)
    if dense:
        with scope("dense_mlp"):
            f = swiglu(h2, p["mlp_gate"], p["mlp_up"], p["mlp_down"])
        load = None
    else:
        f, load = mixture(cfg, p, h2)
    with scope("norm"):
        return x1 + rmsnorm(f, p["norm_post_mlp"], eps), load


def hidden(cfg: AfmoeConfig, params: dict, input_ids):
    """input_ids [N, L] -> (final normed hidden [N, L, H],
    load [N, expert layers, held + 4]): the dense layers report no
    load."""
    x = params["embed"][input_ids]
    if cfg.mup:
        x = x * math.sqrt(cfg.hidden_size)
    loads = []
    for i in range(cfg.num_layers):
        f = functools.partial(layer, cfg, i < cfg.num_dense_layers,
                              bool(cfg.window_layout[i]))
        if cfg.remat:
            f = jax.checkpoint(f, policy=KEEP_ROUTED)
        x, load = f(params[f"layer_{i}"], x)
        if load is not None:
            loads.append(load)
    with scope("norm"):
        x = rmsnorm(x, params["final_norm"], cfg.rms_eps)
    return x, jnp.stack(loads, axis=1)


def logits(cfg: AfmoeConfig, params: dict, input_ids):
    """[N, L] -> [N, L, V] float32, all at once (tests, small sizes)."""
    h, _ = hidden(cfg, params, input_ids)
    return (h @ params["head"]).astype(jnp.float32)


def make_lm_loss(cfg: AfmoeConfig, pad_id: int):
    """`decoder.make_lm_loss` over this model's layers:
    losses [W], (load [W, expert layers, held + 4],)."""
    return decoder.make_lm_loss(cfg, pad_id, hidden)

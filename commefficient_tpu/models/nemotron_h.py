"""Nemotron-H (NVIDIA's hybrid family, `model_type` nemotron_h): a
stack of single-mixer layers laid out by `hybrid_override_pattern`,
one letter a layer: Mamba-2 state-space mixers (M), mixtures of
ungated relu^2 experts beside a shared one (E), and grouped-query
attention with no position encoding (*).

Every layer is one pre-normed mixer on the residual stream,
x = x + mixer(rmsnorm(x)), on x [T, H] (float32):

    M   [z | xBC | dt] = h W_in
        xBC = silu(causal_conv(xBC) + conv_b);  xBC = [x | B | C]
        dt  = softplus(dt + dt_bias);  A = -exp(A_log)
        y   = SSD(x, dt, A, B, C) + D x        head i reads group
                                               i // (heads / groups)
        y   = rmsnorm_grouped(y * silu(z)) * g   groups of I / G
        out = y W_out
    E   s = sigmoid(h W_r) [E];  idx = top_k(s + b)
        w = s[idx] / sum(s[idx]) * route_scale
        out = relu(h U_s)^2 D_s + sum_{e in idx, held} w_e relu(h U_e)^2 D_e
    *   out = softmax(causal(q k^T / sqrt(Dh))) v W_o, q = h W_q,
        k = h W_k, v = h W_v; query head i reads key-value head
        i // (heads / kv heads); no position encoding

then a final RMSNorm and an untied head; the embedding is not scaled.
The SSD scan, the convolution and the gated norm are `ops/ssd.py`'s;
the routed experts are `models/experts.py`'s held-expert layer, given
the sigmoid router it shares with AFMoE (`experts.sigmoid_route`), the
ungated weights and relu^2. `b` (`expert_bias`, the published
`e_score_correction_bias`) decides the selection and weighs nothing:
its gradient is zero.

Scopes: the mixer's two projections under `ssm_proj`; the
convolution, dt, the scan, the D skip and the gated norm under
`ssm_scan`; the rest as in AFMoE. Each layer is rematerialised; the
expert layer runs in chunks of `moe_chunk` positions, attention
blockwise, head and loss in blocks. A mixer layer reports, per
sequence, the sum over its chunks of the mean over heads of the share
of a chunk's end state that reaches the next chunk, and its chunks:
the round's telemetry makes `ssm<l>_carry` and `ssm<l>_chunks` of them
(telemetry/metrics.mixer_vector).

Everything is a function of a plain parameter tree, the one the
benchmark's plain reference builds (`fedbench/configs/nemotron_h.py`),
flattened in `jax.tree_util` order.
"""
from __future__ import annotations

import dataclasses
import functools
import math
from typing import Tuple

import jax
import jax.numpy as jnp

from commefficient_tpu.models import decoder
from commefficient_tpu.models.decoder import rmsnorm
from commefficient_tpu.models.experts import (
    UNGATED, expert_layer, sigmoid_route,
)
from commefficient_tpu.ops.attention import blockwise_attention
from commefficient_tpu.ops.ssd import causal_conv, gated_rmsnorm, ssd_scan
from commefficient_tpu.scopes import scope


@dataclasses.dataclass(frozen=True)
class NemotronHConfig:
    vocab_size: int = 131072
    hidden_size: int = 2688
    layer_kinds: str = ("MEMEM*EMEMEM*EMEMEM*EMEMEM*EMEMEM*EMEMEMEM*"
                        "EMEMEMEME")
    num_heads: int = 32
    num_kv_heads: int = 2
    head_dim: int = 128
    expert_width: int = 1856
    shared_width: int = 3712
    num_experts: int = 128           # the router's width
    experts_per_token: int = 6
    held_experts: Tuple[int, int] = (0, 128)   # (first, count) held here
    route_scale: float = 2.5
    route_norm: bool = True
    ssm_heads: int = 64
    ssm_head_dim: int = 64
    ssm_groups: int = 8
    ssm_state: int = 128
    conv_kernel: int = 4
    ssm_chunk: int = 128
    # dt's initial range (min, max, floor), from which dt_bias is drawn
    time_step: Tuple[float, float, float] = (1e-3, 0.1, 1e-4)
    rms_eps: float = 1e-5
    initializer_range: float = 0.02
    remat: bool = True
    moe_chunk: int = 2048
    loss_block: int = 2048
    attn_block: int = 512

    # the experts' weights (`models/experts.py`): up and down only
    expert_weights = UNGATED

    def replace(self, **kw) -> "NemotronHConfig":
        return dataclasses.replace(self, **kw)

    @property
    def num_layers(self) -> int:
        return len(self.layer_kinds)

    @property
    def expert_layers(self) -> int:
        return self.layer_kinds.count("E")

    @property
    def mixer_layers(self) -> int:
        return self.layer_kinds.count("M")

    @property
    def ssm_width(self) -> int:
        return self.ssm_heads * self.ssm_head_dim

    @property
    def conv_width(self) -> int:
        return self.ssm_width + 2 * self.ssm_groups * self.ssm_state

    def route(self, r, p):
        """`experts.sigmoid_route` with the layer's expert bias."""
        return sigmoid_route(r, p["expert_bias"], self.experts_per_token,
                             self.route_norm, self.route_scale)

    def expert_act(self, x):
        return jnp.square(jax.nn.relu(x))

    @classmethod
    def from_published(cls, c: dict, **kw) -> "NemotronHConfig":
        """From the keys of the model's public `config.json`. The layer
        kinds are `hybrid_override_pattern` where it has
        `num_hidden_layers` letters (a file cut to its layers), else its
        letters from `first_layer` (0 unless the file says) on."""
        if c.get("mlp_hidden_act", "relu2") != "relu2":
            raise ValueError(f"Nemotron-H: mlp_hidden_act "
                             f"{c['mlp_hidden_act']!r}, only 'relu2' is "
                             f"written")
        n = int(c["num_hidden_layers"])
        pattern = c["hybrid_override_pattern"]
        if len(pattern) != n:
            first = int(c.get("first_layer", 0))
            pattern = pattern[first:first + n]
        if len(pattern) != n or set(pattern) - set(MIXERS):
            raise ValueError(f"Nemotron-H: layer kinds {pattern!r} for "
                             f"{n} layers")
        return cls(
            vocab_size=int(c["vocab_size"]),
            hidden_size=int(c["hidden_size"]), layer_kinds=pattern,
            num_heads=int(c["num_attention_heads"]),
            num_kv_heads=int(c["num_key_value_heads"]),
            head_dim=int(c["head_dim"]),
            expert_width=int(c["moe_intermediate_size"]),
            shared_width=int(c["moe_shared_expert_intermediate_size"])
            * int(c["n_shared_experts"]),
            experts_per_token=int(c["num_experts_per_tok"]),
            route_scale=float(c["routed_scaling_factor"]),
            route_norm=bool(c["norm_topk_prob"]),
            ssm_heads=int(c["mamba_num_heads"]),
            ssm_head_dim=int(c["mamba_head_dim"]),
            ssm_groups=int(c["n_groups"]),
            ssm_state=int(c["ssm_state_size"]),
            conv_kernel=int(c["conv_kernel"]),
            ssm_chunk=int(c["chunk_size"]),
            time_step=(float(c["time_step_min"]),
                       float(c["time_step_max"]),
                       float(c["time_step_floor"])),
            rms_eps=float(c["layer_norm_epsilon"]), **kw)


# the test-suite size: one whole period (E, M, E, M, E, M, *), every
# mechanism present
TINY = NemotronHConfig(
    vocab_size=96, hidden_size=64, layer_kinds="EMEMEM*", num_heads=4,
    num_kv_heads=2, head_dim=16, expert_width=32, shared_width=48,
    num_experts=16, experts_per_token=4, held_experts=(0, 16),
    ssm_heads=8, ssm_head_dim=8, ssm_groups=2, ssm_state=16, ssm_chunk=8,
    moe_chunk=16, loss_block=16, attn_block=8)


def param_shapes(cfg: NemotronHConfig) -> dict:
    H, nh = cfg.hidden_size, cfg.ssm_heads
    I, conv = cfg.ssm_width, cfg.conv_width
    q, kv = cfg.num_heads * cfg.head_dim, cfg.num_kv_heads * cfg.head_dim
    F, S, n = cfg.expert_width, cfg.shared_width, cfg.held_experts[1]
    kinds = {
        "M": {"norm": (H,), "in_proj": (H, I + conv + nh),
              "conv_w": (cfg.conv_kernel, conv), "conv_b": (conv,),
              "dt_bias": (nh,), "A_log": (nh,), "D": (nh,),
              "gate_norm": (I,), "out_proj": (I, H)},
        "E": {"norm": (H,), "router": (H, cfg.num_experts),
              "expert_bias": (cfg.num_experts,), "up": (n, H, F),
              "down": (n, F, H), "shared_up": (H, S),
              "shared_down": (S, H)},
        "*": {"norm": (H,), "wq": (H, q), "wk": (H, kv), "wv": (H, kv),
              "wo": (q, H)},
    }
    tree = {"embed": (cfg.vocab_size, H), "final_norm": (H,),
            "head": (H, cfg.vocab_size)}
    for i, kind in enumerate(cfg.layer_kinds):
        tree[f"layer_{i}"] = dict(kinds[kind])
    return tree


def init_params(cfg: NemotronHConfig, key) -> dict:
    """N(0, initializer_range) matrices, W_out over sqrt(layers), unit
    norm scales, a zero expert bias; the mixer's own as Mamba-2 starts
    them: A_log = log(1..heads), D = 1, dt_bias the inverse softplus
    of a dt drawn log-uniform in (min, max) and floored, the
    convolution uniform in +-1/sqrt(kernel). Every leaf from its own
    fold of `key`, in one jitted call."""
    leaves, treedef = jax.tree_util.tree_flatten_with_path(
        param_shapes(cfg), is_leaf=lambda x: isinstance(x, tuple))
    lo, hi, floor = cfg.time_step
    bound = 1.0 / math.sqrt(cfg.conv_kernel)

    def a_log(k, shape):
        return jnp.log(jnp.arange(1, shape[0] + 1, dtype=jnp.float32))

    def dt_bias(k, shape):
        dt = jnp.maximum(jnp.exp(jax.random.uniform(
            k, shape, jnp.float32, math.log(lo), math.log(hi))), floor)
        return dt + jnp.log(-jnp.expm1(-dt))

    def conv(k, shape):
        return jax.random.uniform(k, shape, jnp.float32, -bound, bound)

    def matrix(k, shape):
        return jax.random.normal(k, shape, jnp.float32) \
            * cfg.initializer_range

    def out_proj(k, shape):
        return matrix(k, shape) / math.sqrt(cfg.num_layers)

    def scale(k, shape):
        return jnp.ones(shape, jnp.float32)

    def zero(k, shape):
        return jnp.zeros(shape, jnp.float32)

    by_name = {"A_log": a_log, "dt_bias": dt_bias, "conv_w": conv,
               "conv_b": conv, "out_proj": out_proj, "expert_bias": zero}

    def leaf(i, path, shape):
        draw = by_name.get(path[-1].key,
                           matrix if len(shape) > 1 else scale)
        return draw(jax.random.fold_in(key, i), shape)

    @jax.jit
    def make(key):
        return jax.tree_util.tree_unflatten(treedef, [
            leaf(i, path, shape) for i, (path, shape) in enumerate(leaves)])

    return make(key)


def num_params(cfg: NemotronHConfig) -> int:
    return sum(math.prod(shape) for shape in jax.tree_util.tree_leaves(
        param_shapes(cfg), is_leaf=lambda x: isinstance(x, tuple)))


# ---------------- the three mixers -----------------------------------------

def relu2_mlp(h, up, down):
    return jnp.square(jax.nn.relu(h @ up)) @ down


def ssm(cfg: NemotronHConfig, p: dict, h):
    """The Mamba-2 mixer: h [N, L, H] (normed) -> (out [N, L, H],
    stat [N, 2]: the sum over chunks of the heads' mean carried share,
    and the chunks)."""
    N, L, _ = h.shape
    nh, P, G, S = (cfg.ssm_heads, cfg.ssm_head_dim, cfg.ssm_groups,
                   cfg.ssm_state)
    I = cfg.ssm_width
    with scope("ssm_proj"):
        zxbcdt = h @ p["in_proj"]
    with scope("ssm_scan"):
        z, xbc, dt = jnp.split(zxbcdt, [I, I + cfg.conv_width], axis=-1)
        xbc = jax.nn.silu(causal_conv(xbc, p["conv_w"], p["conv_b"]))
        x, B, C = jnp.split(xbc, [I, I + G * S], axis=-1)
        x = x.reshape(N, L, nh, P)
        y, carry = ssd_scan(x, jax.nn.softplus(dt + p["dt_bias"]),
                            -jnp.exp(p["A_log"]), B.reshape(N, L, G, S),
                            C.reshape(N, L, G, S), cfg.ssm_chunk)
        y = y + x * p["D"][:, None]
        y = gated_rmsnorm(y.reshape(N, L, I), z, p["gate_norm"], I // G,
                          cfg.rms_eps)
        stat = jnp.stack([carry.mean(-1).sum(-1),
                          jnp.full((N,), carry.shape[1], jnp.float32)], -1)
    with scope("ssm_proj"):
        return y @ p["out_proj"], stat


def mixture(cfg: NemotronHConfig, p: dict, h):
    """The expert layer: h [N, L, H] (normed) -> (shared expert plus
    the held experts' part [N, L, H], load [N, held + 4])."""
    with scope("moe_route"):
        r = h @ p["router"]
    with scope("shared_expert"):
        shared = relu2_mlp(h, p["shared_up"], p["shared_down"])
    y, load = expert_layer(cfg, p, h, r)
    return shared + y, load


def attention(cfg: NemotronHConfig, p: dict, h):
    """Causal grouped-query attention, no position encoding: h
    [N, L, H] (normed) -> [N, L, H]."""
    N, L, _ = h.shape
    with scope("attn_proj"):
        q = (h @ p["wq"]).reshape(N, L, cfg.num_heads, cfg.head_dim)
        k = (h @ p["wk"]).reshape(N, L, cfg.num_kv_heads, cfg.head_dim)
        v = (h @ p["wv"]).reshape(N, L, cfg.num_kv_heads, cfg.head_dim)
    with scope("attention_full"):
        a = blockwise_attention(q, k, v, None, cfg.attn_block)
    with scope("attn_proj"):
        return a.reshape(N, L, -1) @ p["wo"], None


MIXERS = {"M": ssm, "E": mixture, "*": attention}


def layer(cfg: NemotronHConfig, kind: str, p: dict, x):
    """x + mixer(rmsnorm(x)) -> (x, what the mixer reports)."""
    with scope("norm"):
        h = rmsnorm(x, p["norm"], cfg.rms_eps)
    out, stat = MIXERS[kind](cfg, p, h)
    with scope("norm"):
        return x + out, stat


def hidden(cfg: NemotronHConfig, params: dict, input_ids):
    """input_ids [N, L] -> (final normed hidden [N, L, H],
    load [N, expert layers, held + 4], mixer stat [N, mixer layers,
    2])."""
    x = params["embed"][input_ids]
    stats = {"E": [], "M": []}
    for i, kind in enumerate(cfg.layer_kinds):
        f = functools.partial(layer, cfg, kind)
        if cfg.remat:
            f = jax.checkpoint(f)
        x, stat = f(params[f"layer_{i}"], x)
        if stat is not None:
            stats[kind].append(stat)
    with scope("norm"):
        x = rmsnorm(x, params["final_norm"], cfg.rms_eps)
    N = x.shape[0]
    return (x, *(jnp.stack(stats[k], axis=1) if stats[k]
                 else jnp.zeros((N, 0, width), jnp.float32)
                 for k, width in (("E", cfg.held_experts[1] + 4),
                                  ("M", 2))))


def logits(cfg: NemotronHConfig, params: dict, input_ids):
    """[N, L] -> [N, L, V] float32, all at once (tests, small sizes)."""
    h = hidden(cfg, params, input_ids)[0]
    return (h @ params["head"]).astype(jnp.float32)


def make_lm_loss(cfg: NemotronHConfig, pad_id: int):
    """`decoder.make_lm_loss` over this model's layers: losses [W],
    (load [W, expert layers, held + 4], mixer stat [W, mixer layers,
    2])."""
    return decoder.make_lm_loss(cfg, pad_id, hidden)

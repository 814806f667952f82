"""Plain reference of Nemotron-H (NVIDIA's hybrid family, `model_type`
nemotron_h) as one chip's share of an expert-parallel deployment:
weights from a seed and one client's next-token loss over PersonaChat
sequences, in straightforward jax.numpy float32. No kernel, nothing
imported from the program; attention and RMSNorm are the AFMoE
reference's (`configs/afmoe.py`).

The model's layer `i` is the published layer `first_layer + i`, of
the kind `hybrid_override_pattern[i]` says (M, E or *). One layer, on
x [L, H]: x = x + mixer(rmsnorm(x, g)), with

    M   [z | xBC | dt] = h @ W_in
        xBC = silu(conv(xBC) + conv_b)   depthwise, causal, conv_kernel
        x, B, C = xBC                    x [heads, P], B, C [groups, N]
        dt = softplus(dt + dt_bias);  A = -exp(A_log)
        per head i, reading group i // (heads / groups), position t:
            S_t = exp(dt_t A_i) S_{t-1} + dt_t x_t B_t^T     S [P, N]
            y_t = S_t C_t + D_i x_t
        y = rmsnorm over groups of I / groups of (y * silu(z)), times g
        out = y @ W_out
    E   sc  = sigmoid(h @ W_r)                     all router_width
        idx = top_k(sc + expert_bias)              selection only
        w   = sc[idx] / (sum(sc[idx]) + 1e-20) * routed_scaling_factor
        out = relu(h @ U_s)^2 @ D_s + sum over held e in idx of
              w_e relu(h @ U_e)^2 @ D_e
    *   out = attention(h W_q, h W_k, h W_v) @ W_o, causal, grouped
              (query head i reads key-value head i // (heads / kv
              heads)), no position encoding

then a final RMSNorm and an untied head; the embedding is not scaled.
The state recurrence is the step form above, a `lax.scan` over
positions, rematerialised SCAN_BLOCK positions at a time so that its
gradient fits at 8,192 positions; it shares nothing with the
program's chunked form. The chip holds experts `held_experts` =
[first, count): every held expert is applied to every position and
weighted by the weight the router gave it there (zero where it was not
among the top k); what the absent experts would add is left out, and
the router's sum runs over all k picks, held or not. The shared expert
is every chip's alike.

Departures from the published description, each also under `assumed`
in the configuration file: NoPE attention, the gated norm's order and
group size, dt without a clamp and the router in float32 follow the
family's modeling file (`transformers`
models/nemotron_h/modeling_nemotron_h.py), not the config; the
correction bias is drawn from the seed and its update not run; the
weights are random, the mixer's own drawn as Mamba-2 starts them.

Precision: each matmul's operands are the quantities written above,
taken at the configuration's stated matmul precision (the harness
sets `jax.default_matmul_precision` from its `precision` block), as in
the other references; the recurrence itself is elementwise float32.

The parameter tree has the names the program uses and flattens in
`jax.tree_util` order, which is the order of the program's flat
vector.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from fedbench.configs.afmoe import _attention, _rmsnorm

SCAN_BLOCK = 256


def _sizes(config):
    nh, P = config["mamba_num_heads"], config["mamba_head_dim"]
    G, N = config["n_groups"], config["ssm_state_size"]
    return nh, P, G, N, nh * P, nh * P + 2 * G * N


def shapes(config):
    H = config["hidden_size"]
    nh, _, _, _, I, conv = _sizes(config)
    q = config["num_attention_heads"] * config["head_dim"]
    kv = config["num_key_value_heads"] * config["head_dim"]
    F = config["moe_intermediate_size"]
    S = config["moe_shared_expert_intermediate_size"] \
        * config["n_shared_experts"]
    E = config["router_width"]
    n = config["n_routed_experts"]               # held here
    kinds = {
        "M": {"norm": (H,), "in_proj": (H, I + conv + nh),
              "conv_w": (config["conv_kernel"], conv), "conv_b": (conv,),
              "dt_bias": (nh,), "A_log": (nh,), "D": (nh,),
              "gate_norm": (I,), "out_proj": (I, H)},
        "E": {"norm": (H,), "router": (H, E), "expert_bias": (E,),
              "up": (n, H, F), "down": (n, F, H), "shared_up": (H, S),
              "shared_down": (S, H)},
        "*": {"norm": (H,), "wq": (H, q), "wk": (H, kv), "wv": (H, kv),
              "wo": (q, H)},
    }
    tree = {"embed": (config["vocab_size"], H), "final_norm": (H,),
            "head": (H, config["vocab_size"])}
    for i, kind in enumerate(config["hybrid_override_pattern"]):
        tree[f"layer_{i}"] = dict(kinds[kind])
    return tree


def init_params(config, seed: int):
    """The whole tree in one jitted call on the device: N(0,
    initializer_range) matrices, W_out over sqrt(the published layer
    count) (`rescale_prenorm_residual`), unit norm scales, the expert
    bias N(0, expert_bias_std); A_log = log(1..heads), D = 1, dt_bias
    the inverse softplus of a dt log-uniform in (time_step_min,
    time_step_max) and floored at time_step_floor, the convolution's
    weight and bias uniform in +-1/sqrt(conv_kernel)."""
    leaves, treedef = jax.tree_util.tree_flatten_with_path(
        shapes(config), is_leaf=lambda x: isinstance(x, tuple))
    std = config["initializer_range"]
    layers = config["published"].get("num_hidden_layers",
                                     config["num_hidden_layers"])
    lo, hi = config["time_step_min"], config["time_step_max"]
    bound = 1.0 / math.sqrt(config["conv_kernel"])

    def leaf(k, name, shape):
        if name == "expert_bias":
            return jax.random.normal(k, shape, jnp.float32) \
                * config["expert_bias_std"]
        if name == "A_log":
            return jnp.log(jnp.arange(1, shape[0] + 1, dtype=jnp.float32))
        if name == "dt_bias":
            dt = jnp.maximum(jnp.exp(jax.random.uniform(
                k, shape, jnp.float32, math.log(lo), math.log(hi))),
                config["time_step_floor"])
            return dt + jnp.log(-jnp.expm1(-dt))
        if name in ("conv_w", "conv_b"):
            return jax.random.uniform(k, shape, jnp.float32, -bound, bound)
        if len(shape) == 1:
            return jnp.ones(shape, jnp.float32)
        w = jax.random.normal(k, shape, jnp.float32) * std
        return w / math.sqrt(layers) if name == "out_proj" else w

    @jax.jit
    def make(key):
        return jax.tree_util.tree_unflatten(treedef, [
            leaf(jax.random.fold_in(key, i), path[-1].key, shape)
            for i, (path, shape) in enumerate(leaves)])

    return make(jax.random.PRNGKey(seed % (2 ** 31)))


def _conv(x, w, b):
    """x [L, C] -> b + sum_i w_i x_{t - K + 1 + i}, zero before t = 0."""
    K, L = w.shape[0], x.shape[0]
    xp = jnp.concatenate([jnp.zeros((K - 1, x.shape[1]), x.dtype), x])
    return b + sum(w[i] * xp[i:i + L] for i in range(K))


def _block_of(L: int) -> int:
    b = min(SCAN_BLOCK, L)
    while L % b:
        b -= 1
    return b


def _recurrence(x, dt, A, B, C):
    """The step form: x [L, heads, P], dt [L, heads], A [heads], B and
    C [L, groups, N] -> y [L, heads, P] (without D x)."""
    L, heads, P = x.shape
    G, N = B.shape[1:]
    per = heads // G

    def step(S, inp):
        xt, dtt, bt, ct = inp
        bh = jnp.repeat(bt, per, axis=0)             # [heads, N]
        ch = jnp.repeat(ct, per, axis=0)
        S = (jnp.exp(dtt * A)[:, None, None] * S
             + (dtt[:, None] * xt)[:, :, None] * bh[:, None, :])
        return S, (S * ch[:, None, :]).sum(-1)

    @jax.checkpoint
    def block(S, inp):
        return jax.lax.scan(step, S, inp)

    b = _block_of(L)
    _, y = jax.lax.scan(
        block, jnp.zeros((heads, P, N), jnp.float32),
        tuple(v.reshape(L // b, b, *v.shape[1:]) for v in (x, dt, B, C)))
    return y.reshape(L, heads, P)


def _mixer(config, p, h):
    L = h.shape[0]
    nh, P, G, N, I, conv = _sizes(config)
    zxbcdt = h @ p["in_proj"]
    z, xbc, dt = zxbcdt[:, :I], zxbcdt[:, I:I + conv], zxbcdt[:, I + conv:]
    xbc = jax.nn.silu(_conv(xbc, p["conv_w"], p["conv_b"]))
    x = xbc[:, :I].reshape(L, nh, P)
    B = xbc[:, I:I + G * N].reshape(L, G, N)
    C = xbc[:, I + G * N:].reshape(L, G, N)
    dt = jax.nn.softplus(dt + p["dt_bias"])
    y = _recurrence(x, dt, -jnp.exp(p["A_log"]), B, C) \
        + p["D"][:, None] * x
    y = (y.reshape(L, I) * jax.nn.silu(z)).reshape(L, G, I // G)
    y = y / jnp.sqrt((y * y).mean(-1, keepdims=True)
                     + config["layer_norm_epsilon"])
    return (y.reshape(L, I) * p["gate_norm"]) @ p["out_proj"]


def _relu2(h, up, down):
    return jnp.square(jax.nn.relu(h @ up)) @ down


def _route(config, p, h):
    """-> (idx [L, k] over all router_width experts, w [L, k])."""
    sc = jax.nn.sigmoid((h @ p["router"]).astype(jnp.float32))
    _, idx = jax.lax.top_k(sc + p["expert_bias"],
                           config["num_experts_per_tok"])
    w = jnp.take_along_axis(sc, idx, axis=-1)
    if config["norm_topk_prob"]:
        w = w / (w.sum(-1, keepdims=True) + 1e-20)
    return idx, w * config["routed_scaling_factor"]


def _experts(config, p, h):
    """The held routed experts' part of the expert layer (the shared
    expert not included)."""
    first, held = config["held_experts"]
    idx, w = _route(config, p, h)

    def add_expert(y, expert):
        e, up, down = expert
        # the weight the router gave expert e, 0 where it was not
        # among the top k
        weight = (w * (idx == first + e)).sum(-1)            # [L]
        return y + weight[:, None].astype(h.dtype) * _relu2(h, up, down), \
            None

    y, _ = jax.lax.scan(add_expert, jnp.zeros_like(h),
                        (jnp.arange(held), p["up"], p["down"]))
    return y


def _layer(config, kind, p, x):
    h = _rmsnorm(x, p["norm"], config["layer_norm_epsilon"])
    if kind == "M":
        out = _mixer(config, p, h)
    elif kind == "E":
        out = (_relu2(h, p["shared_up"], p["shared_down"])
               + _experts(config, p, h))
    else:
        L, Dh = h.shape[0], config["head_dim"]
        q = (h @ p["wq"]).reshape(L, config["num_attention_heads"], Dh)
        k = (h @ p["wk"]).reshape(L, config["num_key_value_heads"], Dh)
        v = (h @ p["wv"]).reshape(L, config["num_key_value_heads"], Dh)
        out = _attention(q, k, v, 0) @ p["wo"]
    return x + out


def sequence_logits(config, params, ids):
    """ids [L] -> logits [L, V] float32."""
    x = params["embed"][ids]
    for i, kind in enumerate(config["hybrid_override_pattern"]):
        # a layer's intermediates are recomputed in the backward pass
        x = jax.checkpoint(
            lambda p, x, kind=kind: _layer(config, kind, p, x))(
            params[f"layer_{i}"], x)
    h = _rmsnorm(x, params["final_norm"], config["layer_norm_epsilon"])
    return (h @ params["head"]).astype(jnp.float32)


def client_loss(config, params, data, mask):
    """One client's loss as the driver defines it: the next-token loss
    averaged over the real (non-pad) next tokens of its valid
    examples. `data[0]` is input_ids [B, C, L]; every candidate
    sequence counts (the cell feeds one)."""
    ids = data[0]
    B, C, L = ids.shape
    pad = config["pad_token_id"]
    total = jnp.float32(0.0)
    count = jnp.float32(0.0)
    for b in range(B):
        for c in range(C):
            z = sequence_logits(config, params, ids[b, c])[:-1]
            labels = ids[b, c, 1:]
            valid = (labels != pad).astype(jnp.float32) * mask[b]
            logp = z - jax.scipy.special.logsumexp(z, axis=-1,
                                                   keepdims=True)
            nll = -jnp.take_along_axis(logp, labels[:, None],
                                       axis=-1)[:, 0]
            total = total + (nll * valid).sum()
            count = count + valid.sum()
    return total / jnp.maximum(count, 1.0)


def cast_data(data, dtype):
    """The control's lower precision touches no integer input."""
    return data

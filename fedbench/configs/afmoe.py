"""Plain reference of AFMoE (Arcee's Trinity family, `model_type`
afmoe) as one chip's share of an expert-parallel deployment: weights
from a seed and one client's next-token loss over PersonaChat
sequences, in straightforward jax.numpy float32. No kernel, nothing
imported from the program.

The model's layer `i` here is the published layer `first_layer + i`:
`layer_types` (the published list, copied whole) says whether it is a
sliding-window layer, and it is dense where i < `num_dense_layers`.
One layer, on x [T, H]:

    h  = rmsnorm(x, g_in)
    q  = rmsnorm_head(h @ W_q, g_q)    per head, over head_dim
    k  = rmsnorm_head(h @ W_k, g_k)
    v  = h @ W_v
    q, k = rope(q), rope(k)            rotate-half, theta rope_theta,
                                       sliding layers only; a full layer
                                       has no position encoding
    s  = (q . k_group) / sqrt(Dh)      query head i reads key-value
                                       head i // (heads / kv heads)
    allowed(i, j) = j <= i and (not sliding or j > i - sliding_window)
    a  = softmax(where(allowed, s, -inf)) . v_group
    a  = a * sigmoid(h @ W_g)          the attention output gate
    x1 = x + rmsnorm(a @ W_o, g_post_attn)
    h2 = rmsnorm(x1, g_pre_mlp)
    dense:  f = (silu(h2 @ G) * (h2 @ U)) @ D       intermediate_size
    experts:
        sc  = sigmoid(h2 @ W_r)                    all router_width
        idx = top_k(sc + expert_bias)              selection only
        w   = sc[idx] / (sum(sc[idx]) + 1e-20) * route_scale
        f   = SwiGLU_shared(h2) + sum over held e in idx of
              w_e (silu(h2 @ G_e) * (h2 @ U_e)) @ D_e
    x2 = x1 + rmsnorm(f, g_post_mlp)

The embedding is scaled by sqrt(H) (`mup_enabled`); then the layers,
a final RMSNorm and an untied head. The chip holds experts
`held_experts` = [first, count): every held expert is applied to every
position and weighted by the weight the router gave it there (zero
where it was not among the top k); what the absent experts would add
is left out, and `route_norm`'s sum runs over all k picks, held or
not. The shared expert (`num_shared_experts` of width
`moe_intermediate_size`, as one SwiGLU of their total width) is every
chip's alike.

Departures from the published description, each also under
`assumed` in the configuration file: the per-head q/k norms, the
output gate, the sandwich norms and NoPE on the full layers follow the
family's modeling file (`transformers` models/afmoe/modeling_afmoe.py),
not the config; the expert bias is drawn from the seed (a trained
model's is learned) and its load-balancing update
(`load_balance_coeff`) is not run; the weights are random.

Precision: each matmul's operands are the quantities written above,
taken at the configuration's stated matmul precision (the harness
sets `jax.default_matmul_precision` from its `precision` block).
"default" is the precision the program states for itself: float32
parameters with the chip's default matmul (bfloat16 operands, float32
accumulation), so program and reference round the same operands and
what tells them apart is the arithmetic, not the precision; the
control (the program's own bfloat16 forward and backward) then rounds
what the reference does not, and the comparison's limits are set
between the two.

Attention is computed a block of queries at a time against all the
keys, each block recomputed in the backward pass, so that 8,192
positions fit; the mathematics is the dense masked softmax above.

The parameter tree has the names the program uses and flattens in
`jax.tree_util` order, which is the order of the program's flat
vector.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp

QUERY_BLOCK = 512


def shapes(config):
    H, Dh = config["hidden_size"], config["head_dim"]
    q = config["num_attention_heads"] * Dh
    kv = config["num_key_value_heads"] * Dh
    F = config["moe_intermediate_size"]
    S = F * config["num_shared_experts"]
    I = config["intermediate_size"]
    E = config["router_width"]
    n = config["num_experts"]                    # held here
    V = config["vocab_size"]
    attn = {"norm_in": (H,), "wq": (H, q), "wk": (H, kv), "wv": (H, kv),
            "wg": (H, q), "wo": (q, H), "q_norm": (Dh,), "k_norm": (Dh,),
            "norm_post_attn": (H,), "norm_pre_mlp": (H,),
            "norm_post_mlp": (H,)}
    dense = {"mlp_gate": (H, I), "mlp_up": (H, I), "mlp_down": (I, H)}
    moe = {"router": (H, E), "expert_bias": (E,), "gate": (n, H, F),
           "up": (n, H, F), "down": (n, F, H), "shared_gate": (H, S),
           "shared_up": (H, S), "shared_down": (S, H)}
    tree = {"embed": (V, H), "final_norm": (H,), "head": (H, V)}
    for i in range(config["num_hidden_layers"]):
        tree[f"layer_{i}"] = {
            **attn, **(dense if i < config["num_dense_layers"] else moe)}
    return tree


def init_params(config, seed: int):
    """The whole tree in one jitted call on the device: N(0,
    initializer_range) matrices, unit norm scales, the expert bias
    N(0, expert_bias_std)."""
    leaves, treedef = jax.tree_util.tree_flatten_with_path(
        shapes(config), is_leaf=lambda x: isinstance(x, tuple))
    std = config["initializer_range"]
    bias_std = config["expert_bias_std"]

    @jax.jit
    def make(key):
        out = []
        for i, (path, shape) in enumerate(leaves):
            k = jax.random.fold_in(key, i)
            if path[-1].key == "expert_bias":
                out.append(jax.random.normal(k, shape, jnp.float32)
                           * bias_std)
            elif len(shape) == 1:
                out.append(jnp.ones(shape, jnp.float32))
            else:
                out.append(jax.random.normal(k, shape, jnp.float32) * std)
        return jax.tree_util.tree_unflatten(treedef, out)

    return make(jax.random.PRNGKey(seed % (2 ** 31)))


def _rmsnorm(x, g, eps):
    x32 = x.astype(jnp.float32)
    var = (x32 * x32).mean(-1, keepdims=True)
    return (x32 / jnp.sqrt(var + eps) * g.astype(jnp.float32)) \
        .astype(x.dtype)


def _rope(x, theta):
    """x [L, heads, Dh]; rotate-half over the whole head."""
    L, _, Dh = x.shape
    inv = 1.0 / (theta ** (jnp.arange(0, Dh, 2, dtype=jnp.float32) / Dh))
    ang = jnp.arange(L, dtype=jnp.float32)[:, None] * inv[None, :]
    ang = jnp.concatenate([ang, ang], axis=-1)[:, None, :]
    x32 = x.astype(jnp.float32)
    rotated = jnp.concatenate([-x32[..., Dh // 2:], x32[..., :Dh // 2]],
                              axis=-1)
    return (x32 * jnp.cos(ang) + rotated * jnp.sin(ang)).astype(x.dtype)


def _attention(q, k, v, window):
    """q [L, Hq, Dh], k and v [L, Hkv, Dh] -> [L, Hq * Dh]."""
    L, Hq, Dh = q.shape
    Hkv = k.shape[1]
    block = min(QUERY_BLOCK, L)
    while L % block:
        block -= 1
    kg = k.transpose(1, 0, 2)                        # [Hkv, L, Dh]
    vg = v.transpose(1, 0, 2)
    k_pos = jnp.arange(L)

    @jax.checkpoint
    def rows(qb, start):
        # qb [block, Hq, Dh] -> [Hkv, G, block, Dh]
        qg = qb.transpose(1, 0, 2).reshape(Hkv, Hq // Hkv, block, Dh)
        s = jnp.einsum("hgqd,hkd->hgqk", qg, kg).astype(jnp.float32) \
            / math.sqrt(Dh)
        q_pos = start + jnp.arange(block)
        allowed = k_pos[None, :] <= q_pos[:, None]
        if window:
            allowed = allowed & (k_pos[None, :] > q_pos[:, None] - window)
        p = jax.nn.softmax(jnp.where(allowed, s, -jnp.inf), axis=-1)
        a = jnp.einsum("hgqk,hkd->hgqd", p.astype(vg.dtype), vg)
        return a.reshape(Hq, block, Dh).transpose(1, 0, 2)

    out = jax.lax.map(lambda xs: rows(*xs),
                      (q.reshape(L // block, block, Hq, Dh),
                       jnp.arange(0, L, block)))
    return out.reshape(L, Hq * Dh)


def _swiglu(h, gate, up, down):
    return (jax.nn.silu(h @ gate) * (h @ up)) @ down


def _route(config, p, h2):
    """-> (idx [L, k] over all router_width experts, w [L, k])."""
    sc = jax.nn.sigmoid((h2 @ p["router"]).astype(jnp.float32))
    _, idx = jax.lax.top_k(sc + p["expert_bias"],
                           config["num_experts_per_tok"])
    w = jnp.take_along_axis(sc, idx, axis=-1)
    if config["route_norm"]:
        w = w / (w.sum(-1, keepdims=True) + 1e-20)
    return idx, w * config["route_scale"]


def _experts(config, p, h2):
    """The held routed experts' part of the expert layer (the shared
    expert not included)."""
    first, held = config["held_experts"]
    idx, w = _route(config, p, h2)

    def add_expert(y, expert):
        e, gate, up, down = expert
        # the weight the router gave expert e, 0 where it was not
        # among the top k
        weight = (w * (idx == first + e)).sum(-1)            # [L]
        out = _swiglu(h2, gate, up, down)
        return y + weight[:, None].astype(h2.dtype) * out, None

    y, _ = jax.lax.scan(
        add_expert, jnp.zeros_like(h2),
        (jnp.arange(held), p["gate"], p["up"], p["down"]))
    return y


def _layer(config, p, x, sliding, dense):
    eps = config["rms_norm_eps"]
    L = x.shape[0]
    Dh = config["head_dim"]
    Hq, Hkv = config["num_attention_heads"], config["num_key_value_heads"]
    h = _rmsnorm(x, p["norm_in"], eps)
    q = _rmsnorm((h @ p["wq"]).reshape(L, Hq, Dh), p["q_norm"], eps)
    k = _rmsnorm((h @ p["wk"]).reshape(L, Hkv, Dh), p["k_norm"], eps)
    v = (h @ p["wv"]).reshape(L, Hkv, Dh)
    if sliding:
        q = _rope(q, config["rope_theta"])
        k = _rope(k, config["rope_theta"])
    a = _attention(q, k, v, config["sliding_window"] if sliding else 0)
    a = a * jax.nn.sigmoid(h @ p["wg"])
    x1 = x + _rmsnorm(a @ p["wo"], p["norm_post_attn"], eps)
    h2 = _rmsnorm(x1, p["norm_pre_mlp"], eps)
    if dense:
        f = _swiglu(h2, p["mlp_gate"], p["mlp_up"], p["mlp_down"])
    else:
        f = (_swiglu(h2, p["shared_gate"], p["shared_up"],
                     p["shared_down"]) + _experts(config, p, h2))
    return x1 + _rmsnorm(f, p["norm_post_mlp"], eps)


def sequence_logits(config, params, ids):
    """ids [L] -> logits [L, V] float32."""
    x = params["embed"][ids]
    if config["mup_enabled"]:
        x = x * math.sqrt(config["hidden_size"])
    first = config.get("first_layer", 0)
    for i in range(config["num_hidden_layers"]):
        sliding = (config["layer_types"][first + i]
                   == "sliding_attention")
        # a layer's intermediates are recomputed in the backward pass:
        # eight experts over every position are 0.8 GB a layer
        x = jax.checkpoint(
            lambda p, x, sliding=sliding, dense=(
                i < config["num_dense_layers"]): _layer(
                config, p, x, sliding, dense))(params[f"layer_{i}"], x)
    h = _rmsnorm(x, params["final_norm"], config["rms_norm_eps"])
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

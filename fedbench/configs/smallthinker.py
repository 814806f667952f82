"""Plain reference of SmallThinker (PowerInfer, 2025) as one chip's
share of an expert-parallel deployment: weights from a seed and one
client's next-token loss over PersonaChat sequences, in
straightforward jax.numpy float32. No kernel, nothing imported from
the program.

One layer, on x [T, H] (`rope_layout[l]` and
`sliding_window_layout[l]` say which kind layer l is):

    r  = x @ W_r                      router logits from the layer's
                                      input, before the norm and
                                      before attention
    h  = rmsnorm(x, g1)
    q, k, v = h @ W_q, h @ W_k, h @ W_v
    q, k = rope(q), rope(k)           rotate-half; only where the
                                      layout has 1
    s  = (q . k_group) / sqrt(Dh)     query head i reads key-value
                                      head i // (heads / kv heads)
    allowed(i, j) = j <= i and (not window or j > i - window_size)
    a  = softmax(where(allowed, s, -inf)) . v_group
    x1 = x + a @ W_o
    h2 = rmsnorm(x1, g2)
    idx = top_k(r);  p = softmax(r[idx])
    y  = sum over held e in idx of p_e (relu(h2 G_e) * (h2 U_e)) D_e
    x2 = x1 + y

then a final RMSNorm and an untied head. The chip holds experts
`held_experts` = [first, count): every held expert is applied to
every position and weighted by the probability the router gave it
there (zero where it was not among the top k); what the absent
experts would add is left out. Each matmul's operands are the
quantities written above (scores are scaled after the product), so
at the chip's default precision they are rounded as the program's
are.

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
    H, F = config["hidden_size"], config["moe_ffn_hidden_size"]
    Dh = config["head_dim"]
    q = config["num_attention_heads"] * Dh
    kv = config["num_key_value_heads"] * Dh
    E = config["router_width"]
    n = config["moe_num_primary_experts"]        # held here
    V = config["vocab_size"]
    layer = {"router": (H, E), "norm1": (H,), "wq": (H, q),
             "wk": (H, kv), "wv": (H, kv), "wo": (q, H), "norm2": (H,),
             "gate": (n, H, F), "up": (n, H, F), "down": (n, F, H)}
    tree = {"embed": (V, H), "final_norm": (H,), "head": (H, V)}
    for i in range(config["num_hidden_layers"]):
        tree[f"layer_{i}"] = dict(layer)
    return tree


def init_params(config, seed: int):
    """The whole tree in one jitted call on the device: N(0,
    initializer_range) matrices, unit norm scales."""
    leaves, treedef = jax.tree_util.tree_flatten(
        shapes(config), is_leaf=lambda x: isinstance(x, tuple))
    std = config["initializer_range"]

    @jax.jit
    def make(key):
        return jax.tree_util.tree_unflatten(treedef, [
            jnp.ones(shape, jnp.float32) if len(shape) == 1
            else jax.random.normal(jax.random.fold_in(key, i), shape,
                                   jnp.float32) * std
            for i, shape in enumerate(leaves)])

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


def _attention(config, q, k, v, window):
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


def _experts(config, p, h2, r):
    k = config["moe_num_active_primary_experts"]
    first, held = config["held_experts"]
    top, idx = jax.lax.top_k(r.astype(jnp.float32), k)
    prob = jax.nn.softmax(top, axis=-1)

    def add_expert(y, expert):
        e, gate, up, down = expert
        # the probability the router gave expert e, 0 where it was
        # not among the top k
        weight = (prob * (idx == first + e)).sum(-1)          # [T]
        act = jax.nn.relu(h2 @ gate) * (h2 @ up)
        return y + weight[:, None].astype(h2.dtype) * (act @ down), None

    y, _ = jax.lax.scan(
        add_expert, jnp.zeros_like(h2),
        (jnp.arange(held), p["gate"], p["up"], p["down"]))
    return y


def _layer(config, p, x, rope, window):
    eps = config["rms_norm_eps"]
    L = x.shape[0]
    Dh = config["head_dim"]
    r = x @ p["router"]
    h = _rmsnorm(x, p["norm1"], eps)
    q = (h @ p["wq"]).reshape(L, config["num_attention_heads"], Dh)
    k = (h @ p["wk"]).reshape(L, config["num_key_value_heads"], Dh)
    v = (h @ p["wv"]).reshape(L, config["num_key_value_heads"], Dh)
    if rope:
        q = _rope(q, config["rope_theta"])
        k = _rope(k, config["rope_theta"])
    a = _attention(config, q, k, v,
                   config["sliding_window_size"] if window else 0)
    x1 = x + a @ p["wo"]
    return x1 + _experts(config, p, _rmsnorm(x1, p["norm2"], eps), r)


def sequence_logits(config, params, ids):
    """ids [L] -> logits [L, V] float32."""
    x = params["embed"][ids]
    for i in range(config["num_hidden_layers"]):
        # a layer's intermediates are recomputed in the backward pass:
        # sixteen experts over every position are 1.2 GB a layer
        x = jax.checkpoint(
            lambda p, x, i=i: _layer(
                config, p, x, config["rope_layout"][i],
                config["sliding_window_layout"][i]))(
            params[f"layer_{i}"], x)
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

"""Plain reference of GPT-2 with double heads (language modelling and
candidate choice) on PersonaChat candidates: weights from a seed and
one client's loss, in straightforward jax.numpy. Attention is the
full softmax(QK^T / sqrt(d) + causal) V, no kernel, no tiling.

Nothing of the program is imported. The parameter tree has the names
the program's Flax module gives its own and flattens in
`jax.tree_util` order, which is the order of the program's flat
vector.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

IGNORE_INDEX = -1


def _block_shapes(E):
    return {
        "ln_1": {"scale": (E,), "bias": (E,)},
        "attn": {"c_attn": {"kernel": (E, 3 * E), "bias": (3 * E,)},
                 "c_proj": {"kernel": (E, E), "bias": (E,)}},
        "ln_2": {"scale": (E,), "bias": (E,)},
        "mlp": {"c_fc": {"kernel": (E, 4 * E), "bias": (4 * E,)},
                "c_proj": {"kernel": (4 * E, E), "bias": (E,)}},
    }


def shapes(config):
    E = config["n_embd"]
    tr = {"wte": {"embedding": (config["vocab_size"], E)},
          "wpe": {"embedding": (config["n_positions"], E)},
          "ln_f": {"scale": (E,), "bias": (E,)}}
    for i in range(config["n_layer"]):
        tr[f"h_{i}"] = _block_shapes(E)
    return {"params": {"transformer": tr,
                       "mc_head": {"kernel": (E, 1), "bias": (1,)}}}


def init_params(config, seed: int):
    """The whole parameter tree in one jitted call on the device:
    N(0, initializer_range) kernels and embeddings, zero biases, unit
    LayerNorm scales."""
    tree = shapes(config)
    leaves, treedef = jax.tree_util.tree_flatten_with_path(
        tree, is_leaf=lambda x: isinstance(x, tuple))
    std = config["initializer_range"]

    @jax.jit
    def make(key):
        out = []
        for i, (path, shape) in enumerate(leaves):
            name = str(getattr(path[-1], "key", path[-1]))
            if name == "bias":
                out.append(jnp.zeros(shape, jnp.float32))
            elif name == "scale":
                out.append(jnp.ones(shape, jnp.float32))
            else:
                out.append(jax.random.normal(
                    jax.random.fold_in(key, i), shape, jnp.float32) * std)
        return jax.tree_util.tree_unflatten(treedef, out)

    return make(jax.random.PRNGKey(seed % (2 ** 31)))


def _layer_norm(x, p, eps):
    mean = x.mean(-1, keepdims=True)
    var = ((x - mean) ** 2).mean(-1, keepdims=True)
    return (x - mean) / jnp.sqrt(var + eps) * p["scale"] + p["bias"]


def _dense(x, p):
    return x @ p["kernel"] + p["bias"]


def _attention(h, p, n_head):
    N, L, E = h.shape
    hd = E // n_head
    q, k, v = jnp.split(_dense(h, p["c_attn"]), 3, axis=-1)

    def heads(x):
        return x.reshape(N, L, n_head, hd).transpose(0, 2, 1, 3)

    q, k, v = heads(q), heads(k), heads(v)
    att = jnp.einsum("nhqd,nhkd->nhqk", q, k) / jnp.sqrt(jnp.float32(hd))
    causal = jnp.tril(jnp.ones((L, L), bool))
    att = jnp.where(causal[None, None], att.astype(jnp.float32), -1e9)
    att = jax.nn.softmax(att, axis=-1).astype(v.dtype)
    out = jnp.einsum("nhqk,nhkd->nhqd", att, v)
    return _dense(out.transpose(0, 2, 1, 3).reshape(N, L, E), p["c_proj"])


def _gelu(x):
    return 0.5 * x * (1.0 + jnp.tanh(
        0.7978845608028654 * (x + 0.044715 * x ** 3)))


def hidden_and_logits(config, params, input_ids, token_type_ids):
    """[N, L] ids -> (hidden [N, L, E], LM logits [N, L, V] through
    the tied embedding)."""
    tr = params["params"]["transformer"]
    eps = config["layer_norm_epsilon"]
    wte = tr["wte"]["embedding"]
    L = input_ids.shape[-1]
    h = wte[input_ids] + tr["wpe"]["embedding"][:L] + wte[token_type_ids]
    for i in range(config["n_layer"]):
        b = tr[f"h_{i}"]
        h = h + _attention(_layer_norm(h, b["ln_1"], eps), b["attn"],
                           config["n_head"])
        m = _layer_norm(h, b["ln_2"], eps)
        h = h + _dense(_gelu(_dense(m, b["mlp"]["c_fc"])),
                       b["mlp"]["c_proj"])
    h = _layer_norm(h, tr["ln_f"], eps)
    return h, h @ wte.T


def client_loss(config, params, data, mask):
    """One client's loss as the driver defines it: lm_coef times the
    next-token loss averaged over the labelled tokens of its valid
    examples, plus mc_coef times the candidate-choice loss averaged
    over its valid examples. `data` = (input_ids [B, C, L],
    mc_token_ids [B, C], lm_labels [B, C, L], mc_labels [B],
    token_type_ids [B, C, L])."""
    input_ids, mc_token_ids, lm_labels, mc_labels, token_type_ids = data
    B, C, L = input_ids.shape
    h, logits = hidden_and_logits(
        config, params, input_ids.reshape(B * C, L),
        token_type_ids.reshape(B * C, L))
    logits = logits.reshape(B, C, L, -1).astype(jnp.float32)
    labels = lm_labels[..., 1:]
    valid = ((labels != IGNORE_INDEX) * mask[:, None, None]) \
        .astype(jnp.float32)
    z = logits[..., :-1, :]
    logp = z - jax.scipy.special.logsumexp(z, axis=-1, keepdims=True)
    nll = -jnp.take_along_axis(
        logp, jnp.maximum(labels, 0)[..., None], axis=-1)[..., 0]
    lm = (nll * valid).sum() / jnp.maximum(valid.sum(), 1.0)
    pos = mc_token_ids.reshape(B * C).astype(jnp.int32)
    summary = jnp.take_along_axis(h, pos[:, None, None], axis=1)[:, 0]
    mc_head = params["params"]["mc_head"]
    mc_logits = _dense(summary, mc_head)[:, 0].reshape(B, C) \
        .astype(jnp.float32)
    mc_logp = mc_logits - jax.scipy.special.logsumexp(
        mc_logits, axis=-1, keepdims=True)
    mc_nll = -jnp.take_along_axis(
        mc_logp, mc_labels[:, None].astype(jnp.int32), axis=1)[:, 0]
    mc = (mc_nll * mask).sum() / jnp.maximum(mask.sum(), 1.0)
    return config["lm_coef"] * lm + config["mc_coef"] * mc


def cast_data(data, dtype):
    """The control's lower precision touches no integer input."""
    return data

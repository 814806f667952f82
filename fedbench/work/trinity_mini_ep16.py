"""Operations that Trinity's (AFMoE's) forward and backward passes
require on this chip's share, from shapes alone, and what its expert
layer needs for its roofline.

Per position, forward, one layer (H hidden, q = heads * head_dim,
kv = kv_heads * head_dim, I the dense width, F the expert width, S the
shared experts' width, E router width, k experts a token, n held of E):

    projections   2 * H * (3 q + 2 kv)            q, k, v, o and the
                  attention's output gate
    attention     2 * 2 * q * pairs(L) / L        scores and the
                  weighted sum over the allowed (query, key) pairs
                  only: L (L + 1) / 2 in a full layer,
                  sum_i min(i + 1, window) in a window layer
    dense layer   3 * 2 * H * I                   its SwiGLU
    expert layer  router 2 * H * E, shared expert 3 * 2 * H * S, and
                  k * (n / E) * 3 * 2 * H * F     the picks that fall
                  on held experts, as a uniform router sends them (the
                  counters in the round's telemetry say what it sent)

plus the head, 2 * H * V. Embedding look-ups, norms, RoPE, the gate's
sigmoid, softmax, top-k and the sort are not counted. Backward needs
twice forward; recomputed operations (each layer is rematerialised) do
not count. An example is `num_candidates` sequences padded to the
corpus's longest, and every padded position is computed, so all of
them count.
"""
from __future__ import annotations


def allowed_pairs(L: int, window: int) -> int:
    if not window or window >= L:
        return L * (L + 1) // 2
    return window * (window + 1) // 2 + (L - window) * window


def sliding(config: dict, i: int) -> bool:
    return (config["layer_types"][config.get("first_layer", 0) + i]
            == "sliding_attention")


def layer_flops_per_position(config: dict, L: int, i: int) -> dict:
    H, Dh = config["hidden_size"], config["head_dim"]
    q = config["num_attention_heads"] * Dh
    kv = config["num_key_value_heads"] * Dh
    pairs = allowed_pairs(L, config["sliding_window"] if sliding(config, i)
                          else 0)
    out = {"projections": 2 * H * (3 * q + 2 * kv),
           "attention": 4 * q * pairs / L}
    if i < config["num_dense_layers"]:
        out["dense"] = 6 * H * config["intermediate_size"]
        return out
    F, E = config["moe_intermediate_size"], config["router_width"]
    k, n = config["num_experts_per_tok"], config["held_experts"][1]
    out.update(router=2 * H * E,
               shared=6 * H * F * config["num_shared_experts"],
               experts=k * n / E * 6 * H * F)
    return out


def forward_flops_per_position(config: dict, L: int) -> float:
    total = 2 * config["hidden_size"] * config["vocab_size"]
    for i in range(config["num_hidden_layers"]):
        total += sum(layer_flops_per_position(config, L, i).values())
    return total


def train_flops_per_example(config: dict, traffic: dict) -> float:
    L = traffic["corpus"]["max_tokens"]
    return (3 * forward_flops_per_position(config, L)
            * config["num_candidates"] * L)


def expert_ffn_work(config: dict, positions: int,
                    dtype_bytes: int = 4) -> dict:
    """What the scope `expert_ffn` has to do for `positions` positions
    of one round, forward and backward, in all the expert layers:
    FLOPs of the three grouped products over the routed picks on held
    experts (times three: forward, and the two products of the backward
    pass; the shared expert runs under its own scope), and the bytes it
    has to move at least once: each held expert's three matrices read
    forward and backward and their gradients written, the picked rows
    read and written at the expert layer's input and output, forward
    and backward."""
    H, F = config["hidden_size"], config["moe_intermediate_size"]
    layers = config["num_hidden_layers"] - config["num_dense_layers"]
    n = config["held_experts"][1]
    picks = positions * config["num_experts_per_tok"] \
        * n / config["router_width"]
    flops = layers * 3 * picks * 6 * H * F
    weights = n * 3 * H * F * dtype_bytes
    rows = picks * H * dtype_bytes
    return {"flops": flops,
            "bytes": layers * (3 * weights + 4 * rows)}

"""Operations that SmallThinker's forward and backward passes require
on this chip's share, from shapes alone, and what each of its named
layers needs for its roofline.

Per position, forward, one layer (H hidden, q = heads * head_dim,
kv = kv_heads * head_dim, F expert width, E router width, k experts a
token, n held of E):

    projections   2 * H * (2 q + 2 kv)            q, k, v and o
    router        2 * H * E
    attention     2 * 2 * q * pairs(L) / L        scores and the
                  weighted sum over the allowed (query, key) pairs
                  only: L (L + 1) / 2 in a full layer,
                  sum_i min(i + 1, window) in a window layer
    experts       k * (n / E) * 3 * 2 * H * F     the picks that fall
                  on held experts, as a uniform router sends them (the
                  counters in the round's telemetry say what it sent)

plus the head, 2 * H * V. Embedding look-ups, norms, RoPE, softmax,
top-k and the sort are not counted. Backward needs twice forward;
recomputed operations (each layer is rematerialised) do not count. An
example is `num_candidates` sequences padded to the corpus's longest,
and every padded position is computed, so all of them count.
"""
from __future__ import annotations


def allowed_pairs(L: int, window: int) -> int:
    if not window or window >= L:
        return L * (L + 1) // 2
    return window * (window + 1) // 2 + (L - window) * window


def layer_flops_per_position(config: dict, L: int, window: bool) -> dict:
    H, Dh = config["hidden_size"], config["head_dim"]
    q = config["num_attention_heads"] * Dh
    kv = config["num_key_value_heads"] * Dh
    F, E = config["moe_ffn_hidden_size"], config["router_width"]
    k = config["moe_num_active_primary_experts"]
    n = config["held_experts"][1]
    pairs = allowed_pairs(L, config["sliding_window_size"] if window
                          else 0)
    return {"projections": 2 * H * (2 * q + 2 * kv),
            "router": 2 * H * E,
            "attention": 4 * q * pairs / L,
            "experts": k * n / E * 6 * H * F}


def forward_flops_per_position(config: dict, L: int) -> float:
    total = 2 * config["hidden_size"] * config["vocab_size"]
    for i in range(config["num_hidden_layers"]):
        total += sum(layer_flops_per_position(
            config, L, bool(config["sliding_window_layout"][i])).values())
    return total


def train_flops_per_example(config: dict, traffic: dict) -> float:
    L = traffic["corpus"]["max_tokens"]
    return (3 * forward_flops_per_position(config, L)
            * config["num_candidates"] * L)


def expert_ffn_work(config: dict, positions: int,
                    dtype_bytes: int = 4) -> dict:
    """What the scope `expert_ffn` has to do for `positions` positions
    of one round, forward and backward, in all the expert layers:
    FLOPs of the three grouped products over the picks on held experts
    (times three: forward, and the two products of the backward pass),
    and the bytes it has to move at least once: each held expert's
    three matrices read forward and backward and their gradients
    written, the picked rows read and written at the expert layer's
    input and output, forward and backward."""
    H, F = config["hidden_size"], config["moe_ffn_hidden_size"]
    layers = config["num_hidden_layers"]
    n = config["held_experts"][1]
    picks = positions * config["moe_num_active_primary_experts"] \
        * n / config["router_width"]
    flops = layers * 3 * picks * 6 * H * F
    weights = n * 3 * H * F * dtype_bytes
    rows = picks * H * dtype_bytes
    return {"flops": flops,
            "bytes": layers * (3 * weights + 4 * rows)}

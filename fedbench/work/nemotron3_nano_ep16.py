"""Operations that Nemotron-H's forward and backward passes require on
this chip's share, from shapes alone, and what its expert layer and
its state-space scan need for their rooflines.

Per position, forward, one layer by its kind (H hidden; q = heads *
head_dim, kv = kv_heads * head_dim; I = mamba heads * mamba head_dim,
X = I + 2 groups * state the convolution's width, nh mamba heads; F
the expert width, S the shared expert's, E router width, k experts a
token, n held of E):

    M  projections   2 * H * (I + X + nh) + 2 * I * H     W_in, W_out
       scan          2 * Q * (groups * state + I)         C B^T, and its
                     + 4 * I * state                      weighted sum
                     over a chunk of Q positions (the whole square,
                     as computed); each chunk's end state and what
                     each position reads of the entering one
    E  router 2 * H * E, shared expert 2 * 2 * H * S, and
       k * (n / E) * 2 * 2 * H * F        the picks that fall on held
       experts, as a uniform router sends them (the counters in the
       round's telemetry say what it sent); two products an expert
    *  projections   2 * H * (2 q + 2 kv)                 q, k, v, o
       attention     2 * 2 * q * (L + 1) / 2              the causal
                     (query, key) pairs only

plus the head, 2 * H * V. Embedding look-ups, norms, the convolution,
softplus, the decays' exp, the gate's silu, the recurrence over
chunks, top-k and the sort are not counted. Backward needs twice
forward; recomputed operations (each layer is rematerialised) do not
count. An example is `num_candidates` sequences padded to the
corpus's longest, and every padded position is computed, so all of
them count.
"""
from __future__ import annotations


def _ssm_sizes(config):
    nh, P = config["mamba_num_heads"], config["mamba_head_dim"]
    G, N = config["n_groups"], config["ssm_state_size"]
    return nh, nh * P, nh * P + 2 * G * N, G * N, N


def kind(config: dict, i: int) -> str:
    return config["hybrid_override_pattern"][i]


def ssm_scan_flops_per_position(config: dict) -> int:
    _, I, _, GN, N = _ssm_sizes(config)
    return 2 * config["chunk_size"] * (GN + I) + 4 * I * N


def layer_flops_per_position(config: dict, L: int, i: int) -> dict:
    H = config["hidden_size"]
    if kind(config, i) == "M":
        nh, I, X, _, _ = _ssm_sizes(config)
        return {"projections": 2 * H * (I + X + nh) + 2 * I * H,
                "scan": ssm_scan_flops_per_position(config)}
    if kind(config, i) == "E":
        F, E = config["moe_intermediate_size"], config["router_width"]
        S = config["moe_shared_expert_intermediate_size"] \
            * config["n_shared_experts"]
        k, n = config["num_experts_per_tok"], config["held_experts"][1]
        return {"router": 2 * H * E, "shared": 4 * H * S,
                "experts": k * n / E * 4 * H * F}
    q = config["num_attention_heads"] * config["head_dim"]
    kv = config["num_key_value_heads"] * config["head_dim"]
    return {"projections": 2 * H * (2 * q + 2 * kv),
            "attention": 4 * q * (L + 1) / 2}


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
    FLOPs of the two grouped products over the routed picks on held
    experts (times three: forward, and the two products of the backward
    pass; the shared expert runs under its own scope), and the bytes it
    has to move at least once: each held expert's two matrices read
    forward and backward and their gradients written, the picked rows
    read and written at the expert layer's input and output, forward
    and backward."""
    H, F = config["hidden_size"], config["moe_intermediate_size"]
    layers = config["hybrid_override_pattern"].count("E")
    n = config["held_experts"][1]
    picks = positions * config["num_experts_per_tok"] \
        * n / config["router_width"]
    flops = layers * 3 * picks * 4 * H * F
    weights = n * 2 * H * F * dtype_bytes
    rows = picks * H * dtype_bytes
    return {"flops": flops,
            "bytes": layers * (3 * weights + 4 * rows)}


def ssm_scan_work(config: dict, positions: int,
                  dtype_bytes: int = 4) -> dict:
    """What the scope `ssm_scan` has to do for `positions` positions of
    one round, forward and backward, in all the mixer layers: FLOPs of
    the chunked scan's products (times three, as a product's backward
    is two more), and the bytes it has to move at least once: forward,
    W_in's output [z | xBC | dt] read and the normed y written;
    backward, that output and y's gradient read and the output's
    gradient written."""
    layers = config["hybrid_override_pattern"].count("M")
    nh, I, X, _, _ = _ssm_sizes(config)
    width = I + X + nh
    return {"flops": layers * 3 * positions
            * ssm_scan_flops_per_position(config),
            "bytes": layers * positions * dtype_bytes
            * ((width + I) + (width + I + width))}

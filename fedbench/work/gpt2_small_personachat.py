"""Operations that GPT-2's forward and backward passes require, from
shapes alone, and what one flash-attention forward call needs.

Per position, forward: each block's four projections are
2 * (E*3E + E*E + E*4E + 4E*E) = 24 E^2; causal attention over a
sequence of L positions averages 2 * 2 * E * (L + 1) / 2 per position
(scores and the weighted sum, lower triangle only); the tied LM head
is 2 * E * V. Embedding look-ups, LayerNorm, GELU, softmax and the
one-row MC head are not counted. Backward needs twice forward. An
example is `num_candidates` sequences padded to the corpus length L,
and every padded position is computed, so all of them count: that is
the work the batch requires at the shapes the driver feeds.
"""
from __future__ import annotations


def forward_flops_per_position(config: dict, L: int) -> float:
    E, V = config["n_embd"], config["vocab_size"]
    per_block = 24 * E * E + 2 * E * (L + 1)
    return config["n_layer"] * per_block + 2 * E * V


def train_flops_per_example(config: dict, traffic: dict) -> float:
    """One example is `num_candidates` sequences padded to the
    corpus's longest, which the traffic file states."""
    L = traffic["corpus"]["max_tokens"]
    positions = config["num_candidates"] * L
    return 3 * forward_flops_per_position(config, L) * positions


def flash_forward_work(B: int, H: int, L: int, Dh: int,
                       dtype_bytes: int = 4) -> dict:
    """One causal flash-attention forward over [B, H, L, Dh]: FLOPs of
    QK^T and PV on the lower triangle (diagonal included), and the
    bytes it has to move: read Q, K, V, write O and the row
    log-sum-exp."""
    pairs = L * (L + 1) // 2
    flops = B * H * pairs * Dh * 2 * 2
    bytes_moved = B * H * (4 * L * Dh * dtype_bytes + L * 4)
    return {"flops": flops, "bytes": bytes_moved}

"""Layer names for the device trace: `jax.named_scope` around the
round program's layer boundaries.

A scope is metadata. It extends the op-name path XLA keeps per
instruction (`jit(round_step)/.../fed_fwdbwd/conv_general_dilated`),
which the profiler's `.xplane.pb` carries per op as the `tf_op` stat;
the traced jaxpr, the compiled program, its donation and its results
are the same with or without it (tests/test_scopes.py compares three
rounds' ServerState bit for bit). The persistent compile cache is
keyed with the metadata included (utils/cache.py), so a program
cached before a scope moved is never handed back under old names.

Every name carries SCOPE_PREFIX so that no JAX primitive at the end
of a path (`select_n`, `gather`, `scatter-add`) reads as a layer. A
reader gives an op to the OUTERMOST of these names on its path,
matched as a whole component also inside the wrappers that
transformations add (`jvp(fed_fwdbwd)`, `transpose(jvp(...))`,
`vmap(...)`): the re-sketch inside `server_state` calls the same
`CSVec.encode` as a client's `encode` and stays `server_state`.
XLA fuses across scope borders and names a fusion after one of its
ops, so the split is exact to a fusion, not to an instruction.

    fwdbwd            forward, backward, microbatch scan, weight
                      decay, gradient masking (client.forward_grad,
                      fused_shard_grads, fedavg_step's local steps)
    residual          local momentum, local error, per-client top-k
                      and masking (client.local_step's tail)
    encode            sketching a client or the shard's client sum,
                      the wire dtype round trip
    aggregate         the shard's local sum, the psums, the
                      post-aggregation hook, the divide
    select            estimates from the table and top-k or sampled
                      threshold (server._sketched, _true_topk)
    server_state      the rest of the server update: momentum and
                      error, the re-sketch, zeroing what was sent,
                      the alive gate, the weight update, the
                      cohort-row merge
    telemetry         telemetry.metrics.round_vector
    gather_cohort, scatter_back, pack_change_bits
                      their bodies, findable inside round_full and
                      the scanned program too

Inside `fwdbwd` a model may name its own layers (they are INNER names:
the rule above still gives their ops to `fwdbwd`, a reader that asks
for one of them by name finds it anywhere on the path):

    attention_full, attention_window
                      RoPE and the blockwise attention core of a
                      full-attention and of a sliding-window layer
    moe_route         the router's matmul, top-k, the sort of the picks
                      by expert, the carried rows' gather and masks
    expert_ffn        the grouped expert products, the return to
                      position order and the weighted combine
    shared_expert     an expert every position goes through beside
                      the routed ones (models/afmoe.py)
    attn_proj         the q, k, v input projections, the output gate's
                      projection and its product (models/afmoe.py),
                      the output projection
    dense_mlp         a dense layer's SwiGLU (models/afmoe.py)
    norm              the residual stream's RMSNorms with the residual
                      adds they wrap, and the final norm
    head_loss         the head product, the softmax and the label pick
                      in blocks, and their sums (decoder.next_token_nll)
    ssm_proj          a Mamba-2 mixer's input and output projections
                      (models/nemotron_h.py)
    ssm_scan          the rest of the mixer: the causal convolution,
                      dt, the chunked scan (ops/ssd.py), the D skip
                      and the gated group norm
    unravel           the flat vector's split into the model's leaves
                      and their cast (client.make_flat_grad_fn,
                      make_flat_loss_fn); its transpose assembles the
                      flat gradient

Each is laid at its call sites, never inside a helper another layer
calls too (`rmsnorm` also normalises q and k under `attention_*`), so
that no op's path carries two inner names.
"""
from __future__ import annotations

import jax

SCOPE_PREFIX = "fed_"
SCOPES = ("fwdbwd", "residual", "encode", "aggregate", "select",
          "server_state", "telemetry", "gather_cohort", "scatter_back",
          "pack_change_bits",
          "attention_full", "attention_window", "moe_route", "expert_ffn",
          "shared_expert", "attn_proj", "dense_mlp", "norm", "head_loss",
          "unravel", "ssm_proj", "ssm_scan")


def scope(name: str):
    """Context manager: ops traced inside belong to layer `name`."""
    if name not in SCOPES:
        raise ValueError(f"unknown layer scope {name!r}; one of {SCOPES}")
    return jax.named_scope(SCOPE_PREFIX + name)

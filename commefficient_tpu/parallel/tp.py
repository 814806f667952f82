"""Tensor parallelism over the mesh's `model` axis.

The reference's only parallelism is one worker process per GPU
(fed_aggregator.py:143-158) — model parallelism does not exist there.
Here it is a first-class mesh axis: `make_client_model_mesh` lays
devices out as (clients, model) with `model` innermost so its
collectives ride the fastest ICI links, the round engine runs manual
(`shard_map`) over `clients` only, and GSPMD partitions each client's
forward/backward over `model`, steered by the sharding constraints
below. No communication code changes per model: XLA inserts the
all-reduces where the Megatron-style kernel layout requires them.

Layout (the standard two-matmul sandwich per block):
  * column-parallel first matmuls — QKV projection [E, 3E] and MLP
    up-projection [E, 4E] sharded P(None, MODEL_AXIS), their biases
    P(MODEL_AXIS) — each shard computes a slice of heads / hidden units;
  * row-parallel second matmuls — attention/MLP output projections
    sharded P(MODEL_AXIS, None) — partial products all-reduced by GSPMD;
  * the (tied) token embedding [V, E] sharded over the vocab axis
    P(MODEL_AXIS, None); `attend` logits are likewise reduced by GSPMD.

Usage (workload level — the engine is workload-agnostic):
    params = constrain_params(params, mesh, GPT2_TP_RULES)  # in loss_fn
"""
from __future__ import annotations

import re
from typing import Callable, Sequence, Tuple

import jax
from jax.sharding import Mesh, NamedSharding
from jax.sharding import PartitionSpec as P

from commefficient_tpu.analysis.domains import MODEL_AXIS

# (path regex, spec) — first match wins; unmatched leaves replicate.
# Paths are "/"-joined pytree key paths, e.g.
# "params/transformer/h_3/attn/c_attn/kernel".
GPT2_TP_RULES: Sequence[Tuple[str, P]] = (
    (r"attn/c_attn/kernel$", P(None, MODEL_AXIS)),
    (r"attn/c_attn/bias$", P(MODEL_AXIS)),
    (r"attn/c_proj/kernel$", P(MODEL_AXIS, None)),
    (r"mlp/c_fc/kernel$", P(None, MODEL_AXIS)),
    (r"mlp/c_fc/bias$", P(MODEL_AXIS)),
    (r"mlp/c_proj/kernel$", P(MODEL_AXIS, None)),
    (r"wte/embedding$", P(MODEL_AXIS, None)),
)


def _path_str(path) -> str:
    out = []
    for p in path:
        out.append(str(getattr(p, "key", getattr(p, "idx", p))))
    return "/".join(out)


def constrain_params(params, mesh: Mesh,
                     rules: Sequence[Tuple[str, P]] = GPT2_TP_RULES):
    """Apply with_sharding_constraint to every rule-matched leaf.
    Call inside the traced loss (the params pytree there is rebuilt
    from the flat [D] vector each step, so constraints must be
    re-stated per trace). No-op outside rule matches."""
    compiled = [(re.compile(rx), spec) for rx, spec in rules]
    # constraints must be expressed against the TRACE's mesh: inside
    # the engine's partially-manual shard_map the clients axis is
    # Manual (and params arrive clients-varying via pcast), which the
    # concrete mesh — all-Auto axis types — cannot describe
    am = jax.sharding.get_abstract_mesh()
    target = am if MODEL_AXIS in am.axis_names else mesh

    def constrain(path, leaf):
        s = _path_str(path)
        for rx, spec in compiled:
            if rx.search(s):
                return jax.lax.with_sharding_constraint(
                    leaf, NamedSharding(target, spec))
        return leaf

    return jax.tree_util.tree_map_with_path(constrain, params)


def tp_loss(loss_fn: Callable, mesh: Mesh,
            rules: Sequence[Tuple[str, P]] = GPT2_TP_RULES) -> Callable:
    """Wrap a loss_fn(params, batch, mask) so its parameters carry the
    tensor-parallel layout before the model runs."""
    if MODEL_AXIS not in mesh.axis_names:
        return loss_fn

    def wrapped(params, batch, mask):
        return loss_fn(constrain_params(params, mesh, rules), batch, mask)

    return wrapped

"""On-device round metrics: a fixed-shape, NAMED f32 vector computed
inside the jitted round.

The round engine's observability contract (ISSUE 4 tentpole): every
round produces one `[NUM_METRICS]` f32 vector — always the same shape
and dtype, so carrying it through `lax.scan` spans costs one stacked
`[N, NUM_METRICS]` output and never perturbs the treedef. Telemetry is
READ-ONLY observation: `round_vector` consumes values the round already
computed (losses, counts, the applied weight delta, the new
momentum/error state) and feeds nothing back, so a telemetry-on round
is bit-identical in `ServerState` to a telemetry-off round
(tests/test_telemetry.py proves it). Export to the host happens only at
span boundaries via explicit `jax.device_get` (telemetry.TelemetrySession),
so the transfer-guard contract holds with telemetry permanently on.

Metric semantics (indices are `METRIC_NAMES` order):

  train_loss        survivor-example-weighted mean client loss — dropped
                    clients and padding examples carry zero weight
  update_l2         l2 norm of the weight delta the round actually
                    applied (zero on a zero-survivor no-op round)
  error_l2          l2 norm of the NEW server error accumulator
                    (table-space for sketch mode, dense for true_topk;
                    zero when error_type == none)
  velocity_l2       l2 norm of the new server (virtual) momentum state
  survivors         number of sampled clients that completed the round
  examples          examples actually processed (straggler budgets and
                    dropout already applied — the FedNova denominator)
  realized_k        nonzero count of the applied weight delta: the
                    REALIZED top-k support, next to the analytic k the
                    accountant bills (ops/flat.py tie-widening and
                    sketch decode collisions make the two diverge)
  estimate_residual the sketch/top-k estimate-error proxy: the fraction
                    of accumulated update mass the compressed channel
                    FAILED to transmit this round,
                    error_l2 / (error_l2 + update_l2). Rising values
                    mean the compression budget (k, sketch geometry) is
                    falling behind the gradient — the knob PowerSGD-
                    style error feedback otherwise hides. 0 when the
                    mode has no error accumulator.

A model with expert layers (Config.expert_load_layers: how many)
appends seven counters an expert layer, `moe<l>_<name>` for name in
LOAD_COUNTERS, from the per-client loads its loss reports
(models/experts.expert_layer). `l` counts the expert layers only, in
the order of the stack: where leading layers are dense
(models/afmoe.py) they report no load, and moe0 is the first layer
with experts.

  routed            picks of the round's positions that fell on an
                    expert this chip holds
  max_load, min_load
                    the picks on the most and on the least loaded held
                    expert (0 for an expert no position reached: its
                    gradient is zero this round)
  absent_share      the share of all picks that fell on experts this
                    chip does not hold (1 - held / all under a uniform
                    router)
  compact_share     the share of the round's chunks of positions whose
                    expert layer ran in one of its capacities, under a
                    row a pick (0 where every expert is held)
  chunks            the round's chunks of positions whose weight
                    gradient went into the layer's one accumulator
                    (models/experts._chunks): every chunk the layer
                    ran
  live_peak         the held picks' share of all picks, in the chunk
                    that had most of them

A model with state-space mixer layers as well (Config.mixer_layers;
models/nemotron_h.py) appends, after those, two counters a mixer
layer, `ssm<l>_<name>` for name in MIXER_COUNTERS:

  carry             the mean over heads and chunks of exp(sum of dt A
                    over the chunk): the share of a chunk's end state
                    that reaches the next chunk (ops/ssd.ssd_scan)
  chunks            the chunks the layer scanned in the round
"""
from __future__ import annotations

import jax.numpy as jnp

METRIC_NAMES = (
    "train_loss",
    "update_l2",
    "error_l2",
    "velocity_l2",
    "survivors",
    "examples",
    "realized_k",
    "estimate_residual",
)
NUM_METRICS = len(METRIC_NAMES)
LOAD_COUNTERS = ("routed", "max_load", "min_load", "absent_share",
                 "compact_share", "chunks", "live_peak")
MIXER_COUNTERS = ("carry", "chunks")
METRIC_INDEX = {name: i for i, name in enumerate(METRIC_NAMES)}

_EPS = 1e-12


def empty_vector() -> jnp.ndarray:
    """The telemetry-off placeholder: a zero-size leaf, so the
    RoundMetrics treedef stays stable per config and `lax.scan` stacks
    it for free."""
    return jnp.zeros((0,), jnp.float32)


def round_vector(losses, counts, delta, verror, vvelocity,
                 survivors) -> jnp.ndarray:
    """[NUM_METRICS] f32 from values the round already computed.

    losses:    [W] per-client mean losses
    counts:    [W] examples actually processed per client (dropped
               clients already zeroed)
    delta:     [D] applied weight update (new_ps - old_ps)
    verror:    new server error accumulator (any shape; may be size 0)
    vvelocity: new server momentum state (any shape; may be size 0)
    survivors: scalar survivor count (traced or static)

    Pure jnp — trace-safe under jit/scan/shard_map, no host touches.
    """
    counts = counts.astype(jnp.float32)
    total = counts.sum()
    train_loss = (losses * counts).sum() / jnp.maximum(total, 1.0)
    update_l2 = jnp.sqrt(jnp.sum(delta * delta))
    error_l2 = jnp.sqrt(jnp.sum(verror.astype(jnp.float32) ** 2))
    velocity_l2 = jnp.sqrt(jnp.sum(vvelocity.astype(jnp.float32) ** 2))
    realized_k = jnp.sum(delta != 0).astype(jnp.float32)
    estimate_residual = error_l2 / (error_l2 + update_l2 + _EPS)
    return jnp.stack([
        train_loss,
        update_l2,
        error_l2,
        velocity_l2,
        jnp.asarray(survivors, jnp.float32),
        total,
        realized_k,
        estimate_residual,
    ])


def expert_load_vector(load) -> jnp.ndarray:
    """[layers * len(LOAD_COUNTERS)] f32 from the cohort's expert
    loads: load [W, layers, held + 4], per client and layer the picks
    on each held expert, the picks in all, those of them in chunks of
    positions that ran compacted, the chunks and, last, the held
    picks' share of the chunk that had most
    (models/experts.expert_layer)."""
    load = load.astype(jnp.float32)
    live_peak = load[..., -1].max(0)                # over the cohort
    load = load[..., :-1].sum(0)
    held, picks, compact, chunks = (load[:, :-3], load[:, -3], load[:, -2],
                                    load[:, -1])
    routed = held.sum(-1)
    picks = jnp.maximum(picks, 1.0)
    return jnp.stack(
        [routed, held.max(-1), held.min(-1), 1.0 - routed / picks,
         compact / picks, chunks, live_peak], axis=-1).reshape(-1)


def mixer_vector(stat) -> jnp.ndarray:
    """[layers * len(MIXER_COUNTERS)] f32 from the cohort's mixer
    stats: stat [W, layers, 2], per client and layer the sum over its
    chunks of the heads' mean carried share, and the chunks
    (models/nemotron_h.ssm)."""
    stat = stat.astype(jnp.float32).sum(0)
    chunks = stat[:, 1]
    return jnp.stack([stat[:, 0] / jnp.maximum(chunks, 1.0), chunks],
                     axis=-1).reshape(-1)


def layer_vectors(aux, mixer_layers: int) -> tuple:
    """The per-layer counters of a model with expert layers, from its
    loss's metrics `aux`: they end with the expert load and, where the
    model has `mixer_layers`, the mixer stat after it."""
    if not mixer_layers:
        return (expert_load_vector(aux[-1]),)
    return expert_load_vector(aux[-2]), mixer_vector(aux[-1])


def metric_names(size: int, mixer_layers: int = 0) -> tuple:
    """Names of a telemetry vector of `size` entries: METRIC_NAMES,
    then LOAD_COUNTERS for each expert layer the vector carries, then
    MIXER_COUNTERS for each of its `mixer_layers`."""
    mixer = mixer_layers * len(MIXER_COUNTERS)
    layers = (size - NUM_METRICS - mixer) // len(LOAD_COUNTERS)
    return METRIC_NAMES + tuple(
        f"moe{l}_{c}" for l in range(layers) for c in LOAD_COUNTERS) \
        + tuple(f"ssm{l}_{c}" for l in range(mixer_layers)
                for c in MIXER_COUNTERS)


def named(vec, mixer_layers: int = 0) -> dict:
    """Host-side convenience: {metric name: float} from one materialized
    telemetry vector (or a no-op {} for a zero-size placeholder);
    `mixer_layers` as Config.mixer_layers."""
    if vec is None or getattr(vec, "size", 0) == 0:
        return {}
    return {name: float(vec[i]) for i, name in
            enumerate(metric_names(len(vec), mixer_layers))}

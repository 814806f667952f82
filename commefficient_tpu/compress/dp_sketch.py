"""dp_sketch: differentially-private FetchSGD transport (ISSUE 19
plugin #2, the FedSKETCH-style DP scenario from PAPERS.md).

The Gaussian mechanism applied in SKETCH SPACE:

  * every client encodes its gradient into the [r, c] count-sketch
    table PER CLIENT (never the deferred shard-sum encode — the clip
    below is nonlinear) and, after the count scaling that makes its
    table the client's SUM contribution, clips the table's Frobenius
    norm to --dp_clip. Each client's contribution to the psum'd
    aggregate is therefore bounded by dp_clip, i.e. the sum query's
    l2 sensitivity to one client is exactly dp_clip;
  * ONCE per round, calibrated Gaussian noise with
    std = dp_noise_mult * dp_clip is added to the aggregated table
    inside the jitted round, on the registered "dp" PRNG domain
    folded into the round key (deterministic in (seed, round):
    crash->resume replays the identical noise, and GL009 keeps the
    domain honest);
  * everything downstream — divide-by-total, server-side virtual
    momentum/error, top-k decode — is post-processing, which costs no
    additional privacy.

Composition over rounds is tracked by the Rényi accountant
(compress/privacy.py): the host journals a `privacy` event with the
cumulative epsilon each round and fails LOUD when --dp_target_epsilon
is exhausted.

Deliberately rejected compositions (validate below): --dp (the PR-0
per-gradient worker/server DP path — two mechanisms would double-
count the budget) and the robust aggregators (an order statistic is
not the bounded-sensitivity SUM the noise is calibrated for). The
admission screen and byzantine drills compose fine: screening only
REMOVES clients, and a sum over fewer dp_clip-bounded contributions
keeps its sensitivity bound.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from commefficient_tpu.compress.base import Compressor
from commefficient_tpu.ops.flat import clip_to_l2
from commefficient_tpu.ops.sketch import CSVec


class DpSketchCompressor(Compressor):
    name = "dp_sketch"
    sketch_like = True

    # ---- static specs -------------------------------------------------
    def wire_floats(self, cfg) -> int:
        return cfg.num_rows * cfg.num_cols

    # wire_bytes: base 4 * wire_floats — the dp_sketch table rides the
    # wire at f32 (sketch_table_dtype is validated sketch-only; a
    # quantized DP table would need its own sensitivity analysis)

    def validate(self, cfg) -> None:
        if cfg.dp_noise_mult <= 0:
            raise ValueError(
                "dp_sketch requires --dp_noise_mult > 0: zero noise "
                "is not differential privacy — use --mode sketch for "
                "the noise-free transport (compress/dp_sketch.py)")
        if cfg.dp_clip <= 0:
            raise ValueError(
                f"dp_clip={cfg.dp_clip} must be > 0 (the per-client "
                "sketch-table sensitivity bound)")
        if not 0.0 < cfg.dp_delta < 1.0:
            raise ValueError(
                f"dp_delta={cfg.dp_delta} must be in (0, 1)")
        if cfg.dp_target_epsilon < 0:
            raise ValueError(
                f"dp_target_epsilon={cfg.dp_target_epsilon} must be "
                ">= 0 (0 = track epsilon but never fail)")
        if cfg.error_type == "local":
            raise ValueError(
                "dp_sketch cannot use per-client local error "
                "accumulation (same table-space contract as sketch "
                "mode)")
        if cfg.local_momentum != 0:
            raise ValueError(
                "dp_sketch cannot use local momentum (same table-"
                "space contract as sketch mode)")
        if cfg.do_dp:
            raise ValueError(
                "--dp (the per-gradient worker/server DP path) and "
                "--mode dp_sketch are mutually exclusive: two "
                "mechanisms would each consume privacy budget the "
                "accountant tracks only once (compress/dp_sketch.py)")
        if cfg.robust_aggregation:
            raise ValueError(
                "dp_sketch does not compose with robust aggregators "
                f"(--aggregator {cfg.aggregator}): the Gaussian noise "
                "is calibrated for the bounded-sensitivity SUM of "
                "dp_clip-clipped tables, and an order statistic has "
                "no such sensitivity bound — pick one "
                "(compress/dp_sketch.py)")

    # ---- traced hooks -------------------------------------------------
    def encode(self, cfg, grad, key=None):
        # always per-client (never the deferred shard-sum encode):
        # the sensitivity clip in residual() is nonlinear
        sketch = CSVec(d=cfg.grad_size, c=cfg.num_cols,
                       r=cfg.num_rows, num_blocks=cfg.num_blocks,
                       seed=42)
        return sketch.encode(grad)

    def residual(self, cfg, to_transmit, error, velocity, key=None):
        # to_transmit is the count-scaled [r, c] table — this client's
        # additive contribution to the round's sum. Frobenius-clip it
        # to dp_clip: the sum query's per-client l2 sensitivity bound
        # the noise is calibrated against.
        return clip_to_l2(to_transmit, cfg.dp_clip), error, velocity

    def post_aggregate(self, cfg, transmit, round_key):
        from commefficient_tpu.analysis.domains import domain
        noise_key = jax.random.fold_in(round_key, domain("dp"))
        sigma = cfg.dp_noise_mult * cfg.dp_clip
        return transmit + sigma * jax.random.normal(
            noise_key, transmit.shape, jnp.float32)

    def decode(self, cfg, gradient, Vvelocity, Verror, lr, key=None):
        # post-processing: the noisy aggregate table rides the exact
        # sketch-mode server path (virtual momentum/error in table
        # space, top-k decode)
        from commefficient_tpu.federated import server as fserver
        return fserver._sketched(gradient, Vvelocity, Verror, cfg,
                                 lr, key)

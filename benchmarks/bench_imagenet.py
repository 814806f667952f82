"""Benchmark: wall-clock per federated round at ImageNet scale.

BASELINE config #4 / VERDICT r4 next #7: one ImageNet-shaped round on
hardware — FixupResNet50 at 224px with `benchmarks/imagenet.sh`'s
exact training flags (uncompressed mode, 7 workers, local batch 64,
virtual error/momentum 0.9, weight decay 1e-4 — the reference's tuned
recipe, reference CommEfficient/imagenet.sh:2-21), synthetic image
bytes (zero-egress environment; the tensor shapes, parameter count,
and code path are the real ones).

Single-chip note: the reference runs 7 workers as 7 GPUs each doing a
serialized batch-64 fwd/bwd (fed_worker.py:60); here all 7 clients are
one vmapped jitted program on one chip, so client-local microbatching
(`--microbatch_size`, a lax.scan inside each client — the same knob
the reference exposes) bounds activation memory to
7 clients x IMAGENET_BENCH_MICRO images instead of 7 x 64.

Same measurement discipline as bench.py (child under hard kill, CPU
degrade, one-scalar digest, analytic per-client-serialized stand-in).

Writes one JSON line:
  {"metric": "imagenet_fixupresnet50_uncompressed_round_time", ...}

Usage:  python benchmarks/bench_imagenet.py             (TPU if up)
        JAX_PLATFORMS=cpu IMAGENET_BENCH_SMALL=1 python benchmarks/bench_imagenet.py
"""
from __future__ import annotations

import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import bench  # repo-root harness: log/alarm_guard/acquire_backend/...

NUM_WORKERS = int(os.environ.get("IMAGENET_BENCH_WORKERS", "7"))
LOCAL_BATCH = int(os.environ.get("IMAGENET_BENCH_BATCH", "64"))
ROUNDS = int(os.environ.get("IMAGENET_BENCH_ROUNDS", "2"))
MICRO = int(os.environ.get("IMAGENET_BENCH_MICRO", "8"))
SMALL = os.environ.get("IMAGENET_BENCH_SMALL", "") == "1"
# run the REAL 224px/1000-class geometry even on a CPU backend (an
# execution proof of config #4 at real shapes when no TPU is
# reachable; slow — minutes per round)
FORCE_FULL = os.environ.get("IMAGENET_BENCH_FORCE_FULL", "") == "1"
STAGE_TIMEOUT = int(os.environ.get("BENCH_STAGE_TIMEOUT", "900"))


def main() -> int:
    jax, platform = bench.acquire_backend()
    import jax.numpy as jnp
    import numpy as np

    from commefficient_tpu.utils.cache import (
        enable_persistent_compilation_cache,
    )
    enable_persistent_compilation_cache()

    from commefficient_tpu.config import Config
    from commefficient_tpu.federated import round as fround
    from commefficient_tpu.models import build_model
    from commefficient_tpu.ops.flat import flatten_params
    from commefficient_tpu.parallel.mesh import make_client_mesh

    device_kind = jax.devices()[0].device_kind
    mesh = make_client_mesh(min(len(jax.devices()), NUM_WORKERS))

    small = SMALL and not FORCE_FULL
    if small:
        px, batch, micro, classes = 64, 4, 2, 10
        model = build_model("FixupResNet50", num_classes=classes, width=8)
    else:
        px, batch, micro, classes = 224, LOCAL_BATCH, MICRO, 1000
        model = build_model("FixupResNet50", num_classes=classes)

    x0 = jnp.zeros((1, px, px, 3), jnp.float32)
    params = model.init(jax.random.PRNGKey(0), x0)
    vec, unravel = flatten_params(params)
    D = int(vec.shape[0])
    bench.log(f"imagenet bench D={D} small={small} rounds={ROUNDS} "
              f"W={NUM_WORKERS} B={batch} px={px} micro={micro}")

    # imagenet.sh's exact training flags; k/num_rows/num_cols carried
    # from the recipe but inert in uncompressed mode (as there)
    cfg = Config(
        mode="uncompressed", error_type="virtual",
        virtual_momentum=0.9, local_momentum=0.0,
        weight_decay=1e-4, microbatch_size=micro,
        k=1_000_000, num_rows=1, num_cols=10_000_000,
        num_workers=NUM_WORKERS, num_clients=NUM_WORKERS,
        local_batch_size=batch, max_local_batch=batch,
        grad_size=D,
        # timing loops re-dispatch from one retained (server, clients)
        donate_round_state=False,
    ).validate()

    loss_fn = bench.ce_loss_fn(model)
    train_round = fround.make_train_fn(loss_fn, unravel, cfg, mesh)
    server = fround.init_server_state(cfg, vec)
    clients = fround.init_client_state(cfg, cfg.resolved_num_clients(),
                                       vec, mesh=mesh)

    rng = np.random.RandomState(0)
    W = NUM_WORKERS
    x = jnp.asarray(
        rng.randn(W, batch, px, px, 3).astype(np.float32))
    y = jnp.asarray(
        rng.randint(0, classes, (W, batch)).astype(np.int32))
    mask = jnp.ones((W, batch), jnp.float32)
    batches = fround.RoundBatch(
        jnp.broadcast_to(jnp.arange(W, dtype=jnp.int32), (ROUNDS, W)),
        (jnp.broadcast_to(x, (ROUNDS,) + x.shape),
         jnp.broadcast_to(y, (ROUNDS,) + y.shape)),
        jnp.broadcast_to(mask, (ROUNDS, W, batch)))
    lrs = jnp.full((ROUNDS,), 0.1)
    key = jax.random.PRNGKey(0)
    run_digest = bench.make_run_digest(train_round.train_rounds)

    t0 = time.time()
    with bench.alarm_guard(STAGE_TIMEOUT, "compile+first run"):
        float(np.asarray(run_digest(server, clients, batches, lrs, key)))
    bench.log(f"compile+first run: {time.time() - t0:.1f}s")

    flops_per_round = bench.cost_flops(
        run_digest, (server, clients, batches, lrs, key), ROUNDS)

    with bench.alarm_guard(STAGE_TIMEOUT, "measure"):
        round_ms = bench.median_ms(
            run_digest, (server, clients, batches, lrs, key),
            divisor=ROUNDS)

    # analytic reference stand-in: per-client serialized fwd/bwd x W on
    # this same chip (the reference's GPUs each run ONE batch-64 client
    # serially; full-batch grad fits when not multiplied by vmap)
    def one_client_step(params_vec, xb, yb):
        def loss(v):
            l, _ = loss_fn(unravel(v), (xb, yb),
                           jnp.ones(xb.shape[0], jnp.float32))
            return l
        return jax.grad(loss)(params_vec)

    @jax.jit
    def serial_steps(params_vec, xb, yb):
        def body(v, _):
            return v - 1e-6 * one_client_step(v, xb, yb), None
        v, _ = jax.lax.scan(body, params_vec, None, length=ROUNDS)
        return v.sum()

    with bench.alarm_guard(STAGE_TIMEOUT, "baseline measure"):
        float(np.asarray(serial_steps(vec, x[0], y[0])))  # compile
        ref_round_ms = bench.median_ms(serial_steps, (vec, x[0], y[0]),
                                       divisor=ROUNDS) * NUM_WORKERS

    out = {
        "metric": "imagenet_fixupresnet50_uncompressed_round_time",
        "value": round(round_ms, 3),
        "unit": "ms/round",
        "vs_baseline": round(ref_round_ms / round_ms, 3),
        "platform": platform,
        "device_kind": device_kind,
        "num_workers": NUM_WORKERS,
        "local_batch": batch,
        "image_px": px,
        "microbatch": micro,
        "grad_size": D,
    }
    bench.add_flops_fields(out, flops_per_round, round_ms, device_kind)
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    raise SystemExit(bench.worker_entry(main))

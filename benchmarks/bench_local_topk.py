"""Benchmark: wall-clock per federated round for BASELINE config #3.

ResNet-18 (the PreAct variant with StatelessBatchNorm — see
models/fixup_resnet.py; the norm-free Fixup variant is FixupResNet18,
not what is measured here) on CIFAR100-shaped data, `local_topk`
compression with per-client local error feedback and
local momentum, 100 non-IID clients with 8 participating per round —
the reference entry point is `cv_train.py --mode local_topk
--error_type local` (BASELINE.md configs table).

local_topk stresses a different path than the headline sketch bench:
no sketch encode/decode at all, but per-participant `masked_topk` on
the [D] gradient (ops/flat.py — the approx_max_k selection path) and
gather/scatter of the participants' rows of the [num_clients, D] error
and velocity state (federated/round.py) — at 100 clients x 11M params
that state is the memory hazard SURVEY §7.3 ranks third.

Same measurement discipline as bench.py / bench_gpt2.py, whose
machinery this reuses: child process under hard kill-on-timeout, one
jitted scalar digest (no DCE, one 4-byte sync), analytic reference
stand-in = num_workers x a measured single-client serialized fwd/bwd
on the same chip (the reference serializes clients per GPU,
fed_worker.py:60).

Writes one JSON line to stdout:
  {"metric": "cifar100_resnet18_local_topk_round_time", ...}

Usage:  python benchmarks/bench_local_topk.py            (TPU if up)
        JAX_PLATFORMS=cpu LTK_BENCH_SMALL=1 python benchmarks/bench_local_topk.py
"""
from __future__ import annotations

import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import bench  # repo-root harness: orchestration, backend bring-up, logging

NUM_WORKERS = int(os.environ.get("LTK_BENCH_WORKERS", "8"))
LOCAL_BATCH = int(os.environ.get("LTK_BENCH_BATCH", "32"))
ROUNDS = int(os.environ.get("LTK_BENCH_ROUNDS", "10"))
NUM_CLIENTS = int(os.environ.get("LTK_BENCH_CLIENTS", "100"))
SMALL = os.environ.get("LTK_BENCH_SMALL", "") == "1"
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

    small = SMALL
    num_classes = 100
    if small:
        model_mod = build_model("ResNet9", num_classes=num_classes,
                                channels={"prep": 8, "layer1": 8,
                                          "layer2": 8, "layer3": 8})
    else:
        model_mod = build_model("ResNet18", num_classes=num_classes)

    key = jax.random.PRNGKey(0)
    x0 = jnp.zeros((LOCAL_BATCH, 32, 32, 3), jnp.float32)
    params = model_mod.init(key, x0)
    vec, unravel = flatten_params(params)
    D = int(vec.shape[0])
    num_clients = 20 if small else NUM_CLIENTS
    bench.log(f"local_topk bench D={D} small={small} rounds={ROUNDS} "
              f"W={NUM_WORKERS} B={LOCAL_BATCH} clients={num_clients}")

    cfg = Config(
        mode="local_topk", error_type="local", local_momentum=0.9,
        virtual_momentum=0.0,
        k=max(D // 130, 500),  # reference default ratio: 50k at D=6.6M
        weight_decay=5e-4, microbatch_size=-1, num_workers=NUM_WORKERS,
        num_clients=num_clients, local_batch_size=LOCAL_BATCH,
        grad_size=D,
        # timing loops re-dispatch from one retained (server, clients)
        donate_round_state=False,
    ).validate()

    loss_fn = bench.ce_loss_fn(model_mod)

    train_round = fround.make_train_fn(loss_fn, unravel, cfg, mesh)
    server = fround.init_server_state(cfg, vec)
    clients = fround.init_client_state(cfg, cfg.resolved_num_clients(),
                                       vec, mesh=mesh)

    rng = np.random.RandomState(0)
    W, B = NUM_WORKERS, LOCAL_BATCH
    x = jnp.asarray(rng.randn(W, B, 32, 32, 3).astype(np.float32))
    y = jnp.asarray(
        rng.randint(0, num_classes, (W, B)).astype(np.int32))
    mask = jnp.ones((W, B), jnp.float32)
    data = (x, y)

    # distinct participants each round, cycling the 100 clients — the
    # gather/scatter of participant state rows is part of the cost
    # being measured
    cids = np.stack([(np.arange(W) + r * W) % num_clients
                     for r in range(ROUNDS)]).astype(np.int32)
    batches = fround.RoundBatch(
        jnp.asarray(cids),
        tuple(jnp.broadcast_to(d, (ROUNDS,) + d.shape) for d in data),
        jnp.broadcast_to(mask, (ROUNDS, W, B)))
    lrs = jnp.full((ROUNDS,), 0.1)
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

    # analytic reference stand-in: per-client serialized fwd/bwd
    def one_client_step(params_vec, xb, yb):
        def loss(v):
            l, _ = loss_fn(unravel(v), (xb, yb), mask[0])
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
        "metric": "cifar100_resnet18_local_topk_round_time",
        "value": round(round_ms, 3),
        "unit": "ms/round",
        "vs_baseline": round(ref_round_ms / round_ms, 3),
        "platform": platform,
        "device_kind": device_kind,
        "num_workers": NUM_WORKERS,
        "local_batch": LOCAL_BATCH,
        "num_clients": num_clients,
        "k": cfg.k,
        "grad_size": D,
    }
    bench.add_flops_fields(out, flops_per_round, round_ms, device_kind)
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    raise SystemExit(bench.worker_entry(main))

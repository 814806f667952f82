"""Component-level timing breakdown of the sketch federated round.

VERDICT r2 weak #1: BENCH_r02 measured 174.5 ms/round on a v5e against
a ~36 ms analytic reference stand-in, with no breakdown of where the
~138 ms of compression overhead went. This script times each stage of
the round in isolation on the current backend, so the optimization
work (fast top-k selection, encode kernels) is driven by measurement
instead of suspicion.

Stages timed (bench geometry: ResNet9 D=6.57M, 5x500k sketch, k=50k,
8 clients x batch 32):
  null_dispatch    a scalar add — the per-dispatch floor
  client_fwd_bwd   8 clients' vmapped fwd/bwd, no compression
  encode           8 clients' vmapped sketch encode [D] -> [5, 500k]
  decode_topk      server decode_topk_sparse(table, k)
  encode_sparse    server re-sketch of the k-sparse update
  masked_topk      dense top-k on [D] (true_topk/local_topk path)
  pack_change_bits accounting bitset pack (f32-dot reformulation)
  encode_pallas_x1 / estimate_all_{xla,pallas} /
  threshold_decode_pallas
                   the ISSUE-6 fused kernel stages next to their XLA
                   counterparts (VMEM-gated; skips are reported)
  quant_roundtrip_{bf16,int8}
                   sketch-table wire quantize+dequantize
  full_round       one train round (single, unscanned)
  scanned_round    per-round time of the 10-round scanned program

Usage:  python benchmarks/profile_round.py           (TPU if up)
        JAX_PLATFORMS=cpu PROF_SMALL=1 python benchmarks/profile_round.py
"""
from __future__ import annotations

import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import bench  # repo-root harness: backend bring-up, logging

import jax

import jax.numpy as jnp
import numpy as np

from commefficient_tpu.config import Config
from commefficient_tpu.utils.cache import \
    enable_persistent_compilation_cache

enable_persistent_compilation_cache()
from commefficient_tpu.federated import round as fround
from commefficient_tpu.federated.accounting import pack_change_bits
from commefficient_tpu.models import ResNet9
from commefficient_tpu.ops import kernels as pkern
from commefficient_tpu.ops.flat import flatten_params, masked_topk
from commefficient_tpu.ops.sketch import CSVec
from commefficient_tpu.parallel.mesh import make_client_mesh


NUM_WORKERS = 8
LOCAL_BATCH = 32
ROUNDS = 10
SMALL = os.environ.get("PROF_SMALL", "") == "1"
REPS = int(os.environ.get("PROF_REPS", "5"))


def scalarize(fn):
    """Wrap fn so it returns one f32 scalar summing every output leaf:
    nothing is DCE-able, and the sync transfer is 4 bytes."""
    def wrapped(*args):
        out = fn(*args)
        acc = jnp.float32(0)
        for l in jax.tree.leaves(out):
            if jnp.issubdtype(l.dtype, jnp.floating):
                acc = acc + jnp.sum(l)
            else:
                # integer outputs (e.g. the uint32 change bitset) must
                # be consumed too, or XLA deletes the work that
                # produced them from the timed program
                acc = acc + jnp.sum(l, dtype=jnp.uint32).astype(jnp.float32)
        return acc
    return jax.jit(wrapped)


def timeit(fn, *args, reps=REPS):
    """Median wall-clock of scalarize(fn)(*args), syncing via the 4-byte
    host transfer."""
    fn = scalarize(fn)
    float(np.asarray(fn(*args)))  # compile
    ts = []
    for _ in range(reps):
        t0 = time.perf_counter()
        float(np.asarray(fn(*args)))
        ts.append(time.perf_counter() - t0)
    return float(np.median(ts)) * 1e3


def main():
    _, platform = bench.acquire_backend()
    small = SMALL
    mesh = make_client_mesh(min(len(jax.devices()), NUM_WORKERS))
    channels = ({"prep": 8, "layer1": 8, "layer2": 8, "layer3": 8}
                if small else None)
    model = ResNet9(num_classes=10, channels=channels)
    x0 = jnp.zeros((LOCAL_BATCH, 32, 32, 3), jnp.float32)
    params = model.init(jax.random.PRNGKey(0), x0)
    vec, unravel = flatten_params(params)
    D = int(vec.shape[0])
    cfg = Config(
        mode="sketch", k=500 if small else 50_000, num_rows=5,
        num_cols=max(256, D // 13) if small else 500_000, num_blocks=20,
        error_type="virtual", virtual_momentum=0.9, local_momentum=0.0,
        weight_decay=5e-4, microbatch_size=-1, num_workers=NUM_WORKERS,
        num_clients=10 * NUM_WORKERS, grad_size=D,
        # stage timing re-dispatches from one retained state object —
        # donation would delete it after the first call
        donate_round_state=False,
    ).validate()
    sketch = CSVec(d=D, c=cfg.num_cols, r=cfg.num_rows,
                   num_blocks=cfg.num_blocks, seed=42)

    loss_fn = bench.ce_loss_fn(model)

    rng = np.random.RandomState(0)
    x = jnp.asarray(rng.randn(NUM_WORKERS, LOCAL_BATCH, 32, 32, 3)
                    .astype(np.float32))
    y = jnp.asarray(rng.randint(0, 10, (NUM_WORKERS, LOCAL_BATCH))
                    .astype(np.int32))
    mask = jnp.ones((NUM_WORKERS, LOCAL_BATCH), jnp.float32)
    gvec = jnp.asarray(rng.randn(D).astype(np.float32))
    table = sketch.encode(gvec)
    kidx = jnp.asarray(
        rng.choice(D, size=cfg.k, replace=False).astype(np.int32))
    kvals = jnp.asarray(rng.randn(cfg.k).astype(np.float32))

    out = {"platform": platform,
           "device_kind": jax.devices()[0].device_kind,
           "D": D, "k": cfg.k, "num_cols": cfg.num_cols,
           "stages_ms": {}}

    class Stages(dict):
        # print each stage as it completes, to stderr: the parent
        # (_run_child) relays the stderr tail even for a hung/killed
        # child, so a mid-profile death still leaves the completed
        # stages visible, and stdout stays clean for the JSON line
        def __setitem__(self, k2, v):
            super().__setitem__(k2, round(v, 2))
            print(f"  {k2}: {v:.2f} ms", file=sys.stderr, flush=True)

    S = out["stages_ms"] = Stages()

    # --- dispatch overhead ---------------------------------------------
    S["null_dispatch"] = timeit(lambda s: s + 1.0, jnp.float32(0))

    # --- client fwd/bwd, no compression --------------------------------
    def grads_only(v, xb, yb, m):
        def one(xc, yc, mc):
            def loss(vv):
                l, _ = loss_fn(unravel(vv), (xc, yc), mc)
                return l
            return jax.grad(loss)(v)
        return jax.vmap(one)(xb, yb, m).sum(0)

    S["client_fwd_bwd"] = timeit(jax.jit(grads_only), vec, x, y, mask)

    # --- sketch encode (8 clients) -------------------------------------
    S["encode_x8"] = timeit(
        jax.jit(lambda g: jax.vmap(sketch.encode)(g)),
        jnp.broadcast_to(gvec, (NUM_WORKERS, D)))
    S["encode_x1"] = timeit(jax.jit(sketch.encode), gvec)

    # --- server decode / re-sketch -------------------------------------
    S["decode_topk"] = timeit(
        jax.jit(lambda t: sketch.decode_topk_sparse(t, cfg.k)), table)
    S["encode_sparse"] = timeit(
        jax.jit(lambda i, v: sketch.encode_sparse(i, v)), kidx, kvals)

    # --- dense top-k (true/local_topk path) ----------------------------
    S["masked_topk"] = timeit(
        jax.jit(lambda g: masked_topk(g, cfg.k)), gvec)

    # --- accounting bit-pack (the f32-dot reformulation) ---------------
    S["pack_change_bits"] = timeit(jax.jit(pack_change_bits), gvec)

    # --- fused Pallas kernels, timed per kernel (ISSUE 6) --------------
    # Each stage is its own jitted single-scalar digest (timeit
    # scalarizes) — the per-kernel rows of PERF.md's stage table. A
    # geometry past a kernel's VMEM gate reports the skip instead of
    # silently timing the XLA fallback under a kernel's name.
    sk_pl = CSVec(d=D, c=cfg.num_cols, r=cfg.num_rows,
                  num_blocks=cfg.num_blocks, seed=42, backend="pallas")
    if pkern.pallas_fits(sk_pl, "encode"):
        S["encode_pallas_x1"] = timeit(jax.jit(sk_pl.encode), gvec)
    else:
        print("  encode_pallas_x1: skipped (VMEM gate)",
              file=sys.stderr, flush=True)
    S["estimate_all_xla"] = timeit(jax.jit(sketch.estimate_all), table)
    if pkern.pallas_fits(sk_pl, "estimate"):
        S["estimate_all_pallas"] = timeit(
            jax.jit(lambda t: pkern.pallas_estimate_all(sk_pl, t)),
            table)
        S["threshold_decode_pallas"] = timeit(
            jax.jit(lambda t: pkern.pallas_threshold_decode(
                sk_pl, t, cfg.k)), table)
    else:
        print("  estimate/threshold pallas: skipped (VMEM gate)",
              file=sys.stderr, flush=True)

    # --- quantized wire transport round-trip (--sketch_table_dtype) ----
    S["quant_roundtrip_bf16"] = timeit(
        jax.jit(lambda t: pkern.wire_roundtrip(t, "bf16")), table)
    S["quant_roundtrip_int8"] = timeit(
        jax.jit(lambda t: pkern.wire_roundtrip(t, "int8")), table)

    # --- full round ----------------------------------------------------
    train_round = fround.make_train_fn(loss_fn, unravel, cfg, mesh)
    server = fround.init_server_state(cfg, vec)
    clients = fround.init_client_state(cfg, cfg.resolved_num_clients(),
                                       vec, mesh=mesh)
    batch = fround.RoundBatch(
        jnp.arange(NUM_WORKERS, dtype=jnp.int32), (x, y), mask)
    key = jax.random.PRNGKey(0)
    S["full_round"] = timeit(
        lambda: train_round(server, clients, batch, 0.1, key))

    batches = fround.RoundBatch(
        jnp.broadcast_to(batch.client_ids,
                         (ROUNDS,) + batch.client_ids.shape),
        tuple(jnp.broadcast_to(d, (ROUNDS,) + d.shape)
              for d in batch.data),
        jnp.broadcast_to(batch.mask, (ROUNDS,) + batch.mask.shape))
    lrs = jnp.full((ROUNDS,), 0.1)
    t_scan = timeit(
        lambda: train_round.train_rounds(server, clients, batches, lrs,
                                         key), reps=max(2, REPS // 2))
    S["scanned_round_per_round"] = t_scan / ROUNDS

    print(json.dumps(out), flush=True)


if __name__ == "__main__":
    sys.exit(bench.worker_entry(main))

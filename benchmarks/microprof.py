"""Micro-profile of the sketch/topk ops at the two failing bench
geometries (BASELINE configs #5 and #3), on whatever backend is up.

Times each op in isolation (scalarized sync, same rules as
profile_round.py) so the config-#5/#3 optimization work is driven by
measurement:

  config #5 (GPT2-small): D=124M, sketch 5 x 9.5M, k=952k
  config #3 (ResNet18):   D=5.25M, local_topk k=40402, 8 clients

Usage:  python benchmarks/microprof.py          (TPU child if up)
        JAX_PLATFORMS=cpu python benchmarks/microprof.py
"""
from __future__ import annotations

import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import bench

REPS = int(os.environ.get("PROF_REPS", "5"))


def main():
    _, platform = bench.acquire_backend()
    import jax
    import jax.numpy as jnp
    import numpy as np

    from commefficient_tpu.utils.cache import (
        enable_persistent_compilation_cache,
    )
    enable_persistent_compilation_cache()
    from commefficient_tpu.ops.flat import masked_topk
    from commefficient_tpu.ops.sketch import CSVec

    def scalarize(fn):
        def wrapped(*args):
            out = fn(*args)
            acc = jnp.float32(0)
            for l in jax.tree.leaves(out):
                if jnp.issubdtype(l.dtype, jnp.floating):
                    acc = acc + jnp.sum(l)
                else:
                    acc = acc + jnp.sum(
                        l, dtype=jnp.uint32).astype(jnp.float32)
            return acc
        return jax.jit(wrapped)

    def timeit(fn, *args, reps=REPS):
        f = scalarize(fn)
        float(np.asarray(f(*args)))  # compile
        ts = []
        for _ in range(reps):
            t0 = time.perf_counter()
            float(np.asarray(f(*args)))
            ts.append(time.perf_counter() - t0)
        return float(np.median(ts)) * 1e3

    out = {"platform": platform, "stages_ms": {}}
    S = out["stages_ms"]

    def rec(name, v):
        S[name] = round(v, 2)
        print(f"  {name}: {v:.2f} ms", file=sys.stderr, flush=True)

    small = os.environ.get("PROF_SMALL", "") == "1"

    # ---- config #5 geometry (GPT2-small) -------------------------------
    D5 = 1_000_000 if small else 123_756_289
    c5 = D5 // 13
    k5 = D5 // 130
    sk = CSVec(d=D5, c=c5, r=5, num_blocks=20, seed=42)
    rng = np.random.RandomState(0)
    g5 = jnp.asarray(rng.randn(D5).astype(np.float32))
    table5 = jax.jit(sk.encode)(g5)
    kidx = jnp.asarray(rng.choice(D5, size=k5, replace=False)
                       .astype(np.int32))
    kvals = jnp.asarray(rng.randn(k5).astype(np.float32))

    rec("g5_encode_dense", timeit(sk.encode, g5))
    rec("g5_estimate_all", timeit(sk.estimate_all, table5))
    rec("g5_decode_topk_sparse",
        timeit(lambda t: sk.decode_topk_sparse(t, k5), table5))

    def approx_only(t):
        est = sk.estimate_all(t).reshape(-1)
        _, idx = jax.lax.approx_max_k(est * est, k5)
        return idx
    rec("g5_estimate+approx_max_k", timeit(approx_only, table5))

    def dense_update(i, v):
        return jnp.zeros(D5, jnp.float32).at[i].set(v, mode="drop")
    rec("g5_scatter_dense_update", timeit(dense_update, kidx, kvals))
    rec("g5_encode_sparse", timeit(sk.encode_sparse, kidx, kvals))

    upd5 = jax.jit(dense_update)(kidx, kvals)
    rec("g5_reencode_dense_of_sparse", timeit(sk.encode, upd5))

    # threshold-mask alternative to scatter+gather for the dense update
    def thresh_update(t):
        est = sk.estimate_all(t).reshape(-1)
        if est.shape[0] != D5:
            iota = jnp.arange(est.shape[0], dtype=jnp.int32)
            est = jnp.where(iota < D5, est, 0.0)
        sq = est * est
        vals, _ = jax.lax.approx_max_k(sq, k5)
        thr = vals[-1]
        return jnp.where(sq >= thr, est, 0.0)[:D5]
    rec("g5_thresh_update_total", timeit(thresh_update, table5))

    from commefficient_tpu.federated.accounting import pack_change_bits
    rec("g5_pack_change_bits", timeit(pack_change_bits, g5))

    # ---- config #3 geometry (local_topk) --------------------------------
    D3 = 500_000 if small else 5_252_388
    k3 = max(D3 // 130, 100)
    g3 = jnp.asarray(rng.randn(8, D3).astype(np.float32))
    rec("l3_masked_topk_x8", timeit(lambda g: masked_topk(g, k3), g3))
    rec("l3_masked_topk_x1", timeit(lambda g: masked_topk(g[0], k3), g3))

    def thresh_topk(v):
        sq = v * v
        vals, _ = jax.lax.approx_max_k(sq, k3)
        return jnp.where(sq >= vals[-1], v, 0.0)
    rec("l3_thresh_topk_x8", timeit(jax.vmap(thresh_topk), g3))

    def approx_only3(v):
        _, idx = jax.lax.approx_max_k(v * v, k3)
        return idx
    rec("l3_approx_max_k_x8", timeit(jax.vmap(approx_only3), g3))

    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    raise SystemExit(bench.worker_entry(main))

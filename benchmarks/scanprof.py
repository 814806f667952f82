"""Scanned per-stage profile at the config-#5 (GPT2) and config-#3
(local_topk) bench geometries.

PROFILE_tpu_r05.json recorded a per-dispatch floor of ~73 ms —
larger than every isolated stage — so single-dispatch stage
timing cannot resolve where the GPT2 round's ~350 ms of non-client
time goes. This profiler times each stage as a `lax.scan` of N
serialized iterations inside ONE dispatch (each iteration's input
depends on the previous output through a tiny perturbation, so XLA can
neither CSE the iterations nor run them in parallel), subtracts the
scan-of-nothing baseline, and divides by N.

Stages (gpt2 geometry D=124M, 5 x 9.5M sketch, k=952k):
  noop            carry-chained scalar adds: dispatch + scan floor
  encode_dense    CSVec.encode of a [D] vector
  estimate_all    decode estimates for all coordinates
  approx_topk     approx_max_k(est^2, k) over the [D] estimate
  gather_vals     est[idx] gather of k values
  scatter_update  zeros.at[idx].set(vals): dense k-sparse update
  encode_sparse   r*k scatter-add re-sketch (the r4 server path)
  server_sketched the full _sketched server step (real state carry)
  client_fwd_bwd  W clients' vmapped fwd/bwd (the useful work)

local_topk geometry (D=5.25M, k=40402, 8 clients):
  ltk_masked_topk_x8   vmapped masked_topk over [8, D]
  ltk_server           _local_topk server step
  ltk_state_gather_scatter  [100, D] error-state row gather+scatter

Usage:  python benchmarks/scanprof.py            (TPU child if up)
        JAX_PLATFORMS=cpu python benchmarks/scanprof.py   (small)
"""
from __future__ import annotations

import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import bench

ITERS = int(os.environ.get("SCANPROF_ITERS", "8"))
REPS = int(os.environ.get("PROF_REPS", "3"))
STAGE_TIMEOUT = int(os.environ.get("BENCH_STAGE_TIMEOUT", "600"))


def main():
    _, platform = bench.acquire_backend()
    import jax
    import jax.numpy as jnp
    import numpy as np

    from commefficient_tpu.utils.cache import (
        enable_persistent_compilation_cache,
    )
    enable_persistent_compilation_cache()
    from commefficient_tpu.config import Config
    from commefficient_tpu.federated import server as fserver
    from commefficient_tpu.ops.flat import masked_topk
    from commefficient_tpu.ops.sketch import CSVec

    small = os.environ.get("SCANPROF_SMALL", "") == "1"

    def chain_ms(step, init=None, iters=ITERS, reps=REPS):
        """Median per-iteration ms of `step(carry) -> carry` scanned
        `iters` times in one dispatch, NET of the scan/dispatch floor
        (measured with a 1-iter scan of the same program). `init`
        builds the initial carry (default: one f32 scalar)."""
        c0 = jnp.float32(0) if init is None else init()

        def run(n):
            @jax.jit
            def prog(c):
                def body(carry, _):
                    return step(carry), None
                out, _ = jax.lax.scan(body, c, None, length=n)
                acc = jnp.float32(0)
                for l in jax.tree.leaves(out):
                    acc = acc + jnp.sum(l).astype(jnp.float32)
                return acc
            float(np.asarray(prog(c0)))  # compile
            ts = []
            for _ in range(reps):
                t0 = time.perf_counter()
                float(np.asarray(prog(c0)))
                ts.append(time.perf_counter() - t0)
            return float(np.median(ts)) * 1e3

        with bench.alarm_guard(STAGE_TIMEOUT, "chain stage"):
            t_n, t_1 = run(iters), run(1)
        return max(t_n - t_1, 0.0) / (iters - 1)

    out = {"platform": platform, "iters": ITERS, "stages_ms": {}}
    S = out["stages_ms"]

    def rec(name, v):
        S[name] = round(v, 2)
        print(f"  {name}: {v:.2f} ms", file=sys.stderr, flush=True)

    rng = np.random.RandomState(0)

    # ---- gpt2 geometry --------------------------------------------------
    D = 1_000_000 if small else 123_756_289
    c = D // 13
    k = D // 130
    sk = CSVec(d=D, c=c, r=5, num_blocks=20, seed=42)
    g = jnp.asarray(rng.randn(D).astype(np.float32))
    table = jax.jit(sk.encode)(g)
    kidx = jnp.asarray(
        np.sort(rng.choice(D, size=k, replace=False)).astype(np.int32))
    kvals = jnp.asarray(rng.randn(k).astype(np.float32))
    out["gpt2_geom"] = {"D": D, "c": c, "k": k}

    rec("noop", chain_ms(lambda s: s + 1.0))
    rec("encode_dense",
        chain_ms(lambda s: sk.encode(g + s).sum() * 1e-30 + s))
    rec("estimate_all",
        chain_ms(lambda s: sk.estimate_all(table + s).sum() * 1e-30 + s))

    def approx_step(s):
        vals, _ = jax.lax.approx_max_k((g + s) * (g + s), k)
        return vals.sum() * 1e-30 + s
    rec("approx_topk", chain_ms(approx_step))

    rec("decode_threshold",
        chain_ms(lambda s: sk.decode_topk_dense(
            table + s, k).sum() * 1e-30 + s))

    rec("gather_vals",
        chain_ms(lambda s: (g + s)[kidx].sum() * 1e-30 + s))
    rec("scatter_update",
        chain_ms(lambda s: jnp.zeros(D, jnp.float32).at[kidx].set(
            kvals + s, mode="drop").sum() * 1e-30 + s))
    rec("encode_sparse",
        chain_ms(lambda s: sk.encode_sparse(
            kidx, kvals + s).sum() * 1e-30 + s))

    cfg5 = Config(mode="sketch", k=k, num_rows=5, num_cols=c,
                  num_blocks=20, error_type="virtual",
                  virtual_momentum=0.9, local_momentum=0.0,
                  microbatch_size=-1, num_workers=4, num_clients=40,
                  grad_size=D).validate()
    sgrad = jax.jit(sk.encode)(g)

    def server_step(carry):
        Vvel, Verr = carry
        upd = fserver.get_server_update(sgrad, Vvel, Verr, cfg5, 0.1)
        return (upd.Vvelocity, upd.Verror)

    rec("server_sketched", chain_ms(
        server_step,
        init=lambda: (jnp.zeros_like(table), jnp.zeros_like(table))))

    # the useful work: W=4 clients' vmapped fwd/bwd at the bench shapes
    # (chained through the weight vector), so the round's remainder can
    # be attributed: round ≈ fwd_bwd + encode + server + scan floor
    if os.environ.get("SCANPROF_GPT2_FWD", "1") == "1":
        from commefficient_tpu.models.gpt2 import (
            GPT2Config, GPT2DoubleHeads,
        )
        from commefficient_tpu.ops.flat import flatten_params
        from commefficient_tpu.training.gpt2_train import (
            make_compute_loss_train,
        )
        W, B, CANDS, L = 4, 4, 2, 128
        gcfg = (GPT2Config(vocab_size=5005, n_positions=128, n_embd=64,
                           n_layer=2, n_head=2) if small
                else GPT2Config(vocab_size=50262, n_positions=128))
        module = GPT2DoubleHeads(gcfg)
        x0 = jnp.zeros((1, CANDS, L), jnp.int32)
        params = module.init(jax.random.PRNGKey(0), x0, x0,
                             jnp.zeros((1, CANDS), jnp.int32))
        vec, unravel = flatten_params(params)
        loss_fn = make_compute_loss_train(module, cfg5)
        V = gcfg.vocab_size

        def tok(shape, hi):
            return jnp.asarray(
                rng.randint(0, hi, shape).astype(np.int32))
        bdata = (tok((W, B, CANDS, L), V), tok((W, B, CANDS), L),
                 tok((W, B, CANDS, L), V), tok((W, B), CANDS),
                 tok((W, B, CANDS, L), V))
        bmask = jnp.ones((W, B), jnp.float32)

        def fwd_bwd_vmap(v):
            def one(d, m):
                def loss(vv):
                    l, _ = loss_fn(unravel(vv), d, m)
                    return l
                return jax.grad(loss)(v)
            return jax.vmap(one)(bdata, bmask).sum(0)
        rec("gpt2_fwd_bwd_vmap_x4",
            chain_ms(lambda v: v - 1e-9 * fwd_bwd_vmap(v),
                     init=lambda: vec, iters=4))

        def fwd_bwd_fused(v):
            def total(vv):
                def one(d, m):
                    l, _ = loss_fn(unravel(vv), d, m)
                    return l * m.sum()
                return jax.vmap(one)(bdata, bmask).sum()
            return jax.grad(total)(v)
        rec("gpt2_fwd_bwd_fused_x4",
            chain_ms(lambda v: v - 1e-9 * fwd_bwd_fused(v),
                     init=lambda: vec, iters=4))

    # ---- local_topk geometry -------------------------------------------
    D3 = 500_000 if small else 5_252_388
    k3 = max(D3 // 130, 100)
    g3 = jnp.asarray(rng.randn(8, D3).astype(np.float32))
    out["ltk_geom"] = {"D": D3, "k": k3}

    rec("ltk_masked_topk_x8",
        chain_ms(lambda s: jnp.sum(
            masked_topk(g3 + s, k3)) * 1e-30 + s))

    from commefficient_tpu.ops.flat import (
        _topk_exact_1d, _topk_threshold_1d,
    )
    rec("ltk_topk_exact_x8",
        chain_ms(lambda s: jnp.sum(jax.vmap(
            lambda v: _topk_exact_1d(v, k3))(g3 + s)) * 1e-30 + s))
    rec("ltk_topk_threshold_x8",
        chain_ms(lambda s: jnp.sum(jax.vmap(
            lambda v: _topk_threshold_1d(v, k3))(g3 + s)) * 1e-30 + s))

    cfg3 = Config(mode="local_topk", error_type="local",
                  local_momentum=0.9, virtual_momentum=0.0, k=k3,
                  microbatch_size=-1, num_workers=8, num_clients=100,
                  grad_size=D3).validate()

    def ltk_server(s):
        upd = fserver.get_server_update(
            g3[0] + s, jnp.zeros(D3), jnp.zeros((0,)), cfg3, 0.1)
        return upd.update.sum() * 1e-30 + s
    rec("ltk_server", chain_ms(ltk_server))

    state = jnp.asarray(rng.randn(104, D3).astype(np.float32))
    ids = jnp.arange(8, dtype=jnp.int32)

    def gs_step(s):
        rows = state[ids] + s
        return (state.at[ids].set(rows).sum(axis=(0, 1)) * 1e-30 + s)
    rec("ltk_state_gather_scatter", chain_ms(gs_step))

    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    raise SystemExit(bench.worker_entry(main))

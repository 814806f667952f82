"""Benchmark: wall-clock per federated round at GPT2 scale.

BASELINE config #5: GPT2-small double-heads (124M params) on
PersonaChat-shaped data, count-sketch compression + virtual momentum.
This is the regime where MFU stops being dominated by round overhead
(VERDICT r2 next #3): the transformer fwd/bwd is ~0.5 TFLOP/round at
the shapes below, vs ResNet9/CIFAR's 0.05.

Same measurement discipline as the repo-root bench.py (whose
machinery this reuses): one process on the chip JAX finds (no chip,
no number), ONE jitted scalar digest per measurement so XLA DCE
cannot distort the number,
analytic reference stand-in = num_workers x a measured single-client
serialized fwd/bwd on the same chip (the reference serializes clients
per GPU, fed_worker.py:60).

Writes one JSON line to stdout:
  {"metric": "persona_gpt2s_sketch_round_time", "value": .., ...}

Usage:  python benchmarks/bench_gpt2.py                (TPU if up)
        JAX_PLATFORMS=cpu GPT2_BENCH_SMALL=1 python benchmarks/bench_gpt2.py
"""
from __future__ import annotations

import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import bench  # repo-root harness: log/alarm_guard/acquire_backend/PEAK_TFLOPS

NUM_WORKERS = int(os.environ.get("GPT2_BENCH_WORKERS", "4"))
LOCAL_BATCH = int(os.environ.get("GPT2_BENCH_BATCH", "4"))
# 8 rounds per dispatch
ROUNDS = int(os.environ.get("GPT2_BENCH_ROUNDS", "8"))
SEQ_LEN = int(os.environ.get("GPT2_BENCH_SEQ", "128"))
CANDS = 2
SMALL = os.environ.get("GPT2_BENCH_SMALL", "") == "1"
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
    from commefficient_tpu.models.gpt2 import GPT2Config, GPT2DoubleHeads
    from commefficient_tpu.ops.flat import flatten_params
    from commefficient_tpu.parallel.mesh import make_client_mesh
    from commefficient_tpu.training.gpt2_train import (
        make_compute_loss_train,
    )

    device_kind = jax.devices()[0].device_kind
    mesh = make_client_mesh(min(len(jax.devices()), NUM_WORKERS))

    small = SMALL
    if small:
        gcfg = GPT2Config(vocab_size=5005, n_positions=max(SEQ_LEN, 64),
                          n_embd=64, n_layer=2, n_head=2)
    else:
        # GPT2-small sized for the PersonaChat tokenizer (50257 + 5
        # special tokens, data/persona.py)
        gcfg = GPT2Config(vocab_size=50262,
                          n_positions=max(SEQ_LEN, 128))
    module = GPT2DoubleHeads(gcfg)

    key = jax.random.PRNGKey(0)
    x0 = jnp.zeros((1, CANDS, SEQ_LEN), jnp.int32)
    params = module.init(key, x0, x0, jnp.zeros((1, CANDS), jnp.int32))
    vec, unravel = flatten_params(params)
    D = int(vec.shape[0])
    bench.log(f"gpt2 bench D={D} small={small} rounds={ROUNDS} "
              f"W={NUM_WORKERS} B={LOCAL_BATCH} L={SEQ_LEN}")

    cfg = Config(
        mode="sketch",
        # the reference flagship geometry RATIOS scaled to this D
        # (utils.py:142-145 is 5 x 500k at D=6.6M -> ~13 coords/cell)
        k=max(D // 130, 1000),
        num_rows=5,
        num_cols=max(D // 13, 10_000),
        num_blocks=20, error_type="virtual", virtual_momentum=0.9,
        local_momentum=0.0, weight_decay=0.0, microbatch_size=-1,
        num_workers=NUM_WORKERS, num_clients=10 * NUM_WORKERS,
        grad_size=D, lm_coef=1.0, mc_coef=1.0,
        # timing loops re-dispatch from one retained (server, clients)
        donate_round_state=False,
    ).validate()

    loss_fn = make_compute_loss_train(module, cfg)

    train_round = fround.make_train_fn(loss_fn, unravel, cfg, mesh)
    server = fround.init_server_state(cfg, vec)
    clients = fround.init_client_state(cfg, cfg.resolved_num_clients(),
                                       vec, mesh=mesh)

    rng = np.random.RandomState(0)
    V = gcfg.vocab_size

    def tok(shape, hi):
        return jnp.asarray(rng.randint(0, hi, shape).astype(np.int32))

    W, B = NUM_WORKERS, LOCAL_BATCH
    input_ids = tok((W, B, CANDS, SEQ_LEN), V)
    mc_token_ids = tok((W, B, CANDS), SEQ_LEN)
    lm_labels = tok((W, B, CANDS, SEQ_LEN), V)
    mc_labels = tok((W, B), CANDS)
    token_type_ids = tok((W, B, CANDS, SEQ_LEN), V)
    data = (input_ids, mc_token_ids, lm_labels, mc_labels, token_type_ids)
    mask = jnp.ones((W, B), jnp.float32)

    batches = fround.RoundBatch(
        jnp.broadcast_to(jnp.arange(W, dtype=jnp.int32), (ROUNDS, W)),
        tuple(jnp.broadcast_to(d, (ROUNDS,) + d.shape) for d in data),
        jnp.broadcast_to(mask, (ROUNDS, W, B)))
    lrs = jnp.full((ROUNDS,), 4e-2)
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
    def one_client_step(params_vec, d):
        def loss(v):
            l, _ = loss_fn(unravel(v),
                           tuple(x[0] for x in d), mask[0])
            return l
        return jax.grad(loss)(params_vec)

    @jax.jit
    def serial_steps(params_vec, d):
        def body(v, _):
            return v - 1e-6 * one_client_step(v, d), None
        v, _ = jax.lax.scan(body, params_vec, None, length=ROUNDS)
        return v.sum()

    with bench.alarm_guard(STAGE_TIMEOUT, "baseline measure"):
        float(np.asarray(serial_steps(vec, data)))  # compile
        ref_round_ms = bench.median_ms(serial_steps, (vec, data),
                                       divisor=ROUNDS) * NUM_WORKERS

    # secondary measurement: the --bf16 round (bf16 client fwd/bwd on
    # the MXU's native path, f32 master weights) — same reporting split
    # as the flagship bench: primary value/vs_baseline stay the f32
    # apples-to-apples comparison with the reference's fp32 CUDA path
    bf16_round_ms = None
    if platform == "tpu":
        try:
            tr_bf16 = fround.make_train_fn(
                loss_fn, unravel, cfg.replace(do_bf16=True), mesh)
            digest_bf16 = bench.make_run_digest(tr_bf16.train_rounds)
            with bench.alarm_guard(STAGE_TIMEOUT, "bf16 compile+measure"):
                float(np.asarray(digest_bf16(server, clients, batches,
                                             lrs, key)))  # compile
                bf16_round_ms = bench.median_ms(
                    digest_bf16, (server, clients, batches, lrs, key),
                    divisor=ROUNDS)
        except bench.StageTimeout:
            bench.log("bf16 measurement timed out; omitting")
        except Exception as e:
            bench.log(f"bf16 measurement failed: {e}")

    out = {
        "metric": "persona_gpt2s_sketch_round_time",
        "value": round(round_ms, 3),
        "unit": "ms/round",
        "vs_baseline": round(ref_round_ms / round_ms, 3),
        "platform": platform,
        "device_kind": device_kind,
        "num_workers": NUM_WORKERS,
        "local_batch": LOCAL_BATCH,
        "seq_len": SEQ_LEN,
        "num_candidates": CANDS,
        "grad_size": D,
    }
    if bf16_round_ms is not None:
        out["value_bf16"] = round(bf16_round_ms, 3)
        out["vs_baseline_bf16"] = round(ref_round_ms / bf16_round_ms, 3)
    bench.add_flops_fields(out, flops_per_round, round_ms, device_kind)
    if bf16_round_ms is not None and out.get("flops_per_round"):
        bf16 = {}
        bench.add_flops_fields(bf16, out["flops_per_round"],
                               bf16_round_ms, device_kind)
        if "mfu" in bf16:
            out["mfu_bf16"] = bf16["mfu"]
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    raise SystemExit(bench.worker_entry(main))

"""Benchmark: wall-clock per federated round, flagship config.

BASELINE.json config #2: ResNet9 on CIFAR10-shaped data, count-sketch
compression (default geometry: 5 x 500k table, 20 blocks, k=50k,
reference utils.py:142-145) + virtual error feedback + virtual
momentum, 8 participating clients per round.

The reference publishes no numbers (BASELINE.md), so vs_baseline is
reported against an analytic stand-in: the reference runs one worker
process per GPU with the per-client loop serialized on each GPU
(fed_worker.py:60), so its round time is bounded below by
num_workers x per-client fwd/bwd; ours runs all clients in one jitted
program. vs_baseline = analytic_reference_round_ms / measured_round_ms
computed on THIS hardware from a measured single-client fwd/bwd step,
i.e. >1.0 means faster than a faithful per-client-serialized port.

One process: the measurement runs here, on the chip JAX finds, and
fails where JAX finds none — there is no CPU stand-in under the same
metric name. Diagnostics go to stderr, stdout carries exactly ONE JSON
line.

Extra fields beyond the required four: platform, device_kind,
flops_per_round (XLA cost analysis), tflops_per_s, mfu (vs the chip's
bf16 peak; a device kind missing from PEAK_TFLOPS is an error).
"""
from __future__ import annotations

import json
import os
import signal
import sys
import time

NUM_WORKERS = int(os.environ.get("BENCH_WORKERS", "8"))
LOCAL_BATCH = int(os.environ.get("BENCH_BATCH", "32"))
ROUNDS = int(os.environ.get("BENCH_ROUNDS", "10"))
# BENCH_SMALL=1 shrinks model + sketch geometry (a quick pass over the
# bench mechanism on the chip; reported numbers are full-size runs)
SMALL = os.environ.get("BENCH_SMALL", "") == "1"
INIT_TIMEOUT = int(os.environ.get("BENCH_INIT_TIMEOUT", "300"))
STAGE_TIMEOUT = int(os.environ.get("BENCH_STAGE_TIMEOUT", "900"))

# bf16 peak TFLOP/s per chip, for the MFU estimate
PEAK_TFLOPS = {
    "TPU v2": 45.0, "TPU v3": 123.0, "TPU v4": 275.0,
    "TPU v5 lite": 197.0, "TPU v5e": 197.0, "TPU v5p": 459.0,
    "TPU v6 lite": 918.0, "TPU v6e": 918.0,
}


def make_run_digest(run):
    """Jit a scanned-round runner `(server, clients, batches, lrs, key)
    -> (server', clients', metrics, bits)` into a single-f32-scalar
    digest: every output feeds the scalar (nothing DCE-able), and the
    sync transfer is 4 bytes — the measurement discipline all benches
    share (see PERF.md 'Measurement rules')."""
    import jax
    import jax.numpy as jnp

    @jax.jit
    def digest(server, clients, batches, lrs, key):
        server2, clients2, m, bits = run(server, clients, batches, lrs,
                                         key)
        leaves = [l for l in jax.tree.leaves(clients2) if l.size > 0]
        client_digest = sum([l.reshape(-1)[0] for l in leaves],
                            jnp.float32(0))
        return (m.losses.mean() + server2.ps_weights[0]
                + bits.sum(dtype=jnp.uint32).astype(jnp.float32)
                + client_digest)
    return digest


def cost_flops(jitted, args, rounds):
    """Per-round FLOPs of an already-compiled jitted call from XLA's
    cost analysis (lower()/compile() hit the trace/executable
    caches)."""
    cost = jitted.lower(*args).compile().cost_analysis()
    if isinstance(cost, (list, tuple)):
        cost = cost[0]
    return float(cost["flops"]) / rounds


def median_ms(fn, args, divisor=1, reps=3):
    """Median wall-clock of fn(*args) in ms / `divisor` (rounds per
    call), syncing each rep through the digest's 4-byte transfer."""
    import numpy as np
    ts = []
    for _ in range(reps):
        t0 = time.perf_counter()
        float(np.asarray(fn(*args)))
        ts.append(time.perf_counter() - t0)
    return float(np.median(ts)) / divisor * 1e3


def add_flops_fields(out, flops_per_round, round_ms, device_kind):
    """Fold flops/TFLOP/s/MFU into a bench JSON dict (shared reporting
    rules: MFU against the chip's bf16 peak from PEAK_TFLOPS; a
    device kind the table does not know is an error, not a default)."""
    tflops_per_s = flops_per_round / (round_ms / 1e3) / 1e12
    out["flops_per_round"] = flops_per_round
    out["tflops_per_s"] = round(tflops_per_s, 3)
    peak = next(v for k, v in PEAK_TFLOPS.items()
                if k.lower() in device_kind.lower())
    out["mfu"] = round(tflops_per_s / peak, 4)


def ce_loss_fn(model):
    """Masked cross-entropy + accuracy loss in the framework's
    `(params, batch, mask) -> (loss, (metrics,))` contract, shared by
    the CV-shaped benches."""
    import jax
    import jax.numpy as jnp

    def loss_fn(params, batch, mask):
        xb, yb = batch
        logits = model.apply(params, xb)
        logp = jax.nn.log_softmax(logits)
        per_ex = -jnp.take_along_axis(logp, yb[:, None], axis=1)[:, 0]
        denom = jnp.maximum(mask.sum(), 1.0)
        loss = (per_ex * mask).sum() / denom
        acc = ((logits.argmax(-1) == yb) * mask).sum() / denom
        return loss, (acc,)
    return loss_fn


def log(msg):
    print(msg, file=sys.stderr, flush=True)


class StageTimeout(Exception):
    pass


class alarm_guard:
    """SIGALRM watchdog: raises StageTimeout if the stage runs past
    `seconds`."""

    def __init__(self, seconds, label):
        self.seconds = seconds
        self.label = label

    def __enter__(self):
        seconds = self.seconds

        def handler(signum, frame):
            raise StageTimeout(self.label)
        self._old = signal.signal(signal.SIGALRM, handler)
        signal.alarm(seconds)

    def __exit__(self, *exc):
        signal.alarm(0)
        signal.signal(signal.SIGALRM, self._old)
        return False


def acquire_backend(want: str = "tpu"):
    """The platform asked for, or a failure: returns (jax, platform)
    when `jax.devices()` is on `want`, exits otherwise. No retry and
    no stand-in platform."""
    import jax

    devs = jax.devices()
    if devs[0].platform != want:
        raise SystemExit(
            f"bench: JAX found {devs[0].platform} "
            f"({devs[0].device_kind}), not a {want}: nothing measured")
    log(f"backend: {devs[0].platform} x{len(devs)} "
        f"({devs[0].device_kind})")
    return jax, devs[0].platform


def main() -> int:
    jax, platform = acquire_backend()
    import jax.numpy as jnp
    import numpy as np

    from commefficient_tpu.utils.cache import (
        enable_persistent_compilation_cache,
    )
    enable_persistent_compilation_cache()

    from commefficient_tpu.config import Config
    from commefficient_tpu.federated import round as fround
    from commefficient_tpu.models import ResNet9
    from commefficient_tpu.ops.flat import flatten_params
    from commefficient_tpu.parallel.mesh import make_client_mesh

    device_kind = jax.devices()[0].device_kind
    mesh = make_client_mesh(min(len(jax.devices()), NUM_WORKERS))

    small = SMALL
    channels = ({"prep": 8, "layer1": 8, "layer2": 8, "layer3": 8}
                if small else None)
    model = ResNet9(num_classes=10, channels=channels)
    x0 = jnp.zeros((LOCAL_BATCH, 32, 32, 3), jnp.float32)
    params = model.init(jax.random.PRNGKey(0), x0)
    vec, unravel = flatten_params(params)
    D = int(vec.shape[0])
    log(f"model D={D} small={small} rounds={ROUNDS}")

    cfg = Config(
        mode="sketch",
        k=500 if small else 50_000,
        num_rows=5,
        num_cols=max(256, D // 13) if small else 500_000,
        num_blocks=20, error_type="virtual", virtual_momentum=0.9,
        local_momentum=0.0, weight_decay=5e-4, microbatch_size=-1,
        num_workers=NUM_WORKERS, num_clients=10 * NUM_WORKERS,
        grad_size=D,
        # BENCH_BF16=1 measures the --bf16 round (bf16 client fwd/bwd,
        # f32 master weights); the baseline stand-in stays f32 either
        # way, since the reference's CUDA path is fp32-only
        do_bf16=os.environ.get("BENCH_BF16", "") == "1",
        # timing loops re-dispatch from ONE retained (server, clients)
        # — donation would delete those operands on the first call
        donate_round_state=False,
    ).validate()

    loss_fn = ce_loss_fn(model)

    def build_digest(cfg_variant):
        """Single-scalar digest for a config variant (make_run_digest
        holds the shared anti-DCE / one-sync rules)."""
        tr = fround.make_train_fn(loss_fn, unravel, cfg_variant, mesh)
        return make_run_digest(tr.train_rounds)

    server = fround.init_server_state(cfg, vec)
    clients = fround.init_client_state(cfg, cfg.resolved_num_clients(),
                                       vec, mesh=mesh)

    rng = np.random.RandomState(0)
    x = jnp.asarray(
        rng.randn(NUM_WORKERS, LOCAL_BATCH, 32, 32, 3).astype(np.float32))
    y = jnp.asarray(
        rng.randint(0, 10, (NUM_WORKERS, LOCAL_BATCH)).astype(np.int32))
    batch = fround.RoundBatch(
        jnp.arange(NUM_WORKERS, dtype=jnp.int32), (x, y),
        jnp.ones((NUM_WORKERS, LOCAL_BATCH), jnp.float32))
    key = jax.random.PRNGKey(0)

    # an epoch-sized span of rounds runs as ONE scanned device program
    # (round.train_rounds), synced through the digest's scalar
    batches = fround.RoundBatch(
        jnp.broadcast_to(batch.client_ids,
                         (ROUNDS,) + batch.client_ids.shape),
        tuple(jnp.broadcast_to(d, (ROUNDS,) + d.shape) for d in batch.data),
        jnp.broadcast_to(batch.mask, (ROUNDS,) + batch.mask.shape))
    lrs = jnp.full((ROUNDS,), 0.1)

    # One jitted digest wrapping the scanned program (build_digest):
    # every output feeds one scalar, so nothing is dead code and the
    # sync is one 4-byte transfer.
    run_digest = build_digest(cfg)

    t0 = time.monotonic()
    with alarm_guard(STAGE_TIMEOUT, "compile+first run"):
        float(np.asarray(run_digest(server, clients, batches, lrs, key)))
    log(f"compile+first run: {time.monotonic() - t0:.1f}s")

    flops_per_round = cost_flops(
        run_digest, (server, clients, batches, lrs, key), ROUNDS)

    with alarm_guard(STAGE_TIMEOUT, "measure"):
        round_ms = median_ms(run_digest,
                             (server, clients, batches, lrs, key),
                             divisor=ROUNDS)

    # analytic reference stand-in: per-client serialized fwd/bwd on
    # this same hardware (measured), x num_workers per round
    def one_client_step(params_vec, xb, yb):
        def loss(v):
            l, _ = loss_fn(unravel(v), (xb, yb), jnp.ones(xb.shape[0]))
            return l
        return jax.grad(loss)(params_vec)

    @jax.jit
    def serial_steps(params_vec, xb, yb):
        def body(v, _):
            return v - 1e-6 * one_client_step(v, xb, yb), None
        v, _ = jax.lax.scan(body, params_vec, None, length=ROUNDS)
        # scalar digest: one 4-byte sync, no DCE (every step feeds v)
        return v.sum()

    with alarm_guard(STAGE_TIMEOUT, "baseline measure"):
        float(np.asarray(serial_steps(vec, x[0], y[0])))  # compile
        ref_round_ms = median_ms(serial_steps, (vec, x[0], y[0]),
                                 divisor=ROUNDS) * NUM_WORKERS

    # secondary measurement: the --bf16 round (TPU-native fast path;
    # f32 master weights). Reported as extra fields — the primary
    # `value`/`vs_baseline` stay the f32 round vs the f32 baseline, the
    # apples-to-apples comparison with the reference's fp32 CUDA path.
    bf16_round_ms = None
    if not cfg.do_bf16 and platform == "tpu":
        try:
            digest_bf16 = build_digest(cfg.replace(do_bf16=True))
            with alarm_guard(STAGE_TIMEOUT, "bf16 compile+measure"):
                float(np.asarray(digest_bf16(server, clients, batches,
                                             lrs, key)))  # compile
                bf16_round_ms = median_ms(
                    digest_bf16, (server, clients, batches, lrs, key),
                    divisor=ROUNDS)
        except StageTimeout:
            log("bf16 measurement timed out; omitting")
        except Exception as e:
            log(f"bf16 measurement failed: {e}")

    # scheduler-mode measurement (ISSUE 5 satellite): the scheduled
    # round is the SAME scanned program carrying the survivor + work
    # operands a deadline-driven round rides (round.py's third traced
    # program) — this measures the device-side cost of scheduling so
    # future BENCH_*.json can compare scheduled vs uniform rounds.
    # Deterministic work fractions emulate a 0.9-quantile deadline
    # truncating ~10% of slots; survivors stay all-ones (idle-slot
    # over-provisioning is the dropout path, already the surv program).
    sched_round_ms = None
    try:
        rngw = np.random.RandomState(7)
        work = np.ones((ROUNDS, NUM_WORKERS), np.float32)
        trunc = rngw.rand(ROUNDS, NUM_WORKERS) < 0.1
        work[trunc] = rngw.uniform(0.5, 0.95, int(trunc.sum()))
        batches_sched = batches._replace(
            survivors=jnp.ones((ROUNDS, NUM_WORKERS), jnp.float32),
            work=jnp.asarray(work))
        with alarm_guard(STAGE_TIMEOUT, "scheduled compile+measure"):
            float(np.asarray(run_digest(server, clients, batches_sched,
                                        lrs, key)))  # compile
            sched_round_ms = median_ms(
                run_digest, (server, clients, batches_sched, lrs, key),
                divisor=ROUNDS)
    except StageTimeout:
        log("scheduled-round measurement timed out; omitting")
    except Exception as e:
        log(f"scheduled-round measurement failed: {e}")

    # kernel-backend + table-dtype sweep (ISSUE 6 satellite): the same
    # scanned program with (a) the compression hot path on the fused
    # Pallas kernels and (b) the sketch table quantized for the wire.
    # Each variant is a config replace -> its own jitted digest under
    # the same one-scalar sync discipline; any variant may time out or
    # fail without killing the primary measurement.
    pallas_round_ms = None
    try:
        digest_pallas = build_digest(cfg.replace(kernel_backend="pallas"))
        with alarm_guard(STAGE_TIMEOUT, "pallas compile+measure"):
            float(np.asarray(digest_pallas(server, clients, batches,
                                           lrs, key)))  # compile
            pallas_round_ms = median_ms(
                digest_pallas, (server, clients, batches, lrs, key),
                divisor=ROUNDS)
    except StageTimeout:
        log("pallas-backend measurement timed out; omitting")
    except Exception as e:
        log(f"pallas-backend measurement failed: {e}")

    table_dtype_ms = {}
    for td in ("bf16", "int8"):
        try:
            digest_td = build_digest(cfg.replace(sketch_table_dtype=td))
            with alarm_guard(STAGE_TIMEOUT, f"{td}-table compile+measure"):
                float(np.asarray(digest_td(server, clients, batches,
                                           lrs, key)))  # compile
                table_dtype_ms[td] = median_ms(
                    digest_td, (server, clients, batches, lrs, key),
                    divisor=ROUNDS)
        except StageTimeout:
            log(f"{td}-table measurement timed out; omitting")
        except Exception as e:
            log(f"{td}-table measurement failed: {e}")

    # robust-aggregator sweep (ISSUE 17): the flagship sketch round
    # with the cross-client reduction swapped for each Byzantine-robust
    # aggregator. All three arms (including `mean`) run the SCREENED
    # program family under --update_screen norm with a zeros poison
    # mask and the screen flag OFF, so the ratios isolate the
    # order-statistic reduction itself — per-client encoded tables
    # gathered, ranked, trimmed/medianed — from the admission-mask
    # plumbing the screened family always carries.
    aggregator_ms = {}
    batches_robust = batches._replace(
        survivors=jnp.ones((ROUNDS, NUM_WORKERS), jnp.float32),
        poison=jnp.zeros((ROUNDS, NUM_WORKERS), jnp.float32),
        screen=jnp.zeros((ROUNDS,), jnp.float32))
    for agg in ("mean", "coord_median", "trimmed_mean"):
        try:
            digest_agg = build_digest(cfg.replace(
                update_screen="norm", aggregator=agg))
            with alarm_guard(STAGE_TIMEOUT,
                             f"{agg}-aggregator compile+measure"):
                float(np.asarray(digest_agg(
                    server, clients, batches_robust, lrs, key)))
                aggregator_ms[agg] = median_ms(
                    digest_agg,
                    (server, clients, batches_robust, lrs, key),
                    divisor=ROUNDS)
        except StageTimeout:
            log(f"{agg}-aggregator measurement timed out; omitting")
        except Exception as e:
            log(f"{agg}-aggregator measurement failed: {e}")

    # compressor-plugin sweep (ISSUE 19): the same workload through
    # the powersgd plugin at rank 1/2/4 and the dp_sketch plugin.
    # These modes carry DIFFERENT state geometry (powersgd: dense [D]
    # server tables + client error/warm-Q rows; dp_sketch: the sketch
    # table plus clip+noise), so each arm initializes its own state —
    # unlike the table-dtype arms, the sketch operands cannot be
    # reused.
    def _mode_cfg(name, **kw):
        return cfg.replace(mode=name, **kw).validate()

    comp_arms = []
    for r in (1, 2, 4):
        comp_arms.append((f"powersgd_r{r}", _mode_cfg(
            "powersgd", error_type="local", powersgd_rank=r)))
    comp_arms.append(("dp_sketch", _mode_cfg(
        "dp_sketch", dp_clip=1.0, dp_noise_mult=1.0)))
    compressor_ms = {}
    compressor_bytes = {}
    for name, cfg_c in comp_arms:
        compressor_bytes[name] = int(cfg_c.upload_bytes)
        try:
            server_c = fround.init_server_state(cfg_c, vec)
            clients_c = fround.init_client_state(
                cfg_c, cfg_c.resolved_num_clients(), vec, mesh=mesh)
            digest_c = build_digest(cfg_c)
            with alarm_guard(STAGE_TIMEOUT,
                             f"{name} compile+measure"):
                float(np.asarray(digest_c(
                    server_c, clients_c, batches, lrs, key)))
                compressor_ms[name] = median_ms(
                    digest_c,
                    (server_c, clients_c, batches, lrs, key),
                    divisor=ROUNDS)
        except StageTimeout:
            log(f"{name} measurement timed out; omitting")
        except Exception as e:
            log(f"{name} measurement failed: {e}")
    # exact bytes one client ships per round in every mode at THIS
    # geometry (Config.upload_bytes — the figure the accountant
    # bills): pure config math, reported even when a timing arm fails
    bytes_per_mode = {"sketch": int(cfg.upload_bytes),
                      **compressor_bytes}
    for name, kw in (
            ("true_topk", dict(error_type="virtual")),
            ("local_topk", dict(error_type="local")),
            ("fedavg", dict(error_type="none", virtual_momentum=0.9,
                            local_batch_size=-1,
                            fedavg_batch_size=LOCAL_BATCH)),
            ("uncompressed", dict(error_type="none"))):
        try:
            bytes_per_mode[name] = int(_mode_cfg(name,
                                                 **kw).upload_bytes)
        except Exception as e:
            log(f"{name} bytes-on-wire config failed: {e}")

    out = {
        "metric": "cifar10_resnet9_sketch_round_time",
        "value": round(round_ms, 3),
        "unit": "ms/round",
        "vs_baseline": round(ref_round_ms / round_ms, 3),
        "platform": platform,
        "device_kind": device_kind,
        "num_workers": NUM_WORKERS,
        "local_batch": LOCAL_BATCH,
        "grad_size": D,
    }
    if cfg.do_bf16:
        out["bf16"] = True
    if bf16_round_ms is not None:
        out["value_bf16"] = round(bf16_round_ms, 3)
        out["vs_baseline_bf16"] = round(ref_round_ms / bf16_round_ms, 3)
    if sched_round_ms is not None:
        # scheduled (survivor+work operand) round next to the uniform
        # one: vs_uniform < 1.0 means the scheduling operands cost
        # device time, > 1.0 means the truncated work actually saved it
        out["value_scheduled"] = round(sched_round_ms, 3)
        out["vs_uniform_scheduled"] = round(round_ms / sched_round_ms, 3)
    if pallas_round_ms is not None:
        # fused-kernel round next to the XLA one: vs_xla_backend > 1.0
        # means the Pallas hot path is faster than the XLA lowering of
        # the same math
        out["value_pallas"] = round(pallas_round_ms, 3)
        out["vs_xla_backend"] = round(round_ms / pallas_round_ms, 3)
    for td, ms in sorted(table_dtype_ms.items()):
        out[f"value_table_{td}"] = round(ms, 3)
    for agg, ms in sorted(aggregator_ms.items()):
        # screened-family arms: value_agg_mean is the apples-to-apples
        # denominator for the robust ratios (same operands, mean
        # reduction); vs_mean_<agg> > 1.0 means the order statistics
        # cost device time over the psum-mean
        out[f"value_agg_{agg}"] = round(ms, 3)
    if "mean" in aggregator_ms:
        for agg, ms in sorted(aggregator_ms.items()):
            if agg != "mean":
                out[f"vs_mean_{agg}"] = round(
                    ms / aggregator_ms["mean"], 3)
    # bytes one client's sketch upload occupies per round at each wire
    # dtype (Config.upload_bytes — the figure the accountant bills):
    # the bytes-on-wire dimension of the sweep, reported even when a
    # timing variant failed, since it is pure config math
    out["upload_bytes_on_wire"] = {
        td: cfg.replace(sketch_table_dtype=td).upload_bytes
        for td in ("f32", "bf16", "int8")}
    for name, ms in sorted(compressor_ms.items()):
        # compressor-plugin arms (ISSUE 19): vs_sketch_<name> > 1.0
        # means the plugin round is faster than the flagship sketch
        out[f"value_{name}"] = round(ms, 3)
        out[f"vs_sketch_{name}"] = round(round_ms / ms, 3)
    out["bytes_on_wire_per_mode"] = dict(sorted(bytes_per_mode.items()))
    add_flops_fields(out, flops_per_round, round_ms, device_kind)
    journal_digest(out, "bench_digest")
    print(json.dumps(out), flush=True)
    return 0


def population_main() -> int:
    """ISSUE 9 population sweep: the O(population) -> O(cohort) claim
    as numbers. For num_clients in {1e3, 1e5, 1e6} (tiny D so the
    sharded [population, D] blocks fit anywhere, local_topk so all
    three state blocks exist) it measures, per population:

      * round_ms             wall-clock of the three-program dispatch
                             (cohort-gather -> round -> scatter-back)
      * round_operand_bytes  bytes entering the jitted ROUND program
                             (server + cohort + batch + lr + key) —
                             must stay FLAT as the population grows
      * device_state_bytes   the sharded [padded_population, D] blocks
                             (the one remaining O(population) term, by
                             design: it shards across hosts)
      * checkpoint_bytes     a sparse (crows_*) save after two rounds
                             — must stay FLAT
      * host_state_bytes     tracker + accountant host state after the
                             same rounds — O(clients-ever-seen)
      * device_hbm_bytes     ISSUE 11: the same rounds under
                             state_tier=host with a FIXED
                             --state_working_set — the device-resident
                             client-state block; must be EXACTLY flat
                             1e3 -> 1e6 (the residency claim as a
                             number), with nonzero spills proving the
                             tier actually moved rows

    Runs in-process (CPU-friendly: ~200 MB at the 1e6 point); invoked
    via BENCH_POPULATION=1 or `python bench.py --population`. The
    result is journaled as a bench_digest.
    """
    import tempfile

    import numpy as np

    with alarm_guard(INIT_TIMEOUT, "backend init"):
        import jax
        import jax.numpy as jnp
        platform = jax.devices()[0].platform

    from commefficient_tpu.config import Config
    from commefficient_tpu.federated import round as fround
    from commefficient_tpu.federated.accounting import CommAccountant
    from commefficient_tpu.ops.flat import flatten_params
    from commefficient_tpu.parallel.mesh import make_client_mesh
    from commefficient_tpu.telemetry.clients import (
        ClientThroughputTracker,
    )
    from commefficient_tpu.utils.checkpoint import save_checkpoint

    Dp, Wp, Bp, ROUNDS_P = 16, 64, 4, 3
    # the tiered arm's fixed device working set (ISSUE 11): < the
    # distinct clients the rounds sample at every population, so
    # spills are forced, while >= Wp so each cohort fits
    TIER_WS = 128
    n_dev = len(jax.devices())
    n_mesh = 1
    for n in range(min(n_dev, Wp), 0, -1):
        if Wp % n == 0:
            n_mesh = n
            break
    mesh = make_client_mesh(n_mesh)
    log(f"population sweep on {platform} ({n_mesh}-way clients mesh)")

    def loss_fn(params, batch, mask):
        x, y = batch
        pred = x @ params["w"]
        per_ex = 0.5 * (pred - y) ** 2
        loss = (per_ex * mask).sum() / jnp.maximum(mask.sum(), 1.0)
        return loss, (loss,)

    params = {"w": jnp.zeros(Dp, jnp.float32)}
    vec, unravel = flatten_params(params)
    rng = np.random.RandomState(0)
    x = jnp.asarray(rng.randn(Wp, Bp, Dp).astype(np.float32))
    y = jnp.asarray(rng.randn(Wp, Bp).astype(np.float32))
    mask = jnp.ones((Wp, Bp), jnp.float32)
    key = jax.random.PRNGKey(0)

    def tree_bytes(tree):
        import jax as _j
        return int(sum(int(getattr(l, "nbytes", 0))
                       for l in _j.tree.leaves(tree)))

    def state_dict_bytes(sd):
        return int(sum(np.asarray(v).nbytes for v in sd.values()))

    sweep = {}
    for pop in (1_000, 100_000, 1_000_000):
        cfg = Config(
            mode="local_topk", error_type="local", local_momentum=0.9,
            do_topk_down=True, k=8, down_k=16, grad_size=Dp,
            weight_decay=0.0, num_workers=Wp, microbatch_size=-1,
            num_clients=pop, seed=0).validate()
        with alarm_guard(STAGE_TIMEOUT, f"pop={pop} build"):
            tr = fround.make_train_fn(loss_fn, unravel, cfg, mesh)
            server = fround.init_server_state(cfg, vec, mesh=mesh)
            clients = fround.init_client_state(cfg, pop, vec,
                                               mesh=mesh)
        device_state_bytes = tree_bytes(clients)
        ids_rounds = [rng.choice(pop, Wp, replace=False)
                      .astype(np.int32) for _ in range(ROUNDS_P)]
        tracker = ClientThroughputTracker(pop)
        acct = CommAccountant(cfg, pop)
        prev = None

        def one_round(server, clients, ids):
            b = fround.RoundBatch(jnp.asarray(ids), (x, y), mask)
            return tr(server, clients, b, 0.1, key)

        with alarm_guard(STAGE_TIMEOUT, f"pop={pop} rounds"):
            t_rounds = []
            for n, ids in enumerate(ids_rounds):
                t0 = time.perf_counter()
                server, clients, m = one_round(server, clients, ids)
                # block on a cohort-sized output (the 4-byte-class
                # sync every bench uses)
                float(np.asarray(m.losses).sum())
                t_rounds.append(time.perf_counter() - t0)
                tracker.update_round(ids, np.full(Wp, float(Bp)),
                                     round_seconds=t_rounds[-1])
                d, u = acct.record_round(ids, prev)
                prev = np.zeros(acct.n_words, np.uint32)
            round_ms = float(np.median(t_rounds[1:])) * 1e3

        # the round program's operand bytes: what actually crosses
        # into the jitted round — cohort rows, never the population
        cohort = tr.gather(clients, jnp.asarray(ids_rounds[-1]))
        batch = fround.RoundBatch(jnp.asarray(ids_rounds[-1]), (x, y),
                                  mask)
        round_operand_bytes = (tree_bytes(server) + tree_bytes(cohort)
                               + tree_bytes(batch) + 4
                               + tree_bytes(key))

        # sparse checkpoint: touched rows only (the drivers'
        # client_rows payload, assembled here without a FedModel)
        touched = np.unique(np.concatenate(ids_rounds)).astype(np.int64)
        gidx = jnp.asarray(touched.astype(np.int32))
        payload = {
            "ids": touched,
            "errors": np.asarray(clients.errors[gidx]),
            "velocities": np.asarray(clients.velocities[gidx]),
            "weights": np.asarray(clients.weights[gidx]),
            "base_weights": np.asarray(vec, np.float32),
        }
        with tempfile.TemporaryDirectory() as td:
            p = os.path.join(td, "ck.npz")
            save_checkpoint(p, server, clients=None,
                            client_rows=payload,
                            accountant=acct,
                            throughput=tracker.state_dict())
            checkpoint_bytes = os.path.getsize(p)

        host_state_bytes = (state_dict_bytes(tracker.state_dict())
                            + state_dict_bytes(acct.state_dict()))

        # tiered residency arm (ISSUE 11): the same rounds behind
        # state_tier=host at a FIXED working set — device HBM for
        # client state is the bounded [working_set, D] block, flat in
        # the population, while spills prove rows actually moved
        from commefficient_tpu.federated.statestore import (
            TieredStateStore,
        )
        cfg_t = cfg.replace(state_tier="host",
                            state_working_set=TIER_WS).validate()
        with alarm_guard(STAGE_TIMEOUT, f"pop={pop} tiered"):
            tr_t = fround.make_train_fn(loss_fn, unravel, cfg_t, mesh)
            server_t = fround.init_server_state(cfg_t, vec)
            block = fround.init_client_state(
                cfg_t, fround.client_state_rows(cfg_t, pop), vec,
                mesh=mesh)
            store = TieredStateStore(cfg_t, mesh, tr_t, vec, pop)
            for ids in ids_rounds:
                plan = store.plan_round(ids)
                block = store.execute(block, plan)
                b = fround.RoundBatch(jnp.asarray(plan.slots), (x, y),
                                      mask)
                server_t, block, m_t = tr_t(server_t, block, b, 0.1,
                                            key)
            float(np.asarray(m_t.losses).sum())
            store.flush()
        device_hbm_bytes = tree_bytes(block)
        tier_spills = int(store.spills)
        store.close()
        del server_t, block, tr_t, store

        sweep[str(pop)] = {
            "round_ms": round(round_ms, 3),
            "round_operand_bytes": round_operand_bytes,
            "device_state_bytes": device_state_bytes,
            "checkpoint_bytes": checkpoint_bytes,
            "host_state_bytes": host_state_bytes,
            "device_hbm_bytes": device_hbm_bytes,
            "tier_spills": tier_spills,
        }
        log(f"pop={pop}: {sweep[str(pop)]}")
        del server, clients, tr

    flat = [sweep[k]["round_operand_bytes"] for k in sweep]
    ck = [sweep[k]["checkpoint_bytes"] for k in sweep]
    hbm = [sweep[k]["device_hbm_bytes"] for k in sweep]
    out = {
        "metric": "client_state_population_sweep",
        "value": sweep["1000000"]["round_ms"],
        "unit": "ms/round",
        "vs_baseline": None,
        "platform": platform,
        "geometry": {"D": Dp, "num_workers": Wp, "local_batch": Bp,
                     "mode": "local_topk",
                     "state_working_set": TIER_WS},
        "populations": sweep,
        # the acceptance claims, as booleans the artifact itself checks
        "round_operands_flat": len(set(flat)) == 1,
        "checkpoint_flat": max(ck) <= min(ck) + 65536,
        # ISSUE 11: device-HBM client-state bytes EXACTLY flat under
        # the fixed working-set cap, with the tier demonstrably live
        "device_hbm_flat": len(set(hbm)) == 1,
        "tier_spills_nonzero": all(
            sweep[k]["tier_spills"] > 0 for k in sweep),
    }
    journal_digest(out, "bench_digest")
    print(json.dumps(out), flush=True)
    return 0


def pipeline_main() -> int:
    """ISSUE 10 pipeline sweep: round-cadence histogram, synchronous
    vs pipelined, measured on the REAL scanned staging loop
    (training/scanloop.run_scanned_rounds + FedModel) with the full
    persistence load armed — per-span journal fsyncs and per-span
    rotated checkpoints — because that host work is exactly what the
    pipeline moves off the critical path.

    Both arms drive the identical synthetic stream (scan_span=1, so
    every round is a span boundary = worst-case persistence cadence);
    the histogram is computed from the JOURNAL's own round events
    (consecutive `ts` diffs — the artifact a production cadence
    investigation would read), warmup spans dropped. Reported:
    p50/p95 inter-round seconds per arm and `vs_sync` = pipelined p50
    / sync p50 (< 1.0 = the pipeline shortened the critical path).
    In-process and CPU-friendly; invoked via BENCH_PIPELINE=1 or
    `python bench.py --pipeline`. Lands in BENCH_r10.json."""
    import tempfile

    import numpy as np

    with alarm_guard(INIT_TIMEOUT, "backend init"):
        import jax
        import jax.numpy as jnp
        platform = jax.devices()[0].platform

    from commefficient_tpu.config import Config
    from commefficient_tpu.federated.api import FedModel, FedOptimizer
    from commefficient_tpu.telemetry import TelemetrySession
    from commefficient_tpu.telemetry.journal import (
        RunJournal, read_journal, validate_journal,
    )
    from commefficient_tpu.training.scanloop import (
        make_span_checkpoint, run_scanned_rounds,
    )
    from commefficient_tpu.utils.schedules import LambdaLR

    Dp = int(os.environ.get("BENCH_PIPELINE_D", "65536"))
    Wp, Bp = 8, 32
    ROUNDS_P = int(os.environ.get("BENCH_PIPELINE_ROUNDS", "40"))
    WARMUP = 8
    log(f"pipeline cadence sweep on {platform} "
        f"(D={Dp}, {ROUNDS_P} rounds, span=1)")

    def loss_fn(params, batch, mask):
        x, y = batch
        pred = x @ params["w"]
        per_ex = 0.5 * (pred - y) ** 2
        loss = (per_ex * mask).sum() / jnp.maximum(mask.sum(), 1.0)
        return loss, (loss,)

    # lr small enough that the repeated-batch regression stays finite
    # over the whole sweep: the bit-identity check below compares the
    # final weights, and NaN != NaN would mask a real divergence
    LR = 1e-4
    rng = np.random.RandomState(0)
    x = rng.randn(Wp, Bp, Dp).astype(np.float32)
    y = rng.randn(Wp, Bp).astype(np.float32)
    ids = np.arange(Wp, dtype=np.int32)
    mask = np.ones((Wp, Bp), np.float32)
    stream = [(r, ids, (x, y), mask, LR) for r in range(ROUNDS_P)]

    def run_arm(pipeline: bool, workdir: str) -> dict:
        cfg = Config(
            mode="uncompressed", error_type="none", local_momentum=0.0,
            virtual_momentum=0.9, grad_size=Dp, weight_decay=0.0,
            num_workers=Wp, microbatch_size=-1, num_clients=Wp,
            checkpoint_every=1, ckpt_every_spans=1, keep_checkpoints=2,
            pipeline=pipeline, seed=0).validate()
        model = FedModel(None, loss_fn, cfg,
                         params={"w": jnp.zeros(Dp, jnp.float32)})
        opt = FedOptimizer(model)
        opt.param_groups[0]["lr"] = LR
        sch = LambdaLR(opt, lr_lambda=lambda s: 1.0)
        jpath = os.path.join(workdir, "journal.jsonl")
        tele = TelemetrySession(journal=RunJournal(
            jpath, run_id="bench", async_writer=pipeline))
        model.attach_telemetry(tele)
        hook = make_span_checkpoint(
            os.path.join(workdir, "ck"), model, cfg, sch)
        with alarm_guard(STAGE_TIMEOUT,
                         f"pipeline={pipeline} rounds"):
            t0 = time.perf_counter()
            ok = run_scanned_rounds(model, iter(stream), 1,
                                    lambda *a: True, checkpoint=hook,
                                    pipeline=pipeline)
            assert ok
            wall = time.perf_counter() - t0
        model.close_persistence()
        tele.close(ok=True)
        recs, problems = validate_journal(jpath)
        assert not problems, problems
        # inter-round gaps on the MONOTONIC stamp (ISSUE 13): a wall-
        # clock `ts` diff is not a duration — an NTP step mid-sweep
        # would corrupt the cadence histogram (graftlint GL011's
        # hazard class, held out of the journal-reading path too)
        ts = [r.get("mono", r["ts"]) for r in recs
              if r.get("event") == "round"]
        gaps = np.diff(np.asarray(ts, np.float64))[WARMUP:]
        weights = np.asarray(model.server.ps_weights)
        assert np.all(np.isfinite(weights)), \
            "bench workload diverged — lower LR"
        return {
            "p50_inter_round_s": round(float(np.percentile(gaps, 50)),
                                       6),
            "p95_inter_round_s": round(float(np.percentile(gaps, 95)),
                                       6),
            "rounds": len(ts),
            "wall_s": round(wall, 3),
            "final_weights": weights,
        }

    with tempfile.TemporaryDirectory() as td_s, \
            tempfile.TemporaryDirectory() as td_p:
        sync = run_arm(False, td_s)
        pipe = run_arm(True, td_p)

    # the two arms ran the identical stream: their final state must
    # agree bit-for-bit (the overlap reorders host work only)
    bit_identical = bool(np.array_equal(sync.pop("final_weights"),
                                        pipe.pop("final_weights")))
    vs_sync = (pipe["p50_inter_round_s"] / sync["p50_inter_round_s"]
               if sync["p50_inter_round_s"] > 0 else None)
    out = {
        "metric": "pipelined_round_cadence",
        "value": pipe["p50_inter_round_s"],
        "unit": "s/round (p50 inter-round, journal round events)",
        "vs_baseline": None,
        "vs_sync": None if vs_sync is None else round(vs_sync, 4),
        "platform": platform,
        "geometry": {"D": Dp, "num_workers": Wp, "local_batch": Bp,
                     "rounds": ROUNDS_P, "scan_span": 1,
                     "ckpt_every_spans": 1, "mode": "uncompressed"},
        "sync": sync,
        "pipelined": pipe,
        "bit_identical": bit_identical,
    }
    journal_digest(out, "bench_digest")
    print(json.dumps(out), flush=True)
    return 0


def control_main() -> int:
    """ISSUE 20 self-tuning control sweep: per-round cadence under a
    heavy straggler load (straggler_rate 0.6), static scan_span=1 vs
    the adaptive span palette (1,2,4) with all three feedback
    controllers live — cohort speed matching, adaptive span cadence,
    and adaptive staleness decay — on the REAL scanned staging loop
    with the full per-span persistence load armed (journal fsyncs +
    rotated checkpoints), because amortizing that host work over
    bigger spans is exactly the lever the cadence controller tunes.

    Both arms drive the identical throughput-sampled stream through
    the pipelined engine; the metric is the p50/p95 of the JOURNAL's
    per-round `seconds` (the span wall amortized per round — rounds
    inside one scanned span share a collect stamp, so raw inter-event
    gaps would be bursty, not a cadence), warmup rounds dropped.
    Reported: p50/p95 per-round seconds per arm, `vs_static` =
    adaptive p95 / static p95 (< 1.0 = the controllers shortened the
    straggler-dominated tail), and the per-controller journaled
    adjustment counts — an inert controller fails the run. In-process
    and CPU-friendly; invoked via BENCH_CONTROL=1 or
    `python bench.py --control`. Lands in BENCH_r20.json."""
    import tempfile

    import numpy as np

    with alarm_guard(INIT_TIMEOUT, "backend init"):
        import jax
        import jax.numpy as jnp
        platform = jax.devices()[0].platform

    from commefficient_tpu.config import Config
    from commefficient_tpu.data.sampler import FedSampler
    from commefficient_tpu.federated.api import FedModel, FedOptimizer
    from commefficient_tpu.scheduler import RoundScheduler
    from commefficient_tpu.telemetry import TelemetrySession
    from commefficient_tpu.telemetry.journal import (
        RunJournal, summarize, validate_journal,
    )
    from commefficient_tpu.training.scanloop import (
        make_span_checkpoint, run_scanned_rounds,
    )
    from commefficient_tpu.utils.schedules import LambdaLR

    Dc = int(os.environ.get("BENCH_CONTROL_D", "32768"))
    Wc, Bc, NCc = 8, 32, 16
    ROUNDS_C = int(os.environ.get("BENCH_CONTROL_ROUNDS", "48"))
    WARMUP = 8
    log(f"self-tuning control sweep on {platform} "
        f"(D={Dc}, {ROUNDS_C} rounds, straggler_rate=0.6)")

    def loss_fn(params, batch, mask):
        x, y = batch
        pred = x @ params["w"]
        per_ex = 0.5 * (pred - y) ** 2
        loss = (per_ex * mask).sum() / jnp.maximum(mask.sum(), 1.0)
        return loss, (loss,)

    LR = 1e-4
    rng = np.random.RandomState(0)
    x = rng.randn(NCc, Bc, Dc).astype(np.float32)
    y = rng.randn(NCc, Bc).astype(np.float32)

    def run_arm(adaptive: bool, workdir: str) -> dict:
        knobs = (dict(scan_span_palette="1,2,4", speed_match=True,
                      adapt_staleness=True)
                 if adaptive else dict(scan_span=1))
        cfg = Config(
            mode="uncompressed", error_type="none", local_momentum=0.0,
            virtual_momentum=0.9, grad_size=Dc, weight_decay=0.0,
            num_workers=Wc, microbatch_size=-1, num_clients=NCc,
            sampler="throughput", async_admit_rounds=1,
            straggler_rate=0.6, straggler_min_work=0.4,
            scan_rounds=True, pipeline=True,
            checkpoint_every=1, ckpt_every_spans=1, keep_checkpoints=2,
            seed=0, **knobs).validate()
        model = FedModel(None, loss_fn, cfg,
                         params={"w": jnp.zeros(Dc, jnp.float32)})
        opt = FedOptimizer(model)
        opt.param_groups[0]["lr"] = LR
        sch = LambdaLR(opt, lr_lambda=lambda s: 1.0)
        smp = FedSampler(np.full(NCc, Bc), Wc, Bc, seed=7)
        sched = RoundScheduler(cfg, model.num_clients, model.throughput)
        smp.scheduler = sched
        model.attach_scheduler(sched)
        model.attach_data_sampler(smp)
        jpath = os.path.join(workdir, "journal.jsonl")
        tele = TelemetrySession(journal=RunJournal(
            jpath, run_id="bench", async_writer=True))
        model.attach_telemetry(tele)
        hook = make_span_checkpoint(
            os.path.join(workdir, "ck"), model, cfg, sch)
        done = [0]

        def stream():
            while done[0] < ROUNDS_C:
                sched.begin_epoch(done[0])
                for ids, idx, mask in smp.epoch():
                    ids_arr = np.asarray(ids)
                    yield (done[0], ids_arr,
                           (x[ids_arr[:, None], idx],
                            y[ids_arr[:, None], idx]), mask, LR)
                    done[0] += 1
                    if done[0] >= ROUNDS_C:
                        return

        with alarm_guard(STAGE_TIMEOUT,
                         f"adaptive={adaptive} rounds"):
            t0 = time.perf_counter()
            ok = run_scanned_rounds(model, stream(),
                                    model.control_bank or 1,
                                    lambda *a: True, checkpoint=hook,
                                    pipeline=True)
            assert ok
            wall = time.perf_counter() - t0
        model.close_persistence()
        tele.close(ok=True)
        recs, problems = validate_journal(jpath)
        assert not problems, problems
        secs = np.asarray([r["seconds"] for r in recs
                           if r.get("event") == "round"],
                          np.float64)[WARMUP:]
        weights = np.asarray(model.server.ps_weights)
        assert np.all(np.isfinite(weights)), \
            "bench workload diverged — lower LR"
        ctls = summarize(recs).get("controllers", {})
        return {
            "p50_round_s": round(float(np.percentile(secs, 50)), 6),
            "p95_round_s": round(float(np.percentile(secs, 95)), 6),
            "rounds": int(len(secs) + WARMUP),
            "wall_s": round(wall, 3),
            "adjustments": {n: v["adjustments"]
                            for n, v in sorted(ctls.items())},
        }

    with tempfile.TemporaryDirectory() as td_s, \
            tempfile.TemporaryDirectory() as td_a:
        static = run_arm(False, td_s)
        adaptive = run_arm(True, td_a)

    want = {"speed_match", "span_cadence", "staleness_decay"}
    inert = sorted(want - {n for n, c in adaptive["adjustments"].items()
                           if c >= 1})
    assert not inert, f"controller(s) never adjusted: {inert}"
    vs_static = (adaptive["p95_round_s"] / static["p95_round_s"]
                 if static["p95_round_s"] > 0 else None)
    out = {
        "metric": "self_tuning_round_cadence",
        "value": adaptive["p95_round_s"],
        "unit": "s/round (p95 per-round seconds, journal round events)",
        "vs_baseline": None,
        "vs_static": None if vs_static is None else round(vs_static, 4),
        "platform": platform,
        "geometry": {"D": Dc, "num_workers": Wc, "local_batch": Bc,
                     "num_clients": NCc, "rounds": ROUNDS_C,
                     "straggler_rate": 0.6, "span_palette": "1,2,4",
                     "ckpt_every_spans": 1, "mode": "uncompressed"},
        "static": static,
        "adaptive": adaptive,
    }
    journal_digest(out, "bench_digest")
    print(json.dumps(out), flush=True)
    return 0


def trace_main() -> int:
    """ISSUE 13 graftscope arm: the pipelined cadence workload of
    pipeline_main rerun with --trace armed, so the bench digest gains
    the STAGE-RESOLVED view — per-stage p50 seconds, writer queue
    gauges, and the pipeline overlap-efficiency metric (device-busy /
    wall over the device_execute spans) — turning BENCH_r10's one-off
    0.79x cadence claim into a continuously-measured number. Every
    duration comes from monotonic span records, never wall-clock
    diffs. In-process and CPU-friendly; invoked via BENCH_TRACE=1 or
    `python bench.py --trace`. Lands in BENCH_r13.json."""
    import tempfile

    import numpy as np

    with alarm_guard(INIT_TIMEOUT, "backend init"):
        import jax
        import jax.numpy as jnp
        platform = jax.devices()[0].platform

    from commefficient_tpu.config import Config
    from commefficient_tpu.federated.api import FedModel, FedOptimizer
    from commefficient_tpu.telemetry import TelemetrySession
    from commefficient_tpu.telemetry.journal import (
        RunJournal, summarize, validate_journal,
    )
    from commefficient_tpu.training.scanloop import (
        make_span_checkpoint, run_scanned_rounds,
    )
    from commefficient_tpu.utils.schedules import LambdaLR

    Dp = int(os.environ.get("BENCH_TRACE_D", "65536"))
    Wp, Bp = 8, 32
    ROUNDS_T = int(os.environ.get("BENCH_TRACE_ROUNDS", "40"))
    WARMUP = 8
    log(f"graftscope stage sweep on {platform} "
        f"(D={Dp}, {ROUNDS_T} rounds, span=1, trace on)")

    def loss_fn(params, batch, mask):
        x, y = batch
        pred = x @ params["w"]
        per_ex = 0.5 * (pred - y) ** 2
        loss = (per_ex * mask).sum() / jnp.maximum(mask.sum(), 1.0)
        return loss, (loss,)

    LR = 1e-4
    rng = np.random.RandomState(0)
    x = rng.randn(Wp, Bp, Dp).astype(np.float32)
    y = rng.randn(Wp, Bp).astype(np.float32)
    ids = np.arange(Wp, dtype=np.int32)
    mask = np.ones((Wp, Bp), np.float32)
    stream = [(r, ids, (x, y), mask, LR) for r in range(ROUNDS_T)]

    with tempfile.TemporaryDirectory() as td:
        cfg = Config(
            mode="uncompressed", error_type="none", local_momentum=0.0,
            virtual_momentum=0.9, grad_size=Dp, weight_decay=0.0,
            num_workers=Wp, microbatch_size=-1, num_clients=Wp,
            checkpoint_every=1, ckpt_every_spans=1, keep_checkpoints=2,
            pipeline=True, trace=True, seed=0).validate()
        model = FedModel(None, loss_fn, cfg,
                         params={"w": jnp.zeros(Dp, jnp.float32)})
        opt = FedOptimizer(model)
        opt.param_groups[0]["lr"] = LR
        sch = LambdaLR(opt, lr_lambda=lambda s: 1.0)
        jpath = os.path.join(td, "journal.jsonl")
        tele = TelemetrySession(
            journal=RunJournal(jpath, run_id="bench",
                               async_writer=True),
            trace=True)
        model.attach_telemetry(tele)
        hook = make_span_checkpoint(os.path.join(td, "ck"), model,
                                    cfg, sch)
        with alarm_guard(STAGE_TIMEOUT, "traced pipelined rounds"):
            t0 = time.perf_counter()
            ok = run_scanned_rounds(model, iter(stream), 1,
                                    lambda *a: True, checkpoint=hook,
                                    pipeline=True)
            assert ok
            wall = time.perf_counter() - t0
        model.close_persistence()
        tele.close(ok=True)
        recs, problems = validate_journal(jpath)
        assert not problems, problems
        weights = np.asarray(model.server.ps_weights)
        assert np.all(np.isfinite(weights)), \
            "bench workload diverged — lower LR"
        summary = summarize(recs)
        mono = [r["mono"] for r in recs if r.get("event") == "round"]
        gaps = np.diff(np.asarray(mono, np.float64))[WARMUP:]

    stages = summary.get("trace_stages", {})
    out = {
        "metric": "stage_resolved_round_cadence",
        "value": round(float(np.percentile(gaps, 50)), 6),
        "unit": "s/round (p50 inter-round, monotonic journal stamps)",
        "vs_baseline": None,
        "platform": platform,
        "geometry": {"D": Dp, "num_workers": Wp, "local_batch": Bp,
                     "rounds": ROUNDS_T, "scan_span": 1,
                     "ckpt_every_spans": 1, "mode": "uncompressed",
                     "pipeline": True, "trace": True},
        "p95_inter_round_s": round(float(np.percentile(gaps, 95)), 6),
        "wall_s": round(wall, 3),
        # the stage-resolved cadence baseline: per-stage p50 seconds
        # over the whole sweep (ISSUE 13 acceptance)
        "stage_p50_s": {name: st["p50_s"]
                        for name, st in sorted(stages.items())},
        "stage_p95_s": {name: st["p95_s"]
                        for name, st in sorted(stages.items())},
        "overlap_efficiency": summary.get("overlap_efficiency"),
        "writer_queue_max": summary.get("writer_queue_max", {}),
        "trace_spans": summary.get("trace_spans", 0),
    }
    journal_digest(out, "bench_digest")
    print(json.dumps(out), flush=True)
    return 0


def worker_entry(main_fn) -> int:
    """Run `main_fn` here, in this process; a stage that timed out is
    exit code 3."""
    try:
        return main_fn() or 0
    except StageTimeout as e:
        log(f"FATAL: stage timed out: {e}")
        return 3


def artifact_dest(path: str, platform: str) -> str:
    """Where a results-JSON should be written so a CPU-degraded rerun
    never clobbers a landed TPU artifact: if `path` already records
    platform=="tpu" (top-level or under "config") and this run is not
    TPU, divert to the *_cpu.json sibling. Shared by every
    file-artifact measurement script (gpt2_full_smoke, real_format_data,
    convergence)."""
    if platform == "tpu" or not os.path.isfile(path):
        return path
    try:
        with open(path) as f:
            rec = json.load(f)
    except Exception:
        return path
    plat = None
    if isinstance(rec, dict):
        plat = (rec.get("platform")
                or rec.get("config", {}).get("platform"))
    if plat == "tpu":
        return path.replace(".json", "_cpu.json")
    return path


def _static_ulp_bounds():
    """Per-program worst-case psum-reassociation ulp bound from the
    graftnum baseline (ISSUE 18 satellite): the static twin of the
    measured round-time metric, so a BENCH_*.json consumer weighing
    the quantization estimate-residual trade-off reads the numeric
    headroom and the speed from one record. Read from the shipped
    exact-match baseline — tier-1 gates it against a fresh trace every
    run — rather than re-tracing inside the bench process."""
    try:
        here = os.path.dirname(os.path.abspath(__file__))
        with open(os.path.join(here, "graftnum.baseline.json")) as f:
            base = json.load(f)
        ulp = {k: int(v["worst_case_ulp"])
               for k, v in (base.get("ulp") or {}).items()
               if isinstance(v, dict) and "worst_case_ulp" in v}
        if not ulp:
            return None
        return {"per_program": ulp, "max": max(ulp.values())}
    except (OSError, KeyError, TypeError, ValueError):
        return None


def journal_digest(out, kind):
    """Append a bench digest to the shared telemetry journal (ISSUE 4
    satellite: BENCH_*.json records and training runs share one
    versioned JSONL schema — telemetry/journal.py). Path comes from
    BENCH_JOURNAL (set it to 0 to disable), defaulting to
    bench_out/telemetry.jsonl next to this file. Best-effort: a
    journal failure must never fail the measurement itself. Every
    digest carries the static per-program reassociation ulp bound
    next to the measured value (ISSUE 18 satellite)."""
    path = os.environ.get("BENCH_JOURNAL", "")
    if path == "0":
        return
    if not path:
        path = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                            "bench_out", "telemetry.jsonl")
    try:
        from commefficient_tpu.telemetry.journal import append_event
        bounds = _static_ulp_bounds()
        if bounds is not None and isinstance(out, dict):
            out = dict(out)
            out["worst_case_ulp"] = bounds
        append_event(path, kind, digest=out)
        log(f"digest journaled to {path}")
    except (ImportError, OSError, TypeError, ValueError) as e:
        log(f"digest journal append failed ({e}); continuing")


if __name__ == "__main__":
    if (os.environ.get("BENCH_PIPELINE") == "1"
            or "--pipeline" in sys.argv):
        # ISSUE 10 pipeline cadence sweep: in-process (CPU-friendly);
        # sync vs pipelined round cadence from journal round events
        raise SystemExit(worker_entry(pipeline_main))
    if (os.environ.get("BENCH_CONTROL") == "1"
            or "--control" in sys.argv):
        # ISSUE 20 self-tuning control sweep: in-process
        # (CPU-friendly); static vs adaptive per-round cadence under
        # a heavy straggler load, all three controllers live
        raise SystemExit(worker_entry(control_main))
    if (os.environ.get("BENCH_TRACE") == "1"
            or "--trace" in sys.argv):
        # ISSUE 13 graftscope arm: stage-resolved cadence (per-stage
        # p50s + overlap efficiency) on the traced pipelined workload
        raise SystemExit(worker_entry(trace_main))
    if (os.environ.get("BENCH_POPULATION") == "1"
            or "--population" in sys.argv):
        # ISSUE 9 population sweep: in-process (tiny D, CPU-friendly);
        # the primary flagship bench below is untouched
        raise SystemExit(worker_entry(population_main))
    raise SystemExit(worker_entry(main))

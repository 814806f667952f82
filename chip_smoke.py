#!/usr/bin/env python
"""chip_smoke.py — does the federated round still start on the chip?

One process, run from the root of the checkout on a machine with a
TPU:

    python chip_smoke.py               one chip: cv, then attention
    python chip_smoke.py --phase gpt2  one chip: the gpt2 phase, which
                                       does not fit beside the other two
                                       in the 1200 s a cold run may take
    python chip_smoke.py --chips 4     four chips: the sharded cv round
                                       against one device of that host
                                       (--tp adds gpt2 model_parallel 2
                                       against 1) and no other phase

It drives the drivers a user would call — `cv_train.main` and
`gpt2_train.main`, without `--test`, so the models have their
published widths — on data written from `--seed` in the real on-disk
formats, and checks what they leave behind by the repo's own means
(the run journal and its validator, the accountant's byte counts, the
reference attention). Everything is written under `--out` (default
`chip_smoke_out/` in the checkout, listed in .gitignore).

It is a smoke, not a benchmark: the compile seconds and ms per round
it prints on its earlier lines are readings of one run. The last line
of standard output is one JSON object,

    {"ok": true, "device": {"platform": "tpu", "kind": "...", "count": N}}

printed only when JAX found a TPU and every phase passed; any other
outcome is a non-zero exit without that line. Nothing here catches a
phase's failure.
"""
from __future__ import annotations

import argparse
import json
import os
import statistics
import sys
import time

REPO = os.path.dirname(os.path.abspath(__file__))

# what the script insists JAX runs on; the CPU rehearsal among the
# tests sets this from the test, the program has no switch for it
EXPECT_PLATFORM = "tpu"

# BASELINE config #2, the flagship: ResNet9 (full widths, D=6,568,640)
# on CIFAR-10, count-sketch 5 x 500,000, k=50,000, virtual momentum
# and error feedback, 8 of 100 clients a round. --iid: with one class
# to a client a round's loss says which classes were drawn, not
# whether training works, and "the last loss is below the first" is a
# check this smoke makes.
CV = dict(num_workers=8, local_batch_size=32, num_clients=100,
          k=50_000, num_rows=5, num_cols=500_000,
          rounds=12, scan_span=4, images_per_batch_file=10_000,
          grad_size=6_568_640, extra=("--iid",))

# BASELINE config #5: GPT2-small at published widths (12 layers, 768,
# 12 heads), from scratch on the fallback tokenizer (D=89,683,201),
# sketch + virtual momentum, 4 clients x 4 dialogs a round. The table
# is 5 x 5,000,000: 28% of D, the class of the flagship's (38% of its
# D). At the drivers' default 5 x 500,000 this D is 180 chunks, the
# sketch passes unroll to 900 static rotations each, and compiling the
# round needs 32.5 GB of host memory (measured with the TPU compiler
# off the chip) where the one-chip machine has 40 GiB for everything:
# the first chip run of this phase was killed there.
GPT2 = dict(num_workers=4, local_batch_size=4, num_clients=32,
            k=50_000, num_rows=5, num_cols=5_000_000, rounds=3,
            personas=32, dialogs_per_persona=2, utterances_per_dialog=4,
            extra=())

# the attention phase: GPT2-small heads at the model's n_positions
ATTN = dict(B=4, H=12, L=1024, Dh=64)
# max |flash - reference| / max |reference| allowed, forward and each
# gradient. The reference runs in f32 at "highest" matmul precision;
# flash runs at the chip's default, where an f32 matmul rounds its
# operands to bf16 (2^-9 = 2e-3 relative each) before accumulating in
# f32. A score is a 64-term dot product of O(1) operands, so it moves
# by about 1e-2 absolute, a probability by about 1e-2 relative, and
# outputs and gradients by a few times that against their largest
# value; the bf16 case adds the rounding of the stored inputs and
# outputs. A wrong mask, block offset or rescale shows as O(1).
ATTN_TOL = {"float32": 5e-2, "bfloat16": 5e-2}


def say(msg: str) -> None:
    print(msg, flush=True)


def require(cond: bool, what: str) -> None:
    if not cond:
        raise SystemExit(f"chip_smoke: FAILED: {what}")


# ---------------------------------------------------------------------------
# driving a driver


def run_driver(name, driver, argv, out_dir, spec, steady_from,
               mesh=None, probe_sync=False, keep_weights=False):
    """Call `driver.main(argv)`, check what it journaled (report_run)
    and return (kept, losses): `kept` holds the FedModel the driver
    built — kept by subclassing the name the driver looks up — and the
    last training output. `mesh` and `keep_weights` serve the
    four-chip comparison: hand the model a mesh instead of letting it
    take every device, and copy the weights out before training and
    after its first round. `probe_sync` times block_until_ready
    against a small host transfer on two steady rounds."""
    import numpy as np
    from commefficient_tpu.telemetry.journal import validate_journal

    kept = {}

    class Kept(driver.FedModel):
        def __init__(self, *a, **kw):
            if mesh is not None:
                kw["mesh"] = mesh
            super().__init__(*a, **kw)
            kept["model"] = self
            self._calls = 0
            if keep_weights:
                kept["w0"] = np.asarray(self.ps_weights)

        def __call__(self, batch):
            if not self.training:
                return super().__call__(batch)
            if keep_weights and self._calls == 1:
                kept["w1"] = np.asarray(self.ps_weights)
            t0 = time.perf_counter()
            out = kept["train_out"] = super().__call__(batch)
            self._calls += 1
            if probe_sync and self._calls in (6, 9):
                kept.setdefault("sync", []).append(sync_probe(
                    self, out, t0, block_first=self._calls == 6))
            return out

    journal = os.path.join(out_dir,
                           f"journal_{name.replace('/', '_')}.jsonl")
    if os.path.exists(journal):
        os.remove(journal)      # the journal appends
    original = driver.FedModel
    driver.FedModel = Kept
    t0 = time.perf_counter()
    try:
        ok = driver.main(list(argv) + ["--journal_path", journal])
    finally:
        driver.FedModel = original
    wall = time.perf_counter() - t0
    require(bool(ok), f"{name}: {driver.__name__}.main returned {ok!r}")
    records, problems = validate_journal(journal)
    require(not problems, f"{name}: journal {journal}: {problems[:3]}")
    up = spec["num_workers"] * spec["num_rows"] * spec["num_cols"] * 4
    losses = report_run(name, records, wall, up, steady_from)
    model = kept["model"]
    say(f"[{name}] D={model.cfg.grad_size} table {spec['num_rows']}x"
        f"{spec['num_cols']} mesh={dict(model.mesh.shape)} on devices "
        f"{[d.id for d in model.mesh.devices.flat]}")
    return kept, losses


def sync_probe(model, out, t_dispatch0, block_first: bool) -> dict:
    """On one steady round of the per-round loop: how long does
    `block_until_ready` on the round's outputs wait, against a host
    transfer of the round's [W] f32 loss vector (32 bytes at W=8)?
    Whichever comes first pays for the round; if block_until_ready
    really waits for the device, the one that comes second is free."""
    import jax

    def timed(fn):
        t = time.perf_counter()
        fn()
        return round((time.perf_counter() - t) * 1e3, 3)

    dispatch_ms = round((time.perf_counter() - t_dispatch0) * 1e3, 3)
    steps = [("block_until_ready", lambda: jax.block_until_ready(
                  (model.server.ps_weights, out[0]))),
             ("transfer", lambda: jax.device_get(out[0]))]
    if not block_first:
        steps.reverse()
    return {"order": " then ".join(n for n, _ in steps),
            "dispatch_ms": dispatch_ms,
            "first_ms": timed(steps[0][1]),
            "second_ms": timed(steps[1][1])}


def report_run(name, records, wall_s, expect_up_bytes, steady_from):
    """Check and print what one driver run journaled: finite losses
    with the last below the first, every round's upload bytes as the
    accountant bills them, a finite eval, and the smoke's readings.
    Returns the per-round losses."""
    import math

    rounds = [r for r in records if r.get("event") == "round"]
    losses = [r["metrics"]["train_loss"] for r in rounds]
    require(len(rounds) >= 2, f"{name}: {len(rounds)} round(s) journaled")
    require(all(math.isfinite(v) for v in losses),
            f"{name}: non-finite loss in {losses}")
    require(losses[-1] < losses[0],
            f"{name}: loss did not fall: {losses}")
    ups = {r["up_bytes"] for r in rounds}
    require(ups == {float(expect_up_bytes)},
            f"{name}: upload bytes per round {sorted(ups)} != "
            f"{expect_up_bytes}")
    compile_s = sum(r["seconds"] for r in records
                    if r.get("event") == "compile")
    steady = [r["seconds"] * 1e3 for r in rounds[steady_from:]
              if "seconds" in r]
    say(f"[{name}] rounds={len(rounds)} loss first={losses[0]:.4f} "
        f"last={losses[-1]:.4f} up_bytes/round={expect_up_bytes}")
    steady_txt = (f"steady {statistics.median(steady):.2f} ms/round "
                  f"(median of {len(steady)} journaled rounds after "
                  f"warm-up)" if steady else "no steady rounds")
    say(f"[{name}] smoke readings, not a benchmark: wall {wall_s:.1f} s,"
        f" compile {compile_s:.1f} s (sum of journaled backend "
        f"compiles), {steady_txt}")
    for e in (r for r in records if r.get("event") == "epoch"):
        for key in ("test_loss", "test_acc"):
            if key in e:
                require(math.isfinite(e[key]), f"{name}: {key}={e[key]}")
        say(f"[{name}] epoch event: "
            + json.dumps({k: e[k] for k in sorted(e)
                          if k in ("train_loss", "test_loss", "test_acc",
                                   "up_mib", "down_mib", "rounds")}))
    return losses


def device_memory_line(tag):
    import jax
    parts = []
    for d in jax.devices():
        st = d.memory_stats() or {}
        parts.append(f"{d.id}: in_use={st.get('bytes_in_use')} "
                     f"peak={st.get('peak_bytes_in_use')}")
    say(f"[{tag}] device memory (bytes) " + "; ".join(parts))


def cache_entries(cache_dir) -> int:
    return len(os.listdir(cache_dir)) if os.path.isdir(cache_dir) else 0


# ---------------------------------------------------------------------------
# phases


def write_cifar(out_dir, seed):
    from commefficient_tpu.data.cifar import write_cifar10_archive
    data_dir = os.path.join(out_dir, "data")
    t0 = time.perf_counter()
    write_cifar10_archive(data_dir, seed=seed,
                          n_per_batch=CV["images_per_batch_file"])
    say(f"[cv] CIFAR-10 archive (5 x {CV['images_per_batch_file']} train"
        f" + {CV['images_per_batch_file']} test, real pickle format) "
        f"from seed {seed} in {time.perf_counter() - t0:.1f} s")
    return data_dir


def cv_argv(data_dir, rounds, scan):
    spe = -(-CV["images_per_batch_file"] * 5
            // (CV["local_batch_size"] * CV["num_workers"]))
    epochs = (rounds - 0.5) / spe      # ceil(epochs * spe) == rounds
    argv = ["--dataset_name", "CIFAR10", "--dataset_dir", data_dir,
            "--model", "ResNet9", "--mode", "sketch",
            "--error_type", "virtual", "--virtual_momentum", "0.9",
            "--local_momentum", "0",
            "--num_workers", str(CV["num_workers"]),
            "--local_batch_size", str(CV["local_batch_size"]),
            "--num_clients", str(CV["num_clients"]),
            "--k", str(CV["k"]), "--num_rows", str(CV["num_rows"]),
            "--num_cols", str(CV["num_cols"]),
            "--num_epochs", repr(epochs),
            "--pivot_epoch", repr(epochs / 2), "--lr_scale", "0.05",
            "--valid_batch_size", "1000", *CV["extra"]]
    if scan:
        argv += ["--scan_rounds", "--scan_span", str(CV["scan_span"])]
    return argv


def phase_cv(out_dir, seed, cache_dir):
    from commefficient_tpu.native import native_accounting
    from commefficient_tpu.training import cv_train

    data_dir = write_cifar(out_dir, seed)
    for scan in (False, True):
        name = "cv/scan" if scan else "cv/per-round"
        before = cache_entries(cache_dir)
        # steady rounds: per-round, 0-1 hold the compile (and 5/8 the
        # sync probe's stalls, which a median shrugs off); scanned,
        # the first span compiles
        kept, _ = run_driver(
            name, cv_train, cv_argv(data_dir, CV["rounds"], scan),
            out_dir, CV, steady_from=CV["scan_span"] if scan else 2,
            probe_sync=not scan)
        D = kept["model"].cfg.grad_size
        require(D == CV["grad_size"], f"{name}: D={D}")
        say(f"[{name}] compile cache entries: {before} before, "
            f"{cache_entries(cache_dir)} after (in {cache_dir})")
        for probe in kept.get("sync", ()):
            say(f"[{name}] sync probe: " + json.dumps(probe))
        device_memory_line(name)
    say("[cv] download accounting path: "
        + ("native (C extension)" if native_accounting is not None
           else "numpy"))


def gpt2_argv(out_dir, seed, model_parallel=1):
    """Write the raw PersonaChat file from `seed` and return the
    driver's arguments."""
    from commefficient_tpu.data.persona import write_personachat_raw

    data_dir = os.path.join(out_dir, "data")
    write_personachat_raw(
        data_dir, seed=seed, num_personas=GPT2["personas"],
        dialogs_per_persona=GPT2["dialogs_per_persona"],
        utterances_per_dialog=GPT2["utterances_per_dialog"])
    utterances = (GPT2["personas"] * GPT2["dialogs_per_persona"]
                  * GPT2["utterances_per_dialog"])
    spe = -(-utterances // (GPT2["local_batch_size"]
                            * GPT2["num_workers"]))
    # a directory with nothing in it: no checkpoint and no tokenizer
    # is looked up by name, so the model is built from scratch at the
    # "gpt2" preset's widths on the fallback tokenizer
    nothing = os.path.join(out_dir, "no_checkpoint")
    os.makedirs(nothing, exist_ok=True)
    return ["--dataset_name", "PERSONA", "--dataset_dir", data_dir,
            "--model_checkpoint", nothing, "--mode", "sketch",
            "--error_type", "virtual", "--virtual_momentum", "0.9",
            "--local_momentum", "0",
            "--num_workers", str(GPT2["num_workers"]),
            "--local_batch_size", str(GPT2["local_batch_size"]),
            "--num_clients", str(GPT2["num_clients"]),
            "--k", str(GPT2["k"]), "--num_rows", str(GPT2["num_rows"]),
            "--num_cols", str(GPT2["num_cols"]),
            "--num_epochs", repr(GPT2["rounds"] / spe),
            "--model_parallel", str(model_parallel), *GPT2["extra"]]


def phase_gpt2(out_dir, seed, cache_dir):
    from commefficient_tpu.training import gpt2_train

    before = cache_entries(cache_dir)
    run_driver("gpt2", gpt2_train, gpt2_argv(out_dir, seed), out_dir,
               GPT2, steady_from=1)
    say(f"[gpt2] compile cache entries: {before} before, "
        f"{cache_entries(cache_dir)} after (in {cache_dir})")
    device_memory_line("gpt2")


def phase_attention():
    """flash_attention (the Pallas forward on a TPU, the tiled
    backward) against reference_attention, forward and gradient."""
    import jax
    import jax.numpy as jnp
    from commefficient_tpu.ops.attention import (
        flash_attention, reference_attention,
    )

    shape = (ATTN["B"], ATTN["H"], ATTN["L"], ATTN["Dh"])

    def loss(fn):
        return lambda q, k, v: jnp.mean(
            jnp.square(fn(q, k, v).astype(jnp.float32)))

    flash = jax.jit(jax.value_and_grad(loss(flash_attention), (0, 1, 2)))
    flash_fwd = jax.jit(flash_attention)

    @jax.jit
    def ref(q, k, v):
        with jax.default_matmul_precision("highest"):
            q, k, v = (x.astype(jnp.float32) for x in (q, k, v))
            out = reference_attention(q, k, v)
            _, grads = jax.value_and_grad(
                loss(reference_attention), (0, 1, 2))(q, k, v)
        return out, grads

    for dtype in (jnp.float32, jnp.bfloat16):
        name = jnp.dtype(dtype).name
        keys = jax.random.split(jax.random.PRNGKey(0), 3)
        q, k, v = (jax.random.normal(kk, shape, jnp.float32).astype(dtype)
                   for kk in keys)
        if jax.devices()[0].platform == "tpu":
            text = flash_fwd.lower(q, k, v).compile().as_text()
            require("tpu_custom_call" in text,
                    f"attention/{name}: no Pallas kernel in the program")
        out = flash_fwd(q, k, v)
        _, grads = flash(q, k, v)
        ref_out, ref_grads = ref(q, k, v)
        require(out.shape == shape and out.dtype == dtype,
                f"attention/{name}: out {out.shape} {out.dtype}")
        def rel_err(a, b):
            return float(jnp.max(jnp.abs(a.astype(jnp.float32) - b))
                         / jnp.max(jnp.abs(b)))

        errs = {"out": rel_err(out, ref_out)}
        for g, rg, which in zip(grads, ref_grads, "qkv"):
            require(g.shape == shape, f"attention/{name}: d{which}")
            errs["d" + which] = rel_err(g, rg)
        tol = ATTN_TOL[name]
        say(f"[attention/{name}] shape {shape} max abs error over max "
            f"abs reference (reference_attention in f32 at highest "
            f"precision): "
            + json.dumps({k_: round(v_, 6) for k_, v_ in errs.items()})
            + f" tolerance {tol}")
        require(all(e == e and e <= tol for e in errs.values()),
                f"attention/{name}: error over {tol}: {errs}")


def phase_four_chips(out_dir, seed, tp: bool):
    """What exists only across chips: the cv round on the clients=4
    mesh against the same rounds on one device of the host; with
    `tp`, gpt2 on clients 2 x model 2 against model_parallel 1."""
    import jax
    import numpy as np
    from commefficient_tpu.parallel.mesh import make_client_mesh
    from commefficient_tpu.training import cv_train

    require(len(jax.devices()) == 4,
            f"--chips 4 needs four devices, found {len(jax.devices())}")

    def weights(kept):
        return (kept["w0"], kept["w1"],
                np.asarray(kept["model"].ps_weights))

    data_dir = write_cifar(out_dir, seed)
    argv = cv_argv(data_dir, FOUR_CHIP_ROUNDS, scan=False)
    kept4, losses4 = run_driver("cv/4-chip", cv_train, argv, out_dir, CV,
                                steady_from=2, keep_weights=True)
    kept1, losses1 = run_driver("cv/1-of-4", cv_train, argv, out_dir, CV,
                                steady_from=2, keep_weights=True,
                                mesh=make_client_mesh(1))
    # the work really is on four devices
    require(kept4["model"].mesh.devices.size == 4
            and kept1["model"].mesh.devices.size == 1,
            "meshes are not of 4 devices and of 1")
    for what, arr in zip(("loss", "metric"), kept4["train_out"]):
        n = len(arr.sharding.device_set)
        require(n == 4, f"per-client {what} of the last round lives on "
                        f"{n} device(s)")
    for d in jax.devices():
        stats = d.memory_stats()    # None off the chip (CPU rehearsal)
        require(stats is None or stats["bytes_in_use"] > 0,
                f"device {d.id} holds no memory")
    device_memory_line("cv/4-chip")
    compare("cv 4-chip vs 1-of-4", losses4, losses1,
            weights(kept4), weights(kept1))
    if tp:
        from commefficient_tpu.training import gpt2_train
        got = {}
        for mp in (2, 1):
            kept, losses = run_driver(
                f"gpt2/model_parallel={mp}", gpt2_train,
                gpt2_argv(out_dir, seed, mp), out_dir, GPT2,
                steady_from=2, keep_weights=True)
            got[mp] = (losses, weights(kept))
            del kept
        compare("gpt2 model_parallel 2 vs 1", got[2][0], got[1][0],
                got[2][1], got[1][1])


FOUR_CHIP_ROUNDS = 4
# What the two runs are held to. tests/test_mesh.py holds one round
# at D=16 to rtol=1e-6, and the first round's loss here (no update
# yet, only the forward pass re-associated over another batch split)
# is held to the same. After that the runs part, and not by rounding
# alone: the per-shard backward sums 64 examples on four chips and 256
# on one, the tables add in another order, and wherever the top-k
# threshold falls between two estimates that differ in their last
# bits, one run sends a coordinate and the other keeps it back; each
# such coordinate carries 1/sqrt(k) of an update's norm, moves the
# next round's gradients, and the sets drift apart (first chip run, 4
# rounds: losses within 2.2e-5, 4,285 of 6.5M weights apart by more
# than 1%, update cosine 0.993; the CPU rehearsal on four virtual
# devices saw between no flip and a few). A wrong mesh — a lost psum,
# rows on the wrong shard — moves every updated coordinate (3% of D
# here) and the losses with them. So: later losses to 1e-4
# (tests/test_tp.py's 2e-5 with room for the flips), the two updates'
# cosine at least 0.98, and at most 1% of coordinates apart by more
# than 1%.
FIRST_LOSS_RTOL = 1e-6
LOSS_RTOL = 1e-4
UPDATE_COSINE = 0.98
APART_RTOL, APART_SHARE = 1e-2, 1e-2


def compare(what, losses_a, losses_b, run_a, run_b):
    """`run_*` = weights (before, after the first round, after the
    last) of the two runs."""
    import numpy as np
    la, lb = np.asarray(losses_a), np.asarray(losses_b)
    rel = np.abs(la - lb) / np.abs(lb)
    require(np.array_equal(run_a[0], run_b[0]),
            f"{what}: initial weights differ")
    stats = {}
    for when, w_a, w_b in (("first round", run_a[1], run_b[1]),
                           ("last round", run_a[2], run_b[2])):
        dw_a, dw_b = w_a - run_a[0], w_b - run_b[0]
        diff = np.abs(w_a - w_b)
        stats[when] = {
            "update_cosine": float(
                dw_a @ dw_b / (np.linalg.norm(dw_a) * np.linalg.norm(dw_b))),
            "update_rel_l2": float(np.linalg.norm(dw_a - dw_b)
                                   / np.linalg.norm(dw_b)),
            "max_abs": float(diff.max()),
            "apart": {tol: int((diff > tol * np.abs(w_b)).sum())
                      for tol in (1e-6, 1e-4, APART_RTOL)}}
    say(f"[{what}] losses {la.tolist()} vs {lb.tolist()}: relative "
        f"difference first round {rel[0]:.3e} (gate {FIRST_LOSS_RTOL}), "
        f"worst {rel.max():.3e} (gate {LOSS_RTOL}); weights of "
        f"{run_b[2].size} after the " + "; after the ".join(
            f"{when}: {json.dumps(st)}" for when, st in stats.items())
        + f" (gates on the last: cosine >= {UPDATE_COSINE}, share apart "
        f"by rtol {APART_RTOL} <= {APART_SHARE})")
    last = stats["last round"]
    require(rel[0] <= FIRST_LOSS_RTOL, f"{what}: first losses differ")
    require(rel.max() <= LOSS_RTOL, f"{what}: losses differ")
    require(last["update_cosine"] >= UPDATE_COSINE,
            f"{what}: updates point apart")
    require(last["apart"][APART_RTOL] <= APART_SHARE * run_b[2].size,
            f"{what}: too many weights apart")


# ---------------------------------------------------------------------------


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    p.add_argument("--chips", type=int, choices=(1, 4), default=1)
    p.add_argument("--phase", choices=("cv", "attention", "gpt2"),
                   action="append",
                   help="one-chip run: only this phase (repeatable; "
                        "default cv and attention)")
    p.add_argument("--tp", action="store_true",
                   help="with --chips 4: also gpt2 model_parallel 2 vs 1")
    p.add_argument("--seed", type=int, default=0)
    p.add_argument("--out", default=os.path.join(REPO, "chip_smoke_out"))
    args = p.parse_args(argv)

    sys.path.insert(0, REPO)
    import jax
    devices = jax.devices()
    device = {"platform": devices[0].platform,
              "kind": devices[0].device_kind, "count": len(devices)}
    if device["platform"] != EXPECT_PLATFORM:
        print(f"chip_smoke: JAX found {device}, not a "
              f"{EXPECT_PLATFORM}: nothing was run", file=sys.stderr)
        return 1

    from commefficient_tpu.utils.cache import (
        enable_persistent_compilation_cache,
    )
    cache_dir = enable_persistent_compilation_cache()
    os.makedirs(args.out, exist_ok=True)
    say(f"chip_smoke: {device} jax {jax.__version__} seed {args.seed} "
        f"out {args.out} compile cache {cache_dir} "
        f"({cache_entries(cache_dir)} entries)")
    t0 = time.perf_counter()
    if args.chips == 4:
        phase_four_chips(args.out, args.seed, args.tp)
    else:
        for phase in args.phase or ("cv", "attention"):
            t = time.perf_counter()
            if phase == "cv":
                phase_cv(args.out, args.seed, cache_dir)
            elif phase == "gpt2":
                phase_gpt2(args.out, args.seed, cache_dir)
            else:
                phase_attention()
            say(f"[{phase}] phase passed in "
                f"{time.perf_counter() - t:.1f} s")
    say(f"chip_smoke: all phases passed in "
        f"{time.perf_counter() - t0:.1f} s")
    print(json.dumps({"ok": True, "device": device}), flush=True)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())

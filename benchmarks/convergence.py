"""Convergence-under-compression demo: the algorithmic point of
FetchSGD, measured end to end.

Trains ResNet9 on an IID federated CIFAR-shaped corpus (the
reference's --iid resharding; its natural one-class-per-client
partition is also supported, but single-class local batches destroy
the class-mean signal under batch normalization — BN subtracts it —
so the normed quick-converging config used here runs IID, like the
reference's own imagenet.sh recipe) under `sketch` compression with
virtual error feedback + momentum, against an `uncompressed` control
at identical rounds/LR, and emits the rounds-vs-accuracy-vs-bytes
curves the paper reports (BASELINE.md: the metric is the curve, not a
scalar).

The run asserts the paper's qualitative claims:
  * sketched training reaches nontrivial accuracy (learns, not noise);
  * sketched accuracy lands within a few points of uncompressed;
  * sketched upload bytes per round are a fraction of uncompressed.

Writes benchmarks/convergence_results.json. The default config is
sized for the 8-device CPU test mesh: ~1 s/round -> all three modes
(sketch, uncompressed, local_topk) in roughly 10 minutes. CONV_FULL=1
selects the full-width model + 8192-example corpus for a real TPU;
CONV_EPOCHS trims the budget either way.

Usage:
  XLA_FLAGS=--xla_force_host_platform_device_count=8 JAX_PLATFORMS=cpu \
      python benchmarks/convergence.py
"""
from __future__ import annotations

import json
import os
import sys
import time

import jax

import jax.numpy as jnp
import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from commefficient_tpu.config import Config
from commefficient_tpu.data import FedCIFAR10, FedLoader, FedValLoader
from commefficient_tpu.data.transforms import cifar10_transforms
from commefficient_tpu.federated.api import FedModel, FedOptimizer
from commefficient_tpu.models import ResNet9
from commefficient_tpu.training.cv_train import make_compute_loss
from commefficient_tpu.utils.schedules import LambdaLR, PiecewiseLinear

FULL = os.environ.get("CONV_FULL", "") == "1"
# 24 epochs: at the calibrated signal=0.14 difficulty a 12-epoch run
# leaves every mode under-trained (uncompressed 0.64, sketch 0.43 on
# seeds 0-2) and the behind-by margins bind on training budget rather
# than compression cost; doubling the budget lets the modes approach
# their asymptotes while the difficulty keeps them differentiated
EPOCHS = int(os.environ.get("CONV_EPOCHS", "24"))
# seed variance (VERDICT r4 next #3): the cheap CPU suite runs every
# config at 3 seeds and reports mean±spread; the FULL TPU run stays
# single-seed (wall-clock) unless CONV_SEEDS overrides
SEEDS = tuple(int(s) for s in os.environ.get(
    "CONV_SEEDS", "0" if FULL else "0,1,2").split(","))
WORKERS = 8
BATCH = 32 if FULL else 8
# the FULL (TPU) run gets its own artifact so it never clobbers the
# cheap 3-seed CPU suite's results (both are committed evidence)
OUT = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                   "convergence_full_results.json" if FULL
                   else "convergence_results.json")


def make_data(seed=0, num_clients=10):
    train_t, test_t = cifar10_transforms(seed=seed)
    n_train = 8192 if FULL else 1024
    # sizing+partition+seed-specific cache (the corpus itself is
    # seeded, so seed variance covers data draw + init + sampling)
    root = f"/tmp/conv_bench_ds_{n_train}_{num_clients}_{seed}"
    # default sizing targets the 8-device CPU mesh: ~20 s/round at the
    # old 8192x(16,32,32,32)-channel config made even a 2-epoch smoke
    # take an hour; 1024 examples x batch 8 x the narrower net below
    # is ~1 s/round and still converges on the class-prototype corpus
    # signal=0.14: the default 0.6 v2 corpus (and even 0.45) is so
    # learnable that every mode saturates at 1.0 and the suite's
    # claims (fedavg starvation lift, down_k truncation cost) lose
    # their discriminative power — a ceiling, not a finding.
    # Calibrated by a linear-probe sweep on the augmented corpus
    # (val acc: 0.30->0.99, 0.22->0.98, 0.16->0.88, 0.10->0.58):
    # 0.14 leaves real headroom below saturation while staying well
    # above chance.
    common = dict(transform=None, do_iid=True, num_clients=num_clients,
                  seed=seed, synthetic_signal=0.14,
                  synthetic_examples=(n_train, n_train // 4))
    train = FedCIFAR10(root, transform=train_t, train=True,
                       **{k: v for k, v in common.items()
                          if k != "transform"})
    val = FedCIFAR10(root, transform=test_t, train=False,
                     **{k: v for k, v in common.items()
                        if k != "transform"})
    return train, val


def run_mode(mode: str, train_set, val_set, seed=0, label=None,
             down_k_mult=0, num_fedavg_epochs=1, table_dtype="f32"):
    D_kw = {} if FULL else {"channels": {"prep": 8, "layer1": 16,
                                         "layer2": 16, "layer3": 16}}
    # batchnorm on (the --do_batchnorm surface both frameworks expose):
    # the no-norm ResNet9 needs the full cifar10-fast LR recipe over
    # many epochs to move at all — measured flat at ln(10) for 100+
    # rounds at this scale — while the normed net separates the corpus
    # in a couple of epochs, which is what a convergence comparison of
    # COMPRESSION modes needs (the control and the compressed runs
    # share the model either way)
    model_mod = ResNet9(num_classes=10, do_batchnorm=True, **D_kw)
    x0 = jnp.zeros((2, 32, 32, 3), jnp.float32)
    params = model_mod.init(jax.random.PRNGKey(seed), x0)

    from commefficient_tpu.ops.flat import flatten_params
    D = int(flatten_params(params)[0].shape[0])

    base = dict(seed=seed, num_workers=WORKERS,
                local_batch_size=(-1 if mode == "fedavg" else BATCH),
                weight_decay=5e-4, microbatch_size=-1,
                num_epochs=float(EPOCHS))
    # Peak LR is tuned PER MODE, as the paper's grid searches are
    # (BASELINE.md): FetchSGD's momentum factor masking zeroes the
    # server momentum at every transmitted coordinate, so the
    # compressed modes see ~1/(1-rho) less effective step than the
    # uncompressed control at the same lr — measured flat-at-chance
    # until compensated.
    peak_lr = {"sketch": 2.4, "sketch_topk_down": 2.4,
               "local_topk": 1.6, "uncompressed": 0.4,
               "fedavg": 0.4}[mode]
    if mode in ("sketch", "sketch_topk_down"):
        # the reference's flagship geometry RATIOS (utils.py defaults:
        # D=6.6M -> 5 x 500k, ~13 coords/cell): r*c = D/2.6, k = D/50.
        # A 10x-smaller table (50 coords/cell) was measured to destroy
        # recovery — the paper's own ablations degrade the same way —
        # so the table ratio stays at the reference's operating point;
        # the >=10x upload-compression curve is local_topk's below.
        # sketch_topk_down additionally compresses the server->client
        # download to the top-k changed weights (--topk_down,
        # reference fed_worker.py:232-247).
        # down_k_mult sweeps the DOWNLOAD budget (Config.down_k) as a
        # multiple of the upload k: the server's update is k-sparse per
        # round but a 1-in-5-participating client accumulates ~5 rounds
        # of changes between downloads, so download-k must exceed
        # upload-k for staleness to stay bounded (VERDICT r3 weak #5)
        cfg = Config(mode="sketch", error_type="virtual",
                     virtual_momentum=0.9, local_momentum=0.0,
                     num_rows=5, num_cols=max(D // 13, 256), num_blocks=1,
                     k=max(D // 50, 64),
                     down_k=down_k_mult * max(D // 50, 64),
                     sketch_table_dtype=table_dtype,
                     do_topk_down=(mode == "sketch_topk_down"), **base)
    elif mode == "fedavg":
        # the paper's FedAvg baseline: whole-client local SGD at the
        # server's LR, weighted weight-delta aggregation with virtual
        # momentum at lr=1 (reference fed_worker.py:61-113)
        cfg = Config(mode="fedavg", error_type="none",
                     local_momentum=0.0, virtual_momentum=0.9,
                     num_fedavg_epochs=num_fedavg_epochs,
                     fedavg_batch_size=BATCH, **base)
    elif mode == "local_topk":
        # upload = k floats -> 50x per-round upload compression
        cfg = Config(mode="local_topk", error_type="local",
                     local_momentum=0.9, virtual_momentum=0.0,
                     k=max(D // 50, 64), **base)
    else:
        cfg = Config(mode="uncompressed", error_type="virtual",
                     virtual_momentum=0.9, local_momentum=0.0, **base)

    loader = FedLoader(train_set, WORKERS, cfg.local_batch_size,
                       seed=seed)
    val_loader = FedValLoader(val_set, 64,
                              num_shards=min(jax.device_count(), WORKERS))
    model = FedModel(None, make_compute_loss(model_mod), cfg,
                     params=params, num_clients=train_set.num_clients)
    opt = FedOptimizer(model)
    spe = loader.steps_per_epoch
    sched = PiecewiseLinear([0, 2, EPOCHS], [0, peak_lr, 0])
    lr_sched = LambdaLR(opt, lr_lambda=lambda s: sched(s / spe))

    curve = []
    total_up = 0.0
    total_down = 0.0
    rounds = 0
    t_start = time.time()
    for epoch in range(EPOCHS):
        for client_ids, data, mask in loader.epoch():
            lr_sched.step()
            loss, acc, down, up = model((client_ids, data, mask))
            opt.step()
            total_up += float(up.sum())
            total_down += float(down.sum())
            rounds += 1
            if rounds == 1 or rounds % 16 == 0:
                # early signs of life: the first round carries the
                # compile (minutes on the CPU mesh)
                print(f"[{mode}] round {rounds} loss "
                      f"{float(np.mean(loss)):.3f} "
                      f"({time.time() - t_start:.0f}s)", flush=True)
        # eval
        model.train(False)
        tot = n = 0.0
        for vdata, vmask in val_loader.batches():
            vl, va, vc = model((vdata, vmask))
            tot += float((va * vc).sum())
            n += float(vc.sum())
        model.train(True)
        acc = tot / max(n, 1)
        curve.append({"round": rounds, "epoch": epoch + 1,
                      "test_acc": round(acc, 4),
                      "upload_MiB": round(total_up / 2**20, 3),
                      "download_MiB": round(total_down / 2**20, 3)})
        print(f"[{mode}] epoch {epoch+1} round {rounds} "
              f"acc {acc:.4f} up {total_up/2**20:.2f} MiB", flush=True)
    # model.cfg is the validated config with the real grad_size filled
    # in (the local cfg's grad_size is still the default)
    return {"mode": label or mode, "grad_size": D,
            "num_clients": int(train_set.num_clients),
            "upload_floats_per_client_round": model.cfg.upload_floats,
            "upload_bytes_per_client_round": model.cfg.upload_bytes,
            "curve": curve}


def seeded(label: str, fn) -> dict:
    """Run `fn(seed)` (returning a run_mode dict) for every seed in
    SEEDS; return seed-0's full record annotated with the per-seed
    final accuracies, their mean, and spread (max-min). All summary
    claims below are made on MEANS — a single seed's 2-point edge is
    within spread at this scale (VERDICT r4 weak #3)."""
    per_seed = [fn(s) for s in SEEDS]
    rec = per_seed[0]
    accs = [r["curve"][-1]["test_acc"] for r in per_seed]
    rec["seeds"] = list(SEEDS)
    rec["final_accs_per_seed"] = accs
    rec["final_acc_mean"] = round(float(np.mean(accs)), 4)
    rec["final_acc_spread"] = round(float(np.max(accs) - np.min(accs)), 4)
    print(f"[{label}] final accs {accs} mean {rec['final_acc_mean']} "
          f"spread {rec['final_acc_spread']}", flush=True)
    return rec


def main():
    t0 = time.time()
    data = {s: make_data(seed=s) for s in SEEDS}
    runs = [seeded(m, lambda s, m=m: run_mode(m, *data[s], seed=s))
            for m in ("sketch", "uncompressed", "local_topk", "fedavg")]
    # fedavg knob sweep (VERDICT r4 next #3): with local_batch -1 the
    # sampler yields num_clients//num_workers = 10//8 -> ONE aggregation
    # round per epoch, so fedavg trains 12 server rounds total where
    # the per-batch modes train ~16x more — round starvation by config,
    # not an optimizer bug. The reference's own knob for this regime is
    # more local computation per round (num_fedavg_epochs,
    # fed_worker.py:61-113); 4 local epochs at the same 12 rounds must
    # close most of the gap if that explanation is right.
    runs += [seeded("fedavg_e4", lambda s: run_mode(
        "fedavg", *data[s], seed=s, label="fedavg_e4",
        num_fedavg_epochs=4))]
    # sketch table-transport dtype arm (ISSUE 19 satellite): the same
    # sketch run with the client->server table narrowed on the wire to
    # bf16 / int8 (Config.sketch_table_dtype; server decode still runs
    # f32). The claim: transport quantization buys its 2x/~4x byte
    # cut at an accuracy cost within seed noise of the f32 table.
    runs += [seeded(f"sketch_{td}", lambda s, td=td: run_mode(
        "sketch", *data[s], seed=s, label=f"sketch_{td}",
        table_dtype=td)) for td in ("bf16", "int8")]
    # download top-k pair at sparse participation: with 40 clients each
    # participates ~1 round in 5, accumulating several rounds of
    # changed coordinates between downloads — the regime --topk_down
    # truncates (reference fed_worker.py:232-247). NB the byte
    # ACCOUNTING intentionally matches the reference's, which counts
    # weights-changed-since-last-participation regardless of topk_down
    # (fed_aggregator.py:239-289) — so the measured effect here is the
    # accuracy cost of training on truncated weights, the trade-off
    # the paper reports for download compression, not a bytes delta.
    data40 = {s: make_data(seed=s, num_clients=40) for s in SEEDS}
    runs += [seeded("sketch_40c", lambda s: run_mode(
                 "sketch", *data40[s], seed=s, label="sketch_40c")),
             seeded("sketch_topk_down_40c", lambda s: run_mode(
                 "sketch_topk_down", *data40[s], seed=s,
                 label="sketch_topk_down_40c"))]
    # download-k sweep: the k-vs-accuracy tradeoff curve for download
    # compression (down_k = upload k x {1 (above), 4, 16}); with each
    # client participating ~1 round in 5 and the server update k-sparse
    # per round, down_k ≈ 5k is where staleness stops accumulating —
    # the sweep brackets it
    runs += [seeded(f"sketch_topk_down_40c_down{m}x",
                    lambda s, m=m: run_mode(
                        "sketch_topk_down", *data40[s], seed=s,
                        label=f"sketch_topk_down_40c_down{m}x",
                        down_k_mult=m))
             for m in (4, 16)]
    results = {
        "config": {"workers": WORKERS, "batch": BATCH, "epochs": EPOCHS,
                   "full_model": FULL, "seeds": list(SEEDS),
                   "platform": jax.devices()[0].platform,
                   "num_clients": int(data[SEEDS[0]][0].num_clients)},
        "runs": runs,
    }
    results["wall_clock_s"] = round(time.time() - t0, 1)

    by_mode = {r["mode"]: r for r in results["runs"]}

    def acc(m):
        return by_mode[m]["final_acc_mean"]

    un_floats = by_mode["uncompressed"]["upload_floats_per_client_round"]
    sk_ratio = un_floats / by_mode["sketch"]["upload_floats_per_client_round"]
    lt_ratio = un_floats / by_mode["local_topk"]["upload_floats_per_client_round"]
    results["summary"] = {
        # every *_final_acc is the MEAN over config.seeds; per-seed
        # values and spread live in each run record
        "sketch_final_acc": acc("sketch"),
        "uncompressed_final_acc": acc("uncompressed"),
        "local_topk_final_acc": acc("local_topk"),
        "fedavg_final_acc": acc("fedavg"),
        "fedavg_e4_final_acc": acc("fedavg_e4"),
        "sketch_40c_final_acc": acc("sketch_40c"),
        "sketch_topk_down_40c_final_acc": acc("sketch_topk_down_40c"),
        "sketch_topk_down_40c_down4x_final_acc":
            acc("sketch_topk_down_40c_down4x"),
        "sketch_topk_down_40c_down16x_final_acc":
            acc("sketch_topk_down_40c_down16x"),
        "sketch_bf16_final_acc": acc("sketch_bf16"),
        "sketch_int8_final_acc": acc("sketch_int8"),
        "sketch_bf16_wire_cut_x": round(
            by_mode["sketch"]["upload_bytes_per_client_round"]
            / by_mode["sketch_bf16"]["upload_bytes_per_client_round"],
            2),
        "sketch_int8_wire_cut_x": round(
            by_mode["sketch"]["upload_bytes_per_client_round"]
            / by_mode["sketch_int8"]["upload_bytes_per_client_round"],
            2),
        "sketch_upload_compression_x": round(sk_ratio, 2),
        "local_topk_upload_compression_x": round(lt_ratio, 2),
        "max_seed_spread": max(r["final_acc_spread"] for r in runs),
    }

    def spread(m):
        return by_mode[m]["final_acc_spread"]

    # whether the round-starvation claim can be demanded at all at
    # this corpus difficulty (see the assertion block below); recorded
    # in the artifact so a saturated suite is visibly degenerate. The
    # gap is a difference of two noisy means: widen the gate by BOTH
    # spreads so a lucky uncompressed seed can't flakily demand the
    # strict lift.
    starved_gap = (results["summary"]["uncompressed_final_acc"]
                   - results["summary"]["fedavg_final_acc"])
    claim_exercised = (starved_gap
                       > 0.12 + spread("fedavg") + spread("uncompressed"))
    results["summary"]["starvation_claim_exercised"] = claim_exercised
    import bench
    with open(bench.artifact_dest(
            OUT, results["config"]["platform"]), "w") as f:
        json.dump(results, f, indent=1)
    print(json.dumps(results["summary"]))

    # the paper's qualitative claims, asserted on seed MEANS. Margins
    # are seed-noise-aware: at this corpus size a single seed swings
    # several points (measured sketch spread 0.059 over seeds 0-2), so
    # fixed margins tuned on one seed produce flaky claims — each
    # behind-by margin widens by the claimant's own measured spread
    # (`spread`, defined with the summary above).
    assert acc("sketch") > 0.5, "sketched training failed to learn"
    assert acc("sketch") > acc("uncompressed") - 0.05 - spread("sketch"), \
        "sketch fell behind uncompressed beyond a few points + seed noise"
    assert sk_ratio >= 2.5, "sketch table not compressed (ref ratio 2.6x)"
    assert acc("local_topk") > acc("uncompressed") - 0.1 \
        - spread("local_topk"), "local_topk fell far behind uncompressed"
    # table-transport dtype arm (ISSUE 19): the quantized tables must
    # hold their byte cut (pure config math) AND stay within a few
    # points + seed noise of the f32 table's accuracy
    assert results["summary"]["sketch_bf16_wire_cut_x"] >= 2.0, \
        "bf16 table transport lost its 2x byte cut"
    assert results["summary"]["sketch_int8_wire_cut_x"] >= 3.0, \
        "int8 table transport lost its ~4x byte cut"
    assert acc("sketch_bf16") > acc("sketch") - 0.05 \
        - spread("sketch_bf16"), \
        "bf16 table transport cost more than a few points vs f32"
    assert acc("sketch_int8") > acc("sketch") - 0.08 \
        - spread("sketch_int8"), \
        "int8 table transport cost more than a few points vs f32"
    assert lt_ratio >= 10, "local_topk upload not >=10x compressed"
    assert acc("fedavg") > 0.5, "fedavg failed to learn"
    # fedavg trains ~16x fewer aggregation rounds than the per-batch
    # modes at this corpus (see sweep note above); 4 local epochs at
    # the same round count must recover most of the uncompressed gap —
    # the round-starvation explanation, asserted. CEILING-AWARE: the
    # lift can only be demanded when starvation actually cost
    # something at this corpus difficulty — on a corpus easy enough
    # that 12 starved rounds already match uncompressed, e4 must
    # merely not regress.
    if claim_exercised:
        assert acc("fedavg_e4") > acc("fedavg") + 0.1, \
            "more local epochs failed to lift fedavg (round-" \
            "starvation explanation would be wrong -> investigate)"
    else:
        # corpus too easy for starvation to bind — keep the degeneracy
        # LOUD so a saturated suite is never mistaken for evidence
        print(f"WARNING: starvation claim NOT exercised (gap "
              f"{starved_gap:.3f} within noise) — corpus difficulty "
              f"leaves no headroom; lower synthetic_signal",
              flush=True)
        assert acc("fedavg_e4") >= acc("fedavg") - 0.05 \
            - spread("fedavg_e4"), \
            "fedavg_e4 regressed below starved fedavg"
    assert acc("fedavg_e4") > acc("uncompressed") - 0.15, \
        "fedavg_e4 still far behind uncompressed"
    # topk_down trains on truncated stale weights; the paper reports
    # the same accuracy cost for download compression — learning (well
    # above 10-class chance), just behind full-download sketch
    assert acc("sketch_topk_down_40c") > 0.5, \
        "sketch+topk_down failed to learn"
    # the download-k tradeoff: a larger download budget must recover
    # (monotonically, within noise) toward the full-download sketch —
    # the k-vs-accuracy curve VERDICT r3 asked for. At down_k = 16k
    # (~D/3 per download vs ~5 server-rounds of k-sparse changes per
    # participation gap) the staleness truncation should cost almost
    # nothing.
    assert acc("sketch_topk_down_40c_down4x") >= \
        acc("sketch_topk_down_40c") - 0.03 \
        - spread("sketch_topk_down_40c_down4x"), \
        "down_k=4k fell below down_k=k"
    assert acc("sketch_topk_down_40c_down16x") >= \
        acc("sketch_topk_down_40c_down4x") - 0.03 \
        - spread("sketch_topk_down_40c_down16x"), \
        "down_k=16k fell below down_k=4k"
    assert acc("sketch_topk_down_40c_down16x") > \
        acc("sketch_40c") - 0.06 - spread("sketch_topk_down_40c_down16x"), \
        "a near-full download budget still far behind full download"
    print("convergence-under-compression: OK")


if __name__ == "__main__":
    main()

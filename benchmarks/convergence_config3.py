"""BASELINE config #3 convergence at FULL model width: FixupResNet18 /
CIFAR100, 100 non-IID clients (the natural one-class-per-client
partition), local_topk + local error feedback + local momentum —
reference entry `cv_train.py --mode local_topk --error_type local`
(BASELINE.md configs table row 3).

This closes VERDICT r3 weak item: the committed convergence suite
(benchmarks/convergence.py) covers config-#1/#2 shapes on a shrunken
model; this run is `full_model: true` — the real 11M-parameter
FixupResNet18 (norm-free, the reference's own answer to BN under
non-IID client batches, models/fixup_resnet18.py) with per-client
error/momentum state at 100 clients (the [100, D] sharded rows that
SURVEY.md §7.3 calls the memory hazard).

Corpus: the synthetic class-signal CIFAR100 substitute (zero-egress
environment — data/cifar.py) sized by CONV3_TRAIN/CONV3_VAL; the code
path is identical to real CIFAR100 pickles when those are on disk.

Writes benchmarks/convergence_config3_results.json.

Usage:
  XLA_FLAGS=--xla_force_host_platform_device_count=8 JAX_PLATFORMS=cpu \
      python benchmarks/convergence_config3.py
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
from commefficient_tpu.data import FedCIFAR100, FedLoader, FedValLoader
from commefficient_tpu.data.transforms import cifar100_transforms
from commefficient_tpu.federated.api import FedModel, FedOptimizer
from commefficient_tpu.models import build_model
from commefficient_tpu.ops.flat import flatten_params
from commefficient_tpu.training.cv_train import (
    _fixup_lr_scales, make_compute_loss,
)
from commefficient_tpu.utils.cache import enable_persistent_compilation_cache
from commefficient_tpu.utils.schedules import LambdaLR, PiecewiseLinear

EPOCHS = int(os.environ.get("CONV3_EPOCHS", "6"))
N_TRAIN = int(os.environ.get("CONV3_TRAIN", "2000"))
N_VAL = int(os.environ.get("CONV3_VAL", "500"))
WORKERS = 8
BATCH = int(os.environ.get("CONV3_BATCH", "4"))
PEAK_LR = float(os.environ.get("CONV3_LR", "0.4"))
OUT = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                   "convergence_config3_results.json")


def main():
    enable_persistent_compilation_cache()
    t0 = time.time()
    root = os.environ.get("CONV3_DATA",
                          os.path.join("/tmp", "conv3_data"))
    train_t, test_t = cifar100_transforms(seed=0)
    # num_clients=None -> the natural partition: one class per client,
    # 100 clients for CIFAR100 (reference fed_cifar.py:77-84)
    train_set = FedCIFAR100(root, transform=train_t, train=True,
                            synthetic_examples=(N_TRAIN, N_VAL))
    val_set = FedCIFAR100(root, transform=test_t, train=False,
                          synthetic_examples=(N_TRAIN, N_VAL))
    assert train_set.num_clients == 100

    model_mod = build_model("FixupResNet18", num_classes=100)
    x0 = jnp.zeros((2, 32, 32, 3), jnp.float32)
    params = model_mod.init(jax.random.PRNGKey(0), x0)
    D = int(flatten_params(params)[0].shape[0])
    print(f"FixupResNet18 D={D} ({D / 1e6:.1f}M params), "
          f"100 non-IID clients, local_topk k={max(D // 50, 64)}",
          flush=True)

    cfg = Config(mode="local_topk", error_type="local",
                 local_momentum=0.9, virtual_momentum=0.0,
                 k=max(D // 50, 64), seed=0, num_workers=WORKERS,
                 local_batch_size=BATCH, weight_decay=5e-4,
                 microbatch_size=-1, num_epochs=float(EPOCHS))

    loader = FedLoader(train_set, WORKERS, BATCH, seed=0)
    val_loader = FedValLoader(val_set, 64,
                              num_shards=min(jax.device_count(), WORKERS))
    # Fixup nets train bias/scale scalars at 0.1x LR (the reference's
    # param groups, cv_train.py:366-376; our driver does the same)
    model = FedModel(None, make_compute_loss(model_mod), cfg,
                     params=params, num_clients=100,
                     lr_scale_vec=_fixup_lr_scales(params))
    opt = FedOptimizer(model)
    spe = loader.steps_per_epoch
    sched = PiecewiseLinear([0, 1, EPOCHS], [0.05, PEAK_LR, 0])
    lr_sched = LambdaLR(opt, lr_lambda=lambda s: sched(s / spe))

    curve = []
    total_up = total_down = 0.0
    rounds = 0
    for epoch in range(EPOCHS):
        for client_ids, data, mask in loader.epoch():
            lr_sched.step()
            loss, acc, down, up = model((client_ids, data, mask))
            opt.step()
            total_up += float(up.sum())
            total_down += float(down.sum())
            rounds += 1
            if rounds == 1 or rounds % 16 == 0:
                print(f"round {rounds} loss {float(np.mean(loss)):.3f} "
                      f"({time.time() - t0:.0f}s)", flush=True)
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
        print(f"epoch {epoch + 1} round {rounds} acc {acc:.4f} "
              f"up {total_up / 2**20:.2f} MiB", flush=True)

    un_floats = D
    results = {
        "config": {
            "baseline_config": 3,
            "model": "FixupResNet18", "dataset": "CIFAR100",
            "full_model": True, "grad_size": D,
            "num_clients": 100, "partition": "non-IID (1 class/client)",
            "mode": "local_topk", "error_type": "local",
            "local_momentum": 0.9,
            "k": model.cfg.k, "workers": WORKERS, "batch": BATCH,
            "epochs": EPOCHS, "train_examples": N_TRAIN,
            "platform": jax.devices()[0].platform,
        },
        "upload_floats_per_client_round": model.cfg.upload_floats,
        "upload_compression_x": round(un_floats / model.cfg.upload_floats,
                                      2),
        "curve": curve,
        "wall_clock_s": round(time.time() - t0, 1),
    }
    import bench
    with open(bench.artifact_dest(
            OUT, results["config"]["platform"]), "w") as f:
        json.dump(results, f, indent=1)
    print(json.dumps({"final_acc": curve[-1]["test_acc"],
                      "upload_compression_x":
                          results["upload_compression_x"],
                      "wall_clock_s": results["wall_clock_s"]}))

    # 100-class chance is 1%; the full-width non-IID local_topk run
    # must genuinely learn
    assert curve[-1]["test_acc"] > 0.1, "config #3 failed to learn"
    print("config #3 full-model convergence: OK")


if __name__ == "__main__":
    main()

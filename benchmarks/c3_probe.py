"""Short discriminating probe for the config-#3 stall: run N rounds of
FixupResNet18/CIFAR100 under one of three arms and print the loss
trajectory. Arms:
  uncompressed       no compression at all (isolates model/recipe)
  ltk_exact          local_topk with the threshold gate lifted (exact
                     index top-k at 11M — the pre-round-5 path)
  ltk_threshold      local_topk with the sampled-threshold route (the
                     round-5 path, active at D=11.2M > 4M)

If all three stall: the recipe (lr/schedule/init), not compression.
If only threshold stalls: the round-5 selection broke something.

Usage: C3P_ARM=ltk_threshold python benchmarks/c3_probe.py
"""
from __future__ import annotations

import os
import sys
import time

import jax

import jax.numpy as jnp
import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from commefficient_tpu.config import Config
from commefficient_tpu.data import FedCIFAR100, FedLoader
from commefficient_tpu.data.transforms import cifar100_transforms
from commefficient_tpu.federated.api import FedModel, FedOptimizer
from commefficient_tpu.models import build_model
from commefficient_tpu.ops import flat as flat_mod
from commefficient_tpu.ops.flat import flatten_params
from commefficient_tpu.training.cv_train import (
    _fixup_lr_scales, make_compute_loss,
)
from commefficient_tpu.utils.cache import enable_persistent_compilation_cache

ARM = os.environ.get("C3P_ARM", "ltk_threshold")
ROUNDS = int(os.environ.get("C3P_ROUNDS", "24"))
LR = float(os.environ.get("C3P_LR", "0.1"))
MOM = float(os.environ.get("C3P_MOMENTUM", "0"))
BATCH = int(os.environ.get("C3P_BATCH", "4"))
SCALES = os.environ.get("C3P_LR_SCALES", "1") == "1"


def main():
    enable_persistent_compilation_cache()
    if ARM == "ltk_exact":
        flat_mod.TOPK_THRESHOLD_MIN_D = 1 << 60   # lift the gate
    t0 = time.time()
    train_t, _ = cifar100_transforms(seed=0)
    train_set = FedCIFAR100(os.environ.get("C3P_DATA", "/tmp/c3p_data"),
                            transform=train_t, train=True,
                            synthetic_examples=(2000, 400))
    model_mod = build_model("FixupResNet18", num_classes=100)
    params = model_mod.init(jax.random.PRNGKey(0),
                            jnp.zeros((2, 32, 32, 3), jnp.float32))
    D = int(flatten_params(params)[0].shape[0])

    mode = "uncompressed" if ARM == "uncompressed" else "local_topk"
    cfg = Config(mode=mode,
                 error_type="none" if mode == "uncompressed" else "local",
                 local_momentum=0.0 if mode == "uncompressed" else 0.9,
                 virtual_momentum=MOM if mode == "uncompressed" else 0.0,
                 k=max(D // 50, 64), seed=0,
                 num_workers=8, local_batch_size=BATCH,
                 weight_decay=5e-4, microbatch_size=-1, num_epochs=1.0)
    loader = FedLoader(train_set, 8, BATCH, seed=0)
    model = FedModel(None, make_compute_loss(model_mod), cfg,
                     params=params, num_clients=100,
                     lr_scale_vec=(_fixup_lr_scales(params)
                                   if SCALES else None))
    opt = FedOptimizer(model)
    opt.param_groups[0]["lr"] = LR

    print(f"[{ARM}] D={D} k={cfg.k} lr={LR}", flush=True)
    r = 0
    for epoch in range(100):
        for client_ids, data, mask in loader.epoch():
            loss, acc, down, up = model((client_ids, data, mask))
            opt.step()
            r += 1
            if r <= 4 or r % 4 == 0:
                print(f"[{ARM}] round {r} loss "
                      f"{float(np.mean(np.asarray(loss))):.4f} acc "
                      f"{float(np.mean(np.asarray(acc))):.4f} "
                      f"({time.time() - t0:.0f}s)", flush=True)
            if r >= ROUNDS:
                return


if __name__ == "__main__":
    main()

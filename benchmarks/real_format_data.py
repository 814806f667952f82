"""Real-FORMAT, full-SIZE data archives through the real readers
(VERDICT r4 next #6).

Zero-egress means the genuine CIFAR bytes cannot be downloaded, so
everything short of the bytes is proven here: a full-size CIFAR-10
archive in the exact on-disk format torchvision/the reference download
(`cifar-10-batches-py/` with five `data_batch_*` pickles of 10,000
CHW uint8 rows + `test_batch` + `batches.meta`, pickle keys
b'data'/b'labels'/b'batch_label'/b'filenames' — reference
CommEfficient/data_utils/fed_cifar.py:28-75 consumes this via
torchvision), written at the real 50,000/10,000 geometry, then
consumed END TO END through `data/cifar.py`'s REAL pickle reader (not
the synthetic fallback): natural 10-client partition, flagship
full-width ResNet9, sketch rounds at the reference's 5x500k/k=50k
geometry, and a full 10,000-image eval pass.

If genuine archives ARE present under $CIFAR_DIR (or ./dataset), they
are used as-is — only the bytes, never the code path, differ.

Writes benchmarks/real_format_results.json.

Usage:
  XLA_FLAGS=--xla_force_host_platform_device_count=8 JAX_PLATFORMS=cpu \
      python benchmarks/real_format_data.py       (or plain, on TPU)
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

ROUNDS = int(os.environ.get("REALFMT_ROUNDS", "8"))
WORKERS = 8
BATCH = 32
OUT = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                   "real_format_results.json")

def main():
    from commefficient_tpu.config import Config
    from commefficient_tpu.data import FedCIFAR10, FedLoader, FedValLoader
    from commefficient_tpu.data.cifar import (
        _try_load_cifar_pickles, write_cifar10_archive,
    )
    from commefficient_tpu.data.transforms import cifar10_transforms
    from commefficient_tpu.federated.api import FedModel, FedOptimizer
    from commefficient_tpu.models import ResNet9
    from commefficient_tpu.training.cv_train import make_compute_loss
    from commefficient_tpu.utils.cache import (
        enable_persistent_compilation_cache,
    )
    from commefficient_tpu.utils.schedules import LambdaLR, PiecewiseLinear

    enable_persistent_compilation_cache()
    t0 = time.time()
    root = os.environ.get("CIFAR_DIR", "/tmp/real_format_cifar")
    genuine = _try_load_cifar_pickles(root, "CIFAR10") is not None
    if not genuine:
        write_cifar10_archive(root)
    src = "genuine archives found on disk" if genuine else \
        "format-exact synthetic archive (zero-egress)"
    print(f"archive under {root}: {src}", flush=True)

    # the REAL reader: no synthetic_examples passed — a missing/broken
    # archive would raise, so this run can only succeed via the pickle
    # path the reference's own download feeds
    train_t, test_t = cifar10_transforms(seed=0)
    train_set = FedCIFAR10(root, transform=train_t, train=True)
    val_set = FedCIFAR10(root, transform=test_t, train=False)
    assert int(train_set.data_per_client.sum()) == 50_000
    assert train_set.num_val_images == 10_000
    assert train_set.num_clients == 10

    model_mod = ResNet9(num_classes=10)  # FULL width: the flagship model
    x0 = jnp.zeros((2, 32, 32, 3), jnp.float32)
    params = model_mod.init(jax.random.PRNGKey(0), x0)
    from commefficient_tpu.ops.flat import flatten_params
    D = int(flatten_params(params)[0].shape[0])

    # flagship sketch geometry (reference utils.py:142-145)
    cfg = Config(mode="sketch", error_type="virtual",
                 virtual_momentum=0.9, local_momentum=0.0,
                 k=50_000, num_rows=5, num_cols=500_000, num_blocks=20,
                 weight_decay=5e-4, microbatch_size=-1, seed=0,
                 num_workers=WORKERS, local_batch_size=BATCH)
    loader = FedLoader(train_set, WORKERS, BATCH, seed=0)
    val_loader = FedValLoader(val_set, 100,
                              num_shards=min(jax.device_count(), WORKERS))
    model = FedModel(None, make_compute_loss(model_mod), cfg,
                     params=params, num_clients=10)
    opt = FedOptimizer(model)
    # gentle LR: this run proves the real-format DATA PATH at full
    # geometry, not a tuned convergence curve (the no-norm full-width
    # ResNet9 needs the cifar10-fast warmup recipe to take lr 0.4;
    # at 8 rounds a blowup would just make the artifact ugly)
    peak = float(os.environ.get("REALFMT_LR", "0.05"))
    sched = PiecewiseLinear([0, ROUNDS], [peak, peak / 10])
    lr_sched = LambdaLR(opt, lr_lambda=sched)

    losses = []
    rounds = 0
    for client_ids, data, mask in loader.epoch():
        if rounds >= ROUNDS:
            break
        lr_sched.step()
        loss, acc, down, up = model((client_ids, data, mask))
        opt.step()
        losses.append(float(np.mean(np.asarray(loss))))
        rounds += 1
        if rounds in (1, 2) or rounds % 4 == 0:
            print(f"round {rounds} loss {losses[-1]:.3f} "
                  f"({time.time() - t0:.0f}s)", flush=True)

    # full 10,000-image eval through the real val.npz written from the
    # archive's test_batch
    model.train(False)
    tot = n = 0.0
    for vdata, vmask in val_loader.batches():
        vl, va, vc = model((vdata, vmask))
        tot += float((va * vc).sum())
        n += float(vc.sum())
    acc = tot / max(n, 1)
    print(f"eval over {int(n)} images: acc {acc:.4f}", flush=True)

    out = {
        "metric": "real_format_cifar10_full_geometry",
        "platform": jax.devices()[0].platform,
        "archive": src,
        "archive_format": "cifar-10-batches-py pickles "
                          "(5x10k train + 10k test, CHW uint8 rows)",
        "reader": "data/cifar.py _try_load_cifar_pickles "
                  "(synthetic fallback NOT reachable in this run)",
        "train_images": 50_000, "val_images": 10_000,
        "grad_size": D, "rounds": rounds,
        "sketch_geometry": {"rows": 5, "cols": 500_000, "k": 50_000,
                            "blocks": 20},
        "loss_first": round(losses[0], 4),
        "loss_last": round(losses[-1], 4),
        "eval_images": int(n), "eval_acc": round(acc, 4),
        "wall_clock_s": round(time.time() - t0, 1),
    }
    import bench
    with open(bench.artifact_dest(OUT, out["platform"]), "w") as f:
        json.dump(out, f, indent=1)
    print(json.dumps(out))
    assert np.all(np.isfinite(losses)), "non-finite training loss"
    assert n == 10_000.0
    print("real-format full-geometry run: OK")


if __name__ == "__main__":
    main()

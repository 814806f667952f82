"""Federated CIFAR10/CIFAR100.

Capability parity with the reference (reference:
CommEfficient/data_utils/fed_cifar.py): the train set is partitioned
into one natural unit per class — label == natural client id
(reference fed_cifar.py:77-84) — and resharded over `num_clients` by
FedDataset.data_per_client; the val set is flat.

Sources, in order of preference:
  1. the standard CIFAR python pickle batches under dataset_dir
     (cifar-10-batches-py / cifar-100-python), if present on disk;
  2. a deterministic synthetic substitute (class-dependent Gaussian
     blobs) sized by `synthetic_examples` — this environment has no
     network egress, and tests/benchmarks need data with the real
     shapes and a learnable class signal.

Storage: one .npy per class (the reference's layout choice,
fed_cifar.py:45-58) under <dataset_dir>/<name>/.
"""
from __future__ import annotations

import os
import pickle
from typing import Optional, Tuple

import numpy as np

from commefficient_tpu.data.fed_dataset import FedDataset
from commefficient_tpu.utils.atomic_io import (
    atomic_save, atomic_savez, atomic_write_bytes,
)


def _try_load_cifar_pickles(root: str, name: str):
    """Read the standard CIFAR batch pickles if present."""
    if name == "CIFAR10":
        d = os.path.join(root, "cifar-10-batches-py")
        if not os.path.isdir(d):
            return None
        xs, ys = [], []
        for i in range(1, 6):
            with open(os.path.join(d, f"data_batch_{i}"), "rb") as f:
                b = pickle.load(f, encoding="bytes")
            xs.append(b[b"data"])
            ys.extend(b[b"labels"])
        with open(os.path.join(d, "test_batch"), "rb") as f:
            tb = pickle.load(f, encoding="bytes")
        train = (np.concatenate(xs), np.array(ys))
        test = (np.asarray(tb[b"data"]), np.array(tb[b"labels"]))
    else:
        d = os.path.join(root, "cifar-100-python")
        if not os.path.isdir(d):
            return None
        with open(os.path.join(d, "train"), "rb") as f:
            b = pickle.load(f, encoding="bytes")
        train = (np.asarray(b[b"data"]), np.array(b[b"fine_labels"]))
        with open(os.path.join(d, "test"), "rb") as f:
            b = pickle.load(f, encoding="bytes")
        test = (np.asarray(b[b"data"]), np.array(b[b"fine_labels"]))

    def to_nhwc(x):
        return x.reshape(-1, 3, 32, 32).transpose(0, 2, 3, 1)

    return (to_nhwc(train[0]), train[1]), (to_nhwc(test[0]), test[1])


# bump when the generator's semantics change: the on-disk .npy cache
# is keyed by example counts only, so a semantic change must force a
# re-prepare (see _cached_stats_ok)
_SYNTH_VERSION = 2


def _synthetic_cifar(num_classes: int, n_train: int, n_val: int, seed: int,
                     signal: float = 0.6):
    """Deterministic class-separable images: per-class mean pattern +
    noise. Gives smoke/bench runs a learnable signal.

    v2: the class protos are LOW-FREQUENCY (8x8 blocks upsampled to
    32x32) and horizontally symmetric. v1 used i.i.d. per-pixel
    protos, which the standard train transforms destroy: a +-4px
    random crop decorrelates a per-pixel pattern almost entirely and
    a horizontal flip negates it, so even direct SGD sat at chance
    for epochs (measured on the chip, round 5).
    Blocky symmetric protos survive crop (75%+ block overlap) and
    flip (exactly invariant), making the augmented synthetic task
    behave like real CIFAR instead of an adversarial one.

    `signal` is the proto mixing weight (1-signal is noise): 0.6 makes
    an easy corpus for smokes/benches; convergence studies that need
    the compression modes to DIFFERENTIATE (not all saturate at 1.0)
    pass a lower value."""
    rng = np.random.RandomState(seed)
    base = rng.rand(num_classes, 8, 8, 3).astype(np.float32)
    base = (base + base[:, :, ::-1]) / 2            # flip-invariant
    protos = np.repeat(np.repeat(base, 4, axis=1), 4, axis=2)

    def gen(n):
        labels = rng.randint(0, num_classes, size=n)
        noise = rng.rand(n, 32, 32, 3).astype(np.float32)
        imgs = signal * protos[labels] + (1.0 - signal) * noise
        return (imgs * 255).astype(np.uint8), labels.astype(np.int64)

    return gen(n_train), gen(n_val)


CIFAR10_LABELS = [
    b"airplane", b"automobile", b"bird", b"cat", b"deer",
    b"dog", b"frog", b"horse", b"ship", b"truck",
]


def write_cifar10_archive(root: str, seed: int = 0,
                          n_per_batch: int = 10_000) -> str:
    """A `cifar-10-batches-py` directory format-identical to the real
    download: 5 train pickles x 10,000 rows + test_batch + batches.meta,
    CHW uint8 b'data' rows, python list b'labels', pickle protocol 2
    (the original archives' encoding). Image content is the
    deterministic class-signal synthetic (the bytes are the only thing
    zero-egress can't reproduce); everything downstream — file layout,
    dict keys, dtypes, row format, reader code — is the real thing."""
    d = os.path.join(root, "cifar-10-batches-py")
    if os.path.isfile(os.path.join(d, "batches.meta")):  # written last
        return d
    os.makedirs(d, exist_ok=True)
    rng = np.random.RandomState(seed)
    protos = rng.rand(10, 32, 32, 3).astype(np.float32)

    def make_rows(n, tag):
        labels = rng.randint(0, 10, size=n)
        noise = rng.rand(n, 32, 32, 3).astype(np.float32)
        imgs = ((0.6 * protos[labels] + 0.4 * noise) * 255).astype(np.uint8)
        # real row format: CHW flattened to 3072, R plane first
        data = imgs.transpose(0, 3, 1, 2).reshape(n, 3072)
        fnames = [b"%s_s_%06d.png" % (CIFAR10_LABELS[l], i)
                  for i, l in enumerate(labels)]
        return {b"batch_label": tag, b"labels": labels.tolist(),
                b"data": data, b"filenames": fnames}

    def dump(name, obj):
        atomic_write_bytes(os.path.join(d, name),
                           pickle.dumps(obj, protocol=2))

    for i in range(1, 6):
        dump(f"data_batch_{i}",
             make_rows(n_per_batch, b"training batch %d of 5" % i))
    dump("test_batch", make_rows(n_per_batch, b"testing batch 1 of 1"))
    dump("batches.meta", {b"num_cases_per_batch": n_per_batch,
                          b"label_names": CIFAR10_LABELS,
                          b"num_vis": 3072})
    return d


class FedCIFAR10(FedDataset):
    num_classes = 10

    def __init__(self, dataset_dir, dataset_name="CIFAR10", transform=None,
                 do_iid=False, num_clients=None, train=True, download=False,
                 synthetic_examples: Optional[Tuple[int, int]] = None,
                 seed: int = 0, synthetic_signal: float = 0.6):
        self._synthetic_examples = synthetic_examples
        self._synthetic_signal = synthetic_signal
        self._seed = seed
        super().__init__(dataset_dir, dataset_name, transform, do_iid,
                         num_clients, train, download, seed)
        self._cache = {}

    def _dir(self):
        return os.path.join(self.dataset_dir, self.dataset_name)

    def _cached_stats_ok(self) -> bool:
        """Re-prepare when the cached corpus isn't the one that would
        be prepared NOW: real pickle archives on disk always win (so a
        cache stamped source=synthetic is stale the moment pickles
        appear), and a synthetic cache must match both the requested
        sizing and the current generator version."""
        try:
            import json
            with open(self.stats_path()) as f:
                stats = json.load(f)
        except (OSError, ValueError):
            # missing/unreadable/torn stats file -> re-prepare; anything
            # else (incl. InjectedFault from the fault harness) raises
            return False
        have_pickles = _try_load_cifar_pickles(
            self.dataset_dir, self.dataset_name) is not None
        if have_pickles:
            return stats.get("source") == "pickles"
        if self._synthetic_examples is None:
            # no pickles and nothing to generate: let prepare() raise
            # its actionable FileNotFoundError only if the cache is
            # absent; an existing cache (whatever its source) is all
            # there is
            return True
        n_train, n_val = self._synthetic_examples
        return (stats.get("source") == "synthetic"
                and sum(stats["images_per_client"]) == n_train
                and stats["num_val_images"] == n_val
                and stats.get("synthetic_version") == _SYNTH_VERSION
                and stats.get("synthetic_signal") == self._synthetic_signal)

    def prepare(self, download: bool = False):
        loaded = _try_load_cifar_pickles(self.dataset_dir,
                                         self.dataset_name)
        if loaded is None:
            if self._synthetic_examples is None:
                raise FileNotFoundError(
                    f"No {self.dataset_name} archives under "
                    f"{self.dataset_dir} and no network egress; pass "
                    f"synthetic_examples=(n_train, n_val) to generate "
                    f"synthetic data")
            n_train, n_val = self._synthetic_examples
            (xtr, ytr), (xva, yva) = _synthetic_cifar(
                self.num_classes, n_train, n_val, self._seed,
                signal=self._synthetic_signal)
        else:
            (xtr, ytr), (xva, yva) = loaded

        os.makedirs(self._dir(), exist_ok=True)
        images_per_client = []
        for c in range(self.num_classes):
            sel = ytr == c
            atomic_save(os.path.join(self._dir(), f"client{c}.npy"),
                        xtr[sel])
            images_per_client.append(int(sel.sum()))
        atomic_savez(os.path.join(self._dir(), "val.npz"),
                     images=xva, labels=yva)
        # the source + generator-version stamp is what
        # _cached_stats_ok uses to invalidate a cache that is stale
        # (v1 corpus) or of the wrong provenance (synthetic .npy left
        # behind after real pickles appeared)
        self.write_stats(
            images_per_client, len(yva),
            extra=({"source": "pickles"} if loaded is not None else
                   {"source": "synthetic",
                    "synthetic_version": _SYNTH_VERSION,
                    "synthetic_signal": self._synthetic_signal}))

    def _client_images(self, cid: int) -> np.ndarray:
        if cid not in self._cache:
            self._cache[cid] = np.load(
                os.path.join(self._dir(), f"client{cid}.npy"))
        return self._cache[cid]

    def _get_train_batch(self, nat_client_id: int, idxs: np.ndarray):
        imgs = self._client_images(nat_client_id)[idxs]
        # label == natural client id (reference fed_cifar.py:77-84)
        labels = np.full(len(idxs), nat_client_id, np.int64)
        return imgs, labels

    def _get_val_batch(self, idxs: np.ndarray):
        if "val" not in self._cache:
            z = np.load(os.path.join(self._dir(), "val.npz"))
            self._cache["val"] = (z["images"], z["labels"])
        imgs, labels = self._cache["val"]
        return imgs[idxs], labels[idxs]


class FedCIFAR100(FedCIFAR10):
    num_classes = 100

    def __init__(self, dataset_dir, dataset_name="CIFAR100", **kw):
        super().__init__(dataset_dir, dataset_name, **kw)

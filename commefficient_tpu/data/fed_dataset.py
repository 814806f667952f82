"""Federated dataset base: client partitioning + metadata.

Capability parity with the reference data layer's core abstractions
(reference: CommEfficient/data_utils/fed_dataset.py — flat-index ->
(client_id, datum) mapping at :68-95, `data_per_client` at :31-48,
`stats.json` metadata at :55-59,97-98; non-IID natural partitions and
IID reshuffle at :28-29,71-75).

Host-side numpy only — the TPU program never sees ragged structures;
`commefficient_tpu.data.sampler` turns this into padded, static-shape
round batches.
"""
from __future__ import annotations

import functools
import json
import os
import time
from typing import Optional, Sequence, Tuple

import numpy as np

from commefficient_tpu.telemetry.trace import TRACE
from commefficient_tpu.utils.atomic_io import atomic_write_text


class FedDataset:
    """Base class: a train corpus partitioned over clients, plus a flat
    validation set.

    Subclasses implement `prepare()` (fill `self.images_per_client`,
    `self.num_val_images`, and storage) and the two fetchers
    `_get_train_batch(client_id, idxs)` / `_get_val_batch(idxs)`, each
    returning a tuple of stacked numpy arrays.
    """

    def __init__(self, dataset_dir: str, dataset_name: str,
                 transform=None, do_iid: bool = False,
                 num_clients: Optional[int] = None, train: bool = True,
                 download: bool = False, seed: int = 0):
        self.dataset_dir = dataset_dir
        self.dataset_name = dataset_name
        self.transform = transform
        self.transform_s = 0.0
        self.do_iid = do_iid
        self._num_clients = num_clients
        self.train = train

        if not do_iid and num_clients == 1:
            raise ValueError("can't have 1 client when non-iid")

        if (not os.path.exists(self.stats_path())
                or not self._cached_stats_ok()):
            self.prepare(download=download)
        self._load_meta()

        if self.do_iid:
            # IID: a fixed permutation reassigns data to clients
            # uniformly (reference fed_dataset.py:28-29,71-75)
            rng = np.random.RandomState(seed)
            self.iid_shuffle = rng.permutation(len(self))

        # precompute flat-index offsets of the natural partition
        self._nat_cumsum = np.concatenate(
            [[0], np.cumsum(self.images_per_client)])

    # ---- metadata -------------------------------------------------------
    def stats_path(self) -> str:
        return os.path.join(self.dataset_dir, self.dataset_name,
                            "stats.json")

    def write_stats(self, images_per_client: Sequence[int],
                    num_val_images: int, extra: Optional[dict] = None):
        """`extra`: dataset-specific metadata written alongside the
        counts in one shot — e.g. the synthetic-generator version and
        corpus source that _cached_stats_ok implementations use to
        invalidate stale caches (a semantic change to a generator
        must not silently serve the pre-change corpus)."""
        os.makedirs(os.path.dirname(self.stats_path()), exist_ok=True)
        stats = {"images_per_client": [int(x) for x in images_per_client],
                 "num_val_images": int(num_val_images)}
        if extra:
            stats.update(extra)
        # atomic (GL006): a preemption mid-write must not leave a torn
        # stats file shadowing an intact cache — _cached_stats_ok would
        # read garbage and re-prepare over good data
        atomic_write_text(self.stats_path(), json.dumps(stats))

    def _load_meta(self):
        with open(self.stats_path()) as f:
            stats = json.load(f)
        self.images_per_client = np.array(stats["images_per_client"])
        self.num_val_images = int(stats["num_val_images"])

    def _cached_stats_ok(self) -> bool:
        """Is the on-disk prepared dataset the one THIS construction
        asks for? Subclasses with a sized synthetic fallback override
        this to compare the cached stats against the requested sizing —
        without the check, constructing with different
        `synthetic_examples` silently reuses whatever sizing was
        prepared first in the same dataset_dir (a 2000-example cache
        once served a run that asked for 400)."""
        return True

    # ---- partition geometry --------------------------------------------
    @property
    def num_clients(self) -> int:
        return (self._num_clients if self._num_clients is not None
                else len(self.images_per_client))

    @property
    def data_per_client(self) -> np.ndarray:
        """Per-client example counts after resharding the natural
        partition over `num_clients` (reference fed_dataset.py:31-48:
        each natural unit — a class, writer, persona — is split across
        num_clients/num_units clients)."""
        if self.do_iid:
            n = len(self)
            per = np.full(self.num_clients, n // self.num_clients, dtype=int)
            per[self.num_clients - (n % self.num_clients):] += 1 \
                if n % self.num_clients else 0
            return per
        out = []
        n_units = len(self.images_per_client)
        per_unit = (self._num_clients // n_units
                    if self._num_clients is not None else 1)
        if per_unit < 1 or (self._num_clients is not None
                            and self._num_clients % n_units):
            # the reference dies with a bare ZeroDivisionError below the
            # unit count and silently builds a partition shorter than
            # num_clients for non-multiples (fed_dataset.py:42-44, then
            # an IndexError downstream); fail with an actionable message
            raise ValueError(
                f"non-IID partition needs num_clients to be a positive "
                f"multiple of the natural unit count ({n_units}; one "
                f"class/writer/persona per unit), got "
                f"num_clients={self._num_clients}. Use a multiple of "
                f"{n_units}, or --iid.")
        for n_images in self.images_per_client:
            counts = [n_images // per_unit] * per_unit
            counts[-1] += n_images % per_unit
            out.extend(counts)
        return np.array(out)

    def __len__(self) -> int:
        if self.train:
            return int(np.sum(self.images_per_client))
        return self.num_val_images

    # ---- fetch ----------------------------------------------------------
    @functools.cached_property
    def _client_offsets(self) -> np.ndarray:
        return np.concatenate([[0], np.cumsum(self.data_per_client)])

    def client_flat_indices(self, client_id, idx_within: np.ndarray
                            ) -> np.ndarray:
        """Map (client, local index) to flat dataset indices; one
        client for all of `idx_within`, or one per index."""
        flat = self._client_offsets[client_id] + idx_within
        if self.do_iid:
            flat = self.iid_shuffle[flat]
        return flat

    def _fetch(self, client_id, idx_within: np.ndarray
               ) -> Tuple[np.ndarray, ...]:
        """Stored examples by (client, local index), in the order
        asked, with one read per natural unit they fall in."""
        flat = self.client_flat_indices(client_id, np.asarray(idx_within))
        # flat index -> (natural client, index within natural client)
        nat = np.searchsorted(self._nat_cumsum, flat, side="right") - 1
        return self._gather_train(nat, flat - self._nat_cumsum[nat])

    def _transformed(self, transform, *args):
        if not TRACE.enabled:
            return transform(*args)
        # seconds inside the transform, for FedLoader's `load_fetch`
        # span (summed only while tracing)
        t0 = time.monotonic()
        try:
            return transform(*args)
        finally:
            self.transform_s += time.monotonic() - t0

    def get_client_batch(self, client_id: int,
                         idx_within: np.ndarray) -> Tuple[np.ndarray, ...]:
        """Fetch one client's (transformed) examples by local index."""
        batch = self._fetch(client_id, idx_within)
        if self.transform is not None:
            batch = self._transformed(self.transform, *batch)
        return batch

    def example_protos(self) -> Tuple[np.ndarray, ...]:
        """Zero-length arrays shaped and typed like a client's
        transformed batch: what FedLoader sizes its rounds' buffers
        from. A batch of no examples draws nothing from a transform's
        random stream."""
        batch = self._get_train_batch(0, np.zeros(0, np.int64))
        if self.transform is not None:
            batch = self.transform(*batch)
        return tuple(batch)

    def get_round_batch(self, client_ids: np.ndarray,
                        idx_within: np.ndarray, n_valid: np.ndarray,
                        out) -> bool:
        """Fetch and transform a round's rows: row i is client
        `client_ids[i]`'s examples `idx_within[i, :n_valid[i]]`,
        written into the views `out[i]`, one per array of an example;
        rows with no valid example are left alone. Where the transform
        has a cohort form (`transform.cohort`, data/transforms.py) all
        rows are fetched together and transformed in one call, and
        the result is True; otherwise client by client through
        `get_client_batch`, and False."""
        active = np.flatnonzero(n_valid)
        cohort = getattr(self.transform, "cohort", None)
        if cohort is None:
            for i in active:
                got = self.get_client_batch(
                    int(client_ids[i]), idx_within[i, :n_valid[i]])
                for dst, g in zip(out[i], got):
                    dst[...] = g
            return False
        valid = np.arange(idx_within.shape[1]) < n_valid[:, None]
        batch = self._fetch(np.repeat(client_ids, n_valid),
                            idx_within[valid])
        self._transformed(cohort, *batch, n_valid[active],
                          [out[i] for i in active])
        return True

    def get_val_batch(self, idxs: np.ndarray) -> Tuple[np.ndarray, ...]:
        batch = self._get_val_batch(np.asarray(idxs))
        if self.transform is not None:
            batch = self.transform(*batch)
        return batch

    def _gather_train(self, nat_clients: np.ndarray,
                      idx_within: np.ndarray) -> Tuple[np.ndarray, ...]:
        """Default gather: group by natural client and concatenate."""
        parts = []
        order = np.argsort(nat_clients, kind="stable")
        inv = np.empty_like(order)
        inv[order] = np.arange(len(order))
        sorted_nat = nat_clients[order]
        sorted_within = idx_within[order]
        outs = None
        for cid in np.unique(sorted_nat):
            sel = sorted_nat == cid
            got = self._get_train_batch(int(cid), sorted_within[sel])
            if outs is None:
                outs = [[] for _ in got]
            for o, g in zip(outs, got):
                o.append(g)
        stacked = [np.concatenate(o, axis=0) for o in outs]
        return tuple(s[inv] for s in stacked)

    # ---- subclass API ---------------------------------------------------
    def prepare(self, download: bool = False):
        raise NotImplementedError

    def _get_train_batch(self, nat_client_id: int, idxs: np.ndarray):
        raise NotImplementedError

    def _get_val_batch(self, idxs: np.ndarray):
        raise NotImplementedError

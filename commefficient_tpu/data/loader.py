"""Round-batch assembly: dataset + sampler -> device-ready arrays.

The glue the reference spreads across torch DataLoader construction
(reference: CommEfficient/cv_train.py:254-287) and the per-round
client grouping inside the aggregator (fed_aggregator.py:218-237).
Here grouping is free — the sampler already emits [num_workers, B]
per-client blocks — and batches go to the device as single contiguous
NHWC arrays.
"""
from __future__ import annotations

from typing import Iterator, Optional, Tuple

import numpy as np

from commefficient_tpu.data.fed_dataset import FedDataset
from commefficient_tpu.data.sampler import FedSampler, RoundIndices, ValSampler
from commefficient_tpu.telemetry.trace import TRACE


class FedLoader:
    """Iterates training rounds: for each RoundIndices, fetches and
    transforms every participating client's examples and stacks them
    into (client_ids [W], data pytree [W, B, ...], mask [W, B])."""

    def __init__(self, dataset: FedDataset, num_workers: int,
                 local_batch_size: int, seed: int = 0,
                 max_local_batch: int = -1,
                 feed_slice: Optional[slice] = None):
        """feed_slice: per-process batch feeding for multi-controller
        runs (parallel/multihost.local_row_slice) — the sampler still
        runs over the GLOBAL round (identical on every process, it is
        pure seeded index math), but only the rows in `feed_slice` are
        fetched/transformed/materialized. Yielded batches then carry
        global client_ids with process-local data/mask rows, which is
        exactly FedModel._call_train's multi-controller contract."""
        self.dataset = dataset
        self.sampler = FedSampler(dataset.data_per_client, num_workers,
                                  local_batch_size, seed=seed,
                                  max_local_batch=max_local_batch)
        self.feed_slice = feed_slice
        self._protos = None     # dataset.example_protos(), at first use

    @property
    def steps_per_epoch(self) -> int:
        return self.sampler.steps_per_epoch()

    def epoch(self, skip: int = 0
              ) -> Iterator[Tuple[np.ndarray, Tuple[np.ndarray, ...],
                                  np.ndarray]]:
        """skip: advance past the first `skip` rounds using sampler
        index math only — no fetch/transform/materialization — for
        O(1)-per-round mid-epoch resume fast-forward (the sampler's RNG
        state still advances identically to a full epoch)."""
        B = self.sampler.round_batch_size
        rounds = self.sampler.epoch()
        seq = 0
        while True:
            # graftscope: `load` is what one batch cost (`seq`: batches
            # yielded before it), made of `load_sample` (the sampler's
            # index math; an epoch's first holds its permutations),
            # `load_assemble` (the round's zeroed buffers) and
            # `load_fetch` (the dataset's fetch and transform of the
            # cohort, written into them). Every span closes before the
            # yield, so none holds the consumer's time; an epoch's last
            # `load` holds only the `load_sample` that found the epoch
            # over.
            with TRACE.span("load", seq=seq):
                with TRACE.span("load_sample"):
                    r = next(rounds, None)
                    while r is not None and skip > 0:
                        skip -= 1
                        r = next(rounds, None)
                batch = None if r is None else self._assemble(r, B)
            if batch is None:
                return
            seq += 1
            yield batch

    def _assemble(self, r: RoundIndices, B: int):
        """One round's (client_ids, data, mask) from its indices."""
        rows = slice(None) if self.feed_slice is None else self.feed_slice
        client_ids, idx_within, mask = (
            r.client_ids[rows], r.idx_within[rows], r.mask[rows])
        if len(client_ids) == 0:
            raise NotImplementedError(
                "this process owns no rows of the clients axis; "
                "zero-row feeding is not supported — use a mesh "
                "layout that gives every process client shards")
        n_valid = mask.sum(axis=1).astype(np.int64)
        if not n_valid.any():
            raise NotImplementedError(
                "every row this process feeds is an idle (zero-mask) "
                "slot — scheduler over-provisioning is single-"
                "controller only (Config.validate enforces this)")
        with TRACE.span("load_assemble") as assemble:
            # static [W_local, B, ...] buffers, written once by the
            # fetch through a view per row. Idle slots (a scheduler
            # that over-provisioned fewer than num_workers pads with
            # zero-mask rows) fetch nothing: their rows stay zeros and
            # the round engine sees them as survivor-0 dead slots
            if self._protos is None:
                self._protos = self.dataset.example_protos()
            data = tuple(
                np.zeros((len(client_ids), B) + p.shape[1:], p.dtype)
                for p in self._protos)
            out = [tuple(buf[i, :n] for buf in data)
                   for i, n in enumerate(n_valid)]
            assemble.tag(bytes=sum(buf.nbytes for buf in data))
        with TRACE.span("load_fetch") as fetch:
            transform_s0 = self.dataset.transform_s
            cohort = self.dataset.get_round_batch(
                client_ids, idx_within, n_valid, out)
            fetch.tag(clients=len(client_ids), cohort=int(cohort),
                      transform_s=round(
                          self.dataset.transform_s - transform_s0, 6))
        return r.client_ids, data, mask


class FedValLoader:
    """Validation batches as [num_shards, valid_batch_size, ...] blocks
    (reference _call_val sharding, fed_aggregator.py:337-348)."""

    def __init__(self, dataset: FedDataset, valid_batch_size: int,
                 num_shards: int, feed_slice: Optional[slice] = None):
        """feed_slice: as FedLoader — only the shard rows this process
        feeds are fetched in multi-controller runs."""
        self.dataset = dataset
        self.sampler = ValSampler(dataset.num_val_images, valid_batch_size,
                                  num_shards)
        self.vb = valid_batch_size
        self.num_shards = num_shards
        self.feed_slice = feed_slice

    def batches(self):
        for r in self.sampler.batches():
            idx = r.idx_within
            mask = r.mask
            if self.feed_slice is not None:
                idx = idx[self.feed_slice]
                mask = mask[self.feed_slice]
            flat_idx = idx.reshape(-1)
            got = self.dataset.get_val_batch(flat_idx)
            data = tuple(
                g.reshape((idx.shape[0], self.vb) + g.shape[1:])
                for g in got)
            yield data, mask

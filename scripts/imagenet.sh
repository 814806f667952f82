#!/usr/bin/env bash
# ImageNet launch recipe — the reference's tuned configuration
# (reference: CommEfficient/imagenet.sh:2-21) re-issued against this
# framework's CLI: uncompressed FixupResNet50, IID shards, virtual
# error/momentum 0.9, weight decay 1e-4, local batch 64.
#
# Differences from the reference script, on purpose:
#   * --mixup/--mixup_alpha/--supervised are dropped: they no longer
#     exist in the reference's own arg parser (its imagenet.sh has
#     drifted; running it verbatim there argparse-errors), so they are
#     not part of the supported surface being matched.
#   * --num_devices is omitted: device count comes from the JAX mesh.
#   * --max_local_batch 64 and --scan_span 0 are stated explicitly:
#     max_local_batch bounds the [W, B, 224, 224, 3] staging arrays
#     when clients carry whole-dataset batches (the ImageNet-scale
#     memory hazard; see tests/test_imagenet_scale.py for the bound
#     being exercised at ResNet50/224px shapes).
#
# The k/num_rows/num_cols values are carried from the reference recipe
# for parity; in uncompressed mode they are inert (as there).
exec cv-train \
    --dataset_dir "${IMAGENET_DIR:-/data/imagenet}" \
    --dataset_name ImageNet \
    --model FixupResNet50 \
    --local_batch_size 64 \
    --max_local_batch 64 \
    --scan_span 0 \
    --local_momentum 0.0 \
    --virtual_momentum 0.9 \
    --weight_decay 1e-4 \
    --error_type virtual \
    --mode uncompressed \
    --iid \
    --num_clients 7 \
    --num_workers 7 \
    --k 1000000 \
    --num_rows 1 \
    --num_cols 10000000 \
    "$@"

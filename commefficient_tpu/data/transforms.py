"""Batched numpy augmentation pipelines, NHWC.

Capability parity with the reference's per-dataset torchvision
pipelines (reference: CommEfficient/data_utils/transforms.py:17-75),
re-designed for TPU input pipelines: a train transform works on a
whole round's cohort in a few passes over all of its images (one
padding, one gather of whole window rows, one cast into the batch
buffer, normalisation in place) instead of Python-per-image PIL work
or a call per client, emitting float32 NHWC arrays ready for device
transfer. Normalization constants match the reference exactly.
"""
from __future__ import annotations

import numpy as np
from numpy.lib.stride_tricks import sliding_window_view

CIFAR10_MEAN = np.array([0.4914, 0.4822, 0.4465], np.float32)
CIFAR10_STD = np.array([0.2471, 0.2435, 0.2616], np.float32)
CIFAR100_MEAN = np.array([0.5071, 0.4867, 0.4408], np.float32)
CIFAR100_STD = np.array([0.2675, 0.2565, 0.2761], np.float32)
FEMNIST_MEAN = np.array([0.9637], np.float32)
FEMNIST_STD = np.array([0.1597], np.float32)
IMAGENET_MEAN = np.array([0.485, 0.456, 0.406], np.float32)
IMAGENET_STD = np.array([0.229, 0.224, 0.225], np.float32)


def _normalize_rows(x: np.ndarray, from_uint8: bool, mean_row: np.ndarray,
                    std_row: np.ndarray) -> None:
    """`(x / 255 - mean) / std` in place on float32 `[..., W, C]`
    (`/ 255` for what was stored as uint8 only): the same float32
    operation per element as the broadcast over C, made on rows of
    W*C values against the constants tiled W times (`mean_row`,
    `std_row`), so no pass runs a C-wide inner loop and none
    allocates."""
    wide = x.view()
    wide.shape = (-1, mean_row.size)      # raises where that would copy
    if from_uint8:
        wide /= 255.0
    wide -= mean_row
    wide /= std_row


def normalize(images: np.ndarray, mean: np.ndarray,
              std: np.ndarray) -> np.ndarray:
    out = images.astype(np.float32)
    w = images.shape[-2]
    _normalize_rows(out, images.dtype == np.uint8, np.tile(mean, w),
                    np.tile(std, w))
    return out


def _window_crop(padded: np.ndarray, ys: np.ndarray, xs: np.ndarray,
                 h: int, w: int) -> np.ndarray:
    """`padded[i, ys[i]:ys[i] + h, xs[i]:xs[i] + w]` for every i as
    one gather: with W and C merged a window's row is w*C values in a
    line, so whole rows are copied, not pixels."""
    n, ph, pw, c = padded.shape
    windows = sliding_window_view(padded.reshape(n, ph, pw * c),
                                  (h, w * c), axis=(1, 2))
    return windows[np.arange(n), ys, xs * c].reshape(n, h, w, c)


def _hflip_rows(x: np.ndarray, flips: np.ndarray) -> None:
    """Reverse the W axis of `x[flips]` in place. A pixel's C values
    move as one opaque element: reversing an axis of C-long runs
    value by value takes twice as long."""
    n, h, w, c = x.shape
    pixels = x.view(np.dtype((np.void, c * x.itemsize))).reshape(n, h, w)
    pixels[flips] = pixels[flips, :, ::-1]


def _image_transforms(mean, std, seed, pad=0, reflect=False, flip=False):
    """(train, test) for stored NHWC images. `train` crops a random
    h x w window out of the image padded by `pad` (by reflection, or
    else with white: the stored dtype's 1.0), flips half the images,
    and normalises; `test` only normalises.

    `train.cohort(images, labels, counts, out=None)` is `train` over a
    whole round: `images`/`labels` hold the clients' examples one
    client after the other, `counts[i]` of them client i's, and
    `out[i]` are the (images, labels) views that take client i's
    part (fresh arrays are returned where `out` is None). It draws
    from `train.rng` client by client in the order a call per client
    would (ys, xs, flip), so the stream and every value are those of
    `train` called once per client; `train` is the cohort of one."""
    rng = np.random.RandomState(seed)

    def cohort(images, labels, counts, out=None):
        n, h, w, _ = images.shape
        bounds = np.concatenate([[0], np.cumsum(counts, dtype=np.int64)])
        if bounds[-1] != n:
            raise ValueError(f"counts sum to {bounds[-1]}, not to the "
                             f"{n} images given")
        segments = list(zip(bounds[:-1], bounds[1:]))
        ys, xs = np.empty(n, np.intp), np.empty(n, np.intp)
        flips = np.empty(n, bool)
        for lo, hi in segments:
            if pad:
                ys[lo:hi] = rng.randint(0, 2 * pad + 1, size=hi - lo)
                xs[lo:hi] = rng.randint(0, 2 * pad + 1, size=hi - lo)
            if flip:
                flips[lo:hi] = rng.rand(hi - lo) < 0.5
        from_uint8 = images.dtype == np.uint8
        mean_row, std_row = np.tile(mean, w), np.tile(std, w)
        x = images
        if pad:
            fill = ({"mode": "reflect"} if reflect else
                    {"constant_values": 255 if from_uint8 else 1.0})
            x = _window_crop(
                np.pad(x, ((0, 0), (pad, pad), (pad, pad), (0, 0)), **fill),
                ys, xs, h, w)
        if flip:
            if x is images:
                x = x.copy()      # the caller's array stays as it was
            _hflip_rows(x, flips)
        result = None
        if out is None:
            result = (np.empty(x.shape, np.float32),
                      np.empty(n, np.int32))
            out = [tuple(a[lo:hi] for a in result) for lo, hi in segments]
        # one cast into the destination, then in place; client by
        # client, because a client's rows are one contiguous block of
        # a [W, B, ...] round buffer and a ragged cohort's are not
        for (lo, hi), (dst, dst_labels) in zip(segments, out):
            dst[...] = x[lo:hi]
            _normalize_rows(dst, from_uint8, mean_row, std_row)
            dst_labels[...] = labels[lo:hi]
        return result

    def train(images, labels):
        return cohort(images, labels, (len(images),))

    def test(images, labels):
        return normalize(images, mean, std), labels.astype(np.int32)

    train.cohort = cohort
    train.rng = rng
    return train, test


def cifar10_transforms(seed=0):
    return _image_transforms(CIFAR10_MEAN, CIFAR10_STD, seed, pad=4,
                             reflect=True, flip=True)


def cifar100_transforms(seed=0):
    return _image_transforms(CIFAR100_MEAN, CIFAR100_STD, seed, pad=4,
                             reflect=True, flip=True)


def femnist_transforms(seed=0):
    """Crop-jitter on 28x28x1 digits: white padding by 2, then a random
    28x28 crop (reference transforms.py:47-54; its rotation/rescale
    distortions are approximated by the shift — same augmentation
    intent without a per-image interpolation kernel)."""
    return _image_transforms(FEMNIST_MEAN, FEMNIST_STD, seed, pad=2)


def imagenet_transforms(seed=0, size=224):
    """Random flip / nothing at eval (reference transforms.py:66-75).
    Assumes pre-resized source images."""
    return _image_transforms(IMAGENET_MEAN, IMAGENET_STD, seed, flip=True)


TRANSFORMS = {
    "CIFAR10": cifar10_transforms,
    "CIFAR100": cifar100_transforms,
    "EMNIST": femnist_transforms,
    "ImageNet": imagenet_transforms,
}

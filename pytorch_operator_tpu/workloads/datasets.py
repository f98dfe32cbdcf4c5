"""Datasets for the in-tree workloads.

The build environment has no network (SURVEY.md §7 environment facts), so:

- ``digits``: the real handwritten-digit set shipped with scikit-learn
  (1797 8×8 grayscale images, 10 classes) — the honest stand-in for the
  reference's MNIST example (``examples/mnist``): real pixels, a real
  train/test generalization gap, and the >97% accuracy bar is meaningful.
- ``synthetic_images``: procedurally generated image/label batches for
  throughput benchmarking (isolates compute from input pipeline).
"""

from __future__ import annotations

from typing import Tuple

import numpy as np


def digits(split: str = "train", test_fraction: float = 0.2) -> Tuple[np.ndarray, np.ndarray]:
    """Real 8×8 handwritten digits, deterministic split, NHWC float32 in [0,1]."""
    from sklearn.datasets import load_digits

    d = load_digits()
    x = (d.data.reshape(-1, 8, 8, 1) / 16.0).astype(np.float32)
    y = d.target.astype(np.int32)
    rng = np.random.default_rng(0)
    perm = rng.permutation(len(x))
    x, y = x[perm], y[perm]
    n_test = int(len(x) * test_fraction)
    if split == "train":
        return x[n_test:], y[n_test:]
    if split == "test":
        return x[:n_test], y[:n_test]
    raise ValueError(f"unknown split {split!r}")


def synthetic_images(
    batch: int, height: int, width: int, classes: int, seed: int = 0
) -> Tuple[np.ndarray, np.ndarray]:
    """Random images/labels for synthetic-data benchmark mode."""
    rng = np.random.default_rng(seed)
    x = rng.standard_normal((batch, height, width, 3), dtype=np.float32)
    y = rng.integers(0, classes, size=(batch,), dtype=np.int32)
    return x, y

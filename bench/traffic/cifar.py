"""The benchmark's CIFAR-like images, generated from the seed.

A copy of the program's ``repro.data.SyntheticCIFAR`` arithmetic (two
class templates per class, a random mix in [0.6, 1.0], Gaussian noise),
kept here so that no change to the program can change the benchmark's
inputs. ``pool`` makes the few distinct batches a run cycles through.
"""
from __future__ import annotations

from typing import Dict, List

import numpy as np


def pool(seed: int, n_batches: int, batch: int, n_classes: int = 10,
         noise: float = 0.6) -> List[Dict[str, np.ndarray]]:
    """``n_batches`` batches of ``batch`` (32, 32, 3) float32 images and
    int32 labels; batch ``i`` is the same for every run of one seed."""
    rng = np.random.default_rng([seed, 0])
    templates = rng.normal(0, 1, (n_classes, 2, 32, 32, 3)).astype(np.float32)
    out = []
    for i in range(n_batches):
        r = np.random.default_rng([seed, 1 + i])
        labels = r.integers(0, n_classes, batch)
        comp = r.integers(0, 2, batch)
        mix = r.uniform(0.6, 1.0, (batch, 1, 1, 1)).astype(np.float32)
        base = templates[labels, comp] * mix
        imgs = base + r.normal(0, noise, base.shape).astype(np.float32)
        out.append({"images": imgs.astype(np.float32),
                    "labels": labels.astype(np.int32)})
    return out

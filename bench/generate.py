"""The one generator of federation traffic: synthetic labelled images.

Every class has a smooth random template; an image is its class's
template plus Gaussian pixel noise.  Labels are drawn uniformly, so
cutting the training images into equal contiguous shards is an iid
split with whole batches on every node.  The same seed gives the same
images, labels and shards; nothing is read from disk.
"""
from __future__ import annotations

from typing import Dict, List, Tuple

import numpy as np


def _templates(rng, classes: int, hw) -> np.ndarray:
    h, w, c = hw
    yy, xx = np.meshgrid(np.linspace(0, 1, h), np.linspace(0, 1, w),
                         indexing="ij")
    out = np.zeros((classes, h, w, c), np.float32)
    for k in range(classes):
        for ch in range(c):
            fy, fx = rng.uniform(0.5, 3.0, 2)
            py, px = rng.uniform(0, 2 * np.pi, 2)
            out[k, :, :, ch] = np.sin(2 * np.pi * fy * yy + py) \
                * np.cos(2 * np.pi * fx * xx + px)
    return out


def make_federation_data(seed: int, config: dict, traffic: dict
                         ) -> Tuple[List[Dict[str, np.ndarray]],
                                    Dict[str, np.ndarray]]:
    """-> (per-node training shards, test set), each
    ``{"image": [n, H, W, C] float32, "label": [n] int32}``."""
    if traffic.get("split", "iid") != "iid":
        raise ValueError(f"unsupported split {traffic['split']!r}")
    rng = np.random.default_rng(seed)
    hw = tuple(config["input_hw"])
    classes = config["num_classes"]
    nodes = config["nodes"]
    per_node = config["images_per_node"]
    n_test = config["test_images"]
    n = nodes * per_node + n_test
    labels = rng.integers(0, classes, n).astype(np.int32)
    images = _templates(rng, classes, hw)[labels]
    images += np.float32(traffic["noise"]) * rng.standard_normal(
        images.shape, np.float32)
    test = {"image": images[:n_test], "label": labels[:n_test]}
    shards = [{"image": images[n_test + i * per_node:
                               n_test + (i + 1) * per_node],
               "label": labels[n_test + i * per_node:
                               n_test + (i + 1) * per_node]}
              for i in range(nodes)]
    return shards, test

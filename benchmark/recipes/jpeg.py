"""Recipe ``jpeg``: ``train_images`` JPEG files of ``height`` x ``width`` at
``quality`` on disk, for the program's decoder."""

from __future__ import annotations

import os
from concurrent.futures import ThreadPoolExecutor

import numpy as np

from benchmark import datasets


def write(tmp: str, root: str, recipe: dict, size: int, classes: np.ndarray,
          names: list[str], num_classes: int) -> None:
    from PIL import Image

    h, w = recipe["height"], recipe["width"]
    img_dir = os.path.join(tmp, "img")
    tables = datasets.class_tables(num_classes)
    for sub in sorted({os.path.dirname(name) for name in names}):
        os.makedirs(os.path.join(img_dir, sub), exist_ok=True)

    def one(i: int) -> None:
        rng = np.random.default_rng([0, i])
        # The pattern stretched with the frame (scale), coarse 8x8 blocks
        # of noise and a little per-pixel noise: ~100 KB a file at 1000x667.
        img = datasets.patterns(classes[i : i + 1], h, w, tables, max(h, w) / 128.0)[0]
        coarse = rng.integers(0, 18, size=(-(-h // 8), -(-w // 8), 3), dtype=np.uint8)
        img += np.repeat(np.repeat(coarse, 8, axis=0), 8, axis=1)[:h, :w]
        img += rng.integers(0, 13, size=img.shape, dtype=np.uint8)  # <= 255
        Image.fromarray(img).save(os.path.join(img_dir, names[i]), quality=recipe["quality"])

    with ThreadPoolExecutor(max_workers=os.cpu_count() or 1) as pool:
        list(pool.map(one, range(len(names))))


def flags(root: str) -> dict:
    return {}

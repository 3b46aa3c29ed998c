"""Recipe ``pack``: ``train_images`` squares of the configuration's image
size in the program's packed format (``data/packed.py``: ``<stem>.images.npy``
uint8 [N,H,W,3], ``.labels.npy``, ``.meta.json``), read back through mmap."""

from __future__ import annotations

import json
import os
from concurrent.futures import ThreadPoolExecutor

import numpy as np

from benchmark import datasets

_CHUNK = 1024  # images generated per vectorized call


def write(tmp: str, root: str, recipe: dict, size: int, classes: np.ndarray,
          names: list[str], num_classes: int) -> None:
    n = len(names)
    stem = os.path.join(tmp, "packed", f"train_{size}x{size}")
    os.makedirs(os.path.dirname(stem))
    out = np.lib.format.open_memmap(
        stem + ".images.npy", mode="w+", dtype=np.uint8, shape=(n, size, size, 3)
    )
    tables = datasets.class_tables(num_classes)

    def fill(start: int) -> None:
        stop = min(start + _CHUNK, n)
        rng = np.random.default_rng([0, start])
        img = datasets.patterns(classes[start:stop], size, size, tables, 1.0)
        img += rng.integers(0, 26, size=img.shape, dtype=np.uint8)  # <= 255
        out[start:stop] = img

    with ThreadPoolExecutor(max_workers=os.cpu_count() or 1) as pool:
        list(pool.map(fill, range(0, n, _CHUNK)))
    out.flush()
    del out
    np.save(stem + ".labels.npy", classes.astype(np.int32))
    with open(stem + ".meta.json", "w") as f:
        # The FINAL image directory: the loader compares real paths.
        json.dump(
            {"version": 1, "image_size": [size, size], "img_dir": os.path.join(root, "img"),
             "synthetic": False, "filenames": names},
            f,
        )


def flags(root: str) -> dict:
    return {"packed-dir": os.path.join(root, "packed")}

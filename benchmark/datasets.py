"""The image task's dataset generator (reached through
``benchmark/tasks/images.py`` and from its recipes, never from the harness
itself): a recipe (data, in a traffic file) in, a directory of generated
inputs and the entry-point flags that name it out.

The program receives only what is generated here. Pixels are a function of
the recipe alone and are built ONCE per checkout (2 GB for the 40 000-image
pack, which no run should pay twice); what ``--seed`` draws is the manifest
laid over them — which class each image carries and, through the trainer's
own ``--seed``, the visit order and the weights. The same seed gives the
same inputs; another seed gives other labels over the same pixels, and costs
two small CSV files.

Images are class-conditioned, a copy of the idea in the program's
``data/pipeline.synthetic_image`` (a low-frequency pattern keyed by the
class, plus noise) so a model can learn from them, built in bulk: the
pattern depends on ``y + x`` only, so one short sine table per image is
gathered into the frame instead of evaluating a sine per pixel.

How the pixels are stored is the recipe's own file, found by its name:
``benchmark/recipes/<recipe>.py`` (``pack``, ``jpeg``). Every recipe also
gets ``test_images`` rows in a test manifest the trainer insists on reading
(its images are never opened: validation is off in every window).
"""

from __future__ import annotations

import hashlib
import importlib
import json
import os
import shutil

import numpy as np


def _key(obj) -> str:
    return hashlib.sha1(json.dumps(obj, sort_keys=True).encode()).hexdigest()[:12]


def class_tables(num_classes: int):
    """Per-class pattern parameters: three frequencies and phases each."""
    rng = np.random.default_rng([0, num_classes])
    return (
        rng.uniform(0.02, 0.3, size=(num_classes, 3)).astype(np.float32),
        rng.uniform(0.0, 2 * np.pi, size=(num_classes, 3)).astype(np.float32),
    )


def patterns(classes: np.ndarray, h: int, w: int, tables, scale: float) -> np.ndarray:
    """uint8 [n, h, w, 3] patterns in [0, 225]: 0.5 + 0.5 sin(f (y+x) / scale
    + phase), the sine evaluated on the h+w-1 distinct values of y+x."""
    freq, phase = tables
    t = np.arange(h + w - 1, dtype=np.float32) / scale
    table = 0.5 + 0.5 * np.sin(
        freq[classes][:, None, :] * t[None, :, None] + phase[classes][:, None, :]
    )
    table = (table * 225.0).astype(np.uint8)  # [n, h+w-1, 3]
    diag = np.add.outer(np.arange(h), np.arange(w))  # [h, w] -> y + x
    return table[:, diag, :]


def _write_manifests(root: str, seed: int, recipe: dict, num_classes: int) -> str:
    """The seed's labels over the recipe's pixels: a seeded permutation of
    the class ids (images of one pattern class still share one label)."""
    out = os.path.join(root, f"manifest-seed{seed}")
    if os.path.isdir(out):
        return out
    classes = np.load(os.path.join(root, "classes.npy"))
    with open(os.path.join(root, "names.json")) as f:
        names = json.load(f)
    relabel = np.random.default_rng([0, seed, 11]).permutation(num_classes)
    tmp = f"{out}.tmp{os.getpid()}"
    os.makedirs(tmp)
    with open(os.path.join(tmp, "train.csv"), "w") as f:
        f.write("file_name,category_id\n")
        f.writelines(f"{name},{relabel[c]}\n" for name, c in zip(names, classes))
    test_rng = np.random.default_rng([seed, 13])
    with open(os.path.join(tmp, "test.csv"), "w") as f:
        f.write("file_name,category_id\n")
        f.writelines(
            f"images/test/{i:07d}.jpg,{c}\n"
            for i, c in enumerate(test_rng.integers(0, num_classes, recipe["test_images"]))
        )
    os.rename(tmp, out)
    return out


def ensure(recipe: dict, *, image_size: int, num_classes: int, seed: int,
           data_root: str) -> dict:
    """Build (first run in a checkout) or find the recipe's dataset and the
    seed's manifests; returns the entry-point flags that point at them."""
    writer = importlib.import_module("benchmark.recipes." + recipe["recipe"])
    key = _key({"recipe": recipe, "image_size": image_size, "num_classes": num_classes})
    root = os.path.join(data_root, f"{recipe['recipe']}-{key}")
    if not os.path.isdir(root):
        # Everything that does not depend on ``--seed``, under a temporary
        # name: the rename publishes a complete dataset or none.
        tmp = f"{root}.tmp{os.getpid()}"
        shutil.rmtree(tmp, ignore_errors=True)
        os.makedirs(tmp)
        n = recipe["train_images"]
        # The pattern class of image i; the seed's manifest relabels it.
        classes = np.random.default_rng([0, 7]).integers(0, num_classes, size=n)
        names = [f"images/{i // 1000:03d}/{i:07d}.jpg" for i in range(n)]
        writer.write(tmp, root, recipe, image_size, classes, names, num_classes)
        np.save(os.path.join(tmp, "classes.npy"), classes)
        with open(os.path.join(tmp, "names.json"), "w") as f:
            json.dump(names, f)
        os.rename(tmp, root)
    manifests = _write_manifests(root, seed, recipe, num_classes)
    return {
        "synthetic-data": False,
        "debug": False,
        "train-csv": os.path.join(manifests, "train.csv"),
        "test-csv": os.path.join(manifests, "test.csv"),
        "train-img-dir": os.path.join(root, "img"),
        "test-img-dir": os.path.join(root, "img"),
        **writer.flags(root),
    }

"""Task ``images``: a sample is one square RGB image with one class label.
The model's input is stated by ``model["image_size"]`` and its output by
``model["num_classes"]``; a recipe holds ``train_images`` of them. The code
here is the harness's own of PRs 22-25, moved behind the task's name."""

from __future__ import annotations

FORWARD_SAMPLES = 256  # forward agreement
TRAIN_CHECK_SHARE = 8  # train-step agreement: batch_per_chip / 8 images
REHEARSAL_SAMPLES = 8  # both checks of a CPU rehearsal


# -- the dataset step ------------------------------------------------------

def ensure(recipe: dict, model: dict, *, seed: int, data_root: str) -> dict:
    """Build (first run in a checkout) or find the recipe's images and the
    seed's manifests; the entry-point flags that name them."""
    from benchmark import datasets

    return datasets.ensure(
        recipe, image_size=model["image_size"], num_classes=model["num_classes"],
        seed=seed, data_root=data_root,
    )


def model_flags(model: dict) -> dict:
    return {"num-classes": model["num_classes"], "image-size": model["image_size"]}


def train_samples(recipe: dict) -> int:
    return recipe["train_images"]


# -- the seeded check batch ------------------------------------------------

def seeded_batch(model: dict, mesh, key, batch: int):
    """``float32 [batch, size, size, 3]`` normals split over the mesh's
    first axis, and ``[batch]`` labels."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec as P

    size, classes = model["image_size"], model["num_classes"]
    k_img, k_lab = jax.random.split(key)
    images = jax.device_put(
        jax.random.normal(k_img, (batch, size, size, 3), jnp.float32),
        NamedSharding(mesh, P(mesh.axis_names[0])),
    )
    return images, jax.random.randint(k_lab, (batch,), 0, classes)


def batch_shapes(model: dict, batch: int, inputs_sharding, targets_sharding):
    """``seeded_batch`` as shapes, for a compile without a device."""
    import jax
    import numpy as np

    size = model["image_size"]
    return (
        jax.ShapeDtypeStruct((batch, size, size, 3), np.float32, sharding=inputs_sharding),
        jax.ShapeDtypeStruct((batch,), np.int32, sharding=targets_sharding),
    )


def cache_shapes(model: dict, rows: int, samples: int, dtype, rows_sharding, replicated):
    """The resident dataset the scanned epoch reads (``--device-cache``):
    ``rows`` images (the samples padded to the chips) split by rows, and the
    samples' labels on every chip."""
    import jax
    import numpy as np

    size = model["image_size"]
    return (
        jax.ShapeDtypeStruct((rows, size, size, 3), np.dtype(dtype), sharding=rows_sharding),
        jax.ShapeDtypeStruct((samples,), np.int32, sharding=replicated),
    )


# -- the check's sizes and the epoch's count -------------------------------

def check_defaults(config: dict, rehearse: bool) -> dict:
    if rehearse:
        return {"forward_samples": REHEARSAL_SAMPLES, "train_samples": REHEARSAL_SAMPLES}
    return {
        "forward_samples": FORWARD_SAMPLES,
        "train_samples": config["batch_per_chip"] // TRAIN_CHECK_SHARE,
    }


def epoch_samples(record: dict) -> int:
    """Images a ``kind="epoch"`` record says its epoch trained."""
    return round(record["images_per_sec"] * record["time_s"])

"""Task ``tokens``: a sample is one PACKED sequence of ``seq_len + 1`` int32
token ids (documents end to end, no padding); the system reads inputs
``[:, :-1]`` and targets ``[:, 1:]``. The model's input is stated by
``model["seq_len"]`` and its output by ``model["vocab_size"]``; a recipe holds
``sequences`` of them, of its own ``seq_len`` (the two must agree). The whole
``model`` reaches the entry point one way, ``--model-config`` (a JSON object
in the source's key names; keys the program does not read, ``seq_len`` among
them, pass through it)."""

from __future__ import annotations

import json

ZIPF_EXPONENT = 1.1  # the check batch's ids are skewed as the traffic's are


# -- the dataset step ------------------------------------------------------

def ensure(recipe: dict, model: dict, *, seed: int, data_root: str) -> dict:
    """Build or find the seed's packed sequences; the entry-point flags that
    name them. Unlike pixels, the sequences themselves are drawn from
    ``--seed`` (16 x 8 193 ids: half a megabyte a seed)."""
    import importlib

    if recipe["seq_len"] != model["seq_len"]:
        raise ValueError(
            f"traffic seq_len {recipe['seq_len']} != model seq_len {model['seq_len']}"
        )
    writer = importlib.import_module("benchmark.recipes." + recipe["recipe"])
    root = writer.ensure(recipe, vocab=model["vocab_size"], seed=seed, data_root=data_root)
    return {"synthetic-data": False, "debug": False, **writer.flags(root)}


def model_flags(model: dict) -> dict:
    return {"model-config": json.dumps(model, sort_keys=True)}


def train_samples(recipe: dict) -> int:
    return recipe["sequences"]


# -- the seeded check batch ------------------------------------------------

def _zipf_logits(vocab: int):
    import jax.numpy as jnp

    return -ZIPF_EXPONENT * jnp.log(jnp.arange(1, vocab + 1, dtype=jnp.float32))


def seeded_batch(model: dict, mesh, key, batch: int):
    """``int32 [batch, seq_len]`` inputs split over the mesh's first axis and
    ``[batch, seq_len]`` next-token targets, from one draw of ``seq_len + 1``
    Zipf-distributed ids a row."""
    import jax
    from jax.sharding import NamedSharding, PartitionSpec as P

    rows = jax.random.categorical(
        key, _zipf_logits(model["vocab_size"]), shape=(batch, model["seq_len"] + 1)
    ).astype("int32")
    inputs = jax.device_put(rows[:, :-1], NamedSharding(mesh, P(mesh.axis_names[0])))
    return inputs, rows[:, 1:]


def batch_shapes(model: dict, batch: int, inputs_sharding, targets_sharding):
    import jax
    import numpy as np

    shape = (batch, model["seq_len"])
    return (
        jax.ShapeDtypeStruct(shape, np.int32, sharding=inputs_sharding),
        jax.ShapeDtypeStruct(shape, np.int32, sharding=targets_sharding),
    )


def cache_shapes(model: dict, rows: int, samples: int, dtype, rows_sharding, replicated):
    """The resident dataset the scanned epoch reads: ``rows`` packed sequences
    split by rows (``dtype`` is the image cells' pixel type and is not read),
    and the unread label column on every chip."""
    import jax
    import numpy as np

    return (
        jax.ShapeDtypeStruct((rows, model["seq_len"] + 1), np.int32, sharding=rows_sharding),
        jax.ShapeDtypeStruct((samples,), np.int32, sharding=replicated),
    )


# -- the check's sizes and the epoch's count -------------------------------

def check_defaults(config: dict, rehearse: bool) -> dict:
    return {"forward_samples": 1, "train_samples": 1}


def epoch_samples(record: dict) -> int:
    """Sequences a ``kind="epoch"`` record says its epoch trained (a token
    model's ``images_per_sec`` counts sequences; ``tokens`` is beside it)."""
    return round(record["images_per_sec"] * record["time_s"])

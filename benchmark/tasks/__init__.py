"""What a cell's samples are, found by name like its driver, recipe,
reference and metrics: ``benchmark/tasks/<task>.py``, named by the
configuration file's ``"task"`` (absent: ``images``). Everything in the
harness that knows what a sample looks like lives in that one file; the
driver, the check and the compile rehearsal ask it and never look inside a
``model`` or a ``recipe`` themselves. A task module answers:

- the dataset step — ``ensure(recipe, model, seed=, data_root=) -> flags``
  (build or find the generated inputs; the entry-point flags that name
  them), ``model_flags(model) -> flags`` (how the trainer is told the
  model's input and output sizes), ``train_samples(recipe) -> int``;
- the seeded check batch — ``seeded_batch(model, mesh, key, batch) ->
  (inputs, targets)`` on the mesh, and the same as shapes for the compile
  rehearsal, ``batch_shapes(model, batch, inputs_sharding, targets_sharding)``;
  ``cache_shapes(model, rows, samples, dtype, rows_sharding, replicated)``
  for the resident dataset the scanned epoch reads;
- the check's default sizes — ``check_defaults(config, rehearse) ->
  {"forward_samples": n, "train_samples": n}`` (``check_sizes`` below lays
  the configuration's own over them);
- the epoch's count — ``epoch_samples(record) -> int``, how many samples a
  ``kind="epoch"`` record says it trained.

The system and the reference take ``(inputs, targets)`` whatever those are.
"""

from __future__ import annotations

import importlib
import os

DEFAULT = "images"
SIZES = ("forward_samples", "train_samples")


def load(config: dict):
    """The task module of a configuration; a name without a file is an error
    that says where the file was looked for."""
    name = config.get("task", DEFAULT)
    module = f"{__name__}.{name}"
    try:
        return importlib.import_module(module)
    except ModuleNotFoundError as e:
        if e.name != module:
            raise  # the task's file is there; something it imports is not
        looked = ", ".join(os.path.join(root, name + ".py") for root in __path__)
        raise FileNotFoundError(
            f"configuration {config.get('name')!r} names the task {name!r}: no file {looked}"
        ) from e


def check_sizes(task, config: dict, rehearse: bool = False) -> dict:
    """Samples in the forward check's batch and in the train-step check's:
    the task's defaults, under what the configuration states in its own
    ``"check"`` (a rehearsal reads ``config["rehearse"]["check"]``). A size
    that is no whole number of at least 1 is an error that names its key."""
    stated = (config["rehearse"] if rehearse else config).get("check", {})
    unknown = sorted(set(stated) - set(SIZES))
    if unknown:
        raise ValueError(f"check.{unknown[0]}: not a check size (those are {', '.join(SIZES)})")
    sizes = {**task.check_defaults(config, rehearse), **stated}
    for key in SIZES:
        size = sizes[key]
        if isinstance(size, bool) or not isinstance(size, int) or size < 1:
            where = "stated" if key in stated else "the task's default"
            raise ValueError(
                f"check.{key} comes out as {size!r} ({where}; batch_per_chip "
                f"{config.get('batch_per_chip')}): state a whole number >= 1 under "
                f"\"check\": {{\"{key}\": ...}} in configuration {config.get('name')!r}"
            )
    return sizes

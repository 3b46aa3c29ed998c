"""What a sample is sits behind the task's name (``benchmark/tasks/``): the
image task is the harness's code of PRs 22-25 moved, to the bit; the check's
sizes are the task's defaults under what a configuration states; and a task
whose samples are token sequences goes through the driver's dataset step,
the seeded batches and the size arithmetic as NEW FILES ONLY."""

import hashlib
import importlib
import json
import os
import sys
import textwrap
import time
import types
from typing import NamedTuple

import pytest

from benchmark import tasks

SEED = 2147483659


def _one_chip_mesh():
    import jax
    import numpy as np
    from jax.sharding import Mesh

    return Mesh(np.array(jax.devices()[:1]), ("data",))


def test_the_image_tasks_seeded_batch_is_the_array_the_check_always_made():
    """Checksums of ``correct._seeded_batch`` at the parent of PR 26 (CPU,
    key ``seed + 17``, 8 images of 32 px, 100 classes)."""
    import jax
    import numpy as np

    images, labels = tasks.load({}).seeded_batch(
        {"image_size": 32, "num_classes": 100}, _one_chip_mesh(), jax.random.PRNGKey(SEED + 17), 8
    )
    assert (images.shape, images.dtype, labels.shape, labels.dtype) == (
        (8, 32, 32, 3), np.float32, (8,), np.int32)
    assert hashlib.sha256(np.asarray(images).tobytes()).hexdigest() == (
        "8e78bebdcd14a6a0b86d45dd37d49974e57f281bf816151a76177df86eedc005")
    assert np.asarray(labels).tolist() == [99, 85, 30, 61, 67, 74, 67, 31]
    shapes = tasks.load({}).batch_shapes({"image_size": 32, "num_classes": 100}, 8, None, None)
    assert [(s.shape, s.dtype) for s in shapes] == [(images.shape, images.dtype),
                                                    (labels.shape, labels.dtype)]


def test_the_image_tasks_answers_are_the_harnesss_old_ones():
    images = tasks.load({"name": "no task stated"})
    assert images is tasks.load({"task": "images"})
    model = {"image_size": 128, "num_classes": 64500, "else": 1}
    assert images.model_flags(model) == {"num-classes": 64500, "image-size": 128}
    assert list(images.model_flags(model)) == ["num-classes", "image-size"]  # the argv's order
    assert images.train_samples({"recipe": "pack", "train_images": 40000}) == 40000
    # 40 000 images in 1.45184 s, as the trainer writes them.
    assert images.epoch_samples({"images_per_sec": 38912 / 1.45184, "time_s": 1.45184}) == 38912
    dataset, labels = images.cache_shapes(model, 40000, 40000, "uint8", None, None)
    assert (dataset.shape, str(dataset.dtype), labels.shape) == ((40000, 128, 128, 3), "uint8", (40000,))


@pytest.mark.parametrize(
    "config,rehearse,sizes",
    [
        ({"batch_per_chip": 2048}, False, (256, 256)),  # resnet18's cell, to the digit
        ({"batch_per_chip": 128}, False, (256, 16)),  # ViT-B/16's
        ({"batch_per_chip": 128, "rehearse": {}}, True, (8, 8)),
        ({"batch_per_chip": 4, "check": {"forward_samples": 2, "train_samples": 1}}, False, (2, 1)),
        ({"batch_per_chip": 4, "check": {"train_samples": 1}}, False, (256, 1)),
        ({"batch_per_chip": 4, "rehearse": {"check": {"forward_samples": 2}}}, True, (2, 8)),
    ],
)
def test_check_sizes_are_the_tasks_defaults_under_the_configurations_own(config, rehearse, sizes):
    got = tasks.check_sizes(tasks.load(config), config, rehearse)
    assert (got["forward_samples"], got["train_samples"]) == sizes


@pytest.mark.parametrize(
    "config,key",
    [
        ({"name": "four", "batch_per_chip": 4}, "check.train_samples"),  # 4 // 8: never a silent 0
        ({"batch_per_chip": 128, "check": {"forward_samples": 0}}, "check.forward_samples"),
        ({"batch_per_chip": 128, "check": {"train_samples": 1.5}}, "check.train_samples"),
        ({"batch_per_chip": 128, "check": {"train_sample": 1}}, "check.train_sample"),
    ],
)
def test_a_check_size_below_one_is_an_error_that_names_its_key(config, key):
    with pytest.raises(ValueError, match=key.replace(".", r"\.") + r"\b"):
        tasks.check_sizes(tasks.load(config), config)


def test_a_task_without_a_file_fails_at_load_with_the_path_looked_for():
    with pytest.raises(FileNotFoundError) as e:
        tasks.load({"name": "lfm2", "task": "tokens"})
    assert os.path.join(os.path.dirname(tasks.__file__), "tokens.py") in str(e.value)
    assert "'lfm2'" in str(e.value)


@pytest.mark.parametrize(
    "config,flags",
    [
        ({"batch_per_chip": 4}, []),
        # a rehearsal is held to the sizes IT will use, not the real run's
        ({"batch_per_chip": 128, "rehearse": {"check": {"train_samples": 0}}}, ["--rehearse"]),
    ],
)
def test_run_py_checks_the_sizes_before_the_run(monkeypatch, tmp_path, config, flags):
    """A size that comes out below 1 (``batch_per_chip: 4`` and nothing
    stated; a rehearsal's own stated 0): ``run.py`` stops before it imports
    JAX or a driver."""
    from benchmark import run

    bench = {
        "configs": [{"name": "c", "file": "c.json"}],
        "workloads": [{"name": "w", "config": "c", "traffic": "train_hbm", "chips": 1}],
    }
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(bench))
    (tmp_path / "c.json").write_text(json.dumps({"name": "c", **config}))
    monkeypatch.setattr(run, "ROOT", str(tmp_path))
    monkeypatch.setattr(sys, "path", list(sys.path))
    monkeypatch.chdir(tmp_path)
    with pytest.raises(ValueError, match=r"check\.train_samples"):
        run.main(["--workload", "w", "--seed", "1", "--seconds", "1", "--trace", "0", *flags])


# ---------------------------------------------------------------------------
# A task of token sequences, from new files only. The SYSTEM is stubbed in
# this test and nowhere else: ``trainer.main`` (a fake job that writes the
# records and spans the harness reads), ``correct._system``, ``eval_logits``
# and ``make_train_step`` (a two-matrix language model stands in; the
# program has no token path yet, that is the next PR's). Everything between
# — the driver's dataset step, flags, watcher and window, the seeded
# batches, the sizes, the epoch count, the comparison — is the harness's own
# code, unedited, reaching the toy task, its reference and nothing of images.
# ---------------------------------------------------------------------------

TOY_TASK = '''
"""Task ``toy_tokens``: a sample is one packed sequence of token ids."""
import os


def ensure(recipe, model, *, seed, data_root):
    import numpy as np

    root = os.path.join(data_root, f"{recipe['recipe']}-seed{seed}")
    os.makedirs(root, exist_ok=True)
    ids = np.random.default_rng(seed).integers(
        0, model["vocab_size"], (recipe["train_sequences"], recipe["seq_len"]), dtype=np.int32)
    np.save(os.path.join(root, "train.npy"), ids)
    return {"token-file": os.path.join(root, "train.npy")}


def model_flags(model):
    return {"vocab-size": model["vocab_size"], "seq-len": model["seq_len"]}


def train_samples(recipe):
    return recipe["train_sequences"]


def seeded_batch(model, mesh, key, batch):
    import jax
    import jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec as P

    ids = jax.random.randint(key, (batch, model["seq_len"] + 1), 0, model["vocab_size"], jnp.int32)
    rows = NamedSharding(mesh, P(mesh.axis_names[0]))
    return jax.device_put(ids[:, :-1], rows), jax.device_put(ids[:, 1:], rows)


def batch_shapes(model, batch, inputs_sharding, targets_sharding):
    import jax
    import numpy as np

    shape = (batch, model["seq_len"])
    return (jax.ShapeDtypeStruct(shape, np.int32, sharding=inputs_sharding),
            jax.ShapeDtypeStruct(shape, np.int32, sharding=targets_sharding))


def cache_shapes(model, rows, samples, dtype, rows_sharding, replicated):
    import jax
    import numpy as np

    return (jax.ShapeDtypeStruct((rows, model["seq_len"] + 1), np.int32, sharding=rows_sharding),
            jax.ShapeDtypeStruct((samples,), np.int32, sharding=replicated))


def check_defaults(config, rehearse):
    return {"forward_samples": config["batch_per_chip"] // 2, "train_samples": 0}


def epoch_samples(record):
    return round(record["sequences_per_sec"] * record["time_s"])
'''

TOY_REFERENCE = '''
"""Plain reference ``toy_lm``: embedding, one matrix, cross-entropy per token."""
import jax
import jax.numpy as jnp


def forward(variables, ids):
    return variables["embed"][ids] @ variables["head"]


def cross_entropy(logits, targets):
    logp = jax.nn.log_softmax(logits.astype(jnp.float32))
    return -jnp.mean(jnp.take_along_axis(logp, targets[..., None], axis=-1))


def loss_and_grads(variables, ids, targets):
    return jax.value_and_grad(lambda v: cross_entropy(forward(v, ids), targets))(variables)
'''


class _State(NamedTuple):
    variables: dict
    opt_state: tuple


class _Adam(NamedTuple):
    mu: dict


def _fake_trainer_main(argv):
    """A job of ``steps`` optimizer steps an epoch that trains nothing: the
    records and spans ``trainer.main`` would write, until the preempt file."""
    flags = dict(zip((a[2:] for a in argv[::2]), argv[1::2]))
    import numpy as np

    n = len(np.load(flags["token-file"]))
    t0, events = time.perf_counter(), []
    with open(flags["metrics-file"], "w", buffering=1) as f:
        f.write(json.dumps({"kind": "compile", "executable": "train_step", "seconds": 0.0,
                            "mosaic_calls": 0, "devices": [0], "sharded_inputs": 0}) + "\n")
        for epoch in range(10_000):
            if os.path.exists(flags["preempt-file"]):
                break
            began = time.perf_counter()
            time.sleep(0.02)
            took = time.perf_counter() - began
            events.append({"name": "step", "ph": "X", "ts": (began - t0) * 1e6, "dur": took * 1e6,
                           "args": {"epoch": epoch, "mode": "scan"}})
            f.write(json.dumps({"kind": "epoch", "epoch": epoch, "loss": 9.0 - 0.01 * epoch,
                                "time_s": took, "sequences_per_sec": n / took}) + "\n")
    with open(flags["trace-file"], "w") as f:
        json.dump({"traceEvents": events, "otherData": {"t0_perf_counter_s": t0}}, f)
    _fake_trainer_main.flags = flags


def test_a_token_task_goes_through_the_harness_as_new_files_only(tmp_path, monkeypatch):
    import jax
    import jax.numpy as jnp

    from benchmark import correct, reference
    from benchmark.drivers import train
    from mpi_pytorch_tpu.train import step as system_step, trainer

    (tmp_path / "tasks").mkdir()
    (tmp_path / "tasks" / "toy_tokens.py").write_text(textwrap.dedent(TOY_TASK))
    (tmp_path / "reference").mkdir()
    (tmp_path / "reference" / "toy_lm.py").write_text(textwrap.dedent(TOY_REFERENCE))
    monkeypatch.setattr(tasks, "__path__", [*tasks.__path__, str(tmp_path / "tasks")])
    monkeypatch.setattr(reference, "__path__", [*reference.__path__, str(tmp_path / "reference")])
    for name in ("benchmark.tasks.toy_tokens", "benchmark.reference.toy_lm"):
        monkeypatch.delitem(sys.modules, name, raising=False)

    # What the next PR's files would say: a configuration and a traffic mix.
    model = {"vocab_size": 512, "seq_len": 64, "hidden_size": 16}
    config = {
        "name": "toy-lm", "task": "toy_tokens", "reference": "toy_lm", "model": model,
        "flags": {"model-name": "toy_lm"}, "batch_per_chip": 4,
        "check": {"forward_samples": 2, "train_samples": 1},
        "min_mosaic_calls": 0, "tolerance": {"logits_rel_l2": 1e-6, "train_loss_abs": 1e-6,
                                             "grad_rel_l2": 1e-5, "why": "the stub IS the reference"},
    }
    traffic = {
        "driver": "train", "dataset": {"recipe": "tokens", "train_sequences": 16, "seq_len": 64},
        "flags": {"scan-epoch": True}, "warmup_epochs": 1, "trace_epochs": 1,
    }
    task = tasks.load(config)
    # The default comes out 0 for the train step: an error, until stated.
    with pytest.raises(ValueError, match=r"check\.train_samples"):
        tasks.check_sizes(task, {**config, "check": {}})
    assert tasks.check_sizes(task, config) == {"forward_samples": 2, "train_samples": 1}

    # The dataset step and the window, through the driver.
    monkeypatch.setattr(trainer, "main", _fake_trainer_main)
    out = tmp_path / "out"
    out.mkdir()
    obs = train.run({
        "config": config, "traffic": traffic, "chips": 1, "seed": SEED, "seconds": 0.2,
        "trace": False, "rehearse": False, "out_dir": str(out),
        "data_root": str(tmp_path / "data"), "t_start": time.perf_counter(),
    })
    flags = _fake_trainer_main.flags
    assert flags["vocab-size"] == "512" and flags["seq-len"] == "64" and flags["batch-size"] == "4"
    assert "num-classes" not in flags and "image-size" not in flags
    assert os.path.isfile(flags["token-file"])
    assert (obs["steps_per_epoch"], obs["steps_per_program"], obs["global_batch"]) == (4, 4, 4)
    assert len(obs["epoch_marks"]) > 2

    # The check, with the system stubbed by the reference's own arithmetic.
    toy_lm = importlib.import_module("benchmark.reference.toy_lm")
    k1, k2 = jax.random.split(jax.random.PRNGKey(0))
    state = _State(
        {"embed": jax.random.normal(k1, (512, 16)), "head": jax.random.normal(k2, (16, 512))},
        (),
    )
    cfg = types.SimpleNamespace(compute_dtype="float32", remat="none",
                                parsed_compiler_options=lambda: None)
    seen = []

    def make_train_step(dtype, remat, accum_steps, mesh):
        @jax.jit
        def step(state, batch):
            seen.append(("train", batch[0].shape, batch[0].dtype, batch[1].shape))
            loss, grads = toy_lm.loss_and_grads(state.variables, *batch)
            mu = jax.tree_util.tree_map(lambda g: 0.1 * g, grads)  # Adam's (1 - b1) g
            return _State(state.variables, (_Adam(mu),)), {"loss": loss, "grad_norm": correct._norm(grads)}

        return step

    def eval_logits(state, inputs, dtype):
        seen.append(("forward", inputs.shape, inputs.dtype))
        return toy_lm.forward(state.variables, inputs)

    monkeypatch.setattr(correct, "_system", lambda obs, devices=None: (cfg, _one_chip_mesh(), state))
    monkeypatch.setattr(system_step, "make_train_step", make_train_step)
    monkeypatch.setattr(system_step, "eval_logits", eval_logits)
    assert correct.check(obs, config, SEED) == []
    assert ("forward", (2, 64), jnp.int32) in seen and ("train", (1, 64), jnp.int32, (1, 64)) in seen

    # An epoch that trained another count than the cell's arithmetic says is
    # told by the TASK's reading of the record.
    obs["epoch_marks"][1][1]["sequences_per_sec"] *= 0.5
    why = correct.check(obs, config, SEED)
    assert len(why) == 1 and "trained 8 samples, the cell says 16" in why[0]

"""Rehearsal 3, no chip time: compile a cell's scanned-epoch program at its
REAL size for the described ``v5e:2x2`` topology and print what the chip's
compiler says — memory per device, Mosaic calls, collectives.

    JAX_PLATFORMS=cpu python3 benchmark/rehearse/compile_v5e.py r18_train_dp4

Nothing runs and no time comes out of this: it finds what the compiler would
refuse (a kernel it cannot tile, a program that does not fit HBM) before a
chip call does. It also compiles the programs of the correctness check and
keeps everything in a compile cache of its own, whose size it prints: the
chip machine caps its cache (``JAX_COMPILATION_CACHE_MAX_SIZE`` 192 MiB, least
recently used out first), and a cell whose programs do not fit together
compiles in every run. The trainer builds its mesh from ``jax.devices()`` and its
kernels ask ``jax.default_backend()``, both of which see the CPU here, so
this script hands the program the described devices and steers the kernels'
backend gate itself (as the on-chip-measurement guide says a scratch script
should); the program gets no new option for it.
"""

from __future__ import annotations

import importlib
import json
import os
import re
import shutil
import sys
import time

os.environ.setdefault("TPU_LOG_DIR", "disabled")
HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
CACHE = os.path.join(os.path.dirname(HERE), "out", "rehearse_cache")


def main(workload: str) -> None:
    sys.path.insert(0, ROOT)
    cache = os.path.join(CACHE, workload)
    shutil.rmtree(cache, ignore_errors=True)
    os.environ.update(
        JAX_COMPILATION_CACHE_DIR=cache, JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS="0",
        JAX_PERSISTENT_CACHE_MIN_ENTRY_SIZE_BYTES="-1",
    )
    import jax
    import numpy as np
    from jax.experimental import topologies
    from jax.sharding import NamedSharding, PartitionSpec as P

    from benchmark import correct, tasks
    from benchmark.drivers.train import argv
    from mpi_pytorch_tpu.config import parse_config
    from mpi_pytorch_tpu.parallel.mesh import create_mesh
    from mpi_pytorch_tpu.train import trainer
    from mpi_pytorch_tpu.train.step import make_scanned_epoch
    from mpi_pytorch_tpu.utils import hardware

    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    cell = next(w for w in bench["workloads"] if w["name"] == workload)
    entry = next(c for c in bench["configs"] if c["name"] == cell["config"])
    with open(os.path.join(ROOT, entry["file"])) as f:
        config = json.load(f)
    with open(os.path.join(ROOT, "benchmark", "traffic", cell["traffic"] + ".json")) as f:
        traffic = json.load(f)
    chips, model = cell["chips"], config["model"]
    task = tasks.load(config)  # the shapes and sample counts below are its
    sizes = tasks.check_sizes(task, config)
    batch = config["batch_per_chip"] * chips
    n_train = task.train_samples(traffic["dataset"])
    flags = {**config["flags"], **traffic["flags"], **task.model_flags(model),
             "batch-size": batch, "metrics-file": "", "log-file": ""}
    cfg = parse_config(argv(flags))

    topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    mesh = create_mesh(cfg.mesh, devices=list(topo.devices)[:chips])
    hardware.tpu_backend = lambda: True  # the kernels, not their XLA compositions
    # Shapes only: the described devices hold no arrays.
    cfg.synthetic_data, cfg.debug = True, True  # any manifest will do for shapes
    _, _, state, _ = trainer.build_training(cfg, mesh=mesh)
    replicated = NamedSharding(mesh, P())
    rows = NamedSharding(mesh, P(mesh.axis_names[0]))
    shape = lambda x, s: jax.ShapeDtypeStruct(np.shape(x), np.asarray(x).dtype if not hasattr(x, "dtype") else x.dtype, sharding=s)
    state_s = jax.tree_util.tree_map(lambda x: shape(x, replicated), state)
    padded = -(-n_train // chips) * chips
    steps = n_train // batch
    dataset, labels = task.cache_shapes(model, padded, n_train, cfg.input_dtype, rows, replicated)
    idx = jax.ShapeDtypeStruct((steps, batch), np.int32, sharding=replicated)
    valid = jax.ShapeDtypeStruct((steps, batch), np.bool_, sharding=replicated)

    t0 = time.perf_counter()
    compiled = jax.jit(
        make_scanned_epoch(mesh, trainer._dtype(cfg.compute_dtype)),
        donate_argnums=(0,),
        out_shardings=(jax.tree_util.tree_map(lambda s: s.sharding, state_s), None),
    ).lower(state_s, dataset, labels, idx, valid).compile(
        compiler_options=cfg.parsed_compiler_options()
    )
    text = compiled.as_text()
    found = re.findall(
        r"\b(all-reduce|all-gather|reduce-scatter|all-to-all|collective-permute)(?:-start)?\(", text
    )
    reference = importlib.import_module("benchmark.reference." + config["reference"])
    correct.forward_compare(reference, trainer._dtype(cfg.compute_dtype)).lower(
        state_s, *task.batch_shapes(model, sizes["forward_samples"], rows, replicated)
    ).compile()
    check = compile_check_step(cfg, config, task, sizes["train_samples"], topo.devices[0])
    entries: dict[str, int] = {}  # bytes by program name (two programs can share one)
    for name in os.listdir(cache):
        if name.endswith("-cache"):
            program = name.rsplit("-", 2)[0]
            entries[program] = entries.get(program, 0) + os.path.getsize(os.path.join(cache, name))
    print(json.dumps({
        "workload": workload, "compile_only": True, "chips": chips,
        "global_batch": batch, "steps_per_epoch": steps,
        "compile_seconds_here": round(time.perf_counter() - t0, 1),
        "memory_analysis": str(compiled.memory_analysis()),
        "mosaic_calls": hardware.mosaic_call_count(compiled),
        "collectives": {k: found.count(k) for k in sorted(set(found))},
        "train_step_check": check,
        "cache_mib": round(sum(entries.values()) / 2**20, 1),
        "cache_entries_over_1_mib": {k: round(v / 2**20, 1) for k, v in entries.items() if v > 2**20},
    }, indent=1))


def compile_check_step(cfg, config: dict, task, batch: int, device) -> dict:
    """The two programs of ``correct.train_step_agreement`` at their real
    size on one described chip: the system's train step on ``batch`` samples
    of the task (images: an eighth of the cell's batch per chip), and the
    reference's float32 loss and gradient."""
    import jax
    import numpy as np
    from jax.sharding import NamedSharding, PartitionSpec as P

    from mpi_pytorch_tpu.parallel.mesh import create_mesh
    from mpi_pytorch_tpu.train import trainer
    from mpi_pytorch_tpu.train.step import make_train_step
    from mpi_pytorch_tpu.utils import hardware

    mesh = create_mesh(cfg.mesh, devices=[device])
    _, _, state, _ = trainer.build_training(cfg, mesh=mesh)
    one = NamedSharding(mesh, P())
    state_s = jax.tree_util.tree_map(
        lambda x: jax.ShapeDtypeStruct(np.shape(x), x.dtype, sharding=one), state
    )
    inputs, targets = task.batch_shapes(config["model"], batch, one, one)
    step = make_train_step(
        trainer._dtype(cfg.compute_dtype), remat=(cfg.remat == "full"), accum_steps=1, mesh=mesh
    ).lower(state_s, (inputs, targets)).compile(compiler_options=cfg.parsed_compiler_options())
    reference = importlib.import_module("benchmark.reference." + config["reference"])
    wanted = jax.jit(reference.loss_and_grads).lower(state_s.variables, inputs, targets).compile()
    return {
        "batch": batch, "mosaic_calls": hardware.mosaic_call_count(step),
        "system_step": str(step.memory_analysis()),
        "reference_grads": str(wanted.memory_analysis()),
    }


if __name__ == "__main__":
    main(sys.argv[1])

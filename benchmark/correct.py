"""The comparison that decides ``correct``. Everything here runs OUTSIDE the
measured window, after the trainer has returned.

A run is correct when
- no program was compiled inside the window;
- the compiled train program holds the Mosaic calls the configuration asks
  for (a kernel replaced by its XLA composition shows as a missing call) and,
  on several chips, has a shard on every local device and a split batch;
- every epoch loss is finite, and every epoch trained the samples the cell's
  arithmetic says it should;
- one seeded batch through the SYSTEM's forward (its evaluation path, at the
  cell's precision, inference mode so both sides use the same BatchNorm
  statistics) agrees with the plain float32 reference on logits and loss;
- one seeded TRAIN step of the system (``make_train_step`` with the cell's
  compile options: train-mode forward, the kernels' own backward calls, the
  optimizer) agrees with ``jax.value_and_grad`` of the plain reference on the
  same weights and batch: the loss, the gradient's norm, and the gradient
  itself, over the whole tree and module by module;
each within the tolerance the configuration file states with its reason.

What this cannot see: an error that only the cell's own batch size, the
scanned epoch or the split over several chips brings out (the train step is
checked on ONE chip at the task's size, for images an eighth of the cell's
batch per chip; across chips the compile record and the finite losses stand
in), and a wrong second optimizer moment (the first is compared, as the
gradient).

What a sample is — the seeded batch, the two checks' sizes, an epoch
record's count — is the task's (``benchmark/tasks/<task>.py``, by the
configuration's ``"task"``); the system and the reference take ``(inputs,
targets)`` whatever those are.
"""

from __future__ import annotations

import importlib
import math

from benchmark import tasks


def _first_moment(node):
    """Adam's ``mu`` inside an optax state, however it is wrapped."""
    if hasattr(node, "mu"):
        return node.mu
    if isinstance(node, dict):
        node = tuple(node.values())
    for child in node if isinstance(node, (tuple, list)) else ():
        found = _first_moment(child)
        if found is not None:
            return found
    return None


def _norm(tree):
    import jax
    import jax.numpy as jnp

    leaves = jax.tree_util.tree_leaves(tree)
    return jnp.sqrt(sum(jnp.sum(jnp.square(x.astype(jnp.float32))) for x in leaves))


def _system(obs: dict, devices=None):
    """The system as the cell's flags build it, on the cell's mesh or on the
    given devices: (cfg, mesh, state placed on the mesh)."""
    from benchmark.drivers.train import argv
    from mpi_pytorch_tpu.config import parse_config
    from mpi_pytorch_tpu.parallel.mesh import create_mesh
    from mpi_pytorch_tpu.train.step import place_state_on_mesh
    from mpi_pytorch_tpu.train.trainer import build_training

    cfg = parse_config(argv(obs["flags"]))
    mesh = create_mesh(cfg.mesh, devices=devices)
    mesh, _bundle, state, _ = build_training(cfg, mesh=mesh)
    return cfg, mesh, place_state_on_mesh(state, mesh)


def forward_compare(reference, dtype):
    """The program of ``forward_agreement`` (also compiled by the rehearsal)."""
    import jax
    import jax.numpy as jnp

    from mpi_pytorch_tpu.train.step import eval_logits

    @jax.jit
    def compare(state, inputs, targets):
        got = eval_logits(state, inputs, dtype)
        want = reference.forward(state.variables, inputs)
        return {
            "logits_rel_l2": jnp.linalg.norm(got - want) / jnp.linalg.norm(want),
            "loss_abs": jnp.abs(
                reference.cross_entropy(got, targets) - reference.cross_entropy(want, targets)
            ),
            "reference_loss": reference.cross_entropy(want, targets),
        }

    return compare


def forward_agreement(obs: dict, config: dict, seed: int, batch: int) -> dict:
    """Relative L2 error of the system's logits against the reference's, and
    the absolute difference of the two losses, on one seeded batch."""
    import jax

    from mpi_pytorch_tpu.train.trainer import _dtype

    reference = importlib.import_module(f"benchmark.reference.{config['reference']}")
    cfg, mesh, state = _system(obs)
    inputs, targets = tasks.load(config).seeded_batch(
        obs["model"], mesh, jax.random.PRNGKey(seed + 17), batch
    )
    compare = forward_compare(reference, _dtype(cfg.compute_dtype))
    return {k: float(v) for k, v in compare(state, inputs, targets).items()}


def train_step_agreement(obs: dict, config: dict, seed: int, batch: int) -> dict:
    """One train step of the system from freshly initialised weights against
    the reference's loss and gradient on the same weights and batch, on one
    chip. The system's gradient is read from what its step leaves behind:
    after one Adam step from zero moments ``mu`` is the gradient times
    (1 - b1), so ``mu`` scaled to the step's own ``grad_norm`` is the gradient
    (its size is compared through ``grad_norm``, its direction and the
    modules' relative sizes through ``mu``): relative L2 error over the whole
    tree, the largest of any top-level module of the model, and the largest
    relative error of a module's gradient norm. With an optimizer that keeps
    no ``mu`` only the loss and the norm are measured."""
    import jax
    import jax.numpy as jnp

    from mpi_pytorch_tpu.train.step import make_train_step
    from mpi_pytorch_tpu.train.trainer import _dtype

    reference = importlib.import_module(f"benchmark.reference.{config['reference']}")
    cfg, mesh, state = _system(obs, devices=jax.local_devices()[:1])
    inputs, targets = tasks.load(config).seeded_batch(
        obs["model"], mesh, jax.random.PRNGKey(seed + 23), batch
    )

    @jax.jit
    def want_fn(variables, inputs, targets):
        return reference.loss_and_grads(variables, inputs, targets)

    want_loss, want = want_fn(state.variables, inputs, targets)  # before the step donates the state
    step = make_train_step(
        _dtype(cfg.compute_dtype), remat=(cfg.remat == "full"), accum_steps=1, mesh=mesh,
    )
    compiled = step.lower(state, (inputs, targets)).compile(
        compiler_options=cfg.parsed_compiler_options()
    )
    new_state, metrics = compiled(state, (inputs, targets))

    mu = _first_moment(new_state.opt_state)
    if mu is None or jax.tree_util.tree_structure(mu) != jax.tree_util.tree_structure(want):
        mu = None

    @jax.jit
    def compare(metrics, mu, want_loss, want):
        want_norm = _norm(want)
        out = {
            "train_loss_abs": jnp.abs(metrics["loss"] - want_loss),
            "grad_norm_rel": jnp.abs(metrics["grad_norm"] - want_norm) / want_norm,
            "reference_grad_norm": want_norm,
        }
        if mu is not None:
            scale = metrics["grad_norm"] / _norm(mu)
            diff = jax.tree_util.tree_map(lambda m, w: m * scale - w, mu, want)
            out["grad_rel_l2"] = _norm(diff) / want_norm
            out["modules"] = {k: _norm(diff[k]) / _norm(want[k]) for k in want}
            out["grad_module_rel_l2"] = jnp.max(jnp.stack(list(out["modules"].values())))
            out["grad_module_norm_rel"] = jnp.max(jnp.stack(
                [jnp.abs(_norm(mu[k]) * scale / _norm(want[k]) - 1.0) for k in want]
            ))
        return out

    out = jax.tree_util.tree_map(float, jax.device_get(compare(metrics, mu, want_loss, want)))
    out.update(train_batch=batch, reference_train_loss=float(want_loss))
    return out


def check(obs: dict, config: dict, seed: int, rehearse: bool = False) -> list[str]:
    """Reasons the run is NOT correct; empty when it is."""
    import jax

    why = []
    in_window = [t for t in obs["compiles"] if obs["window_start"] <= t <= obs["t_end"]]
    if in_window:
        why.append(f"{len(in_window)} compilation(s) inside the measured window")
    compiled = [r for r in obs["records"] if r["kind"] == "compile"]
    if len(compiled) != 1:
        why.append(f"{len(compiled)} compile records, expected the train program's one")
    for rec in compiled:
        want = 0 if rehearse else config["min_mosaic_calls"]
        if rec["mosaic_calls"] < want:
            why.append(f"{rec['mosaic_calls']} Mosaic call(s) in {rec['executable']}, expected >= {want}")
        ids = sorted(d.id for d in jax.local_devices())
        if rec["devices"] != ids:
            why.append(f"{rec['executable']} has shards on devices {rec['devices']}, not {ids}")
        if len(ids) > 1 and rec["sharded_inputs"] < 2:
            why.append(f"{rec['executable']}: the batch is not split over {len(ids)} devices")
    task = tasks.load(config)
    sizes = tasks.check_sizes(task, config, rehearse)
    samples = obs["steps_per_epoch"] * obs["global_batch"]
    for _, rec in obs["epoch_marks"]:
        if not math.isfinite(rec["loss"]):
            why.append(f"epoch {rec['epoch']} loss {rec['loss']}")
        trained = task.epoch_samples(rec)
        if trained != samples:
            why.append(f"epoch {rec['epoch']} trained {trained} samples, the cell says {samples}")
    agreement = forward_agreement(obs, config, seed, sizes["forward_samples"])
    print(f"benchmark: forward agreement {agreement}", flush=True)
    trained = train_step_agreement(obs, config, seed, sizes["train_samples"])
    print(f"benchmark: train step agreement {trained}", flush=True)
    agreement.update(trained)
    tolerance = config["tolerance"]
    if rehearse:  # tiny sizes on the CPU: the configuration's looser rehearsal bounds
        tolerance = dict(tolerance, **config["rehearse"]["tolerance"])
    for key, limit in tolerance.items():
        if key == "why":
            continue
        if key not in agreement:
            why.append(f"{key} has a tolerance and was not measured")
        elif not agreement[key] <= limit:
            why.append(f"{key} {agreement[key]:.3g} over the tolerance {limit}")
    return why

"""Device mesh construction and sharding rules.

The reference's process model — N MPI ranks, each a full model replica
(``main.py:16-18``) — becomes one global ``jax.sharding.Mesh`` with a
``data`` axis (DP, ≙ MPI ranks) and a ``model`` axis (TP). The reference has
no tensor parallelism (SURVEY §2c), but its 64 500-class head is the one
layer where sharding matters (512×64500 ≈ 33 M params for resnet18, ~25% of
the model): the ``model`` axis column-shards exactly that head, as a config
change (``--mesh.model-parallel N``).
"""

from __future__ import annotations

from typing import Any

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from mpi_pytorch_tpu.config import MeshConfig


def create_mesh(cfg: MeshConfig, devices: list | None = None) -> Mesh:
    """Build a (data, model[, pipe]) mesh over all devices (or the given
    ones). The ``pipe`` axis exists only when ``pipe_parallel > 1``
    (--pp-stages), so 2-axis layouts — and everything keyed on
    ``axis_names[0] == data`` / ``axis_names[1] == model`` — are untouched.
    Pipe is the LAST reshape axis: consecutive pipeline stages land on
    adjacent devices, so the stage→stage ``ppermute`` rides neighbor ICI
    links.

    ``pods > 1`` (--mesh-pods, ISSUE 15 / ROADMAP item 5) FACTORS the data
    axis into the nested ``(pod, ici)`` pair instead: the mesh becomes
    ``(pod, ici, model)`` with ``pod`` as the MAJOR reshape axis, so every
    ``ici`` group is a contiguous run of devices — and, multi-host, a
    contiguous run of whole processes — meaning the within-pod collectives
    never cross a pod boundary (ICI stays ICI, and only the ``pod`` axis
    rides the DCN). Flat meshes (pods == 1) are byte-identical to before."""
    from mpi_pytorch_tpu.utils.env import fault_countdown

    if fault_countdown("MPT_FAULT_BACKEND_WEDGE_N"):
        # The wedged-backend-init scenario: deterministic, in-process,
        # absorbed by the resume-side retry loop
        # (train/elastic.with_retries).
        raise RuntimeError(
            "injected fault: backend init wedged (MPT_FAULT_BACKEND_WEDGE_N)"
        )
    devices = devices if devices is not None else jax.devices()
    n = len(devices)
    mp, pp = cfg.model_parallel, cfg.pipe_parallel
    if n % (mp * pp) != 0:
        raise ValueError(
            f"{n} devices not divisible by model_parallel={mp} x pipe_parallel={pp}"
        )
    dp = cfg.data_parallel if cfg.data_parallel > 0 else n // (mp * pp)
    if dp * mp * pp != n:
        raise ValueError(
            f"data_parallel×model_parallel×pipe_parallel = {dp}×{mp}×{pp} "
            f"!= {n} devices"
        )
    if cfg.pods > 1:
        if pp > 1:
            raise ValueError(
                "mesh pods (hierarchical data axis) does not compose with "
                "pipe_parallel — the pipe axis claims the trailing reshape "
                "position the nested layout needs"
            )
        if dp % cfg.pods != 0:
            raise ValueError(
                f"data-parallel size {dp} not divisible by pods={cfg.pods}; "
                "the data axis factors as pods × ici"
            )
        ici = dp // cfg.pods
        per_pod = ici * mp
        local = jax.local_device_count()
        if jax.process_count() > 1 and per_pod % local != 0:
            raise ValueError(
                f"each pod spans {per_pod} device(s) but processes hold "
                f"{local}; a process may not straddle a pod boundary "
                "(pods are whole hosts on separate DCN domains)"
            )
        arr = np.asarray(devices).reshape(cfg.pods, ici, mp)
        return Mesh(arr, (cfg.pod_axis, cfg.ici_axis, cfg.model_axis))
    if pp == 1:
        arr = np.asarray(devices).reshape(dp, mp)
        return Mesh(arr, (cfg.data_axis, cfg.model_axis))
    arr = np.asarray(devices).reshape(dp, mp, pp)
    return Mesh(arr, (cfg.data_axis, cfg.model_axis, cfg.pipe_axis))


def create_serve_mesh(shard_degree: int, devices: list | None = None) -> Mesh:
    """The nested ``(data, model)`` SERVE mesh (ISSUE 17): ``model`` spans
    ``shard_degree`` chips (one tenant's TP/FSDP split), ``data`` the rest
    (distinct batch rows — and, fleet-wise, distinct tenants — land on
    distinct data-slices). The axis names are FIXED to the trainer defaults
    so every helper below (``data_axis_names``, ``model_axis_name``,
    ``shard_first_divisible``) reads a serve mesh exactly like a flat
    training mesh — PR 15's axis-name discipline, reused rather than
    reinvented. ``shard_degree == 1`` is the degenerate replicated layout
    (``(n, 1)``, identical to ``serve.server.local_replica_mesh``)."""
    devices = devices if devices is not None else jax.devices()
    n = len(devices)
    k = int(shard_degree)
    if k < 1:
        raise ValueError(f"serve shard degree must be >= 1, got {shard_degree}")
    if n % k != 0:
        raise ValueError(
            f"{n} device(s) not divisible by serve shard degree {k}; a "
            "sharded tenant occupies exactly K chips per data-slice"
        )
    arr = np.asarray(devices).reshape(n // k, k)
    return Mesh(arr, (SERVE_DATA_AXIS, SERVE_MODEL_AXIS))


def create_pipe_serve_mesh(stages: int, devices: list | None = None) -> Mesh:
    """The nested ``(data, pipe)`` SERVE mesh (ISSUE 20): ``pipe`` spans
    ``stages`` chip groups — stage ``s`` of a pipeline tenant owns column
    ``s`` (``mesh.devices[:, s]``), ``data`` the ``n // stages`` chips
    within each stage group (distinct micro-batch rows). Like the serve
    ``(data, model)`` mesh the axis names are FIXED: residency records and
    the planner's per-chip byte arithmetic key on the literal ``"pipe"``
    axis, which is reserved exactly like ``pod``/``ici`` (MeshConfig
    rejects configurable axes claiming it)."""
    devices = devices if devices is not None else jax.devices()
    n = len(devices)
    k = int(stages)
    if k < 2:
        raise ValueError(
            f"pipeline serve mesh needs >= 2 stages, got {stages}"
        )
    if n % k != 0:
        raise ValueError(
            f"{n} device(s) not divisible by pipe stage count {k}; each "
            "stage occupies an equal disjoint chip group"
        )
    arr = np.asarray(devices).reshape(n // k, k)
    return Mesh(arr, (SERVE_DATA_AXIS, SERVE_PIPE_AXIS))


# ---------------------------------------------------------------------------
# Nested (hierarchical) data-axis helpers — the one vocabulary every layer
# keys the pod/ici factoring on, so "is this mesh hierarchical" can never
# drift between the step, the state sharder, and the trainer.
# ---------------------------------------------------------------------------

# Serve-mesh axis names are FIXED like the pod/ici pair (not MeshConfig-
# renameable): residency records, the packing planner's per-chip byte
# arithmetic, and the reshard path all key on them.
SERVE_DATA_AXIS, SERVE_MODEL_AXIS = "data", "model"

# The pipeline-stage axis of the nested (data, pipe) serve mesh (ISSUE 20).
# Reserved: stage chip-group membership, interstage ledger booking, and the
# planner's stage byte arithmetic all key on the literal name.
SERVE_PIPE_AXIS = "pipe"

# The nested data-axis names are FIXED (unlike the flat axis, which
# MeshConfig can rename): the traffic ledger classifies collectives by
# whether they touch "pod", and a renamed pod axis would silently book DCN
# traffic as ICI.
POD_AXIS, ICI_AXIS = "pod", "ici"


def is_hierarchical(mesh: Mesh) -> bool:
    """Whether ``mesh`` carries the nested ``(pod, ici)`` data factoring."""
    return POD_AXIS in mesh.axis_names and ICI_AXIS in mesh.axis_names


def data_axis_names(mesh: Mesh) -> tuple[str, ...]:
    """The batch-sharding axes, major→minor: ``("pod", "ici")`` on a nested
    mesh, ``(axis_names[0],)`` on a flat one. Everything that shards a batch
    dimension (or psums a per-shard scalar globally) reduces over exactly
    this tuple."""
    if is_hierarchical(mesh):
        return (POD_AXIS, ICI_AXIS)
    return (mesh.axis_names[0],)


def data_axis_size(mesh: Mesh) -> int:
    """Total data-parallel shard count (pods × ici on a nested mesh)."""
    size = 1
    for a in data_axis_names(mesh):
        size *= int(mesh.shape[a])
    return size


def pod_shape(mesh: Mesh) -> tuple[int, int]:
    """``(pods, ici)`` — ``(1, data_size)`` on a flat mesh, so flat-mesh
    callers can treat every mesh as one pod."""
    if is_hierarchical(mesh):
        return int(mesh.shape[POD_AXIS]), int(mesh.shape[ICI_AXIS])
    return 1, data_axis_size(mesh)


def zero_shard_axis(mesh: Mesh) -> tuple[str, int]:
    """``(axis_name, n_shards)`` the ZeRO optimizer-state partition keys on:
    the ``ici`` axis on a nested mesh — shards place WITHIN a pod, each pod
    holding a full (pod-replicated) copy, so the param all_gather that
    reassembles full weights every step never touches the DCN — and the
    whole data axis on a flat one."""
    if is_hierarchical(mesh):
        return ICI_AXIS, int(mesh.shape[ICI_AXIS])
    axis = mesh.axis_names[0]
    return axis, int(mesh.shape[axis])


def model_axis_name(mesh: Mesh) -> str:
    """The TP axis: ``axis_names[2]`` on a nested ``(pod, ici, model)``
    mesh, ``axis_names[1]`` otherwise (flat 2-axis and pipe 3-axis alike)."""
    return mesh.axis_names[2] if is_hierarchical(mesh) else mesh.axis_names[1]


def mesh_topology(mesh: Mesh) -> dict:
    """The world shape of ``mesh`` as plain JSON-able data — the vocabulary
    of the checkpoint topology manifest and the ``kind="resume"`` record
    (train/elastic.py): device/process counts plus the per-axis sizes in
    axis order."""
    return {
        "device_count": int(mesh.devices.size),
        "process_count": int(jax.process_count()),
        "mesh_axes": list(mesh.axis_names),
        "mesh_shape": {str(a): int(mesh.shape[a]) for a in mesh.axis_names},
    }


def describe_topology(topo: dict | None) -> str:
    """``"8 devices (data=8, model=1)"`` — the human rendering of a
    ``mesh_topology`` dict for logs and resume records; legacy (None) reads
    as unknown."""
    if not topo:
        return "unknown (legacy checkpoint, no manifest)"
    axes = ", ".join(f"{a}={s}" for a, s in topo.get("mesh_shape", {}).items())
    return f"{topo.get('device_count', '?')} devices ({axes})"


def flat_mesh(mesh: Mesh, axis: str) -> Mesh:
    """A one-axis mesh over the SAME devices as ``mesh``, for the in-model
    SP/EP wrappers (they shard sequence/experts over their own axis name
    while the surrounding step stays batch-sharded over ``data``)."""
    devices = mesh.devices.reshape(-1)
    return Mesh(np.asarray(devices).reshape(len(devices), 1), (axis, "_"))


def is_head_kernel(path_keys: tuple) -> tuple[bool, bool]:
    """(is_head_param, is_kernel) for a param path. Head layers are named
    ``head``/``aux_head`` across the whole zoo (models/common.py)."""
    keys = [str(getattr(k, "key", k)) for k in path_keys]
    is_head = any(k in ("head", "aux_head") for k in keys)
    return is_head, keys[-1] == "kernel"


def shard_first_divisible(shape, axis_name: str, size: int) -> P:
    """The ZeRO shard-selection rule, shared by FSDP param placement and the
    ZeRO-1 moment placement (train/step.py): shard the FIRST dimension that
    divides evenly by the axis size; no divisible dim → replicate."""
    for i, dim in enumerate(shape):
        if dim > 0 and dim % size == 0:
            return P(*([None] * i + [axis_name] + [None] * (len(shape) - i - 1)))
    return P()


def param_specs(params: Any, mesh: Mesh, fsdp: bool = False) -> Any:
    """PartitionSpecs for a param tree: classifier-head kernels column-sharded
    over the ``model`` axis (Megatron-style vocab-parallel classifier), head
    bias sharded likewise, everything else replicated (pure DP).

    ``fsdp`` (ZeRO-3-style, beyond reference parity): every param that would
    be replicated is instead sharded over the ``data`` axis on its first
    evenly-divisible dimension. At rest each device then holds 1/n of the
    weights; inside the jitted step XLA all-gathers each layer's weights just
    before use and reduce-scatters its gradient — the compiler-native form of
    fully-sharded data parallelism. Params with no divisible axis (small
    biases, BN scales) stay replicated."""
    model_axis = model_axis_name(mesh)
    data_axis, data_size = mesh.axis_names[0], mesh.shape[mesh.axis_names[0]]

    def spec(path, leaf):
        is_head, is_kernel = is_head_kernel(path)
        if not is_head or mesh.shape[model_axis] == 1:
            if fsdp and data_size > 1:
                return shard_first_divisible(leaf.shape, data_axis, data_size)
            return P()
        if is_kernel:
            # Dense kernel [in, out] or 1×1-conv kernel [kh, kw, in, out]:
            # shard the output (class) dim, provided it divides evenly.
            if leaf.shape[-1] % mesh.shape[model_axis] == 0:
                return P(*([None] * (leaf.ndim - 1) + [model_axis]))
            return P()
        if leaf.ndim == 1 and leaf.shape[0] % mesh.shape[model_axis] == 0:
            return P(model_axis)  # bias over classes
        return P()

    return jax.tree_util.tree_map_with_path(spec, params)


def named_shardings(tree_specs: Any, mesh: Mesh) -> Any:
    return jax.tree_util.tree_map(
        lambda s: NamedSharding(mesh, s), tree_specs,
        is_leaf=lambda x: isinstance(x, P),
    )


def shard_batch(batch: tuple, mesh: Mesh) -> tuple:
    """Place a host batch onto the mesh, batch axis over ``data`` — the
    scatter step (``main.py:91``) as a pure device placement.

    Multi-host: each host holds only its own shard of the global batch
    (per-host manifest sharding, trainer.build_training), so the global array
    is assembled from process-local data — no cross-host scatter traffic,
    unlike the reference's rank-0 pickled-dataframe scatter.

    Nested meshes shard the batch over BOTH data factors (``("pod",
    "ici")`` on dim 0) — pod-major, so shard (p, i) holds exactly the rows
    flat shard ``p*ici + i`` would (the property the hierarchical ≡ flat
    parity tests rest on)."""
    data_axis = data_axis_names(mesh)

    def put(x):
        spec = P(data_axis, *([None] * (x.ndim - 1)))
        sharding = NamedSharding(mesh, spec)
        if jax.process_count() > 1:
            return jax.make_array_from_process_local_data(sharding, x)
        return jax.device_put(x, sharding)

    return jax.tree_util.tree_map(put, batch)

"""Checkpoint save/restore — parity with ``helpers.py`` + its call sites.

Reference semantics preserved:
- epoch-granular save of ``{epoch, state_dict, optimizer, loss}``
  (``main.py:162-171``, ``helpers.py:4-7``) → here
  ``{epoch, params, batch_stats, opt_state, loss, step, config}``;
- rank-0-only writes (``main.py:162``) → process-0-only writes;
- ``FROM_CHECKPOINT`` resume restoring model+optimizer and returning the
  epoch (``main.py:127-130``, ``helpers.py:10-15``);
- post-restore broadcast (``sync_params``, ``main.py:131``) → restored
  arrays are ``device_put`` replicated/sharded onto the mesh.

Improvements the reference lacks (SURVEY §5 failure-detection row): the file
is written atomically (tmp+rename, so a crash mid-write can't corrupt the
resume path — the reference overwrites its single fixed path in place,
``helpers.py:6-7``), the last-k checkpoints are kept, and ``latest`` resolves
automatically for auto-resume.
"""

from __future__ import annotations

import functools
import os
import re
import threading
from typing import Any

import jax
import numpy as np
from flax import serialization

from mpi_pytorch_tpu.utils.logging import process_index, run_logger

_CKPT_RE = re.compile(r"ckpt_(\d+)\.msgpack$")

# Version of the msgpack payload layout ``_payload_from`` writes — stamped
# into the topology-manifest sidecar so a future payload change can be
# detected at load time instead of failing deep inside deserialization.
PAYLOAD_SCHEMA = 1

# Sidecar files that ride a checkpoint and share its lifecycle (written
# after the atomic rename, removed by retention alongside the payload).
_SIDECARS = (".dirty", ".manifest.json")


class CheckpointCorruptError(RuntimeError):
    """The checkpoint file exists but cannot be restored (truncated write,
    bit rot, or a payload that no longer matches the expected schema).
    ``train/elastic.py`` catches this and falls back to the previous
    checkpoint instead of crashing the resume."""


def _ckpt_path(ckpt_dir: str, epoch: int) -> str:
    return os.path.join(ckpt_dir, f"ckpt_{epoch:05d}.msgpack")


def checkpoint_epoch(path: str) -> int | None:
    """The epoch a checkpoint file is filed under, from its name."""
    m = _CKPT_RE.search(os.path.basename(path))
    return int(m.group(1)) if m else None


def _state_arrays(state: Any) -> dict:
    """The device-array view of a TrainState that goes into a checkpoint —
    the one place that knows which state fields are persisted."""
    return {
        "step": state.step,
        "params": state.params,
        "batch_stats": state.batch_stats,
        "opt_state": state.opt_state,
        "rng": state.rng,
    }


# Chunked (leaf-sliced, sequential) D2H for the background writer was
# built and rejected at headline scale (docs/RESULTS.md §2, round 5):
# sequential 32 MB fetches each pay a full request latency, while one
# whole-tree jax.device_get pipelines every leaf's transfer in a single
# async batch. The snapshot-size lever that DOES work is ``moments_bf16``;
# the whole-tree async get stays.


def _payload_from(arrays: dict, epoch: int, loss: float) -> dict:
    """The single checkpoint schema, built from a ``_state_arrays`` dict
    (live state or async snapshot) — save paths and the restore template all
    route through here so they can never drift apart."""
    return {
        "epoch": epoch,
        "step": np.asarray(jax.device_get(arrays["step"])),
        "loss": np.asarray(loss, np.float32),
        "params": jax.device_get(arrays["params"]),
        "batch_stats": jax.device_get(arrays["batch_stats"])
        if arrays["batch_stats"] is not None
        else {},
        "opt_state": jax.device_get(arrays["opt_state"]),
        "rng": jax.device_get(arrays["rng"]),
    }


def _payload(state: Any, epoch: int = 0, loss: float = 0.0) -> dict:
    return _payload_from(_state_arrays(state), epoch, loss)


def _write_atomic(
    ckpt_dir: str, path: str, payload: dict, keep: int, dirty: bool = False,
    manifest: dict | None = None,
) -> None:
    tmp = path + ".tmp"
    with open(tmp, "wb") as f:
        f.write(serialization.to_bytes(payload))
    # Topology manifest (ISSUE 7): the writer's world shape, so an elastic
    # restore knows what layout the payload was gathered FROM. Sidecar, so
    # the msgpack schema stays stable across checkpoint generations;
    # atomically written BEFORE the payload rename so a loadable payload
    # always has its manifest (a crash in between leaves an orphan sidecar
    # next to no payload — harmless noise, overwritten by the next save of
    # that epoch — whereas the reverse order would leave a manifest-less
    # checkpoint that restores as 'legacy' with its topology unrecorded).
    write_manifest(path, manifest)
    os.replace(tmp, path)  # atomic on POSIX
    # Dirty = the state carries a partial epoch's updates beyond the epoch it
    # is filed under (mid-epoch preemption). A sidecar rather than a payload
    # field keeps the msgpack schema stable across checkpoint generations;
    # written AFTER the rename so a marker never outlives a failed write,
    # and a clean overwrite of the same epoch clears it.
    marker = path + ".dirty"
    if dirty:
        with open(marker, "w") as f:
            f.write("partial-epoch state: resume replays the interrupted epoch\n")
    elif os.path.exists(marker):
        os.remove(marker)
    _cleanup(ckpt_dir, keep)


def write_manifest(ckpt_path: str, manifest: dict | None) -> None:
    """Atomically (re)write the topology-manifest sidecar of ``ckpt_path``
    (None clears it — an overwrite by a manifest-less writer must not leave
    a stale topology lying next to a new payload)."""
    import json

    sidecar = ckpt_path + ".manifest.json"
    if manifest is None:
        if os.path.exists(sidecar):
            os.remove(sidecar)
        return
    tmp = sidecar + ".tmp"
    with open(tmp, "w") as f:
        json.dump(manifest, f, indent=1, sort_keys=True)
    os.replace(tmp, sidecar)


def read_manifest(ckpt_path: str) -> dict | None:
    """The topology manifest saved next to ``ckpt_path``, or None for a
    legacy/manifest-less checkpoint (including an unreadable sidecar — a
    corrupt manifest downgrades the restore to legacy behavior rather than
    failing a resume the payload itself could serve)."""
    import json

    sidecar = ckpt_path + ".manifest.json"
    if not os.path.exists(sidecar):
        return None
    try:
        with open(sidecar) as f:
            return json.load(f)
    except (OSError, ValueError):
        run_logger().warning("unreadable checkpoint manifest %s (treating as legacy)", sidecar)
        return None


def save_checkpoint(
    ckpt_dir: str,
    *,
    epoch: int,
    state: Any,
    loss: float,
    keep: int = 3,
    dirty: bool = False,
    manifest: dict | None = None,
) -> str | None:
    """Synchronous save (process 0 only); returns the path written. The
    trainer uses ``AsyncCheckpointer``; this stays as the blocking variant
    for tools and tests."""
    if process_index() != 0:
        return None
    os.makedirs(ckpt_dir, exist_ok=True)
    path = _ckpt_path(ckpt_dir, epoch)
    _write_atomic(ckpt_dir, path, _payload(state, epoch, loss), keep, dirty, manifest)
    return path


def _cleanup(ckpt_dir: str, keep: int) -> None:
    """Last-k retention — except the best-marked checkpoint (``best.json``),
    which survives however old it gets (≙ the reference's *intended*
    ``is_best``/``best_model_dir`` machinery, accepted-and-ignored at
    ``helpers.py:4-7``)."""
    best = best_marker(ckpt_dir)
    pinned = os.path.basename(best["checkpoint"]) if best else None
    ckpts = sorted(
        (m.group(1), name)
        for name in os.listdir(ckpt_dir)
        if (m := _CKPT_RE.search(name))
    )
    for _, name in ckpts[:-keep] if keep > 0 else []:
        if name != pinned:
            os.remove(os.path.join(ckpt_dir, name))
            for suffix in _SIDECARS:
                marker = os.path.join(ckpt_dir, name + suffix)
                if os.path.exists(marker):
                    os.remove(marker)


def best_marker(ckpt_dir: str) -> dict | None:
    """Read ``best.json`` ({epoch, accuracy, checkpoint}) if present."""
    import json

    path = os.path.join(ckpt_dir, "best.json")
    if not os.path.exists(path):
        return None
    with open(path) as f:
        return json.load(f)


def write_best_marker(ckpt_dir: str, *, epoch: int, accuracy: float, ckpt_path: str) -> None:
    """Atomically point ``best.json`` at the best-validation checkpoint
    (process 0 only)."""
    import json

    if process_index() != 0:
        return
    path = os.path.join(ckpt_dir, "best.json")
    tmp = path + ".tmp"
    with open(tmp, "w") as f:
        json.dump(
            {"epoch": epoch, "accuracy": accuracy,
             "checkpoint": os.path.basename(ckpt_path)},
            f,
        )
    os.replace(tmp, path)


def latest_checkpoint(ckpt_dir: str) -> str | None:
    paths = checkpoint_paths(ckpt_dir)
    return paths[-1] if paths else None


def checkpoint_paths(ckpt_dir: str) -> list[str]:
    """Every checkpoint in ``ckpt_dir``, oldest→newest — the fallback order
    (reversed) an elastic restore walks when the newest file is corrupt."""
    if not os.path.isdir(ckpt_dir):
        return []
    ckpts = sorted(
        (int(m.group(1)), name)
        for name in os.listdir(ckpt_dir)
        if (m := _CKPT_RE.search(name))
    )
    return [os.path.join(ckpt_dir, name) for _, name in ckpts]


@functools.lru_cache(maxsize=None)
def _copy_fn(out_sharding=None):
    # jit output buffers never alias inputs (no donation), so this yields
    # FRESH device arrays — the snapshot the async writer reads while the
    # training loop donates the originals into the next step. With
    # ``out_sharding`` (a replicated NamedSharding) the copy additionally
    # gathers every leaf onto all devices, which makes ZeRO-sharded Adam
    # moments and the TP-sharded head process-0-addressable on multi-host
    # meshes — the all-gather that turns a distributed state into a
    # checkpointable one.
    copy = lambda t: jax.tree_util.tree_map(lambda x: x.copy(), t)  # noqa: E731
    if out_sharding is None:
        return jax.jit(copy)
    return jax.jit(copy, out_shardings=out_sharding)


# Optimizer-moment tensors at or above this element count are cast to bf16
# by the ``moments_bf16`` snapshot option; schedule scalars / step counts
# below it stay exact (a bf16 Adam count would corrupt bias correction).
_MOMENT_CAST_MIN_SIZE = 4096


@functools.lru_cache(maxsize=None)
def _moment_cast_fn():
    """Jitted device-side cast of the big f32 optimizer-moment tensors to
    bf16 — fused into the snapshot so the D2H transfer and the file carry
    half the bytes (~540 MB → ~270 MB of Adam moments at headline scale).
    Shardings pass through untouched (no donation: the live state keeps
    training). Lossy by design: restore returns moments quantized to bf16
    (~3 decimal digits), which perturbs the post-resume trajectory within
    optimizer-noise — the flag trades that for 2× faster snapshots."""

    import jax.numpy as jnp  # local: keep module import surface minimal

    def cast(opt_state):
        return jax.tree_util.tree_map(
            lambda x: x.astype(jnp.bfloat16)
            if x.dtype == jnp.float32 and x.size >= _MOMENT_CAST_MIN_SIZE
            else x,
            opt_state,
        )

    return jax.jit(cast)


def _replicated_sharding(arrays: dict):
    """``NamedSharding(mesh, P())`` over the mesh the state lives on, or None
    for states that aren't mesh-placed (plain host/numpy test states)."""
    from jax.sharding import NamedSharding, PartitionSpec

    for leaf in jax.tree_util.tree_leaves(arrays):
        s = getattr(leaf, "sharding", None)
        if isinstance(s, NamedSharding):
            return NamedSharding(s.mesh, PartitionSpec())
    return None


def _any_sharded(arrays: dict) -> bool:
    for leaf in jax.tree_util.tree_leaves(arrays):
        s = getattr(leaf, "sharding", None)
        if s is not None and not s.is_fully_replicated:
            return True
    return False


# Leaves above this (unsharded) size gather individually; everything smaller
# shares one jitted gather. 4 MB ≈ where a leaf's transient replication
# starts to matter against HBM, while biases/BN stats stay batched.
_BIG_LEAF_BYTES = 4 * 1024 * 1024


def _gather_to_host(arrays: dict, repl) -> dict:
    """All-gather a SHARDED state (fsdp / zero_optimizer / TP) to host numpy.

    A whole-tree replicated gather would transiently hold the full unsharded
    state — params plus both Adam moments, ~3x params — on EVERY device at
    once, which can OOM exactly the configurations that needed sharding.
    Instead: every small leaf rides ONE jitted gather (one XLA compile, a
    few MB of transient HBM), and each BIG leaf (> ``_BIG_LEAF_BYTES``
    unsharded) gathers alone and is freed once on host — peak per-device
    overhead is the small-leaf total plus ONE big leaf. Strictly per-leaf
    gathering would bound memory the same way but costs one collective
    compile per leaf (observed: minutes of stall on a 2-process save). The
    device_get runs on the caller thread (the async writer then only
    serializes), a trade the sharded configs accept."""
    flat, treedef = jax.tree_util.tree_flatten(arrays)
    gather = _copy_fn(repl)
    p0 = process_index() == 0

    def to_host(g):
        # Only process 0 writes the checkpoint; the other processes skip the
        # D2H copy (and the full-state host allocation) they'd never use —
        # but EVERY process runs the collective gather itself.
        host = np.asarray(jax.device_get(g)) if p0 else None
        g.delete()  # free the replicated copy before the next gather
        return host

    big = {i for i, leaf in enumerate(flat) if leaf.nbytes > _BIG_LEAF_BYTES}
    out: list = [None] * len(flat)
    small_idx = [i for i in range(len(flat)) if i not in big]
    if small_idx:
        gathered = gather([flat[i] for i in small_idx])
        for i, g in zip(small_idx, gathered):
            out[i] = to_host(g)
    for i in sorted(big):
        out[i] = to_host(gather(flat[i]))
    return jax.tree_util.tree_unflatten(treedef, out)


def _cast_moments(opt_state):
    """``moments_bf16`` cast for a MIXED device/host optimizer tree:
    jax.Array leaves go through the jitted device-side cast (fused into the
    snapshot, as before); host numpy leaves — a ZeRO run's gathered-on-save
    moments (trainer ``_saveable``) — are cast on the HOST. Routing them
    through the jitted cast would device_put the full unsharded moment tree
    back onto every device: exactly the 2×params transient the sharding
    freed."""
    import jax.numpy as jnp

    flat, treedef = jax.tree_util.tree_flatten(opt_state)
    dev_idx = [i for i, leaf in enumerate(flat) if isinstance(leaf, jax.Array)]
    out = [
        leaf.astype(jnp.bfloat16)
        if (
            not isinstance(leaf, jax.Array)
            and hasattr(leaf, "dtype")
            and leaf.dtype == np.float32
            and leaf.size >= _MOMENT_CAST_MIN_SIZE
        )
        else leaf
        for leaf in flat
    ]
    if dev_idx:
        casted = _moment_cast_fn()([flat[i] for i in dev_idx])
        for i, c in zip(dev_idx, casted):
            out[i] = c
    return jax.tree_util.tree_unflatten(treedef, out)


def _snapshot_mixed(arrays: dict, repl) -> dict:
    """Donation-safe snapshot of a MIXED device/host state tree: jax.Array
    leaves get the ~ms on-device jitted copy (fresh buffers the background
    writer can read while the train loop donates the originals), host numpy
    leaves pass through untouched. Jitting the whole tree would silently
    device_put every host leaf replicated onto ALL devices — for a ZeRO
    run's gathered-on-save optimizer state (trainer ``_saveable``) that is
    exactly the 2×params transient HBM spike gather-on-save exists to
    avoid."""
    flat, treedef = jax.tree_util.tree_flatten(arrays)
    dev_idx = [i for i, leaf in enumerate(flat) if isinstance(leaf, jax.Array)]
    if dev_idx:
        copied = _copy_fn(repl)([flat[i] for i in dev_idx])
        jax.block_until_ready(copied)  # copy is cheap; be certain
        for i, c in zip(dev_idx, copied):
            flat[i] = c
    return jax.tree_util.tree_unflatten(treedef, flat)


class AsyncCheckpointer:
    """Non-blocking checkpointing: a ~ms on-device copy snapshots the state,
    then a background thread does the expensive ``device_get`` + serialize +
    atomic write while training continues.

    Rationale: the jitted train step donates the state (train/step.py), so a
    background transfer from the *live* arrays would race with their deletion
    on the next step; the device-side copy gives the writer its own buffers.
    One save in flight at a time (a new save waits for the previous write);
    call ``wait()`` before reading the file or exiting."""

    def __init__(self) -> None:
        self._thread: threading.Thread | None = None
        self._error: BaseException | None = None

    def save(
        self,
        ckpt_dir: str,
        *,
        epoch: int,
        state: Any,
        loss: float,
        keep: int = 3,
        on_durable=None,
        dirty: bool = False,
        moments_bf16: bool = False,
        manifest: dict | None = None,
    ) -> str | None:
        """Snapshot now, write in the background; returns the path that will
        exist once the write completes (None on processes > 0).

        ``moments_bf16`` casts the large f32 optimizer-moment tensors to
        bf16 on device before the snapshot (``--ckpt-bf16-moments``):
        halves the moment D2H bytes and the file size; restore casts back
        to the optimizer's dtype (values quantized to bf16).

        EVERY process must call this (the trainer does): the snapshot is a
        global SPMD computation on multi-host meshes, so gating it to
        process 0 would diverge the programs the processes run. Only process
        0 spawns the writer thread. Replicated state takes the fast path (a
        ~ms on-device copy; the background thread does the device_get).
        Sharded state (fsdp / ZeRO-1 moments / the TP head) goes through
        ``_gather_to_host`` instead: a synchronous all-gather streamed to
        host numpy on the caller thread — all small leaves in one program,
        big leaves one at a time, so the peak device overhead is the
        small-leaf total plus one big unsharded leaf, not the whole state —
        after which the writer only serializes."""
        self.wait()
        arrays = _state_arrays(state)
        if moments_bf16:
            arrays = dict(arrays, opt_state=_cast_moments(arrays["opt_state"]))
        repl = _replicated_sharding(arrays)
        if repl is not None and _any_sharded(arrays):
            # Sharded state: leaf-by-leaf host gather (see _gather_to_host)
            # instead of materializing the whole unsharded state on-device.
            snapshot = _gather_to_host(arrays, repl)
        else:
            snapshot = _snapshot_mixed(arrays, repl)
        if process_index() != 0:
            return None
        os.makedirs(ckpt_dir, exist_ok=True)
        path = _ckpt_path(ckpt_dir, epoch)

        def _worker() -> None:
            try:
                _write_atomic(
                    ckpt_dir, path, _payload_from(snapshot, epoch, loss), keep, dirty,
                    manifest,
                )
                if on_durable is not None:
                    # Runs strictly AFTER the atomic rename: anything the
                    # callback publishes (e.g. the best.json marker) can
                    # never reference a file that doesn't exist yet.
                    on_durable(path)
            except BaseException as e:  # surfaced on the next save()/wait()
                self._error = e

        self._thread = threading.Thread(
            target=_worker, name="async-checkpoint", daemon=True
        )
        self._thread.start()
        return path

    def wait(self) -> None:
        """Block until the in-flight write (if any) has landed; re-raise any
        writer error on the caller thread."""
        if self._thread is not None:
            self._thread.join()
            self._thread = None
        if self._error is not None:
            err, self._error = self._error, None
            raise err


def load_checkpoint(path: str, state: Any) -> tuple[Any, int, float]:
    """Restore (state, epoch, loss) from a checkpoint file (≙
    ``load_checkpoint``, helpers.py:10-15 — which returns the epoch so the
    driver can continue the epoch loop, main.py:127-129)."""
    if os.path.exists(path + ".dirty"):
        m = _CKPT_RE.search(os.path.basename(path))
        epoch_txt = (m.group(1).lstrip("0") or "0") if m else "the filed epoch"
        run_logger().warning(
            "resuming from a DIRTY checkpoint (%s): it was saved after a "
            "mid-epoch preemption, so the state already carries part of epoch "
            "%s+1's updates. When the saved data cursor validates, the "
            "trainer continues EXACTLY at the interrupted step (no replayed "
            "updates); otherwise that epoch is replayed, double-applying "
            "those batches' steps (trajectory may differ from an "
            "uninterrupted run)",
            path, epoch_txt,
        )
    with open(path, "rb") as f:
        data = f.read()
    try:
        restored = serialization.from_bytes(_payload(state), data)
        # A moments_bf16 checkpoint stores the big moment tensors in bf16; the
        # optimizer expects its own dtype (f32) back. Cast against the live
        # state's opt_state as the dtype template (no-op for exact saves).
        opt_state = jax.tree_util.tree_map(
            lambda tmpl, got: np.asarray(got).astype(tmpl.dtype)
            if hasattr(tmpl, "dtype") and got.dtype != tmpl.dtype
            else got,
            _state_arrays(state)["opt_state"],
            restored["opt_state"],
        )
    except OSError:
        raise  # a vanished file is a caller error, not payload corruption
    except MemoryError:
        # Host memory pressure, not on-disk damage: falling back to an
        # OLDER checkpoint would silently discard good progress while the
        # next attempt would fail the same way — surface it.
        raise
    except Exception as e:
        # Truncated msgpack, garbage bytes, missing/mismatched payload keys:
        # typed so the elastic restore (train/elastic.py) can fall back to
        # the previous checkpoint instead of crashing the resume.
        raise CheckpointCorruptError(
            f"checkpoint {path} failed to restore ({type(e).__name__}: {e})"
        ) from e
    new_state = state.replace(
        step=jax.numpy.asarray(restored["step"]),
        params=restored["params"],
        batch_stats=restored["batch_stats"] if state.batch_stats is not None else None,
        opt_state=opt_state,
        rng=jax.numpy.asarray(restored["rng"]),
    )
    return new_state, int(restored["epoch"]), float(restored["loss"])


def load_for_eval(path: str, state: Any) -> tuple[Any, int, float]:
    """Restore params + batch_stats only — the inference path (≙ predictor
    ranks loading just the ``state_dict``, ``evaluation_pipeline.py:142-144``).
    No optimizer template is needed, so eval never materializes Adam moments."""
    with open(path, "rb") as f:
        raw = serialization.msgpack_restore(f.read())
    params = serialization.from_state_dict(jax.device_get(state.params), raw["params"])
    batch_stats = None
    if state.batch_stats is not None:
        batch_stats = serialization.from_state_dict(
            jax.device_get(state.batch_stats), raw["batch_stats"]
        )
    new_state = state.replace(params=params, batch_stats=batch_stats)
    return new_state, int(raw["epoch"]), float(raw["loss"])

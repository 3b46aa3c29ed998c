"""Hardware peak table for MFU / roofline accounting (SURVEY §5 observability
— the reference only has wall-clock ``MPI.Wtime`` pairs, ``main.py:145,158``)
and the one backend gate the Pallas kernels share."""

from __future__ import annotations

import glob
import re

# (peak bf16 TFLOP/s, peak HBM GB/s) per chip, keyed by the EXACT
# ``device_kind`` string the chip reports. One row per kind this repo has
# actually run on; a TPU that is not here is an error (``_lookup``), never a
# silently dropped MFU column.
_PEAKS = {
    # Reported by the v5e chip machine (chip_smoke.py's first line, PR 21).
    # Peaks: Google Cloud documentation, "TPU v5e" system architecture page
    # (197 TFLOP/s bf16, 819 GB/s HBM per chip).
    "TPU v5 lite": (197.0, 819.0),
}


def _lookup(device, column: int) -> float | None:
    if device.platform != "tpu":
        return None  # CPU (the test backend) has no peak worth a ratio
    kind = device.device_kind
    if kind not in _PEAKS:
        raise KeyError(
            f"no peak FLOP/s / HBM bandwidth recorded for TPU device_kind "
            f"{kind!r}; add a row (with its source) to utils/hardware._PEAKS"
        )
    return _PEAKS[kind][column]


def peak_bf16_tflops(device) -> float | None:
    """Peak bf16 TFLOP/s for a jax device; None on CPU; raises on a TPU
    kind that is not in the table."""
    return _lookup(device, 0)


def peak_hbm_gbps(device) -> float | None:
    """Peak HBM GB/s for a jax device; None on CPU; raises on a TPU kind
    that is not in the table."""
    return _lookup(device, 1)


def tpu_backend() -> bool:
    """True when the default backend is a TPU. THE gate every Pallas kernel
    uses to choose compiled kernel vs XLA composition, kept in one place so
    the kernels cannot disagree. A backend that fails to initialize raises
    here — it never reads as "not a TPU"."""
    import jax

    return jax.default_backend() == "tpu"


def local_tpu_chips() -> int:
    """TPU chips this machine exposes, counted WITHOUT starting a JAX
    backend (which would take them): the chips' device nodes,
    ``/dev/vfio/<n>`` on v5e-class hosts and ``/dev/accel<n>`` on older
    ones. 0 on a CPU-only machine. For a parent that spawns the processes
    that will own the chips and must itself stay off them."""
    return len(glob.glob("/dev/vfio/[0-9]*")) + len(glob.glob("/dev/accel[0-9]*"))


_MOSAIC_CALL = re.compile(r'(?:@|custom_call_target=")tpu_custom_call\b')


def mosaic_call_count(stage) -> int:
    """Mosaic (Pallas TPU) custom calls in a Lowered or Compiled stage's
    text. StableHLO spells one ``@tpu_custom_call``, optimized HLO
    ``custom_call_target="tpu_custom_call"``."""
    return len(_MOSAIC_CALL.findall(stage.as_text()))


def compile_record(name: str, compiled, seconds: float) -> dict:
    """The ``kind="compile"`` metrics record of one AOT-compiled executable:
    what RAN, not what was asked for (chip_smoke.py checks it).

    - ``mosaic_calls``: Mosaic (Pallas TPU) custom calls in the optimized
      HLO — a requested kernel replaced by its XLA composition shows as a
      missing call.
    - ``devices``: ids of the local devices EVERY input (state leaves and
      batch) has an addressable shard on — an AOT executable rejects inputs
      placed otherwise, so this is where the run's arrays live.
    - ``sharded_inputs``: how many inputs are split rather than replicated
      (the batch's arrays on a >1-device data axis; 0 on one device)."""
    import jax

    shardings = jax.tree_util.tree_leaves(compiled.input_shardings)
    devices = set.intersection(
        *({d.id for d in s.addressable_devices} for s in shardings)
    )
    return {
        "kind": "compile",
        "executable": name,
        "seconds": round(seconds, 3),
        "mosaic_calls": mosaic_call_count(compiled),
        "devices": sorted(devices),
        "sharded_inputs": sum(not s.is_fully_replicated for s in shardings),
    }


def step_flops(compiled) -> float:
    """Total FLOPs of an XLA executable (0.0 if unavailable). Accepts either
    a Compiled or a Lowered stage — cost analysis does not require the
    (expensive) backend compile."""
    try:
        ca = compiled.cost_analysis()
        if isinstance(ca, (list, tuple)):
            ca = ca[0] if ca else {}
        return float(ca.get("flops", 0.0))
    except Exception:
        return 0.0

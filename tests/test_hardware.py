"""``utils/hardware.py``: nothing here may hide the device.

The peak table answers for the chips this repo has run on, says ``None`` on
the CPU test backend, and RAISES for a TPU it does not know (a silently
dropped ``mfu_pct`` column is how a wrong number ships). ``tpu_backend`` has
no alias and swallows nothing. ``compile_record`` reports what an executable
carries and where its inputs live."""

import types

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from mpi_pytorch_tpu.utils import hardware


def _device(platform, kind):
    return types.SimpleNamespace(platform=platform, device_kind=kind)


def test_peaks_for_the_chip_this_repo_runs_on():
    v5e = _device("tpu", "TPU v5 lite")  # what the v5e machine reports (PR 21)
    assert hardware.peak_bf16_tflops(v5e) == 197.0
    assert hardware.peak_hbm_gbps(v5e) == 819.0


def test_cpu_has_no_peak():
    assert hardware.peak_bf16_tflops(jax.devices()[0]) is None
    assert hardware.peak_hbm_gbps(jax.devices()[0]) is None


def test_unknown_tpu_kind_raises_and_names_the_kind():
    with pytest.raises(KeyError, match="TPU v9 hypothetical"):
        hardware.peak_bf16_tflops(_device("tpu", "TPU v9 hypothetical"))
    # No substring matching: a near-miss is unknown, not "close enough".
    with pytest.raises(KeyError):
        hardware.peak_hbm_gbps(_device("tpu", "TPU v5"))


def test_tpu_backend_has_no_alias_and_swallows_nothing(monkeypatch):
    assert hardware.tpu_backend() is False  # the CPU test backend
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    assert hardware.tpu_backend() is True
    monkeypatch.setattr(jax, "default_backend", lambda: "some_tpu_plugin")
    assert hardware.tpu_backend() is False

    def broken():
        raise RuntimeError("Unable to initialize backend 'tpu'")

    monkeypatch.setattr(jax, "default_backend", broken)
    with pytest.raises(RuntimeError, match="Unable to initialize"):
        hardware.tpu_backend()


def test_local_tpu_chips_is_zero_here_and_starts_no_backend():
    assert hardware.local_tpu_chips() == 0  # this sandbox has no accelerator


def test_compile_record_reports_mosaic_calls_and_input_placement():
    mesh = Mesh(np.array(jax.devices()), ("data",))
    batch = jax.device_put(
        np.zeros((len(jax.devices()) * 2, 4), np.float32),
        NamedSharding(mesh, P("data")),
    )
    weights = jax.device_put(np.ones((4,), np.float32), NamedSharding(mesh, P()))
    compiled = jax.jit(lambda w, x: jnp.sum(x * w)).lower(weights, batch).compile()
    rec = hardware.compile_record("probe", compiled, 1.23456)
    assert rec["kind"] == "compile" and rec["executable"] == "probe"
    assert rec["seconds"] == 1.235
    assert rec["mosaic_calls"] == 0  # a CPU executable carries no Mosaic call
    assert rec["devices"] == sorted(d.id for d in jax.devices())
    assert rec["sharded_inputs"] == 1  # the batch; the weights are replicated

    from mpi_pytorch_tpu.obs.schema import validate_record

    assert validate_record({"ts": 0.0, **rec}) == []


def test_mosaic_call_count_reads_both_renderings():
    def stage(text):
        return types.SimpleNamespace(as_text=lambda: text)

    stablehlo = "%3:2 = stablehlo.custom_call @tpu_custom_call(%0, %1) {backend_config"
    hlo = 'custom-call(%a, %b), custom_call_target="tpu_custom_call", operand_layout'
    other = 'custom_call_target="Sharding" @tpu_custom_call_not_this'
    assert hardware.mosaic_call_count(stage(stablehlo)) == 1
    assert hardware.mosaic_call_count(stage(hlo + hlo)) == 2
    assert hardware.mosaic_call_count(stage(other)) == 0

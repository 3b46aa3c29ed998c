"""Import every ``mpi_pytorch_tpu`` module — the version-skew tripwire.

A moving-API break (e.g. ``shard_map`` relocating between JAX versions,
see ``parallel/compat.py``) used to surface as EIGHT opaque pytest
collection errors spread across the suite. This walks the package and
imports each module so the same break surfaces as ONE named failure
pointing at the module that raised.
"""

import importlib
import pkgutil

import pytest

import mpi_pytorch_tpu

_MODULES = sorted(
    info.name
    for info in pkgutil.walk_packages(
        mpi_pytorch_tpu.__path__, prefix="mpi_pytorch_tpu."
    )
    # native/_mptnative_<hash>.so is a plain ctypes shared library (built
    # on demand by native/__init__.py), not a Python extension module —
    # importlib would look for a PyInit symbol it deliberately lacks.
    if "._mptnative" not in info.name
)


def test_package_walk_found_the_tree():
    # Guard against an empty walk silently passing: the package has well
    # over a dozen modules across ops/parallel/train/models/data/utils.
    assert len(_MODULES) > 20, _MODULES
    for expected in (
        "mpi_pytorch_tpu.parallel.compat",
        "mpi_pytorch_tpu.ops.fused_stem",
        "mpi_pytorch_tpu.train.step",
    ):
        assert expected in _MODULES


@pytest.mark.parametrize("name", _MODULES)
def test_module_imports(name):
    importlib.import_module(name)

"""The ONE place the codebase touches JAX APIs that are not public and
stable: ``shard_map`` is re-exported from here (callers write the public
``jax.shard_map`` signature, ``check_vma=`` included), ``axis_is_manual``
wraps the private axis-env lookup, and ``process_index`` the distributed
client's state. A JAX upgrade that moves any of them surfaces as one failed
import of this module
(tests/test_imports.py names it), not as a scatter of collection errors.
"""

from __future__ import annotations

from jax import shard_map  # noqa: F401  (re-export)
from jax._src import core as _core
from jax._src import distributed as _distributed


def axis_is_manual(name: str) -> bool:
    """True when ``name`` is already a BOUND mesh axis in the current trace
    context — i.e. this code is executing inside a shard_map over that axis
    (e.g. the spmd-mode train step). Self-partitioning ops
    (ops/fused_stem.py, ops/fused_head_ce.py) use this to skip their own
    shard_map wrap: nesting over the same axis is an error, and inside the
    outer map they already see per-shard operands."""
    return name in _core.get_axis_env().axis_sizes


def process_index() -> int:
    """``jax.process_index()`` without its side effect. The public call
    starts the default backend — on a TPU host that takes every chip — just
    to read a number ``jax.distributed.initialize`` already stored (0 when
    it was never called, i.e. single-process)."""
    return _distributed.global_state.process_id

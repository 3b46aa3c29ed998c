"""The one way this package issues a Pallas kernel, so that every kernel is
found by name in a device trace and none by its shapes.

Convention (listed in PERF.md section 3): ``kernel_call("stem_bwd", ...)``
passes ``name="stem_bwd"`` to ``pallas_call`` (the name Mosaic gives the
kernel) and runs the call under the ``jax.named_scope`` ``kernel/stem_bwd``,
so the custom call's ``op_name`` ends in ``kernel/stem_bwd/…`` whatever
wraps it (``bn1`` on one chip, ``shard_map`` on four). Where two kernels are
one to a reader (the stem forward with and without its window index) both
calls give the scope's name and the second passes its own ``name=`` among
``pallas_call``'s keywords. Scopes are metadata: the compiled program is the
same with and without them.
"""

from __future__ import annotations

import jax
from jax.experimental import pallas as pl


def kernel_call(scope: str, kernel, /, **kwargs):
    """``pl.pallas_call(kernel, name=scope, **kwargs)`` whose invocation runs
    under the scope ``kernel/<scope>``; ``kwargs`` may give the kernel a
    ``name`` of its own."""
    call = pl.pallas_call(kernel, name=kwargs.pop("name", scope), **kwargs)

    def run(*args):
        with jax.named_scope(f"kernel/{scope}"):
            return call(*args)

    return run

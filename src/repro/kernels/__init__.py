"""Pallas TPU kernels for SPEC-RL hot spots.

Each kernel subpackage ships kernel.py (pl.pallas_call + BlockSpec tiling),
ops.py (jit'd public wrapper) and ref.py (pure-jnp oracle used by tests).

Every op takes ``impl=``: ``"auto"`` (what the program passes), ``"pallas"``,
``"interpret"`` (the kernel in Pallas interpret mode, any backend) or the
op's reference.  ``resolve`` is the one place that maps ``"auto"`` to a
concrete implementation.
"""
from __future__ import annotations

import jax


def resolve(kernel: str, impl: str, ref: str) -> str:
    """The implementation ``impl`` names for the op of package ``kernel``:
    ``"auto"`` is the Pallas kernel on a TPU backend and the op's reference
    ``ref`` elsewhere; any other name is returned as it is.  Ops call it
    at trace time through this module, so a test can patch it (see the
    ``interpret_kernels`` fixture in tests/conftest.py)."""
    if impl != "auto":
        return impl
    return "pallas" if jax.default_backend() == "tpu" else ref

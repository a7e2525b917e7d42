"""Pallas kernels for the paper's device-side hot spots.

Every kernel entry point takes ``interpret`` as a required argument, so a
call never runs in interpret mode by accident. Wrappers that may run on
any backend resolve their choice through `pallas_interpret`, the one
place the backend decides it.
"""
from __future__ import annotations

import jax


def pallas_interpret(interpret: bool | None = None) -> bool:
    """Interpret mode for a Pallas call: the caller's explicit choice, or,
    when None, compiled on a TPU backend and interpreted on any other."""
    if interpret is not None:
        return interpret
    return jax.default_backend() != "tpu"

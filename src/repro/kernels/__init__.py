"""Pallas TPU kernels of the collective-I/O hot path (sort, coalesce,
pack, and the fused round drain) plus their jit'd wrappers (``ops``).

Every kernel takes ``interpret: bool | None = None`` and resolves it
through :func:`interpret_mode`, so a kernel is compiled on a TPU and
interpreted elsewhere unless its caller asks otherwise.
"""
from __future__ import annotations

import jax


def interpret_mode(interpret: bool | None) -> bool:
    """``interpret`` as given, or — when ``None`` — interpret on every
    backend but the TPU."""
    if interpret is None:
        return jax.default_backend() != "tpu"
    return interpret

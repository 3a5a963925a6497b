"""Kernel-wide platform policy: where a Pallas kernel compiles or interprets."""
from __future__ import annotations

import jax


def resolve_interpret(interpret: bool | None) -> bool:
    """``None`` -> compile with Mosaic on a TPU, interpret elsewhere."""
    if interpret is None:
        return jax.default_backend() != "tpu"
    return bool(interpret)

"""Mesh builders: the one place a ``jax.sharding.Mesh`` is made.

Every mesh here has ``Auto`` axes. ``jax.make_mesh`` defaults to
``Explicit`` axes on JAX >= 0.7, whose sharding-in-types rules refuse the
reshapes and slices the FFT pipelines apply around ``shard_map``
(``ShardingTypeError: Splitting on more than 1 axis``); with ``Auto`` axes
GSPMD propagates those shardings as it always has.
"""
from __future__ import annotations

import jax
from jax.sharding import AxisType

__all__ = ["make_mesh", "make_fft_mesh"]


def make_mesh(shape, axes, *, devices=None):
    """``jax.make_mesh`` with every axis ``Auto`` (see the module doc)."""
    shape, axes = tuple(shape), tuple(axes)
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes),
                         devices=devices)


def make_fft_mesh(shards: int | None = None, data: int = 1):
    """Mesh carrying the ``fft`` signal axis for the distributed transform.

    ``shards`` devices along ``fft`` hold pencils of each signal (see
    core/fft/distributed.py); a leading ``data`` axis batch-parallelizes
    independent transforms — the 2-D batch x pencil composition every entry
    point (distributed_fft/ifft, the spectral consumers, serve --mode fft)
    auto-detects. The multi-dimensional transforms (core/fft/multidim.py)
    reuse the same mesh: slab shards the grid over ``fft`` with the batch
    on ``data``, while the pencil decomposition spends ``data`` on the
    second transform axis, scaling ONE grid over all ``data * shards``
    devices.

    ``shards=None`` takes the largest power of two of the visible devices
    per ``data`` row (spare devices stay idle). An explicit request is
    built exactly as asked or refused: a mesh never silently holds fewer
    devices than the caller named.
    """
    if data < 1:
        raise ValueError(f"data axis size must be >= 1, got {data}")
    n = len(jax.devices())
    if shards is None:
        shards = 1 << (max(1, n // data).bit_length() - 1)
    if shards < 1 or shards & (shards - 1):
        raise ValueError(f"the pencil split needs a power-of-two shard "
                         f"count, got shards={shards}")
    if data * shards > n:
        raise ValueError(f"a data={data} x fft={shards} mesh needs "
                         f"{data * shards} devices; {n} are visible")
    if data > 1:
        return make_mesh((data, shards), ("data", "fft"))
    return make_mesh((shards,), ("fft",))

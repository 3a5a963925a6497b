"""FFT library extensions beyond the paper's C2C core: real-input transform,
2-D transform, and FT-protected inverse via conjugation.

These compose the validated building blocks (no new numerics):
  rfft:  real -> half-spectrum via one C2C FFT of half length (the classic
         packing trick: x_even + i*x_odd),
  fft2:  thin wrapper over a rank-2 plan (``core.fft.api`` — slab/pencil on
         a mesh, local otherwise),
  ft_ifft: ifft(x) = conj(fft(conj(x))) / N — runs the *forward* protected
         kernel, so the two-sided ABFT covers the inverse transform too.

Every function here is spec-builder sugar over ``core.fft.api``: it builds
(or LRU-hits) the :class:`~repro.core.fft.api.FFTPlan` describing the call
and runs the plan executor — the same single dispatch path ``kernels.ops``
and ``launch.serve`` use. ``rfft``/``irfft`` therefore accept ``mesh=``:
the half-length C2C transform runs the distributed pencil pipeline when the
mesh (and a power-of-two half length >= shards^2) allows, and falls back to
the local transform otherwise — including the odd-``n`` ``irfft`` branch,
which is a direct DFT (odd lengths are outside the power-of-two planner).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from .distributed import _AUTO, FFT_AXIS, _resolve_mesh
from .stockham import fft as _fft, ifft as _ifft, naive_dft

__all__ = ["rfft", "irfft", "fft2", "ifft2", "rfft2", "irfft2", "ft_ifft"]


def _complex_for(dtype) -> jnp.dtype:
    """The complex dtype a real input of ``dtype`` promotes to: float64
    keeps double precision (complex128), everything else is complex64."""
    return jnp.dtype(jnp.complex128 if dtype == jnp.float64
                     else jnp.complex64)


def _plan_c2c(z, mesh, axis, data_axis, *, natural_order=True):
    """The plan for one C2C helper transform of ``z`` — distributed iff the
    resolved mesh can actually split ``z``'s last axis, local otherwise."""
    from . import api

    mesh = _resolve_mesh(mesh, axis)
    if mesh is not None and mesh.shape[axis] > 1 \
            and api._feasible_1d(z.shape[-1], mesh.shape[axis]):
        return api.plan(api.spec_for(z, mesh=mesh, axis=axis,
                                     data_axis=data_axis,
                                     natural_order=natural_order))
    return None


def rfft(x: jax.Array, *, mesh=None, axis: str = FFT_AXIS,
         data_axis: str | None = _AUTO) -> jax.Array:
    """Real-input FFT over the last axis -> (..., N/2+1) half spectrum.

    ``mesh`` distributes the underlying half-length C2C transform over the
    pencil pipeline (the Hermitian unpacking is elementwise and stays
    wherever GSPMD puts it); infeasible sizes fall back to the local path.
    Odd lengths cannot split into the even/odd pack, so they run the local
    O(n^2) direct DFT and crop to the ``n//2 + 1`` bins — the same
    documented fallback as the odd-``n`` :func:`irfft` branch.
    """
    x = jnp.asarray(x)
    n = x.shape[-1]
    if n == 0:
        raise ValueError("rfft: empty signal axis (n=0) has no spectrum")
    if n % 2:
        # odd n: no even/odd split — direct DFT, cropped half spectrum
        full = naive_dft(x.astype(_complex_for(x.dtype)))
        return full[..., :n // 2 + 1]
    z = pack_half(x)
    p = _plan_c2c(z, mesh, axis, data_axis)
    zf = p.fft(z) if p is not None else _fft(z)
    return unpack_half(zf, n)


# The packing trick's two elementwise halves are jitted: run eagerly under
# x64, their Python scalars would reach the device as 64-bit (complex128)
# operands, which the TPU compiler cannot lower.
@jax.jit
def pack_half(x: jax.Array) -> jax.Array:
    """(..., N) real -> (..., N/2) complex ``z[k] = x[2k] + i x[2k+1]``."""
    return (x[..., 0::2] + 1j * x[..., 1::2]).astype(_complex_for(x.dtype))


@functools.partial(jax.jit, static_argnames="n")
def unpack_half(zf: jax.Array, n: int) -> jax.Array:
    """Hermitian unpack: the (..., N/2) C2C bins of :func:`pack_half`'s
    ``z`` -> the (..., N/2+1) rfft bins of the length-``n`` real signal."""
    # host-side table: an on-device exp of int64 iotas (x64) is complex128
    w = jnp.asarray(np.exp(-2j * np.pi * np.arange(n // 2 + 1) / n), zf.dtype)
    zf_ext = jnp.concatenate([zf, zf[..., :1]], axis=-1)      # Z[half] = Z[0]
    zconj = jnp.conj(zf_ext[..., ::-1])                        # Z*[half-k]
    return 0.5 * (zf_ext + zconj) - 0.5j * w * (zf_ext - zconj)


def irfft(y: jax.Array, n: int | None = None, *, mesh=None,
          axis: str = FFT_AXIS, data_axis: str | None = _AUTO) -> jax.Array:
    """Inverse of rfft: (..., N/2+1) half spectrum -> (..., N) real.

    Even ``n`` keeps this library's documented semantics: reconstruct the
    ``2*(len-1)``-point signal and truncate it to ``n`` samples. Odd ``n``
    is a genuinely different transform — the spectrum then has no Nyquist
    bin and the Hermitian tail is ``conj(y[..., 1:][..., ::-1])``, not the
    even-length tail (truncating the even reconstruction silently returns
    wrong values). For odd ``n`` we therefore crop to the ``(n+1)//2`` bins
    an odd-length real signal has (numpy's convention) and invert exactly;
    the odd full length is outside the power-of-two Stockham planner, so
    that branch runs the O(n^2) direct inverse DFT — locally even when a
    ``mesh`` is passed (the documented fallback).
    """
    y = jnp.asarray(y)
    if y.shape[-1] == 0:
        raise ValueError("irfft: empty spectrum (0 bins)")
    if n is None:
        if y.shape[-1] == 1:
            raise ValueError(
                "irfft: a single-bin spectrum has no default length "
                "(2*(bins-1) = 0) — pass n explicitly (n=1 or n=2)")
        n = 2 * (y.shape[-1] - 1)
    if n <= 0:
        raise ValueError(f"irfft: output length must be positive, got n={n}")
    if n == 1:
        # one sample: the spectrum is just the (real) DC bin
        return jnp.real(y[..., :1])
    if n % 2:
        m = (n + 1) // 2   # bins of an odd-length real signal
        if y.shape[-1] < m:
            raise ValueError(
                f"irfft: spectrum has {y.shape[-1]} bins but odd n={n} "
                f"needs at least {m}")
        yh = y[..., :m]
        tail = jnp.conj(yh[..., 1:][..., ::-1])
        full = jnp.concatenate([yh, tail], axis=-1)     # length n, odd
        return jnp.real(naive_dft(full, inverse=True))
    # reconstruct the full spectrum by Hermitian symmetry, ifft, take real
    tail = jnp.conj(y[..., 1:-1][..., ::-1])
    full = jnp.concatenate([y, tail], axis=-1)
    p = _plan_c2c(full, mesh, axis, data_axis)
    inv = p.ifft(full) if p is not None else _ifft(full)
    return jnp.real(inv)[..., :n]


def fft2(x: jax.Array, *, mesh=None, interpret: bool | None = None,
         axis: str = "fft", natural_order: bool = True,
         decomp: str = "auto") -> jax.Array:
    """2-D FFT over the last two axes — spec-builder sugar over a rank-2
    plan (``core.fft.api``).

    ``mesh`` (or an ``x`` already committed to an fft-axis mesh) dispatches
    to the slab/pencil decomposition; without one this is the local
    transform (odd / non-power-of-two axes run the direct DFT, and
    ``interpret`` routes power-of-two axes through the Pallas kernel).
    """
    from . import api

    x = jnp.asarray(x)
    if not jnp.issubdtype(x.dtype, jnp.complexfloating):
        x = x.astype(_complex_for(x.dtype))
    spec = api.spec_for(x, rank=2, mesh=mesh, axis=axis,
                        natural_order=natural_order, decomp=decomp,
                        interpret=interpret)
    return api.plan(spec).fft(x)


def ifft2(x: jax.Array, *, mesh=None, interpret: bool | None = None,
          axis: str = "fft", natural_order: bool = True,
          decomp: str = "auto") -> jax.Array:
    """Inverse of :func:`fft2` (normalized by 1/(R*C)); same mesh /
    interpret threading, see :func:`repro.core.fft.multidim.distributed_ifft2`.
    """
    from . import api

    x = jnp.asarray(x)
    if not jnp.issubdtype(x.dtype, jnp.complexfloating):
        x = x.astype(_complex_for(x.dtype))
    spec = api.spec_for(x, rank=2, mesh=mesh, axis=axis,
                        natural_order=natural_order, decomp=decomp,
                        interpret=interpret)
    return api.plan(spec).ifft(x)


def rfft2(x: jax.Array, *, mesh=None, interpret: bool | None = None,
          axis: str = FFT_AXIS, data_axis: str | None = _AUTO,
          decomp: str = "auto") -> jax.Array:
    """2-D real-input FFT over the last two axes -> (..., R, C/2+1) half
    spectrum — spec-builder sugar over a rank-2 *real* plan.

    On a mesh the row pass is the half-length packed C2C transform and only
    the C/2+1 surviving column pencils (padded to a shard-divisible width)
    flow through the inter-axis transpose — about half the all-to-all bytes
    of :func:`fft2` on the same grid (see ``multidim.distributed_rfft2``).
    """
    from . import api

    x = jnp.asarray(x)
    if jnp.issubdtype(x.dtype, jnp.complexfloating):
        raise ValueError(f"rfft2 takes a real input, got {x.dtype}")
    spec = api.spec_for(x, rank=2, mesh=mesh, axis=axis, data_axis=data_axis,
                        decomp=decomp, interpret=interpret, real=True)
    return api.plan(spec).rfft2(x)


def irfft2(y: jax.Array, *, mesh=None, interpret: bool | None = None,
           axis: str = FFT_AXIS, data_axis: str | None = _AUTO,
           decomp: str = "auto") -> jax.Array:
    """Inverse of :func:`rfft2`: (..., R, C/2+1) half spectrum ->
    (..., R, C) real grid with ``C = 2*(bins-1)`` (even columns only; odd
    grids go through the local :func:`irfft` per axis)."""
    from . import api
    from repro.parallel.fft_sharding import infer_fft_mesh

    y = jnp.asarray(y)
    if y.ndim < 2:
        raise ValueError(f"irfft2 needs a rank >= 2 spectrum, got {y.shape}")
    if y.shape[-1] < 2:
        raise ValueError(
            "irfft2: a single-bin half spectrum has no default width — "
            "the columns' full length 2*(bins-1) would be 0")
    cc = 2 * (y.shape[-1] - 1)
    dtype = "complex128" if y.dtype in (jnp.complex128, jnp.float64) \
        else "complex64"
    spec = api.FFTSpec(shape=y.shape[:-2] + (y.shape[-2], cc), dtype=dtype,
                       rank=2, mesh=mesh if mesh is not None
                       else infer_fft_mesh(y, axis), axis=axis,
                       data_axis=data_axis, decomp=decomp,
                       interpret=interpret, real=True)
    return api.plan(spec).irfft2(y)


def ft_ifft(x: jax.Array, **ft_kwargs):
    """Fault-tolerant inverse FFT via conjugation around the protected
    forward kernel: ifft(x) = conj(fft(conj(x))) / N. Returns the same
    FTFFTResult as ops.ft_fft, with y already conjugated/normalized."""
    from repro.kernels import ops

    x = jnp.asarray(x)
    if not jnp.issubdtype(x.dtype, jnp.complexfloating):
        x = x.astype(_complex_for(x.dtype))
    n = x.shape[-1]
    res = ops.ft_fft(jnp.conj(x), **ft_kwargs)
    y = jnp.conj(res.y) / n
    import dataclasses

    return dataclasses.replace(res, y=y)

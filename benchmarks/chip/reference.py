"""Plain references for the benchmark's correctness checks.

Nothing here imports the program under test. Two references:

* :func:`fft64`, the oracle: NumPy's FFT in float64 on the host.
* :func:`lowp_fft`, the control: a plain DFT on the device whose matrix
  products take three bf16 passes (``hi*hi + hi*lo + lo*hi``), what
  ``Precision.HIGH`` computes on the MXU, one step below the program's
  ``HIGHEST`` (float32). The split is written out so that the control
  computes the same thing on every backend.

The compared number is :func:`rel_err`: the widest gap of any row,
``max|got - want| / max|want|`` per row.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

# a dense DFT matrix up to this size; a two-factor (four-step) DFT above it
DENSE_MAX = 4096


def fft64(x) -> np.ndarray:
    """Float64 FFT of every row (last axis) of ``x``."""
    return np.fft.fft(np.asarray(x, np.complex128), axis=-1)


def rel_err(got, want) -> float:
    """Largest per-row ``max|got - want| / max|want|``; inf where ``got``
    holds a non-finite value."""
    got = np.asarray(got)
    want = np.asarray(want)
    if got.shape != want.shape:
        raise ValueError(f"output shape {got.shape} != reference "
                         f"{want.shape}")
    if not np.isfinite(got).all():
        return float("inf")
    got = got.reshape(-1, got.shape[-1])
    want = want.reshape(-1, want.shape[-1])
    num = np.abs(got - want).max(axis=-1)
    den = np.maximum(np.abs(want).max(axis=-1), np.finfo(np.float64).tiny)
    return float((num / den).max())


def _twiddles(rows: int, cols: int, n: int) -> tuple[np.ndarray, np.ndarray]:
    """``exp(-2 pi i (r*c mod n) / n)`` for ``r < rows``, ``c < cols``, as
    float32 (re, im); the angle is reduced in integers, then rounded once."""
    r = np.arange(rows, dtype=np.int64)[:, None]
    c = np.arange(cols, dtype=np.int64)[None, :]
    ang = (-2.0 * np.pi / n) * ((r * c) % n)
    return np.cos(ang).astype(np.float32), np.sin(ang).astype(np.float32)


def _to_bf16(v):
    """Each float32 of ``v`` rounded to the nearest bf16 (ties to even), as
    float32. Done on the bits, not as a round trip through bf16, which XLA
    may fold away."""
    bits = jax.lax.bitcast_convert_type(v, jnp.uint32)
    bits = bits + jnp.uint32(0x7FFF) + ((bits >> 16) & jnp.uint32(1))
    return jax.lax.bitcast_convert_type(bits & jnp.uint32(0xFFFF0000),
                                        jnp.float32)


def _split_dot(a, b, passes: int):
    """``a @ b`` in float32 from bf16 pieces of each operand, the three
    products ``hi@hi + hi@lo + lo@hi`` (``Precision.HIGH``)."""
    if passes != 3:
        raise ValueError(f"the control computes 3 bf16 passes, got {passes}")

    def pieces(v):
        hi = _to_bf16(v)
        return hi.astype(jnp.bfloat16), (v - hi).astype(jnp.bfloat16)

    def dot(u, v):
        return jnp.matmul(u, v, preferred_element_type=jnp.float32)

    (ah, al), (bh, bl) = pieces(a), pieces(b)
    return dot(al, bh) + dot(ah, bl) + dot(ah, bh)


def _cdot(xr, xi, wr, wi, passes: int):
    """Complex ``x @ w`` from four real products."""
    return (_split_dot(xr, wr, passes) - _split_dot(xi, wi, passes),
            _split_dot(xr, wi, passes) + _split_dot(xi, wr, passes))


@functools.partial(jax.jit, static_argnames=("passes",))
def _dense(xr, xi, wr, wi, *, passes: int):
    return _cdot(xr, xi, wr, wi, passes)


@functools.partial(jax.jit, static_argnames=("passes",))
def _four_step(xr, xi, pr, pi, qr, qi, tr, ti, *, passes: int):
    rows, n = xr.shape
    p, q = pr.shape[0], qr.shape[0]
    # n = q*i + j: a[r, j, i] = x[q*i + j]; DFT over i (length p)
    ar = jnp.swapaxes(xr.reshape(rows, p, q), 1, 2)
    ai = jnp.swapaxes(xi.reshape(rows, p, q), 1, 2)
    br, bi = _cdot(ar, ai, pr, pi, passes)               # (rows, j, k1)
    br, bi = jnp.swapaxes(br, 1, 2), jnp.swapaxes(bi, 1, 2)  # (rows, k1, j)
    br, bi = br * tr - bi * ti, br * ti + bi * tr        # exp(-2pi i k1 j / n)
    cr, ci = _cdot(br, bi, qr, qi, passes)               # (rows, k1, k2)
    # X[k1 + p*k2] = c[k1, k2]
    return (jnp.swapaxes(cr, 1, 2).reshape(rows, n),
            jnp.swapaxes(ci, 1, 2).reshape(rows, n))


@functools.lru_cache(maxsize=4)
def _tables(n: int) -> tuple:
    """The control's device constants for ``n`` points: a dense DFT matrix,
    or the two factors' matrices and the twiddles between them."""
    if n <= DENSE_MAX:
        return tuple(map(jnp.asarray, _twiddles(n, n, n)))
    p = 1 << ((n.bit_length() - 1) // 2)
    q = n // p
    if p * q != n:
        raise ValueError(f"the four-step control needs a power of two, "
                         f"got n={n}")
    return tuple(map(jnp.asarray, _twiddles(p, p, p) + _twiddles(q, q, q)
                     + _twiddles(p, q, n)))


def lowp_fft(x, *, passes: int = 3):
    """Forward DFT of every row of the complex64 device array ``x`` (2-D),
    with three bf16 passes per matrix product. Returns complex64."""
    xr = jnp.real(x).astype(jnp.float32)
    xi = jnp.imag(x).astype(jnp.float32)
    tables = _tables(x.shape[-1])
    fn = _dense if len(tables) == 2 else _four_step
    yr, yi = fn(xr, xi, *tables, passes=passes)
    return jax.lax.complex(yr, yi)

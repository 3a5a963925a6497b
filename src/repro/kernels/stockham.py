"""Pallas TPU block FFT kernel — two MXU stages per block, VMEM-resident.

TPU adaptation of the paper's threadblock-level FFT (§3.1): a grid tile loads
``bs`` signals HBM->VMEM, transforms them entirely in VMEM, and stores back —
one "transaction" in the paper's vocabulary. Twiddle tables are precomputed
host-side (no in-kernel trigonometry, paper §3.1 "twiddling factor table").

Block layout. A length-``n`` signal is carried as an ``(R, L)`` row-major
block with ``L = min(n, 128)`` on the lanes and ``R = n // L`` on the
sublanes, so a tile of ``bs`` signals is a 2-D ``(bs * R, L)`` array whose
rows are ``(signal, n1)``. The split ``(B, n) -> (B * R, L)`` happens outside
``pallas_call``; no reshape inside the kernel splits or merges the lane
dimension (Mosaic refuses such shape casts). The transform is the
Cooley-Tukey split ``n = R * L`` (input ``n = L*n1 + n2``, output ``k = k1 +
R*k2``), run over 128-row chunks:

1. sublane stage: ``kron(I, W_R) @ chunk`` — the radix-R DFT of every
   signal in the chunk as one (128, 128) matmul;
2. twiddle ``T[k1, n2]`` (elementwise);
3. lane stage: ``chunk @ W_L`` — the radix-L DFT as a 2-D matmul.

The kernel's output rows are ``(signal, k1)`` with ``k2`` on the lanes; the
wrapper's transpose back to natural order fuses with the re/im join.

Complex data is carried as split real/imag float arrays (TPU Pallas has no
complex dtype). Every contraction asks for ``Precision.HIGHEST``: the MXU's
default f32 matmul is a single bf16 pass, too coarse for the FFT's 4e-5
tolerance.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl

from repro.core.fft import factors
from repro.core.fft.plan import LANES, Plan, make_plan

from ._platform import resolve_interpret

__all__ = ["block_fft_pallas", "block_layout", "chunk_rows", "const_specs",
           "layout_consts", "fft_chunk", "for_each_chunk", "mxu_dot",
           "to_blocks", "from_blocks"]

# rows one sublane-stage matmul covers: kron(I, W_R) is (CHUNK_ROWS, CHUNK_ROWS)
CHUNK_ROWS = 128
PRECISION = jax.lax.Precision.HIGHEST


def block_layout(n: int) -> tuple[int, int]:
    """``(R, L)``: a length-n signal as R rows of L = min(n, 128) lanes."""
    lanes = min(n, LANES)
    return n // lanes, lanes


def chunk_rows(rows: int) -> int:
    """Rows per chunk for a tile of ``rows`` rows (a multiple of R, since
    R divides both ``rows`` and CHUNK_ROWS)."""
    return math.gcd(rows, CHUNK_ROWS)


@jax.named_scope("fft.layout")
def to_blocks(x: jax.Array) -> jax.Array:
    """(B, n) -> (B * R, L), rows ``(signal, n1)``."""
    b, n = x.shape
    r, lanes = block_layout(n)
    return x.reshape(b * r, lanes)


@jax.named_scope("fft.layout")
def from_blocks(y: jax.Array, n: int) -> jax.Array:
    """(B * R, L) with rows ``(signal, k1)`` -> (B, n) natural order."""
    r, lanes = block_layout(n)
    b = y.shape[0] // r
    return jnp.swapaxes(y.reshape(b, r, lanes), 1, 2).reshape(b, n)


def layout_consts(n: int, ch: int, dtype=np.float32, *,
                  inverse: bool = False) -> list[np.ndarray]:
    """Host-side tables for one chunk of ``ch`` rows: ``[Kr, Ki, Tr, Ti,
    Wr, Wi]`` (just ``[Wr, Wi]`` when the signal fits the lanes, R == 1).
    ``K = kron(I, W_R)`` is the sublane stage, ``T`` its twiddle tiled to
    the chunk, ``W = W_L`` the lane stage."""
    r, lanes = block_layout(n)
    w = factors.dft_matrix(lanes, inverse=inverse)
    tail = [w.real, w.imag]
    if r > 1:
        k = np.kron(np.eye(ch // r), factors.dft_matrix(r, inverse=inverse))
        t = np.tile(factors.stage_twiddle(r, lanes, inverse=inverse),
                    (ch // r, 1))
        tail = [k.real, k.imag, t.real, t.imag] + tail
    return [np.ascontiguousarray(c, dtype=dtype) for c in tail]


def mxu_dot(a, b):
    """``a @ b`` at full f32 (f64) precision on the MXU."""
    return jnp.dot(a, b, precision=PRECISION, preferred_element_type=a.dtype)


def fft_chunk(xr, xi, consts):
    """Transform one ``(ch, L)`` chunk (values, not refs) with the tables
    from :func:`layout_consts`."""
    if len(consts) == 6:
        kr, ki, tr, ti, wr, wi = consts
        xr, xi = (mxu_dot(kr, xr) - mxu_dot(ki, xi),
                  mxu_dot(kr, xi) + mxu_dot(ki, xr))
        xr, xi = xr * tr - xi * ti, xr * ti + xi * tr
    else:
        wr, wi = consts
    return (mxu_dot(xr, wr) - mxu_dot(xi, wi),
            mxu_dot(xr, wi) + mxu_dot(xi, wr))


def for_each_chunk(rows: int, ch: int, body) -> None:
    """Call ``body(row0)`` for each ``ch``-row chunk of a ``rows``-row tile
    (an int32 loop: the kernels must compile with x64 enabled)."""
    steps = rows // ch
    if steps == 1:
        body(0)
        return

    def step(c, carry):
        body(pl.multiple_of(c * ch, ch))
        return carry

    jax.lax.fori_loop(jnp.int32(0), jnp.int32(steps), step, jnp.int32(0))


def const_specs(consts, **kw):
    """Whole-array BlockSpecs for 2-D constant tables, on any grid (int32
    index maps: the kernels must compile with x64 enabled)."""
    zero = lambda *_: (jnp.int32(0), jnp.int32(0))  # noqa: E731
    return [pl.BlockSpec(c.shape, zero, **kw) for c in consts]


def _fft_kernel(ch, n_const, xr_ref, xi_ref, *rest):
    consts = [c[...] for c in rest[:n_const]]
    yr_ref, yi_ref = rest[n_const:]

    def body(r0):
        rows = pl.ds(r0, ch)
        yr, yi = fft_chunk(xr_ref[rows, :], xi_ref[rows, :], consts)
        yr_ref[rows, :] = yr
        yi_ref[rows, :] = yi

    for_each_chunk(xr_ref.shape[0], ch, body)


@functools.partial(
    jax.jit,
    static_argnames=("plan", "bs", "interpret", "inverse"),
)
def block_fft_pallas(
    xr: jax.Array,
    xi: jax.Array,
    *,
    plan: Plan | None = None,
    bs: int | None = None,
    inverse: bool = False,
    interpret: bool | None = None,
) -> tuple[jax.Array, jax.Array]:
    """Batched single-pass FFT: (B, N) split re/im -> (B, N) split re/im.

    ``B`` must be divisible by the tile size ``bs`` (ops.py pads). N must fit
    a single VMEM pass (``plan.num_passes == 1``); larger sizes are composed
    by ``ops.fft`` at the JAX level (the paper's kernel-level N1xN2xN3).
    ``interpret=None`` compiles on a TPU and interprets elsewhere.
    """
    b, n = xr.shape
    if plan is None:
        plan = make_plan(n, batch=b, itemsize=xr.dtype.itemsize,
                         inverse=inverse)
    if plan.num_passes != 1:
        raise ValueError(
            f"block_fft_pallas is single-pass; got {plan.describe()} — "
            f"compose larger sizes at the JAX level (ops.fft)")
    if bs is None:
        bs = min(plan.bs, b)
    if b % bs != 0:
        raise ValueError(f"batch {b} is not divisible by tile size bs={bs}")

    r, lanes = block_layout(n)
    rows = bs * r
    ch = chunk_rows(rows)
    np_dtype = np.float64 if xr.dtype == jnp.float64 else np.float32
    consts = [jnp.asarray(c) for c in
              layout_consts(n, ch, np_dtype, inverse=inverse)]

    x_spec = pl.BlockSpec((rows, lanes), lambda i: (i, jnp.int32(0)))
    xr, xi = to_blocks(xr), to_blocks(xi)
    with jax.named_scope("fft.kernel"):
        yr, yi = pl.pallas_call(
            functools.partial(_fft_kernel, ch, len(consts)),
            grid=(b // bs,),
            in_specs=[x_spec, x_spec] + const_specs(consts),
            out_specs=[x_spec, x_spec],
            out_shape=[jax.ShapeDtypeStruct((b * r, lanes), xr.dtype)] * 2,
            interpret=resolve_interpret(interpret),
            name="block_fft",
        )(xr, xi, *consts)
    yr, yi = from_blocks(yr, n), from_blocks(yi, n)
    if inverse:
        scale = jnp.asarray(1.0 / n, dtype=xr.dtype)
        yr, yi = yr * scale, yi * scale
    return yr, yi

"""Fused two-side ABFT GEMM Pallas kernel: tiled matmul + in-kernel
checksum strips.

TPU analogue of the paper's fused threadblock ABFT applied to the GEMM view
(§2.2.2): while the MXU computes C = X @ W tile-by-tile, the kernel
accumulates the *output* checksum strips in VMEM scratch —

    out2 = e2^T C   (column sums)          vs  pred2 = (e2^T X) @ W
    out3 = e3^T C   (e3 = [1..M] location) vs  pred3 = (e3^T X) @ W

— where the predicted strips are computed in the same K loop from the tiny
precomputed ``e2^T X`` / ``e3^T X`` vectors, so the two-side scheme adds
zero extra HBM traffic over the matmul itself. The caller decodes ``d2 =
pred2 - out2`` / ``d3 = pred3 - out3`` per column (``d3/d2 = row + 1``) and
corrects in place — :func:`repro.core.abft.gemm.decode_columns`, the same
decode the interpreter path uses, so both backends agree by construction.

An optional in-kernel SEU injector perturbs the computed product *before*
the output strips accumulate (modeling a MAC-unit fault the checksums must
catch, not an HBM corruption they could not).

Grid: (N/bn, M/bm, K/bk) — K innermost (accumulate), M middle (checksum
strips accumulate across M tiles), N outer (strips emitted when their last
(m, k) tile completes).
"""
from __future__ import annotations

import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ._platform import resolve_interpret

__all__ = ["ft_matmul_pallas", "FTMatmulChecks"]


class FTMatmulChecks(NamedTuple):
    """Product + the four fused checksum strips (each ``(N,)`` float32)."""

    c: jax.Array
    out2: jax.Array    # e2^T C   — fused output column sums
    pred2: jax.Array   # (e2^T X) @ W
    out3: jax.Array    # e3^T C   — fused location checksum, e3 = [1..M]
    pred3: jax.Array   # (e3^T X) @ W


def _kernel(nm, nk, bm, bn, nf, x_ref, w_ref, xsum_ref, xloc_ref, inj_ref,
            c_ref, out2_ref, pred2_ref, out3_ref, pred3_ref,
            acc_ref, col_acc, pred2_acc, row_acc, pred3_acc):
    n_i = pl.program_id(0)
    m_i = pl.program_id(1)
    k_i = pl.program_id(2)

    @pl.when((m_i == 0) & (k_i == 0))
    def _init_strip():
        col_acc[...] = jnp.zeros_like(col_acc)
        pred2_acc[...] = jnp.zeros_like(pred2_acc)
        row_acc[...] = jnp.zeros_like(row_acc)
        pred3_acc[...] = jnp.zeros_like(pred3_acc)

    @pl.when(k_i == 0)
    def _init_tile():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    x = x_ref[...]
    w = w_ref[...]
    acc_ref[...] += jnp.dot(x, w, preferred_element_type=jnp.float32)

    # predicted strips: (e2^T X) @ W and (e3^T X) @ W, accumulated once
    # per (n, k) from the precomputed input checksums
    @pl.when(m_i == 0)
    def _pred():
        pred2_acc[...] += (xsum_ref[...] @ w).reshape(pred2_acc.shape)
        pred3_acc[...] += (xloc_ref[...] @ w).reshape(pred3_acc.shape)

    @pl.when(k_i == nk - 1)
    def _emit_tile():
        c = acc_ref[...]
        # in-kernel SEU injection: lands in the computed product BEFORE the
        # output strips accumulate — exactly what the scheme must detect
        # Mosaic builds integer iotas only: index in int32, then cast
        rows = (m_i * bm + jax.lax.broadcasted_iota(
            jnp.int32, (bm, bn), 0)).astype(jnp.float32)
        cols = (n_i * bn + jax.lax.broadcasted_iota(
            jnp.int32, (bm, bn), 1)).astype(jnp.float32)
        inj = inj_ref[...]
        for f in range(nf):
            hit = (rows == inj[f, 0]) & (cols == inj[f, 1])
            c = c + jnp.where(hit, inj[f, 2] * inj[f, 3], 0.0)
        c_ref[...] = c.astype(c_ref.dtype)
        col_acc[...] += jnp.sum(c, axis=0, keepdims=True)
        loc = (m_i * bm + 1 + jax.lax.broadcasted_iota(
            jnp.int32, (bm, 1), 0)).astype(jnp.float32)
        row_acc[...] += jnp.sum(c * loc, axis=0, keepdims=True)

    @pl.when((k_i == nk - 1) & (m_i == nm - 1))
    def _emit_strip():
        out2_ref[...] = col_acc[...]
        pred2_ref[...] = pred2_acc[...]
        out3_ref[...] = row_acc[...]
        pred3_ref[...] = pred3_acc[...]


@functools.partial(jax.jit,
                   static_argnames=("bm", "bn", "bk", "interpret"))
def _ft_matmul_pallas(x, w, inj, *, bm, bn, bk, interpret):
    m, k = x.shape
    _, n = w.shape
    nm, nn, nk = m // bm, n // bn, k // bk
    xf = x.astype(jnp.float32)
    xsum = jnp.sum(xf, axis=0, keepdims=True)                     # e2^T X
    xloc = (jnp.arange(1, m + 1, dtype=jnp.float32)[None] @ xf)   # e3^T X

    grid = (nn, nm, nk)
    kernel = functools.partial(_kernel, nm, nk, bm, bn, inj.shape[0])
    # int32 block indices throughout: the kernel must compile with x64 on
    zero = lambda: jnp.int32(0)  # noqa: E731
    strip = pl.BlockSpec((1, bn), lambda ni, mi, ki: (zero(), ni))
    c, out2, pred2, out3, pred3 = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((bm, bk), lambda ni, mi, ki: (mi, ki)),
            pl.BlockSpec((bk, bn), lambda ni, mi, ki: (ki, ni)),
            pl.BlockSpec((1, bk), lambda ni, mi, ki: (zero(), ki)),
            pl.BlockSpec((1, bk), lambda ni, mi, ki: (zero(), ki)),
            pl.BlockSpec(inj.shape, lambda ni, mi, ki: (zero(), zero())),
        ],
        out_specs=[
            pl.BlockSpec((bm, bn), lambda ni, mi, ki: (mi, ni)),
            strip, strip, strip, strip,
        ],
        out_shape=[
            jax.ShapeDtypeStruct((m, n), x.dtype),
            jax.ShapeDtypeStruct((1, n), jnp.float32),
            jax.ShapeDtypeStruct((1, n), jnp.float32),
            jax.ShapeDtypeStruct((1, n), jnp.float32),
            jax.ShapeDtypeStruct((1, n), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((bm, bn), jnp.float32),
            pltpu.VMEM((1, bn), jnp.float32),
            pltpu.VMEM((1, bn), jnp.float32),
            pltpu.VMEM((1, bn), jnp.float32),
            pltpu.VMEM((1, bn), jnp.float32),
        ],
        interpret=interpret,
    )(x, w, xsum, xloc, inj)
    return FTMatmulChecks(c, out2[0], pred2[0], out3[0], pred3[0])


def ft_matmul_pallas(x, w, *, bm=128, bn=128, bk=128,
                     interpret: bool | None = None,
                     inject: jax.Array | None = None) -> FTMatmulChecks:
    """Fused product + two-side checksum strips (:class:`FTMatmulChecks`).

    Detection/correction at the caller: ``d2 = pred2 - out2`` / ``d3 =
    pred3 - out3`` through :func:`repro.core.abft.gemm.decode_columns`.
    x: (M, K), w: (K, N). Dims must be multiples of the tile sizes (the
    ``core.gemm`` plan layer falls back to the interpreter path otherwise).

    ``interpret=None`` resolves per platform: the compiled Mosaic kernel on
    TPU, the Pallas interpreter elsewhere (CPU CI). ``inject`` is an
    optional ``(4,)`` ``[row, col, enable, eps]`` descriptor — or ``(F, 4)``
    for concurrent SEUs — applied to the computed product inside the kernel.
    """
    m, k = x.shape
    k2, n = w.shape
    if k2 != k:
        raise ValueError(f"contraction mismatch: x (M={m}, K={k}) vs "
                         f"w (K={k2}, N={n})")
    if m % bm or n % bn or k % bk:
        raise ValueError(
            f"fused ABFT GEMM needs tile-aligned dims: (M, K, N)="
            f"({m}, {k}, {n}) vs tiles (bm, bk, bn)=({bm}, {bk}, {bn}) — "
            f"pad the operands or use the interpreter path "
            f"(core.abft.gemm.ft_matmul)")
    if inject is None:
        inj = jnp.zeros((1, 4), jnp.float32)
    else:
        inj = jnp.reshape(jnp.asarray(inject, jnp.float32), (-1, 4))
    return _ft_matmul_pallas(x, w, inj, bm=bm, bn=bn, bk=bk,
                             interpret=resolve_interpret(interpret))

"""Fused two-sided ABFT FFT kernel (paper §4.2-4.3), TPU Pallas.

One kernel instance = one transaction group. The grid is ``(G, T)``: group g
runs T sequential transactions (HBM read -> VMEM FFT -> HBM write), exactly
the paper's multi-transaction threadblock. Checksums are *fused*:

* left side (thread-level analogue): per-signal ``(e1^T W) x_b`` vs
  ``e1^T y_b`` — computed from the VMEM-resident tile, zero extra HBM traffic,
* right side (threadblock/multi-transaction analogue): ``X e2 / X e3`` input
  and output checksums accumulated in VMEM scratch **across grid steps** and
  written once on the last transaction — the reduction cost is amortized 1/T
  with no inter-transaction communication (paper: "each thread exactly maps to
  the same ABFT encoding workload").

An optional in-kernel SEU injector corrupts one output element of one tile —
simulating a transient compute-unit fault *inside* the protected region, so
tests exercise true end-to-end detect->locate->correct.

The tile is the block kernel's ``(bs * R, L)`` layout (kernels/stockham.py):
rows ``(signal, n1)`` on input and ``(signal, k1)`` on output. The group
checksums sum rows with equal ``n1`` (``k1``) as a selection matmul per
chunk; the per-signal sums come out per row and are added over each
signal's R rows outside the kernel.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.abft.encoding import EPS, left_encoding, left_encoding_image
from repro.core.fft.plan import Plan, make_plan

from ._platform import resolve_interpret
from .stockham import (block_layout, chunk_rows, const_specs, fft_chunk,
                       for_each_chunk, from_blocks, layout_consts, mxu_dot,
                       to_blocks)

__all__ = ["abft_fft_pallas"]


def _abft_kernel(r, ch, transactions, per_signal, n_const,
                 xr_ref, xi_ref, inj_i_ref, inj_f_ref, *rest):
    enc = [c[...] for c in rest[:4]] if per_signal else None
    rest = rest[4:] if per_signal else rest
    consts = [c[...] for c in rest[:n_const]]
    yr_ref, yi_ref = rest[n_const:n_const + 2]
    part_ref = rest[n_const + 2] if per_signal else None
    cs_ref, acc_ref = rest[-2:]

    t = pl.program_id(1)
    tile = pl.program_id(0) * transactions + t
    rows, lanes = xr_ref.shape
    ftype = xr_ref.dtype

    @pl.when(t == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    # selection rows for the group checksums: sel[q, j] = 1 where chunk row
    # j holds n1 (or k1) == q; padded to a full sublane tile when R < 8
    rp = acc_ref.shape[1]
    q_io = jax.lax.broadcasted_iota(jnp.int32, (rp, ch), 0)
    j_io = jax.lax.broadcasted_iota(jnp.int32, (rp, ch), 1)
    sel = ((j_io & (r - 1)) == q_io).astype(ftype)
    row_io = jax.lax.broadcasted_iota(jnp.int32, (ch, lanes), 0)
    lane_io = jax.lax.broadcasted_iota(jnp.int32, (ch, lanes), 1)
    shift = r.bit_length() - 1

    def body(r0):
        sl = pl.ds(r0, ch)
        xr = xr_ref[sl, :]
        xi = xi_ref[sl, :]
        yr, yi = fft_chunk(xr, xi, consts)

        # ---- simulated SEU at the compute units (inside the protected
        # region): [row in the whole array, lane, enabled]
        grow = tile * rows + r0
        hit = ((inj_i_ref[0, 2] > 0) & (grow + row_io == inj_i_ref[0, 0])
               & (lane_io == inj_i_ref[0, 1]))
        yr = yr + jnp.where(hit, inj_f_ref[0, 0], 0).astype(ftype)
        yi = yi + jnp.where(hit, inj_f_ref[0, 1], 0).astype(ftype)
        yr_ref[sl, :] = yr
        yi_ref[sl, :] = yi

        # ---- right-side checksums, accumulated across transactions.
        # Location encoding: global 1-based signal id (paper: "each thread
        # aggregates the product of its share and the global ID for the
        # signal").
        gid = (((grow + j_io) >> shift) + 1).astype(ftype)
        selg = sel * gid
        for q, (s, v) in enumerate(((sel, xr), (sel, xi), (selg, xr),
                                    (selg, xi), (sel, yr), (sel, yi),
                                    (selg, yr), (selg, yi))):
            acc_ref[q] += mxu_dot(s, v)

        # ---- left-side per-row sums: s_in = (e1^T W) x, s_out = e1^T y
        if per_signal:
            ewr, ewi, e1r, e1i = enc
            for q, p in enumerate((xr * ewr - xi * ewi, xr * ewi + xi * ewr,
                                   yr * e1r - yi * e1i, yr * e1i + yi * e1r)):
                part_ref[sl, q:q + 1] = jnp.sum(p, axis=1, keepdims=True)

    for_each_chunk(rows, ch, body)

    @pl.when(t == transactions - 1)
    def _emit():
        cs_ref[0] = acc_ref[...]


@functools.partial(
    jax.jit,
    static_argnames=("plan", "bs", "transactions", "per_signal", "encoding",
                     "inverse", "interpret"),
)
def abft_fft_pallas(
    xr: jax.Array,
    xi: jax.Array,
    *,
    plan: Plan | None = None,
    bs: int | None = None,
    transactions: int = 1,
    per_signal: bool = True,
    encoding: str = "wang",
    inverse: bool = False,
    interpret: bool | None = None,
    inject: jax.Array | None = None,
):
    """Fused FT-FFT: returns (yr, yi, delta, cs).

    * ``delta`` — (B,) per-signal left-checksum relative divergence
      (all-zero when ``per_signal=False``, the threadblock-level variant),
    * ``cs`` — (G, 8, N) packed right-side checksums
      [x*e2 r/i, x*e3 r/i, y*e2 r/i, y*e3 r/i] per transaction group,
    * ``inject`` — optional (6,) array [tile, row, col, enabled, eps_r, eps_i]
      (float; integer fields rounded) simulating one SEU.

    ``interpret=None`` compiles on a TPU and interprets elsewhere.
    """
    b, n = xr.shape
    if inverse:
        raise NotImplementedError(
            "ABFT protection covers the forward transform (paper scope); "
            "protect ifft by conjugation: ifft(x) = conj(fft(conj(x)))/n")
    if plan is None:
        plan = make_plan(n, batch=b, itemsize=xr.dtype.itemsize)
    if plan.num_passes != 1:
        raise ValueError(
            f"abft_fft_pallas is single-pass; got {plan.describe()} — "
            f"compose larger sizes at the JAX level (ops.ft_fft)")
    if bs is None:
        bs = min(plan.bs, b)
    if b % bs != 0:
        raise ValueError(f"batch {b} is not divisible by tile size bs={bs}")
    tiles = b // bs
    if tiles % transactions != 0:
        raise ValueError(f"tiles={tiles} (batch {b} / bs={bs}) is not "
                         f"divisible by transactions={transactions}")
    groups = tiles // transactions

    r, lanes = block_layout(n)
    rows = bs * r
    ch = chunk_rows(rows)
    rp = max(r, 8)
    np_dtype = np.float64 if xr.dtype == jnp.float64 else np.float32
    consts = [jnp.asarray(c) for c in layout_consts(n, ch, np_dtype)]
    if per_signal:
        # e1^T W indexes inputs n = L*n1 + n2; e1 indexes outputs
        # k = k1 + R*k2, i.e. the output tile's (k1, k2) layout
        ew = left_encoding_image(n, encoding).reshape(r, lanes)
        e1 = left_encoding(n, encoding).reshape(lanes, r).T
        enc = [jnp.asarray(np.tile(v, (ch // r, 1)).astype(np_dtype))
               for v in (ew.real, ew.imag, e1.real, e1.imag)]
    else:
        enc = []

    if inject is None:
        inject = jnp.full((6,), -1.0, dtype=jnp.float32)
    with jax.named_scope("fft.kernel"):     # the kernel's SMEM operands
        fields = jnp.round(inject[:4]).astype(jnp.int32)
        tile, row, col, enabled = fields[0], fields[1], fields[2], fields[3]
        inj_i = jnp.stack([(tile * bs + row) * r + col % r, col // r,
                           enabled])[None]
        inj_f = inject[4:6].astype(np_dtype)[None]

    x_spec = pl.BlockSpec(
        (rows, lanes), lambda g, t: (g * transactions + t, jnp.int32(0)))
    out_specs = [x_spec, x_spec]
    out_shape = [jax.ShapeDtypeStruct((b * r, lanes), xr.dtype)] * 2
    if per_signal:
        out_specs.append(pl.BlockSpec(
            (rows, 4), lambda g, t: (g * transactions + t, jnp.int32(0))))
        out_shape.append(jax.ShapeDtypeStruct((b * r, 4), xr.dtype))
    out_specs.append(pl.BlockSpec((1, 8, rp, lanes),
                                  lambda g, t: (g,) + (jnp.int32(0),) * 3))
    out_shape.append(jax.ShapeDtypeStruct((groups, 8, rp, lanes), xr.dtype))

    kernel = functools.partial(_abft_kernel, r, ch, transactions, per_signal,
                               len(consts))
    xr, xi = to_blocks(xr), to_blocks(xi)
    with jax.named_scope("fft.kernel"):
        outs = pl.pallas_call(
            kernel,
            grid=(groups, transactions),
            in_specs=([x_spec, x_spec]
                      + const_specs([inj_i, inj_f], memory_space=pltpu.SMEM)
                      + const_specs(enc + consts)),
            out_specs=out_specs,
            out_shape=out_shape,
            scratch_shapes=[pltpu.VMEM((8, rp, lanes), jnp.dtype(np_dtype))],
            interpret=resolve_interpret(interpret),
            name="abft_fft",
        )(xr, xi, inj_i, inj_f, *enc, *consts)
    yr, yi, cs = from_blocks(outs[0], n), from_blocks(outs[1], n), outs[-1]

    with jax.named_scope("abft.decode"):
        # checksums back to natural order: input sums are (n1, n2)
        # row-major, output sums (k1, k2) with k = k1 + R*k2
        cs = cs[:, :, :r]
        cs = jnp.concatenate(
            [cs[:, :4].reshape(groups, 4, n),
             jnp.swapaxes(cs[:, 4:], -1, -2).reshape(groups, 4, n)], axis=1)
        if per_signal:
            s = outs[2].reshape(b, r, 4).sum(axis=1)
            dr, di = s[:, 0] - s[:, 2], s[:, 1] - s[:, 3]
            mag = jnp.sqrt(s[:, 0] ** 2 + s[:, 1] ** 2) + EPS
            delta = jnp.sqrt(dr * dr + di * di) / mag
        else:
            delta = jnp.zeros((b,), xr.dtype)
    return yr, yi, delta, cs

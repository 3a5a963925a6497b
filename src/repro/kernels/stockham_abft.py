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

The tile is laid out as the block kernel's (kernels/stockham.py
:class:`Tiling`), and so are its chunks' rows. The group checksums sum the
rows of equal block (``n1`` on input; on output the natural 128-lane tile,
or ``k1`` on the ``blocks`` path) as a selection matmul per chunk; the
per-signal sums come out per row and are added over each signal's R rows
outside the kernel.
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
from .stockham import Tiling, const_specs, fft_chunk, for_each_chunk, mxu_dot

__all__ = ["abft_fft_pallas"]


def _abft_kernel(tiling, transactions, per_signal, n_const,
                 xr_ref, xi_ref, inj_i_ref, inj_f_ref, *rest):
    enc = [c[...] for c in rest[:4]] if per_signal else None
    rest = rest[4:] if per_signal else rest
    consts = [c[...] for c in rest[:n_const]]
    yr_ref, yi_ref = rest[n_const:n_const + 2]
    part_ref = rest[n_const + 2] if per_signal else None
    cs_ref, acc_ref = rest[-2:]

    t = pl.program_id(1)
    tile = pl.program_id(0) * transactions + t
    ch, lanes = tiling.ch, tiling.lanes
    ftype = xr_ref.dtype

    @pl.when(t == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    # selection rows for the group checksums: sel[q, j] = 1 where chunk row
    # j holds block q; padded to a full sublane tile when R < 8
    rp = acc_ref.shape[1]
    q_io = jax.lax.broadcasted_iota(jnp.int32, (rp, ch), 0)
    j_io = jax.lax.broadcasted_iota(jnp.int32, (rp, ch), 1)
    sel = (tiling.row_block(j_io) == q_io).astype(ftype)
    row_io = jax.lax.broadcasted_iota(jnp.int32, (ch, lanes), 0)
    k_io = tiling.out_index(row_io, jax.lax.broadcasted_iota(
        jnp.int32, (ch, lanes), 1))

    def body(c):
        xr = tiling.load(xr_ref, c)
        xi = tiling.load(xi_ref, c)
        yr, yi = fft_chunk(tiling, xr, xi, consts)
        yr, yi = tiling.arrange(yr), tiling.arrange(yi)

        # ---- simulated SEU at the compute units (inside the protected
        # region): [signal, output index, enabled]
        sig0 = tile * tiling.bs + c * tiling.cs
        hit = ((inj_i_ref[0, 2] > 0)
               & (sig0 + tiling.row_signal(row_io) == inj_i_ref[0, 0])
               & (k_io == inj_i_ref[0, 1]))
        yr = yr + jnp.where(hit, inj_f_ref[0, 0], 0).astype(ftype)
        yi = yi + jnp.where(hit, inj_f_ref[0, 1], 0).astype(ftype)
        tiling.store(yr_ref, c, yr)
        tiling.store(yi_ref, c, yi)

        # ---- right-side checksums, accumulated across transactions.
        # Location encoding: the signal's 1-based id within its group (the
        # paper's global ID less the group's first; the decode adds it
        # back). Ids up to the group size keep the e3 sums' float32
        # rounding, which the location estimate divides by the error, far
        # below half an id; global ids up to 2^16 did not.
        gid = (t * tiling.bs + c * tiling.cs + tiling.row_signal(j_io)
               + 1).astype(ftype)
        selg = sel * gid
        for q, (s, v) in enumerate(((sel, xr), (sel, xi), (selg, xr),
                                    (selg, xi), (sel, yr), (sel, yi),
                                    (selg, yr), (selg, yi))):
            acc_ref[q] += mxu_dot(s, v)

        # ---- left-side per-row sums: s_in = (e1^T W) x, s_out = e1^T y
        if per_signal:
            ewr, ewi, e1r, e1i = enc
            rows = tiling.chunk_rows(c)
            for q, p in enumerate((xr * ewr - xi * ewi, xr * ewi + xi * ewr,
                                   yr * e1r - yi * e1i, yr * e1i + yi * e1r)):
                part_ref[rows, q:q + 1] = jnp.sum(p, axis=1, keepdims=True)

    for_each_chunk(tiling.chunks, body)

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
      (float; integer fields rounded) simulating one SEU at output ``col``
      of signal ``tile * bs + row``.

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

    tiling = Tiling(n, bs)
    r, lanes = tiling.r, tiling.lanes
    rp = max(r, 8)
    np_dtype = np.float64 if xr.dtype == jnp.float64 else np.float32
    consts = [jnp.asarray(c) for c in tiling.consts(np_dtype)]
    if per_signal:
        # e1^T W indexes inputs n = L*n1 + n2; e1 indexes outputs
        ew = left_encoding_image(n, encoding).reshape(r, lanes)
        e1 = tiling.out_table(left_encoding(n, encoding))
        enc = [jnp.asarray(tiling.rows_table(v).astype(np_dtype))
               for v in (ew.real, ew.imag, e1.real, e1.imag)]
    else:
        enc = []

    if inject is None:
        inject = jnp.full((6,), -1.0, dtype=jnp.float32)
    with jax.named_scope("fft.kernel"):     # the kernel's SMEM operands
        fields = jnp.round(inject[:4]).astype(jnp.int32)
        tile, row, col, enabled = fields[0], fields[1], fields[2], fields[3]
        inj_i = jnp.stack([tile * bs + row, col, enabled])[None]
        inj_f = inject[4:6].astype(np_dtype)[None]

    tile_of = lambda g, t: g * transactions + t  # noqa: E731
    x_spec = tiling.spec(tile_of)
    out_specs = [x_spec, x_spec]
    out_shape = [jax.ShapeDtypeStruct(tiling.io_shape(b), xr.dtype)] * 2
    if per_signal:
        out_specs.append(pl.BlockSpec(
            (tiling.bs * r, 4), lambda g, t: (tile_of(g, t), jnp.int32(0))))
        out_shape.append(jax.ShapeDtypeStruct((b * r, 4), xr.dtype))
    out_specs.append(pl.BlockSpec((1, 8, rp, lanes),
                                  lambda g, t: (g,) + (jnp.int32(0),) * 3))
    out_shape.append(jax.ShapeDtypeStruct((groups, 8, rp, lanes), xr.dtype))

    kernel = functools.partial(_abft_kernel, tiling, transactions,
                               per_signal, len(consts))
    xr, xi = tiling.to_io(xr), tiling.to_io(xi)
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
    yr, yi, cs = tiling.from_io(outs[0]), tiling.from_io(outs[1]), outs[-1]

    with jax.named_scope("abft.decode"):
        # checksums in natural order: input sums are (n1, n2) row-major,
        # output sums by the arranged chunk's (block, lane)
        cs = cs[:, :, :r]
        cs = jnp.concatenate([cs[:, :4].reshape(groups, 4, n),
                              tiling.natural_order(cs[:, 4:])], axis=1)
        if per_signal:
            s = tiling.signal_sums(outs[2])
            dr, di = s[:, 0] - s[:, 2], s[:, 1] - s[:, 3]
            mag = jnp.sqrt(s[:, 0] ** 2 + s[:, 1] ** 2) + EPS
            delta = jnp.sqrt(dr * dr + di * di) / mag
        else:
            delta = jnp.zeros((b,), xr.dtype)
    return yr, yi, delta, cs

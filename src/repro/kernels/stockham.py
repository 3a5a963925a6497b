"""Pallas TPU block FFT kernel — two DFT stages per block, VMEM-resident.

TPU adaptation of the paper's threadblock-level FFT (§3.1): a grid tile loads
``bs`` signals HBM->VMEM, transforms them entirely in VMEM, and stores back —
one "transaction" in the paper's vocabulary. Twiddle tables are precomputed
host-side (no in-kernel trigonometry, paper §3.1 "twiddling factor table").

A length-``n`` signal is the Cooley-Tukey split ``n = R * L`` with ``L =
min(n, 128)`` on the lanes and ``R = n // L`` rows: input ``n = L*n1 + n2``,
output ``k = k1 + R*k2``. A tile is transformed in chunks of ``cs`` signals,
``ch = cs * R`` rows (at most 128):

1. sublane stage: the radix-R DFT of every signal in the chunk, in the
   form its row order allows (below);
2. twiddle ``T[k1, n2]`` (elementwise);
3. lane stage: ``chunk @ W_L`` — the radix-L DFT as a 2-D matmul.

:class:`Tiling` holds the two ways a chunk lies in HBM (``plan.io_layout``):

* ``natural`` (R <= 16): the kernel reads and writes the ``(B, n)`` planes.
  A chunk is loaded as rows ``(n1, signal)`` by lane-aligned slices of the
  signals' row tiles: R row tiles of ``cs >= 8`` rows, one per n1. The
  sublane stage combines whole tiles with constant scalars, so it runs on
  the VPU as log2 R radix-2 butterfly stages (:func:`sublane_dft`), not as
  the MXU product ``kron(W_R, I_cs) @ chunk``, 15/16 of whose multiply-adds
  would multiply zeros. The lane stage's columns are permuted so that a
  lane roll and select per row tile and output tile put every ``X[k]`` at
  its natural position before the store.
* ``blocks`` (R = 32, 64, or a tile that is not whole sublane tiles): the
  planes are relaid out to ``(B * R, L)`` rows ``(signal, n1)`` in HBM
  around the kernel (``Tiling.to_io``, ``Tiling.from_io``), and the
  sublane stage is the matmul ``kron(I_cs, W_R) @ chunk``: its DFT mixes
  rows inside a sublane tile, which no elementwise combination of tiles
  can do. ``kron(W_R, I_cs)`` would need chunks of ``8 * R`` rows, at
  several times the sublane stage's cost.

Complex data is carried as split real/imag float arrays (TPU Pallas has no
complex dtype). Every contraction asks for ``Precision.HIGHEST``: the MXU's
default f32 matmul is a single bf16 pass, too coarse for the FFT's 4e-5
tolerance. The butterflies are plain f32 (f64) adds and multiplies.
"""
from __future__ import annotations

import dataclasses
import functools
import math

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.fft import factors
from repro.core.fft.plan import LANES, Plan, io_layout, make_plan

from ._platform import resolve_interpret

__all__ = ["block_fft_pallas", "Tiling", "const_specs", "fft_chunk",
           "for_each_chunk", "mxu_dot", "sublane_dft"]

# rows a chunk holds at most: the M of its matmuls
CHUNK_ROWS = 128
PRECISION = jax.lax.Precision.HIGHEST


def _chunk_offset(c, size):
    return c * size if isinstance(c, int) else pl.multiple_of(c * size, size)


@dataclasses.dataclass(frozen=True)
class Tiling:
    """Where each element of a tile of ``bs`` length-``n`` signals lies:
    in HBM, in a chunk's rows, and in the output."""

    n: int
    bs: int

    @property
    def lanes(self) -> int:
        return min(self.n, LANES)

    @property
    def r(self) -> int:
        return self.n // self.lanes

    @property
    def natural(self) -> bool:
        return io_layout(self.n, self.bs) == "natural"

    @property
    def cs(self) -> int:
        """Signals per chunk."""
        return math.gcd(self.bs, CHUNK_ROWS // self.r)

    @property
    def ch(self) -> int:
        """Rows per chunk."""
        return self.cs * self.r

    @property
    def chunks(self) -> int:
        return self.bs // self.cs

    # -- HBM --------------------------------------------------------------

    def io_shape(self, b: int) -> tuple[int, int]:
        """The kernel's operand shape for ``b`` signals."""
        return (b, self.n) if self.natural else (b * self.r, self.lanes)

    def spec(self, tile) -> pl.BlockSpec:
        """The tile's BlockSpec; ``tile`` maps grid indices to a tile."""
        return pl.BlockSpec(self.io_shape(self.bs),
                            lambda *g: (tile(*g), jnp.int32(0)))

    def to_io(self, x: jax.Array) -> jax.Array:
        """(B, n) planes as the kernel reads them; the blocks path's rows
        are ``(signal, n1)``."""
        if self.natural:
            return x
        with jax.named_scope("fft.layout"):
            return x.reshape(-1, self.lanes)

    def from_io(self, y: jax.Array) -> jax.Array:
        """The kernel's output as (B, n) planes; the blocks path's rows are
        ``(signal, k1)``."""
        if self.natural:
            return y
        with jax.named_scope("fft.layout"):
            return self.natural_order(y.reshape(-1, self.r, self.lanes))

    # -- a chunk's rows ---------------------------------------------------
    # rows are (signal, block) on the blocks path and (block, signal) on
    # the natural path; a block is n1 on input and k1 (blocks) or the
    # output's 128-lane tile (natural) after arrange

    def row_signal(self, row):
        if self.natural:
            return row & (self.cs - 1)
        return row >> (self.r.bit_length() - 1)

    def row_block(self, row):
        if self.natural:
            return row >> (self.cs.bit_length() - 1)
        return row & (self.r - 1)

    def out_index(self, row, lane):
        """The output index k of an arranged chunk's element."""
        if self.natural:
            return self.row_block(row) * self.lanes + lane
        return self.row_block(row) + self.r * lane

    def rows_table(self, v: np.ndarray) -> np.ndarray:
        """An ``(R, L)`` table by (block, lane), repeated over a chunk's
        signals in its row order."""
        if self.natural:
            return np.repeat(v, self.cs, 0)
        return np.tile(v, (self.cs, 1))

    def out_table(self, v: np.ndarray) -> np.ndarray:
        """A natural-order length-n vector as an ``(R, L)`` table by the
        arranged chunk's (block, lane)."""
        if self.natural:
            return v.reshape(self.r, self.lanes)
        return v.reshape(self.lanes, self.r).T

    def chunk_rows(self, c):
        """Chunk ``c``'s rows of a ``(bs * R, ·)`` tile."""
        return pl.ds(_chunk_offset(c, self.ch), self.ch)

    def load(self, ref, c):
        """Chunk ``c`` of a tile, ``(ch, L)``."""
        if not self.natural:
            return ref[self.chunk_rows(c), :]
        rows = pl.ds(_chunk_offset(c, self.cs), self.cs)
        parts = [ref[rows, pl.ds(j * self.lanes, self.lanes)]
                 for j in range(self.r)]
        return parts[0] if self.r == 1 else jnp.concatenate(parts, 0)

    def store(self, ref, c, o) -> None:
        if not self.natural:
            ref[self.chunk_rows(c), :] = o
            return
        rows = pl.ds(_chunk_offset(c, self.cs), self.cs)
        for j in range(self.r):
            ref[rows, pl.ds(j * self.lanes, self.lanes)] = \
                o[j * self.cs:(j + 1) * self.cs]

    def arrange(self, z):
        """The lane stage's result in store order. Natural path: row tile
        k1 holds ``X[k1 + R*k2]`` at lane ``R*m + j`` for ``k2 = (L/R)*j +
        m``, so output tile j's lane ``R*m + k1`` is row tile k1 rolled by
        ``k1 - j`` lanes: ``X[L*j + R*m + k1]``."""
        r, cs, lanes = self.r, self.cs, self.lanes
        if not self.natural or r == 1:
            return z
        tiles = [z[k1 * cs:(k1 + 1) * cs] for k1 in range(r)]
        which = jax.lax.broadcasted_iota(jnp.int32, (cs, lanes), 1) & (r - 1)
        out = []
        for j in range(r):
            o = tiles[j]
            for k1 in range(r):
                if k1 != j:
                    rolled = pltpu.roll(tiles[k1], jnp.int32((k1 - j) % lanes),
                                        1)
                    o = jnp.where(which == k1, rolled, o)
            out.append(o)
        return jnp.concatenate(out, 0)

    def natural_order(self, a):
        """``(..., R, L)`` by the arranged chunk's (block, lane) -> ``(...,
        n)`` in output order."""
        if not self.natural:
            a = jnp.swapaxes(a, -1, -2)
        return a.reshape(a.shape[:-2] + (self.n,))

    def signal_sums(self, part):
        """``(B * R, m)`` values by chunk row -> ``(B, m)`` sums by
        signal."""
        b, m = part.shape[0] // self.r, part.shape[1]
        if self.natural:
            part = part.reshape(b // self.cs, self.r, self.cs, m)
            return part.sum(axis=1).reshape(b, m)
        return part.reshape(b, self.r, m).sum(axis=1)

    def consts(self, dtype=np.float32, *, inverse: bool = False
               ) -> list[np.ndarray]:
        """Host-side tables for one chunk: ``[Kr, Ki, Tr, Ti, Wr, Wi]`` on
        the blocks path, ``[Tr, Ti, Wr, Wi]`` on the natural path (its
        sublane stage is butterflies, no table) and ``[Wr, Wi]`` when the
        signal fits the lanes (R == 1): ``K`` the sublane stage, ``T`` its
        twiddle in the chunk's rows, ``W`` the lane stage."""
        r, lanes, cs = self.r, self.lanes, self.cs
        w = factors.dft_matrix(lanes, inverse=inverse)
        if self.natural:
            lane = np.arange(lanes)
            w = w[:, (lanes // r) * (lane % r) + lane // r]
        tail = [w.real, w.imag]
        if r > 1:
            t = self.rows_table(factors.stage_twiddle(r, lanes,
                                                      inverse=inverse))
            tail = [t.real, t.imag] + tail
            if not self.natural:
                k = np.kron(np.eye(cs), factors.dft_matrix(r, inverse=inverse))
                tail = [k.real, k.imag] + tail
        return [np.ascontiguousarray(c, dtype=dtype) for c in tail]


def mxu_dot(a, b):
    """``a @ b`` at full f32 (f64) precision on the MXU."""
    return jnp.dot(a, b, precision=PRECISION, preferred_element_type=a.dtype)


def _rotate(ar, ai, k, r, sign):
    """``(ar + i*ai) * exp(sign * 2*pi*i * k / r)``. The twiddle is a Python
    float: under x64 a NumPy float64 would promote f32 tiles to f64."""
    if k == 0:
        return ar, ai
    if 4 * k == r:                          # exp(sign * i*pi/2) = sign * i
        return (-ai, ar) if sign > 0 else (ai, -ar)
    ang = sign * 2.0 * math.pi * k / r
    c, s = math.cos(ang), math.sin(ang)
    return ar * c - ai * s, ar * s + ai * c


def _butterflies(tr, ti, sign):
    """The DFT over lists of equal tiles, radix-2 decimation in time: the
    outputs come back as a list in natural order."""
    r = len(tr)
    if r == 1:
        return tr, ti
    er, ei = _butterflies(tr[0::2], ti[0::2], sign)
    odd_r, odd_i = _butterflies(tr[1::2], ti[1::2], sign)
    h = r // 2
    yr, yi = [None] * r, [None] * r
    for k in range(h):
        ar, ai = _rotate(odd_r[k], odd_i[k], k, r, sign)
        yr[k], yi[k] = er[k] + ar, ei[k] + ai
        yr[k + h], yi[k + h] = er[k] - ar, ei[k] - ai
    return yr, yi


def sublane_dft(xr, xi, r: int, *, inverse: bool = False):
    """The natural path's sublane stage, ``kron(W_r, I_cs) @ x`` for a
    ``(r * cs, L)`` chunk of rows ``(n1, signal)``: log2 r radix-2 stages of
    elementwise butterflies over the chunk's r row tiles, on the VPU."""
    cs = xr.shape[0] // r
    tiles = lambda x: [x[j * cs:(j + 1) * cs] for j in range(r)]  # noqa: E731
    yr, yi = _butterflies(tiles(xr), tiles(xi), 1.0 if inverse else -1.0)
    return jnp.concatenate(yr, 0), jnp.concatenate(yi, 0)


def fft_chunk(tiling, xr, xi, consts, *, inverse: bool = False):
    """Transform one ``(ch, L)`` chunk (values, not refs) with the tables
    from :meth:`Tiling.consts`. The sublane stage follows the tiling's
    path: butterflies over row tiles on the natural path, the ``K`` matmul
    on the blocks path; the twiddle and lane stage are the same on both."""
    if tiling.r == 1:
        wr, wi = consts
    else:
        if tiling.natural:
            tr, ti, wr, wi = consts
            xr, xi = sublane_dft(xr, xi, tiling.r, inverse=inverse)
        else:
            kr, ki, tr, ti, wr, wi = consts
            xr, xi = (mxu_dot(kr, xr) - mxu_dot(ki, xi),
                      mxu_dot(kr, xi) + mxu_dot(ki, xr))
        xr, xi = xr * tr - xi * ti, xr * ti + xi * tr
    return (mxu_dot(xr, wr) - mxu_dot(xi, wi),
            mxu_dot(xr, wi) + mxu_dot(xi, wr))


def for_each_chunk(steps: int, body) -> None:
    """Call ``body(c)`` for each chunk ``c < steps`` of a tile (an int32
    loop: the kernels must compile with x64 enabled), two chunks a loop
    step where the count is even: a step is one basic block, so the second
    chunk's matmuls can overlap the first chunk's lane rolls and selects
    (``Tiling.arrange``), which come after its last matmul."""
    if steps == 1:
        body(0)
        return
    per_step = 2 if steps % 2 == 0 else 1

    def step(c, carry):
        for u in range(per_step):
            body(c * per_step + u)
        return carry

    jax.lax.fori_loop(jnp.int32(0), jnp.int32(steps // per_step), step,
                      jnp.int32(0))


def const_specs(consts, **kw):
    """Whole-array BlockSpecs for 2-D constant tables, on any grid (int32
    index maps: the kernels must compile with x64 enabled)."""
    zero = lambda *_: (jnp.int32(0), jnp.int32(0))  # noqa: E731
    return [pl.BlockSpec(c.shape, zero, **kw) for c in consts]


def _fft_kernel(tiling, n_const, inverse, xr_ref, xi_ref, *rest):
    consts = [c[...] for c in rest[:n_const]]
    yr_ref, yi_ref = rest[n_const:]

    def body(c):
        yr, yi = fft_chunk(tiling, tiling.load(xr_ref, c),
                           tiling.load(xi_ref, c), consts, inverse=inverse)
        tiling.store(yr_ref, c, tiling.arrange(yr))
        tiling.store(yi_ref, c, tiling.arrange(yi))

    for_each_chunk(tiling.chunks, body)


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

    tiling = Tiling(n, bs)
    np_dtype = np.float64 if xr.dtype == jnp.float64 else np.float32
    consts = [jnp.asarray(c) for c in tiling.consts(np_dtype,
                                                    inverse=inverse)]

    x_spec = tiling.spec(lambda i: i)
    xr, xi = tiling.to_io(xr), tiling.to_io(xi)
    with jax.named_scope("fft.kernel"):
        yr, yi = pl.pallas_call(
            functools.partial(_fft_kernel, tiling, len(consts), inverse),
            grid=(b // bs,),
            in_specs=[x_spec, x_spec] + const_specs(consts),
            out_specs=[x_spec, x_spec],
            out_shape=[jax.ShapeDtypeStruct(tiling.io_shape(b),
                                            xr.dtype)] * 2,
            interpret=resolve_interpret(interpret),
            name="block_fft",
        )(xr, xi, *consts)
    yr, yi = tiling.from_io(yr), tiling.from_io(yi)
    if inverse:
        scale = jnp.asarray(1.0 / n, dtype=xr.dtype)
        yr, yi = yr * scale, yi * scale
    return yr, yi

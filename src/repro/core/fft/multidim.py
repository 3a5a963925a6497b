"""Distributed multi-dimensional FFT: slab + pencil fft2/fftn on a mesh.

The 1-D stack (``distributed.py``) scales a single transform axis; real
workloads (2-D/3-D convolution, imaging, PDE spectral solvers) transform
grids. "Coded FFT and Its Communication Overhead" (Jeong et al.) shows the
*decomposition* choice dominates the communication cost of multi-dim FFT, so
this module offers both classical layouts and a model-driven chooser:

**Slab** (block decomposition — small meshes, cheapest collectives).
The first transform axis block-shards over the ``fft`` mesh axis; every
other transform axis is resident, so the transform is

    local FFT over trailing axes -> ONE all-to-all (the inter-axis
    transpose: split last axis, gather first) -> local FFT over the first

— exactly one all-to-all per transform regardless of rank, and because the
sharding lands on a *true array axis* (not a digit), the natural-order
result is free: output sharded over the last transform axis, zero
all-gathers. Batch dims shard over ``data``. Feasible while the first and
last transform axes both divide by the ``fft``-axis size.

**Pencil** (digit decomposition — large meshes, one transform over the
whole 2-D mesh). The last two transform axes each run the existing 1-D
:class:`~repro.core.fft.distributed.DistPlan` pencil pipeline — the last
axis over ``fft``, the second-to-last over ``data`` — so a SINGLE transform
scales over ``data * fft`` devices (slab caps at ``fft`` alone, and needs a
batch to keep ``data`` busy). Two all-to-alls (one per mesh axis), each
confined to its own axis. The output keeps both distributed axes in the
1-D pipeline's transposed digit order; ``natural_order=True`` pays the
digit restore (all-gathers, like the 1-D natural path), which is why the
spectral consumer (:func:`fft_convolve2`) never asks for it.

:func:`choose_decomp` picks between them by evaluating the extended
communication model :func:`collective_volume_nd` (asserted model == HLO by
``benchmarks/fft_distributed.py``) over the feasible candidates — slab wins
whenever a batch keeps the data axis busy (one all-to-all vs two), pencil
wins when a single large grid must use the whole mesh.

**Grouped two-side ABFT** (:func:`ft_distributed_fft2`) composes the PR-3
grouped multi-transaction scheme with the slab row pass: per checksum
group, two right-side checksum *grids* (``cs2 = sum_b x_b``,
``cs3 = sum_b id_b x_b`` — linearity makes them signals) ride the
inter-axis transpose as extra batch rows, and the verdict is ONE psum of 3
scalars per group plus a shared energy scalar, confined to the ``fft``
axis. One SEU per group per pass is detected, located (to a signal), and
corrected elementwise; batch rows shard over ``data`` with no batch
all-gather (HLO-verified).
"""
from __future__ import annotations

import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, PartitionSpec as P
from jax import shard_map

from . import factors
from .distributed import (_AUTO, EPS, FFT_AXIS, DistFFTResult,
                          _grouped_verdict, _local_fft, _resolve_data_axis,
                          _resolve_mesh, _splice_recomputed, make_dist_plan,
                          resolve_abft_groups, resolve_chunks)
from .extensions import pack_half, unpack_half
from .stockham import naive_dft

__all__ = [
    "DECOMP_SLAB", "DECOMP_PENCIL", "choose_decomp", "collective_volume_nd",
    "distributed_fft2", "distributed_ifft2", "distributed_fftn",
    "distributed_ifftn", "ft_distributed_fft2", "fft_convolve2",
    "rslab_feasible", "distributed_rfft2", "distributed_irfft2",
    "ft_distributed_rfft2",
]

DECOMP_SLAB = "slab"
DECOMP_PENCIL = "pencil"
_DECOMPS = (DECOMP_SLAB, DECOMP_PENCIL)


def _is_pow2(n: int) -> bool:
    return n > 0 and not (n & (n - 1))


def _local_axis_fft(z: jax.Array, axis: int, *, inverse: bool) -> jax.Array:
    """Unnormalized local FFT over one axis (any position, any size).

    Power-of-two lengths run the Stockham stages; anything else falls back
    to the O(n^2) direct DFT — the local fallback that lets ``fft2`` accept
    odd grid sizes (the distributed paths stay power-of-two, like the 1-D
    pipeline).
    """
    z = jnp.moveaxis(z, axis, -1)
    if _is_pow2(z.shape[-1]):
        z = _local_fft(z, inverse)
    else:
        z = naive_dft(z, inverse=inverse)
        if inverse:          # _local_axis_fft is unnormalized by contract
            z = z * z.shape[-1]
    return jnp.moveaxis(z, -1, axis)


def _local_fftn(x: jax.Array, ndim: int, *, inverse: bool,
                interpret=None) -> jax.Array:
    """Local n-D transform over the last ``ndim`` axes (numpy conventions).

    ``interpret`` (True/False) routes power-of-two axes through the Pallas
    block kernel (``kernels.ops``); ``None`` keeps the Stockham graph path
    — the efficient choice on CPU hosts and inside larger jitted programs.
    """
    scale = 1
    if interpret is not None:
        from repro.kernels.ops import _fft_impl  # lazy: ops imports core.fft

    for ax in range(-ndim, 0):
        if interpret is not None and _is_pow2(x.shape[ax]):
            z = jnp.moveaxis(x, ax, -1)
            z = _fft_impl(z, inverse=inverse, interpret=interpret)
            if inverse:      # _fft_impl normalizes; undo, normalize once
                z = z * z.shape[-1]
            x = jnp.moveaxis(z, -1, ax)
        else:
            x = _local_axis_fft(x, ax, inverse=inverse)
        scale *= x.shape[ax]
    return x / scale if inverse else x


# ---------------------------------------------------------------------------
# decomposition choice + communication model
# ---------------------------------------------------------------------------


def slab_feasible(shape: tuple[int, ...], fft_shards: int) -> bool:
    """Slab shards ``shape[0]`` and all-to-alls ``shape[-1]``: both must
    divide by the fft-axis size (power-of-two axes, like the 1-D stack)."""
    return (len(shape) >= 2 and all(_is_pow2(s) for s in shape)
            and shape[0] % fft_shards == 0 and shape[-1] % fft_shards == 0)


def rslab_feasible(shape: tuple[int, ...], fft_shards: int) -> bool:
    """Real-input slab feasibility: a 2-D power-of-two grid whose rows AND
    packed half width both tile over the fft axis — ``D | R`` for the input
    sharding and ``D | C/2`` so the padded half spectrum ``Cp = C/2 + D``
    stays shard-divisible through the inter-axis transpose (which needs
    ``C >= 2*D``). Rank-3 real grids are not supported."""
    return (len(shape) == 2 and all(_is_pow2(s) for s in shape)
            and shape[-1] >= 2 and shape[0] % fft_shards == 0
            and (shape[-1] // 2) % fft_shards == 0)


def pencil_feasible(shape: tuple[int, ...], fft_shards: int,
                    data_shards: int = 1) -> bool:
    """Pencil digit-splits the last axis over ``fft`` and the second-to-last
    over ``data``: each needs the 1-D DistPlan constraint N >= shards^2."""
    if len(shape) < 2 or not all(_is_pow2(s) for s in shape):
        return False
    if not _is_pow2(fft_shards) or not _is_pow2(data_shards):
        return False
    return (shape[-1] >= fft_shards * fft_shards
            and shape[-2] >= data_shards * data_shards)


def collective_volume_nd(shape: tuple[int, ...], batch: int, fft_shards: int,
                         *, decomp: str = DECOMP_SLAB, itemsize: int = 8,
                         ft: bool = False, groups: int = 1,
                         data_shards: int = 1, natural_order: bool = True,
                         real: bool = False, chunks: int = 1) -> dict:
    """Analytic per-device communication model of one distributed n-D
    transform over ``shape`` (cross-checked against the post-partitioning
    HLO by ``benchmarks/fft_distributed.py``).

    **slab**: ONE all-to-all over the locally-resident block — ``rows *
    grid/D`` elements, ``rows = (batch + 2*groups if ft)/data_shards``
    (batch and its checksum grids shard over ``data``; the 2 checksum grids
    per group are the ABFT's only volume, ``2*groups/batch`` relative).
    Natural order is FREE (the output sharding lands on the last transform
    axis — no digit restore, zero all-gathers), so ``natural_order`` does
    not change the slab model. The grouped verdict psum is identical to
    the 1-D model: ``3*groups/data_shards + 1`` verdict scalars plus the
    ``5*groups/data_shards``-real replicated-stats broadcast, at ring
    factor 2.

    **pencil**: TWO all-to-alls (one per mesh axis; one when
    ``data_shards == 1``), each moving the full local block — ``batch *
    grid/(D*data)`` elements (the batch is *replicated*: pencil spends the
    data axis on the second transform axis). ``natural_order=True`` adds
    the digit restore, which GSPMD lowers to one all-gather per mesh axis:
    ``full/data_shards`` (fft gathered first) then ``full`` bytes, where
    ``full = batch * grid * itemsize``. ABFT composes with the slab
    transpose only — ``ft=True`` raises here.

    **real** (``real=True``, slab only): the transpose moves the PADDED
    half spectrum — ``Cp = C/2 + D`` columns instead of C — so every slab
    a2a/local term scales by ``(C/2 + D)/C`` (about 0.5 + D/C, the ~2x
    byte win of :func:`distributed_rfft2`); checksum grids ride at the
    same half width, and the verdict psum is unchanged. The pencil real
    path is a composition of two 1-D transforms with no closed-form nd
    model here, so ``real=True`` with ``decomp='pencil'`` raises.

    ``chunks > 1`` (pencil only — the slab pipeline is bulk-synchronous)
    models the multi-transaction pencil: each digit pass splits into
    ``chunks`` all-to-alls of ``1/chunks`` the bytes, total volume
    unchanged, with ``exposed_fraction = 1/chunks`` of the collective
    latency left unhidden (chunk i's transfer overlaps chunk i+1's local
    digit FFTs) and ``overlap_efficiency = 1 - 1/chunks``.

    ``*_wire`` entries are link-crossing bytes; ``hlo_bytes`` matches
    ``repro.analysis.hlo.summarize(parse_collectives(...))`` on the same
    program.
    """
    if decomp not in _DECOMPS:
        raise ValueError(f"decomp must be {'|'.join(_DECOMPS)}, got {decomp!r}")
    chunks = max(1, int(chunks))
    if chunks > 1 and decomp != DECOMP_PENCIL:
        raise ValueError(
            "chunked (multi-transaction) execution rides the pencil digit "
            "passes; the slab inter-axis transpose is bulk-synchronous — "
            f"got decomp={decomp!r} with chunks={chunks}")
    if real and decomp != DECOMP_SLAB:
        raise ValueError(
            "the real-input model is slab-only (rfft2 rides the padded "
            "half-spectrum transpose); the pencil real path composes two "
            "1-D transforms — model each with collective_volume(real=True)")
    cols = shape[-1] // 2 + fft_shards if real else shape[-1]
    grid = int(np.prod(shape[:-1])) * cols
    d = fft_shards
    dd = data_shards
    if decomp == DECOMP_SLAB:
        if ft and groups % dd:
            raise ValueError(f"groups={groups} must divide over "
                             f"data_shards={dd}")
        rows = (batch + (2 * groups if ft else 0)) / dd
        a2a_hlo = rows * grid * itemsize / d
        a2a_wire = a2a_hlo * (d - 1) / d
        # per-group verdict scalars + one energy scalar, plus the stats
        # extraction: ONE stacked (G/dd, 5)-real block broadcast, G = 1
        # included — same structure the 1-D model counts; the plan
        # auditor's per-kind psum diff pinned both terms down here too
        verdict = (3 * groups // dd + 1) * (itemsize // 2)
        stats = 5 * groups // dd * (itemsize // 2)
        psum_hlo = 2.0 * (verdict + stats) if ft else 0.0
        psum_wire = psum_hlo * (d - 1) / d
        gather_hlo = gather_wire = 0.0
        a2a_count, gather_count = 1, 0
        local_bytes = rows * grid * itemsize / d
    else:
        if ft:
            raise ValueError("grouped ABFT rides the slab inter-axis "
                             "transpose; decomp='pencil' has no ft model")
        local = batch * grid * itemsize / (d * dd)
        a2a_count = (2 if dd > 1 else 1) * chunks
        a2a_hlo = (2 if dd > 1 else 1) * local
        # the two all-to-alls live on different axes with different fanouts
        a2a_wire = local * (d - 1) / d
        if dd > 1:
            a2a_wire += local * (dd - 1) / dd
        psum_hlo = psum_wire = 0.0
        full = float(batch * grid * itemsize)
        if natural_order:
            gather_hlo = full + (full / dd if dd > 1 else 0.0)
            gather_wire = full * (d - 1) / d if dd == 1 else (
                (full / dd) * (d - 1) / d + full * (dd - 1) / dd)
            gather_count = 2 if dd > 1 else 1
        else:
            gather_hlo = gather_wire = 0.0
            gather_count = 0
        local_bytes = local
    return {
        "decomp": decomp,
        "shape": tuple(shape),
        "shards": d,
        "data_shards": dd,
        "groups": groups,
        "real": real,
        "chunks": chunks,
        "exposed_fraction": 1.0 / chunks,
        "overlap_efficiency": 1.0 - 1.0 / chunks,
        "all_to_all_count": a2a_count,
        "all_gather_count": gather_count,
        "all_to_all_bytes": a2a_hlo,
        "all_to_all_wire": a2a_wire,
        "gather_hlo": gather_hlo,
        "gather_wire": gather_wire,
        "psum_hlo": psum_hlo,
        "psum_wire": psum_wire,
        "total_wire": a2a_wire + gather_wire + psum_wire,
        "hlo_bytes": a2a_hlo + gather_hlo + psum_hlo,
        "local_bytes": local_bytes,
        "abft_overhead": 2.0 * groups / batch if (ft and batch) else 0.0,
    }


def choose_decomp(shape: tuple[int, ...], mesh: Mesh | None, *,
                  batch: int = 1, ft: bool = False,
                  natural_order: bool = True, axis: str = FFT_AXIS,
                  data_axis: str | None = _AUTO) -> str:
    """Pick the decomposition for an n-D transform over ``shape`` on
    ``mesh`` — ``"slab"``, ``"pencil"``, or ``"local"``.

    Driven by :func:`collective_volume_nd`: among the feasible candidates
    the one moving fewer modeled bytes wins. In practice slab wins whenever
    the batch can keep the ``data`` axis busy (one all-to-all vs two of
    the same size), and pencil wins when one large grid must scale over
    the whole 2-D mesh (slab would leave ``data`` idle, paying ``dd`` times
    the per-device volume). ABFT (``ft=True``) rides the slab transpose,
    so it forces slab.
    """
    shape = tuple(int(s) for s in shape)
    mesh = _resolve_mesh(mesh, axis)
    if mesh is None or mesh.shape[axis] == 1:
        return "local"
    d = mesh.shape[axis]
    daxis = _resolve_data_axis(mesh, data_axis)
    dd = mesh.shape[daxis] if daxis else 1
    cands = []
    if slab_feasible(shape, d):
        # batch shards over data only when it divides (else it replicates
        # and the data axis buys slab nothing)
        bdd = dd if (dd > 1 and batch % dd == 0) else 1
        g = 1 if not ft else max(bdd, 1)
        cands.append((DECOMP_SLAB, collective_volume_nd(
            shape, batch, d, data_shards=bdd, ft=ft, groups=g,
            natural_order=natural_order)))
    if not ft and pencil_feasible(shape, d, dd):
        cands.append((DECOMP_PENCIL, collective_volume_nd(
            shape, batch, d, decomp=DECOMP_PENCIL, data_shards=dd,
            natural_order=natural_order)))
    if not cands:
        raise ValueError(
            f"no feasible decomposition for shape={shape} on a "
            f"{d}-way fft axis (data={dd}): slab needs fft | shape[0] and "
            f"fft | shape[-1]; pencil needs shape[-1] >= fft^2 and "
            f"shape[-2] >= data^2 (power-of-two axes throughout)")
    # fewer modeled collective bytes wins; per-device footprint breaks the
    # tie (a batch-of-one slab leaves the data axis idle, so at equal
    # volume the pencil's smaller resident block carries the day)
    cands.sort(key=lambda c: (c[1]["hlo_bytes"], c[1]["local_bytes"]))
    return cands[0][0]


# ---------------------------------------------------------------------------
# slab pipeline
# ---------------------------------------------------------------------------


@functools.lru_cache(maxsize=None)
def _slab_fftn_fn(mesh: Mesh, axis: str, ndim: int, inverse: bool,
                  data_axis: str | None = None):
    """Jitted slab pipeline for one (mesh, rank, direction).

    Forward: input sharded over the FIRST transform axis -> local FFT over
    the trailing axes -> one all-to-all (split last, gather first) -> local
    FFT over the first -> output sharded over the LAST transform axis.
    Inverse runs the same dataflow mirrored (input sharded over the last
    axis, output over the first), so ``ifftn(fftn(x))`` round-trips with no
    relayout between the two calls.
    """
    dsize = mesh.shape[data_axis] if data_axis else 1

    @jax.jit
    def run(x):  # x: (..., s0, ..., s_{nd-1}) complex
        shape = x.shape
        tshape = shape[-ndim:]
        z = x.reshape((-1,) + tshape)
        b = z.shape[0]
        bspec = data_axis if (data_axis and b % dsize == 0) else None
        first, last = 1, ndim   # transform-axis positions in the (B, ...) cube

        def body(zl):
            if inverse:
                # input sharded over the last axis: every other axis resident
                for ax in range(first, last):
                    zl = _local_axis_fft(zl, ax, inverse=True)
                zl = jax.lax.all_to_all(zl, axis, split_axis=first,
                                        concat_axis=last, tiled=True)
                zl = _local_axis_fft(zl, last, inverse=True)
                return zl / int(np.prod(tshape))
            # forward: input sharded over the first axis
            for ax in range(first + 1, last + 1):
                zl = _local_axis_fft(zl, ax, inverse=False)
            zl = jax.lax.all_to_all(zl, axis, split_axis=last,
                                    concat_axis=first, tiled=True)
            return _local_axis_fft(zl, first, inverse=False)

        shard_pos = (last if inverse else first, first if inverse else last)
        in_spec = [bspec] + [None] * ndim
        out_spec = [bspec] + [None] * ndim
        in_spec[shard_pos[0]] = axis
        out_spec[shard_pos[1]] = axis
        out = shard_map(body, mesh=mesh, in_specs=P(*in_spec),
                        out_specs=P(*out_spec), check_vma=False)(z)
        return out.reshape(shape)

    return run


# ---------------------------------------------------------------------------
# pencil pipeline
# ---------------------------------------------------------------------------


def _chunk_apply(zl, fn, chunks: int, caxes):
    """Run ``fn`` over ``zl`` in ``chunks`` transactions split along the
    first axis in ``caxes`` that can carry them (all candidates are
    unsharded and ``fn`` is independent along each, so contiguous chunks
    concatenate back bitwise-identically). Falls through to one bulk call
    when no axis divides."""
    for ca in caxes:
        ce = resolve_chunks(zl.shape[ca], chunks)
        if ce > 1:
            parts = jnp.split(zl, ce, axis=ca)
            return jnp.concatenate([fn(p) for p in parts], axis=ca)
    return fn(zl)


@functools.lru_cache(maxsize=None)
def _pencil_fftn_fn(mesh: Mesh, axis: str, ndim: int, inverse: bool,
                    natural_order: bool, data_axis: str | None = None,
                    chunks: int = 1):
    """Jitted pencil pipeline: the last two transform axes each run the 1-D
    DistPlan digit decomposition — last over ``axis`` (fft), second-to-last
    over ``data_axis`` — leading transform axes stay local. The cube layout
    is ``(B, lead..., r1, r2, c1, c2)``; forward output holds both
    distributed axes in transposed digit order (``k1`` sharded), and the
    inverse consumes exactly that order (TRANSPOSED_IN), so the round trip
    never redistributes. ``natural_order=True`` adds the digit restore
    outside the shard_map (GSPMD lowers it to one all-gather per mesh
    axis; see ``collective_volume_nd``).

    ``chunks > 1`` pipelines the distributed passes: the batch — or, for a
    single rank-3 grid, the leading (local) transform axis, where overlap
    matters most — splits into that many transactions so transaction i's
    all-to-alls hide behind transaction i+1's local digit FFTs. The
    leading-axis FFTs themselves run unchunked (they are local and precede
    any collective); results are bitwise-identical to the bulk path.
    """
    shards = mesh.shape[axis]
    dsize = mesh.shape[data_axis] if data_axis else 1

    @jax.jit
    def run(x):  # x: (..., s0, ..., R, C) complex
        shape = x.shape
        tshape = shape[-ndim:]
        rr, cc = tshape[-2], tshape[-1]
        pc = make_dist_plan(cc, shards)
        c1, c2 = pc.n1, pc.n2
        if dsize > 1:
            pr = make_dist_plan(rr, dsize)
            r1, r2 = pr.n1, pr.n2
        else:
            r1, r2 = rr, 1
        lead = tshape[:-2]
        nl = len(lead)
        cube = (-1,) + lead + (r1, r2, c1, c2)
        z = x.reshape(cube)
        # cube axis positions (leading batch dim at 0)
        ax_r1, ax_r2 = 1 + nl, 2 + nl
        ax_c1, ax_c2 = 3 + nl, 4 + nl
        tw_c = jnp.asarray(factors.stage_twiddle(c1, c2, inverse=inverse),
                           dtype=x.dtype)
        tw_r = (jnp.asarray(factors.stage_twiddle(r1, r2, inverse=inverse),
                            dtype=x.dtype) if dsize > 1 else None)

        def fwd_pass(zl, mesh_ax, a1, a2, tw):
            """One 1-D digit pass: FFT over the slow digit (a1), twiddle,
            all-to-all (split a1, gather a2), FFT over the fast digit."""
            i = jax.lax.axis_index(mesh_ax)
            nloc = zl.shape[a2]
            zl = _local_axis_fft(zl, a1, inverse=inverse)
            twl = jax.lax.dynamic_slice_in_dim(tw, i * nloc, nloc, axis=1)
            zl = zl * jnp.expand_dims(
                twl, [d for d in range(zl.ndim) if d not in (a1, a2)])
            zl = jax.lax.all_to_all(zl, mesh_ax, split_axis=a1,
                                    concat_axis=a2, tiled=True)
            return _local_axis_fft(zl, a2, inverse=inverse)

        def inv_pass(zl, mesh_ax, a1, a2, tw):
            """Mirror of fwd_pass consuming transposed digit order: IFFT
            over the fast digit, conjugate twiddle (sliced over the sharded
            k1 rows), all-to-all (split a2, gather a1), IFFT over the slow
            digit."""
            i = jax.lax.axis_index(mesh_ax)
            n1l = zl.shape[a1]
            zl = _local_axis_fft(zl, a2, inverse=True)
            twl = jax.lax.dynamic_slice_in_dim(tw, i * n1l, n1l, axis=0)
            zl = zl * jnp.expand_dims(
                twl, [d for d in range(zl.ndim) if d not in (a1, a2)])
            zl = jax.lax.all_to_all(zl, mesh_ax, split_axis=a2,
                                    concat_axis=a1, tiled=True)
            return _local_axis_fft(zl, a1, inverse=True)

        # chunk candidates: the (replicated) batch axis first, then the
        # leading local transform axes — the rank-3 single-grid case rides
        # the first lead axis. All are unsharded and both digit passes are
        # independent along them, so contiguous chunks are placement-safe.
        caxes = (0,) + tuple(1 + k for k in range(nl))

        def dist_fwd(zc):
            """The distributed tail of the forward: both digit passes.
            Per-transaction when chunked — chunk i's all-to-alls overlap
            chunk i+1's local digit FFTs."""
            zc = fwd_pass(zc, axis, ax_c1, ax_c2, tw_c)
            if dsize > 1:
                zc = fwd_pass(zc, data_axis, ax_r1, ax_r2, tw_r)
            else:
                zc = _local_axis_fft(zc, ax_r1, inverse=False)
            return zc

        def dist_inv(zc):
            """The distributed head of the inverse (mirror of dist_fwd)."""
            if dsize > 1:
                zc = inv_pass(zc, data_axis, ax_r1, ax_r2, tw_r)
            else:
                zc = _local_axis_fft(zc, ax_r1, inverse=True)
            return inv_pass(zc, axis, ax_c1, ax_c2, tw_c)

        def body(zl):
            if not inverse:
                for k in range(nl):                 # leading axes: local
                    zl = _local_axis_fft(zl, 1 + k, inverse=False)
                return _chunk_apply(zl, dist_fwd, chunks, caxes)
            zl = _chunk_apply(zl, dist_inv, chunks, caxes)
            for k in range(nl):
                zl = _local_axis_fft(zl, 1 + k, inverse=True)
            return zl / int(np.prod(tshape))

        daxis_spec = data_axis if dsize > 1 else None
        # forward in / inverse out: fast digits sharded (r2/data, c2/fft);
        # forward out / inverse in: slow digits sharded (transposed order)
        spec_in = [None] * (1 + nl) + [None, daxis_spec, None, axis]
        spec_t = [None] * (1 + nl) + [daxis_spec, None, axis, None]
        in_spec, out_spec = ((spec_t, spec_in) if inverse
                             else (spec_in, spec_t))
        out = shard_map(body, mesh=mesh, in_specs=P(*in_spec),
                        out_specs=P(*out_spec), check_vma=False)(z)
        if inverse or not natural_order:
            return out.reshape(shape)
        # digit restore to natural order: (k1, k2) -> (k2, k1) per
        # distributed axis — GSPMD pays one all-gather per mesh axis here
        perm = (list(range(1 + nl))
                + [ax_r2, ax_r1, ax_c2, ax_c1])
        return out.transpose(perm).reshape(shape)

    return run


def _pencil_to_transposed_cube(x, r1, r2, c1, c2):
    """Natural-order input -> the transposed-digit cube layout the pencil
    inverse consumes (the forward's ``natural_order=False`` output is
    already in this layout and skips this)."""
    shape = x.shape
    lead = shape[:-2]
    z = x.reshape(lead + (r2, r1, c2, c1))
    nl = len(lead)
    perm = list(range(nl)) + [nl + 1, nl, nl + 3, nl + 2]
    return z.transpose(perm).reshape(shape)


# ---------------------------------------------------------------------------
# real-input (half-spectrum) transforms: rfft2 / irfft2 on the slab
# ---------------------------------------------------------------------------
#
# The 1-D Hermitian packing trick (extensions.rfft) composed with the slab
# row pass: the column transform of a real (R, C) grid runs as ONE C2C FFT
# of length C/2 on z = x[2k] + i*x[2k+1], the elementwise unpack recovers
# the C/2+1 surviving half-spectrum bins, and only those columns — padded
# with D-1 dead zero columns to Cp = C/2 + D so the all-to-all's split axis
# stays shard-divisible — flow through the inter-axis transpose before the
# row FFT. Roughly HALF the all-to-all bytes of the C2C slab on the same
# grid ((C/2 + D)/C, modeled by collective_volume_nd(real=True)).


def _complex_of(dtype) -> jnp.dtype:
    return jnp.dtype(jnp.complex128 if dtype in (jnp.float64, jnp.complex128)
                     else jnp.complex64)


def _rfft_cols(x: jax.Array) -> jax.Array:
    """Packed rfft over the (even-length) last axis: (..., C) real ->
    (..., C/2+1) half spectrum, via one half-length C2C transform."""
    return unpack_half(_local_axis_fft(pack_half(x), -1, inverse=False),
                       x.shape[-1])


@jax.jit   # not eager: see extensions.pack_half (no complex128 operands)
def _irfft_cols(y: jax.Array) -> jax.Array:
    """Inverse of :func:`_rfft_cols` (normalized): (..., C/2+1) half
    spectrum -> (..., C) real, C = 2*(bins-1). Recovers the packed
    half-length time signal z = x_even + i*x_odd from the spectrum's
    even/odd split, then interleaves its real and imaginary parts."""
    half = y.shape[-1] - 1
    cc = 2 * half
    winv = jnp.asarray(np.exp(2j * np.pi * np.arange(half) / cc), y.dtype)
    yh = y[..., :half]
    ymir = jnp.conj(y[..., 1:][..., ::-1])                     # Y*[half-k]
    e = 0.5 * (yh + ymir)
    o = 0.5 * winv * (yh - ymir)
    z = _local_axis_fft(e + 1j * o, -1, inverse=True) / half
    out = jnp.stack([jnp.real(z), jnp.imag(z)], axis=-1)
    return out.reshape(out.shape[:-2] + (cc,))


def _local_rfft2(x: jax.Array) -> jax.Array:
    """Local rfft2 over the last two axes ((..., R, C) real ->
    (..., R, C/2+1)); odd C runs the direct DFT and crops (the same
    fallback as the odd-n 1-D paths)."""
    cc = x.shape[-1]
    if cc % 2:
        z = _local_axis_fft(x.astype(_complex_of(x.dtype)), -1,
                            inverse=False)[..., :cc // 2 + 1]
    else:
        z = _rfft_cols(x)
    return _local_axis_fft(z, -2, inverse=False)


def _local_irfft2(y: jax.Array, *, cc: int | None = None) -> jax.Array:
    """Local irfft2: (..., R, bins) half spectrum -> (..., R, cc) real
    (default ``cc = 2*(bins-1)``; odd ``cc`` reconstructs the full
    Hermitian spectrum and runs the direct inverse DFT)."""
    bins = y.shape[-1]
    if cc is None:
        cc = 2 * (bins - 1)
    rr = y.shape[-2]
    y = y.astype(_complex_of(y.dtype))
    z = _local_axis_fft(y, -2, inverse=True) / rr
    if cc % 2:
        m = (cc + 1) // 2   # bins of an odd-length real signal
        yh = z[..., :m]
        tail = jnp.conj(yh[..., 1:][..., ::-1])
        full = jnp.concatenate([yh, tail], axis=-1)
        return jnp.real(naive_dft(full, inverse=True))
    return _irfft_cols(z[..., :cc // 2 + 1])


@functools.lru_cache(maxsize=None)
def _rslab_fft2_fn(mesh: Mesh, axis: str, data_axis: str | None = None):
    """Jitted real slab forward: input real grids sharded over R ->
    packed half-length FFT over C + Hermitian unpack (local) -> pad to
    Cp = C/2 + D -> ONE all-to-all (split columns, gather R) -> local FFT
    over R. Output is the PADDED (..., R, Cp) half spectrum sharded over
    the column axis; callers slice the C/2+1 live bins."""
    shards = mesh.shape[axis]
    dsize = mesh.shape[data_axis] if data_axis else 1

    @jax.jit
    def run(x):  # x: (..., R, C) real
        shape = x.shape
        rr, cc = shape[-2], shape[-1]
        cp = cc // 2 + shards
        z = x.reshape((-1, rr, cc))
        b = z.shape[0]
        bspec = data_axis if (data_axis and b % dsize == 0) else None

        def body(zl):                                  # (b, R/D, C) real
            hc = _rfft_cols(zl)                        # (b, R/D, C/2+1)
            hc = jnp.pad(hc, ((0, 0), (0, 0), (0, shards - 1)))
            hc = jax.lax.all_to_all(hc, axis, split_axis=2, concat_axis=1,
                                    tiled=True)        # (b, R, Cp/D)
            return _local_axis_fft(hc, 1, inverse=False)

        out = shard_map(body, mesh=mesh, in_specs=P(bspec, axis, None),
                        out_specs=P(bspec, None, axis),
                        check_vma=False)(z)
        return out.reshape(shape[:-2] + (rr, cp))

    return run


@functools.lru_cache(maxsize=None)
def _rslab_ifft2_fn(mesh: Mesh, axis: str, data_axis: str | None = None):
    """Jitted real slab inverse, mirroring :func:`_rslab_fft2_fn`: padded
    (..., R, Cp) half spectrum sharded over columns -> local IFFT over R ->
    ONE all-to-all (split R, gather columns) -> slice the live bins ->
    local Hermitian inverse over C -> (..., R, C) real sharded over R."""
    shards = mesh.shape[axis]
    dsize = mesh.shape[data_axis] if data_axis else 1

    @jax.jit
    def run(y):  # y: (..., R, Cp) complex, Cp = C/2 + D
        shape = y.shape
        rr, cp = shape[-2], shape[-1]
        half = cp - shards
        cc = 2 * half
        z = y.reshape((-1, rr, cp))
        b = z.shape[0]
        bspec = data_axis if (data_axis and b % dsize == 0) else None

        def body(zl):                                  # (b, R, Cp/D)
            zl = _local_axis_fft(zl, 1, inverse=True) / rr
            zl = jax.lax.all_to_all(zl, axis, split_axis=1, concat_axis=2,
                                    tiled=True)        # (b, R/D, Cp)
            return _irfft_cols(zl[..., :half + 1])     # (b, R/D, C) real

        out = shard_map(body, mesh=mesh, in_specs=P(bspec, None, axis),
                        out_specs=P(bspec, axis, None),
                        check_vma=False)(z)
        return out.reshape(shape[:-2] + (rr, cc))

    return run


def distributed_rfft2(x: jax.Array, mesh: Mesh | None = None, *,
                      axis: str = FFT_AXIS,
                      data_axis: str | None = _AUTO) -> jax.Array:
    """2-D real-input FFT over the last two axes -> (..., R, C/2+1) half
    spectrum, distributed over ``mesh`` via the real slab pipeline (about
    half the all-to-all bytes of :func:`distributed_fft2` on the same
    grid — see :func:`collective_volume_nd` with ``real=True``).

    Matches ``jnp.fft.rfft2``. Like the 1-D ``extensions.rfft``, sizes the
    mesh cannot split (:func:`rslab_feasible`) fall back to the local
    transform, which also covers odd grids via the direct DFT.
    """
    x = jnp.asarray(x)
    if jnp.issubdtype(x.dtype, jnp.complexfloating):
        raise ValueError(f"rfft2 takes a real input, got {x.dtype}")
    if x.ndim < 2:
        raise ValueError(f"rfft2 needs a rank >= 2 input, got {x.shape}")
    mesh = _resolve_mesh(mesh, axis)
    tshape = (int(x.shape[-2]), int(x.shape[-1]))
    if mesh is None or mesh.shape[axis] == 1 \
            or not rslab_feasible(tshape, mesh.shape[axis]):
        return _local_rfft2(x)
    daxis = _resolve_data_axis(mesh, data_axis)
    out = _rslab_fft2_fn(mesh, axis, daxis)(x)
    return out[..., :tshape[-1] // 2 + 1]


def distributed_irfft2(y: jax.Array, mesh: Mesh | None = None, *,
                       axis: str = FFT_AXIS,
                       data_axis: str | None = _AUTO) -> jax.Array:
    """Inverse of :func:`distributed_rfft2`: (..., R, bins) half spectrum
    -> (..., R, 2*(bins-1)) real grid. Matches ``jnp.fft.irfft2`` (even
    output widths; infeasible sizes run locally)."""
    y = jnp.asarray(y)
    if y.ndim < 2:
        raise ValueError(f"irfft2 needs a rank >= 2 spectrum, got {y.shape}")
    if y.shape[-1] < 2:
        raise ValueError("irfft2: a single-bin half spectrum has no "
                         "default width (2*(bins-1) = 0) — the planned "
                         "grid needs >= 2 bins")
    y = y.astype(_complex_of(y.dtype))
    half = y.shape[-1] - 1
    cc = 2 * half
    mesh = _resolve_mesh(mesh, axis)
    tshape = (int(y.shape[-2]), cc)
    if mesh is None or mesh.shape[axis] == 1 \
            or not rslab_feasible(tshape, mesh.shape[axis]):
        return _local_irfft2(y, cc=cc)
    daxis = _resolve_data_axis(mesh, data_axis)
    shards = mesh.shape[axis]
    yp = jnp.pad(y, [(0, 0)] * (y.ndim - 1) + [(0, shards - 1)])
    return _rslab_ifft2_fn(mesh, axis, daxis)(yp)


def _composed_rfft2(x: jax.Array, *, mesh: Mesh | None,
                    axis: str = FFT_AXIS,
                    data_axis: str | None = _AUTO) -> jax.Array:
    """Pencil-path rfft2: a correctness-first composition — the 1-D
    distributed rfft over the columns (half-length pencil pipeline plus
    elementwise Hermitian unpack), then a natural-order C2C pass over the
    rows. The slab is the optimized real path; this exists so explicit
    ``decomp='pencil'`` real plans still scale the column transform."""
    from . import api
    from .extensions import rfft as _rfft_ext

    y = _rfft_ext(x, mesh=mesh, axis=axis, data_axis=data_axis)
    z = jnp.moveaxis(y, -2, -1)                        # (..., C/2+1, R)
    if mesh is not None and mesh.shape[axis] > 1 \
            and api._feasible_1d(z.shape[-1], mesh.shape[axis]):
        p = api.plan(api.spec_for(z, mesh=mesh, axis=axis, data_axis=None))
        z = p.fft(z)
    else:
        z = _local_axis_fft(z, -1, inverse=False)
    return jnp.moveaxis(z, -1, -2)


def _composed_irfft2(y: jax.Array, *, cc: int, mesh: Mesh | None,
                     axis: str = FFT_AXIS,
                     data_axis: str | None = _AUTO) -> jax.Array:
    """Inverse of :func:`_composed_rfft2`: C2C inverse over the rows, then
    the 1-D distributed irfft over the columns (length ``cc``)."""
    from . import api
    from .extensions import irfft as _irfft_ext

    y = jnp.asarray(y)
    y = y.astype(_complex_of(y.dtype))
    z = jnp.moveaxis(y, -2, -1)                        # (..., bins, R)
    if mesh is not None and mesh.shape[axis] > 1 \
            and api._feasible_1d(z.shape[-1], mesh.shape[axis]):
        p = api.plan(api.spec_for(z, mesh=mesh, axis=axis, data_axis=None))
        z = p.ifft(z)
    else:
        z = _local_axis_fft(z, -1, inverse=True) / z.shape[-1]
    z = jnp.moveaxis(z, -1, -2)
    return _irfft_ext(z, n=cc, mesh=mesh, axis=axis, data_axis=data_axis)


# ---------------------------------------------------------------------------
# public entry points
# ---------------------------------------------------------------------------


def distributed_fftn(x: jax.Array, mesh: Mesh | None = None, *,
                     ndim: int | None = None, decomp: str = "auto",
                     inverse: bool = False, natural_order: bool = True,
                     axis: str = FFT_AXIS, data_axis: str | None = _AUTO,
                     interpret: bool | None = None,
                     chunks: int = 1) -> jax.Array:
    """N-D FFT over the last ``ndim`` axes (default: all, capped at 3),
    distributed over ``mesh``. Matches ``jnp.fft.fftn`` conventions.

    ``decomp`` picks the layout — ``"slab"``, ``"pencil"``, or ``"auto"``
    (:func:`choose_decomp` via the communication model). ``natural_order``
    only matters for pencil (slab's natural order is free; the flag is a
    no-op there): ``False`` keeps the two distributed axes in the 1-D
    pipeline's transposed digit order — ``y[.., k1*N2+k2] = X[.., k1+N1*k2]``
    per axis — and on the *inverse* declares the input to be in exactly
    that order (TRANSPOSED_IN), so a pencil round trip pays zero
    all-gathers. With ``mesh=None`` (or a trivial fft axis) this is the
    local transform; odd / non-power-of-two axes are supported there via
    the direct DFT, and ``interpret`` routes power-of-two axes through the
    Pallas block kernel.

    ``chunks > 1`` (pencil only) splits the batch — or, for a single
    rank-3 grid, the leading local transform axis — into that many
    transactions so each chunk's all-to-alls overlap the next chunk's
    local digit FFTs (see :func:`collective_volume_nd`). The slab and
    local paths ignore it (bulk-synchronous by construction); results are
    bitwise-identical for every chunk count.
    """
    x = jnp.asarray(x)
    if not jnp.issubdtype(x.dtype, jnp.complexfloating):
        x = x.astype(jnp.complex64)
    if ndim is None:
        ndim = min(x.ndim, 3)
    if ndim < 2 or ndim > 3:
        raise ValueError(f"ndim must be 2 or 3, got {ndim}")
    if x.ndim < ndim:
        raise ValueError(f"input rank {x.ndim} < ndim={ndim}")
    mesh = _resolve_mesh(mesh, axis)
    tshape = tuple(int(s) for s in x.shape[-ndim:])
    batch = int(np.prod(x.shape[:-ndim], dtype=np.int64)) if x.ndim > ndim \
        else 1
    if decomp == "auto":
        decomp = choose_decomp(tshape, mesh, batch=batch, axis=axis,
                               natural_order=natural_order,
                               data_axis=data_axis) \
            if mesh is not None and mesh.shape[axis] > 1 else "local"
    if decomp not in _DECOMPS + ("local",):
        raise ValueError(f"decomp must be auto|{'|'.join(_DECOMPS)}|local, "
                         f"got {decomp!r}")
    if decomp == "local" or mesh is None or mesh.shape[axis] == 1:
        return _local_fftn(x, ndim, inverse=inverse, interpret=interpret)
    daxis = _resolve_data_axis(mesh, data_axis)
    if decomp == DECOMP_SLAB:
        if not slab_feasible(tshape, mesh.shape[axis]):
            raise ValueError(
                f"slab needs power-of-two axes with "
                f"{mesh.shape[axis]} | {tshape[0]} and "
                f"{mesh.shape[axis]} | {tshape[-1]}, got {tshape}")
        return _slab_fftn_fn(mesh, axis, ndim, inverse, daxis)(x)
    dd = mesh.shape[daxis] if daxis else 1
    if not pencil_feasible(tshape, mesh.shape[axis], dd):
        raise ValueError(
            f"pencil needs {tshape[-1]} >= fft^2={mesh.shape[axis] ** 2} "
            f"and {tshape[-2]} >= data^2={dd * dd} (power-of-two axes), "
            f"got {tshape}")
    if inverse and natural_order:
        # natural-order input: permute into the transposed cube the
        # inverse pipeline consumes (the redistribution the transposed
        # pairing exists to skip)
        pc = make_dist_plan(tshape[-1], mesh.shape[axis])
        if dd > 1:
            pr = make_dist_plan(tshape[-2], dd)
            r1, r2 = pr.n1, pr.n2
        else:
            r1, r2 = tshape[-2], 1
        x = _pencil_to_transposed_cube(x, r1, r2, pc.n1, pc.n2)
    return _pencil_fftn_fn(mesh, axis, ndim, inverse,
                           bool(natural_order), daxis, int(chunks))(x)


def distributed_fft2(x: jax.Array, mesh: Mesh | None = None,
                     **kwargs) -> jax.Array:
    """2-D FFT over the last two axes (see :func:`distributed_fftn`)."""
    return distributed_fftn(x, mesh, ndim=2, **kwargs)


def distributed_ifft2(x: jax.Array, mesh: Mesh | None = None,
                      **kwargs) -> jax.Array:
    """Inverse 2-D FFT (normalized by 1/(R*C)); ``natural_order=False``
    consumes the forward's transposed-digit pencil output with no
    redistribution."""
    return distributed_fftn(x, mesh, ndim=2, inverse=True, **kwargs)


def distributed_ifftn(x: jax.Array, mesh: Mesh | None = None,
                      **kwargs) -> jax.Array:
    """Inverse of :func:`distributed_fftn` (normalized by 1/prod(shape))."""
    return distributed_fftn(x, mesh, inverse=True, **kwargs)


# ---------------------------------------------------------------------------
# grouped two-side ABFT on the slab pipeline (2-D)
# ---------------------------------------------------------------------------


@functools.lru_cache(maxsize=None)
def _ft_slab_fft2_fn(mesh: Mesh, axis: str, threshold: float, correct: bool,
                     groups: int = 1, data_axis: str | None = None):
    """The slab 2-D forward with the PR-3 grouped two-side ABFT composed
    onto it: per checksum group, two right-side checksum GRIDS ride the
    inter-axis transpose as extra batch rows, and the verdict is one psum
    of 3 scalars per locally-owned group + 1 shared energy scalar,
    confined to the ``fft`` axis."""
    shards = mesh.shape[axis]
    dsize = mesh.shape[data_axis] if data_axis else 1

    @jax.jit
    def run(x, inject):  # x: (B, R, C) complex; inject: (F, 7) real
        b, rr, cc = x.shape
        g = groups
        s = b // g
        rc = rr * cc
        bspec = data_axis if (
            data_axis and b % dsize == 0 and g % dsize == 0) else None
        dloc = dsize if bspec else 1
        bl, gl = b // dloc, g // dloc
        rl = rr // shards                    # local R rows in pass 1
        ftype = np.float64 if x.dtype == jnp.complex128 else np.float32
        ids = jnp.arange(1, s + 1, dtype=ftype)[None, :, None, None]

        def body(zl):
            d = jax.lax.axis_index(axis)
            md = jax.lax.axis_index(data_axis) if bspec else jnp.int32(0)
            # checksum grids: rows [0, bl) data | [bl, bl+gl) cs2 |
            # [bl+gl, bl+2gl) cs3 — linearity makes each a signal grid
            zg = zl.reshape((gl, s, rl, cc))
            cs2_in = jnp.sum(zg, axis=1)
            cs3_in = jnp.sum(ids * zg, axis=1)
            zc = jnp.concatenate([zl, cs2_in, cs3_in], axis=0)
            # ---- pass 1: FFT over C (resident) + left checksum ------------
            zf = _local_fft(zc, False)
            res1 = jnp.abs(jnp.sum(zf, axis=-1) - cc * zc[..., 0])
            scale1 = jnp.sqrt(jnp.mean(jnp.abs(zc) ** 2, axis=-1)) + EPS
            delta = jnp.max(res1 / (float(np.sqrt(cc)) * scale1))
            zc = zf
            # ---- fault injection (tests/benchmarks): one SEU per row
            # [fft_device, signal, local_r, col, enable, eps_re, eps_im]
            # on the pass-1 output: ``local_r`` indexes this device's R
            # rows (R is sharded pre-transpose), ``col`` is the global C
            # bin. ``signal`` in [B, B+G) / [B+G, B+2G) hits a group's
            # cs2 / cs3 checksum grid. ---------------------------------
            dev = inject[:, 0].astype(jnp.int32)
            sig = inject[:, 1].astype(jnp.int32)
            row = inject[:, 2].astype(jnp.int32)
            col = inject[:, 3].astype(jnp.int32)
            eps = (inject[:, 5] + 1j * inject[:, 6]).astype(zc.dtype)
            is_data = sig < b
            is_cs2 = (sig >= b) & (sig < b + g)
            gidx = jnp.where(is_cs2, sig - b, sig - b - g)
            owner = jnp.where(is_data, sig // bl, gidx // gl)
            lrow = jnp.where(
                is_data, sig - owner * bl,
                bl + jnp.where(is_cs2, 0, gl) + gidx - owner * gl)
            amp = inject[:, 4] * ((owner == md) & (d == dev)).astype(ftype)
            onehot = (
                (jnp.arange(bl + 2 * gl)[None] == lrow[:, None])
                [:, :, None, None]
                * (jnp.arange(rl)[None] == row[:, None])[:, None, :, None]
                * (jnp.arange(cc)[None] == col[:, None])[:, None, None, :])
            zc = zc + jnp.sum((eps * amp.astype(zc.real.dtype))
                              [:, None, None, None]
                              * onehot.astype(zc.real.dtype), axis=0)
            # ---- the one collective: the inter-axis transpose -------------
            zc = jax.lax.all_to_all(zc, axis, split_axis=2, concat_axis=1,
                                    tiled=True)      # (bl+2gl, R, C/D)
            # ---- pass 2: FFT over R (resident) + left checksum ------------
            zt = jnp.swapaxes(zc, -1, -2)
            zf2 = _local_fft(zt, False)
            res2 = jnp.abs(jnp.sum(zf2, axis=-1) - rr * zt[..., 0])
            scale2 = jnp.sqrt(jnp.mean(jnp.abs(zt) ** 2, axis=-1)) + EPS
            delta = jnp.maximum(
                delta, jnp.max(res2 / (float(np.sqrt(rr)) * scale2)))
            zf2 = jnp.swapaxes(zf2, -1, -2)          # (bl+2gl, R, C/D)
            # ---- detect / locate per group --------------------------------
            yl = zf2[:bl]
            fcs2, fcs3 = zf2[bl:bl + gl], zf2[bl + gl:]
            ylg = yl.reshape((gl, s) + yl.shape[1:])
            cs2_out = jnp.sum(ylg, axis=1)
            cs3_out = jnp.sum(ids * ylg, axis=1)
            d2 = fcs2 - cs2_out                      # == -eps_y, sharded
            d3 = fcs3 - cs3_out                      # == -id_s * eps_y
            # the shared grouped two-side decode (one psum of 3*gl + 1
            # scalars on the fft axis) — the SAME helper the 1-D pipeline
            # runs, so the fault taxonomy cannot diverge; a signal here is
            # an (R, C) grid, hence n = R*C
            ylg, stats = _grouped_verdict(
                ylg, d2, d3, cs2_out, axis=axis, threshold=threshold, s=s,
                n=rc, md=md, bl=bl, gl=gl, correct=correct)
            yl = ylg.reshape((bl,) + yl.shape[1:])
            return yl, delta[None, None], stats[None]

        yl, deltas, stats = shard_map(
            body, mesh=mesh,
            in_specs=P(bspec, axis, None),
            out_specs=(P(bspec, None, axis), P(bspec, axis),
                       P(axis, bspec, None)),
            check_vma=False)(x)
        st = stats[0]                # (G, 5); fft shards agree post-psum
        flagged = st[:, 1] > 0.5
        correctable = st[:, 3] > 0.5
        return DistFFTResult(
            y=yl, shard_delta=deltas.reshape((-1,)), group_score=st[:, 0],
            flagged=flagged, location=st[:, 2].astype(jnp.int32),
            correctable=correctable, checksum_fault=st[:, 4] > 0.5,
            corrected=jnp.sum(correctable.astype(jnp.int32)) * int(correct),
            recomputed=jnp.zeros((), jnp.int32))

    return run


def _recompute_uncorrectable2(x, res, mesh, axis, groups):
    """Multi-fault-group policy fallback (the shared
    :func:`~repro.core.fft.distributed._splice_recomputed` machinery),
    recomputing with the plain slab pipeline."""
    return _splice_recomputed(
        x, res, groups,
        lambda rows: distributed_fft2(rows, mesh, axis=axis,
                                      decomp=DECOMP_SLAB, data_axis=None),
        "ft_distributed_fft2")


def ft_distributed_fft2(
    x: jax.Array,
    mesh: Mesh | None = None,
    *,
    axis: str = FFT_AXIS,
    threshold: float = 1e-4,
    correct: bool = True,
    inject: jax.Array | None = None,
    groups: int | None = None,
    group_size: int | None = None,
    data_axis: str | None = _AUTO,
    recompute_uncorrectable: bool = False,
) -> DistFFTResult:
    """Fault-tolerant slab 2-D forward FFT (grouped two-side ABFT).

    The mesh-level grouped multi-transaction scheme of
    :func:`~repro.core.fft.distributed.ft_distributed_fft`, composed with
    the 2-D slab row pass: the batch of (R, C) grids splits into G
    checksum groups, each carrying a ``cs2``/``cs3`` checksum *grid* pair
    through the inter-axis transpose (2G/B relative all-to-all overhead),
    with one verdict psum of ``3*G/data + 1`` scalars confined to the
    ``fft`` axis. One SEU per group per pass is detected, located to its
    signal, and corrected elementwise; batch rows shard over ``data`` (no
    batch all-gather). The verdict taxonomy (correctable / uncorrectable /
    checksum_fault) and the ``recompute_uncorrectable`` host fallback
    match the 1-D contract; see :class:`DistFFTResult`.

    ``inject`` rows are ``[fft_device, signal, local_r, col, enable,
    eps_re, eps_im]`` — an SEU on the pass-1 output, where ``local_r``
    indexes the device's resident R rows (R is sharded before the
    transpose) and ``col`` the global C bin; ``signal`` in ``[B, B+G)`` /
    ``[B+G, B+2G)`` targets a group's cs2 / cs3 checksum grid. The slab
    output is natural-order for free, so there is no ``natural_order``
    knob here.
    """
    x = jnp.asarray(x)
    if not jnp.issubdtype(x.dtype, jnp.complexfloating):
        x = x.astype(jnp.complex64)
    if x.ndim != 3:
        raise ValueError(
            f"ft_distributed_fft2 expects (B, R, C), got {x.shape}")
    mesh = _resolve_mesh(mesh, axis)
    if mesh is None:
        raise ValueError("ft_distributed_fft2 requires a mesh with an "
                         f"'{axis}' axis (see launch.mesh.make_fft_mesh)")
    tshape = tuple(int(s) for s in x.shape[1:])
    if not slab_feasible(tshape, mesh.shape[axis]):
        raise ValueError(
            f"the ft pipeline rides the slab transpose: needs "
            f"power-of-two axes divisible by {mesh.shape[axis]}, "
            f"got {tshape}")
    daxis = _resolve_data_axis(mesh, data_axis)
    dsize = mesh.shape[daxis] if daxis else 1
    g = resolve_abft_groups(x.shape[0], groups=groups, group_size=group_size,
                            data_shards=dsize)
    ftype = jnp.float64 if x.dtype == jnp.complex128 else jnp.float32
    if inject is None:
        inject = jnp.zeros((1, 7), ftype)
    inject = jnp.asarray(inject, ftype)
    if inject.ndim == 1:
        inject = inject[None]
    res = _ft_slab_fft2_fn(mesh, axis, float(threshold), bool(correct),
                           g, daxis)(x, inject)
    if recompute_uncorrectable:
        res = _recompute_uncorrectable2(x, res, mesh, axis, g)
    return res


# ---------------------------------------------------------------------------
# grouped two-side ABFT on the real slab pipeline
# ---------------------------------------------------------------------------


@functools.lru_cache(maxsize=None)
def _ft_rslab_fft2_fn(mesh: Mesh, axis: str, threshold: float, correct: bool,
                      groups: int = 1, data_axis: str | None = None):
    """The real slab forward (:func:`_rslab_fft2_fn`) with the grouped
    two-side ABFT composed onto it, on the Hermitian-symmetric checksum
    layout: the cs2/cs3 checksum grids are summed over the REAL input rows,
    and because every map in the pipeline — even/odd pack, half-length C2C
    FFT, Hermitian unpack (the conjugate-tail fold is R-linear), zero-pad,
    transpose, row FFT — is R-linear with *real* group ids,
    ``F(sum id_b x_b) = sum id_b F(x_b)`` holds elementwise on the padded
    half spectrum and the shared decode (:func:`_grouped_verdict`) applies
    unchanged with ``n = R * Cp``. The checksum grids ride the transpose
    at the packed half width — half the checksum traffic of the C2C slab
    ABFT, same relative 2G/B overhead. The pass-1 left checksum guards the
    packed half-length FFT (``sum_k Z[k] = (C/2) * z[0]``)."""
    shards = mesh.shape[axis]
    dsize = mesh.shape[data_axis] if data_axis else 1

    @jax.jit
    def run(x, inject):  # x: (B, R, C) real; inject: (F, 7) real
        b, rr, cc = x.shape
        half = cc // 2
        cp = half + shards
        g = groups
        s = b // g
        rc = rr * cp
        bspec = data_axis if (
            data_axis and b % dsize == 0 and g % dsize == 0) else None
        dloc = dsize if bspec else 1
        bl, gl = b // dloc, g // dloc
        rl = rr // shards                    # local R rows in pass 1
        ftype = np.float64 if x.dtype == jnp.float64 else np.float32
        ids = jnp.arange(1, s + 1, dtype=ftype)[None, :, None, None]

        def body(zl):
            d = jax.lax.axis_index(axis)
            md = jax.lax.axis_index(data_axis) if bspec else jnp.int32(0)
            # checksum grids summed over the REAL rows: [0, bl) data |
            # [bl, bl+gl) cs2 | [bl+gl, bl+2gl) cs3
            zg = zl.reshape((gl, s, rl, cc))
            cs2_in = jnp.sum(zg, axis=1)
            cs3_in = jnp.sum(ids * zg, axis=1)
            zc = jnp.concatenate([zl, cs2_in, cs3_in], axis=0)
            # ---- pass 1: packed half-length FFT over C + left checksum ----
            zpk = pack_half(zc)
            zf = _local_fft(zpk, False)
            res1 = jnp.abs(jnp.sum(zf, axis=-1) - half * zpk[..., 0])
            scale1 = jnp.sqrt(jnp.mean(jnp.abs(zpk) ** 2, axis=-1)) + EPS
            delta = jnp.max(res1 / (float(np.sqrt(half)) * scale1))
            hc = unpack_half(zf, cc)                 # (bl+2gl, rl, C/2+1)
            hc = jnp.pad(hc, ((0, 0), (0, 0), (0, shards - 1)))
            # ---- fault injection (tests/benchmarks): one SEU per row
            # [fft_device, signal, local_r, col, enable, eps_re, eps_im]
            # on the pass-1 HALF-SPECTRUM output (post-unpack): ``col``
            # addresses the padded half spectrum [0, Cp) — live bins are
            # [0, C/2+1) — and the checksum-row location encoding is the
            # C2C layout's, at the folded width. -------------------------
            dev = inject[:, 0].astype(jnp.int32)
            sig = inject[:, 1].astype(jnp.int32)
            row = inject[:, 2].astype(jnp.int32)
            col = inject[:, 3].astype(jnp.int32)
            eps = (inject[:, 5] + 1j * inject[:, 6]).astype(hc.dtype)
            is_data = sig < b
            is_cs2 = (sig >= b) & (sig < b + g)
            gidx = jnp.where(is_cs2, sig - b, sig - b - g)
            owner = jnp.where(is_data, sig // bl, gidx // gl)
            lrow = jnp.where(
                is_data, sig - owner * bl,
                bl + jnp.where(is_cs2, 0, gl) + gidx - owner * gl)
            amp = inject[:, 4] * ((owner == md) & (d == dev)).astype(ftype)
            onehot = (
                (jnp.arange(bl + 2 * gl)[None] == lrow[:, None])
                [:, :, None, None]
                * (jnp.arange(rl)[None] == row[:, None])[:, None, :, None]
                * (jnp.arange(cp)[None] == col[:, None])[:, None, None, :])
            hc = hc + jnp.sum((eps * amp.astype(hc.real.dtype))
                              [:, None, None, None]
                              * onehot.astype(hc.real.dtype), axis=0)
            # ---- the one collective: the inter-axis transpose -------------
            hc = jax.lax.all_to_all(hc, axis, split_axis=2, concat_axis=1,
                                    tiled=True)      # (bl+2gl, R, Cp/D)
            # ---- pass 2: FFT over R (resident) + left checksum ------------
            zt = jnp.swapaxes(hc, -1, -2)
            zf2 = _local_fft(zt, False)
            res2 = jnp.abs(jnp.sum(zf2, axis=-1) - rr * zt[..., 0])
            scale2 = jnp.sqrt(jnp.mean(jnp.abs(zt) ** 2, axis=-1)) + EPS
            delta = jnp.maximum(
                delta, jnp.max(res2 / (float(np.sqrt(rr)) * scale2)))
            zf2 = jnp.swapaxes(zf2, -1, -2)          # (bl+2gl, R, Cp/D)
            # ---- detect / locate per group --------------------------------
            yl = zf2[:bl]
            fcs2, fcs3 = zf2[bl:bl + gl], zf2[bl + gl:]
            ylg = yl.reshape((gl, s) + yl.shape[1:])
            cs2_out = jnp.sum(ylg, axis=1)
            cs3_out = jnp.sum(ids * ylg, axis=1)
            d2 = fcs2 - cs2_out                      # == -eps_y, sharded
            d3 = fcs3 - cs3_out                      # == -id_s * eps_y
            ylg, stats = _grouped_verdict(
                ylg, d2, d3, cs2_out, axis=axis, threshold=threshold, s=s,
                n=rc, md=md, bl=bl, gl=gl, correct=correct)
            yl = ylg.reshape((bl,) + yl.shape[1:])
            return yl, delta[None, None], stats[None]

        yl, deltas, stats = shard_map(
            body, mesh=mesh,
            in_specs=P(bspec, axis, None),
            out_specs=(P(bspec, None, axis), P(bspec, axis),
                       P(axis, bspec, None)),
            check_vma=False)(x)
        st = stats[0]                # (G, 5); fft shards agree post-psum
        flagged = st[:, 1] > 0.5
        correctable = st[:, 3] > 0.5
        return DistFFTResult(
            y=yl, shard_delta=deltas.reshape((-1,)), group_score=st[:, 0],
            flagged=flagged, location=st[:, 2].astype(jnp.int32),
            correctable=correctable, checksum_fault=st[:, 4] > 0.5,
            corrected=jnp.sum(correctable.astype(jnp.int32)) * int(correct),
            recomputed=jnp.zeros((), jnp.int32))

    return run


def ft_distributed_rfft2(
    x: jax.Array,
    mesh: Mesh | None = None,
    *,
    axis: str = FFT_AXIS,
    threshold: float = 1e-4,
    correct: bool = True,
    inject: jax.Array | None = None,
    groups: int | None = None,
    group_size: int | None = None,
    data_axis: str | None = _AUTO,
    recompute_uncorrectable: bool = False,
) -> DistFFTResult:
    """Fault-tolerant real slab 2-D forward FFT (grouped two-side ABFT on
    the Hermitian half-spectrum layout).

    :func:`ft_distributed_fft2` for REAL input grids: the checksum grids
    are real input-row sums that fold through the packing trick alongside
    the data (every pipeline map is R-linear, so the two-side decode is
    exact on the padded half spectrum), ride the transpose at the packed
    half width, and the verdict psum is unchanged. ``res.y`` carries the
    C/2+1 live half-spectrum bins. ``inject`` rows are ``[fft_device,
    signal, local_r, col, enable, eps_re, eps_im]`` — an SEU on the pass-1
    half-spectrum output, ``col`` in the padded columns ``[0, C/2 + D)``
    (live bins ``[0, C/2+1)``); ``signal`` in ``[B, B+G)`` / ``[B+G,
    B+2G)`` targets a group's cs2 / cs3 checksum grid, as in the C2C
    layout.
    """
    x = jnp.asarray(x)
    if jnp.issubdtype(x.dtype, jnp.complexfloating):
        raise ValueError(
            f"ft_distributed_rfft2 takes a real input, got {x.dtype} — "
            f"use ft_distributed_fft2 for complex grids")
    if x.ndim != 3:
        raise ValueError(
            f"ft_distributed_rfft2 expects (B, R, C), got {x.shape}")
    mesh = _resolve_mesh(mesh, axis)
    if mesh is None:
        raise ValueError("ft_distributed_rfft2 requires a mesh with an "
                         f"'{axis}' axis (see launch.mesh.make_fft_mesh)")
    tshape = tuple(int(s) for s in x.shape[1:])
    if not rslab_feasible(tshape, mesh.shape[axis]):
        raise ValueError(
            f"the real ft pipeline rides the slab transpose: needs a "
            f"power-of-two grid with {mesh.shape[axis]} | {tshape[0]} and "
            f"{mesh.shape[axis]} | {tshape[-1]}//2, got {tshape}")
    daxis = _resolve_data_axis(mesh, data_axis)
    dsize = mesh.shape[daxis] if daxis else 1
    g = resolve_abft_groups(x.shape[0], groups=groups, group_size=group_size,
                            data_shards=dsize)
    ftype = jnp.float64 if x.dtype == jnp.float64 else jnp.float32
    x = x.astype(ftype)
    if inject is None:
        inject = jnp.zeros((1, 7), ftype)
    inject = jnp.asarray(inject, ftype)
    if inject.ndim == 1:
        inject = inject[None]
    res = _ft_rslab_fft2_fn(mesh, axis, float(threshold), bool(correct),
                            g, daxis)(x, inject)
    res = dataclasses.replace(res, y=res.y[..., :tshape[-1] // 2 + 1])
    if recompute_uncorrectable:
        res = _splice_recomputed(
            x, res, g,
            lambda rows: distributed_rfft2(rows, mesh, axis=axis,
                                           data_axis=None),
            "ft_distributed_rfft2")
    return res


# ---------------------------------------------------------------------------
# 2-D spectral consumer: convolution via the slab round trip
# ---------------------------------------------------------------------------


@functools.lru_cache(maxsize=None)
def _conv2_pair_fn(mesh: Mesh, axis: str, data_axis: str | None):
    """forward(a, v) -> pointwise product -> inverse in ONE shard_map body.

    The 2-D analogue of ``spectral._spectral_pair_fn``: the kernel's
    forward transform shares the batch with the signals', the pointwise
    product happens in the slab's free natural order, and the inverse
    mirrors the forward's dataflow — so the whole round trip is exactly
    TWO all-to-alls and ZERO all-gathers (the restore the 1-D transposed
    pipeline must *skip*, slab never pays at all).
    """
    dsize = mesh.shape[data_axis] if data_axis else 1

    @jax.jit
    def run(a, v):  # a: (B, R, C), v: (BK, R, C) complex, BK in {1, B}
        b = a.shape[0]
        bk = v.shape[0]
        rc = a.shape[1] * a.shape[2]
        bspec = data_axis if (data_axis and b % dsize == 0) else None
        vspec = bspec if bk == b else None

        def body(al, vl):
            ba = al.shape[0]
            # ---- forward, both operands stacked: ONE all-to-all ----------
            zc = jnp.concatenate([al, vl], axis=0)   # (BA+BK, R/D, C)
            zc = _local_axis_fft(zc, 2, inverse=False)
            zc = jax.lax.all_to_all(zc, axis, split_axis=2, concat_axis=1,
                                    tiled=True)      # (BA+BK, R, C/D)
            zc = _local_axis_fft(zc, 1, inverse=False)
            # ---- pointwise in the slab's resident layout -----------------
            prod = zc[:ba] * zc[ba:]                 # BK==1 broadcasts
            # ---- inverse: mirrored dataflow, ONE all-to-all --------------
            prod = _local_axis_fft(prod, 1, inverse=True)
            prod = jax.lax.all_to_all(prod, axis, split_axis=1,
                                      concat_axis=2, tiled=True)
            prod = _local_axis_fft(prod, 2, inverse=True)
            return prod / rc                         # (BA, R/D, C)

        return shard_map(
            body, mesh=mesh,
            in_specs=(P(bspec, axis, None), P(vspec, axis, None)),
            out_specs=P(bspec, axis, None),
            check_vma=False)(a, v)

    return run


@functools.lru_cache(maxsize=None)
def _rconv2_pair_fn(mesh: Mesh, axis: str, data_axis: str | None):
    """Real-input :func:`_conv2_pair_fn`: both operands run the packed
    half-spectrum forward stacked on the batch, the pointwise product
    lives on the C/2+1 surviving bins (natural order — the Hermitian
    logic stays inside the forward/inverse passes), and the mirrored
    inverse brings back the real grid. Still exactly TWO all-to-alls and
    ZERO all-gathers, at the padded half width ``Cp = C/2 + D`` — roughly
    half the bytes of the complex round trip on the same grid."""
    shards = mesh.shape[axis]
    dsize = mesh.shape[data_axis] if data_axis else 1

    @jax.jit
    def run(a, v):  # a: (B, R, C), v: (BK, R, C) real, BK in {1, B}
        b = a.shape[0]
        bk = v.shape[0]
        rr = a.shape[1]
        bspec = data_axis if (data_axis and b % dsize == 0) else None
        vspec = bspec if bk == b else None

        def body(al, vl):
            ba = al.shape[0]
            half = al.shape[-1] // 2
            # ---- forward, both operands stacked: ONE all-to-all ----------
            zc = jnp.concatenate([al, vl], axis=0)   # (BA+BK, R/D, C) real
            hc = _rfft_cols(zc)                      # (BA+BK, R/D, C/2+1)
            hc = jnp.pad(hc, ((0, 0), (0, 0), (0, shards - 1)))
            hc = jax.lax.all_to_all(hc, axis, split_axis=2, concat_axis=1,
                                    tiled=True)      # (BA+BK, R, Cp/D)
            hc = _local_axis_fft(hc, 1, inverse=False)
            # ---- pointwise on the half spectrum --------------------------
            prod = hc[:ba] * hc[ba:]                 # BK==1 broadcasts
            # ---- inverse: mirrored dataflow, ONE all-to-all --------------
            prod = _local_axis_fft(prod, 1, inverse=True) / rr
            prod = jax.lax.all_to_all(prod, axis, split_axis=1,
                                      concat_axis=2, tiled=True)
            return _irfft_cols(prod[..., :half + 1])  # (BA, R/D, C) real

        return shard_map(
            body, mesh=mesh,
            in_specs=(P(bspec, axis, None), P(vspec, axis, None)),
            out_specs=P(bspec, axis, None),
            check_vma=False)(a, v)

    return run


def _crop2(full, sa: tuple[int, int], sv: tuple[int, int], mode: str):
    """numpy convolve mode cropping applied per transform axis."""
    from .spectral import _crop  # per-axis 1-D crop

    out = _crop(full, sa[1], sv[1], mode)
    out = jnp.swapaxes(out, -1, -2)
    out = _crop(out, sa[0], sv[0], mode)
    return jnp.swapaxes(out, -1, -2)


def fft_convolve2(a, v, mesh: Mesh | None = None, *, mode: str = "full",
                  axis: str = FFT_AXIS,
                  data_axis: str | None = _AUTO) -> jax.Array:
    """2-D linear convolution over the last two axes via the slab round
    trip — ``jnp.convolve`` mode semantics (full/same/valid) applied per
    axis, batched over leading dims.

    ``v`` is one kernel ``(Kr, Kc)`` shared by the whole batch or a
    per-signal batch matching ``a``'s leading dims; real inputs give a
    real result. On a mesh the fused pipeline is exactly two all-to-alls
    and zero all-gathers (kernel spectra ride the forward transpose
    stacked on the batch; the product comes back through the mirrored
    inverse) — modeled by :func:`collective_volume_nd` and asserted
    against the HLO in ``benchmarks/fft_distributed.py``. When BOTH
    operands are real the round trip runs the packed half-spectrum
    pipeline (:func:`_rconv2_pair_fn` — same two all-to-alls at roughly
    half the bytes, ``collective_volume_nd(real=True)``) whenever the
    padded grid is :func:`rslab_feasible`. Without a mesh it runs the
    local transforms.
    """
    from .spectral import _next_pow2, _pad_tail, _result_dtypes

    a = jnp.asarray(a)
    v = jnp.asarray(v)
    if a.ndim < 2 or v.ndim < 2:
        raise ValueError("fft_convolve2 needs 2-D operands")
    cdtype, real = _result_dtypes(a, v)
    rdtype = jnp.float64 if cdtype == jnp.dtype(jnp.complex128) \
        else jnp.float32
    # real operands stay real: the packing trick does the complex lift
    a = a.astype(rdtype if real else cdtype)
    v = v.astype(rdtype if real else cdtype)
    sa = (a.shape[-2], a.shape[-1])
    sv = (v.shape[-2], v.shape[-1])
    mesh = _resolve_mesh(mesh, axis)
    shards = mesh.shape[axis] if mesh is not None else 1
    # pad each axis to a power of two >= the linear size (and >= the shard
    # count, the slab divisibility floor)
    nr = max(_next_pow2(sa[0] + sv[0] - 1), shards)
    nc = max(_next_pow2(sa[1] + sv[1] - 1), shards)
    ap = _pad_tail(jnp.swapaxes(_pad_tail(a, nc), -1, -2), nr)
    ap = jnp.swapaxes(ap, -1, -2)
    vp = _pad_tail(jnp.swapaxes(_pad_tail(v, nc), -1, -2), nr)
    vp = jnp.swapaxes(vp, -1, -2)
    if mesh is None or shards == 1:
        if real and nc % 2 == 0:
            fa = _local_axis_fft(_rfft_cols(ap), -2, inverse=False)
            fv = _local_axis_fft(_rfft_cols(vp), -2, inverse=False)
            full = _irfft_cols(
                _local_axis_fft(fa * fv, -2, inverse=True) / nr)
        else:
            full = _local_fftn(
                _local_fftn(ap.astype(cdtype), 2, inverse=False)
                * _local_fftn(vp.astype(cdtype), 2, inverse=False),
                2, inverse=True)
    else:
        daxis = _resolve_data_axis(mesh, data_axis)
        lead = ap.shape[:-2]
        a3 = ap.reshape((-1, nr, nc))
        v3 = vp.reshape((-1, nr, nc))
        if v3.shape[0] not in (1, a3.shape[0]):
            raise ValueError(
                f"kernel batch must be 1 or match the signal batch "
                f"({a3.shape[0]}), got {v3.shape[0]}")
        if real and rslab_feasible((nr, nc), shards):
            full = _rconv2_pair_fn(mesh, axis, daxis)(a3, v3)
        else:
            full = _conv2_pair_fn(mesh, axis, daxis)(
                a3.astype(cdtype), v3.astype(cdtype))
        full = full.reshape(lead + (nr, nc))
    out = _crop2(full[..., :sa[0] + sv[0] - 1, :sa[1] + sv[1] - 1],
                 sa, sv, mode)
    return out.real if real else out

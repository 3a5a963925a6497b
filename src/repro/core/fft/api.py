"""cuFFT-style plan/execute API: one :class:`FFTSpec` -> a cached
:class:`FFTPlan` executor for the whole FFT stack.

The paper's core engineering idea is template-based codegen: every kernel
decision is captured once in a small parameter set and reused — which is
also how its baseline exposes FFTs (``cufftPlanMany`` -> ``cufftExec*``).
This module is the mesh-level analogue. An :class:`FFTSpec` is a frozen,
hashable description of a transform (shape, dtype, rank, mesh, decomposition,
digit order, fault-tolerance config); :func:`plan` resolves everything ONCE —
mesh axes, :func:`~repro.core.fft.multidim.choose_decomp`,
:func:`~repro.core.fft.distributed.resolve_abft_groups`, the local
:class:`~repro.core.fft.plan.Plan`, the resident PartitionSpecs — and hands
back an :class:`FFTPlan` whose executors (``plan.fft / ifft / ft_fft /
convolve / correlate / power_spectrum``) are bound to the already-built
jitted shard_map pipelines, so repeated serve traffic never re-resolves or
retraces.

Every public entry point of the stack (``kernels.ops``, ``core.fft
.extensions``, ``core.fft.spectral``, ``launch.serve``) funnels through
here: they build (or look up, via the LRU plan cache) a spec and invoke the
plan executor, so there is exactly one dispatch path from a user call to a
shard_map pipeline. The legacy per-call kwarg piles on those entry points
remain as compat shims that emit a one-shot
:class:`FFTKwargDeprecationWarning`.
"""
from __future__ import annotations

import dataclasses
import functools
import sys
import warnings

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh

from repro.core import plan as planbase
from repro.core.plan import FTConfig

from . import multidim
from .distributed import (_AUTO, FFT_AXIS, _resolve_data_axis, _resolve_mesh,
                          choose_chunks, collective_volume, distributed_fft,
                          ft_distributed_fft, make_dist_plan,
                          resolve_abft_groups, resolve_chunks)

__all__ = ["FFTSpec", "FTConfig", "FFTPlan", "plan", "spec_for",
           "plan_cache_info", "plan_cache_clear", "plan_cache_keys",
           "FFTKwargDeprecationWarning", "reset_deprecation_warnings"]

_COMPLEX_DTYPES = ("complex64", "complex128")


class FFTKwargDeprecationWarning(DeprecationWarning):
    """The legacy per-call kwarg pile (``mesh=``, ``natural_order=``,
    ``decomp=``, ``groups=``, ...) on ``kernels.ops`` entry points is
    deprecated in favor of ``plan(FFTSpec(...))`` executors."""


_warned_entries: set[tuple] = set()


def reset_deprecation_warnings() -> None:
    """Clear the one-shot :class:`FFTKwargDeprecationWarning` state so the
    next legacy-kwarg call warns again. For test isolation (pair with
    ``warnings.catch_warnings``): the one-shot set is process-global, so
    without a reset only the first test touching an entry point ever sees
    the warning."""
    _warned_entries.clear()


def warn_deprecated_kwargs(entry: str, names) -> None:
    """One-shot deprecation warning for a legacy kwarg path, keyed by entry
    point AND call site (the frame ``stacklevel=3`` attributes the warning
    to) — two different legacy callers each get their own warning, repeat
    calls from the same line stay silent."""
    try:
        fr = sys._getframe(2)
        key = (entry, fr.f_code.co_filename, fr.f_lineno)
    except ValueError:                 # shallow stack (exotic embedding)
        key = (entry,)
    if key in _warned_entries:
        return
    _warned_entries.add(key)
    warnings.warn(
        f"{entry}({', '.join(sorted(names))}=...) is deprecated: build an "
        f"FFTSpec once and call plan(spec).{entry.rsplit('.', 1)[-1]}(x) "
        f"(see repro.core.fft.api) — the plan resolves mesh/decomp/ABFT "
        f"layout once and caches the jitted executor",
        FFTKwargDeprecationWarning, stacklevel=3)


# FTConfig now lives in the op-agnostic plan layer (repro.core.plan): the
# same config object describes the checked variant of any plan family —
# this FFT instantiation and the GEMM plans in repro.core.gemm. Re-exported
# here (and from repro.core.fft) for compatibility.


@dataclasses.dataclass(frozen=True)
class FFTSpec:
    """Frozen, hashable description of one batched FFT workload.

    ``shape`` is the full operand shape — leading batch dims plus the last
    ``rank`` transform axes. ``dtype`` must be a complex dtype (executors
    coerce real inputs). ``mesh`` (with an ``axis`` mesh axis) selects the
    distributed pipelines; ``decomp`` picks slab/pencil for ``rank >= 2``
    (``"auto"`` = the :func:`~repro.core.fft.multidim.choose_decomp`
    communication-model heuristic, resolved once at plan build).
    ``natural_order=False`` is the FFTW-MPI transposed pairing (see
    ``core.fft.distributed``). ``ft`` attaches an :class:`FTConfig`;
    ``interpret`` routes local power-of-two paths through the Pallas block
    kernel. Specs are value objects: equal specs hash equal and hit the
    same cached :class:`FFTPlan`.

    ``chunks`` is the multi-transaction pipelining knob: split the batch
    (1-D / spectral; ABFT plans split whole checksum groups) or the
    pencil cube into that many transactions so transaction i's
    all-to-alls overlap transaction i+1's local Stockham passes. ``1`` =
    bulk-synchronous (the default), ``0`` = auto — an ft plan reuses
    ``FTConfig.transactions``, otherwise :func:`~repro.core.fft
    .distributed.choose_chunks` picks from the modeled all-to-all bytes.
    The plan resolves the effective count once (clamped so every
    transaction stays shard- and group-divisible; slab/local/real paths
    are bulk-synchronous and resolve to 1); results are bitwise-identical
    for every chunk count.

    ``real=True`` declares the OPERAND real-valued: ``shape`` stays the
    full real shape, ``dtype`` is the complex precision the half spectrum
    carries (``complex64``/``complex128``), and the plan binds the
    ``rfft/irfft`` (rank 1) or ``rfft2/irfft2`` (rank 2) executors — the
    packed half-length transforms that move about half the C2C path's
    collective bytes. Real plans are natural-order only (the Hermitian
    unpack indexes bins by ``k``) and their ft pipeline is the rank-2 slab
    (the 1-D real path has none).
    """

    shape: tuple[int, ...]
    dtype: str = "complex64"
    rank: int = 1
    mesh: Mesh | None = None
    axis: str = FFT_AXIS
    data_axis: str | None = _AUTO
    decomp: str = "auto"
    natural_order: bool = True
    ft: FTConfig | None = None
    interpret: bool | None = None
    real: bool = False
    chunks: int = 1

    def __post_init__(self):
        shape = tuple(int(s) for s in self.shape)
        if not shape or any(s <= 0 for s in shape):
            raise ValueError(f"FFTSpec.shape must be a non-empty tuple of "
                             f"positive sizes, got {self.shape!r}")
        object.__setattr__(self, "shape", shape)
        dt = jnp.dtype(self.dtype).name
        if dt not in _COMPLEX_DTYPES:
            raise ValueError(
                f"FFTSpec.dtype must be one of {_COMPLEX_DTYPES} (executors "
                f"coerce real inputs), got {self.dtype!r}")
        object.__setattr__(self, "dtype", dt)
        if self.rank not in (1, 2, 3):
            raise ValueError(f"FFTSpec.rank must be 1, 2, or 3, "
                             f"got {self.rank!r}")
        if len(shape) < self.rank:
            raise ValueError(f"FFTSpec.shape {shape} has fewer axes than "
                             f"rank={self.rank}")
        if self.mesh is not None and self.axis not in self.mesh.axis_names:
            raise ValueError(
                f"FFTSpec.axis {self.axis!r} is not an axis of the mesh "
                f"{tuple(self.mesh.axis_names)} — build the mesh with "
                f"launch.mesh.make_fft_mesh or pass the right axis name")
        if self.data_axis not in (None, _AUTO) and self.mesh is not None \
                and self.data_axis not in self.mesh.axis_names:
            raise ValueError(
                f"FFTSpec.data_axis {self.data_axis!r} is not an axis of "
                f"the mesh {tuple(self.mesh.axis_names)}")
        if self.rank == 1:
            if self.decomp != "auto":
                raise ValueError(
                    f"FFTSpec.decomp is a multi-dimensional knob (rank >= "
                    f"2); rank-1 transforms are always the pencil digit "
                    f"split — got decomp={self.decomp!r}")
        elif self.decomp not in ("auto", "slab", "pencil", "local"):
            raise ValueError(f"FFTSpec.decomp must be auto|slab|pencil|"
                             f"local, got {self.decomp!r}")
        if self.ft is not None and not isinstance(self.ft, FTConfig):
            raise ValueError(f"FFTSpec.ft must be an FTConfig, "
                             f"got {type(self.ft).__name__}")
        if not isinstance(self.chunks, int) or isinstance(self.chunks, bool) \
                or self.chunks < 0:
            raise ValueError(
                f"FFTSpec.chunks must be a non-negative int (0 = auto, 1 = "
                f"bulk-synchronous, k = k transactions), got "
                f"{self.chunks!r}")
        if self.real:
            if self.rank == 3:
                raise ValueError(
                    "real plans are rank 1 (rfft) or rank 2 (rfft2); rank=3 "
                    "has no real pipeline yet")
            if not self.natural_order:
                raise ValueError(
                    "real plans are natural-order only — the Hermitian "
                    "unpack indexes half-spectrum bins by k, which the "
                    "transposed digit pairing scrambles")
            if self.ft is not None and self.rank != 2:
                raise ValueError(
                    "the 1-D real path has no ft pipeline — fault-tolerant "
                    "real transforms are the rank-2 slab (rfft2 with "
                    "FFTSpec(rank=2, real=True, ft=...))")

    # -- convenience ------------------------------------------------------

    @property
    def np_dtype(self) -> np.dtype:
        return np.dtype(self.dtype)

    @property
    def tshape(self) -> tuple[int, ...]:
        """The transform axes (last ``rank`` entries of ``shape``)."""
        return self.shape[-self.rank:]

    @property
    def batch(self) -> int:
        """Total signals: product of the leading (batch) dims."""
        return int(np.prod(self.shape[:-self.rank], dtype=np.int64)) \
            if len(self.shape) > self.rank else 1


def spec_for(x, *, rank: int = 1, mesh: Mesh | None = None,
             axis: str = FFT_AXIS, data_axis: str | None = _AUTO,
             decomp: str = "auto", natural_order: bool = True,
             ft: FTConfig | None = None,
             interpret: bool | None = None, real: bool = False,
             chunks: int = 1) -> FFTSpec:
    """Build the :class:`FFTSpec` describing ``x``'s transform.

    With ``mesh=None`` the mesh is inferred from ``x``'s committed sharding
    (the legacy auto-dispatch contract of ``kernels.ops``): an operand
    already laid out over an ``axis`` mesh plans distributed. On a C2C spec
    real dtypes map to ``complex64`` — exactly the coercion the legacy
    entry points applied; on a *real* spec (``real=True``) the operand's
    precision is KEPT: ``float64`` signals plan a ``complex128`` half
    spectrum.
    """
    x = jnp.asarray(x)
    if mesh is None:
        from repro.parallel.fft_sharding import infer_fft_mesh
        mesh = infer_fft_mesh(x, axis)
    dt = x.dtype
    if not jnp.issubdtype(dt, jnp.complexfloating):
        dt = jnp.dtype(jnp.complex128 if (real and dt == jnp.float64)
                       else jnp.complex64)
    return FFTSpec(shape=tuple(x.shape), dtype=jnp.dtype(dt).name, rank=rank,
                   mesh=mesh, axis=axis, data_axis=data_axis, decomp=decomp,
                   natural_order=natural_order, ft=ft, interpret=interpret,
                   real=real, chunks=chunks)


def _feasible_1d(n: int, shards: int) -> bool:
    """Whether an n-point transform can pencil-split over ``shards``."""
    return (n > 0 and not (n & (n - 1)) and shards > 0
            and not (shards & (shards - 1)) and n >= shards * shards)


@planbase.register_plan_type(FFTSpec)
class FFTPlan(planbase.Plan):
    """Pre-resolved executor bundle for one :class:`FFTSpec` — the FFT
    instantiation of the op-agnostic plan layer (:mod:`repro.core.plan`).

    The constructor does every per-call resolution the legacy kwarg paths
    repeated — mesh/axis validation, decomposition choice, ABFT group
    layout, local plan, PartitionSpecs, the analytic collective-volume
    model — and binds the executors to the cached jitted shard_map
    pipelines underneath, so ``plan.fft(x)`` is a straight dispatch.
    Construct via :func:`plan` (LRU-cached on the spec), not directly.
    """

    def __init__(self, spec: FFTSpec):
        super().__init__(spec)
        self.rank = spec.rank
        self.tshape = spec.tshape
        self.batch = spec.batch
        self.n = int(np.prod(self.tshape, dtype=np.int64))
        mesh = _resolve_mesh(spec.mesh, spec.axis)
        self.sharded = mesh is not None and mesh.shape[spec.axis] > 1
        if spec.decomp == "local":
            # an explicit local ask is honored even on a sharded mesh (the
            # legacy distributed_fftn contract) — the plan is fully local
            self.sharded = False
        self.mesh = mesh if self.sharded else None
        self.shards = mesh.shape[spec.axis] if self.sharded else 1
        self.daxis = (_resolve_data_axis(mesh, spec.data_axis)
                      if self.sharded else None)
        self.dsize = mesh.shape[self.daxis] if self.daxis else 1
        ft = spec.ft
        self.groups = None
        if ft is not None:
            if self.rank == 3:
                raise ValueError("fault-tolerant transforms are 1-D and "
                                 "2-D (slab) only; rank=3 has no ft "
                                 "pipeline yet")
            if self.sharded:
                # groups are a mesh-path knob; on the local fused-kernel
                # path they are documented no-ops (transactions grouping
                # applies instead), so they are not resolved or validated
                self.groups = resolve_abft_groups(
                    self.batch, groups=ft.groups, group_size=ft.group_size,
                    data_shards=self.dsize)
        self._rdtype = jnp.dtype(
            jnp.float64 if spec.dtype == "complex128" else jnp.float32)
        # effective transaction count; the chunked builders re-resolve this
        # (slab / local / real paths stay bulk-synchronous)
        self.chunks = 1
        if self.rank == 1:
            self._build_1d()
        else:
            self._build_nd()

    # -- construction -----------------------------------------------------

    def _build_1d(self):
        from repro.kernels import ops as _ops  # lazy: ops imports this module
        from repro.parallel.fft_sharding import layout_specs

        spec = self.spec
        n = self.tshape[0]
        if spec.real:
            self._build_1d_real(n)
            return
        if not self.sharded:
            self.decomp = "local"
            self.dist_plan = None
            self.in_spec = self.out_spec = None
            self._fwd = functools.partial(_ops._fft_impl, inverse=False,
                                          interpret=spec.interpret)
            self._inv = functools.partial(_ops._fft_impl, inverse=True,
                                          interpret=spec.interpret)
            self.volume = None
            return
        self.decomp = "pencil"
        # raises with the exact constraint (pow2, N >= shards^2) when the
        # split is infeasible — the spec-validation contract of plan()
        self.dist_plan = make_dist_plan(n, self.shards, spec.axis)
        self.in_spec, self.out_spec = layout_specs(
            1, "pencil", axis=spec.axis, data_axis=self.daxis)
        ft = spec.ft
        base = collective_volume(
            n, max(self.batch, 1), self.shards,
            itemsize=self.spec.np_dtype.itemsize,
            ft=ft is not None, natural_order=spec.natural_order,
            groups=self.groups or 1, data_shards=self._model_dsize())
        # transactions split local batch rows (whole checksum groups on an
        # ft plan): resolve spec.chunks once against the per-device count
        rows = ((self.groups if ft is not None else max(self.batch, 1))
                // max(self._model_dsize(), 1))
        requested = spec.chunks
        if requested == 0:          # auto: ft reuses FTConfig.transactions
            requested = (ft.transactions if ft is not None
                         else choose_chunks(base["all_to_all_bytes"], rows))
        self.chunks = resolve_chunks(rows, max(1, requested)) if rows else 1
        from .distributed import _dist_fft_fn, _dist_ifft_t_fn
        self._fwd = _dist_fft_fn(self.mesh, spec.axis, False,
                                 spec.natural_order, self.daxis, self.chunks,
                                 spec.interpret)
        if spec.natural_order:
            self._inv = _dist_fft_fn(self.mesh, spec.axis, True, True,
                                     self.daxis, self.chunks, spec.interpret)
        else:
            _dist_ifft_t_fn(self.mesh, spec.axis, self.daxis,
                            self.chunks)                       # pre-build
            self._inv = functools.partial(
                distributed_fft, mesh=self.mesh, axis=spec.axis,
                inverse=True, natural_order=False, data_axis=self.daxis,
                chunks=self.chunks)
        if ft is not None:
            from .distributed import _ft_dist_fft_fn
            _ft_dist_fft_fn(self.mesh, spec.axis, float(ft.threshold),
                            bool(ft.correct), bool(spec.natural_order),
                            self.groups, self.daxis,
                            self.chunks)  # pre-build/trace cache
        self.volume = collective_volume(
            n, max(self.batch, 1), self.shards,
            itemsize=self.spec.np_dtype.itemsize,
            ft=ft is not None, natural_order=spec.natural_order,
            groups=self.groups or 1, data_shards=self._model_dsize(),
            chunks=self.chunks)

    def _build_1d_real(self, n: int):
        """Bind the rank-1 real executors (rfft/irfft).

        The transform itself is ``extensions.rfft``'s packed half-length
        C2C; this plan resolves once whether that half-length transform can
        pencil-split over the mesh, and models its collective volume
        (``collective_volume(real=True)`` — half the C2C bytes).
        """
        from repro.parallel.fft_sharding import layout_specs

        spec = self.spec
        self._fwd = self._inv = None          # C2C executors raise on real
        self.dist_plan = None
        self.in_spec = self.out_spec = None
        self.volume = None
        self.decomp = "local"
        if self.sharded and n % 2 == 0 \
                and _feasible_1d(n // 2, self.shards):
            self.decomp = "pencil"
            self.dist_plan = make_dist_plan(n // 2, self.shards, spec.axis)
            self.in_spec, self.out_spec = layout_specs(
                1, "pencil", axis=spec.axis, data_axis=self.daxis)
            self.volume = collective_volume(
                n, max(self.batch, 1), self.shards,
                itemsize=self.spec.np_dtype.itemsize,
                natural_order=True, data_shards=self._model_dsize(),
                real=True)
            # rfft/irfft themselves are bulk-synchronous; the chunk knob
            # feeds the spectral consumer (convolve/correlate round trip)
            rows = max(self.batch, 1) // max(self._model_dsize(), 1)
            requested = spec.chunks
            if requested == 0:
                requested = choose_chunks(
                    self.volume["all_to_all_bytes"], rows)
            self.chunks = resolve_chunks(rows, max(1, requested)) \
                if rows else 1

    def _build_nd_real(self):
        """Bind the rank-2 real executors (rfft2/irfft2).

        slab -> the native half-spectrum pipeline (packed row pass, padded
        ``C/2 + D``-column transpose, ~half the C2C bytes — see
        ``multidim.distributed_rfft2``); pencil -> the composed two-pass
        form (1-D distributed rfft over columns, C2C over rows — correct on
        meshes the slab cannot tile); no mesh -> local.
        """
        from repro.parallel.fft_sharding import layout_specs

        spec = self.spec
        ft = spec.ft
        cc = self.tshape[-1]
        if not self.sharded:
            if ft is not None:
                raise ValueError(
                    "fault-tolerant rfft2 runs the sharded grouped ABFT on "
                    "the slab transpose: the spec needs a mesh with an "
                    f"'{spec.axis}' axis of >= 2 devices")
            self.decomp = "local"
            self.in_spec = self.out_spec = None
            self.volume = None
            self._rfwd = multidim._local_rfft2
            self._rinv = functools.partial(multidim._local_irfft2, cc=cc)
            return
        decomp = spec.decomp
        feasible = multidim.rslab_feasible(self.tshape, self.shards)
        if decomp == "auto":
            decomp = (multidim.DECOMP_SLAB if feasible
                      else multidim.DECOMP_PENCIL)
        if ft is not None and decomp != multidim.DECOMP_SLAB:
            raise ValueError(
                "grouped ABFT rides the slab inter-axis transpose: an ft "
                f"real spec needs decomp='slab' (or 'auto'), got {decomp!r}")
        if decomp == multidim.DECOMP_SLAB and not feasible:
            raise ValueError(
                f"infeasible decomp: the real slab needs power-of-two axes "
                f"with {self.shards} | {self.tshape[0]} and "
                f"{self.shards} | {self.tshape[-1]}//2, got {self.tshape} — "
                f"use decomp='pencil' (the composed real path) or a smaller "
                f"fft axis")
        self.decomp = decomp
        if decomp == multidim.DECOMP_SLAB:
            self.in_spec, self.out_spec = layout_specs(
                2, decomp, axis=spec.axis, data_axis=self.daxis, real=True)
            self._rfwd = functools.partial(
                multidim.distributed_rfft2, mesh=self.mesh, axis=spec.axis,
                data_axis=self.daxis)
            self._rinv = functools.partial(
                multidim.distributed_irfft2, mesh=self.mesh, axis=spec.axis,
                data_axis=self.daxis)
            # pre-build the jitted pipelines (first execution stays a
            # straight dispatch, the plan contract)
            multidim._rslab_fft2_fn(self.mesh, spec.axis, self.daxis)
            multidim._rslab_ifft2_fn(self.mesh, spec.axis, self.daxis)
            if ft is not None:
                multidim._ft_rslab_fft2_fn(
                    self.mesh, spec.axis, float(ft.threshold),
                    bool(ft.correct), self.groups, self.daxis)
            self.volume = multidim.collective_volume_nd(
                self.tshape, max(self.batch, 1), self.shards, decomp=decomp,
                itemsize=self.spec.np_dtype.itemsize, ft=ft is not None,
                groups=self.groups or 1, data_shards=self._model_dsize(),
                natural_order=True, real=True)
            return
        # pencil: the composed two-pass real path — its collectives are the
        # 1-D pieces', so there is no single nd volume model to bind
        self.in_spec = self.out_spec = None
        self.volume = None
        self._rfwd = functools.partial(
            multidim._composed_rfft2, mesh=self.mesh, axis=spec.axis,
            data_axis=self.daxis)
        self._rinv = functools.partial(
            multidim._composed_irfft2, cc=cc, mesh=self.mesh, axis=spec.axis,
            data_axis=self.daxis)

    def _build_nd(self):
        from repro.parallel.fft_sharding import layout_specs

        spec = self.spec
        ft = spec.ft
        if spec.real:
            self._fwd = self._inv = None      # C2C executors raise on real
            self._build_nd_real()
            return
        if not self.sharded:
            if ft is not None:
                raise ValueError(
                    "fault-tolerant 2-D transforms run the sharded grouped "
                    "ABFT on the slab transpose: the spec needs a mesh with "
                    f"an '{spec.axis}' axis of >= 2 devices")
            self.decomp = "local"
            self.in_spec = self.out_spec = None
            self.volume = None
            self._fwd = functools.partial(
                multidim._local_fftn, ndim=self.rank, inverse=False,
                interpret=spec.interpret)
            self._inv = functools.partial(
                multidim._local_fftn, ndim=self.rank, inverse=True,
                interpret=spec.interpret)
            return
        decomp = spec.decomp
        if decomp == "auto":
            decomp = multidim.choose_decomp(
                self.tshape, self.mesh, batch=self.batch, ft=ft is not None,
                natural_order=spec.natural_order, axis=spec.axis,
                data_axis=spec.data_axis)
        if ft is not None and decomp != multidim.DECOMP_SLAB:
            raise ValueError(
                "grouped ABFT rides the slab inter-axis transpose: an ft "
                f"spec needs decomp='slab' (or 'auto'), got {decomp!r}")
        if decomp == multidim.DECOMP_SLAB \
                and not multidim.slab_feasible(self.tshape, self.shards):
            raise ValueError(
                f"infeasible decomp: slab needs power-of-two axes with "
                f"{self.shards} | {self.tshape[0]} and "
                f"{self.shards} | {self.tshape[-1]}, got {self.tshape} — "
                f"use decomp='pencil' or a smaller fft axis")
        if decomp == multidim.DECOMP_PENCIL and not multidim.pencil_feasible(
                self.tshape, self.shards, self.dsize):
            raise ValueError(
                f"infeasible decomp: pencil needs "
                f"{self.tshape[-1]} >= fft^2={self.shards ** 2} and "
                f"{self.tshape[-2]} >= data^2={self.dsize ** 2} "
                f"(power-of-two axes), got {self.tshape} — use "
                f"decomp='slab' or a smaller mesh")
        self.decomp = decomp
        if decomp == multidim.DECOMP_PENCIL:
            base = multidim.collective_volume_nd(
                self.tshape, max(self.batch, 1), self.shards, decomp=decomp,
                itemsize=self.spec.np_dtype.itemsize,
                data_shards=self.dsize, natural_order=spec.natural_order)
            requested = spec.chunks
            if requested == 0:
                requested = choose_chunks(base["all_to_all_bytes"],
                                          self._nd_chunk_rows())
            self.chunks = self._effective_nd_chunks(max(1, requested))
        self.in_spec, self.out_spec = layout_specs(
            self.rank, decomp, axis=spec.axis, data_axis=self.daxis)
        self._fwd = functools.partial(
            multidim.distributed_fftn, mesh=self.mesh, ndim=self.rank,
            decomp=decomp, inverse=False, natural_order=spec.natural_order,
            axis=spec.axis, data_axis=self.daxis, interpret=spec.interpret,
            chunks=self.chunks)
        self._inv = functools.partial(
            multidim.distributed_fftn, mesh=self.mesh, ndim=self.rank,
            decomp=decomp, inverse=True, natural_order=spec.natural_order,
            axis=spec.axis, data_axis=self.daxis, interpret=spec.interpret,
            chunks=self.chunks)
        # pre-build the jitted pipelines so first execution never resolves
        if decomp == multidim.DECOMP_SLAB:
            multidim._slab_fftn_fn(self.mesh, spec.axis, self.rank, False,
                                   self.daxis)
            multidim._slab_fftn_fn(self.mesh, spec.axis, self.rank, True,
                                   self.daxis)
        else:
            multidim._pencil_fftn_fn(self.mesh, spec.axis, self.rank, False,
                                     bool(spec.natural_order), self.daxis,
                                     self.chunks)
        if ft is not None:
            multidim._ft_slab_fft2_fn(
                self.mesh, spec.axis, float(ft.threshold), bool(ft.correct),
                self.groups, self.daxis)
        self.volume = multidim.collective_volume_nd(
            self.tshape, max(self.batch, 1), self.shards, decomp=decomp,
            itemsize=self.spec.np_dtype.itemsize, ft=ft is not None,
            groups=self.groups or 1,
            data_shards=(self._model_dsize()
                         if decomp == multidim.DECOMP_SLAB else self.dsize),
            natural_order=spec.natural_order, chunks=self.chunks)

    # -- helpers ----------------------------------------------------------

    def _nd_chunk_rows(self) -> int:
        """The size of the axis pencil transactions would split: the
        (replicated) batch when it has rows, else the first leading local
        transform axis (the rank-3 single-grid case)."""
        for size in (max(self.batch, 1),) + tuple(self.tshape[:-2]):
            if size > 1:
                return size
        return 1

    def _effective_nd_chunks(self, requested: int) -> int:
        """Mirror of the pencil pipeline's chunk-axis selection
        (``multidim._chunk_apply``): the first candidate axis that can
        carry more than one transaction decides the effective count."""
        for size in (max(self.batch, 1),) + tuple(self.tshape[:-2]):
            ce = resolve_chunks(size, requested)
            if ce > 1:
                return ce
        return 1

    def _model_dsize(self) -> int:
        """The data-shard count the pipeline actually uses: the batch (and
        its checksum groups, on an ft plan) must divide over the data axis,
        else the batch replicates and the model must say so too."""
        if self.dsize <= 1 or self.batch % self.dsize:
            return 1
        if self.groups is not None and self.groups % self.dsize:
            return 1
        return self.dsize

    def _coerce(self, x):
        """Match the plan dtype: a C2C plan coerces real inputs to its
        complex dtype (the legacy contract); a real plan REJECTS complex
        operands and casts to its real precision."""
        x = jnp.asarray(x)
        if self.spec.real:
            if jnp.issubdtype(x.dtype, jnp.complexfloating):
                raise ValueError(
                    f"a real plan takes a real operand, got {x.dtype} — "
                    f"build a C2C FFTSpec (real=False) for complex signals")
            return x if x.dtype == self._rdtype else x.astype(self._rdtype)
        if x.dtype != self.spec.np_dtype:
            x = x.astype(self.spec.np_dtype)
        return x

    def _check_tshape(self, x):
        if tuple(x.shape[-self.rank:]) != self.tshape:
            raise ValueError(
                f"operand transform axes {tuple(x.shape[-self.rank:])} do "
                f"not match the planned {self.tshape} — build a new "
                f"FFTSpec (plans are shape-specialized, like cufftPlanMany)")

    def shard(self, x):
        """Place ``x`` into the plan's resident input layout (a no-op
        relayout on an unsharded plan, or when the plan has no resident
        layout — local-fallback / composed real paths)."""
        x = self._coerce(x)
        if not self.sharded or self.in_spec is None:
            return x
        from repro.parallel.fft_sharding import shard_grid, shard_signals
        if self.rank == 1:
            return shard_signals(x, self.mesh, self.spec.axis,
                                 data_axis=self.daxis)
        return shard_grid(x, self.mesh, self.rank, decomp=self.decomp,
                          axis=self.spec.axis, data_axis=self.daxis)

    # -- executors --------------------------------------------------------

    def fft(self, x):
        """Forward transform over the planned axes (complex in/out)."""
        if self.spec.real:
            raise ValueError(
                "this plan is real-input — its executors are rfft/irfft "
                "(rfft2/irfft2); build a C2C FFTSpec (real=False) for "
                "fft/ifft")
        x = self._coerce(x)
        self._check_tshape(x)
        return self._fwd(x)

    def ifft(self, x):
        """Inverse transform (1/N normalized); a transposed-order plan
        consumes the forward's transposed-digit output (TRANSPOSED_IN)."""
        if self.spec.real:
            raise ValueError(
                "this plan is real-input — its executors are rfft/irfft "
                "(rfft2/irfft2); build a C2C FFTSpec (real=False) for "
                "fft/ifft")
        x = self._coerce(x)
        self._check_tshape(x)
        return self._inv(x)

    # rank-2/3 spellings (same executors; the rank lives in the spec)
    def fft2(self, x):
        if self.rank < 2:
            raise ValueError("fft2 needs a rank>=2 FFTSpec")
        return self.fft(x)

    def ifft2(self, x):
        if self.rank < 2:
            raise ValueError("ifft2 needs a rank>=2 FFTSpec")
        return self.ifft(x)

    fftn = fft2
    ifftn = ifft2

    # -- real-input executors ---------------------------------------------

    def rfft(self, x):
        """Real-input forward transform -> the ``(..., N/2+1)``-bin half
        spectrum (rank 1) or ``(..., R, C/2+1)`` (rank 2). Requires a real
        plan (``FFTSpec(real=True)``); complex operands are rejected, not
        silently truncated."""
        if not self.spec.real:
            raise ValueError(
                "this plan is C2C — build the FFTSpec with real=True for "
                "rfft/irfft")
        x = self._coerce(x)
        self._check_tshape(x)
        if self.rank == 1:
            from . import extensions
            return extensions.rfft(
                x, mesh=self.mesh if self.sharded else None,
                axis=self.spec.axis, data_axis=self.daxis)
        return self._rfwd(x)

    def irfft(self, y):
        """Inverse of :meth:`rfft`: half spectrum -> the planned real
        shape. The spectrum's transform axes must be the planned shape's
        Hermitian half (``last axis -> n//2 + 1`` bins)."""
        if not self.spec.real:
            raise ValueError(
                "this plan is C2C — build the FFTSpec with real=True for "
                "rfft/irfft")
        y = jnp.asarray(y)
        want = self.tshape[:-1] + (self.tshape[-1] // 2 + 1,)
        if tuple(y.shape[-self.rank:]) != want:
            raise ValueError(
                f"half-spectrum axes {tuple(y.shape[-self.rank:])} do not "
                f"match the planned {want} (the Hermitian half of "
                f"{self.tshape}) — build a new FFTSpec")
        if y.dtype != self.spec.np_dtype:
            y = y.astype(self.spec.np_dtype)
        if self.rank == 1:
            from . import extensions
            return extensions.irfft(
                y, n=self.tshape[0],
                mesh=self.mesh if self.sharded else None,
                axis=self.spec.axis, data_axis=self.daxis)
        return self._rinv(y)

    # rank-2 spellings (same executors; the rank lives in the spec)
    def rfft2(self, x):
        if self.rank != 2:
            raise ValueError("rfft2 needs a rank-2 FFTSpec")
        return self.rfft(x)

    def irfft2(self, y):
        if self.rank != 2:
            raise ValueError("irfft2 needs a rank-2 FFTSpec")
        return self.irfft(y)

    def ft_fft(self, x, *, inject=None, bs=None):
        """Fault-tolerant forward transform (requires ``spec.ft``).

        On a mesh: the sharded grouped two-side ABFT
        (:class:`~repro.core.fft.distributed.DistFFTResult`, 1-D pencil or
        2-D slab). Locally (rank 1): the fused-kernel pipeline
        (:class:`~repro.kernels.ops.FTFFTResult`); ``bs`` is its per-call
        block-size override.
        """
        ft = self.spec.ft
        if ft is None:
            raise ValueError("this plan has no FTConfig — set FFTSpec.ft")
        x = self._coerce(x)
        self._check_tshape(x)
        b = int(np.prod(x.shape[:-self.rank], dtype=np.int64)) \
            if x.ndim > self.rank else 1
        if b != self.batch:
            raise ValueError(
                f"operand batch {b} does not match the planned {self.batch} "
                f"— the ABFT group layout (G={self.groups}) was resolved "
                f"for the spec's batch; build a new FFTSpec")
        if self.spec.real:
            # rank-2 slab only (spec validation): the grouped two-side
            # ABFT on the Hermitian-symmetric checksum layout
            return multidim.ft_distributed_rfft2(
                x, self.mesh, axis=self.spec.axis, threshold=ft.threshold,
                correct=ft.correct, inject=inject, groups=self.groups,
                data_axis=self.daxis,
                recompute_uncorrectable=ft.recompute_uncorrectable)
        if self.rank == 1 and not self.sharded:
            from repro.kernels import ops as _ops
            return _ops._ft_fft_local(x, inject=inject,
                                      **self._ft_local_kw(bs))
        if self.rank == 1:
            return ft_distributed_fft(
                x, self.mesh, axis=self.spec.axis, threshold=ft.threshold,
                correct=ft.correct, natural_order=self.spec.natural_order,
                inject=inject, groups=self.groups, data_axis=self.daxis,
                recompute_uncorrectable=ft.recompute_uncorrectable,
                chunks=self.chunks)
        return multidim.ft_distributed_fft2(
            x, self.mesh, axis=self.spec.axis, threshold=ft.threshold,
            correct=ft.correct, inject=inject, groups=self.groups,
            data_axis=self.daxis,
            recompute_uncorrectable=ft.recompute_uncorrectable)

    def _ft_local_kw(self, bs):
        """The static arguments of the local fused-kernel ABFT executor."""
        ft = self.spec.ft
        return dict(transactions=ft.transactions, bs=bs,
                    per_signal=ft.per_signal, encoding=ft.encoding,
                    threshold=ft.threshold, correct=ft.correct,
                    interpret=self.spec.interpret)

    def executor_hlo(self, x, *, op: str = "fft", inject=None) -> str:
        """The compiled module text of the executor ``getattr(self, op)(x,
        ...)`` runs on a rank-1 C2C plan: on a local plan ``_fft_impl`` for
        ``"fft"`` and ``_ft_fft_local`` for ``"ft_fft"`` (``inject`` as the
        caller passes it; an array or a ``jax.ShapeDtypeStruct``); on a
        sharded plan the pencil pipeline for ``"fft"``, where ``x`` carries
        the operand's sharding (a sharded array, or a ``ShapeDtypeStruct``
        with its ``sharding``), which decides the ingest relayout. For a
        sharded plan the text is the per-device SPMD module.

        It lowers the jitted function the plan calls with the static
        arguments the plan binds, so on a warm compile cache it loads the
        executable the calls ran and its instruction names are those of
        their profile's device ops.
        ``repro.analysis.hlo.op_scopes(plan.executor_hlo(x))`` maps each op
        of an XProf trace to its program stage (``fft.split``,
        ``fft.layout``, ``fft.kernel``, ``fft.join``, ``abft.decode``,
        ``fft.local``, ``fft.exchange``, ...).
        """
        if self.rank != 1 or self.spec.real:
            raise ValueError("executor_hlo covers the local rank-1 C2C "
                             "executors and the sharded 'fft'; this is "
                             f"{self!r}")
        if self.sharded:
            if op != "fft":
                raise ValueError("executor_hlo covers a sharded plan's "
                                 f"'fft' executor, not {op!r}")
            if getattr(x, "sharding", None) is None:
                raise ValueError("a sharded plan's executor depends on the "
                                 "operand's sharding: pass the operand or a "
                                 "ShapeDtypeStruct with its sharding")
            return self._fwd.lower(x).compile().as_text()
        if op == "fft":
            lowered = self._fwd.func.lower(x, **self._fwd.keywords)
        elif op == "ft_fft":
            if self.spec.ft is None:
                raise ValueError("this plan has no FTConfig — set FFTSpec.ft")
            from repro.kernels import ops as _ops
            lowered = _ops._ft_fft_local.lower(x, inject=inject,
                                               **self._ft_local_kw(None))
        else:
            raise ValueError(f"op must be 'fft' or 'ft_fft', got {op!r}")
        return lowered.compile().as_text()

    # -- spectral consumers ----------------------------------------------

    def convolve(self, a, v, *, mode: str = "full"):
        """Linear convolution via the planned transform size: 1-D through
        the transposed spectral pipeline, 2-D through the slab round trip.
        The plan's last-axis size(s) must equal the padded FFT size
        (:func:`~repro.core.fft.spectral._conv_nfft` of the operands)."""
        if self.rank == 1:
            return self._spectral_pair(a, v, conj_kernel=False, mode=mode)
        if self.rank == 2:
            return multidim.fft_convolve2(
                a, v, self.mesh, mode=mode, axis=self.spec.axis,
                data_axis=self.daxis)
        raise ValueError("convolve supports rank 1 and 2 plans")

    def correlate(self, a, v, *, mode: str = "full"):
        """Cross-correlation (``np.correlate`` conventions), rank-1 only."""
        if self.rank != 1:
            raise ValueError("correlate is 1-D only")
        return self._spectral_pair(a, v, conj_kernel=True, mode=mode)

    def _spectral_pair(self, a, v, *, conj_kernel: bool, mode: str):
        from . import spectral as spec_mod
        a = jnp.asarray(a)
        v = jnp.asarray(v)
        _, real = spec_mod._result_dtypes(a, v)
        la, lv = a.shape[-1], v.shape[-1]
        nfft = spec_mod._conv_nfft(la, lv, self.mesh, self.spec.axis)
        if nfft != self.tshape[0]:
            raise ValueError(
                f"operand lengths ({la}, {lv}) need an nfft={nfft} plan, "
                f"but this plan is for {self.tshape[0]} — build the spec "
                f"with spectral.conv_spec / fft_convolve")
        out_len = nfft if conj_kernel else la + lv - 1
        full = spec_mod._spectral_pair(
            spec_mod._pad_tail(a, nfft), spec_mod._pad_tail(v, nfft),
            self.mesh, self.spec.axis, self.daxis, conj_kernel=conj_kernel,
            out_len=out_len, chunks=self.chunks)
        if conj_kernel:
            full = jnp.roll(full, lv - 1, axis=-1)[..., :la + lv - 1]
        out = spec_mod._crop(full, la, lv, mode)
        return out.real if real else out

    def power_spectrum(self, x):
        """Periodogram ``|X|^2 / N``; on a transposed-order plan the bins
        stay in the transposed digit order (the cheap choice). A real plan
        returns the one-sided ``N/2+1``-bin spectrum via the packed rfft
        (always natural order)."""
        if self.spec.real:
            return (jnp.abs(self.rfft(x)) ** 2) / self.n
        x = self._coerce(x)
        self._check_tshape(x)
        if self.rank == 1 and not self.sharded:
            from . import stockham
            y = stockham.fft(x)     # the legacy local spectral path
        else:
            y = self._fwd(x)
        return (jnp.abs(y) ** 2) / self.n

    # -- introspection ----------------------------------------------------

    def __repr__(self):
        s = self.spec
        return (f"FFTPlan(shape={s.shape}, dtype={s.dtype}, rank={s.rank}, "
                f"decomp={self.decomp!r}, shards={self.shards}, "
                f"data={self.dsize}, groups={self.groups}, "
                f"chunks={self.chunks}, "
                f"natural_order={s.natural_order}, ft={s.ft is not None})")


def plan(spec: FFTSpec) -> FFTPlan:
    """Build (or fetch from the shared plan-layer LRU cache) the
    :class:`FFTPlan` for ``spec``. Equal specs return the SAME plan object,
    whose executors are bound to already-traced pipelines — the cuFFT
    ``plan once, exec hot`` contract for serve traffic."""
    if not isinstance(spec, FFTSpec):
        raise TypeError(f"plan() takes an FFTSpec, got "
                        f"{type(spec).__name__}")
    return planbase.plan(spec)


# the plan cache is shared across plan families (repro.core.plan); these
# aliases keep the historical FFT-side spelling working
plan_cache_info = planbase.plan_cache_info
plan_cache_clear = planbase.plan_cache_clear
plan_cache_keys = planbase.plan_cache_keys

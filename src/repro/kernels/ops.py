"""Public jit'd wrappers around the Pallas kernels.

* :func:`fft` / :func:`ifft` — batched FFT over the last axis; single-pass
  sizes run one Pallas block kernel, larger sizes compose the paper's
  kernel-level N1xN2(xN3) passes around it.
* :func:`ft_fft` — the full TurboFFT pipeline: fused two-sided-ABFT kernel ->
  detect -> locate -> delayed batched correction. Returns an
  :class:`FTFFTResult` with the corrected outputs and the FT telemetry.

``interpret=None`` resolves by platform: the kernels compile with Mosaic on
a TPU and run in the Pallas interpreter elsewhere (the CPU test suite).
``tests/test_tpu_compile.py`` compiles them for a described v5e.
"""
from __future__ import annotations

import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import abft
from repro.core.fft import api as fft_api
from repro.core.fft import factors as fft_factors
from repro.core.fft.plan import Plan, make_plan
from repro.core.fft.stockham import block_fft_stages

from ._platform import resolve_interpret
from .stockham import block_fft_pallas
from .stockham_abft import abft_fft_pallas

__all__ = ["fft", "ifft", "fft2", "ifft2", "ft_fft", "FTFFTResult"]

# Sentinel marking a legacy kwarg the caller did not pass. The entry points
# below are compat shims over the plan API (core.fft.api): explicitly
# passing a non-default value for one of _DEPRECATED_DEFAULTS emits a
# one-shot FFTKwargDeprecationWarning pointing at plan(FFTSpec(...)).
_UNSET = object()

_DEPRECATED_DEFAULTS = dict(mesh=None, axis="fft", natural_order=True,
                            decomp="auto", groups=None, group_size=None,
                            recompute_uncorrectable=False)


def _resolve_legacy(entry: str, kw: dict) -> dict:
    out = {}
    deprecated = []
    for k, v in kw.items():
        default = _DEPRECATED_DEFAULTS[k]
        if v is _UNSET:
            out[k] = default
        else:
            out[k] = v
            if not (v is default or v == default):
                deprecated.append(k)
    if deprecated:
        fft_api.warn_deprecated_kwargs(f"kernels.ops.{entry}", deprecated)
    return out


@jax.named_scope("fft.split")
def _split(x):
    ftype = jnp.float64 if x.dtype == jnp.complex128 else jnp.float32
    return jnp.real(x).astype(ftype), jnp.imag(x).astype(ftype)


@jax.named_scope("fft.join")
def _join(yr, yi):
    return jax.lax.complex(yr, yi)


def _pad_batch(x, bs):
    b = x.shape[0]
    pad = (-b) % bs
    if pad:
        with jax.named_scope("fft.layout"):
            x = jnp.concatenate([x, jnp.zeros((pad,) + x.shape[1:],
                                              x.dtype)])
    return x, b


def _block_fft_c(x2d, *, inverse, interpret, bs=None):
    """Single-pass complex block FFT via the Pallas kernel. (B, N)->(B, N)."""
    xr, xi = _split(x2d)
    plan = make_plan(x2d.shape[-1], batch=x2d.shape[0],
                     itemsize=xr.dtype.itemsize, inverse=inverse)
    if bs is None:
        bs = plan.bs
    xr, b0 = _pad_batch(xr, bs)
    xi, _ = _pad_batch(xi, bs)
    yr, yi = block_fft_pallas(xr, xi, plan=dataclasses.replace(plan),
                              bs=bs, inverse=inverse, interpret=interpret)
    y = _join(yr, yi)
    with jax.named_scope("fft.layout"):
        return y[:b0]


def _fft_multipass(x2d, plan: Plan, *, inverse, interpret):
    """Kernel-level N1 x N2 (x N3) composition (paper Fig. 3) around the
    Pallas block kernel: per pass, one transposed batched block FFT + twiddle.
    """
    facs = plan.kernel_factors
    n = plan.n
    b = x2d.shape[0]

    def rec(z, facs):
        nloc = z.shape[-1]
        if len(facs) == 1:
            return _block_fft_c(z.reshape(-1, nloc),
                                inverse=inverse,
                                interpret=interpret).reshape(z.shape)
        f1 = facs[0]
        f2 = int(np.prod(facs[1:]))
        with jax.named_scope("fft.layout"):
            zz = z.reshape(z.shape[:-1] + (f1, f2))
            zz = jnp.swapaxes(zz, -1, -2)  # (..., f2, f1)
        zz = rec(zz, (f1,))
        with jax.named_scope("fft.layout"):
            zz = jnp.swapaxes(zz, -1, -2)  # (..., f1, f2)
        with jax.named_scope("fft.twiddle"):
            tw = jnp.asarray(fft_factors.stage_twiddle(f1, f2,
                                                       inverse=inverse),
                             dtype=z.dtype)
            zz = zz * tw
        zz = rec(zz, facs[1:])
        with jax.named_scope("fft.layout"):
            zz = jnp.swapaxes(zz, -1, -2)
            return zz.reshape(z.shape)

    return rec(x2d, facs)


@functools.partial(jax.jit, static_argnames=("inverse", "interpret"))
def _fft_impl(x, *, inverse=False, interpret=None):
    interpret = resolve_interpret(interpret)
    shape = x.shape
    n = shape[-1]
    x2d = x.reshape((-1, n))
    plan = make_plan(n, batch=x2d.shape[0], inverse=inverse)
    if plan.num_passes == 1:
        y = _block_fft_c(x2d, inverse=inverse, interpret=interpret)
    else:
        # each pass's block kernel applies its own 1/f_i; prod(f_i) = n
        y = _fft_multipass(x2d, plan, inverse=inverse, interpret=interpret)
    return y.reshape(shape)


def fft(x, *, interpret=None, mesh=_UNSET, axis=_UNSET,
        natural_order=_UNSET):
    """TurboFFT forward transform over the last axis (complex in/out).

    Compat shim over the plan API: the call builds (or LRU-hits) the
    :class:`~repro.core.fft.api.FFTPlan` for the operand and runs its
    cached executor. An ``x`` committed to an ``fft``-axis mesh plans
    distributed (the auto-dispatch contract); passing ``mesh=`` /
    ``natural_order=`` explicitly still works but is deprecated — build an
    :class:`~repro.core.fft.api.FFTSpec` once and call ``plan(spec).fft``.

    Sharding-based auto-dispatch only works on concrete (eager) operands:
    inside an enclosing ``jax.jit`` the tracer carries no committed
    sharding, so build the spec with ``mesh=`` there.
    """
    x = jnp.asarray(x)
    if not jnp.issubdtype(x.dtype, jnp.complexfloating):
        x = x.astype(jnp.complex64)
    kw = _resolve_legacy("fft", dict(mesh=mesh, axis=axis,
                                     natural_order=natural_order))
    spec = fft_api.spec_for(x, rank=1, mesh=kw["mesh"], axis=kw["axis"],
                            natural_order=kw["natural_order"],
                            interpret=interpret)
    return fft_api.plan(spec).fft(x)


def ifft(x, *, interpret=None, mesh=_UNSET, axis=_UNSET,
         natural_order=_UNSET):
    """Inverse transform; ``natural_order=False`` on the mesh path consumes
    TRANSPOSED-order input (the ``fft(..., natural_order=False)`` output)
    and returns natural-order time domain with no all-gather. Compat shim
    over ``plan(spec).ifft`` — see :func:`fft`."""
    x = jnp.asarray(x)
    if not jnp.issubdtype(x.dtype, jnp.complexfloating):
        x = x.astype(jnp.complex64)
    kw = _resolve_legacy("ifft", dict(mesh=mesh, axis=axis,
                                      natural_order=natural_order))
    spec = fft_api.spec_for(x, rank=1, mesh=kw["mesh"], axis=kw["axis"],
                            natural_order=kw["natural_order"],
                            interpret=interpret)
    return fft_api.plan(spec).ifft(x)


def fft2(x, *, interpret=None, mesh=_UNSET, axis=_UNSET,
         natural_order=_UNSET, decomp=_UNSET):
    """2-D FFT over the last two axes (complex in/out).

    Compat shim over a rank-2 plan: ``decomp`` picks the slab or pencil
    layout (``"auto"`` = the :func:`~repro.core.fft.multidim.choose_decomp`
    communication-model heuristic, resolved once at plan build).
    ``natural_order=False`` keeps a pencil result in the per-axis
    transposed digit order (a no-op for slab, whose natural order is
    free). On the local path odd / non-power-of-two axes are supported,
    and ``interpret`` routes power-of-two axes through the Pallas block
    kernel. The mesh kwargs are deprecated — see :func:`fft`.
    """
    x = jnp.asarray(x)
    if not jnp.issubdtype(x.dtype, jnp.complexfloating):
        x = x.astype(jnp.complex64)
    kw = _resolve_legacy("fft2", dict(mesh=mesh, axis=axis,
                                      natural_order=natural_order,
                                      decomp=decomp))
    spec = fft_api.spec_for(x, rank=2, mesh=kw["mesh"], axis=kw["axis"],
                            natural_order=kw["natural_order"],
                            decomp=kw["decomp"], interpret=interpret)
    return fft_api.plan(spec).fft(x)


def ifft2(x, *, interpret=None, mesh=_UNSET, axis=_UNSET,
          natural_order=_UNSET, decomp=_UNSET):
    """Inverse 2-D transform (1/(R*C) normalized); ``natural_order=False``
    on the mesh pencil path consumes the ``fft2(..., natural_order=False)``
    transposed-digit output with no redistribution. Compat shim — see
    :func:`fft2`."""
    x = jnp.asarray(x)
    if not jnp.issubdtype(x.dtype, jnp.complexfloating):
        x = x.astype(jnp.complex64)
    kw = _resolve_legacy("ifft2", dict(mesh=mesh, axis=axis,
                                       natural_order=natural_order,
                                       decomp=decomp))
    spec = fft_api.spec_for(x, rank=2, mesh=kw["mesh"], axis=kw["axis"],
                            natural_order=kw["natural_order"],
                            decomp=kw["decomp"], interpret=interpret)
    return fft_api.plan(spec).ifft(x)


# ---------------------------------------------------------------------------
# Fault-tolerant FFT (the paper's co-design)
# ---------------------------------------------------------------------------


@jax.tree_util.register_dataclass
@dataclasses.dataclass
class FTFFTResult:
    """Outputs + fault-tolerance telemetry of one ft_fft call."""

    y: jax.Array                 # (B, N) corrected outputs
    delta: jax.Array             # (B,) per-signal left-checksum divergence
    group_score: jax.Array       # (G,) right-checksum divergence per group
    flagged: jax.Array           # (G,) bool — group detected an error
    location: jax.Array          # (G,) int32 — decoded corrupted signal id
    corrected: jax.Array         # scalar — number of corrections applied


def ft_fft(
    x: jax.Array,
    *,
    transactions: int = 4,
    bs: int | None = None,
    per_signal: bool = False,
    encoding: str = "wang",
    threshold: float = 1e-4,
    correct: bool = True,
    interpret: bool | None = None,
    inject: jax.Array | None = None,
    mesh=_UNSET,
    axis=_UNSET,
    groups=_UNSET,
    group_size=_UNSET,
    natural_order=_UNSET,
    recompute_uncorrectable=_UNSET,
):
    """Fault-tolerant forward FFT with online detection and correction.

    ``per_signal=False`` is the threadblock/multi-transaction scheme of the
    paper (detection via group checksums, location via the e3 encoding);
    ``per_signal=True`` additionally computes thread-level per-signal
    checksums (more compute, finer localization).

    Compat shim over an ft plan (``FFTSpec(ft=FTConfig(...))``): an ``x``
    committed to an ``fft``-axis mesh (or an explicit — deprecated —
    ``mesh=``) runs the sharded grouped two-side ABFT
    (``core.fft.distributed.ft_distributed_fft``) and returns its
    :class:`~repro.core.fft.distributed.DistFFTResult`; ``groups``/
    ``group_size`` pick the checksum group count (the mesh-level
    multi-transaction knob; auto = one group per data shard), and
    ``inject`` follows the distributed 7-field layout. On the local path
    those knobs are no-ops and the fused-kernel ``transactions`` grouping
    applies, with the kernel's 6-field ``inject`` layout.
    """
    x = jnp.asarray(x)
    if not jnp.issubdtype(x.dtype, jnp.complexfloating):
        x = x.astype(jnp.complex64)
    kw = _resolve_legacy("ft_fft", dict(
        mesh=mesh, axis=axis, groups=groups, group_size=group_size,
        natural_order=natural_order,
        recompute_uncorrectable=recompute_uncorrectable))
    ft = fft_api.FTConfig(
        threshold=threshold, correct=correct, groups=kw["groups"],
        group_size=kw["group_size"],
        recompute_uncorrectable=kw["recompute_uncorrectable"],
        transactions=transactions, per_signal=per_signal, encoding=encoding)
    spec = fft_api.spec_for(x, rank=1, mesh=kw["mesh"], axis=kw["axis"],
                            natural_order=kw["natural_order"], ft=ft,
                            interpret=interpret)
    return fft_api.plan(spec).ft_fft(x, inject=inject, bs=bs)


@functools.partial(
    jax.jit,
    static_argnames=("transactions", "bs", "per_signal", "encoding",
                     "threshold", "interpret", "correct"),
)
def _ft_fft_local(
    x: jax.Array,
    *,
    transactions: int = 4,
    bs: int | None = None,
    per_signal: bool = False,
    encoding: str = "wang",
    threshold: float = 1e-4,
    correct: bool = True,
    interpret: bool | None = None,
    inject: jax.Array | None = None,
) -> FTFFTResult:
    """The single-device fused-kernel pipeline behind :func:`ft_fft`."""
    interpret = resolve_interpret(interpret)
    b, n = x.shape
    xr, xi = _split(x)
    plan = make_plan(n, batch=b, itemsize=xr.dtype.itemsize)
    if bs is None:
        bs = plan.bs
    # batches not divisible by bs are padded with zero signals (the same
    # treatment _block_fft_c applies) — zero rows contribute nothing to the
    # group checksums and their 1-based location ids lie beyond the real
    # batch, so detection/location/correction are unaffected; the padded
    # rows are sliced back off below. (b // bs alone silently dropped the
    # remainder signals.)
    xr, _ = _pad_batch(xr, bs)
    xi, _ = _pad_batch(xi, bs)
    bp = xr.shape[0]
    tiles = bp // bs
    txn = min(transactions, tiles)
    while tiles % txn:
        txn -= 1
    yr, yi, delta, cs = abft_fft_pallas(
        xr, xi, plan=plan, bs=bs, transactions=txn, per_signal=per_signal,
        encoding=encoding, interpret=interpret, inject=inject)
    y = _join(yr, yi)

    sums = abft.GroupChecksums.from_packed(cs)
    verdict = abft.detect_locate(
        sums, forward=lambda c: block_fft_stages(c), threshold=threshold)
    with jax.named_scope("abft.decode"):
        # the kernel's location ids count from each group's first signal
        first = jnp.arange(tiles // txn, dtype=jnp.int32) * (txn * bs)
        verdict = dataclasses.replace(verdict,
                                      location=verdict.location + first)
    if correct:
        y, _ = abft.apply_correction(y, verdict)
    with jax.named_scope("fft.layout"):
        y, delta = y[:b], delta[:b]
    with jax.named_scope("abft.decode"):
        corrected = jnp.sum(verdict.flagged.astype(jnp.int32))
    return FTFFTResult(
        y=y,
        delta=delta,
        group_score=verdict.error_score,
        flagged=verdict.flagged,
        location=verdict.location,
        corrected=corrected,
    )

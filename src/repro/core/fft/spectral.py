"""Transposed-order spectral consumers: convolution, correlation, spectra.

The paper's own FFT use cases (convolution, spectrum estimation) never look
at the *order* of the frequency bins — they apply a pointwise op and come
straight back. "Coded FFT and Its Communication Overhead" (Jeong et al.)
shows the natural-order redistribution dominates distributed FFT cost, so
everything here stays in the FFTW-MPI transposed digit order
``y[k1*N2 + k2] = X[k1 + N1*k2]`` end-to-end on the sharded path:

    forward  : pass 1 -> twiddle -> all-to-all -> pass 2   (transposed out)
    pointwise: multiply / conjugate-multiply / |.|^2       (shard-local)
    inverse  : pass A -> conj twiddle -> all-to-all -> pass B (transposed in)

The two transforms of a convolution's operands ride ONE all-to-all (the
kernel's rows are stacked onto the batch before the collective), and the
inverse's all-to-all splits the batch axis, so the whole round trip is
exactly TWO all-to-all ops and ZERO all-gathers — verified against the
post-partitioning HLO by benchmarks/fft_distributed.py and modeled by
:func:`repro.core.fft.distributed.spectral_volume`.

When BOTH operands are real the kernel does not even ride as stacked rows:
it rides the *imaginary part* of one packed operand ``p = a + i*v``, since
``p (.) p = a (.) a - v (.) v + 2i (a (.) v)`` makes the convolution the
imaginary half of one self-product — the kernel's forward rows vanish from
the collectives entirely (``spectral_volume(real=True)``). Correlation of
real operands is the same trick on the circularly reversed kernel.

On a 2-D batch x pencil mesh (``launch.mesh.make_fft_mesh(shards, data)``)
batch rows shard over ``data`` while signal pencils shard over ``fft``; the
collectives stay within the ``fft`` axis. Without a mesh every function
falls back to the local Stockham transforms (same math, natural order).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, PartitionSpec as P
from jax import shard_map

from . import factors
from .distributed import (_AUTO, FFT_AXIS, _local_fft, _pad_batch_rows,
                          _resolve_data_axis, _resolve_mesh, make_dist_plan,
                          resolve_chunks)
from .stockham import block_fft_stages, fft as _fft, ifft as _ifft

__all__ = ["fft_convolve", "correlate", "power_spectrum", "conv_spec"]


def _next_pow2(n: int) -> int:
    return 1 << max(0, (n - 1)).bit_length()


def _result_dtypes(a, v):
    """(compute complex dtype, whether the result should be real)."""
    wide = (a.dtype in (jnp.float64, jnp.complex128)
            or v.dtype in (jnp.float64, jnp.complex128))
    cdtype = jnp.complex128 if wide else jnp.complex64
    real = not (jnp.issubdtype(a.dtype, jnp.complexfloating)
                or jnp.issubdtype(v.dtype, jnp.complexfloating))
    return cdtype, real


def _crop(full, la: int, lv: int, mode: str):
    """numpy convolve/correlate mode cropping of the length la+lv-1 result.

    The signal axis of ``full`` is unsharded on every path (the inverse
    leaves whole signals resident per device), so these slices are local.
    """
    lmin, lmax = min(la, lv), max(la, lv)
    if mode == "full":
        return full
    if mode == "same":
        start = (lmin - 1) // 2
        return full[..., start:start + lmax]
    if mode == "valid":
        return full[..., lmin - 1:lmax]
    raise ValueError(f"mode must be full|same|valid, got {mode!r}")


# ---------------------------------------------------------------------------
# the fused sharded pipeline
# ---------------------------------------------------------------------------


def _chunk_within_blocks(x, shards: int, ce: int, ci: int):
    """Chunk ``ci`` of ``x``'s leading rows, taken WITHIN each of the
    ``shards`` destination blocks of a batch-splitting all-to-all.

    The inverse's a2a sends block e (rows ``[e*B/D, (e+1)*B/D)``) to device
    e; a chunk that took contiguous rows would land device d with a
    permutation of the bulk path's rows. Striding the selection — sub-rows
    ``[ci*w, (ci+1)*w)`` of EVERY block — keeps each device's chunks landing
    in bulk order, so concatenating chunk outputs reproduces the
    bulk-synchronous result bitwise.
    """
    blk = x.shape[0] // shards
    w = blk // ce
    blocks = x.reshape((shards, blk) + x.shape[1:])
    return blocks[:, ci * w:(ci + 1) * w].reshape((-1,) + x.shape[1:])


@functools.lru_cache(maxsize=None)
def _spectral_pair_fn(mesh: Mesh, axis: str, data_axis: str | None,
                      conj_kernel: bool, chunks: int = 1):
    """forward(a, v) -> pointwise product -> inverse, one shard_map body.

    Keeping everything in a single body is what pins the collective count:
    the kernel's forward transform shares the batch all-to-all with the
    signals', and no intermediate ever leaves the pencil layout.

    ``chunks > 1`` splits the round trip into that many overlapped batch
    transactions (``2 * chunks`` all-to-alls, same total bytes): chunk i's
    collectives hide behind chunk i+1's local Stockham passes. A broadcast
    kernel transforms once — it rides transaction 0's forward collective
    and its spectrum is reused by every later chunk. Results are
    bitwise-identical to the bulk path for every chunk count.
    """
    shards = mesh.shape[axis]
    dsize = mesh.shape[data_axis] if data_axis else 1

    @jax.jit
    def run(a, v):  # a: (B, N), v: (BK, N) complex, BK in {1, B}
        b, n = a.shape
        bk = v.shape[0]
        plan = make_dist_plan(n, shards, axis)
        n1, n2 = plan.n1, plan.n2
        tw_f = jnp.asarray(factors.stage_twiddle(n1, n2, inverse=False),
                           dtype=a.dtype)
        tw_i = jnp.asarray(factors.stage_twiddle(n1, n2, inverse=True),
                           dtype=a.dtype)
        za = a.reshape((b, n1, n2))
        zv = v.reshape((bk, n1, n2))
        bspec = data_axis if (data_axis and b % dsize == 0) else None
        vspec = bspec if bk == b else None
        bloc = b // (dsize if bspec else 1)
        if bloc % shards:
            raise ValueError(
                f"spectral pipeline needs batch divisible by "
                f"{'data*shards' if bspec else 'shards'}, got {b} — "
                f"fft_convolve/correlate pad the batch automatically")

        def body(al, vl):
            d = jax.lax.axis_index(axis)
            ba = al.shape[0]
            n2l = al.shape[-1]

            def fwd(zc):
                # stacked rows -> transposed spectra: ONE all-to-all
                zc = jnp.swapaxes(zc, -1, -2)
                zc = block_fft_stages(zc, inverse=False)  # FFT over n1
                zc = jnp.swapaxes(zc, -1, -2)
                twl = jax.lax.dynamic_slice_in_dim(tw_f, d * n2l, n2l,
                                                   axis=1)
                zc = zc * twl
                zc = jax.lax.all_to_all(zc, axis, split_axis=1,
                                        concat_axis=2,
                                        tiled=True)      # (.., n1/D, n2)
                return _local_fft(zc, inverse=False)     # FFT over n2

            def inv(prod):
                # transposed product -> natural time domain: batch-split a2a
                prod = _local_fft(prod, inverse=True)    # IFFT over k2
                n1l = prod.shape[-2]
                twi = jax.lax.dynamic_slice_in_dim(tw_i, d * n1l, n1l,
                                                   axis=0)
                prod = prod * twi
                prod = jax.lax.all_to_all(prod, axis, split_axis=0,
                                          concat_axis=1,
                                          tiled=True)    # (BA/D, n1, n2)
                prod = jnp.swapaxes(prod, -1, -2)
                prod = _local_fft(prod, inverse=True)    # IFFT over k1
                prod = jnp.swapaxes(prod, -1, -2)        # natural (n1, n2)
                return prod.reshape(prod.shape[0], n) / n

            def product(ya, yv):
                if conj_kernel:
                    yv = jnp.conj(yv)
                return ya * yv                           # BK==1 broadcasts

            ce = resolve_chunks(ba // shards, chunks)
            if ce == 1:
                zc = fwd(jnp.concatenate([al, vl], axis=0))
                return inv(product(zc[:ba], zc[ba:]))
            per_signal = vl.shape[0] == ba
            outs, yv = [], None
            for ci in range(ce):
                ac = _chunk_within_blocks(al, shards, ce, ci)
                if per_signal:
                    vc = _chunk_within_blocks(vl, shards, ce, ci)
                    zc = fwd(jnp.concatenate([ac, vc], axis=0))
                    ya, yvc = zc[:ac.shape[0]], zc[ac.shape[0]:]
                elif yv is None:
                    # broadcast kernel: spectrum computed once, rides
                    # transaction 0's forward collective
                    zc = fwd(jnp.concatenate([ac, vl], axis=0))
                    ya, yv = zc[:ac.shape[0]], zc[ac.shape[0]:]
                    yvc = yv
                else:
                    ya, yvc = fwd(ac), yv
                outs.append(inv(product(ya, yvc)))
            return jnp.concatenate(outs, axis=0)  # rows land in bulk order

        out = shard_map(
            body, mesh=mesh,
            in_specs=(P(bspec, None, axis), P(vspec, None, axis)),
            out_specs=P((bspec, axis) if bspec else axis, None),
            check_vma=False)(za, zv)
        return out

    return run


@functools.lru_cache(maxsize=None)
def _spectral_real_fn(mesh: Mesh, axis: str, data_axis: str | None,
                      chunks: int = 1):
    """forward(p) -> p*p -> inverse for ONE packed operand ``p = a + i*v``.

    Same transposed round trip as :func:`_spectral_pair_fn` but the kernel
    rides the imaginary part instead of stacked batch rows, so the forward
    all-to-all moves exactly the signal rows — no kernel payload at all.
    The caller takes ``imag(.) / 2`` of the natural-order circular product.
    ``chunks`` pipelines the batch exactly as in the pair path.
    """
    shards = mesh.shape[axis]
    dsize = mesh.shape[data_axis] if data_axis else 1

    @jax.jit
    def run(p):  # p: (B, N) complex, a + i*v packed
        b, n = p.shape
        plan = make_dist_plan(n, shards, axis)
        n1, n2 = plan.n1, plan.n2
        tw_f = jnp.asarray(factors.stage_twiddle(n1, n2, inverse=False),
                           dtype=p.dtype)
        tw_i = jnp.asarray(factors.stage_twiddle(n1, n2, inverse=True),
                           dtype=p.dtype)
        zp = p.reshape((b, n1, n2))
        bspec = data_axis if (data_axis and b % dsize == 0) else None
        bloc = b // (dsize if bspec else 1)
        if bloc % shards:
            raise ValueError(
                f"spectral pipeline needs batch divisible by "
                f"{'data*shards' if bspec else 'shards'}, got {b} — "
                f"fft_convolve/correlate pad the batch automatically")

        def body(zl):
            d = jax.lax.axis_index(axis)
            n2l = zl.shape[-1]

            def round_trip(zc):
                # ---- forward: one packed operand, ONE all-to-all ---------
                zc = jnp.swapaxes(zc, -1, -2)
                zc = block_fft_stages(zc, inverse=False)  # FFT over n1
                zc = jnp.swapaxes(zc, -1, -2)
                twl = jax.lax.dynamic_slice_in_dim(tw_f, d * n2l, n2l,
                                                   axis=1)
                zc = zc * twl
                zc = jax.lax.all_to_all(zc, axis, split_axis=1,
                                        concat_axis=2,
                                        tiled=True)      # (B, n1/D, n2)
                zc = _local_fft(zc, inverse=False)       # FFT over n2
                # ---- pointwise self-product in transposed order ----------
                prod = zc * zc                           # P[k]^2, any order
                # ---- inverse from transposed order: batch-split a2a ------
                prod = _local_fft(prod, inverse=True)    # IFFT over k2
                n1l = prod.shape[-2]
                twi = jax.lax.dynamic_slice_in_dim(tw_i, d * n1l, n1l,
                                                   axis=0)
                prod = prod * twi
                prod = jax.lax.all_to_all(prod, axis, split_axis=0,
                                          concat_axis=1,
                                          tiled=True)    # (B/D, n1, n2)
                prod = jnp.swapaxes(prod, -1, -2)
                prod = _local_fft(prod, inverse=True)    # IFFT over k1
                prod = jnp.swapaxes(prod, -1, -2)        # natural (n1, n2)
                return prod.reshape(prod.shape[0], n) / n

            ce = resolve_chunks(zl.shape[0] // shards, chunks)
            if ce == 1:
                return round_trip(zl)
            return jnp.concatenate(
                [round_trip(_chunk_within_blocks(zl, shards, ce, ci))
                 for ci in range(ce)], axis=0)

        out = shard_map(
            body, mesh=mesh,
            in_specs=(P(bspec, None, axis),),
            out_specs=P((bspec, axis) if bspec else axis, None),
            check_vma=False)(zp)
        return out

    return run


def _pad_tail(x, n: int):
    """Zero-pad the last axis to length n."""
    pad = n - x.shape[-1]
    if pad <= 0:
        return x
    widths = [(0, 0)] * (x.ndim - 1) + [(0, pad)]
    return jnp.pad(x, widths)


def _spectral_pair(a, v, mesh, axis, data_axis, *, conj_kernel: bool,
                   out_len: int, chunks: int = 1):
    """Shared driver: pad, dispatch local vs fused sharded path, crop.

    Returns the length ``out_len`` head of the circular product's inverse
    (linear results need nfft >= la + lv - 1, which callers guarantee).
    Two real operands take the packed single-transform path
    (:func:`_spectral_real`); any complex operand takes the stacked pair.
    ``chunks`` pipelines the sharded round trip (see
    :func:`_spectral_pair_fn`); the local path ignores it.
    """
    cdtype, real = _result_dtypes(a, v)
    if real:
        return _spectral_real(a, v, mesh, axis, data_axis,
                              conj_kernel=conj_kernel, out_len=out_len,
                              cdtype=cdtype, chunks=chunks)
    a = jnp.asarray(a, cdtype)
    v = jnp.asarray(v, cdtype)
    mesh = _resolve_mesh(mesh, axis)
    if mesh is None or mesh.shape[axis] == 1:
        fv = _fft(v)
        if conj_kernel:
            fv = jnp.conj(fv)
        return _ifft(_fft(a) * fv)[..., :out_len]
    daxis = _resolve_data_axis(mesh, data_axis)
    shards = mesh.shape[axis]
    dsize = mesh.shape[daxis] if daxis else 1
    lead = a.shape[:-1]
    n = a.shape[-1]
    a2d = a.reshape((-1, n))
    v2d = v.reshape((-1, n))
    b, bk = a2d.shape[0], v2d.shape[0]
    if bk not in (1, b):
        raise ValueError(
            f"kernel batch must be 1 or match the signal batch ({b}), "
            f"got {bk}")
    # pad the batch so the inverse's batch-split all-to-all divides evenly
    # (padding rows are zero signals; the slice below is free when b already
    # divides, the common serving case)
    a2d, _ = _pad_batch_rows(a2d, dsize, shards)
    if bk == b:
        v2d, _ = _pad_batch_rows(v2d, dsize, shards)
    out = _spectral_pair_fn(mesh, axis, daxis, conj_kernel,
                            int(chunks))(a2d, v2d)
    if out.shape[0] != b:
        out = out[:b]
    return out[..., :out_len].reshape(lead + (out_len,))


def _spectral_real(a, v, mesh, axis, data_axis, *, conj_kernel: bool,
                   out_len: int, cdtype, chunks: int = 1):
    """Circular product of two REAL operands via ONE packed transform.

    ``ifft(fft(a + i*v)^2) = a(.)a - v(.)v + 2i (a(.)v)``, so the circular
    convolution is ``imag(.) / 2`` of one self-product. Correlation with a
    real kernel is convolution with the circularly reversed kernel
    ``w[k] = v[-k mod n]``, so the same path serves ``conj_kernel=True``
    and the caller's roll/crop logic applies unchanged.
    """
    rdtype = jnp.float64 if cdtype == jnp.complex128 else jnp.float32
    a = jnp.asarray(a, rdtype)
    v = jnp.asarray(v, rdtype)
    if conj_kernel:
        v = jnp.concatenate([v[..., :1], v[..., 1:][..., ::-1]], axis=-1)
    p = (a + 1j * v).astype(cdtype)      # kernel rides the imaginary part
    mesh = _resolve_mesh(mesh, axis)
    if mesh is None or mesh.shape[axis] == 1:
        fp = _fft(p)
        return (jnp.imag(_ifft(fp * fp)) * 0.5)[..., :out_len]
    daxis = _resolve_data_axis(mesh, data_axis)
    shards = mesh.shape[axis]
    dsize = mesh.shape[daxis] if daxis else 1
    lead = p.shape[:-1]
    n = p.shape[-1]
    p2d = p.reshape((-1, n))
    b = p2d.shape[0]
    p2d, _ = _pad_batch_rows(p2d, dsize, shards)
    out = _spectral_real_fn(mesh, axis, daxis, int(chunks))(p2d)
    if out.shape[0] != b:
        out = out[:b]
    out = jnp.imag(out) * 0.5
    return out[..., :out_len].reshape(lead + (out_len,))


def _conv_nfft(la: int, lv: int, mesh, axis: str) -> int:
    """FFT length for a linear result: power of two >= la + lv - 1, raised
    to the mesh's minimum pencil size (shards^2) when sharded."""
    nfft = _next_pow2(la + lv - 1)
    mesh = _resolve_mesh(mesh, axis)
    if mesh is not None and mesh.shape[axis] > 1:
        nfft = max(nfft, mesh.shape[axis] ** 2)
    return nfft


# ---------------------------------------------------------------------------
# public API — spec-builder sugar over the plan executors (core.fft.api)
# ---------------------------------------------------------------------------


def conv_spec(a, v, mesh: Mesh | None = None, *, axis: str = FFT_AXIS,
              data_axis: str | None = _AUTO, chunks: int = 1):
    """The :class:`~repro.core.fft.api.FFTSpec` of the padded C2C transform
    one convolution/correlation of ``a`` with ``v`` runs: last axis padded
    to :func:`_conv_nfft`, batch dims from ``a``, compute dtype promoted
    across both operands. Build it once and reuse
    ``plan(spec).convolve/correlate`` on serve traffic. ``chunks`` is the
    multi-transaction overlap knob (see :class:`~repro.core.fft.api
    .FFTSpec`): the spectral round trip splits into that many transactions
    per all-to-all.
    """
    from . import api

    a = jnp.asarray(a)
    v = jnp.asarray(v)
    cdtype, real = _result_dtypes(a, v)
    nfft = _conv_nfft(a.shape[-1], v.shape[-1], mesh, axis)
    return api.FFTSpec(shape=a.shape[:-1] + (nfft,),
                       dtype=jnp.dtype(cdtype).name, rank=1, mesh=mesh,
                       axis=axis, data_axis=data_axis, real=real,
                       chunks=chunks)


def fft_convolve(a, v, mesh: Mesh | None = None, *, mode: str = "full",
                 axis: str = FFT_AXIS,
                 data_axis: str | None = _AUTO) -> jax.Array:
    """Linear convolution along the last axis via the transposed pipeline.

    Matches ``jnp.convolve`` (modes full/same/valid) batched over leading
    dims; ``v`` is one kernel ``(Lv,)`` shared by the whole batch or a
    per-signal batch matching ``a``'s leading dims. Real inputs give a real
    result. On a mesh the whole op lowers to exactly two all-to-alls and
    zero all-gathers (see module docstring); without one it runs the local
    Stockham transforms. Sugar over ``plan(conv_spec(a, v, ...)).convolve``.
    """
    from . import api

    return api.plan(conv_spec(a, v, mesh, axis=axis, data_axis=data_axis)
                    ).convolve(a, v, mode=mode)


def correlate(a, v, mesh: Mesh | None = None, *, mode: str = "full",
              axis: str = FFT_AXIS,
              data_axis: str | None = _AUTO) -> jax.Array:
    """Cross-correlation along the last axis: ``c[m] = sum_k a[m+k] *
    conj(v[k])`` — ``np.correlate`` conventions (modes full/same/valid),
    batched over leading dims. Same collective budget as
    :func:`fft_convolve`: the conjugated kernel spectrum is pointwise in
    transposed order too. Sugar over ``plan(conv_spec(...)).correlate``.
    """
    from . import api

    return api.plan(conv_spec(a, v, mesh, axis=axis, data_axis=data_axis)
                    ).correlate(a, v, mode=mode)


def power_spectrum(x, mesh: Mesh | None = None, *, axis: str = FFT_AXIS,
                   data_axis: str | None = _AUTO,
                   natural_order: bool | None = None,
                   real: bool = False) -> jax.Array:
    """Periodogram ``|X[k]|^2 / N`` along the last axis (real output).

    On the sharded path the bins stay in the transposed digit order by
    default (``natural_order=None`` -> False on a mesh): the |.|^2 is
    elementwise, so the whole op is ONE all-to-all and zero all-gathers.
    Order-agnostic consumers (total power, histograms, thresholds) never
    notice; pass ``natural_order=True`` to pay the redistribution and get
    numpy bin order. The local path is always natural order.

    ``real=True`` (opt-in: it changes the output SHAPE) takes a real input
    through the packed rfft and returns the one-sided ``N/2 + 1``-bin
    spectrum ``|X[k]|^2 / N`` for ``k <= N/2`` — the half-length transform
    moves about half the C2C path's bytes. One-sided bins are indexed by
    ``k``, so this path is always natural order.
    """
    from . import api

    x = jnp.asarray(x)
    mesh_r = _resolve_mesh(mesh, axis)
    on_mesh = mesh_r is not None and mesh_r.shape[axis] > 1
    if real:
        if jnp.issubdtype(x.dtype, jnp.complexfloating):
            raise ValueError(
                f"power_spectrum(real=True) takes a real input, "
                f"got {x.dtype}")
        if natural_order is False:
            raise ValueError(
                "the one-sided real spectrum is natural-order only — the "
                "Hermitian unpack indexes bins by k")
        spec = api.spec_for(x, rank=1, mesh=mesh_r, axis=axis,
                            data_axis=data_axis, real=True)
        return api.plan(spec).power_spectrum(x)
    if natural_order is None:
        natural_order = not on_mesh
    dt = x.dtype if jnp.issubdtype(x.dtype, jnp.complexfloating) \
        else jnp.complex64
    spec = api.FFTSpec(shape=tuple(x.shape), dtype=jnp.dtype(dt).name,
                       rank=1, mesh=mesh_r, axis=axis, data_axis=data_axis,
                       natural_order=natural_order)
    return api.plan(spec).power_spectrum(x)

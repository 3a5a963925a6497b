"""Mesh-sharded distributed FFT: the paper's kernel-level N1 x N2 decomposition
lifted from one device to a device mesh (pencil decomposition over shard_map).

The single-device multi-pass driver (``large.py``) folds the inter-pass
transpose into the access pattern of the next pass; across a mesh that
transpose is irreducibly a collective. "Coded FFT and Its Communication
Overhead" shows this all-to-all dominates distributed FFT cost, so the
decomposition here is chosen to need exactly ONE all-to-all regardless of how
many local radix passes each side of the split runs:

    x (B, N) viewed as (B, N1, N2), n = N2*n1 + n2, sharded over n2
      pass 1  : batched block FFT over n1      — local (columns are resident)
      twiddle : T[k1, n2] slice for this shard — local
      transpose: all-to-all splitting k1, concatenating n2 (the one collective)
      pass 2  : batched block FFT over n2      — local (rows now resident)
    output Z[k1, k2] = X[k1 + N1*k2], sharded over k1

The split reuses :func:`make_plan`'s ``kernel_factors`` (the paper's 1/2/3
HBM-pass regimes); factors beyond the first stay on the local side of the
all-to-all and run as ordinary local multi-pass FFTs.

Two-side ABFT in the sharded setting (the mesh-level analogue of the paper's
multi-transaction amortization, §4.2-4.3):

* left (per-pass) checksums — ``sum_k W[k, n] = r * delta(n)`` makes the
  column sum of every local block FFT predictable from its input; each shard
  verifies its own passes with ZERO extra traffic (``shard_delta``).
* right (batch) checksums — per checksum *group* (the mesh-level analogue of
  the paper's multi-transaction threadblocks), ``cs2_g = sum_{b in g} x_b``
  and ``cs3_g = sum_{b in g} id_b x_b`` are themselves signals, sharded
  exactly like the data. They ride through the same pipeline as two extra
  batch rows per group, so F(cs_in) costs no extra collective volume beyond
  2G/B of the data's. Detection and location compare them against checksums
  of the *computed* outputs; the only cross-device ABFT traffic is ONE psum
  of 3 scalars per group (plus a shared energy scalar), confined to the
  ``fft`` axis, so detect -> locate -> correct works even when the faulty
  element lives on another device — and G concurrent SEUs in G distinct
  groups are all repaired in a single pass. On a 2-D batch x pencil mesh the
  batch (and its groups) shard over ``data``; each data shard owns its
  groups outright, so the ft path composes with batch sharding instead of
  forcing replication.

Transposed order, both directions (the FFTW-MPI ``TRANSPOSED_OUT`` /
``TRANSPOSED_IN`` pairing): ``natural_order=False`` on the forward skips the
final redistribution and returns the digit-permuted spectrum
``y[k1*N2 + k2] = X[k1 + N1*k2]`` still sharded over ``k1``;
``natural_order=False`` on the *inverse* declares its input to be in exactly
that order and consumes it without any up-front redistribution. The inverse's
one all-to-all splits the *batch* axis instead of a signal digit, so the
natural-order time-domain result lands batch-sharded with every signal fully
resident on one device — a forward + pointwise + inverse round trip costs two
all-to-alls and ZERO all-gathers (see ``spectral.py`` for the consumers).

Mesh composition: every entry point takes an optional ``data_axis`` (default:
auto-detect a ``data`` axis on the mesh). Batch rows shard over ``data``
while the signal pencils shard over ``fft``, so independent transforms scale
along one mesh dimension while single-transform size scales along the other.
"""
from __future__ import annotations

import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
from jax import shard_map

from .large import _fft_factors
from .plan import MAX_BLOCK_N, make_plan
from .stockham import block_fft_stages

# Same guard value as core.abft.encoding.EPS (not imported: core.abft itself
# imports core.fft at package level, so importing it back would be a cycle).
EPS = 1e-30

__all__ = [
    "DistPlan", "DistFFTResult", "make_dist_plan", "distributed_fft",
    "distributed_ifft", "ft_distributed_fft", "resolve_abft_groups",
    "resolve_chunks", "choose_chunks", "collective_volume",
    "spectral_volume", "FFT_AXIS", "DATA_AXIS",
]

# Canonical mesh-axis name for the signal (pencil) dimension; see
# launch.mesh.make_fft_mesh and kernels.ops auto-dispatch.
FFT_AXIS = "fft"

# Canonical mesh-axis name for the batch dimension of a 2-D batch x pencil
# mesh (make_fft_mesh(shards, data)); auto-detected by the entry points.
DATA_AXIS = "data"

# Sentinel: auto-detect DATA_AXIS on the mesh. Pass ``data_axis=None`` to
# force batch replication even when the mesh carries a data axis.
_AUTO = "auto"

# Correctability gate on the two-side id decode: id_var is the |d2|^2-weighted
# variance of the per-element id estimates d3/d2. A single fault satisfies
# d3 == id * d2 identically, so its id_var sits at the noise floor (<< 1e-3
# for any fault strong enough to detect); two faults with distinct ids in one
# group push it to ab*(i-j)^2/(a+b)^2 — >= 0.04 until one fault carries ~25x
# the other's amplitude (at which point the weak one is near the detection
# floor anyway). Misclassification is asymmetric by design: a borderline
# single fault flagged uncorrectable costs one clean recompute, while a
# mis-corrected double fault would silently corrupt a THIRD signal.
ID_VAR_TOL = 0.04


def _resolve_data_axis(mesh, data_axis):
    """The batch mesh axis to use, or None (batch replicated).

    ``_AUTO`` picks ``DATA_AXIS`` iff the mesh carries it with size > 1; an
    explicit name is validated; ``None`` disables batch sharding.
    """
    if data_axis is None:
        return None
    if data_axis == _AUTO:
        if DATA_AXIS in mesh.axis_names and mesh.shape[DATA_AXIS] > 1:
            return DATA_AXIS
        return None
    if data_axis not in mesh.axis_names:
        raise ValueError(f"mesh {mesh.axis_names} has no '{data_axis}' axis")
    return data_axis if mesh.shape[data_axis] > 1 else None


def resolve_chunks(rows: int, chunks: int, *, granule: int = 1) -> int:
    """The largest feasible transaction count <= ``chunks`` for ``rows``.

    A chunked pipeline splits its per-shard rows (batch rows, or whole
    checksum groups, or pencil digit planes) into ``chunks`` equal
    transactions so transaction i's all-to-all overlaps transaction i+1's
    local Stockham passes — the mesh-level analogue of the paper's
    multi-transaction threadblock design. Every transaction must carry the
    same whole number of rows, and each chunk's row count must stay a
    multiple of ``granule`` (``shards`` for the batch-splitting inverse
    all-to-all, 1 elsewhere). Static Python arithmetic — safe under jit.
    """
    c = max(1, min(int(chunks), int(rows) if rows else 1))
    while c > 1 and (rows % c or (rows // c) % max(granule, 1)):
        c -= 1
    return c


# Per-transaction fixed cost of one all-to-all, in payload-equivalent bytes
# (dispatch + link latency amortized over the message). Splitting into C
# chunks exposes ~ C*L + bytes/C of communication (first chunk's transfer
# plus per-chunk launch overhead), minimized at C* = sqrt(bytes / L). 64 KiB
# is conservative for both host meshes and TPU ICI: below it, a2a time is
# latency-dominated and chunking buys nothing.
CHUNK_LATENCY_BYTES = 1 << 16


def choose_chunks(a2a_bytes: float, rows: int, *, granule: int = 1,
                  max_chunks: int = 8) -> int:
    """Auto transaction count from the collective-volume model.

    Picks the power of two nearest below ``C* = sqrt(a2a_bytes /
    CHUNK_LATENCY_BYTES)`` (the minimizer of the exposed-cost model
    ``C*L + bytes/C``), capped at ``max_chunks``, then clamps to what
    ``rows`` can actually carry (:func:`resolve_chunks`).
    """
    c_star = int(np.sqrt(max(float(a2a_bytes), 0.0) / CHUNK_LATENCY_BYTES))
    c = 1
    while c * 2 <= min(c_star, max_chunks):
        c *= 2
    return resolve_chunks(rows, c, granule=granule)


@dataclasses.dataclass(frozen=True)
class DistPlan:
    """Distributed split of an N-point FFT over ``shards`` devices.

    ``n1`` is the distributed (pass-1) factor — FFT'd while columns are
    locally resident; ``n2 = N / n1`` is the tail executed after the
    all-to-all (itself multi-pass locally when n2 > MAX_BLOCK_N).
    """

    n: int
    n1: int
    n2: int
    shards: int
    axis: str = FFT_AXIS

    @property
    def local_in(self) -> tuple[int, int]:
        return (self.n1, self.n2 // self.shards)

    @property
    def local_out(self) -> tuple[int, int]:
        return (self.n1 // self.shards, self.n2)


def make_dist_plan(n: int, shards: int, axis: str = FFT_AXIS) -> DistPlan:
    """Choose the (n1, n2) pencil split for ``shards`` devices.

    Starts from ``make_plan(n).kernel_factors`` (the paper's HBM-pass split)
    and shifts powers of two between the sides until both are divisible by
    ``shards`` — the all-to-all needs shards | n1 and the input sharding
    needs shards | n2.
    """
    if n <= 0 or n & (n - 1):
        raise ValueError(f"N must be a power of two, got {n}")
    if shards & (shards - 1):
        raise ValueError(f"shard count must be a power of two, got {shards}")
    if n < shards * shards:
        raise ValueError(
            f"N={n} too small for a {shards}-way pencil split "
            f"(need N >= shards^2)")
    facs = make_plan(n).kernel_factors
    if len(facs) > 1:
        n1 = facs[0]
    else:
        n1 = 1 << ((n.bit_length() - 1 + 1) // 2)  # balanced split
    n2 = n // n1
    while n1 % shards and n2 > shards:
        n1 *= 2
        n2 //= 2
    while n2 % shards and n1 > shards:
        n1 //= 2
        n2 *= 2
    if n1 % shards or n2 % shards:
        raise ValueError(f"n={n} has no n1*n2 split with both factors "
                         f"divisible by shards={shards} "
                         f"(closest: {n1}x{n2})")
    return DistPlan(n=n, n1=n1, n2=n2, shards=shards, axis=axis)


def _twiddle_block(n1: int, n2: int, rows: tuple, cols: tuple, dtype,
                   inverse: bool) -> jax.Array:
    """``T[k1, j] = exp(-+2*pi*i*k1*j/(n1*n2))`` for the ``(start, size)``
    row and column ranges, built in-graph from iotas: the whole (n1, n2)
    table as a host constant would put N elements (512 MiB at N = 2^26)
    into every executable.

    Accuracy: the angle index is exact in int32, but at complex64 its
    float32 cast (inexact beyond 2^24) and the float32 angle near pi cost
    up to ~3e-7 absolute at N = 2^26, against ~4e-8 for a float64 table
    rounded once; the error grows with N
    (``test_twiddle_block_accuracy_at_2_26``)."""
    n = n1 * n2
    k1 = rows[0] + jax.lax.broadcasted_iota(jnp.int32, (rows[1], 1), 0)
    j = cols[0] + jax.lax.broadcasted_iota(jnp.int32, (1, cols[1]), 1)
    m = (k1 * j) % n                       # exact: k1 * j < n <= 2^31
    m = jnp.where(m >= n // 2, m - n, m)   # |angle| <= pi
    rdtype = jnp.float64 if dtype == jnp.complex128 else jnp.float32
    ang = m.astype(rdtype) * ((2.0 if inverse else -2.0) * np.pi / n)
    return jax.lax.complex(jnp.cos(ang), jnp.sin(ang)).astype(dtype)


def _local_fft(z: jax.Array, inverse: bool) -> jax.Array:
    """Unnormalized FFT over the last axis, entirely local to the shard.

    Lengths beyond the single-block budget run the same multi-factor
    composition the single-device driver uses — extra *local* passes, never
    extra collectives.
    """
    nloc = z.shape[-1]
    if nloc == 1:
        return z
    with jax.named_scope("fft.local"):
        if nloc <= MAX_BLOCK_N:
            return block_fft_stages(z, inverse=inverse)
        return _fft_factors(z, make_plan(nloc).kernel_factors, inverse)


def _resolve_mesh(mesh, axis: str):
    if mesh is None:
        return None
    if axis not in mesh.axis_names:
        raise ValueError(f"mesh {mesh.axis_names} has no '{axis}' axis")
    return mesh


# ---------------------------------------------------------------------------
# plain distributed transform
# ---------------------------------------------------------------------------


def _batch_spec(data_axis, b, dsize):
    """The batch-dim spec: sharded over ``data_axis`` when it divides."""
    return data_axis if (data_axis and b % dsize == 0) else None


@functools.lru_cache(maxsize=None)
def _dist_fft_fn(mesh: Mesh, axis: str, inverse: bool,
                 natural_order: bool = True, data_axis: str | None = None,
                 chunks: int = 1):
    """Build the jitted shard_map pipeline for one (mesh, axis, direction).

    With ``data_axis`` set, batch rows shard over it (each data shard runs
    the pencil pipeline on its slice; the all-to-all stays within ``axis``).
    ``chunks > 1`` splits the local batch into that many transactions —
    chunk i's all-to-all overlaps chunk i+1's pass-1 compute; results are
    bitwise-identical to the bulk-synchronous path (every per-row op is
    independent of the batch split).

    The stages carry ``jax.named_scope``s (HLO metadata only): ``fft.local``
    the stage DFTs, ``fft.twiddle`` the in-graph twiddle, ``fft.exchange``
    the all-to-all, and ``fft.layout`` the swaps and reshapes, among them
    the ingest reshape that the partitioner turns into an all-to-all when
    the operand arrives in contiguous blocks (``shard_signals``).
    """
    shards = mesh.shape[axis]
    dsize = mesh.shape[data_axis] if data_axis else 1

    @jax.jit
    def run(x):  # x: (..., N) complex
        shape = x.shape
        n = shape[-1]
        plan = make_dist_plan(n, shards, axis)
        n1, n2 = plan.n1, plan.n2
        with jax.named_scope("fft.layout"):
            z = x.reshape((-1, n1, n2))
        bspec = _batch_spec(data_axis, z.shape[0], dsize)

        def pipeline(zl):
            n2l = zl.shape[-1]
            with jax.named_scope("fft.layout"):
                zl = jnp.swapaxes(zl, -1, -2)
            with jax.named_scope("fft.local"):
                zl = block_fft_stages(zl, inverse=inverse)   # FFT over n1
            with jax.named_scope("fft.layout"):
                zl = jnp.swapaxes(zl, -1, -2)            # (B, n1, n2l)
            with jax.named_scope("fft.twiddle"):
                d = jax.lax.axis_index(axis)
                zl = zl * _twiddle_block(n1, n2, (0, n1), (d * n2l, n2l),
                                         zl.dtype, inverse)
            with jax.named_scope("fft.exchange"):
                zl = jax.lax.all_to_all(zl, axis, split_axis=1,
                                        concat_axis=2,
                                        tiled=True)      # (B, n1/D, n2)
            return _local_fft(zl, inverse)               # FFT over n2

        def body(zl):
            ce = resolve_chunks(zl.shape[0], chunks)
            if ce == 1:
                return pipeline(zl)
            # one transaction per chunk: the unrolled a2as are independent,
            # so the scheduler runs chunk i's transfer under chunk i+1's
            # pass-1 compute
            parts = jnp.split(zl, ce, axis=0)
            return jnp.concatenate([pipeline(p) for p in parts], axis=0)

        out = shard_map(body, mesh=mesh,
                        in_specs=P(bspec, None, axis),
                        out_specs=P(bspec, axis, None),
                        check_vma=False)(z)
        with jax.named_scope("fft.layout"):
            if natural_order:
                # k = k1 + n1*k2: transpose the cube to natural order. The
                # shard axis (k1) lands strided in the flat result, so XLA
                # materializes it with an all-gather — the unavoidable
                # final redistribution every distributed FFT pays for
                # natural order.
                y = jnp.swapaxes(out, -1, -2).reshape((-1, n))
            else:
                # FFTW-MPI-style "transposed order": y[b, k1*N2 + k2] holds
                # X[k1 + N1*k2]. Block-sharded over k1 — zero extra
                # collectives.
                y = out.reshape((-1, n))
        if inverse:
            y = y / n
        return y.reshape(shape)

    return run


@functools.lru_cache(maxsize=None)
def _dist_ifft_t_fn(mesh: Mesh, axis: str, data_axis: str | None = None,
                    chunks: int = 1):
    """Inverse pipeline consuming TRANSPOSED-order input (TRANSPOSED_IN).

    Input ``y[.., k1*N2 + k2] = X[k1 + N1*k2]`` — exactly what the forward
    returns with ``natural_order=False`` — binds shard-aligned (contiguous
    ``k1`` blocks), so no up-front redistribution. With n = n1*N2 + n2 and
    k = k1 + N1*k2 the inverse splits as

        x[n1, n2] = 1/N sum_k1 e^{+2pi i n1 k1/N1}
                    [ T*[k1, n2] sum_k2 X[k1, k2] e^{+2pi i n2 k2/N2} ]

    pass A (local): inverse FFT over k2 — rows are resident
    twiddle        : conjugate T rows for this shard's k1 range
    all-to-all     : splits the BATCH axis while gathering k1 — after it each
                     device holds all k1 rows for 1/D of the batch
    pass B (local): inverse FFT over k1 -> natural-order x, fully resident

    Because the transpose redistributes batch rather than a signal digit, the
    output is natural order AND flat-contiguous (batch-sharded): the round
    trip needs zero all-gathers. Requires batch % (data * shards) == 0 —
    callers pad (see distributed_ifft).
    """
    shards = mesh.shape[axis]
    dsize = mesh.shape[data_axis] if data_axis else 1

    @jax.jit
    def run(y):  # y: (..., N) complex, transposed digit order
        shape = y.shape
        n = shape[-1]
        plan = make_dist_plan(n, shards, axis)
        n1, n2 = plan.n1, plan.n2
        z = y.reshape((-1, n1, n2))   # cube (B, k1, k2)
        b = z.shape[0]
        bspec = _batch_spec(data_axis, b, dsize)
        dloc = dsize if bspec else 1
        if (b // dloc) % shards:
            raise ValueError(
                f"transposed-order inverse needs batch divisible by "
                f"{'data*shards' if bspec else 'shards'} "
                f"({dloc}*{shards}), got {b} — pad the batch "
                f"(distributed_ifft does this automatically)")

        def pipeline(zl):
            d = jax.lax.axis_index(axis)
            n1l = zl.shape[-2]
            zl = _local_fft(zl, inverse=True)            # IFFT over k2
            zl = zl * _twiddle_block(n1, n2, (d * n1l, n1l), (0, n2),
                                     zl.dtype, True)
            zl = jax.lax.all_to_all(zl, axis, split_axis=0, concat_axis=1,
                                    tiled=True)          # (B/D, n1, n2)
            zl = jnp.swapaxes(zl, -1, -2)
            zl = _local_fft(zl, inverse=True)            # IFFT over k1
            zl = jnp.swapaxes(zl, -1, -2)                # natural (n1, n2)
            return zl.reshape(zl.shape[0], n) / n        # flat, local

        def body(zl):
            # the a2a splits the batch into ``shards`` destination blocks;
            # a chunk must take rows from WITHIN each block (a strided
            # selection), else device d's resident rows after the split
            # would be a permutation of the bulk path's
            ce = resolve_chunks(zl.shape[0] // shards, chunks)
            if ce == 1:
                return pipeline(zl)
            blocks = zl.reshape((shards, zl.shape[0] // shards)
                                + zl.shape[1:])
            w = blocks.shape[1] // ce
            outs = []
            for i in range(ce):
                part = blocks[:, i * w:(i + 1) * w]
                outs.append(pipeline(part.reshape((-1,) + zl.shape[1:])))
            return jnp.concatenate(outs, axis=0)  # rows land in bulk order

        out_spec = P((bspec, axis) if bspec else axis, None)
        out = shard_map(body, mesh=mesh,
                        in_specs=P(bspec, axis, None),
                        out_specs=out_spec,
                        check_vma=False)(z)
        return out.reshape(shape)

    return run


def distributed_fft(x: jax.Array, mesh: Mesh | None = None, *,
                    axis: str = FFT_AXIS, inverse: bool = False,
                    natural_order: bool = True,
                    data_axis: str | None = _AUTO,
                    chunks: int = 1) -> jax.Array:
    """FFT over the last axis, pencil-sharded over ``mesh.shape[axis]``
    devices. Matches ``jnp.fft.fft`` conventions. Batch dims shard over
    ``data_axis`` when the mesh carries one (auto-detected ``"data"`` by
    default; pass ``data_axis=None`` to replicate the batch instead).

    ``natural_order=False`` is the FFTW-MPI transposed pairing: on the
    forward it skips the final redistribution and returns the transposed
    digit order ``y[.., k1*N2 + k2] = X[k1 + N1*k2]``, still sharded — the
    cheap choice when the consumer is pointwise anyway (convolution, power
    spectra; see ``core.fft.spectral``). On the inverse it declares the
    *input* to be in that order (TRANSPOSED_IN) and returns natural-order
    time domain, batch-sharded — zero all-gathers either way.

    With ``mesh=None`` or a 1-sized axis this is exactly the local transform
    (where natural and transposed order coincide).

    ``chunks > 1`` splits the batch into that many overlapped transactions
    (multi-transaction pipelining; see :func:`resolve_chunks`) — results are
    bitwise-identical to the bulk-synchronous default.
    """
    x = jnp.asarray(x)
    if not jnp.issubdtype(x.dtype, jnp.complexfloating):
        x = x.astype(jnp.complex64)
    mesh = _resolve_mesh(mesh, axis)
    if mesh is None or mesh.shape[axis] == 1:
        from . import stockham
        return stockham.ifft(x) if inverse else stockham.fft(x)
    daxis = _resolve_data_axis(mesh, data_axis)
    if inverse and not natural_order:
        return _ifft_transposed(x, mesh, axis, daxis, chunks)
    return _dist_fft_fn(mesh, axis, inverse, natural_order, daxis,
                        int(chunks))(x)


def _pad_batch_rows(x2d: jax.Array, dsize: int, shards: int):
    """Pad the batch of a (B, N) array with zero rows to a multiple of
    ``dsize * shards`` — the granule that keeps it both data-shardable and
    batch-splittable by the inverse's all-to-all. Returns (padded, B).

    Padding rides the *unsharded* batch axis (a free local concat); the
    slice back to B is a no-op in the common divisible case.
    """
    b = x2d.shape[0]
    pad = (-b) % (dsize * shards)
    if pad:
        x2d = jnp.concatenate(
            [x2d, jnp.zeros((pad,) + x2d.shape[1:], x2d.dtype)], axis=0)
    return x2d, b


def _ifft_transposed(x, mesh, axis, daxis, chunks: int = 1):
    """Pad the batch so the inverse's batch-split all-to-all divides (and
    the data axis, when present, keeps dividing), run, slice back."""
    shards = mesh.shape[axis]
    dsize = mesh.shape[daxis] if daxis else 1
    lead = x.shape[:-1]
    n = x.shape[-1]
    x2d, b = _pad_batch_rows(x.reshape((-1, n)), dsize, shards)
    out = _dist_ifft_t_fn(mesh, axis, daxis, int(chunks))(x2d)
    if out.shape[0] != b:
        out = out[:b]
    return out.reshape(lead + (n,))


def distributed_ifft(x: jax.Array, mesh: Mesh | None = None, *,
                     axis: str = FFT_AXIS, natural_order: bool = True,
                     data_axis: str | None = _AUTO,
                     chunks: int = 1) -> jax.Array:
    """Inverse of :func:`distributed_fft` (normalized by 1/N).

    ``natural_order=False`` consumes TRANSPOSED-order input (the forward's
    ``natural_order=False`` output) with no up-front redistribution; the
    result is natural-order time domain, batch-sharded over the mesh.
    """
    return distributed_fft(x, mesh, axis=axis, inverse=True,
                           natural_order=natural_order, data_axis=data_axis,
                           chunks=chunks)


# ---------------------------------------------------------------------------
# sharded two-side ABFT (grouped multi-transaction)
# ---------------------------------------------------------------------------


@jax.tree_util.register_dataclass
@dataclasses.dataclass
class DistFFTResult:
    """Corrected outputs + per-group FT telemetry of one sharded ft transform.

    The batch is split into G checksum groups (the mesh-level analogue of the
    fused kernel's multi-transaction threadblocks): every per-group field has
    leading dimension G, and one fault per *group* — not per transform — is
    detected, located, and corrected in a single pass.
    """

    y: jax.Array              # (B, N) corrected outputs
    shard_delta: jax.Array    # (devices,) per-shard left-checksum residual
    group_score: jax.Array    # (G,) relative right-checksum divergence
    flagged: jax.Array        # (G,) bool — group detected a divergence
    location: jax.Array       # (G,) int32 decoded global signal index
    correctable: jax.Array    # (G,) bool — single-fault signature (repaired
                              # in place when correct=True)
    checksum_fault: jax.Array  # (G,) bool — divergence decodes to a checksum
                              # row, not the data (outputs are clean)
    corrected: jax.Array      # scalar int32 — corrections applied
    recomputed: jax.Array     # scalar int32 — groups recomputed by the
                              # policy fallback (see recompute_uncorrectable)

    @property
    def uncorrectable(self) -> jax.Array:
        """(G,) bool — flagged, but neither a single data fault nor a
        checksum-row fault: multiple SEUs hit the same group; the policy
        recompute path is the only repair."""
        return self.flagged & ~self.correctable & ~self.checksum_fault


def resolve_abft_groups(batch: int, *, groups: int | None = None,
                        group_size: int | None = None,
                        data_shards: int = 1) -> int:
    """The checksum group count G for a ``batch``-signal ft transform.

    Explicit ``groups`` wins, else ``group_size`` (G = batch/group_size),
    else auto: one group per data shard when the batch divides (the minimum
    that lets each data shard own whole groups), 1 otherwise. G must divide
    the batch; on a sharded batch each group must live wholly inside one
    data shard, i.e. ``data_shards`` must divide G. A batch that does not
    divide over ``data_shards`` cannot shard at all (the pipeline falls
    back to replicating it), so the data-axis constraint is waived.
    """
    if data_shards > 1 and batch % data_shards:
        data_shards = 1  # batch replicates; groups owe the axis nothing
    if groups is not None and group_size is not None \
            and groups * group_size != batch:
        raise ValueError(f"groups={groups} x group_size={group_size} "
                         f"!= batch={batch}")
    if groups is None:
        if group_size is not None:
            if group_size <= 0 or batch % group_size:
                raise ValueError(
                    f"group_size={group_size} must divide batch={batch}")
            groups = batch // group_size
        else:
            groups = data_shards if (
                data_shards > 1 and batch % data_shards == 0) else 1
    if groups <= 0 or batch % groups:
        raise ValueError(f"groups={groups} must divide batch={batch}")
    if data_shards > 1 and groups % data_shards:
        raise ValueError(
            f"groups={groups} must be a multiple of the data-axis size "
            f"{data_shards} so each data shard owns whole groups "
            f"(or disable batch sharding with data_axis=None)")
    return groups


def _grouped_verdict(ylg, d2, d3, cs2_out, *, axis, threshold, s, n, md, bl,
                     gl, correct, row_offset=0):
    """The shared per-group two-side decode, from checksum divergences to
    verdicts — used by BOTH the 1-D pencil ft pipeline here and the 2-D
    slab ft pipeline (``multidim._ft_slab_fft2_fn``), so the fault
    taxonomy (thresholds, ``ID_VAR_TOL``, cs2/cs3 classification) cannot
    silently diverge between them.

    ``ylg`` is the grouped local output block ``(gl, s, ...)``; ``d2``/
    ``d3`` are the transported-minus-computed checksum divergences
    ``(gl, ...)`` (== -eps_y and -id*eps_y for a single fault); ``n`` is
    the per-signal element count (N for 1-D rows, R*C for 2-D grids). The
    verdict is ONE psum of 3 scalars per locally-owned group + 1 shared
    energy scalar, confined to ``axis``. Returns ``(ylg, stats)`` with the
    located signal repaired in place when ``correct``.

    ``row_offset`` is the first data row this call covers within its data
    shard — non-zero when a chunked pipeline runs one verdict per
    transaction over a slice of the local groups, so decoded ``location``
    stays a global signal index.
    """
    num = jnp.sum((d3 * jnp.conj(d2)).real, axis=(1, 2))
    den = jnp.sum(jnp.abs(d2) ** 2, axis=(1, 2))
    d3sq = jnp.sum(jnp.abs(d3) ** 2, axis=(1, 2))
    energy = jnp.sum(jnp.abs(cs2_out) ** 2)
    payload = jnp.concatenate(
        [jnp.stack([num, den, d3sq], axis=1).ravel(), energy[None]])
    payload = jax.lax.psum(payload, axis)        # 3*gl + 1 scalars
    pg = payload[:-1].reshape((gl, 3))
    num, den, d3sq = pg[:, 0], pg[:, 1], pg[:, 2]
    scale = jnp.sqrt(payload[-1] / (gl * n)) + EPS
    score2 = jnp.sqrt(den / n) / scale
    score3 = jnp.sqrt(d3sq / n) / (s * scale)
    score = jnp.maximum(score2, score3)
    # two-side location decode: lam estimates the within-group id; id_var
    # is the spread of the per-element id estimates — noise-floor for a
    # single fault (d3 == id * d2 identically), O(1) when two faults with
    # distinct ids share a group (even magnitude-symmetric pairs whose
    # mean id lands on an integer)
    lam = num / (den + EPS)
    id_var = jnp.maximum(d3sq / (den + EPS) - lam * lam, 0.0)
    rid = jnp.round(lam).astype(jnp.int32)
    flagged2 = score2 > threshold
    # lam ~ 0 with no spread: the transported cs2 row itself was hit
    # (d3 untouched) — the data is clean, nothing to correct
    cs2_fault = flagged2 & (lam < 0.5) & (id_var < ID_VAR_TOL)
    correctable = (flagged2 & ~cs2_fault & (rid >= 1) & (rid <= s)
                   & (id_var < ID_VAR_TOL))
    # d3 diverged while d2 is quiet: the cs3 row was hit
    cs3_fault = ~flagged2 & (score3 > threshold)
    checksum_fault = cs2_fault | cs3_fault
    flagged = flagged2 | cs3_fault
    loc_local = jnp.clip(rid - 1, 0, s - 1)
    location = md * bl + row_offset + jnp.arange(gl) * s + loc_local
    if correct:
        # d2 is the local slice of -eps_y: elementwise repair of the
        # located signal works no matter which shard holds the fault
        upd = jnp.where(correctable[:, None, None], d2,
                        jnp.zeros_like(d2))
        ylg = ylg.at[jnp.arange(gl), loc_local].add(upd)
    fl = lambda v: v.astype(score.dtype)
    stats = jnp.stack(
        [score, fl(flagged), fl(location), fl(correctable),
         fl(checksum_fault)], axis=1)            # (gl, 5)
    return ylg, stats


def _splice_recomputed(x, res, groups, recompute_fn, caller: str):
    """Shared host-side policy fallback for multi-fault groups: recompute
    the affected group's rows with the plain (unprotected, uninjected)
    pipeline via ``recompute_fn`` and splice them in — SEUs are transient,
    so the recompute is clean. Forces a device sync, hence opt-in."""
    if isinstance(res.flagged, jax.core.Tracer):
        raise ValueError(
            "recompute_uncorrectable is a host-side fallback (it reads the "
            "verdict to decide which group rows to recompute) and cannot "
            f"run under jax.jit — call {caller} eagerly, or pass "
            "recompute_uncorrectable=False inside jit and apply the "
            "recompute on the eager result")
    bad = np.asarray(res.uncorrectable)
    if not bad.any():
        return res
    s = x.shape[0] // groups
    y = res.y
    for gi in np.flatnonzero(bad):
        rows = slice(int(gi) * s, (int(gi) + 1) * s)
        yg = recompute_fn(x[rows])
        y = y.at[rows].set(yg.astype(y.dtype))
    return dataclasses.replace(
        res, y=y, recomputed=jnp.int32(int(bad.sum())))


@functools.lru_cache(maxsize=None)
def _ft_dist_fft_fn(mesh: Mesh, axis: str, threshold: float, correct: bool,
                    natural_order: bool = True, groups: int = 1,
                    data_axis: str | None = None, chunks: int = 1):
    shards = mesh.shape[axis]
    dsize = mesh.shape[data_axis] if data_axis else 1

    @jax.jit
    def run(x, inject):  # x: (B, N) complex; inject: (F, 7) real
        b, n = x.shape
        plan = make_dist_plan(n, shards, axis)
        n1, n2 = plan.n1, plan.n2
        g = groups
        s = b // g                      # signals per group (wrapper-validated)
        # batch rows shard over the data axis iff every group lands wholly
        # inside one data shard (the wrapper validates explicit asks; auto
        # mode falls back to replication)
        bspec = data_axis if (
            data_axis and b % dsize == 0 and g % dsize == 0) else None
        dloc = dsize if bspec else 1
        bl, gl = b // dloc, g // dloc   # per-data-shard rows / groups
        # transactions carry WHOLE checksum groups, so each chunk's verdict
        # (including its energy normalizer) is self-contained — the paper's
        # multi-transaction amortization with the reduction riding per-chunk
        ce = resolve_chunks(gl, chunks)
        glc, blc = gl // ce, bl // ce   # per-transaction groups / rows
        # right-side encodings per group: e2 = ones (correction value),
        # e3 = 1-based within-group ids (location) — twoside.py's pipeline
        # applied along the *unsharded* batch axis so building them is local.
        ftype = np.float64 if x.dtype == jnp.complex128 else np.float32
        ids = jnp.arange(1, s + 1, dtype=ftype)[None, :, None, None]
        z = x.reshape((b, n1, n2))

        def body(zl):
            d = jax.lax.axis_index(axis)
            md = jax.lax.axis_index(data_axis) if bspec else jnp.int32(0)
            n2l = zl.shape[-1]
            # ---- fault-injection decode (tests/benchmarks): one SEU per
            # inject row [fft_device, signal, row, local_col, enable,
            # eps_re, eps_im] on the pass-1 output. ``signal`` is global:
            # [0, B) hits data rows, [B, B+G) the cs2 row of group
            # signal-B, [B+G, B+2G) the cs3 row of group signal-B-G --------
            dev = inject[:, 0].astype(jnp.int32)
            sig = inject[:, 1].astype(jnp.int32)
            row = inject[:, 2].astype(jnp.int32)
            col = inject[:, 3].astype(jnp.int32)
            is_data = sig < b
            is_cs2 = (sig >= b) & (sig < b + g)
            gidx = jnp.where(is_cs2, sig - b, sig - b - g)
            owner = jnp.where(is_data, sig // bl, gidx // gl)
            drow = sig - owner * bl      # data row, local to the data shard
            grow = gidx - owner * gl     # group index, local to the shard
            amp = inject[:, 4] * ((owner == md) & (d == dev)).astype(ftype)

            def transaction(zlc, ci):
                # input checksums ride as 2 extra rows PER GROUP:
                # rows [0, blc) data | [blc, blc+glc) cs2 | [.., +2glc) cs3
                zg = zlc.reshape((glc, s, n1, n2l))
                cs2_in = jnp.sum(zg, axis=1)
                cs3_in = jnp.sum(ids * zg, axis=1)
                zc = jnp.concatenate([zlc, cs2_in, cs3_in], axis=0)
                # ---- pass 1: FFT over n1 (local) + left checksum ----------
                zt = jnp.swapaxes(zc, -1, -2)
                zf = block_fft_stages(zt, inverse=False)
                # sum_k1 W[k1, n1] = n1*delta(n1): column sums predict from
                # x[0]; residual scaling stays in the input's real dtype (a
                # float32 constant would silently downcast the fp64
                # telemetry and inflate false-positive risk at tight
                # thresholds)
                res1 = jnp.abs(jnp.sum(zf, axis=-1) - n1 * zt[..., 0])
                scale1 = jnp.sqrt(jnp.mean(jnp.abs(zt) ** 2, axis=-1)) + EPS
                delta = jnp.max(res1 / (float(np.sqrt(n1)) * scale1))
                zc = jnp.swapaxes(zf, -1, -2)           # (blc+2glc, n1, n2l)
                zc = zc * _twiddle_block(n1, n2, (0, n1), (d * n2l, n2l),
                                         zc.dtype, False)
                # ---- injection, masked to this transaction's rows ---------
                in_chunk = jnp.where(
                    is_data,
                    (drow >= ci * blc) & (drow < (ci + 1) * blc),
                    (grow >= ci * glc) & (grow < (ci + 1) * glc))
                crow = jnp.where(
                    is_data, drow - ci * blc,
                    blc + jnp.where(is_cs2, 0, glc) + grow - ci * glc)
                eps = (inject[:, 5] + 1j * inject[:, 6]).astype(zc.dtype)
                ampc = amp * in_chunk.astype(ftype)
                onehot = (
                    (jnp.arange(blc + 2 * glc)[None] == crow[:, None])
                    [:, :, None, None]
                    * (jnp.arange(n1)[None] == row[:, None])
                    [:, None, :, None]
                    * (jnp.arange(n2l)[None] == col[:, None])
                    [:, None, None, :])
                zc = zc + jnp.sum((eps * ampc.astype(zc.real.dtype))
                                  [:, None, None, None]
                                  * onehot.astype(zc.real.dtype), axis=0)
                # ---- the one collective per transaction: the transpose ----
                zc = jax.lax.all_to_all(zc, axis, split_axis=1,
                                        concat_axis=2,
                                        tiled=True)     # (blc+2glc, n1/D, n2)
                # ---- pass 2: FFT over n2 (local) + left checksum ----------
                zf2 = _local_fft(zc, inverse=False)
                res2 = jnp.abs(jnp.sum(zf2, axis=-1) - n2 * zc[..., 0])
                scale2 = jnp.sqrt(jnp.mean(jnp.abs(zc) ** 2, axis=-1)) + EPS
                delta = jnp.maximum(
                    delta, jnp.max(res2 / (float(np.sqrt(n2)) * scale2)))
                # ---- detect / locate per group: output checksums vs
                # transported ones ------------------------------------------
                yl = zf2[:blc]
                fcs2 = zf2[blc:blc + glc]               # F(cs_in), sharded
                fcs3 = zf2[blc + glc:]
                ylg = yl.reshape((glc, s) + yl.shape[1:])
                cs2_out = jnp.sum(ylg, axis=1)
                cs3_out = jnp.sum(ids * ylg, axis=1)
                d2 = fcs2 - cs2_out                     # == -eps_y, sharded
                d3 = fcs3 - cs3_out                     # == -id_s * eps_y
                # the verdict: 3 scalars per transaction-owned group + ONE
                # energy scalar, psum'd over the fft axis only — the data
                # axis never participates (each data shard owns its groups
                # outright), and each transaction settles its own verdict
                # so correction stays online while later chunks are still
                # in flight
                ylg, stats = _grouped_verdict(
                    ylg, d2, d3, cs2_out, axis=axis, threshold=threshold,
                    s=s, n=n, md=md, bl=bl, gl=glc, correct=correct,
                    row_offset=ci * blc)
                return ylg.reshape((blc,) + yl.shape[1:]), delta, stats

            if ce == 1:
                yl, delta, stats = transaction(zl, 0)
            else:
                outs = [transaction(p, ci)
                        for ci, p in enumerate(jnp.split(zl, ce, axis=0))]
                yl = jnp.concatenate([o[0] for o in outs], axis=0)
                delta = functools.reduce(jnp.maximum,
                                         [o[1] for o in outs])
                stats = jnp.concatenate([o[2] for o in outs], axis=0)
            return yl, delta[None, None], stats[None]

        yl, deltas, stats = shard_map(
            body, mesh=mesh,
            in_specs=P(bspec, None, axis),
            out_specs=(P(bspec, axis, None), P(bspec, axis),
                       P(axis, bspec, None)),
            check_vma=False)(z)
        if natural_order:
            y = jnp.swapaxes(yl, -1, -2).reshape((b, n))
        else:
            y = yl.reshape((b, n))   # transposed digit order, k1-sharded
        st = stats[0]                # (G, 5); fft shards agree post-psum
        flagged = st[:, 1] > 0.5
        correctable = st[:, 3] > 0.5
        return DistFFTResult(
            y=y, shard_delta=deltas.reshape((-1,)), group_score=st[:, 0],
            flagged=flagged, location=st[:, 2].astype(jnp.int32),
            correctable=correctable, checksum_fault=st[:, 4] > 0.5,
            corrected=jnp.sum(correctable.astype(jnp.int32)) * int(correct),
            recomputed=jnp.zeros((), jnp.int32))

    return run


def _recompute_uncorrectable(x, res, mesh, axis, groups, natural_order):
    """Multi-fault-group policy fallback (see :func:`_splice_recomputed`),
    recomputing with the plain 1-D pipeline."""
    return _splice_recomputed(
        x, res, groups,
        lambda rows: distributed_fft(rows, mesh, axis=axis,
                                     natural_order=natural_order,
                                     data_axis=None),
        "ft_distributed_fft")


def ft_distributed_fft(
    x: jax.Array,
    mesh: Mesh | None = None,
    *,
    axis: str = FFT_AXIS,
    threshold: float = 1e-4,
    correct: bool = True,
    natural_order: bool = True,
    inject: jax.Array | None = None,
    groups: int | None = None,
    group_size: int | None = None,
    data_axis: str | None = _AUTO,
    recompute_uncorrectable: bool = False,
    chunks: int = 1,
) -> DistFFTResult:
    """Fault-tolerant sharded forward FFT (grouped two-side ABFT).

    The batch splits into G checksum groups (``groups``/``group_size``; auto:
    one group per data shard, else 1) — the mesh-level analogue of the fused
    kernel's multi-transaction threadblocks. Each group carries its own
    right-side checksum row pair through the transpose and gets its own
    detect/locate/correct verdict, so G concurrent SEUs striking G distinct
    groups are all corrected in one pass. On a 2-D batch x pencil mesh the
    batch rows SHARD over the data axis (each data shard owns G/data whole
    groups); the verdict psum — 3 scalars per group plus one shared energy
    scalar — stays confined to the ``fft`` axis.

    Per-group verdicts (see :class:`DistFFTResult`): a single data SEU is
    ``correctable`` and repaired in place; two SEUs in one group decode as
    inconsistent (``uncorrectable`` — id-estimate spread over
    ``ID_VAR_TOL``) and are repaired by ``recompute_uncorrectable=True``,
    which recomputes just the affected group rows host-side; an SEU in a
    checksum row itself decodes to ``checksum_fault`` (lam ~ 0 for cs2,
    quiet d2 with loud d3 for cs3) and triggers no correction — the data is
    clean.

    ``inject`` (optional, for tests/benchmarks) is one or more length-7
    float rows ``[device, signal, row, local_col, enable, eps_re, eps_im]``
    adding SEUs to the pass-1 output — the errors then propagate through the
    all-to-all and pass 2 exactly like real mid-pipeline faults. ``signal``
    in ``[B, B+G)`` / ``[B+G, B+2G)`` targets a group's cs2 / cs3 checksum
    row. Residuals, scores, and epsilons stay in the input's real dtype
    (fp64 for complex128), so tight fp64 thresholds remain meaningful.

    ``natural_order=False`` keeps ``y`` in the transposed digit order (still
    sharded, no final all-gather); the telemetry is order-independent.

    ``chunks > 1`` splits the local groups into that many overlapped
    transactions, each carrying whole checksum groups AND its own verdict
    psum — correction stays online per transaction. ``y``, the flag
    booleans, and decoded locations are identical to the bulk path;
    ``group_score`` normalizes against the transaction's own energy rather
    than the whole batch's (per-transaction semantics, matching the
    paper's multi-transaction reductions).
    """
    x = jnp.asarray(x)
    if not jnp.issubdtype(x.dtype, jnp.complexfloating):
        x = x.astype(jnp.complex64)
    if x.ndim != 2:
        raise ValueError(f"ft_distributed_fft expects (B, N), got {x.shape}")
    mesh = _resolve_mesh(mesh, axis)
    if mesh is None:
        raise ValueError("ft_distributed_fft requires a mesh with an "
                         f"'{axis}' axis (see launch.mesh.make_fft_mesh)")
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
    res = _ft_dist_fft_fn(mesh, axis, float(threshold), bool(correct),
                          bool(natural_order), g, daxis,
                          int(chunks))(x, inject)
    if recompute_uncorrectable:
        res = _recompute_uncorrectable(x, res, mesh, axis, g,
                                       bool(natural_order))
    return res


# ---------------------------------------------------------------------------
# communication model
# ---------------------------------------------------------------------------


def collective_volume(n: int, batch: int, shards: int, *, itemsize: int = 8,
                      ft: bool = False, natural_order: bool = True,
                      groups: int = 1, data_shards: int = 1,
                      real: bool = False, chunks: int = 1) -> dict:
    """Analytic per-device communication model of one distributed transform.

    Three terms (cross-checked against the post-partitioning HLO by
    benchmarks/fft_distributed.py):

    * the inter-pass transpose: ONE all-to-all over the ``rows * N / D``
      locally-resident elements, of which ``(D-1)/D`` actually cross a link.
      On a 2-D batch x pencil mesh each device carries ``1/data_shards`` of
      the rows;
    * the natural-order redistribution: materializing ``k = k1 + N1*k2``
      order gathers this device's ``batch/data_shards * N`` result rows
      (skipped entirely with ``natural_order=False`` — checksum rows never
      pay it either);
    * the grouped ABFT verdict: one psum of 3 scalars per locally-owned
      checksum group plus ONE energy scalar per transaction — the
      mesh-level analogue of the paper's amortized threadblock reduction,
      and it stays confined to the ``fft`` axis (each data shard owns
      ``groups/data_shards`` groups outright). The scalars live in the
      input's *real* dtype, i.e. ``itemsize / 2`` bytes each (f64 for
      complex128 — hard-coding 4 bytes made the model diverge from the HLO
      for fp64). Extracting the replicated per-group stats block
      (``5 * groups/data_shards`` reals) from the shard_map output costs
      one more small all-reduce — GSPMD's broadcast of shard 0's copy —
      which the model counts so the HLO cross-check holds to pure relative
      tolerance. The checksum *signals* add ``2*groups/batch`` relative
      all-to-all volume (they ride the same transpose), which is the
      ``abft_overhead`` field.

    ``chunks`` is the multi-transaction pipelining degree: the payload
    splits into that many back-to-back all-to-alls (same total bytes —
    ``all_to_all_count`` reports the op count) so transaction i's transfer
    hides behind transaction i+1's local passes. The overlap-efficiency
    term models the schedule: ``exposed_fraction = 1/chunks`` of the
    transpose volume cannot overlap anything (the pipeline has to drain),
    so ``overlap_efficiency = 1 - 1/chunks`` of it is hidden. The ft
    verdict gains one energy scalar per extra transaction.

    ``real=True`` models the rfft packing trick (``extensions.rfft``):
    the executed C2C transform — and so every collective — runs at the
    packed HALF length ``n // 2`` (the Hermitian unpack is elementwise,
    collective-free), halving both the transpose and the natural-order
    gather. The 1-D real path has no ft pipeline (rank-2 ``rfft2`` rides
    the slab ABFT), so ``real=True`` with ``ft=True`` raises.

    ``*_wire`` entries are true link-crossing bytes; ``hlo_bytes`` is what
    ``repro.analysis.hlo.summarize(parse_collectives(...))`` counts for the
    same program (full per-device collective operand bytes, all-reduce at
    ring factor 2).
    """
    if ft and groups % data_shards:
        raise ValueError(f"groups={groups} must divide over "
                         f"data_shards={data_shards}")
    if real:
        if ft:
            raise ValueError(
                "the 1-D real path has no ft pipeline — grouped ABFT on "
                "real input rides the 2-D slab (collective_volume_nd with "
                "real=True)")
        n = n // 2   # the packed half-length C2C is the whole collective cost
    chunks = max(1, int(chunks))
    rows = (batch + (2 * groups if ft else 0)) / data_shards
    a2a_local = rows * n * itemsize / shards
    a2a_wire = a2a_local * (shards - 1) / shards
    gather_hlo = batch / data_shards * n * itemsize if natural_order else 0.0
    gather_wire = gather_hlo * (shards - 1) / shards
    # per-group verdict scalars + one energy scalar per transaction, plus
    # the stats extraction: ONE stacked (G/dd, 5)-real block broadcast
    # (on JAX 0.9's Auto-axis meshes the ungrouped G = 1 pipeline is no
    # exception; pinned down by the plan auditor's per-kind psum diff)
    verdict = (3 * groups // data_shards + chunks) * (itemsize // 2)
    stats = 5 * groups // data_shards * (itemsize // 2)
    psum_hlo = 2.0 * (verdict + stats) if ft else 0.0
    psum_wire = psum_hlo * (shards - 1) / shards
    return {
        "shards": shards,
        "data_shards": data_shards,
        "groups": groups,
        "real": real,
        "chunks": chunks,
        "passes": 2,  # one distributed split -> exactly one transpose
        "all_to_all_count": chunks,
        "all_gather_count": 1 if natural_order else 0,
        "all_to_all_bytes": a2a_local,
        "all_to_all_wire": a2a_wire,
        "gather_hlo": gather_hlo,
        "gather_wire": gather_wire,
        "psum_hlo": psum_hlo,
        "psum_wire": psum_wire,
        "total_wire": a2a_wire + gather_wire + psum_wire,
        "hlo_bytes": a2a_local + gather_hlo + psum_hlo,
        "abft_overhead": 2.0 * groups / batch if (ft and batch) else 0.0,
        "exposed_fraction": 1.0 / chunks,
        "overlap_efficiency": 1.0 - 1.0 / chunks,
    }


def spectral_volume(n: int, batch: int, shards: int, *, kernel_batch: int = 0,
                    itemsize: int = 8, data_shards: int = 1,
                    real: bool = False, chunks: int = 1) -> dict:
    """Analytic per-device model of one transposed-order spectral round trip
    (forward -> pointwise -> inverse; see ``core.fft.spectral``).

    Exactly TWO all-to-alls and ZERO all-gathers:

    * forward transpose over ``batch / data_shards + kernel_batch`` rows —
      the second operand's spectrum rides the same collective as a stacked
      batch (one all-to-all op, bigger payload). A broadcast kernel is
      replicated per data shard, so its rows do NOT divide by
      ``data_shards``; for per-signal kernel batches (sharded like the
      data) pass ``kernel_batch = bk / data_shards``;
    * inverse batch-split transpose over ``batch / data_shards`` rows (only
      the product goes back through the inverse).

    ``kernel_batch=0`` models a plain fft -> ifft round trip
    (``distributed_ifft(distributed_fft(x, natural_order=False),
    natural_order=False)``). On a 2-D batch x pencil mesh each data shard
    moves ``1/data_shards`` of the batch rows; ``shards`` is the fft-axis
    size.

    ``real=True`` models the packed real convolution (both operands real):
    the kernel rides the imaginary part of ``a + i*v``, so its rows vanish
    from the forward transpose entirely — ``kernel_batch`` is ignored and
    both passes move exactly ``batch / data_shards`` rows.

    ``chunks`` splits the round trip into that many overlapped batch
    transactions: ``2 * chunks`` all-to-alls carrying the same total bytes
    (the kernel spectrum rides transaction 0's forward collective only),
    with ``1/chunks`` of the transpose volume exposed.
    """
    chunks = max(1, int(chunks))
    rows_fwd = batch / data_shards + (0 if real else kernel_batch)
    rows_inv = batch / data_shards
    fwd_local = rows_fwd * n * itemsize / shards
    inv_local = rows_inv * n * itemsize / shards
    wire = (fwd_local + inv_local) * (shards - 1) / shards
    return {
        "shards": shards,
        "data_shards": data_shards,
        "real": real,
        "chunks": chunks,
        "all_to_all_count": 2 * chunks,
        "all_gather_count": 0,
        "all_to_all_bytes": fwd_local + inv_local,
        "all_to_all_wire": wire,
        "gather_hlo": 0.0,
        "gather_wire": 0.0,
        "psum_hlo": 0.0,
        "psum_wire": 0.0,
        "total_wire": wire,
        "hlo_bytes": fwd_local + inv_local,
        "exposed_fraction": 1.0 / chunks,
        "overlap_efficiency": 1.0 - 1.0 / chunks,
    }

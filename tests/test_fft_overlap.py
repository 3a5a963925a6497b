"""Chunked multi-transaction mesh pipelines (core.fft.distributed /
spectral / multidim ``chunks``): the double-buffered execution mode that
splits one bulk all-to-all into C overlapped transactions.

The contract under test, end to end:

* ``resolve_chunks`` / ``choose_chunks`` — static transaction-count
  resolution and the sqrt(bytes/latency) auto model;
* ``chunk_layout`` — the sharding-glue mirror of the pipelines' resolution;
* the volume models carry ``chunks``: C (resp. 2C) all-to-alls, conserved
  total bytes, ``exposed_fraction = 1/C``; slab refuses to pretend;
* bitwise chunk-count invariance — every chunked pipeline (1-D natural and
  transposed, spectral round trip, 2-D/3-D pencil, grouped ABFT with and
  without injection) returns results identical to the bulk pipeline, bit
  for bit: chunking is an execution schedule, never a numerical change;
* the fault-injection matrix holds on the chunked ft path — verdicts,
  locations, and corrections agree with bulk wherever the SEU lands
  (first chunk, last chunk, checksum row, double-hit group);
* ``FFTSpec(chunks=...)`` resolves once in the plan (explicit, auto via
  ``FTConfig.transactions`` or the volume model, nd pencil, slab clamp)
  and threads through serve's ``--fft-spec`` string.

Multi-device cases run in-process on >= 4 forced host devices (the CI fast
lane and mesh-8dev lane both force them).
"""
import numpy as np
import pytest

import jax
import jax.numpy as jnp

from conftest import run_py
from repro.core.fft import distributed as dist
from repro.core.fft import multidim as md
from repro.core.fft import spectral as spec
from repro.core.fft.api import FFTSpec, FTConfig, plan
from repro.core.fft.distributed import (CHUNK_LATENCY_BYTES, choose_chunks,
                                        resolve_chunks)
from repro.launch.mesh import make_mesh
from repro.parallel.fft_sharding import chunk_layout


def _mesh1():
    return make_mesh((4,), ("fft",))


def _need(n):
    if len(jax.devices()) < n:
        pytest.skip(f"needs >= {n} host devices")


def _crand(rng, *shape, dtype=np.complex64):
    return (rng.standard_normal(shape) + 1j * rng.standard_normal(shape)
            ).astype(dtype)


# ---------------------------------------------------------------------------
# static resolution: resolve_chunks / choose_chunks / chunk_layout
# ---------------------------------------------------------------------------


def test_resolve_chunks_feasibility():
    assert resolve_chunks(8, 4) == 4
    assert resolve_chunks(8, 3) == 2          # 3 does not divide 8
    assert resolve_chunks(7, 4) == 1          # prime rows: only bulk
    assert resolve_chunks(8, 16) == 8         # never more chunks than rows
    assert resolve_chunks(0, 4) == 1
    assert resolve_chunks(8, 0) == 1
    # granule: each transaction's rows must stay a multiple of it (the
    # batch-splitting inverse all-to-all needs whole shard blocks)
    assert resolve_chunks(8, 4, granule=2) == 4
    assert resolve_chunks(4, 4, granule=2) == 2


def test_choose_chunks_latency_model():
    L = CHUNK_LATENCY_BYTES
    # C* = sqrt(bytes / L), rounded down to a power of two
    assert choose_chunks(64 * L, 64) == 8      # C* = 8, max_chunks = 8
    assert choose_chunks(64 * L, 4) == 4       # clamped by rows
    assert choose_chunks(16 * L, 64) == 4
    assert choose_chunks(L // 2, 64) == 1      # latency-dominated: bulk
    assert choose_chunks(0, 64) == 1
    assert choose_chunks(64 * L, 64, max_chunks=2) == 2
    # feasibility still wins over the model: the pow-2 pick falls back to
    # the largest divisor the rows can actually carry
    assert choose_chunks(16 * L, 6) == 3       # C* = 4, but 4 does not | 6


def test_chunk_layout_no_mesh():
    assert chunk_layout(None, 8, 4) == (4, 2)
    assert chunk_layout(None, 8, 3) == (2, 4)
    # group-wise: whole checksum groups per transaction
    assert chunk_layout(None, 8, 2, groups=4) == (2, 4)
    assert chunk_layout(None, 8, 8, groups=4) == (4, 2)
    with pytest.raises(ValueError, match="abft_group_layout"):
        chunk_layout(None, 8, 2, groups=3)


def test_chunk_layout_on_2d_mesh():
    _need(4)
    mesh = make_mesh((2, 2), ("data", "fft"))
    # 8 rows over 2 data shards: 4 resident rows -> up to 4 transactions
    assert chunk_layout(mesh, 8, 8) == (4, 1)
    assert chunk_layout(mesh, 8, 2, groups=4) == (2, 2)
    # indivisible batch replicates: full rows stay available
    assert chunk_layout(mesh, 7, 7) == (7, 1)


# ---------------------------------------------------------------------------
# volume models carry chunks
# ---------------------------------------------------------------------------


def test_collective_volume_chunks_fields():
    n, b, s = 1 << 12, 8, 4
    bulk = dist.collective_volume(n, b, s)
    v4 = dist.collective_volume(n, b, s, chunks=4)
    assert bulk["all_to_all_count"] == 1 and v4["all_to_all_count"] == 4
    # chunking re-grains the transfer without adding volume
    assert v4["all_to_all_bytes"] == bulk["all_to_all_bytes"]
    assert v4["hlo_bytes"] == bulk["hlo_bytes"]
    assert v4["exposed_fraction"] == 0.25
    assert v4["overlap_efficiency"] == 0.75
    assert bulk["exposed_fraction"] == 1.0


def test_spectral_volume_chunks():
    n, b, s = 1 << 12, 8, 4
    bulk = dist.spectral_volume(n, b, s, kernel_batch=1)
    v2 = dist.spectral_volume(n, b, s, kernel_batch=1, chunks=2)
    assert bulk["all_to_all_count"] == 2 and v2["all_to_all_count"] == 4
    assert v2["hlo_bytes"] == bulk["hlo_bytes"]
    assert v2["exposed_fraction"] == 0.5


def test_volume_nd_chunks_pencil_only():
    bulk = md.collective_volume_nd((64, 128), 8, 4, decomp="pencil")
    v2 = md.collective_volume_nd((64, 128), 8, 4, decomp="pencil", chunks=2)
    assert v2["all_to_all_count"] == 2 * bulk["all_to_all_count"]
    assert v2["all_to_all_bytes"] == bulk["all_to_all_bytes"]
    assert v2["exposed_fraction"] == 0.5
    with pytest.raises(ValueError, match="pencil"):
        md.collective_volume_nd((64, 128), 8, 4, decomp="slab", chunks=2)


# ---------------------------------------------------------------------------
# bitwise chunk-count invariance
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("natural", [True, False])
def test_chunk_invariance_1d(rng, natural):
    _need(4)
    mesh = _mesh1()
    x = jnp.asarray(_crand(rng, 8, 1 << 12))
    bulk = np.asarray(dist.distributed_fft(x, mesh, natural_order=natural))
    for c in (2, 4, 8):
        y = dist.distributed_fft(x, mesh, natural_order=natural, chunks=c)
        assert np.array_equal(np.asarray(y), bulk), c
    # inverse round trip, chunked both ways
    z = dist.distributed_ifft(jnp.asarray(bulk), mesh,
                              natural_order=natural, chunks=4)
    ref = dist.distributed_ifft(jnp.asarray(bulk), mesh,
                                natural_order=natural)
    assert np.array_equal(np.asarray(z), np.asarray(ref))


def test_chunk_invariance_spectral(rng):
    _need(4)
    mesh = _mesh1()
    a = jnp.asarray(_crand(rng, 8, 1 << 10))
    v = jnp.asarray(_crand(rng, 1, 1 << 10))
    bulk = np.asarray(spec.fft_convolve(a, v, mesh, mode="full"))
    for c in (2, 4):
        s = spec.conv_spec(a, v, mesh, chunks=c)
        assert s.chunks == c
        p = plan(s)
        assert p.chunks == c
        got = p.convolve(a, v, mode="full")
        assert np.array_equal(np.asarray(got), bulk), c


def test_chunk_invariance_nd_pencil(rng):
    _need(4)
    mesh = _mesh1()
    # batched 2-D grids: chunks split the (replicated) batch dim
    x = jnp.asarray(_crand(rng, 8, 32, 64))
    for nat in (True, False):
        bulk = np.asarray(md.distributed_fft2(x, mesh, decomp="pencil",
                                              natural_order=nat))
        for c in (2, 4):
            y = md.distributed_fft2(x, mesh, decomp="pencil",
                                    natural_order=nat, chunks=c)
            assert np.array_equal(np.asarray(y), bulk), (nat, c)
    # rank-3 single grid: chunks split the leading (locally transformed)
    # axis — the rank-3 pencil pipeline
    g = jnp.asarray(_crand(rng, 16, 16, 32))
    bulk3 = np.asarray(md.distributed_fftn(g, mesh, ndim=3, decomp="pencil"))
    for c in (2, 4):
        y3 = md.distributed_fftn(g, mesh, ndim=3, decomp="pencil", chunks=c)
        assert np.array_equal(np.asarray(y3), bulk3), c
    back = md.distributed_ifftn(jnp.asarray(bulk3), mesh, ndim=3,
                                decomp="pencil", chunks=2)
    ref = md.distributed_ifftn(jnp.asarray(bulk3), mesh, ndim=3,
                               decomp="pencil")
    assert np.array_equal(np.asarray(back), np.asarray(ref))


# ---------------------------------------------------------------------------
# chunked grouped ABFT: verdicts ride per-transaction, bulk-identical
# ---------------------------------------------------------------------------


def _ft_fields(res):
    return (np.asarray(res.y), np.asarray(res.flagged),
            np.asarray(res.location),
            np.asarray(res.correctable), int(res.corrected))


@pytest.mark.parametrize("inject", [
    None,                                       # clean
    [[0, 1, 3, 1, 1, 60.0, 15.0]],              # SEU in the FIRST chunk
    [[1, 6, 5, 2, 1, -30.0, 60.0]],             # SEU in the LAST chunk
    [[0, 1, 3, 1, 1, 60.0, 15.0],               # one SEU per chunk
     [1, 6, 5, 2, 1, -30.0, 60.0]],
    [[1, 9, 4, 2, 1, 60.0, -60.0]],             # checksum-row fault (cs2)
    [[0, 4, 3, 1, 1, 60.0, 15.0],               # double hit in ONE group:
     [1, 5, 5, 2, 1, -30.0, 60.0]],             # flagged uncorrectable
], ids=["clean", "first-chunk", "last-chunk", "both-chunks",
        "checksum-row", "double-hit"])
def test_chunked_ft_fault_matrix(rng, inject):
    """The grouped-ABFT fault matrix is chunk-invariant: for every fault
    placement the chunked pipeline's verdicts AND outputs match the bulk
    pipeline bit for bit (each transaction carries whole groups with its
    own verdict psum, so where a chunk boundary falls must not matter)."""
    _need(4)
    mesh = _mesh1()
    b, n, g = 8, 1 << 12, 4
    x = jnp.asarray(_crand(rng, b, n))
    inj = None if inject is None else jnp.asarray(inject, jnp.float32)
    bulk = dist.ft_distributed_fft(x, mesh, groups=g, inject=inj)
    for c in (2, 4):
        res = dist.ft_distributed_fft(x, mesh, groups=g, inject=inj,
                                      chunks=c)
        for got, want in zip(_ft_fields(res), _ft_fields(bulk)):
            assert np.array_equal(got, want), c
        # group_score is the one non-bitwise field: its energy
        # normalization is per-transaction (documented on the pipeline),
        # so it only agrees to rounding
        np.testing.assert_allclose(np.asarray(res.group_score),
                                   np.asarray(bulk.group_score), rtol=0.05)
    # semantic spot checks on the bulk reference (shared by every chunking)
    if inject is None:
        assert not _ft_fields(bulk)[1].any()
    elif len(inject) == 1 and inject[0][1] < b:
        grp = inject[0][1] // (b // g)
        assert bool(bulk.flagged[grp]) and int(bulk.location[grp]) == \
            inject[0][1]


# ---------------------------------------------------------------------------
# HLO structure: C transactions lower to exactly C all-to-alls
# ---------------------------------------------------------------------------


def test_hlo_chunk_counts(rng):
    _need(4)
    from repro.launch.dryrun import collective_bytes

    mesh = _mesh1()
    n, b = 1 << 10, 8
    x = jnp.asarray(_crand(rng, b, n))
    for c in (1, 2, 4):
        fn = dist._dist_fft_fn(mesh, "fft", False, True, None, c)
        m = collective_bytes(fn.lower(x).compile().as_text())
        mdl = dist.collective_volume(n, b, 4, chunks=c)
        assert m["count"].get("all-to-all", 0) == mdl["all_to_all_count"] \
            == c, (c, m["count"])
        assert abs(m["total_bytes"] / mdl["hlo_bytes"] - 1.0) < 1e-3
        a2a = [w for k, w in m["ops"] if k == "all-to-all"]
        assert abs(max(a2a) / sum(a2a) - mdl["exposed_fraction"]) < 1e-9


# ---------------------------------------------------------------------------
# plan threading: FFTSpec(chunks=...) resolved once in FFTPlan
# ---------------------------------------------------------------------------


def test_spec_chunks_validation():
    for bad in (-1, True, 1.5, "2"):
        with pytest.raises((ValueError, TypeError)):
            FFTSpec(shape=(8, 1024), chunks=bad)
    assert FFTSpec(shape=(8, 1024), chunks=0).chunks == 0   # 0 = auto


def test_plan_resolves_chunks(rng):
    _need(4)
    mesh = _mesh1()
    x = jnp.asarray(_crand(rng, 8, 1 << 12))
    bulk = plan(FFTSpec(shape=(8, 1 << 12), mesh=mesh))
    p4 = plan(FFTSpec(shape=(8, 1 << 12), mesh=mesh, chunks=4))
    assert bulk.chunks == 1 and p4.chunks == 4
    assert "chunks=4" in repr(p4)
    assert p4.volume["all_to_all_count"] == 4
    assert np.array_equal(np.asarray(p4.fft(x)), np.asarray(bulk.fft(x)))
    # requested counts clamp to what the rows can carry
    assert plan(FFTSpec(shape=(8, 1 << 12), mesh=mesh, chunks=3)).chunks == 2
    # auto on the ft path reuses FTConfig.transactions (clamped to groups)
    pft = plan(FFTSpec(shape=(8, 1 << 12), mesh=mesh, chunks=0,
                       ft=FTConfig(groups=4, transactions=4)))
    assert pft.chunks == 4
    r = pft.ft_fft(x)
    rb = plan(FFTSpec(shape=(8, 1 << 12), mesh=mesh,
                      ft=FTConfig(groups=4))).ft_fft(x)
    assert np.array_equal(np.asarray(r.y), np.asarray(rb.y))
    assert not np.asarray(r.flagged).any()


def test_plan_nd_chunks(rng):
    _need(4)
    mesh = _mesh1()
    x = jnp.asarray(_crand(rng, 8, 32, 64))
    pp = plan(FFTSpec(shape=(8, 32, 64), rank=2, mesh=mesh,
                      decomp="pencil", chunks=2))
    assert pp.chunks == 2
    bulk = plan(FFTSpec(shape=(8, 32, 64), rank=2, mesh=mesh,
                        decomp="pencil"))
    assert np.array_equal(np.asarray(pp.fft2(x)), np.asarray(bulk.fft2(x)))
    # slab has one bulk exchange per axis pair — chunks clamp to 1
    ps = plan(FFTSpec(shape=(8, 32, 64), rank=2, mesh=mesh,
                      decomp="slab", chunks=4))
    assert ps.chunks == 1


# ---------------------------------------------------------------------------
# serve: --fft-spec carries chunks, strict parsing
# ---------------------------------------------------------------------------


def test_serve_spec_arg_chunks_and_strictness():
    import argparse

    from repro.launch.serve import apply_fft_spec_arg, build_fft_spec

    def fresh():
        return argparse.Namespace(fft_n=1 << 12, fft_batch=8, fft_shards=1,
                                  fft_ft=False, fft_groups=None,
                                  fft_natural=True, fft_real=False,
                                  fft_chunks=1)

    a = fresh()
    apply_fft_spec_arg(a, "n=4096,chunks=4")
    assert a.fft_chunks == 4 and a.fft_n == 4096
    a = fresh()
    apply_fft_spec_arg(a, "chunks=auto")
    assert a.fft_chunks == 0
    with pytest.raises(ValueError, match="empty segment at position 2"):
        apply_fft_spec_arg(fresh(), "n=8,,batch=4")
    with pytest.raises(ValueError, match="duplicate key 'n'"):
        apply_fft_spec_arg(fresh(), "n=8,n=16")
    with pytest.raises(SystemExit, match="unknown key"):
        apply_fft_spec_arg(fresh(), "n=8,bogus=1")
    with pytest.raises(ValueError):
        apply_fft_spec_arg(fresh(), "chunks=-2")
    s = build_fft_spec((8, 1 << 12), chunks=2)
    assert s.chunks == 2


@pytest.mark.slow
def test_serve_threads_chunks_subprocess():
    out = run_py("""
import numpy as np, jax, jax.numpy as jnp
from repro.launch.serve import serve_fft

rng = np.random.default_rng(7)
b, n = 8, 1 << 12
x = (rng.standard_normal((b, n)) + 1j * rng.standard_normal((b, n))
     ).astype(np.complex64)
y0, _ = serve_fft(x, shards=4)
y2, info = serve_fft(x, shards=4, chunks=2)
assert info["chunks"] == 2, info
assert np.array_equal(np.asarray(y0), np.asarray(y2))
print('OK')
""", devices=4)
    assert "OK" in out

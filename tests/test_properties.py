"""Property-based FT tests (hypothesis). The whole module skips cleanly when
hypothesis is not installed — the deterministic versions of these contracts
live in test_abft.py / test_kernels.py, so collection never depends on an
optional package.
"""
import numpy as np
import pytest

pytest.importorskip("hypothesis")

from hypothesis import assume, given, settings, strategies as st  # noqa: E402

import jax.numpy as jnp  # noqa: E402

from repro.core import abft  # noqa: E402
from repro.kernels import ops  # noqa: E402
from repro.launch.mesh import make_mesh  # noqa: E402


# hypothesis: ft_matmul detects any sufficiently large injected error
@settings(max_examples=15, deadline=None)
@given(row=st.integers(0, 63), col=st.integers(0, 47),
       eps=st.floats(min_value=50.0, max_value=1e4))
def test_property_ft_matmul_detects(row, col, eps):
    rng = np.random.default_rng(row * 100 + col)
    x = rng.standard_normal((64, 32)).astype(np.float32)
    w = rng.standard_normal((32, 48)).astype(np.float32)
    y, stats = abft.ft_matmul(jnp.asarray(x), jnp.asarray(w),
                              inject=jnp.asarray([row, col, eps]))
    assert float(stats["flagged"]) == 1.0
    np.testing.assert_allclose(np.asarray(y), x @ w, rtol=0,
                               atol=2e-2 * np.abs(x @ w).max())


# hypothesis: the grouped sharded ABFT is a pure observer — ANY group count
# G dividing B leaves the transform output bitwise identical (the checksum
# rows ride alongside the data; they never touch its compute), and clean
# runs never flag at any G
@settings(max_examples=12, deadline=None)
@given(g=st.sampled_from([1, 2, 4, 8]), ln=st.integers(8, 10),
       seed=st.integers(0, 2 ** 16))
def test_property_group_count_invariance(g, ln, seed):
    from repro.core.fft.distributed import ft_distributed_fft

    mesh = make_mesh((1,), ("fft",))
    rng = np.random.default_rng(seed)
    b, n = 8, 1 << ln
    x = (rng.standard_normal((b, n)) + 1j * rng.standard_normal((b, n))
         ).astype(np.complex64)
    base = ft_distributed_fft(x, mesh, groups=1)
    res = ft_distributed_fft(x, mesh, groups=g)
    assert not bool(res.flagged.any()), np.asarray(res.group_score)
    assert np.array_equal(np.asarray(base.y), np.asarray(res.y))


# hypothesis: the chunked multi-transaction pipeline is an execution
# schedule, not a numerical change — ANY chunk count C (feasible or not:
# infeasible requests clamp) leaves the transform output bitwise identical
# to the bulk pipeline, in both digit orders, and the chunked grouped-ABFT
# path flags nothing on clean inputs
@settings(max_examples=12, deadline=None)
@given(c=st.integers(1, 8), ln=st.integers(8, 10),
       natural=st.booleans(), seed=st.integers(0, 2 ** 16))
def test_property_chunk_count_invariance(c, ln, natural, seed):
    from repro.core.fft.distributed import (distributed_fft,
                                            ft_distributed_fft)

    mesh = make_mesh((1,), ("fft",))
    rng = np.random.default_rng(seed)
    b, n = 8, 1 << ln
    x = (rng.standard_normal((b, n)) + 1j * rng.standard_normal((b, n))
         ).astype(np.complex64)
    base = distributed_fft(x, mesh, natural_order=natural)
    y = distributed_fft(x, mesh, natural_order=natural, chunks=c)
    assert np.array_equal(np.asarray(base), np.asarray(y))
    ft_base = ft_distributed_fft(x, mesh, groups=4)
    ft_res = ft_distributed_fft(x, mesh, groups=4, chunks=c)
    assert not bool(ft_res.flagged.any()), np.asarray(ft_res.group_score)
    assert np.array_equal(np.asarray(ft_base.y), np.asarray(ft_res.y))


# hypothesis: inject -> detect -> correct round trip. Any single SEU above
# the noise floor lands in exactly one group, decodes correctable at the
# right global signal, and the corrected output matches the fault-free run
# to checksum-roundoff; a disabled injection is bitwise-invisible
@settings(max_examples=15, deadline=None)
@given(
    g=st.sampled_from([1, 2, 4]),
    sig=st.integers(0, 7),
    row=st.integers(0, 15),
    col=st.integers(0, 15),
    eps_r=st.floats(-200, 200),
    eps_i=st.floats(-200, 200),
)
def test_property_injection_roundtrip(g, sig, row, col, eps_r, eps_i):
    assume(abs(eps_r) + abs(eps_i) > 5.0)  # above noise floor
    from repro.core.fft.distributed import ft_distributed_fft

    mesh = make_mesh((1,), ("fft",))
    rng = np.random.default_rng(sig * 256 + row * 16 + col)
    b, n = 8, 256
    x = (rng.standard_normal((b, n)) + 1j * rng.standard_normal((b, n))
         ).astype(np.complex64)
    clean = ft_distributed_fft(x, mesh, groups=g)
    off = jnp.asarray([[0, sig, row, col, 0, eps_r, eps_i]], jnp.float32)
    disabled = ft_distributed_fft(x, mesh, groups=g, inject=off)
    assert np.array_equal(np.asarray(clean.y), np.asarray(disabled.y))

    inj = jnp.asarray([[0, sig, row, col, 1, eps_r, eps_i]], jnp.float32)
    res = ft_distributed_fft(x, mesh, groups=g, inject=inj)
    grp = sig // (b // g)
    assert bool(res.flagged[grp]) and bool(res.correctable[grp])
    assert int(res.location[grp]) == sig
    assert int(res.corrected) == 1
    ref = np.asarray(clean.y)
    np.testing.assert_allclose(np.asarray(res.y), ref, rtol=0,
                               atol=1e-4 * np.abs(ref).max())


# hypothesis: any injected FFT error above the noise floor is detected,
# located, and corrected by the fused two-sided ABFT kernel
@settings(max_examples=20, deadline=None)
@given(
    tile=st.integers(0, 3),
    row=st.integers(0, 7),
    col=st.integers(0, 255),
    eps_r=st.floats(-200, 200),
    eps_i=st.floats(-200, 200),
    txn=st.sampled_from([1, 2, 4]),
)
def test_property_seu_detect_correct(tile, row, col, eps_r, eps_i, txn):
    assume(abs(eps_r) + abs(eps_i) > 5.0)  # above noise floor
    b, n, bs = 32, 256, 8
    rng = np.random.default_rng(tile * 1000 + row * 100 + col)
    x = (rng.standard_normal((b, n)) + 1j * rng.standard_normal((b, n))
         ).astype(np.complex64)
    want = np.fft.fft(x)
    inj = jnp.asarray([tile, row, col, 1, eps_r, eps_i], dtype=jnp.float32)
    res = ops.ft_fft(x, transactions=txn, bs=bs, inject=inj)
    sig = tile * bs + row
    flagged = np.asarray(res.flagged)
    assert flagged.sum() == 1
    assert np.asarray(res.location)[int(np.argmax(flagged))] == sig
    np.testing.assert_allclose(np.asarray(res.y), want,
                               atol=1e-4 * np.abs(want).max())


# hypothesis: the real plan round trip is exact to dtype roundoff and
# matches jnp.fft.rfft2/irfft2 for any power-of-two grid, both precisions
@settings(max_examples=12, deadline=None)
@given(lr=st.integers(3, 6), lc=st.integers(3, 7),
       f64=st.booleans(), seed=st.integers(0, 2 ** 16))
def test_property_rfft2_matches_jnp_and_roundtrips(lr, lc, f64, seed):
    from repro.core.fft.api import plan, spec_for

    rng = np.random.default_rng(seed)
    dt, tol = (np.float64, 1e-11) if f64 else (np.float32, 4e-5)
    x = rng.standard_normal((2, 1 << lr, 1 << lc)).astype(dt)
    p = plan(spec_for(x, rank=2, real=True))
    y = np.asarray(p.rfft2(x))
    want = np.asarray(jnp.fft.rfft2(x))
    assert y.shape == want.shape
    assert np.abs(y - want).max() < tol * np.abs(want).max()
    back = np.asarray(p.irfft2(jnp.asarray(y)))
    assert back.dtype == dt
    assert np.abs(back - x).max() < tol * np.abs(x).max()
    # re-running the identical plan is deterministic bit-for-bit
    assert np.array_equal(np.asarray(p.rfft2(x)), y)


# hypothesis: Parseval on the half spectrum — sum |x|^2 = (sum of the
# doubled interior bins + the DC/Nyquist bins) / N, for any even length
@settings(max_examples=15, deadline=None)
@given(ln=st.integers(4, 12), seed=st.integers(0, 2 ** 16))
def test_property_rfft_parseval_half_spectrum(ln, seed):
    from repro.core.fft.extensions import rfft

    rng = np.random.default_rng(seed)
    n = 1 << ln
    x = rng.standard_normal((3, n)).astype(np.float32)
    y = np.asarray(rfft(jnp.asarray(x)))
    w = np.full(n // 2 + 1, 2.0)
    w[0] = w[-1] = 1.0          # DC and Nyquist appear once in the full FFT
    lhs = np.sum(np.abs(x) ** 2, axis=-1)
    rhs = np.sum(w * np.abs(y) ** 2, axis=-1) / n
    np.testing.assert_allclose(lhs, rhs, rtol=2e-4)

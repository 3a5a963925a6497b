"""Pallas kernels vs pure-jnp oracles: shape/dtype sweeps + FT properties.

Shared rng / complex-batch helpers come from conftest.py (``rng`` / ``crand``
fixtures); the hypothesis property tests live in test_properties.py so this
module collects without optional packages.
"""
import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.core.fft import factors
from repro.kernels import ops, ref
from repro.kernels.stockham import Tiling, block_fft_pallas, sublane_dft
from repro.kernels.stockham_abft import abft_fft_pallas


def _fft64(x, inverse=False):
    """The float64 NumPy transform of ``x``."""
    x = np.asarray(x, np.complex128)
    return np.fft.ifft(x) if inverse else np.fft.fft(x)


# N <= 2048 reads and writes the natural planes, 4096 and 8192 the blocks;
# b = 24 in tiles of 8 is below a chunk of 128 / R signals for R <= 8, and
# a tile of 64 is several chunks for R >= 4
@pytest.mark.parametrize("n", [128, 256, 512, 1024, 2048, 4096, 8192])
@pytest.mark.parametrize("b,bs", [(8, 8), (32, 16), (24, 8), (128, 64)])
def test_block_fft_kernel_sweep(n, b, bs, crand):
    x = crand(b, n)
    yr, yi = block_fft_pallas(jnp.real(x), jnp.imag(x), bs=bs)
    got = np.asarray(yr) + 1j * np.asarray(yi)
    want = _fft64(x)
    np.testing.assert_allclose(got, want, atol=3e-5 * np.abs(want).max())


@pytest.mark.parametrize("n", [16, 64])  # small & non-128-aligned radices
def test_block_fft_kernel_small_n(n, crand):
    x = crand(8, n)
    yr, yi = block_fft_pallas(jnp.real(x), jnp.imag(x), bs=8)
    got = np.asarray(yr) + 1j * np.asarray(yi)
    np.testing.assert_allclose(got, np.fft.fft(x), atol=2e-5 * n)


def test_block_fft_kernel_fp64(crand):
    x = crand(8, 1024, np.complex128)
    yr, yi = block_fft_pallas(jnp.real(x), jnp.imag(x), bs=8)
    got = np.asarray(yr) + 1j * np.asarray(yi)
    want = np.fft.fft(x)
    np.testing.assert_allclose(got, want, atol=1e-12 * np.abs(want).max())


@pytest.mark.parametrize("n", [256, 512, 1024, 2048, 4096])
def test_block_fft_kernel_inverse(n, crand):
    x = crand(32, n)
    yr, yi = block_fft_pallas(jnp.real(x), jnp.imag(x), bs=16, inverse=True)
    got = np.asarray(yr) + 1j * np.asarray(yi)
    want = _fft64(x, inverse=True)
    np.testing.assert_allclose(got, want, atol=3e-7)


@pytest.mark.parametrize("dtype", [np.float32, np.float64])
@pytest.mark.parametrize("inverse", [False, True], ids=["fft", "ifft"])
@pytest.mark.parametrize("r", [2, 4, 8, 16])
def test_sublane_butterflies_match_dense_stage(r, inverse, dtype, rng):
    """The natural path's butterflies over a chunk's R row tiles equal the
    dense ``kron(W_R, I_cs)`` product, in an interpreted Pallas kernel,
    without promoting the planes' dtype."""
    cs = 8
    xr, xi = rng.standard_normal((2, r * cs, 128)).astype(dtype)

    def kernel(xr_ref, xi_ref, yr_ref, yi_ref):
        yr_ref[...], yi_ref[...] = sublane_dft(xr_ref[...], xi_ref[...], r,
                                               inverse=inverse)

    out = jax.ShapeDtypeStruct(xr.shape, dtype)
    yr, yi = pl.pallas_call(kernel, out_shape=[out, out],
                            interpret=True)(xr, xi)
    assert yr.dtype == yi.dtype == dtype
    want = (np.kron(factors.dft_matrix(r, inverse=inverse), np.eye(cs))
            @ (xr + 1j * xi))
    got = np.asarray(yr) + 1j * np.asarray(yi)
    tol = 4 * np.finfo(dtype).eps * np.log2(r) * np.abs(want).max()
    np.testing.assert_allclose(got, want, atol=tol)


# the natural path's sublane stage needs no table; R == 1 has none
@pytest.mark.parametrize("n,bs,layout,tables", [
    (64, 8, "natural", 2), (128, 8, "natural", 2), (1024, 8, "natural", 4),
    (2048, 16, "natural", 4), (1024, 4, "blocks", 6), (4096, 8, "blocks", 6),
    (8192, 32, "blocks", 6)])
def test_tiling_consts_tables(n, bs, layout, tables):
    tiling = Tiling(n, bs)
    assert tiling.natural == (layout == "natural")
    assert len(tiling.consts()) == tables


# batches padded to whole tiles of 8 signals or more: below 8 (the serving
# runtime's batches) and beyond a tile
@pytest.mark.parametrize("n", [256, 1024, 2048])
@pytest.mark.parametrize("b", [3, 40])
@pytest.mark.parametrize("inverse", [False, True], ids=["fft", "ifft"])
def test_ops_fft_padded_batch(n, b, inverse, crand):
    x = crand(b, n)
    got = np.asarray(ops.ifft(x) if inverse else ops.fft(x))
    want = _fft64(x, inverse)
    np.testing.assert_allclose(got, want, atol=3e-5 * np.abs(want).max())


@pytest.mark.parametrize("n", [1 << 14, 1 << 17])
def test_ops_fft_multipass(n, crand):
    x = crand(2, n)
    got = np.asarray(ops.fft(x))
    want = np.fft.fft(x)
    np.testing.assert_allclose(got, want, atol=4e-5 * np.abs(want).max())


def test_ops_ifft_roundtrip(crand):
    x = crand(4, 2048)
    got = np.asarray(ops.ifft(ops.fft(x)))
    np.testing.assert_allclose(got, x, atol=2e-6 * np.abs(x).max())


def test_ops_ifft_roundtrip_multipass(crand):
    """Each pass's block kernel applies its own 1/f_i: the multi-pass
    inverse is normalized once, by 1/N."""
    x = crand(4, 1 << 14)
    got = np.asarray(ops.ifft(ops.fft(x)))
    np.testing.assert_allclose(got, x, atol=2e-6 * np.abs(x).max())


# ---------------------------------------------------------------------------
# Fused two-sided ABFT kernel
# ---------------------------------------------------------------------------

# N = 2048 and 4096: the natural and the blocks path at their largest R
ABFT_CASES = [(1, 512), (2, 512), (4, 512), (2, 2048), (2, 4096)]


@pytest.mark.parametrize("transactions,n", ABFT_CASES)
@pytest.mark.parametrize("per_signal", [True, False])
def test_abft_fft_clean_no_false_alarm(transactions, per_signal, n, crand):
    x = crand(32, n)
    res = ops.ft_fft(x, transactions=transactions, bs=8,
                     per_signal=per_signal)
    want = np.fft.fft(x)
    np.testing.assert_allclose(np.asarray(res.y), want,
                               atol=3e-5 * np.abs(want).max())
    assert int(res.corrected) == 0
    assert not np.asarray(res.flagged).any()
    if per_signal:
        assert float(np.asarray(res.delta).max()) < 1e-4


@pytest.mark.parametrize("transactions,n", ABFT_CASES)
@pytest.mark.parametrize("per_signal", [True, False])
def test_abft_fft_detect_locate_correct(transactions, per_signal, n, crand):
    b, bs = 32, 8
    x = crand(b, n)
    want = _fft64(x)
    tile, row, col = 2, 5, 37 * n // 512
    sig = tile * bs + row
    inj = jnp.asarray([tile, row, col, 1, 40.0, 25.0], dtype=jnp.float32)
    # the SEU lands on signal tile * bs + row at natural output column col
    raw = ops.ft_fft(x, transactions=transactions, bs=bs,
                     per_signal=per_signal, inject=inj, correct=False)
    err = np.abs(np.asarray(raw.y) - want)
    assert np.unravel_index(np.argmax(err), err.shape) == (sig, col)
    res = ops.ft_fft(x, transactions=transactions, bs=bs,
                     per_signal=per_signal, inject=inj)
    flagged = np.asarray(res.flagged)
    loc = np.asarray(res.location)
    assert flagged.sum() == 1
    g = int(np.argmax(flagged))
    assert loc[g] == sig  # e3/e2 ratio decodes the corrupted signal
    if per_signal:
        # per-signal (thread-level) checksum flags the same signal
        assert int(np.argmax(np.asarray(res.delta))) == sig
    # delayed batched correction restores the output without recompute
    np.testing.assert_allclose(np.asarray(res.y), want,
                               atol=5e-5 * np.abs(want).max())


def test_abft_fft_correction_disabled_keeps_error(crand):
    b, n, bs = 16, 256, 8
    x = crand(b, n)
    inj = jnp.asarray([0, 0, 0, 1, 100.0, 0.0], dtype=jnp.float32)
    res = ops.ft_fft(x, transactions=1, bs=bs, correct=False, inject=inj)
    err = np.abs(np.asarray(res.y) - np.fft.fft(x)).max()
    assert err > 50.0  # error still present
    assert np.asarray(res.flagged).any()


def test_abft_fft_fp64(crand):
    x = crand(16, 1024, np.complex128)
    inj = jnp.asarray([1, 2, 3, 1, 7.0, -3.0], dtype=jnp.float32)
    res = ops.ft_fft(x, transactions=2, bs=8, inject=inj, threshold=1e-8)
    want = np.fft.fft(x)
    np.testing.assert_allclose(np.asarray(res.y), want,
                               atol=1e-9 * np.abs(want).max())
    assert int(res.corrected) == 1


def test_abft_fft_ragged_batch(crand):
    """Batches not divisible by bs pad with zero signals instead of silently
    truncating the remainder (regression: tiles = b // bs dropped it, then
    the kernel's b % bs assertion fired)."""
    b, n, bs = 13, 256, 8   # prime batch, bs does not divide it
    x = crand(b, n)
    want = np.fft.fft(x)
    res = ops.ft_fft(x, transactions=1, bs=bs)
    assert res.y.shape == (b, n) and res.delta.shape == (b,)
    assert not np.asarray(res.flagged).any()
    np.testing.assert_allclose(np.asarray(res.y), want,
                               atol=4e-5 * np.abs(want).max())
    # detect -> locate -> correct still lands on the right (real) signal
    inj = jnp.asarray([0, 2, 9, 1, 60.0, -10.0], dtype=jnp.float32)
    res = ops.ft_fft(x, transactions=1, bs=bs, inject=inj)
    assert int(res.corrected) == 1
    np.testing.assert_allclose(np.asarray(res.y), want,
                               atol=1e-4 * np.abs(want).max())


@pytest.mark.parametrize("n", [256, 4096])
def test_abft_location_ids_count_within_group(n, crand):
    """The e3 checksums weight each signal by its 1-based id within its
    transaction group, so their size, and the float32 rounding that the
    location estimate divides by the error, does not grow with the batch."""
    b, bs, t = 32, 8, 2
    x = crand(b, n)
    _, _, _, cs = abft_fft_pallas(jnp.real(x), jnp.imag(x), bs=bs,
                                  transactions=t)
    ids = np.arange(b) % (bs * t) + 1.0
    want = (ids[:, None] * x.astype(np.complex128)).reshape(-1, bs * t, n)
    got = np.asarray(cs[:, 2]) + 1j * np.asarray(cs[:, 3])
    np.testing.assert_allclose(got, want.sum(axis=1),
                               atol=1e-5 * np.abs(want.sum(axis=1)).max())


def test_abft_multi_transaction_checksum_equivalence(crand):
    """T transactions accumulate exactly the same group checksums as T=1
    over the same signals (paper §4.3: 'the workload of ABFT remains the
    same'), so detection behaviour is transaction-count invariant."""
    x = crand(32, 256)
    r1 = ops.ft_fft(x, transactions=1, bs=32)
    r4 = ops.ft_fft(x, transactions=4, bs=8)
    np.testing.assert_allclose(np.asarray(r1.group_score),
                               np.asarray(r4.group_score), atol=1e-5)
    np.testing.assert_allclose(np.asarray(r1.y), np.asarray(r4.y),
                               atol=1e-5 * np.abs(np.asarray(r1.y)).max())


# ---------------------------------------------------------------------------
# fused ABFT GEMM kernel
# ---------------------------------------------------------------------------
from repro.kernels.ft_matmul import ft_matmul_pallas


@pytest.mark.parametrize("m,k,n", [(128, 128, 128), (256, 384, 256),
                                   (384, 128, 512)])
def test_ft_matmul_kernel_sweep(m, k, n):
    rng = np.random.default_rng(m + k + n)
    x = rng.standard_normal((m, k)).astype(np.float32)
    w = rng.standard_normal((k, n)).astype(np.float32)
    res = ft_matmul_pallas(jnp.asarray(x), jnp.asarray(w))
    want = x @ w
    np.testing.assert_allclose(np.asarray(res.c), want,
                               atol=2e-4 * np.abs(want).max())
    # fused output checksums == true column sums / location sums;
    # predictions agree on a clean run
    np.testing.assert_allclose(np.asarray(res.out2), want.sum(0),
                               atol=1e-2 * np.abs(want.sum(0)).max())
    loc = np.arange(1, m + 1, dtype=np.float64)
    want3 = loc @ want.astype(np.float64)
    np.testing.assert_allclose(np.asarray(res.out3), want3,
                               rtol=0, atol=1e-4 * np.abs(want3).max())
    for out, pred in ((res.out2, res.pred2), (res.out3, res.pred3)):
        rel = np.abs(np.asarray(out) - np.asarray(pred)).max() / (
            np.abs(np.asarray(pred)).max() + 1e-9)
        assert rel < 1e-4


def test_ft_matmul_kernel_in_kernel_injection_locates():
    """An in-kernel SEU diverges out2 vs pred2 at the hit column AND the
    location ratio d3/d2 decodes to row + 1 — the two-side contract the
    plan-layer decode (core.abft.gemm.decode_columns) relies on."""
    rng = np.random.default_rng(5)
    x = rng.standard_normal((256, 128)).astype(np.float32)
    w = rng.standard_normal((128, 128)).astype(np.float32)
    row, col, eps = 201, 13, 1000.0
    res = ft_matmul_pallas(jnp.asarray(x), jnp.asarray(w),
                           inject=jnp.array([row, col, 1.0, eps]))
    want = x @ w
    assert abs(np.asarray(res.c)[row, col] - want[row, col] - eps) < 1e-2
    d2 = np.asarray(res.pred2) - np.asarray(res.out2)
    d3 = np.asarray(res.pred3) - np.asarray(res.out3)
    div = np.abs(d2)
    assert div[col] > 100.0  # corrupted column flagged
    assert np.median(div) < 1.0
    assert abs(d3[col] / d2[col] - (row + 1)) < 0.05  # location decodes


def test_ft_matmul_kernel_rejects_unaligned():
    with pytest.raises(ValueError, match="tile-aligned"):
        ft_matmul_pallas(jnp.zeros((100, 128)), jnp.zeros((128, 128)))

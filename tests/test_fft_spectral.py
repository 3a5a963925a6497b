"""Transposed-order spectral consumers: convolve/correlate/power_spectrum vs
numpy on the local path, plus the sharded invariants — the forward+inverse
transposed round trip is exact and lowers to exactly TWO all-to-alls and
ZERO all-gathers, and fft_convolve on the 2-D batch x pencil mesh matches
jnp.convolve. Multi-device checks run in one consolidated subprocess (the
XLA host-device-count flag must precede jax init) sized to stay in the fast
lane.
"""
import numpy as np
import pytest

from conftest import run_py

# ---------------------------------------------------------------------------
# in-process: local path vs numpy
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("mode", ["full", "same", "valid"])
def test_convolve_local_matches_numpy(mode, rng):
    from repro.core.fft.spectral import fft_convolve

    a = rng.standard_normal((3, 200)).astype(np.float32)
    v = rng.standard_normal(31).astype(np.float32)
    got = np.asarray(fft_convolve(a, v, mode=mode))
    want = np.stack([np.convolve(r, v, mode) for r in a])
    assert got.shape == want.shape
    assert got.dtype == np.float32          # real in -> real out
    np.testing.assert_allclose(got, want, atol=2e-5 * np.abs(want).max())


@pytest.mark.parametrize("mode", ["full", "same", "valid"])
def test_correlate_local_matches_numpy(mode, crand):
    from repro.core.fft.spectral import correlate

    a = crand(2, 160)
    v = crand(1, 24)[0]
    got = np.asarray(correlate(a, v, mode=mode))
    want = np.stack([np.correlate(r, v, mode) for r in a])
    assert got.shape == want.shape
    np.testing.assert_allclose(got, want, atol=4e-5 * np.abs(want).max())


def test_convolve_per_signal_kernels(crand, assert_spectrum_close):
    """A batch of kernels (one per signal) convolves row-wise."""
    from repro.core.fft.spectral import fft_convolve

    a = crand(4, 120)
    v = crand(4, 17)
    got = np.asarray(fft_convolve(a, v))
    want = np.stack([np.convolve(r, k, "full") for r, k in zip(a, v)])
    assert_spectrum_close(got, want)


def test_power_spectrum_local(crand):
    from repro.core.fft.spectral import power_spectrum

    x = crand(3, 512)
    got = np.asarray(power_spectrum(x))
    want = np.abs(np.fft.fft(x)) ** 2 / 512
    assert not np.iscomplexobj(got)
    np.testing.assert_allclose(got, want, atol=1e-4 * want.max())


def test_spectral_volume_model():
    """Two all-to-alls, zero gathers; the kernel rides the forward one."""
    from repro.core.fft.distributed import collective_volume, spectral_volume

    n, b, d = 1 << 14, 8, 4
    rt = spectral_volume(n, b, d)
    cv = spectral_volume(n, b, d, kernel_batch=1)
    plain = collective_volume(n, b, d, natural_order=False)
    assert rt["all_to_all_count"] == 2 and rt["all_gather_count"] == 0
    assert rt["gather_wire"] == 0.0
    # round trip = forward + equally-sized inverse transpose
    assert rt["hlo_bytes"] == pytest.approx(2 * plain["hlo_bytes"])
    # the kernel's spectrum adds 1/B of the forward volume, nothing more
    assert cv["hlo_bytes"] - rt["hlo_bytes"] == pytest.approx(
        plain["hlo_bytes"] / b)
    # 2-D mesh: each data shard moves 1/data of the rows
    half = spectral_volume(n, b, d, data_shards=2)
    assert half["hlo_bytes"] == pytest.approx(rt["hlo_bytes"] / 2)


def test_collective_volume_psum_tracks_itemsize():
    """The grouped ABFT verdict traffic is 8 scalars per checksum group
    (3 verdict-psum + 5 replicated-stats broadcast) plus one shared energy
    scalar, in the input's REAL dtype: f64 for complex128 — the model must
    scale with both the group count and the itemsize. The ungrouped
    pipeline (G = 1) has the same layout."""
    from repro.core.fft.distributed import collective_volume

    n, b, d = 1 << 14, 8, 4

    def psum_bytes(itemsize, groups=1):
        # transposed order isolates the psum: same a2a rows, no gather
        ft = collective_volume(n, b, d, ft=True, natural_order=False,
                               itemsize=itemsize, groups=groups)
        plain = collective_volume(n, b + 2 * groups, d, natural_order=False,
                                  itemsize=itemsize)
        return ft["hlo_bytes"] - plain["hlo_bytes"]

    assert psum_bytes(8) == pytest.approx(2.0 * (4 * 4 + 5 * 4))
    # pre-fix the verdict+score were f32-sized under complex128:
    assert psum_bytes(16) == pytest.approx(2.0 * (4 * 8 + 5 * 8))
    assert psum_bytes(8, groups=4) == pytest.approx(2.0 * 33 * 4)
    # grouped + data-sharded: each device psums only its own groups' stats
    half = collective_volume(n, b, d, ft=True, natural_order=False,
                             groups=4, data_shards=2)
    full = collective_volume(n, b, d, ft=True, natural_order=False, groups=4)
    assert half["psum_wire"] == pytest.approx(
        2.0 * 17 * 4 * (d - 1) / d)
    assert half["all_to_all_wire"] == pytest.approx(
        full["all_to_all_wire"] / 2)


# ---------------------------------------------------------------------------
# sharded invariants (one subprocess, 4 devices, fast-lane sized)
# ---------------------------------------------------------------------------


def test_transposed_order_invariants_and_convolve_on_mesh():
    """(1) ifft_t(fft_t(x)) == x with exactly 2 all-to-alls and 0 all-gathers
    (the parsed collectives of the composed jit); (2) fft_convolve on the 2-D
    data x fft mesh matches jnp.convolve and meets the same collective
    budget, with HLO bytes equal to spectral_volume's model; (3) the
    kernels.ops entry points thread natural_order through."""
    out = run_py("""
import numpy as np, jax, jax.numpy as jnp
from repro.launch.mesh import make_mesh
from repro.core.fft.distributed import (distributed_fft, distributed_ifft,
                                        spectral_volume)
from repro.core.fft import spectral
from repro.analysis.hlo import parse_collectives, summarize
from repro.launch.mesh import make_fft_mesh

rng = np.random.default_rng(3)
b, n = 8, 1 << 12
x = (rng.standard_normal((b, n)) + 1j * rng.standard_normal((b, n))
     ).astype(np.complex64)

# ---- 1-D fft mesh: transposed round trip -------------------------------
mesh = make_mesh((4,), ("fft",))
yt = distributed_fft(x, mesh, natural_order=False)
back = np.asarray(distributed_ifft(yt, mesh, natural_order=False))
assert np.abs(back - x).max() / np.abs(x).max() < 4e-5

rt = jax.jit(lambda v: distributed_ifft(
    distributed_fft(v, mesh, natural_order=False), mesh,
    natural_order=False))
cb = summarize(parse_collectives(
    rt.lower(jnp.asarray(x)).compile().as_text()))
assert cb["count"]["all-to-all"] == 2, cb["count"]
assert cb["count"]["all-gather"] == 0, cb["count"]
assert cb["bytes"]["all-gather"] == 0.0
mdl = spectral_volume(n, b, 4)
assert abs(cb["total_bytes"] / mdl["hlo_bytes"] - 1.0) < 1e-3

# plan-level threading: the same pipeline through the plan executors
from repro.core.fft import FFTSpec, plan
pt = plan(FFTSpec(shape=x.shape, mesh=mesh, natural_order=False))
yt2 = pt.fft(x)
np.testing.assert_array_equal(np.asarray(yt2), np.asarray(yt))
back2 = np.asarray(pt.ifft(yt2))
assert np.abs(back2 - x).max() / np.abs(x).max() < 4e-5

# ragged batch exercises the pad+slice path (correctness, not budget)
x6 = x[:6]
back6 = np.asarray(distributed_ifft(
    distributed_fft(x6, mesh, natural_order=False), mesh,
    natural_order=False))
assert np.abs(back6 - x6).max() / np.abs(x6).max() < 4e-5

# ---- 2-D batch x pencil mesh: convolution end-to-end -------------------
mesh2 = make_fft_mesh(2, data=2)
assert dict(mesh2.shape) == {"data": 2, "fft": 2}
a = rng.standard_normal((b, 1500)).astype(np.float32)
v = rng.standard_normal(63).astype(np.float32)
got = np.asarray(spectral.fft_convolve(a, v, mesh2, mode="same"))
want = np.stack([np.asarray(jnp.convolve(jnp.asarray(r), jnp.asarray(v),
                                         "same")) for r in a])
assert got.shape == want.shape
assert np.abs(got - want).max() < 2e-4 * np.abs(want).max()

# the fused pipeline's collective budget on the 2-D mesh
aa = (rng.standard_normal((b, n)) + 1j * rng.standard_normal((b, n))
      ).astype(np.complex64)
vv = (rng.standard_normal((1, n)) + 1j * rng.standard_normal((1, n))
      ).astype(np.complex64)
fn = spectral._spectral_pair_fn(mesh2, "fft", "data", False)
cb2 = summarize(parse_collectives(
    fn.lower(jnp.asarray(aa), jnp.asarray(vv)).compile().as_text()))
assert cb2["count"]["all-to-all"] == 2, cb2["count"]
assert cb2["count"]["all-gather"] == 0, cb2["count"]
mdl2 = spectral_volume(n, b, 2, kernel_batch=1, data_shards=2)
assert abs(cb2["total_bytes"] / mdl2["hlo_bytes"] - 1.0) < 1e-3

# transposed power spectrum: bins permuted, energy identical
ps = np.asarray(spectral.power_spectrum(aa, mesh2))
ref = np.abs(np.fft.fft(aa)) ** 2 / n
assert np.abs(np.sort(ps, -1) - np.sort(ref, -1)).max() < 1e-4 * ref.max()

# ragged batch on the 2-D mesh (regression: the pad quantum ignored the
# fft-shard factor when data did not divide, then raised mid-pipeline)
x5 = x[:5]
back5 = np.asarray(distributed_ifft(
    distributed_fft(x5, mesh2, natural_order=False), mesh2,
    natural_order=False))
assert np.abs(back5 - x5).max() / np.abs(x5).max() < 4e-5
got5 = np.asarray(spectral.fft_convolve(a[:5], v, mesh2, mode="same"))
assert np.abs(got5 - want[:5]).max() < 2e-4 * np.abs(want).max()
print('OK')
""", devices=4)
    assert "OK" in out

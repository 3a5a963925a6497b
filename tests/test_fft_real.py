"""Real-input transforms end-to-end (``FFTSpec(real=True)``):
``plan(spec).rfft2/irfft2`` vs ``jnp.fft`` on the local, slab, and pencil
paths, the half-spectrum communication models, grouped two-side ABFT on the
Hermitian-symmetric checksum layout, the packed real spectral pipeline
(convolve / correlate / power_spectrum), and the serve threading.
Multi-device cases run in-process on >= 4 host devices (conftest.py asks
for 8) and via subprocess in the slow lane, from one shared scenario
catalogue so the lanes cannot drift.
"""
import numpy as np
import pytest

import jax.numpy as jnp

from conftest import run_py

# ---------------------------------------------------------------------------
# in-process: spec validation + dtype policy
# ---------------------------------------------------------------------------


def test_real_spec_validation():
    from repro.core.fft.api import FFTSpec, FTConfig

    with pytest.raises(ValueError, match="rank=3"):
        FFTSpec(shape=(8, 16, 32), rank=3, real=True)
    with pytest.raises(ValueError, match="natural-order"):
        FFTSpec(shape=(4, 1024), natural_order=False, real=True)
    with pytest.raises(ValueError, match="no ft pipeline"):
        FFTSpec(shape=(4, 1024), ft=FTConfig(), real=True)
    # rank-2 real + ft is the supported ABFT pipeline
    FFTSpec(shape=(8, 32, 64), rank=2, ft=FTConfig(), real=True)


def test_spec_for_real_dtype_policy():
    from repro.core.fft.api import spec_for

    x32 = jnp.zeros((2, 64), jnp.float32)
    x64 = jnp.zeros((2, 64), jnp.float64)
    assert spec_for(x32, real=True).dtype == "complex64"
    # a real fp64 operand keeps full precision (the C2C coercion squashes
    # every real dtype to complex64; the real spec must not)
    assert spec_for(x64, real=True).dtype == "complex128"
    assert spec_for(x64).dtype == "complex64"
    assert spec_for(x32, real=True).real and not spec_for(x32).real


def test_plan_executor_guards(rng):
    from repro.core.fft.api import FFTSpec, plan

    preal = plan(FFTSpec(shape=(2, 32, 64), rank=2, real=True))
    pc2c = plan(FFTSpec(shape=(2, 32, 64), rank=2))
    x = rng.standard_normal((2, 32, 64)).astype(np.float32)
    with pytest.raises(ValueError, match="real-input"):
        preal.fft(x)
    with pytest.raises(ValueError, match="real-input"):
        preal.ifft(x)
    with pytest.raises(ValueError, match="real=True"):
        pc2c.rfft(x)
    with pytest.raises(ValueError, match="real=True"):
        pc2c.irfft(x)
    with pytest.raises(ValueError, match="real operand"):
        preal.rfft(x.astype(np.complex64))
    # the half-spectrum shape contract: bins must be C/2 + 1
    with pytest.raises(ValueError, match="half-spectrum"):
        preal.irfft(jnp.zeros((2, 32, 64), jnp.complex64))
    with pytest.raises(ValueError, match="rank-2"):
        plan(FFTSpec(shape=(2, 1024), real=True)).rfft2(x[:, 0])


# ---------------------------------------------------------------------------
# in-process: local path vs jnp.fft / numpy
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("shape", [(64, 128), (32, 256), (256, 32)])
@pytest.mark.parametrize("dtype", [np.float32, np.float64])
def test_local_rfft2_matches_jnp(shape, dtype, rng, assert_spectrum_close):
    from repro.core.fft.api import plan, spec_for

    x = rng.standard_normal((3,) + shape).astype(dtype)
    p = plan(spec_for(x, rank=2, real=True))
    want = np.asarray(jnp.fft.rfft2(x))
    got = p.rfft2(x)
    assert got.shape == (3,) + shape[:-1] + (shape[-1] // 2 + 1,)
    assert_spectrum_close(got, want)
    back = p.irfft2(got)
    assert back.dtype == x.dtype
    assert_spectrum_close(back, x)


@pytest.mark.parametrize("shape", [(12, 30), (15, 64), (64, 22)])
def test_local_rfft2_odd_sizes(shape, rng, assert_spectrum_close):
    """Odd / non-power-of-two axes run the direct-DFT fallback (the
    distributed real slab stays power-of-two)."""
    from repro.core.fft.extensions import irfft2, rfft2

    x = rng.standard_normal((2,) + shape).astype(np.float32)
    want = np.asarray(jnp.fft.rfft2(x))
    got = rfft2(x)
    assert_spectrum_close(got, want)
    assert_spectrum_close(irfft2(jnp.asarray(got)), x)


def test_extensions_rfft2_rejects_complex(crand):
    from repro.core.fft.extensions import rfft2

    with pytest.raises(ValueError, match="real input"):
        rfft2(crand(2, 64).reshape(2, 8, 8))


@pytest.mark.parametrize("shape,rank", [((4, 64), 1), ((2, 8, 16), 2)])
def test_real_float32_plan_has_no_complex128(shape, rank, caplog):
    """A float32 real plan stays complex64 end to end, even with x64 on:
    the TPU compiler aborts on complex128. Traced, the Hermitian-unpack
    twiddles were an on-device exp of int64 iotas; called eagerly (as the
    serving runtime calls a rank-1 plan), the packing trick's Python
    complex scalars reached the device as complex128 operands."""
    import logging

    import jax

    from repro.core.fft.api import FFTSpec, plan

    p = plan(FFTSpec(shape=shape, rank=rank, real=True))
    half = shape[:-1] + (shape[-1] // 2 + 1,)
    for fn, arg in ((p.rfft, jnp.zeros(shape, jnp.float32)),
                    (p.irfft, jnp.zeros(half, jnp.complex64))):
        assert "complex<f64>" not in jax.jit(fn).lower(arg).as_text()
        with jax.log_compiles(True), caplog.at_level(logging.WARNING):
            fn(arg)     # eager: each program compiled is logged with its types
    assert "complex128" not in caplog.text


def test_power_spectrum_real_one_sided(rng):
    from repro.core.fft.spectral import power_spectrum

    x = rng.standard_normal((3, 1024)).astype(np.float32)
    got = np.asarray(power_spectrum(x, real=True))
    want = np.abs(np.fft.rfft(x)) ** 2 / 1024
    assert got.shape == (3, 513)
    np.testing.assert_allclose(got, want, atol=4e-5 * want.max())
    with pytest.raises(ValueError, match="real input"):
        power_spectrum(x.astype(np.complex64), real=True)
    with pytest.raises(ValueError, match="natural-order"):
        power_spectrum(x, real=True, natural_order=False)


@pytest.mark.parametrize("mode", ["full", "same", "valid"])
def test_real_convolve_correlate_local(mode, rng):
    """Real operands ride the packed pipeline (kernel on the imaginary
    part — ONE C2C transform pair) and still match numpy exactly."""
    from repro.core.fft.spectral import correlate, fft_convolve

    a = rng.standard_normal((3, 200)).astype(np.float32)
    v = rng.standard_normal(31).astype(np.float32)
    got = np.asarray(fft_convolve(a, v, mode=mode))
    want = np.stack([np.convolve(r, v, mode=mode) for r in a])
    assert got.dtype == np.float32 and got.shape == want.shape
    np.testing.assert_allclose(got, want, atol=2e-4 * np.abs(want).max())
    got = np.asarray(correlate(a, v, mode=mode))
    want = np.stack([np.correlate(r, v, mode=mode) for r in a])
    assert got.shape == want.shape, mode
    np.testing.assert_allclose(got, want, atol=2e-4 * np.abs(want).max())


def test_real_convolve_fp64_local(rng):
    from repro.core.fft.spectral import fft_convolve

    a = rng.standard_normal((2, 100)).astype(np.float64)
    v = rng.standard_normal(9).astype(np.float64)
    got = np.asarray(fft_convolve(a, v, mode="full"))
    want = np.stack([np.convolve(r, v) for r in a])
    assert got.dtype == np.float64
    np.testing.assert_allclose(got, want, atol=1e-11 * np.abs(want).max())


# ---------------------------------------------------------------------------
# in-process: communication models + layout specs
# ---------------------------------------------------------------------------


def test_collective_volume_real_model():
    from repro.core.fft.distributed import collective_volume

    n, b, d = 1 << 14, 8, 4
    real = collective_volume(n, b, d, real=True)
    c2c = collective_volume(n, b, d)
    # the packed transform IS the half-length C2C pipeline
    assert real == {**collective_volume(n // 2, b, d), "real": True}
    assert real["hlo_bytes"] == c2c["hlo_bytes"] / 2
    assert real["all_to_all_wire"] == c2c["all_to_all_wire"] / 2
    with pytest.raises(ValueError, match="no ft pipeline"):
        collective_volume(n, b, d, ft=True, real=True)


def test_collective_volume_nd_real_model():
    from repro.core.fft.multidim import collective_volume_nd

    rr, cc, b, d = 128, 256, 8, 4
    real = collective_volume_nd((rr, cc), b, d, real=True)
    c2c = collective_volume_nd((rr, cc), b, d)
    cp = cc // 2 + d
    assert real["real"] is True
    assert real["all_to_all_count"] == 1 and real["all_gather_count"] == 0
    assert real["hlo_bytes"] == b * rr * cp * 8 / d
    # the headline: the padded half spectrum moves (C/2 + D)/C of the
    # C2C slab bytes — comfortably under the 0.6x acceptance line
    assert real["hlo_bytes"] / c2c["hlo_bytes"] == pytest.approx(cp / cc)
    assert real["hlo_bytes"] <= 0.6 * c2c["hlo_bytes"]
    ft = collective_volume_nd((rr, cc), b, d, ft=True, groups=4, real=True)
    # psum: (3G+1) verdict scalars + the 5G replicated-stats broadcast,
    # f32 (the auditor pins this against the lowered HLO)
    assert ft["hlo_bytes"] == pytest.approx(
        (b + 8) * rr * cp * 8 / d + 2 * (3 * 4 + 1 + 5 * 4) * 4)
    with pytest.raises(ValueError, match="slab-only"):
        collective_volume_nd((rr, cc), b, d, decomp="pencil", real=True)


def test_spectral_volume_real_model():
    from repro.core.fft.distributed import spectral_volume

    n, b, d = 1 << 14, 8, 2
    real = spectral_volume(n, b, d, kernel_batch=1, real=True)
    c2c = spectral_volume(n, b, d, kernel_batch=1)
    assert real["real"] is True
    assert real["all_to_all_count"] == 2 and real["all_gather_count"] == 0
    # the kernel rides the imaginary part: its forward rows vanish, so
    # both passes move exactly b rows — 2*b*n/D elements total
    assert real["hlo_bytes"] == 2 * b * n * 8 / d
    assert real["hlo_bytes"] == pytest.approx(
        c2c["hlo_bytes"] * (2 * b) / (2 * b + 1))


def test_layout_specs_real_and_half_spectrum_shape():
    from jax.sharding import PartitionSpec as P

    from repro.parallel.fft_sharding import (half_spectrum_shape,
                                             layout_specs, slab_specs)

    assert layout_specs(2, "slab", data_axis="data", real=True) == \
        slab_specs(2, data_axis="data")
    assert layout_specs(2, "slab", real=True) == (P(None, "fft", None),
                                                  P(None, None, "fft"))
    with pytest.raises(ValueError, match="slab"):
        layout_specs(2, "pencil", real=True)
    with pytest.raises(ValueError, match="slab"):
        layout_specs(3, "slab", real=True)
    assert half_spectrum_shape((8, 64, 128)) == (8, 64, 65)
    assert half_spectrum_shape((31,)) == (16,)
    with pytest.raises(ValueError, match="non-empty"):
        half_spectrum_shape(())


# ---------------------------------------------------------------------------
# in-process: serve threading
# ---------------------------------------------------------------------------


def test_build_fft_spec_real(rng):
    from repro.serve.specs import build_fft_spec, serve_plan
    from repro.core.fft.api import plan

    spec = build_fft_spec((4, 32, 64), op="fft", dims=2, real=True)
    assert spec.real and spec.rank == 2 and spec.natural_order
    p = plan(spec)
    x = rng.standard_normal((4, 32, 64)).astype(np.float32)
    y, info = serve_plan(p, x, op="fft")
    assert info["real"] is True
    want = np.asarray(jnp.fft.rfft2(x))
    assert np.abs(np.asarray(y) - want).max() < 4e-5 * np.abs(want).max()
    with pytest.raises(ValueError, match="natural-order"):
        build_fft_spec((4, 1024), real=True, natural_order=False)


def test_serve_fft_real_rejects_complex(crand):
    from repro.launch.serve import serve_fft

    with pytest.raises(ValueError, match="real"):
        serve_fft(crand(2, 64), real=True)


# ---------------------------------------------------------------------------
# multi-device scenario catalogue (in-process on >= 4 devices, and via
# subprocess in the slow lane)
# ---------------------------------------------------------------------------

_REAL_EQUIV_CODE = """
import numpy as np, jax, jax.numpy as jnp
from repro.launch.mesh import make_mesh
from repro.core.fft import multidim as md
from repro.core.fft.api import FFTSpec, plan, spec_for
from repro.parallel.fft_sharding import shard_grid

mesh1 = make_mesh((4,), ("fft",))
mesh2 = make_mesh((2, 2), ("data", "fft"))
rng = np.random.default_rng(11)

def rel(a, b):
    return np.abs(np.asarray(a) - b).max() / (np.abs(b).max() + 1e-30)

# rank-2 real plans: slab AND pencil (the composed path), fp32 AND fp64,
# on the 1-D and the 2-D mesh, through the plan API
for shape, dt, tol in [((64, 128), np.float32, 4e-5),
                       ((256, 32), np.float32, 4e-5),
                       ((32, 64), np.float64, 1e-11)]:
    x = rng.standard_normal((4,) + shape).astype(dt)
    ref = np.asarray(jnp.fft.rfft2(x))
    for mesh in (mesh1, mesh2):
        for decomp in ("slab", "pencil"):
            p = plan(spec_for(x, rank=2, mesh=mesh, decomp=decomp,
                              real=True))
            assert p.spec.dtype == (
                "complex128" if dt == np.float64 else "complex64")
            y = p.rfft2(x)
            assert y.shape == ref.shape, (decomp, y.shape)
            assert rel(y, ref) < tol, (shape, dt, decomp, rel(y, ref))
            back = p.irfft2(y)
            assert np.asarray(back).dtype == dt
            assert rel(back, x) < tol, (shape, dt, decomp, "roundtrip")
    # pre-sharded slab input dispatches identically
    p = plan(spec_for(x, rank=2, mesh=mesh1, decomp="slab", real=True))
    assert rel(p.rfft2(p.shard(x)), ref) < tol

# module-level entry points agree with the plan path
x = rng.standard_normal((4, 64, 128)).astype(np.float32)
ref = np.asarray(jnp.fft.rfft2(x))
y = md.distributed_rfft2(x, mesh1)
assert rel(y, ref) < 4e-5
assert rel(md.distributed_irfft2(y, mesh1), x) < 4e-5

# rank-1 real plan: the packed pencil path on the mesh
x1 = rng.standard_normal((8, 1 << 13)).astype(np.float32)
p1 = plan(spec_for(x1, mesh=mesh1, real=True))
ref1 = np.fft.rfft(x1)
assert rel(p1.rfft(x1), ref1) < 4e-5
assert rel(p1.irfft(jnp.asarray(ref1.astype(np.complex64))), x1) < 4e-5

# real one-sided power spectrum through the planned mesh path
ps = plan(spec_for(x1, mesh=mesh1, real=True)).power_spectrum(x1)
want_ps = np.abs(ref1) ** 2 / x1.shape[-1]
assert rel(ps, want_ps) < 4e-5

# packed real 1-D convolution / correlation on the mesh vs numpy
from repro.core.fft.spectral import correlate, fft_convolve
a = rng.standard_normal((8, 2000)).astype(np.float32)
v = rng.standard_normal(31).astype(np.float32)
for mode in ("full", "same", "valid"):
    got = np.asarray(fft_convolve(a, v, mesh1, mode=mode))
    want = np.stack([np.convolve(r, v, mode=mode) for r in a])
    assert got.dtype == np.float32 and got.shape == want.shape
    assert np.abs(got - want).max() < 2e-4 * np.abs(want).max(), mode
    got = np.asarray(correlate(a, v, mesh1, mode=mode))
    want = np.stack([np.correlate(r, v, mode=mode) for r in a])
    assert np.abs(got - want).max() < 2e-4 * np.abs(want).max(), mode

# fused REAL 2-D convolution on the mesh
a2 = rng.standard_normal((4, 20, 24)).astype(np.float32)
v2 = rng.standard_normal((5, 7)).astype(np.float32)
full = np.real(np.fft.ifft2(np.fft.fft2(a2, s=(24, 30)) *
                            np.fft.fft2(v2, s=(24, 30))))
for mesh in (mesh1, mesh2):
    got = np.asarray(md.fft_convolve2(a2, v2, mesh, mode="full"))
    assert got.shape == (4, 24, 30)
    assert np.abs(got - full).max() < 2e-4 * np.abs(full).max()
print('OK')
"""

_REAL_FT_CODE = """
import numpy as np, jax, jax.numpy as jnp
from repro.launch.mesh import make_mesh
from repro.core.fft import multidim as md
from repro.core.fft.api import FFTSpec, FTConfig, plan

dtype = np.{dtype}
threshold = {threshold}
tol = {tol}
mesh = make_mesh({mesh_shape}, {mesh_axes})
rng = np.random.default_rng(13)
b, rr, cc, g = 8, 32, 64, 4
shards = mesh.shape["fft"]
cp = cc // 2 + shards                       # padded half-spectrum width
x = rng.standard_normal((b, rr, cc)).astype(dtype)
ref = np.asarray(jnp.fft.rfft2(x))
mag = 60.0 if dtype == np.float32 else 1e-6
ft = jnp.float64 if dtype == np.float64 else jnp.float32
p = plan(FFTSpec(shape=(b, rr, cc), rank=2, mesh=mesh,
                 dtype="complex128" if dtype == np.float64 else "complex64",
                 ft=FTConfig(threshold=threshold, groups=g), real=True))

def run(inj, **kw):
    if kw:
        return md.ft_distributed_rfft2(
            x, mesh, threshold=threshold, groups=g,
            inject=None if inj is None else jnp.asarray(inj, ft), **kw)
    return p.ft_fft(x, inject=None if inj is None
                    else jnp.asarray(inj, ft))

def err(res):
    return np.abs(np.asarray(res.y) - ref).max() / np.abs(ref).max()

# clean: no verdicts, exact half spectrum, quiet left checksums
clean = run(None)
assert np.asarray(clean.y).shape == ref.shape
assert not np.asarray(clean.flagged).any(), np.asarray(clean.group_score)
assert float(jnp.max(clean.shard_delta)) < max(1e-4, 10 * threshold)
assert err(clean) < tol

# k = 4 SEUs in 4 distinct groups on the padded half spectrum (one in a
# live bin past C/4, one in the Hermitian-padding tail): ALL corrected
inj4 = [[0, 1, 3, 1, 1, mag, mag / 4],
        [1, 2, 5, 2, 1, -mag / 2, mag],
        [1, 5, 7, cc // 2, 1, mag, -mag / 3],
        [0, 6, 2, cp - 1, 1, mag / 2, mag / 2]]
res = run(inj4)
assert np.asarray(res.flagged).all(), np.asarray(res.group_score)
assert np.asarray(res.correctable).all()
assert list(np.asarray(res.location)) == [1, 2, 5, 6]
assert int(res.corrected) == 4
assert err(res) < tol, err(res)
bad = run(inj4, correct=False)
assert err(bad) > 50 * tol

# 2 SEUs in ONE group: uncorrectable, repaired by the recompute path
inj2 = [[0, 4, 3, 1, 1, mag, mag / 4], [1, 5, 5, 2, 1, -mag / 2, mag]]
dbl = run(inj2, correct=True)
assert list(np.asarray(dbl.uncorrectable)) == [False, False, True, False]
assert not np.asarray(dbl.correctable).any()
assert int(dbl.corrected) == 0 and err(dbl) > 50 * tol
fixed = run(inj2, correct=True, recompute_uncorrectable=True)
assert int(fixed.recomputed) == 1
assert err(fixed) < tol, err(fixed)

# checksum-grid hits (cs2 / cs3 rows at the folded width): classified,
# data untouched
for sig, tag in ((b + 1, "cs2"), (b + g + 2, "cs3")):
    rc = run([[1, sig, 4, 2, 1, mag, -mag]])
    fl = np.asarray(rc.checksum_fault)
    assert fl.any() and np.asarray(rc.flagged)[np.argmax(fl)], tag
    assert not np.asarray(rc.correctable).any(), tag
    assert err(rc) < tol, (tag, err(rc))
print('OK')
"""


def _ft_params(mesh_shape, mesh_axes):
    return [
        dict(dtype="float32", threshold=1e-4, tol=4e-5,
             mesh_shape=mesh_shape, mesh_axes=mesh_axes),
        dict(dtype="float64", threshold=1e-10, tol=1e-11,
             mesh_shape=mesh_shape, mesh_axes=mesh_axes),
    ]


_MESHES = {"1d": ("(4,)", '("fft",)'), "2d": ("(2, 2)", '("data", "fft")')}


def _needs4():
    import jax

    if len(jax.devices()) < 4:
        pytest.skip("needs >= 4 host devices (tests/conftest.py asks "
                    "for 8 unless XLA_FLAGS names a count)")


def test_real_equivalence_inprocess():
    """Slab + pencil real plans vs jnp.fft.rfft2/irfft2 on 1-D and 2-D
    meshes, fp32 and fp64, plus the packed 1-D/2-D spectral consumers."""
    _needs4()
    exec(_REAL_EQUIV_CODE, {"__name__": "__requiv__"})


@pytest.mark.parametrize("meshname", sorted(_MESHES))
@pytest.mark.parametrize("dtype", ["float32", "float64"])
def test_real_ft_fault_matrix_inprocess(meshname, dtype):
    """k SEUs in k groups on the Hermitian half-spectrum checksum layout:
    detected, located, and corrected in one pass."""
    _needs4()
    shape, axes = _MESHES[meshname]
    p = [c for c in _ft_params(shape, axes) if c["dtype"] == dtype][0]
    exec(_REAL_FT_CODE.format(**p), {"__name__": "__rft__"})


@pytest.mark.slow
def test_real_equivalence_subprocess():
    assert "OK" in run_py(_REAL_EQUIV_CODE, devices=4)


@pytest.mark.slow
@pytest.mark.parametrize("meshname", sorted(_MESHES))
def test_real_ft_fault_matrix_subprocess(meshname):
    shape, axes = _MESHES[meshname]
    for p in _ft_params(shape, axes):
        assert "OK" in run_py(_REAL_FT_CODE.format(**p), devices=4)

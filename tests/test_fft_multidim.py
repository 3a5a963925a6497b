"""Distributed multi-dimensional FFT (core.fft.multidim): slab + pencil
equivalence vs jnp.fft.fft2/fftn, the decomposition chooser and its
communication model, grouped ABFT on the 2-D slab pass, and the fused 2-D
convolution. Multi-device cases run in-process on >= 4 host devices (the CI
mesh-8dev lane) and via subprocess in the slow lane, from one shared
scenario catalogue so the lanes cannot drift.
"""
import numpy as np
import pytest

import jax.numpy as jnp

from conftest import run_py
from repro.launch.mesh import make_mesh

# ---------------------------------------------------------------------------
# in-process: chooser + communication model
# ---------------------------------------------------------------------------


def test_choose_decomp_model_driven():
    import jax
    from repro.core.fft.multidim import choose_decomp

    mesh1 = make_mesh((1,), ("fft",))
    assert choose_decomp((64, 128), None) == "local"
    assert choose_decomp((64, 128), mesh1) == "local"
    if len(jax.devices()) < 2:
        return
    mesh = make_mesh((2,), ("fft",))
    # slab feasible: wins on volume (one all-to-all) / ties
    assert choose_decomp((64, 128), mesh, batch=8) == "slab"
    # slab infeasible (first axis does not divide): pencil takes over
    assert choose_decomp((1, 256), mesh) == "pencil"
    assert choose_decomp((64, 128), mesh, batch=8, ft=True) == "slab"


def test_choose_decomp_2d_mesh_tiebreak():
    """On a batch-of-one 2-D mesh, natural order keeps slab (its natural
    order is free; pencil would pay digit-restore gathers), while
    transposed order breaks the equal-volume tie toward pencil's smaller
    per-device block (the whole-mesh single-transform case)."""
    import jax
    from repro.core.fft.multidim import choose_decomp

    if len(jax.devices()) < 4:
        pytest.skip("needs >= 4 host devices")
    mesh2 = make_mesh((2, 2), ("data", "fft"))
    assert choose_decomp((64, 128), mesh2, batch=1) == "slab"
    assert choose_decomp((64, 128), mesh2, batch=1,
                         natural_order=False) == "pencil"
    assert choose_decomp((64, 128), mesh2, batch=8) == "slab"


def test_choose_decomp_infeasible_raises():
    import jax
    from repro.core.fft.multidim import choose_decomp

    if len(jax.devices()) < 2:
        pytest.skip("needs >= 2 host devices")
    mesh = make_mesh((2,), ("fft",))
    with pytest.raises(ValueError, match="no feasible decomposition"):
        choose_decomp((3, 5), mesh)   # not powers of two


def test_collective_volume_nd_model():
    from repro.core.fft.multidim import collective_volume_nd

    rr, cc, b, d = 128, 256, 8, 4
    grid = rr * cc
    slab = collective_volume_nd((rr, cc), b, d)
    assert slab["all_to_all_count"] == 1 and slab["all_gather_count"] == 0
    assert slab["hlo_bytes"] == b * grid * 8 / d
    assert slab["all_to_all_wire"] == b * grid * 8 / d * (d - 1) / d
    ft = collective_volume_nd((rr, cc), b, d, ft=True, groups=4)
    assert ft["abft_overhead"] == pytest.approx(2 * 4 / b)
    # verdict psum: 3G+1 scalars + the 5G-real replicated-stats broadcast
    assert ft["hlo_bytes"] == pytest.approx(
        (b + 8) * grid * 8 / d + 2 * (3 * 4 + 1 + 5 * 4) * 4)
    # pencil: 2 a2a on a 2-D mesh, batch replicated over the data axis
    pen = collective_volume_nd((rr, cc), b, 2, decomp="pencil",
                               data_shards=2, natural_order=False)
    assert pen["all_to_all_count"] == 2 and pen["all_gather_count"] == 0
    assert pen["hlo_bytes"] == 2 * b * grid * 8 / 4
    nat = collective_volume_nd((rr, cc), b, 2, decomp="pencil",
                               data_shards=2)
    assert nat["all_gather_count"] == 2
    assert nat["hlo_bytes"] == pen["hlo_bytes"] + b * grid * 8 * 1.5
    with pytest.raises(ValueError, match="slab"):
        collective_volume_nd((rr, cc), b, d, decomp="pencil", ft=True)


def test_sharding_spec_helpers():
    from jax.sharding import PartitionSpec as P

    from repro.parallel.fft_sharding import pencil_nd_specs, slab_specs

    assert slab_specs(2, data_axis="data") == (P("data", "fft", None),
                                               P("data", None, "fft"))
    assert slab_specs(3) == (P(None, "fft", None, None),
                             P(None, None, None, "fft"))
    inp, out = pencil_nd_specs(2)
    assert inp == P(None, None, "data", None, "fft")
    assert out == P(None, "data", None, "fft", None)
    with pytest.raises(ValueError):
        slab_specs(4)


# ---------------------------------------------------------------------------
# in-process: local path (mesh=None) vs numpy
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("shape", [(64, 128), (32, 256), (256, 32)])
def test_local_fft2_matches_numpy(shape, crand, assert_spectrum_close):
    from repro.core.fft.multidim import distributed_fft2, distributed_ifft2

    x = crand(2 * shape[0], shape[1]).reshape((2,) + shape)
    assert_spectrum_close(distributed_fft2(x), np.fft.fft2(x))
    assert_spectrum_close(distributed_ifft2(distributed_fft2(x)), x)


@pytest.mark.parametrize("shape", [(12, 30), (15, 64), (64, 21)])
def test_local_fft2_odd_sizes(shape, rng, assert_spectrum_close):
    """Odd / non-power-of-two axes run the direct-DFT fallback on the
    local path (the distributed decompositions stay power-of-two)."""
    from repro.core.fft.multidim import distributed_fft2, distributed_ifft2

    x = (rng.standard_normal((2,) + shape)
         + 1j * rng.standard_normal((2,) + shape)).astype(np.complex64)
    assert_spectrum_close(distributed_fft2(x), np.fft.fft2(x))
    assert_spectrum_close(distributed_ifft2(distributed_fft2(x)), x)


@pytest.mark.parametrize("dtype", [np.complex64, np.complex128])
def test_local_fftn3_and_roundtrip(dtype, crand, assert_spectrum_close):
    from repro.core.fft.multidim import distributed_fftn, distributed_ifftn

    x = crand(2 * 8 * 16, 32, dtype=dtype).reshape(2, 8, 16, 32)
    want = np.fft.fftn(x, axes=(-3, -2, -1))
    assert_spectrum_close(distributed_fftn(x, ndim=3), want, factor=2)
    assert_spectrum_close(distributed_ifftn(jnp.asarray(want), ndim=3), x,
                          factor=2)


def test_fftn_validation(crand):
    from repro.core.fft.multidim import distributed_fftn, ft_distributed_fft2

    x = crand(2, 64).reshape(2, 8, 8)
    with pytest.raises(ValueError, match="ndim"):
        distributed_fftn(x, ndim=4)
    with pytest.raises(ValueError, match="rank"):
        distributed_fftn(x[0, 0], ndim=2)
    with pytest.raises(ValueError, match="decomp"):
        distributed_fftn(x, decomp="cube")
    with pytest.raises(ValueError, match="mesh"):
        ft_distributed_fft2(x)


def test_ops_and_extensions_thread_kwargs(crand, assert_spectrum_close):
    """kernels.ops.fft2 / core.fft.extensions.fft2 accept interpret / mesh
    / natural_order and agree with numpy on the local path (regression:
    the old extensions.fft2 signature rejected every kwarg outright, so
    the 2-D transform could never reach the distributed or kernel paths)."""
    from repro.core.fft.extensions import fft2, ifft2
    from repro.kernels import ops

    x = crand(2 * 32, 64).reshape(2, 32, 64)
    want = np.fft.fft2(x)
    assert_spectrum_close(ops.fft2(x), want)
    assert_spectrum_close(fft2(x, mesh=None), want)
    assert_spectrum_close(ifft2(fft2(x)), x)
    # interpret=True routes the local path through the Pallas block kernel
    assert_spectrum_close(ops.fft2(x, interpret=True), want)
    assert_spectrum_close(ops.ifft2(jnp.asarray(want), interpret=True), x)


def test_fft_convolve2_local_matches_reference(rng):
    from repro.core.fft.multidim import fft_convolve2

    a = rng.standard_normal((2, 20, 24)).astype(np.float32)
    v = rng.standard_normal((5, 7)).astype(np.float32)
    rr, cc = 24, 30
    full = np.real(np.fft.ifft2(np.fft.fft2(a, s=(rr, cc)) *
                                np.fft.fft2(v, s=(rr, cc))))
    for mode, want in (
            ("full", full),
            ("same", full[:, 2:22, 3:27]),
            ("valid", full[:, 4:20, 6:24])):
        got = np.asarray(fft_convolve2(a, v, mode=mode))
        assert got.dtype == np.float32
        assert got.shape == want.shape, (mode, got.shape)
        np.testing.assert_allclose(got, want,
                                   atol=2e-4 * np.abs(want).max())


# ---------------------------------------------------------------------------
# multi-device scenario catalogue (in-process on >= 4 devices — the CI
# mesh-8dev lane — and via subprocess in the slow lane)
# ---------------------------------------------------------------------------

_EQUIV_CODE = """
import numpy as np, jax, jax.numpy as jnp
from repro.launch.mesh import make_mesh
from repro.core.fft import multidim as md
from repro.parallel.fft_sharding import shard_grid

mesh1 = make_mesh((4,), ("fft",))
mesh2 = make_mesh((2, 2), ("data", "fft"))
rng = np.random.default_rng(5)

def rel(a, b):
    return np.abs(np.asarray(a) - b).max() / (np.abs(b).max() + 1e-30)

for shape, dtype, tol in [((64, 128), np.complex64, 4e-5),
                          ((256, 32), np.complex64, 4e-5),
                          ((32, 64), np.complex128, 1e-11)]:
    x = (rng.standard_normal((3,) + shape) +
         1j * rng.standard_normal((3,) + shape)).astype(dtype)
    ref = np.asarray(jnp.fft.fft2(x))
    for mesh in (mesh1, mesh2):
        for decomp in ("slab", "pencil"):
            y = md.distributed_fft2(x, mesh, decomp=decomp)
            assert rel(y, ref) < tol, (shape, dtype, decomp, rel(y, ref))
            back = md.distributed_ifft2(y, mesh, decomp=decomp)
            assert rel(back, x) < tol, (shape, dtype, decomp, "roundtrip")
        # pre-sharded slab input dispatches identically
        y = md.distributed_fft2(shard_grid(x, mesh, 2), mesh, decomp="slab")
        assert rel(y, ref) < tol

# transposed digit order: the pencil forward output is the natural
# spectrum under the per-axis (k1, k2) digit swap; the transposed-in
# inverse consumes it with zero all-gathers
x = (rng.standard_normal((2, 64, 128)) +
     1j * rng.standard_normal((2, 64, 128))).astype(np.complex64)
ref = np.asarray(jnp.fft.fft2(x))
from repro.core.fft.distributed import make_dist_plan
pc = make_dist_plan(128, 2)
pr = make_dist_plan(64, 2)
yt = np.asarray(md.distributed_fft2(x, mesh2, decomp="pencil",
                                    natural_order=False))
cube = yt.reshape(2, pr.n1, pr.n2, pc.n1, pc.n2)
nat = cube.transpose(0, 2, 1, 4, 3).reshape(2, 64, 128)
assert rel(nat, ref) < 4e-5
back = md.distributed_ifft2(jnp.asarray(yt), mesh2, decomp="pencil",
                            natural_order=False)
assert rel(back, x) < 4e-5

# 3-D: slab (1 a2a) on the 1-D mesh, pencil (2 a2a) on the 2-D mesh
x3 = (rng.standard_normal((2, 8, 32, 64)) +
      1j * rng.standard_normal((2, 8, 32, 64))).astype(np.complex64)
ref3 = np.asarray(jnp.fft.fftn(x3, axes=(-3, -2, -1)))
y3 = md.distributed_fftn(x3, mesh1, ndim=3, decomp="slab")
assert rel(y3, ref3) < 2e-4, rel(y3, ref3)
assert rel(md.distributed_ifftn(y3, mesh1, ndim=3, decomp="slab"), x3) < 2e-4
y3 = md.distributed_fftn(x3, mesh2, ndim=3, decomp="pencil")
assert rel(y3, ref3) < 2e-4, rel(y3, ref3)

# fused 2-D convolution on both meshes vs the numpy spectral reference
a = rng.standard_normal((4, 20, 24)).astype(np.float32)
v = rng.standard_normal((5, 7)).astype(np.float32)
full = np.real(np.fft.ifft2(np.fft.fft2(a, s=(24, 30)) *
                            np.fft.fft2(v, s=(24, 30))))
for mesh in (mesh1, mesh2):
    got = np.asarray(md.fft_convolve2(a, v, mesh, mode="full"))
    assert got.shape == (4, 24, 30)
    assert np.abs(got - full).max() < 2e-4 * np.abs(full).max()
print('OK')
"""

_FT_CODE = """
import numpy as np, jax, jax.numpy as jnp
from repro.launch.mesh import make_mesh
from repro.core.fft import multidim as md

dtype = np.{dtype}
threshold = {threshold}
tol = {tol}
mesh = make_mesh({mesh_shape}, {mesh_axes})
rng = np.random.default_rng(9)
b, rr, cc, g = 8, 32, 64, 4
x = (rng.standard_normal((b, rr, cc)) +
     1j * rng.standard_normal((b, rr, cc))).astype(dtype)
ref = np.asarray(jnp.fft.fft2(x))
mag = 60.0 if dtype == np.complex64 else 1e-6
ft = jnp.float64 if dtype == np.complex128 else jnp.float32

def run(inj, **kw):
    return md.ft_distributed_fft2(x, mesh, threshold=threshold, groups=g,
                                  inject=None if inj is None
                                  else jnp.asarray(inj, ft), **kw)

def err(res):
    return np.abs(np.asarray(res.y) - ref).max() / np.abs(ref).max()

# clean: no verdicts, exact output, quiet left checksums
clean = run(None)
assert not np.asarray(clean.flagged).any(), np.asarray(clean.group_score)
assert float(jnp.max(clean.shard_delta)) < max(1e-4, 10 * threshold)
assert err(clean) < tol

# k = 4 SEUs in 4 distinct groups, spread over devices: ALL corrected
inj4 = [[0, 1, 3, 1, 1, mag, mag / 4],
        [1, 2, 5, 2, 1, -mag / 2, mag],
        [1, 5, 7, 3, 1, mag, -mag / 3],
        [0, 6, 2, 0, 1, mag / 2, mag / 2]]
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
dbl = run(inj2)
assert list(np.asarray(dbl.uncorrectable)) == [False, False, True, False]
assert not np.asarray(dbl.correctable).any()
assert int(dbl.corrected) == 0 and err(dbl) > 50 * tol
fixed = run(inj2, recompute_uncorrectable=True)
assert int(fixed.recomputed) == 1
assert err(fixed) < tol, err(fixed)

# checksum-grid hits: classified, data untouched
for sig, tag in ((b + 1, "cs2"), (b + g + 2, "cs3")):
    rc = run([[1, sig, 4, 2, 1, mag, -mag]])
    fl = np.asarray(rc.checksum_fault)
    assert fl.any() and np.asarray(rc.flagged)[np.argmax(fl)], tag
    assert not np.asarray(rc.correctable).any(), tag
    assert err(rc) < tol, (tag, err(rc))
print('OK')
"""

# the batch never all-gathers on a 2-D mesh (slab ft shards it over data),
# and the slab forward is exactly one all-to-all with zero gathers
_HLO_CODE = """
import numpy as np, jax, jax.numpy as jnp
from repro.launch.mesh import make_mesh
from repro.core.fft import multidim as md
from repro.launch.dryrun import collective_bytes

mesh2 = make_mesh((2, 2), ("data", "fft"))
b, rr, cc, g = 8, 128, 256, 4
x = jnp.ones((b, rr, cc), jnp.complex64)
fn = md._ft_slab_fft2_fn(mesh2, "fft", 1e-4, True, g, "data")
meas = collective_bytes(fn.lower(x, jnp.zeros((1, 7), jnp.float32))
                        .compile().as_text())
assert meas["count"]["all-gather"] == 0, meas["count"]
assert meas["count"]["all-to-all"] == 1, meas["count"]
mdl = md.collective_volume_nd((rr, cc), b, 2, ft=True, groups=g,
                              data_shards=2)
assert abs(meas["total_bytes"] / mdl["hlo_bytes"] - 1) < 1e-3, (
    meas["total_bytes"], mdl["hlo_bytes"])
fn = md._slab_fftn_fn(mesh2, "fft", 2, False, "data")
meas = collective_bytes(fn.lower(x).compile().as_text())
assert meas["count"]["all-to-all"] == 1, meas["count"]
assert meas["count"]["all-gather"] == 0, meas["count"]
print('OK')
"""


def _ft_params(mesh_shape, mesh_axes):
    return [
        dict(dtype="complex64", threshold=1e-4, tol=4e-5,
             mesh_shape=mesh_shape, mesh_axes=mesh_axes),
        dict(dtype="complex128", threshold=1e-10, tol=1e-11,
             mesh_shape=mesh_shape, mesh_axes=mesh_axes),
    ]


_MESHES = {"1d": ("(4,)", '("fft",)'), "2d": ("(2, 2)", '("data", "fft")')}


def _needs4():
    import jax

    if len(jax.devices()) < 4:
        pytest.skip("needs >= 4 host devices (the CI mesh-8dev lane sets "
                    "XLA_FLAGS=--xla_force_host_platform_device_count=8)")


def test_multidim_equivalence_inprocess():
    """Slab + pencil vs jnp.fft.fft2/fftn on 1-D and 2-D meshes, fp32 and
    fp64, rectangular shapes, transposed order, conv2 (CI mesh-8dev lane)."""
    _needs4()
    exec(_EQUIV_CODE, {"__name__": "__equiv__"})


@pytest.mark.parametrize("meshname", sorted(_MESHES))
@pytest.mark.parametrize("dtype", ["complex64", "complex128"])
def test_ft_fault_matrix_inprocess(meshname, dtype):
    _needs4()
    shape, axes = _MESHES[meshname]
    p = [c for c in _ft_params(shape, axes) if c["dtype"] == dtype][0]
    exec(_FT_CODE.format(**p), {"__name__": "__ft__"})


def test_no_batch_allgather_inprocess():
    _needs4()
    exec(_HLO_CODE, {"__name__": "__hlo__"})


@pytest.mark.slow
def test_multidim_equivalence_subprocess():
    assert "OK" in run_py(_EQUIV_CODE, devices=4)


@pytest.mark.slow
@pytest.mark.parametrize("meshname", sorted(_MESHES))
def test_ft_fault_matrix_subprocess(meshname):
    shape, axes = _MESHES[meshname]
    for p in _ft_params(shape, axes):
        assert "OK" in run_py(_FT_CODE.format(**p), devices=4)


@pytest.mark.slow
def test_no_batch_allgather_subprocess():
    assert "OK" in run_py(_HLO_CODE, devices=4)


# ---------------------------------------------------------------------------
# serve threading
# ---------------------------------------------------------------------------


@pytest.mark.slow
def test_serve_fft2_threads_decomp_and_ft():
    out = run_py("""
import numpy as np
from repro.launch.serve import serve_fft
rng = np.random.default_rng(0)
x = (rng.standard_normal((4, 64, 128)) +
     1j * rng.standard_normal((4, 64, 128))).astype(np.complex64)
ref = np.fft.fft2(x)
for decomp in ("slab", "pencil", "auto"):
    y, info = serve_fft(x, shards=2, data=2, dims=2, decomp=decomp)
    assert info["dims"] == 2 and info["shards"] == 2 and info["data"] == 2
    assert np.abs(np.asarray(y) - ref).max() / np.abs(ref).max() < 4e-5, decomp
y, info = serve_fft(x, shards=4, dims=2, ft=True, groups=2)
assert info["ft"] and info["groups"] == 2 and info["flagged"] == 0
assert np.abs(np.asarray(y) - ref).max() / np.abs(ref).max() < 4e-5
# ft rides the slab transpose: an explicit pencil ask must fail loudly,
# not silently serve slab results
try:
    serve_fft(x, shards=4, dims=2, ft=True, decomp="pencil")
except ValueError as e:
    assert "slab" in str(e)
else:
    raise AssertionError("ft + decomp='pencil' must raise")
a = rng.standard_normal((4, 20, 24)).astype(np.float32)
v = rng.standard_normal((5, 7)).astype(np.float32)
y, info = serve_fft(a, shards=4, dims=2, op="convolve", kernel=v,
                    mode="full")
assert info["collectives"] == "2 a2a" and y.shape == (4, 24, 30)
y, info = serve_fft(x, shards=4, dims=2, op="spectrum")
assert np.abs(np.asarray(y) -
              np.abs(ref) ** 2 / (64 * 128)).max() < 1e-2
print('OK')
""", devices=4)
    assert "OK" in out

"""Mesh-sharded distributed FFT: host-mesh equivalence, sharded ABFT,
plan/volume invariants. Multi-device cases run in a subprocess (the XLA
host-device-count flag must be set before jax initializes).
"""
import numpy as np
import pytest

from conftest import run_py

# ---------------------------------------------------------------------------
# in-process: plan + communication model + single-device fallback
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("ln", [4, 10, 14, 17, 20, 23])
@pytest.mark.parametrize("shards", [1, 2, 4])
def test_dist_plan_divisible(ln, shards):
    from repro.core.fft.distributed import make_dist_plan

    n = 1 << ln
    if n < shards * shards:
        pytest.skip("pencil needs N >= shards^2")
    p = make_dist_plan(n, shards)
    assert p.n1 * p.n2 == n
    assert p.n1 % shards == 0 and p.n2 % shards == 0
    assert p.local_in == (p.n1, p.n2 // shards)
    assert p.local_out == (p.n1 // shards, p.n2)


def test_dist_plan_rejects_bad_sizes():
    from repro.core.fft.distributed import make_dist_plan

    with pytest.raises(ValueError):
        make_dist_plan(100, 2)  # not a power of two
    with pytest.raises(ValueError):
        make_dist_plan(1 << 14, 3)  # non-power-of-two shards
    with pytest.raises(ValueError):
        make_dist_plan(8, 4)  # N < shards^2


def test_fft_mesh_never_shrinks():
    """An explicit shard request is built exactly or refused: a chip path
    that asks for 4 devices must not silently run on fewer."""
    import jax
    from jax.sharding import AxisType

    from repro.launch.mesh import make_fft_mesh

    n = len(jax.devices())
    with pytest.raises(ValueError, match="devices"):
        make_fft_mesh(2 * n if n > 1 else 2)
    with pytest.raises(ValueError, match="power-of-two"):
        make_fft_mesh(3)
    mesh = make_fft_mesh()
    assert mesh.shape["fft"] == 1 << (n.bit_length() - 1)
    assert set(mesh.axis_types) == {AxisType.Auto}


def test_twiddle_block_accuracy_at_2_26():
    """The in-graph pencil twiddle stays within 5e-7 of the float64 table
    at N = 2^26 on 4 shards (512 x 131072), including blocks where the
    angle index passes float32's 2^24 exact range and the angle nears pi."""
    import jax.numpy as jnp

    from repro.core.fft.distributed import _twiddle_block, make_dist_plan

    n = 1 << 26
    p = make_dist_plan(n, 4)
    blocks = [((0, 8), (0, 256)), ((p.n1 - 8, 8), (p.n2 - 256, 256)),
              ((p.n1 // 2 - 4, 8), (p.n2 // 2 - 128, 256)),
              ((300, 8), (77777, 256))]
    for inverse in (False, True):
        for rows, cols in blocks:
            t = np.asarray(_twiddle_block(p.n1, p.n2, rows, cols,
                                          jnp.complex64, inverse))
            k1 = np.arange(rows[0], rows[0] + rows[1])[:, None]
            j = np.arange(cols[0], cols[0] + cols[1])[None, :]
            sign = 1.0 if inverse else -1.0
            ref = np.exp(sign * 2j * np.pi * ((k1 * j) % n) / n)
            assert np.abs(t - ref).max() <= 5e-7, (inverse, rows, cols)


def test_collective_volume_model():
    """One all-to-all; ABFT adds 2/B volume + scalars; transposed order
    skips the natural-order gather entirely."""
    from repro.core.fft.distributed import collective_volume

    n, b, d = 1 << 17, 8, 4
    plain = collective_volume(n, b, d)
    ft = collective_volume(n, b, d, ft=True)
    transposed = collective_volume(n, b, d, natural_order=False)
    assert plain["passes"] == 2
    assert plain["all_to_all_wire"] == b * n * 8 / d * (d - 1) / d
    assert ft["abft_overhead"] == pytest.approx(2 / b)
    assert ft["all_to_all_wire"] == pytest.approx(
        plain["all_to_all_wire"] * (b + 2) / b)
    assert transposed["gather_wire"] == 0.0
    assert transposed["total_wire"] < plain["total_wire"]


def test_single_device_fallback_matches_local(crand, assert_spectrum_close):
    """mesh=None (and ops.fft without a mesh) is exactly the local path."""
    from repro.core.fft.distributed import distributed_fft
    from repro.kernels import ops

    x = crand(2, 1 << 10)
    assert_spectrum_close(distributed_fft(x), np.fft.fft(x))
    assert_spectrum_close(ops.fft(x), np.fft.fft(x))


# ---------------------------------------------------------------------------
# host-mesh equivalence (subprocess, 4 devices)
# ---------------------------------------------------------------------------


@pytest.mark.slow
@pytest.mark.parametrize("shards", [1, 2, 4])
def test_distributed_fft_matches_numpy(shards):
    """1/2/4-way shardings vs jnp.fft.fft over N = 2^14 .. 2^17, plus the
    sharded ifft roundtrip and the transposed-order digit permutation."""
    out = run_py(f"""
import numpy as np, jax, jax.numpy as jnp
from repro.launch.mesh import make_mesh
from repro.core.fft.distributed import (distributed_fft, distributed_ifft,
                                        make_dist_plan)
shards = {shards}
mesh = make_mesh((shards,), ("fft",)) if shards > 1 else None
rng = np.random.default_rng(shards)
for ln in (14, 15, 16, 17):
    n = 1 << ln
    x = (rng.standard_normal((2, n)) + 1j * rng.standard_normal((2, n))
         ).astype(np.complex64)
    ref = np.asarray(jnp.fft.fft(x))
    y = np.asarray(distributed_fft(x, mesh))
    err = np.abs(y - ref).max() / np.abs(ref).max()
    assert err < 4e-5, (ln, err)
    back = np.asarray(distributed_ifft(jnp.asarray(y), mesh))
    rerr = np.abs(back - x).max() / np.abs(x).max()
    assert rerr < 4e-5, (ln, rerr)
    if mesh is not None:
        # transposed order is the natural order under the (n1, n2) digit swap
        p = make_dist_plan(n, shards)
        yt = np.asarray(distributed_fft(x, mesh, natural_order=False))
        perm = yt.reshape(2, p.n1, p.n2).transpose(0, 2, 1).reshape(2, n)
        assert np.abs(perm - ref).max() / np.abs(ref).max() < 4e-5
print('OK')
""", devices=4)
    assert "OK" in out


@pytest.mark.slow
def test_ops_fft_auto_dispatches_on_sharded_input():
    """kernels.ops.fft routes to the distributed path when the operand is
    committed to an fft-axis mesh (and when a mesh is passed explicitly)."""
    out = run_py("""
import numpy as np, jax, jax.numpy as jnp
from repro.core.fft import FFTSpec, plan
from repro.kernels import ops
from repro.launch.mesh import make_fft_mesh
from repro.parallel import shard_signals, infer_fft_mesh
mesh = make_fft_mesh(4)
rng = np.random.default_rng(0)
x = (rng.standard_normal((2, 1 << 14)) +
     1j * rng.standard_normal((2, 1 << 14))).astype(np.complex64)
ref = np.fft.fft(x)
xs = shard_signals(x, mesh)
assert infer_fft_mesh(xs) is mesh
y1 = np.asarray(ops.fft(xs))             # inferred from committed sharding
p = plan(FFTSpec(shape=x.shape, mesh=mesh))   # explicit plan
y2 = np.asarray(p.fft(x))
for y in (y1, y2):
    assert np.abs(y - ref).max() / np.abs(ref).max() < 4e-5
back = np.asarray(p.ifft(jnp.asarray(y2)))
assert np.abs(back - x).max() / np.abs(x).max() < 4e-5
print('OK')
""", devices=4)
    assert "OK" in out


# ---------------------------------------------------------------------------
# sharded two-side ABFT (subprocess, 4 devices)
# ---------------------------------------------------------------------------


@pytest.mark.slow
def test_sharded_abft_detects_and_corrects_nonlocal_fault():
    """An SEU injected on device 2 mid-pipeline (after pass 1) is detected,
    located to the right signal, and corrected — with the verdict reduced on
    a *different* shard (device 0 reads it), proving the psum'd right-side
    checksums work across the mesh. Clean runs never false-alarm."""
    out = run_py("""
import numpy as np, jax, jax.numpy as jnp
from repro.launch.mesh import make_mesh
from repro.core.fft.distributed import ft_distributed_fft
mesh = make_mesh((4,), ("fft",))
rng = np.random.default_rng(7)
b, n = 8, 1 << 14
x = (rng.standard_normal((b, n)) + 1j * rng.standard_normal((b, n))
     ).astype(np.complex64)
ref = np.fft.fft(x)

clean = ft_distributed_fft(x, mesh)
assert not bool(clean.flagged.any()), np.asarray(clean.group_score)
assert float(jnp.max(clean.shard_delta)) < 1e-4
assert np.abs(np.asarray(clean.y) - ref).max() / np.abs(ref).max() < 4e-5

# device 2 holds the fault; the verdict consumed from shard 0's copy
inj = jnp.asarray([2, 5, 7, 3, 1, 60.0, -25.0], jnp.float32)
res = ft_distributed_fft(x, mesh, inject=inj)
assert bool(res.flagged.all()) and bool(res.correctable.all())
assert int(res.location[0]) == 5
assert int(res.corrected) == 1
err = np.abs(np.asarray(res.y) - ref).max() / np.abs(ref).max()
assert err < 1e-4, err

# without correction the propagated error persists in the output
bad = ft_distributed_fft(x, mesh, inject=inj, correct=False)
res_err = np.abs(np.asarray(bad.y) - ref).max() / np.abs(ref).max()
assert res_err > 1e-2, res_err
print('OK')
""", devices=4)
    assert "OK" in out


@pytest.mark.slow
def test_sharded_abft_fp64_telemetry():
    """complex128 inputs keep residuals/scores/injection in float64
    (regression: float32 scaling constants and a float32 inject path
    downcast the fp64 telemetry), so thresholds far below float32
    resolution work: a clean run scores < 1e-12 while a 1e-6-magnitude
    SEU — invisible at float32 — is detected, located, and corrected."""
    out = run_py("""
import numpy as np, jax, jax.numpy as jnp
from repro.launch.mesh import make_mesh
from repro.core.fft.distributed import ft_distributed_fft
mesh = make_mesh((4,), ("fft",))
rng = np.random.default_rng(11)
b, n = 8, 1 << 14
x = (rng.standard_normal((b, n)) + 1j * rng.standard_normal((b, n))
     ).astype(np.complex128)
ref = np.fft.fft(x)

clean = ft_distributed_fft(x, mesh, threshold=1e-10)
assert clean.group_score.dtype == jnp.float64, clean.group_score.dtype
assert clean.shard_delta.dtype == jnp.float64
assert float(jnp.max(clean.group_score)) < 1e-12
assert float(jnp.max(clean.shard_delta)) < 1e-12
assert not bool(clean.flagged.any())
assert np.abs(np.asarray(clean.y) - ref).max() / np.abs(ref).max() < 1e-11

# an SEU far below float32 visibility, caught by the fp64 pipeline
inj = jnp.asarray([1, 3, 2, 5, 1, 1e-6, -1e-6], jnp.float64)
res = ft_distributed_fft(x, mesh, threshold=1e-10, inject=inj)
assert bool(res.flagged.all()), np.asarray(res.group_score)
assert int(res.location[0]) == 3
assert int(res.corrected) == 1
err = np.abs(np.asarray(res.y) - ref).max() / np.abs(ref).max()
assert err < 1e-11, err
print('OK')
""", devices=4)
    assert "OK" in out


# ---------------------------------------------------------------------------
# the plain pencil's stage DFTs: Pallas kernel passes on (re, im) planes
# (in-process; tests/conftest.py asks for 8 host devices)
# ---------------------------------------------------------------------------

DIRECTIONS = pytest.mark.parametrize("inverse", [False, True],
                                     ids=["fwd", "inv"])


def _rel_err(y, ref):
    return float(np.abs(y - ref).max() / np.abs(ref).max())


def _dft64(x, inverse):
    x = x.astype(np.complex128)
    return np.fft.ifft(x, axis=-1) if inverse else np.fft.fft(x, axis=-1)


@pytest.mark.parametrize("b", [8, 3])
@DIRECTIONS
def test_local_fft_planes_matches_float64(inverse, b, crand):
    """The plane engine at a two-factor length, 2^14 = 128 x 128: two
    kernel passes with the swaps and stage twiddle between them, and the
    inverse scaled once by 1/n over the passes. At B = 3 the first pass's
    384 rows pad to a whole number of tiles."""
    import functools

    import jax

    from repro.core.fft.distributed import _local_fft_planes
    from repro.core.fft.plan import make_plan

    n = 1 << 14
    assert make_plan(n).kernel_factors == (128, 128)
    x = crand(b, n)
    fn = jax.jit(functools.partial(_local_fft_planes, inverse=inverse,
                                   interpret=None))
    yr, yi = fn(x.real, x.imag)
    y = np.asarray(yr) + 1j * np.asarray(yi)
    assert _rel_err(y, _dft64(x, inverse)) < 2e-6


@pytest.mark.parametrize("chunks", [1, 2])
@pytest.mark.parametrize("natural", [True, False],
                         ids=["natural", "transposed"])
@DIRECTIONS
def test_pencil_kernel_passes_match_float64(inverse, natural, chunks, crand):
    """The four-device pencil at N = 2^15 = 256 x 128, B = 8, both
    directions and orders, in one transaction and in two: transposed order
    is ``y[k1*N2 + k2] = X[k1 + N1*k2]`` of the forward or inverse DFT."""
    import jax

    from repro.core.fft.distributed import _dist_fft_fn, make_dist_plan
    from repro.launch.mesh import make_fft_mesh

    if len(jax.devices()) < 4:
        pytest.skip("needs >= 4 host devices (tests/conftest.py asks "
                    "for 8 unless XLA_FLAGS names a count)")
    b, n = 8, 1 << 15
    p = make_dist_plan(n, 4)
    assert (p.n1, p.n2) == (256, 128)
    x = crand(b, n)
    fn = _dist_fft_fn(make_fft_mesh(4), "fft", inverse, natural, None, chunks)
    y = np.asarray(fn(x))
    if not natural:
        y = y.reshape(b, p.n1, p.n2).transpose(0, 2, 1).reshape(b, n)
    assert _rel_err(y, _dft64(x, inverse)) < 2e-6

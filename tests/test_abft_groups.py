"""Grouped multi-transaction sharded ABFT: the fault-tolerance contract is
one SEU per checksum GROUP per pass — k simultaneous SEUs in k distinct
groups are all corrected, two SEUs in one group decode as uncorrectable
(policy recompute path), and checksum-row hits are distinguished from data
corruption by the two-side location encoding.

Layout/validation/model tests run in-process everywhere. The multi-device
matrix runs in-process when the host platform carries >= 4 devices (the
test session asks for 8 in conftest.py) and is additionally covered by consolidated subprocess tests in the slow lane,
so local single-device tier-1 runs still exercise every scenario.
"""
import numpy as np
import pytest

from conftest import run_py

# ---------------------------------------------------------------------------
# group resolution + layout + communication model (in-process, fast)
# ---------------------------------------------------------------------------


def test_resolve_groups_auto_and_validation():
    from repro.core.fft.distributed import resolve_abft_groups

    # auto: one group per data shard when the batch divides, else 1
    assert resolve_abft_groups(8) == 1
    assert resolve_abft_groups(8, data_shards=4) == 4
    assert resolve_abft_groups(6, data_shards=4) == 1  # 4 does not divide 6
    # explicit group count / group size
    assert resolve_abft_groups(8, groups=4) == 4
    assert resolve_abft_groups(8, group_size=2) == 4
    assert resolve_abft_groups(8, groups=4, group_size=2) == 4
    with pytest.raises(ValueError):
        resolve_abft_groups(8, groups=3)            # must divide batch
    with pytest.raises(ValueError):
        resolve_abft_groups(8, group_size=3)
    with pytest.raises(ValueError):
        resolve_abft_groups(8, groups=4, group_size=4)  # inconsistent pair
    with pytest.raises(ValueError):
        # each data shard must own whole groups
        resolve_abft_groups(8, groups=2, data_shards=4)
    # a batch that cannot shard over data at all waives the data-axis
    # constraint (the pipeline replicates it) — the SAME resolution the
    # pipeline uses, so serve-side telemetry sizing can never drift
    assert resolve_abft_groups(6, groups=3, data_shards=4) == 3


def test_recompute_uncorrectable_rejects_jit():
    """The recompute fallback is host-side by design: under jit it must
    fail with an actionable error, not a TracerArrayConversionError."""
    import jax
    import jax.numpy as jnp

    from repro.core.fft.distributed import ft_distributed_fft
    from repro.launch.mesh import make_mesh

    mesh = make_mesh((1,), ("fft",))
    x = jnp.ones((8, 256), jnp.complex64)
    with pytest.raises(ValueError, match="host-side fallback"):
        jax.jit(lambda v: ft_distributed_fft(
            v, mesh, groups=4, recompute_uncorrectable=True).y)(x)
    # without the flag the pipeline is jit-composable
    y = jax.jit(lambda v: ft_distributed_fft(v, mesh, groups=4).y)(x)
    assert y.shape == x.shape


def test_abft_group_layout_without_mesh():
    from repro.parallel import abft_group_layout, abft_group_spec

    assert abft_group_layout(None, 8, groups=4) == (4, 2)
    assert abft_group_layout(None, 8) == (1, 8)
    assert abft_group_spec(None) == __import__(
        "jax").sharding.PartitionSpec(None)


def test_collective_volume_grouped():
    """Checksum rows scale as 2G/B; the verdict traffic is 8 scalars per
    locally-owned group (3 verdict-psum + 5 replicated-stats broadcast)
    plus one shared energy scalar."""
    from repro.core.fft.distributed import collective_volume

    n, b, d = 1 << 17, 8, 4
    plain = collective_volume(n, b, d)
    g1 = collective_volume(n, b, d, ft=True)
    g4 = collective_volume(n, b, d, ft=True, groups=4)
    assert g1["abft_overhead"] == pytest.approx(2 / b)
    assert g4["abft_overhead"] == pytest.approx(8 / b)
    assert g4["all_to_all_wire"] == pytest.approx(
        plain["all_to_all_wire"] * (b + 8) / b)
    # psum payload at ring factor 2: (8G + 1) f32 scalars for G = 4 and
    # for the ungrouped G = 1 alike (the 5G stats-broadcast term is the
    # masked all-reduce XLA emits for the replicated telemetry extraction)
    # — per-kind HLO diffs in repro.analysis pin the layout down
    assert g4["psum_wire"] - g1["psum_wire"] == pytest.approx(
        2.0 * (33 * 4 - 9 * 4) * (d - 1) / d)
    # data sharding divides rows, gather, and per-device verdict scalars
    half = collective_volume(n, b, d, ft=True, groups=4, data_shards=2)
    assert half["all_to_all_wire"] == pytest.approx(
        g4["all_to_all_wire"] / 2)
    assert half["gather_wire"] == pytest.approx(g4["gather_wire"] / 2)
    with pytest.raises(ValueError):
        collective_volume(n, b, d, ft=True, groups=2, data_shards=4)


# ---------------------------------------------------------------------------
# multi-device fault matrix (in-process on >= 4 host devices, and via
# subprocess in the slow lane below)
# ---------------------------------------------------------------------------

# One scenario catalogue drives the in-process and subprocess variants, so
# the two lanes cannot drift apart. b=8 signals, G=4 groups of 2.
_MATRIX_CODE = """
import numpy as np, jax, jax.numpy as jnp
from repro.launch.mesh import make_mesh
from repro.core.fft.distributed import ft_distributed_fft

dtype = np.{dtype}
threshold = {threshold}
tol = {tol}
mesh = make_mesh({mesh_shape}, {mesh_axes})
rng = np.random.default_rng(3)
b, n, g = 8, 1 << 12, 4
x = (rng.standard_normal((b, n)) + 1j * rng.standard_normal((b, n))
     ).astype(dtype)
ref = np.asarray(jnp.fft.fft(x))
mag = 60.0 if dtype == np.complex64 else 1e-6
ft = jnp.float64 if dtype == np.complex128 else jnp.float32

def run(inj, **kw):
    return ft_distributed_fft(x, mesh, threshold=threshold, groups=g,
                              inject=None if inj is None
                              else jnp.asarray(inj, ft), **kw)

def err(res):
    return np.abs(np.asarray(res.y) - ref).max() / np.abs(ref).max()

# clean pass: no verdicts, exact output
clean = run(None)
assert not np.asarray(clean.flagged).any(), np.asarray(clean.group_score)
assert err(clean) < tol

# k = 4 simultaneous SEUs in 4 distinct groups: ALL corrected in one pass
inj4 = [[0, 1, 3, 1, 1, mag, mag / 4],
        [1, 2, 5, 2, 1, -mag / 2, mag],
        [1, 5, 7, 3, 1, mag, -mag / 3],
        [0, 6, 2, 0, 1, mag / 2, mag / 2]]
res = run(inj4)
assert np.asarray(res.flagged).all()
assert np.asarray(res.correctable).all()
assert list(np.asarray(res.location)) == [1, 2, 5, 6]
assert int(res.corrected) == 4
assert err(res) < tol, err(res)

# without correction the propagated error persists (the injected epsilon
# scales with the dtype — 1e-6 for the fp64 cells — so the floor does too)
bad = run(inj4, correct=False)
assert err(bad) > 50 * tol

# 2 SEUs in ONE group (rows 4 and 5 are both group 2): detected, flagged
# uncorrectable, repaired only by the policy recompute path
inj2 = [[0, 4, 3, 1, 1, mag, mag / 4],
        [1, 5, 5, 2, 1, -mag / 2, mag]]
dbl = run(inj2)
u = np.asarray(dbl.uncorrectable)
assert list(u) == [False, False, True, False]
assert not np.asarray(dbl.correctable).any()
assert int(dbl.corrected) == 0 and err(dbl) > 50 * tol
fixed = run(inj2, recompute_uncorrectable=True)
assert int(fixed.recomputed) == 1
assert err(fixed) < tol, err(fixed)

# fault in a checksum row: flagged, classified checksum_fault (cs2 via the
# lam ~ 0 decode, cs3 via loud d3 with quiet d2), data untouched, and no
# correction is applied to the (clean) outputs
for sig, tag in ((b + 1, "cs2"), (b + g + 2, "cs3")):
    inj = [[1, sig, 4, 2, 1, mag, -mag]]
    rc = run(inj)
    fl = np.asarray(rc.checksum_fault)
    assert fl.any() and np.asarray(rc.flagged)[np.argmax(fl)], tag
    assert not np.asarray(rc.correctable).any(), tag
    assert err(rc) < tol, (tag, err(rc))
print('OK')
"""


def _matrix_params(mesh_shape, mesh_axes):
    return [
        dict(dtype="complex64", threshold=1e-4, tol=4e-5,
             mesh_shape=mesh_shape, mesh_axes=mesh_axes),
        dict(dtype="complex128", threshold=1e-10, tol=1e-11,
             mesh_shape=mesh_shape, mesh_axes=mesh_axes),
    ]


_MESHES = {"1d": ("(4,)", '("fft",)'), "2d": ("(2, 2)", '("data", "fft")')}


@pytest.mark.parametrize("meshname", sorted(_MESHES))
@pytest.mark.parametrize("dtype", ["complex64", "complex128"])
def test_group_fault_matrix_inprocess(meshname, dtype):
    """The full scenario matrix, in-process."""
    import jax

    if len(jax.devices()) < 4:
        pytest.skip("needs >= 4 host devices (tests/conftest.py asks "
                    "for 8 unless XLA_FLAGS names a count)")
    shape, axes = _MESHES[meshname]
    p = [c for c in _matrix_params(shape, axes) if c["dtype"] == dtype][0]
    namespace = {"__name__": "__matrix__"}
    exec(_MATRIX_CODE.format(**p), namespace)  # raises on any failed assert


@pytest.mark.slow
@pytest.mark.parametrize("meshname", sorted(_MESHES))
def test_group_fault_matrix_subprocess(meshname):
    """Same matrix via a forced-4-device subprocess (both dtypes)."""
    shape, axes = _MESHES[meshname]
    for p in _matrix_params(shape, axes):
        out = run_py(_MATRIX_CODE.format(**p), devices=4)
        assert "OK" in out


# ---------------------------------------------------------------------------
# regression: 2-D data x fft meshes SHARD the batch (no batch all-gather)
# ---------------------------------------------------------------------------

_HLO_CODE = """
import numpy as np, jax, jax.numpy as jnp
from repro.launch.mesh import make_mesh
from repro.core.fft import distributed as dist
from repro.analysis.hlo import parse_collectives, summarize

mesh = make_mesh((2, 2), ("data", "fft"))
b, n, g = 8, 1 << 12, 4
x = jnp.ones((b, n), jnp.complex64)
for nat in (False, True):
    fn = dist._ft_dist_fft_fn(mesh, "fft", 1e-4, True, nat, g, "data")
    hlo = fn.lower(x, jnp.zeros((1, 7), jnp.float32)).compile().as_text()
    m = summarize(parse_collectives(hlo))
    # transposed order: ZERO all-gathers. natural order: exactly one, and
    # it is the fft-axis spectrum redistribution of THIS shard's batch
    # rows (b/data * n), not a batch all-gather (b * n) — model==HLO with
    # data_shards proves the batch stayed sharded.
    assert m["count"]["all-gather"] == (1 if nat else 0), (nat, m["count"])
    mdl = dist.collective_volume(n, b, 2, ft=True, groups=g, data_shards=2,
                                 natural_order=nat)
    assert abs(m["total_bytes"] / mdl["hlo_bytes"] - 1.0) < 1e-3, (
        nat, m["total_bytes"], mdl["hlo_bytes"])
    replicated = dist.collective_volume(n, b, 2, ft=True, groups=g,
                                        natural_order=nat)
    assert mdl["hlo_bytes"] < replicated["hlo_bytes"]
print('OK')
"""


def test_no_batch_allgather_on_2d_mesh_inprocess():
    import jax

    if len(jax.devices()) < 4:
        pytest.skip("needs >= 4 host devices")
    exec(_HLO_CODE, {"__name__": "__hlo__"})


@pytest.mark.slow
def test_no_batch_allgather_on_2d_mesh_subprocess():
    assert "OK" in run_py(_HLO_CODE, devices=4)


# ---------------------------------------------------------------------------
# serve endpoint + ops auto-dispatch carry the groups knob (subprocess)
# ---------------------------------------------------------------------------


@pytest.mark.slow
def test_serve_and_ops_thread_groups():
    out = run_py("""
import numpy as np, jax, jax.numpy as jnp
from repro.core.fft import FFTSpec, FTConfig, plan
from repro.launch.serve import serve_fft
from repro.launch.mesh import make_fft_mesh
from repro.parallel import shard_signals

rng = np.random.default_rng(5)
b, n = 8, 1 << 12
x = (rng.standard_normal((b, n)) + 1j * rng.standard_normal((b, n))
     ).astype(np.complex64)
ref = np.fft.fft(x)

# serve: 2-D data x fft mesh, grouped ft telemetry
y, info = serve_fft(x, shards=2, data=2, ft=True, groups=4)
assert info["groups"] == 4 and info["group_size"] == 2, info
assert info["flagged"] == 0 and info["recomputed"] == 0, info
assert np.abs(np.asarray(y) - ref).max() / np.abs(ref).max() < 4e-5

# an ft plan threads the groups knob to the grouped sharded path and
# accepts the distributed 7-field inject layout
mesh = make_fft_mesh(4)
xs = shard_signals(x, mesh)
inj = jnp.asarray([[1, 2, 5, 2, 1, 60.0, -25.0],
                   [2, 5, 7, 3, 1, 40.0, 35.0]], jnp.float32)
p = plan(FFTSpec(shape=x.shape, mesh=mesh, ft=FTConfig(groups=4)))
assert p.groups == 4
res = p.ft_fft(xs, inject=inj)
assert res.flagged.shape == (4,)
assert list(np.asarray(res.flagged)) == [False, True, True, False]
assert int(res.location[1]) == 2 and int(res.location[2]) == 5
assert int(res.corrected) == 2
assert np.abs(np.asarray(res.y) - ref).max() / np.abs(ref).max() < 1e-4
print('OK')
""", devices=4)
    assert "OK" in out

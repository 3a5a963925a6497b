"""The cuFFT-style plan/execute API (core.fft.api): FFTSpec validation and
hashability, the LRU plan cache (same spec -> same plan object, ZERO
executor retraces), bitwise identity of the plan executors against the
legacy kwarg paths across {1-D, 2-D slab, 2-D pencil} x {plain, ft,
transposed} x {c64, c128} on 1-D and 2-D host meshes, the deprecation
shims on kernels.ops, rfft/irfft mesh routing, and FTPolicy.to_ft_config.

Multi-device cases run in-process on >= 4 forced host devices
(conftest.py asks for 8).
"""
import dataclasses

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro.core.fft import api
from repro.core.fft.api import FFTSpec, FTConfig, plan
from repro.launch.mesh import make_mesh


def _mesh1():
    return make_mesh((4,), ("fft",))


def _mesh2():
    return make_mesh((2, 2), ("data", "fft"))


def _need(n):
    if len(jax.devices()) < n:
        pytest.skip(f"needs >= {n} host devices")


# ---------------------------------------------------------------------------
# spec validation + hashability
# ---------------------------------------------------------------------------


def test_spec_is_hashable_value_object():
    s1 = FFTSpec(shape=(8, 1024), ft=FTConfig(groups=4))
    s2 = FFTSpec(shape=(8, 1024), ft=FTConfig(groups=4))
    assert s1 == s2 and hash(s1) == hash(s2)
    assert {s1: "a"}[s2] == "a"
    assert s1 != dataclasses.replace(s1, dtype="complex128")
    assert s1 != dataclasses.replace(s1, ft=FTConfig(groups=2))
    # canonicalization: dtype objects and list shapes normalize
    s3 = FFTSpec(shape=[8, 1024], dtype=jnp.complex64,
                 ft=FTConfig(groups=4))
    assert s3 == s1


def test_spec_validation_messages():
    with pytest.raises(ValueError, match="positive sizes"):
        FFTSpec(shape=())
    with pytest.raises(ValueError, match="complex"):
        FFTSpec(shape=(8, 64), dtype="float32")
    with pytest.raises(ValueError, match="rank"):
        FFTSpec(shape=(8, 64), rank=4)
    with pytest.raises(ValueError, match="fewer axes"):
        FFTSpec(shape=(64,), rank=2)
    with pytest.raises(ValueError, match="multi-dimensional knob"):
        FFTSpec(shape=(8, 64), decomp="slab")
    with pytest.raises(ValueError, match="decomp"):
        FFTSpec(shape=(8, 64, 64), rank=2, decomp="cube")
    with pytest.raises(ValueError, match="FTConfig"):
        FFTSpec(shape=(8, 64), ft={"groups": 4})
    with pytest.raises(TypeError, match="FFTSpec"):
        plan({"shape": (8, 64)})


def test_spec_bad_axis_names():
    _need(2)
    mesh = make_mesh((2,), ("model",))
    with pytest.raises(ValueError, match="'fft' .*model"):
        FFTSpec(shape=(8, 64), mesh=mesh)
    mesh_f = make_mesh((2,), ("fft",))
    with pytest.raises(ValueError, match="data_axis 'rows'"):
        FFTSpec(shape=(8, 64), mesh=mesh_f, data_axis="rows")


def test_plan_infeasible_sizes_raise_clearly():
    _need(4)
    mesh = _mesh1()
    with pytest.raises(ValueError, match="power of two"):
        plan(FFTSpec(shape=(8, 1000), mesh=mesh))
    with pytest.raises(ValueError, match="shards\\^2"):
        plan(FFTSpec(shape=(8, 8), mesh=mesh))
    with pytest.raises(ValueError, match="infeasible decomp: slab"):
        plan(FFTSpec(shape=(8, 2, 256), rank=2, mesh=mesh, decomp="slab"))
    with pytest.raises(ValueError, match="infeasible decomp: pencil"):
        plan(FFTSpec(shape=(8, 64, 8), rank=2, mesh=mesh, decomp="pencil"))
    with pytest.raises(ValueError, match="slab"):
        plan(FFTSpec(shape=(8, 64, 256), rank=2, mesh=mesh, decomp="pencil",
                     ft=FTConfig()))
    with pytest.raises(ValueError, match="groups=3"):
        plan(FFTSpec(shape=(8, 1024), mesh=mesh, ft=FTConfig(groups=3)))


# ---------------------------------------------------------------------------
# plan cache: hits, zero retrace, distinct keys
# ---------------------------------------------------------------------------


def test_plan_cache_same_spec_same_plan_zero_retrace(crand):
    _need(4)
    mesh = _mesh1()
    spec = FFTSpec(shape=(8, 4096), mesh=mesh)
    p1 = plan(spec)
    p2 = plan(dataclasses.replace(spec))
    assert p1 is p2, "equal specs must LRU-hit the same plan"
    x = jnp.asarray(crand(8, 4096))
    y1 = p1.fft(x)
    traces = p1._fwd._cache_size()      # jit cache entries after first call
    for _ in range(3):
        y2 = plan(dataclasses.replace(spec)).fft(x)
    assert p1._fwd._cache_size() == traces, "repeat dispatch retraced"
    np.testing.assert_array_equal(np.asarray(y1), np.asarray(y2))


def test_plan_cache_distinct_keys():
    _need(4)
    mesh = _mesh1()
    base = FFTSpec(shape=(8, 4096), mesh=mesh)
    others = [
        dataclasses.replace(base, dtype="complex128"),
        dataclasses.replace(base, mesh=None),
        dataclasses.replace(base, natural_order=False),
        dataclasses.replace(base, ft=FTConfig(groups=4)),
        dataclasses.replace(base, ft=FTConfig(groups=4, threshold=1e-6)),
    ]
    plans = [plan(s) for s in [base] + others]
    assert len({id(p) for p in plans}) == len(plans)
    # resolved once: the ft plan carries its group layout and model
    pf = plans[4]
    assert pf.groups == 4
    assert pf.volume["abft_overhead"] == pytest.approx(1.0)


def test_explicit_local_decomp_honored_on_sharded_mesh(rng):
    """decomp='local' must run the local transform even when a mesh is
    attached (the legacy distributed_fftn contract) — not be re-resolved
    by choose_decomp, which would reject odd grids and could silently
    return pencil digit order."""
    _need(4)
    mesh = _mesh1()
    x = (rng.standard_normal((2, 6, 10))
         + 1j * rng.standard_normal((2, 6, 10))).astype(np.complex64)
    p = plan(FFTSpec(shape=(2, 6, 10), rank=2, mesh=mesh, decomp="local"))
    assert p.decomp == "local" and not p.sharded
    got = np.asarray(p.fft(jnp.asarray(x)))
    np.testing.assert_allclose(got, np.fft.fft2(x), atol=1e-3)
    import warnings as _w
    from repro.kernels import ops
    with _w.catch_warnings():
        _w.simplefilter("ignore")
        got2 = np.asarray(ops.fft2(jnp.asarray(x), mesh=mesh,
                                   decomp="local"))
    np.testing.assert_array_equal(got, got2)


def test_local_ft_plan_treats_groups_as_noop(crand):
    """groups/group_size are mesh-path knobs: a LOCAL ft plan must accept
    any value as a documented no-op (ops.ft_fft contract), not validate it
    against the batch."""
    x = jnp.asarray(crand(6, 256))   # 4 does not divide 6
    p = plan(FFTSpec(shape=(6, 256), ft=FTConfig(groups=4)))
    assert p.groups is None and not p.sharded
    res = p.ft_fft(x)
    assert int(res.corrected) == 0
    np.testing.assert_allclose(np.asarray(res.y), np.fft.fft(np.asarray(x)),
                               atol=1e-3)


def test_plan_resolves_decomp_once():
    _need(4)
    mesh = _mesh1()
    p = plan(FFTSpec(shape=(8, 64, 128), rank=2, mesh=mesh))
    assert p.decomp in ("slab", "pencil")
    assert p.volume["decomp"] == p.decomp
    assert p.in_spec is not None and p.out_spec is not None
    pl = plan(FFTSpec(shape=(8, 64, 128), rank=2))
    assert pl.decomp == "local" and not pl.sharded


# ---------------------------------------------------------------------------
# bitwise identity vs the legacy kwarg paths
# ---------------------------------------------------------------------------


def _legacy_1d(x, mesh, *, ft, natural_order, ftcfg):
    from repro.core.fft.distributed import distributed_fft, ft_distributed_fft
    if ft:
        return ft_distributed_fft(
            x, mesh, threshold=ftcfg.threshold, groups=ftcfg.groups,
            natural_order=natural_order).y
    return distributed_fft(x, mesh, natural_order=natural_order)


def _legacy_2d(x, mesh, *, decomp, ft, natural_order, ftcfg):
    from repro.core.fft import multidim
    if ft:
        return multidim.ft_distributed_fft2(
            x, mesh, threshold=ftcfg.threshold, groups=ftcfg.groups).y
    return multidim.distributed_fft2(x, mesh, decomp=decomp,
                                     natural_order=natural_order)


@pytest.mark.parametrize("dtype", [np.complex64, np.complex128])
@pytest.mark.parametrize("mesh_kind", ["1d", "2d"])
@pytest.mark.parametrize(
    "case", ["1d-plain", "1d-ft", "1d-transposed",
             "2d-slab", "2d-slab-ft", "2d-pencil", "2d-pencil-transposed"])
def test_plan_bitwise_identical_to_legacy(case, mesh_kind, dtype, rng):
    """The acceptance matrix: plan executors must be BITWISE identical to
    the legacy kwarg dispatch (they bind the same cached pipelines)."""
    _need(4)
    mesh = _mesh1() if mesh_kind == "1d" else _mesh2()
    ftcfg = FTConfig(groups=4)
    rank = 1 if case.startswith("1d") else 2
    ft = case.endswith("-ft")
    natural = not case.endswith("transposed")
    decomp = "auto"
    if case.startswith("2d-slab"):
        decomp = "slab"
    elif case.startswith("2d-pencil"):
        decomp = "pencil"
    shape = (8, 4096) if rank == 1 else (8, 64, 128)
    x = (rng.standard_normal(shape)
         + 1j * rng.standard_normal(shape)).astype(dtype)
    x = jnp.asarray(x)
    spec = FFTSpec(shape=shape, dtype=np.dtype(dtype).name, rank=rank,
                   mesh=mesh, decomp=decomp, natural_order=natural,
                   ft=ftcfg if ft else None)
    p = plan(spec)
    got = p.ft_fft(x).y if ft else p.fft(x)
    if rank == 1:
        want = _legacy_1d(x, mesh, ft=ft, natural_order=natural, ftcfg=ftcfg)
    else:
        want = _legacy_2d(x, mesh, decomp=decomp, ft=ft,
                          natural_order=natural, ftcfg=ftcfg)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
    if not ft:
        # the inverse round-trips bitwise against the legacy inverse too
        from repro.core.fft import multidim
        from repro.core.fft.distributed import distributed_ifft
        back = p.ifft(got)
        if rank == 1:
            wback = distributed_ifft(want, mesh, natural_order=natural)
        else:
            wback = multidim.distributed_ifft2(want, mesh, decomp=decomp,
                                               natural_order=natural)
        np.testing.assert_array_equal(np.asarray(back), np.asarray(wback))


def test_plan_spectral_matches_legacy(rng):
    _need(4)
    mesh = _mesh2()
    a = rng.standard_normal((8, 1500)).astype(np.float32)
    v = rng.standard_normal(63).astype(np.float32)
    from repro.core.fft import spectral
    got = spectral.fft_convolve(a, v, mesh, mode="same")
    want = np.stack([np.convolve(r, v, "same") for r in a])
    assert np.abs(np.asarray(got) - want).max() < 2e-4 * np.abs(want).max()
    # the plan behind it is cache-shared with an explicit conv_spec build
    sp = spectral.conv_spec(a, v, mesh)
    assert plan(sp) is plan(spectral.conv_spec(a, v, mesh))
    got2 = plan(sp).correlate(a, v, mode="same")
    wantc = np.stack([np.correlate(r, v, "same") for r in a])
    assert np.abs(np.asarray(got2) - wantc).max() < 2e-4 * np.abs(wantc).max()
    # wrong-size operands against a fixed plan fail loudly, not wrongly
    with pytest.raises(ValueError, match="nfft"):
        plan(sp).convolve(a[:, :200], v)
    ps = plan(FFTSpec(shape=(8, 4096), mesh=mesh,
                      natural_order=False)).power_spectrum(
        jnp.asarray(rng.standard_normal((8, 4096)).astype(np.float32)))
    assert ps.shape == (8, 4096) and ps.dtype == np.float32


# ---------------------------------------------------------------------------
# kernels.ops deprecation shims
# ---------------------------------------------------------------------------


def test_ops_kwargs_deprecated_but_working(crand):
    _need(4)
    from repro.kernels import ops
    mesh = _mesh1()
    x = jnp.asarray(crand(4, 4096))
    api.reset_deprecation_warnings()
    with pytest.warns(api.FFTKwargDeprecationWarning):
        y = ops.fft(x, mesh=mesh)
    want = plan(FFTSpec(shape=(4, 4096), mesh=mesh)).fft(x)
    np.testing.assert_array_equal(np.asarray(y), np.asarray(want))
    # one-shot: a second deprecated call on the same entry stays silent
    import warnings as _w
    with _w.catch_warnings():
        _w.simplefilter("error", api.FFTKwargDeprecationWarning)
        ops.fft(x, mesh=mesh)
    # defaults (and explicit default values) never warn
    with _w.catch_warnings():
        _w.simplefilter("error", api.FFTKwargDeprecationWarning)
        ops.fft(x[:2, :256])
        ops.fft(x[:2, :256], mesh=None, axis="fft", natural_order=True)


def test_deprecation_warnings_resettable(crand):
    """Regression: the one-shot registry must be resettable — two isolated
    invocations (reset between, as the autouse fixture does per test) BOTH
    warn. Before ``reset_deprecation_warnings`` the module-global set made
    the second invocation permanently silent, so warning assertions passed
    or failed depending on suite order."""
    _need(4)
    from repro.kernels import ops
    mesh = _mesh1()
    x = jnp.asarray(crand(4, 4096))

    def legacy_call():                 # ONE call site, invoked repeatedly
        return ops.fft(x, mesh=mesh)

    for _ in range(2):                 # isolated invocation = fresh registry
        api.reset_deprecation_warnings()
        with pytest.warns(api.FFTKwargDeprecationWarning):
            legacy_call()
        # one-shot within an invocation: same call site stays silent
        import warnings as _w
        with _w.catch_warnings():
            _w.simplefilter("error", api.FFTKwargDeprecationWarning)
            legacy_call()
    # distinct entry points are distinct keys: ifft still warns after fft
    with pytest.warns(api.FFTKwargDeprecationWarning):
        ops.ifft(x, mesh=mesh)


def test_ops_auto_dispatch_still_silent(crand):
    _need(4)
    import warnings as _w
    from repro.kernels import ops
    from repro.parallel import shard_signals
    mesh = _mesh1()
    x = crand(4, 4096)
    xs = shard_signals(x, mesh)
    with _w.catch_warnings():
        _w.simplefilter("error", api.FFTKwargDeprecationWarning)
        y = ops.fft(xs)
    np.testing.assert_array_equal(
        np.asarray(y),
        np.asarray(plan(FFTSpec(shape=(4, 4096), mesh=mesh)).fft(xs)))


# ---------------------------------------------------------------------------
# rfft / irfft mesh routing
# ---------------------------------------------------------------------------


def test_rfft_irfft_on_mesh(rng):
    _need(4)
    from repro.core.fft.extensions import irfft, rfft
    mesh = _mesh1()
    x = rng.standard_normal((4, 1 << 13)).astype(np.float32)
    got = np.asarray(rfft(jnp.asarray(x), mesh=mesh))
    want = np.fft.rfft(x)
    assert np.abs(got - want).max() < 4e-5 * np.abs(want).max()
    back = np.asarray(irfft(jnp.asarray(got), mesh=mesh))
    assert np.abs(back - x).max() < 4e-5 * np.abs(x).max()
    # infeasible half length (too small for shards^2) falls back local
    small = rng.standard_normal((2, 16)).astype(np.float32)
    got_s = np.asarray(rfft(jnp.asarray(small), mesh=mesh))
    assert np.abs(got_s - np.fft.rfft(small)).max() < 1e-4


def test_irfft_odd_n_direct_dft_fallback_with_mesh(rng):
    """Odd n has no power-of-two plan: the documented fallback is the
    local direct inverse DFT even when a mesh is passed."""
    _need(4)
    from repro.core.fft.extensions import irfft
    mesh = _mesh1()
    x = rng.standard_normal((2, 511)).astype(np.float32)
    y = np.fft.rfft(x)
    got = np.asarray(irfft(jnp.asarray(y), n=511, mesh=mesh))
    np.testing.assert_allclose(got, np.fft.irfft(y, 511), atol=2e-4)


# ---------------------------------------------------------------------------
# serve integration: one plan per worker
# ---------------------------------------------------------------------------


def test_serve_plan_reuses_one_plan(crand):
    _need(4)
    from repro.serve.specs import build_fft_spec, serve_plan
    mesh = _mesh1()
    spec = build_fft_spec((8, 4096), mesh=mesh, ft=True, groups=4)
    assert spec.ft is not None and spec.ft.groups == 4
    p = plan(spec)
    x = crand(8, 4096)
    y1, info1 = serve_plan(p, x, op="fft")
    y2, info2 = serve_plan(p, x, op="fft")
    assert info1["groups"] == 4 and info1["flagged"] == 0
    np.testing.assert_array_equal(np.asarray(y1), np.asarray(y2))
    # spec strings on the CLI resolve through the same builder
    import argparse
    from repro.serve.specs import apply_fft_spec_arg
    ns = argparse.Namespace(fft_n=0, batch=0, fft_shards=None, fft_data=1,
                            fft_dims=1, fft_rows=0, fft_cols=0, fft_op="fft",
                            fft_decomp="auto", ft=False, fft_groups=None,
                            fft_kernel_n=63, transposed=False,
                            fft_threshold=1e-4)
    apply_fft_spec_arg(ns, "n=4096,batch=8,shards=4,ft=1,groups=4")
    assert (ns.fft_n, ns.batch, ns.fft_shards, ns.ft, ns.fft_groups) == \
        (4096, 8, 4, True, 4)
    with pytest.raises(SystemExit, match="unknown key"):
        apply_fft_spec_arg(ns, "bogus=1")


def test_build_fft_spec_op_defaults():
    _need(4)
    from repro.serve.specs import build_fft_spec
    mesh = _mesh1()
    # order-agnostic periodogram defaults to transposed on a mesh
    assert build_fft_spec((8, 4096), mesh=mesh,
                          op="spectrum").natural_order is False
    assert build_fft_spec((8, 4096), mesh=None,
                          op="spectrum").natural_order is True
    assert build_fft_spec((8, 4096), mesh=mesh, op="fft").natural_order
    # convolve specs describe the PADDED pipeline transform
    sp = build_fft_spec((8, 1500), mesh=mesh, op="convolve",
                        kernel_shape=(63,))
    assert sp.shape == (8, 2048)
    sp2 = build_fft_spec((4, 20, 24), mesh=mesh, op="convolve",
                         kernel_shape=(5, 7), dims=2)
    assert sp2.shape == (4, 32, 32) and sp2.decomp == "slab"
    with pytest.raises(ValueError, match="1-D only"):
        build_fft_spec((4, 20, 24), mesh=mesh, op="correlate", dims=2,
                       kernel_shape=(5, 7))


# ---------------------------------------------------------------------------
# FTPolicy bridge
# ---------------------------------------------------------------------------


def test_policy_to_ft_config_plans():
    from repro.core.ft.policy import FTPolicy
    pol = FTPolicy(mesh_groups=2, threshold=1e-5,
                   recompute_uncorrectable=False)
    spec = FFTSpec(shape=(8, 256), ft=pol.to_ft_config())
    p = plan(spec)
    assert p.spec.ft.threshold == 1e-5
    assert p.spec.ft.recompute_uncorrectable is False
    assert p.groups is None          # groups are a mesh-path knob
    _need(4)
    pm = plan(FFTSpec(shape=(8, 4096), mesh=_mesh1(),
                      ft=pol.to_ft_config()))
    assert pm.groups == 2

"""serve_plan edge paths + the consolidated --fft-spec parser's serving
keys + _ft_telemetry completeness on mesh paths (subprocess)."""
import argparse

import numpy as np
import pytest

from conftest import run_py
from repro.core.fft import api
from repro.launch.serve import (_SPEC_KEYS, apply_fft_spec_arg,
                                build_fft_spec, serve_plan)


@pytest.fixture(autouse=True)
def _fresh_cache():
    api.plan_cache_clear()
    yield
    api.plan_cache_clear()


# -- serve_plan edge paths --------------------------------------------------

def test_serve_plan_kernel_ops_require_kernel(crand):
    p = api.plan(build_fft_spec((4, 128), op="convolve", kernel_shape=(31,)))
    x = np.asarray(crand(4, 128)).real.astype(np.float32)
    with pytest.raises(ValueError, match="needs a kernel"):
        serve_plan(p, x, op="convolve")
    with pytest.raises(ValueError, match="needs a kernel"):
        serve_plan(p, x, op="correlate")


def test_serve_plan_rejects_unknown_op(crand):
    p = api.plan(build_fft_spec((4, 128)))
    with pytest.raises(ValueError, match="op must be"):
        serve_plan(p, crand(4, 128), op="dct")


def test_serve_plan_local_ft_inject_telemetry(crand):
    """The inject= passthrough on the local fused-kernel path: one SEU ->
    flagged verdict, corrected output, complete telemetry dict."""
    from repro.core.plan import FTConfig

    x = crand(4, 256)
    spec = build_fft_spec((4, 256), ft=True, threshold=1e-4)
    assert isinstance(spec.ft, FTConfig)
    p = api.plan(spec)
    y_clean, info_clean = serve_plan(p, x)
    assert info_clean["ft"] is True and info_clean["flagged"] is False
    assert info_clean["corrected"] == 0 and info_clean["location"] == -1
    np.testing.assert_allclose(np.asarray(y_clean), np.fft.fft(x),
                               rtol=1e-3, atol=1e-3)
    inj = np.asarray([0, 1, 3, 1, 250.0, 0.0], np.float32)
    y_f, info_f = serve_plan(p, x, inject=inj)
    assert info_f["flagged"] is True
    assert info_f["corrected"] == 1
    assert info_f["location"] >= 0
    np.testing.assert_allclose(np.asarray(y_f), np.fft.fft(x),
                               rtol=1e-3, atol=1e-3)


# -- _ft_telemetry completeness on the mesh (grouped + real) ----------------

FT_KEYS = ("ft", "groups", "group_size", "score", "flagged", "locations",
           "corrected", "uncorrectable", "checksum_faults", "recomputed",
           "shard_delta_max")


@pytest.mark.slow
def test_ft_telemetry_complete_grouped_and_real_mesh():
    out = run_py(f"""
import numpy as np, jax
from repro.launch.mesh import make_mesh
from repro.core.fft import api
from repro.launch.serve import build_fft_spec, serve_plan

mesh = make_mesh((4,), ('fft',))
rng = np.random.default_rng(0)
KEYS = {FT_KEYS!r}

# grouped 1-D pencil ABFT: every verdict field present and typed
x = (rng.standard_normal((8, 4096)) +
     1j * rng.standard_normal((8, 4096))).astype(np.complex64)
p = api.plan(build_fft_spec((8, 4096), mesh=mesh, ft=True, groups=4))
y, info = serve_plan(p, x)
missing = [k for k in KEYS if k not in info]
assert not missing, missing
assert info['groups'] == 4 and info['group_size'] == 2
assert info['flagged'] == 0 and info['corrected'] == 0
assert isinstance(info['locations'], list) and info['locations'] == []
assert info['shard_delta_max'] < 1e-4, info
np.testing.assert_allclose(np.asarray(y), np.fft.fft(x), rtol=2e-2,
                           atol=2e-2)

# one injected SEU -> flagged group, decoded location, corrected output
inj = np.asarray([[0, 3, 1, 2, 1, 300.0, 0.0]], np.float32)
y_f, info_f = serve_plan(p, x, inject=inj)
assert info_f['flagged'] == 1 and info_f['corrected'] == 1, info_f
assert info_f['locations'], info_f
np.testing.assert_allclose(np.asarray(y_f), np.fft.fft(x), rtol=2e-2,
                           atol=2e-2)

# real (half-spectrum) grouped slab: same completeness contract
xr = rng.standard_normal((8, 64, 256)).astype(np.float32)
pr = api.plan(build_fft_spec((8, 64, 256), mesh=mesh, dims=2, real=True,
                             ft=True, groups=4))
yr, rinfo = serve_plan(pr, xr)
missing = [k for k in KEYS if k not in rinfo]
assert not missing, missing
assert rinfo['real'] is True and rinfo['flagged'] == 0
np.testing.assert_allclose(np.asarray(yr), np.fft.rfft2(xr), rtol=2e-2,
                           atol=2e-2)
print('OK')
""", devices=4)
    assert "OK" in out


# -- the consolidated spec string: serving-policy keys ----------------------

def _fresh_args():
    ns = argparse.Namespace(
        fft_n=1 << 16, batch=4, fft_shards=None, fft_data=1, fft_dims=1,
        fft_rows=256, fft_cols=256, fft_op="fft", fft_decomp="auto",
        ft=False, fft_groups=None, fft_kernel_n=63, transposed=False,
        fft_threshold=1e-4, fft_real=False, fft_chunks=1,
        serve_workers=2, serve_max_batch=8, serve_deadline_ms=2.0,
        serve_queue_depth=64, serve_timeout_ms=None)
    return ns


def test_spec_arg_serve_keys_roundtrip():
    ns = _fresh_args()
    apply_fft_spec_arg(
        ns, "n=4096,workers=4,max_batch=16,deadline_ms=1.5,queue=128,"
            "timeout_ms=250")
    assert ns.fft_n == 4096
    assert ns.serve_workers == 4
    assert ns.serve_max_batch == 16
    assert ns.serve_deadline_ms == 1.5
    assert ns.serve_queue_depth == 128
    assert ns.serve_timeout_ms == 250.0
    # untouched keys keep their flag defaults (the spec only overrides)
    assert ns.batch == 4 and ns.ft is False


def test_spec_arg_serve_keys_strictness():
    with pytest.raises(ValueError, match="duplicate key"):
        apply_fft_spec_arg(_fresh_args(), "workers=2,workers=4")
    with pytest.raises(ValueError, match="empty segment"):
        apply_fft_spec_arg(_fresh_args(), "workers=2,,queue=8")
    with pytest.raises(SystemExit, match="unknown key"):
        apply_fft_spec_arg(_fresh_args(), "max_batchez=8")


def test_spec_keys_shared_with_runtime_package():
    """launch.serve and repro.serve expose the SAME key table — the CLI
    and the runtime must never drift on what a spec string means."""
    from repro.serve import SPEC_KEYS

    assert _SPEC_KEYS is SPEC_KEYS
    for k in ("workers", "max_batch", "deadline_ms", "queue", "timeout_ms"):
        assert k in SPEC_KEYS

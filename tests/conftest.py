"""Shared test fixtures: seeded rng, complex batches, per-dtype tolerances,
host-mesh helpers, and the multi-device subprocess runner.

Every fixture is deterministic per test (seeds derive from the nodeid via
crc32, not Python's salted hash), so reordering or deselecting tests never
changes another test's data.
"""
from __future__ import annotations

import os
import subprocess
import sys
import zlib

import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

# atol = ATOL[dtype] * max|reference| — the suite-wide spectrum tolerance
# per complex dtype (c64 roundoff grows ~sqrt(log N); 4e-5 covers N = 2^20).
ATOL = {
    np.dtype(np.complex64): 4e-5,
    np.dtype(np.complex128): 1e-11,
    np.dtype(np.float32): 4e-5,
    np.dtype(np.float64): 1e-11,
}


@pytest.fixture(autouse=True)
def _fresh_deprecation_warnings():
    """Reset the one-shot FFT kwarg deprecation registry around every test.

    The registry is module-global (so real programs warn once per call
    site), which made warning assertions order-dependent across the suite:
    whichever test tripped a legacy path first swallowed everyone else's
    warning. Resetting per test makes each test observe its own first use.
    """
    from repro.core.fft.api import reset_deprecation_warnings

    reset_deprecation_warnings()
    yield
    reset_deprecation_warnings()


@pytest.fixture
def rng(request) -> np.random.Generator:
    """Per-test deterministic generator, seeded from the test's nodeid."""
    return np.random.default_rng(zlib.crc32(request.node.nodeid.encode()))


@pytest.fixture
def crand(rng):
    """``crand(b, n[, dtype])`` -> random complex (b, n) batch."""

    def make(b, n, dtype=np.complex64):
        x = rng.standard_normal((b, n)) + 1j * rng.standard_normal((b, n))
        return x.astype(dtype)

    return make


def spectrum_atol(ref, factor: float = 1.0, dtype=None) -> float:
    """Absolute tolerance for comparing against a reference spectrum."""
    ref = np.asarray(ref)
    return factor * ATOL[np.dtype(dtype or ref.dtype)] * (
        np.abs(ref).max() + 1e-30)


@pytest.fixture
def assert_spectrum_close():
    """``assert_spectrum_close(got, want[, factor])`` with per-dtype atol.

    The tolerance keys off the *lower-precision* side: numpy < 2 promotes
    np.fft results to complex128, which must not tighten the bound for a
    complex64 implementation under test.
    """

    def check(got, want, factor: float = 1.0):
        got, want = np.asarray(got), np.asarray(want)
        dt = min(got.dtype, want.dtype, key=lambda d: d.itemsize)
        np.testing.assert_allclose(got, want, rtol=0,
                                   atol=spectrum_atol(want, factor, dt))

    return check


@pytest.fixture
def host_mesh():
    """``host_mesh(*sizes, axes=names)`` over however many devices exist,
    clamping to a 1-D single-device mesh when the request doesn't fit."""
    import jax

    from repro.launch.mesh import make_mesh

    def make(*sizes, axes=("data", "model")):
        n = len(jax.devices())
        if int(np.prod(sizes)) > n:
            sizes, axes = (n,), (axes[0],)
        return make_mesh(sizes, axes)

    return make


def run_py(code: str, devices: int = 8, timeout: int = 600) -> str:
    """Run ``code`` in a subprocess with a forced multi-device host platform
    (the XLA device-count flag must be set before jax initializes, so it
    cannot be applied inside the running test process). The child runs on
    the CPU: an accelerator belongs to one process, and the parent may
    hold it."""
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={devices}"
    env["PYTHONPATH"] = os.path.join(REPO, "src")
    out = subprocess.run([sys.executable, "-c", code], env=env,
                         capture_output=True, text=True, timeout=timeout)
    assert out.returncode == 0, f"STDOUT:\n{out.stdout}\nSTDERR:\n{out.stderr}"
    return out.stdout

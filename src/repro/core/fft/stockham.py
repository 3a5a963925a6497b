"""Pure-JAX mixed-radix Stockham FFT (the reference / TPU-graph-level path).

Each stage is a contraction with a small DFT factor matrix followed by a
twiddle multiply — on TPU every stage therefore runs on the MXU. Complex data
is kept in native complex64/128 at this level; the Pallas kernels (see
``repro.kernels``) use the split real/imag representation instead.

Index convention (see ``factors.stage_twiddle``): for N = r*m,

    n = m*n1 + n2          (input:  reshape to (r, m), row-major)
    k = k1 + r*k2          (output: transpose (r, m) -> (m, r), flatten)

    Y[k1,k2] = sum_{n2} T[k1,n2] * (sum_{n1} Wr[k1,n1] X[n1,n2]) * Wm[n2,k2]
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from . import factors
from .plan import Plan, make_plan

__all__ = ["fft", "ifft", "fft_with_plan", "block_fft_stages", "naive_dft",
           "radix2_fft"]

# A TPU runs a DEFAULT-precision f32 matmul as one bf16 pass, too coarse for
# the 4e-5 spectrum tolerance; ask for full f32 (a no-op on the CPU).
_PRECISION = jax.lax.Precision.HIGHEST


def _factor_const(r: int, dtype, inverse: bool):
    return jnp.asarray(factors.dft_matrix(r, inverse=inverse), dtype=dtype)


def _twiddle_const(r: int, m: int, dtype, inverse: bool):
    return jnp.asarray(factors.stage_twiddle(r, m, inverse=inverse),
                       dtype=dtype)


def block_fft_stages(x: jax.Array, *, inverse: bool = False) -> jax.Array:
    """In-"VMEM" mixed-radix FFT over the last axis of ``x`` (batched).

    This is the stage structure the Pallas kernel mirrors (with precomputed
    twiddle tables); at the JAX level it is also the building block of the
    large-N multi-pass driver.
    """
    n = x.shape[-1]
    if n == 1:
        return x
    plan_stages = make_plan(n).stages[0]
    return _fft_recursive(x, list(plan_stages), inverse)


def _fft_recursive(x: jax.Array, stages, inverse: bool) -> jax.Array:
    n = x.shape[-1]
    if len(stages) == 0 or n == 1:
        return x
    st = stages[0]
    r, m = st.radix, st.m
    assert r * m == n, (r, m, n)
    dtype = x.dtype
    z = x.reshape(x.shape[:-1] + (r, m))
    w = _factor_const(r, dtype, inverse)
    z = jnp.einsum("kr,...rm->...km", w, z, precision=_PRECISION)
    if m > 1:
        z = z * _twiddle_const(r, m, dtype, inverse)
        z = _fft_recursive(z, stages[1:], inverse)  # FFT along last axis (m)
    # k = k1 + r*k2  ->  output viewed as (m, r) row-major is Y^T
    z = jnp.swapaxes(z, -1, -2)
    return z.reshape(x.shape[:-1] + (n,))


def fft_with_plan(x: jax.Array, plan: Plan) -> jax.Array:
    """Single-pass (VMEM-sized) FFT following ``plan.stages[0]``."""
    if plan.num_passes != 1:
        raise ValueError(f"fft_with_plan is single-pass, got "
                         f"num_passes={plan.num_passes} — use "
                         f"large.fft_large for multi-pass plans")
    y = _fft_recursive(x, list(plan.stages[0]), plan.inverse)
    if plan.inverse:
        y = y / plan.n
    return y


@functools.partial(jax.jit, static_argnames=("inverse",))
def _fft_jit(x: jax.Array, *, inverse: bool) -> jax.Array:
    n = x.shape[-1]
    plan = make_plan(n, inverse=inverse)
    if plan.num_passes == 1:
        return fft_with_plan(x, plan)
    from . import large  # local import to avoid cycle

    return large.fft_large(x, plan)


def fft(x: jax.Array) -> jax.Array:
    """Forward FFT over the last axis. Matches ``jnp.fft.fft`` conventions."""
    x = jnp.asarray(x)
    if not jnp.issubdtype(x.dtype, jnp.complexfloating):
        x = x.astype(jnp.complex64)
    return _fft_jit(x, inverse=False)


def ifft(x: jax.Array) -> jax.Array:
    """Inverse FFT over the last axis (normalized by 1/N)."""
    x = jnp.asarray(x)
    if not jnp.issubdtype(x.dtype, jnp.complexfloating):
        x = x.astype(jnp.complex64)
    return _fft_jit(x, inverse=True)


# ---------------------------------------------------------------------------
# Baselines for benchmarks/stepwise_opt.py (paper Fig. 15)
# ---------------------------------------------------------------------------

def naive_dft(x: jax.Array, *, inverse: bool = False) -> jax.Array:
    """O(N^2) direct DFT — the paper's conceptual v0 lower bound."""
    n = x.shape[-1]
    w = jnp.asarray(factors.dft_matrix(n, inverse=inverse), dtype=x.dtype)
    y = jnp.einsum("kn,...n->...k", w, x, precision=_PRECISION)
    return y / n if inverse else y


def radix2_fft(x: jax.Array, *, inverse: bool = False) -> jax.Array:
    """Pure radix-2 Stockham (paper's TurboFFT-v0: one radix-2 per 'launch').

    log2(N) stages of radix 2 — maximally launch/stage heavy, no MXU use.
    """
    n = x.shape[-1]
    if n & (n - 1):
        raise ValueError("power of two required")
    stages = []
    m = n
    while m > 1:
        m //= 2
        from .plan import StagePlan

        stages.append(StagePlan(radix=2, m=m))
    y = _fft_recursive(x, stages, inverse)
    return y / n if inverse else y

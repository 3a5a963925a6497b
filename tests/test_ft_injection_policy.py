"""Coverage for the fault-model surface: ``core.ft.injection`` (bit flips,
deterministic and Poisson fault schedules) and ``core.ft.policy`` (knob
plumbing + detection-threshold edge semantics). These modules previously had
no dedicated test file.
"""
import dataclasses
import inspect

import numpy as np
import pytest

import jax.numpy as jnp

from repro.core.ft import (FaultSchedule, FTPolicy, flip_bit,
                           poisson_schedule, random_flip)
from repro.launch.mesh import make_mesh


# ---------------------------------------------------------------------------
# bit-flip SEU model (paper §5.3.1)
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("dtype", [np.float32, np.float64,
                                   np.complex64, np.complex128])
def test_flip_bit_is_involutive(dtype, rng):
    """Flipping the same bit twice restores the exact original pattern."""
    x = rng.standard_normal(8).astype(dtype)
    if np.iscomplexobj(x):
        x = (x + 1j * rng.standard_normal(8)).astype(dtype)
    nbits = {np.dtype(np.float32): 32, np.dtype(np.float64): 64,
             np.dtype(np.complex64): 64, np.dtype(np.complex128): 128}[
        np.dtype(dtype)]
    for bit in (0, nbits // 2 - 1, nbits - 1):
        y = flip_bit(x, (3,), bit)
        assert y[3:4].tobytes() != x[3:4].tobytes()
        z = flip_bit(y, (3,), bit)
        assert z.tobytes() == x.tobytes()  # exact bit-pattern restoration
        # every other element is untouched
        mask = np.arange(8) != 3
        np.testing.assert_array_equal(y[mask], x[mask])


def test_flip_bit_targets_real_and_imag_parts():
    x = np.ones(2, np.complex64)
    lo = flip_bit(x, (0,), 10)      # bit < 32: real representation
    hi = flip_bit(x, (0,), 32 + 10)  # bit >= 32: imag representation
    assert lo[0].real != 1.0 and lo[0].imag == 0.0
    assert hi[0].real == 1.0 and hi[0].imag != 0.0
    # sign bit of the real part negates it exactly
    neg = flip_bit(x, (1,), 31)
    assert neg[1] == -1.0 + 0.0j


def test_flip_bit_rejects_unsupported_dtype():
    with pytest.raises(TypeError):
        flip_bit(np.ones(2, np.int32), (0,), 3)


def test_random_flip_eps_consistency(rng):
    """The returned eps is exactly corrupted - original at the flip site."""
    x = (rng.standard_normal(16) + 1j * rng.standard_normal(16)
         ).astype(np.complex64)
    y, (flat, bit), eps = random_flip(rng, x.copy())
    idx = np.unravel_index(flat, x.shape)
    got = complex(y[idx]) - complex(x[idx])
    if np.isfinite(got):
        assert got == eps
    else:  # exponent-bit flips legitimately produce inf/nan
        assert not np.isfinite(eps)
    mask = np.arange(16) != flat
    np.testing.assert_array_equal(y[mask], x[mask])


def test_random_flip_is_seed_deterministic():
    x = np.ones(32, np.float32)
    a = random_flip(np.random.default_rng(42), x.copy())
    b = random_flip(np.random.default_rng(42), x.copy())
    assert a[1] == b[1] and np.array_equal(a[0], b[0])


# ---------------------------------------------------------------------------
# fault schedules
# ---------------------------------------------------------------------------


def test_fault_schedule_for_step():
    sched = FaultSchedule(entries=((3, 1, 5, 200, 60.0, -25.0),
                                   (7, 0, 2, 17, -8.0, 4.0)))
    assert sched.num_faults == 2
    hit = np.asarray(sched.for_step(3))
    np.testing.assert_allclose(hit, [1, 5, 200, 1, 60.0, -25.0])
    np.testing.assert_allclose(np.asarray(sched.for_step(7)),
                               [0, 2, 17, 1, -8.0, 4.0])
    # a step with no scheduled fault yields a disabled descriptor
    miss = np.asarray(sched.for_step(4))
    assert miss[3] == 0.0
    np.testing.assert_allclose(miss, np.zeros(6))


def test_poisson_schedule_deterministic_and_in_range():
    kw = dict(steps=200, rate_per_step=0.3, tiles=4, bs=8, n=256)
    s1 = poisson_schedule(np.random.default_rng(5), **kw)
    s2 = poisson_schedule(np.random.default_rng(5), **kw)
    assert s1.entries == s2.entries              # same seed, same schedule
    assert 0 < s1.num_faults < 200
    steps = [e[0] for e in s1.entries]
    assert steps == sorted(steps) and len(set(steps)) == len(steps)
    for (step, tile, row, col, er, ei) in s1.entries:
        assert 0 <= step < 200 and 0 <= tile < 4
        assert 0 <= row < 8 and 0 <= col < 256
    # zero rate -> empty schedule
    empty = poisson_schedule(np.random.default_rng(0), steps=50,
                             rate_per_step=0.0, tiles=4, bs=8, n=256)
    assert empty.num_faults == 0


# ---------------------------------------------------------------------------
# policy: knob plumbing + threshold edge
# ---------------------------------------------------------------------------


def test_ftpolicy_config_matches_consumer_signatures():
    """kernel_kwargs stays in sync with the local kernel call site, and
    to_ft_config() carries every policy knob into the plan API's FTConfig
    (a renamed knob would otherwise fail only at serve time)."""
    from repro.core.fft.api import FFTSpec, FTConfig
    from repro.kernels.ops import ft_fft

    pol = FTPolicy(mesh_groups=8, group_size=None,
                   recompute_uncorrectable=False)
    kernel_params = set(inspect.signature(ft_fft).parameters)
    assert set(pol.kernel_kwargs()) <= kernel_params
    cfg = pol.to_ft_config()
    assert isinstance(cfg, FTConfig)
    assert cfg.groups == 8 and cfg.group_size is None
    assert cfg.recompute_uncorrectable is False
    assert cfg.threshold == pol.threshold
    assert cfg.transactions == pol.transactions
    assert cfg.encoding == pol.encoding
    # the config is spec-embeddable (hashable, valid) as-is
    spec = FFTSpec(shape=(16, 256), ft=cfg)
    assert hash(spec) == hash(FFTSpec(shape=(16, 256), ft=pol.to_ft_config()))
    # frozen: policies are config values, not mutable state
    with pytest.raises(dataclasses.FrozenInstanceError):
        pol.threshold = 1.0


def test_detect_threshold_edge_is_strict():
    """Detection fires strictly ABOVE the threshold: a residual sitting at
    exactly the configured value must NOT flag (the ROC operating point
    counts it as noise), while any value below the score does."""
    from repro.core import abft

    n = 16
    t = 0.25  # exactly representable; sqrt(t*t) == t in fp64
    cs2_out = jnp.ones((1, n), jnp.complex128)   # scale == 1 exactly
    cs2_in = cs2_out + t                         # d2 == t everywhere
    cs3 = jnp.zeros((1, n), jnp.complex128)
    cs = abft.GroupChecksums(cs2_in=cs2_in, cs3_in=cs3,
                             cs2_out=cs2_out, cs3_out=cs3)
    ident = lambda c: c
    at = abft.detect_locate(cs, forward=ident, threshold=t)
    assert float(at.error_score[0]) == t         # engineered exact score
    assert not bool(at.flagged[0])               # score > t is strict
    below = abft.detect_locate(cs, forward=ident, threshold=t * (1 - 1e-12))
    assert bool(below.flagged[0])
    above = abft.detect_locate(cs, forward=ident, threshold=t * (1 + 1e-12))
    assert not bool(above.flagged[0])


def test_mesh_threshold_edge_matches_policy(crand):
    """The sharded path keeps the same strict-inequality semantics: a clean
    run scores far under any sane threshold, and setting the threshold to
    the exact observed score of an injected fault un-flags it while any
    smaller threshold flags it — i.e. the knob is a true ROC dial."""
    from repro.core.fft.distributed import ft_distributed_fft

    mesh = make_mesh((1,), ("fft",))
    x = crand(8, 256)
    inj = jnp.asarray([[0, 5, 3, 7, 1, 60.0, -25.0]], jnp.float32)
    res = ft_distributed_fft(x, mesh, groups=4, inject=inj)
    score = float(jnp.max(res.group_score))
    assert bool(res.flagged[2])
    at = ft_distributed_fft(x, mesh, groups=4, inject=inj, threshold=score)
    assert not bool(at.flagged.any())            # strict: score > threshold
    under = ft_distributed_fft(x, mesh, groups=4, inject=inj,
                               threshold=score * 0.99)
    assert bool(under.flagged[2])

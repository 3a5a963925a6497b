"""Two-side ABFT GEMM through the shared plan layer.

Covers the op-agnostic plan registry (one FTPolicy -> FFT *and* GEMM
plans), bitwise parity between the fused Pallas kernel and the XLA
interpreter path, the SEU injection matrix (tile corners, multi-fault
correction, same-column uncorrectable), batched activations, and the
key-traversal ``ft_dot_stats`` aggregation.
"""
import inspect

import numpy as np
import pytest

import jax.numpy as jnp

from repro.core import gemm
from repro.core import plan as planbase
from repro.core.abft import ft_matmul, ft_dot_stats
from repro.core.ft import FTPolicy
from repro.core.plan import FTConfig

FT = FTConfig(threshold=1e-3)


def _int_mats(rng, m, k, n):
    """Integer-valued float32 operands: every sum in both backends is exact
    in f32, so parity checks can demand bitwise equality."""
    x = rng.integers(-4, 5, (m, k)).astype(np.float32)
    w = rng.integers(-4, 5, (k, n)).astype(np.float32)
    return jnp.asarray(x), jnp.asarray(w)


# ---------------------------------------------------------------------------
# plan layer
# ---------------------------------------------------------------------------

def test_plan_registry_shared_cache():
    spec = gemm.GEMMSpec(shape=(128, 128, 128), ft=FT)
    p1 = gemm.plan(spec)
    p2 = gemm.plan(gemm.GEMMSpec(shape=(128, 128, 128), ft=FT))
    assert p1 is p2                      # equal specs hash to one plan
    assert gemm.plan(gemm.GEMMSpec(shape=(128, 128, 256), ft=FT)) is not p1
    d = p1.describe()
    assert d["plan"] == "GEMMPlan" and d["ft"] and d["volume"]["flops"] > 0
    with pytest.raises(TypeError, match="GEMMSpec"):
        planbase.plan(object())


def test_plan_base_has_no_fft_imports():
    """Acceptance: the shared base is op-agnostic — operator families
    register themselves; core/plan.py must not import any of them."""
    src = inspect.getsource(planbase)
    for line in src.splitlines():
        ls = line.strip()
        if ls.startswith(("import ", "from ")):
            assert "fft" not in ls and "gemm" not in ls, ls


def test_one_policy_configures_both_families():
    """The SAME FTPolicy-derived config attaches to FFT and GEMM specs."""
    from repro.core.fft.api import FFTSpec
    from repro.core.fft.api import plan as fft_plan

    pol = FTPolicy(protect_linears=True, threshold=2e-3)
    cfg = pol.to_ft_config()
    assert isinstance(cfg, FTConfig)
    fp = fft_plan(FFTSpec(shape=(8, 64), ft=cfg))
    gp = gemm.plan(gemm.GEMMSpec(shape=(128, 64, 64), ft=cfg))
    assert fp.spec.ft is cfg and gp.spec.ft is cfg


def test_pallas_plan_requires_tile_alignment():
    with pytest.raises(ValueError, match="tile-aligned"):
        gemm.plan(gemm.GEMMSpec(shape=(100, 128, 128), ft=FT,
                                backend="pallas"))
    # auto on unaligned shapes falls back to the interpreter path
    p = gemm.plan(gemm.GEMMSpec(shape=(100, 128, 128), ft=FT))
    assert p.backend == "xla"


# ---------------------------------------------------------------------------
# fused kernel vs interpreter parity
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("tiles", [(128, 128, 128), (64, 64, 64),
                                   (128, 64, 128), (64, 128, 64)])
def test_fused_matches_interpreter_bitwise(rng, tiles):
    m, k, n = 256, 128, 128
    x, w = _int_mats(rng, m, k, n)
    inj = jnp.array([171.0, 40.0, 1.0, 333.0])
    xla = gemm.plan(gemm.spec_for(x, w, ft=FT, backend="xla"))
    pal = gemm.plan(gemm.spec_for(x, w, ft=FT, backend="pallas",
                                  tiles=tiles))
    for inject in (None, inj):
        y1, s1 = xla.ft_matmul(x, w, inject=inject)
        y2, s2 = pal.ft_matmul(x, w, inject=inject)
        assert np.array_equal(np.asarray(y1), np.asarray(y2))
        for key in ("flagged", "corrected", "uncorrectable", "score"):
            assert float(s1[key]) == float(s2[key]), key


# ---------------------------------------------------------------------------
# injection matrix
# ---------------------------------------------------------------------------

_CORNERS = [(0, 0), (0, 255), (255, 0), (255, 255),     # output corners
            (127, 127), (128, 128), (127, 128), (128, 127)]  # tile seams


@pytest.mark.parametrize("backend", ["xla", "pallas"])
@pytest.mark.parametrize("row,col", _CORNERS)
def test_detect_and_correct_at_tile_corners(rng, backend, row, col):
    m = n = 256
    x, w = _int_mats(rng, m, 128, n)
    p = gemm.plan(gemm.spec_for(x, w, ft=FT, backend=backend))
    clean = np.asarray(x) @ np.asarray(w)
    y, s = p.ft_matmul(x, w, inject=jnp.array([row, col, 1.0, 400.0]))
    assert float(s["flagged"]) == 1.0
    assert float(s["corrected"]) == 1.0
    assert float(s["uncorrectable"]) == 0.0
    # integer operands: the decoded correction restores the product exactly
    np.testing.assert_array_equal(np.asarray(y), clean)


@pytest.mark.parametrize("backend", ["xla", "pallas"])
def test_corrects_concurrent_seus_in_distinct_columns(rng, backend):
    x, w = _int_mats(rng, 256, 128, 128)
    p = gemm.plan(gemm.spec_for(x, w, ft=FT, backend=backend))
    inj = jnp.array([[3.0, 7.0, 1.0, 500.0],
                     [200.0, 90.0, 1.0, -450.0],
                     [128.0, 127.0, 1.0, 600.0]])
    y, s = p.ft_matmul(x, w, inject=inj)
    assert float(s["flagged"]) == 3.0
    assert float(s["corrected"]) == 3.0
    np.testing.assert_array_equal(np.asarray(y),
                                  np.asarray(x) @ np.asarray(w))


@pytest.mark.parametrize("backend", ["xla", "pallas"])
def test_flags_multi_seu_in_same_column_uncorrectable(rng, backend):
    x, w = _int_mats(rng, 256, 128, 128)
    p = gemm.plan(gemm.spec_for(x, w, ft=FT, backend=backend))
    inj = jnp.array([[3.0, 7.0, 1.0, 500.0],
                     [200.0, 7.0, 1.0, -450.0]])   # same column twice
    _, s = p.ft_matmul(x, w, inject=inj)
    assert float(s["flagged"]) == 1.0         # one corrupted column
    assert float(s["uncorrectable"]) == 1.0   # non-integer location ratio
    assert float(s["corrected"]) == 0.0


def test_disabled_descriptor_is_a_noop(rng):
    x, w = _int_mats(rng, 128, 128, 128)
    p = gemm.plan(gemm.spec_for(x, w, ft=FT))
    y, s = p.ft_matmul(x, w, inject=jnp.array([3.0, 7.0, 0.0, 500.0]))
    assert float(s["flagged"]) == 0.0
    np.testing.assert_array_equal(np.asarray(y),
                                  np.asarray(x) @ np.asarray(w))


# ---------------------------------------------------------------------------
# batched activations (attention feeds (B, T, D))
# ---------------------------------------------------------------------------

def test_batched_3d_activations_roundtrip(rng):
    b, t, k, n = 4, 64, 128, 128
    x = jnp.asarray(rng.integers(-4, 5, (b, t, k)).astype(np.float32))
    w = jnp.asarray(rng.integers(-4, 5, (k, n)).astype(np.float32))
    # rows of the descriptor index the flattened B*T token axis
    y, s = ft_matmul(x, w, inject=jnp.array([t + 5.0, 9.0, 700.0]))
    assert y.shape == (b, t, n)
    assert float(s["flagged"]) == 1.0 and float(s["corrected"]) == 1.0
    np.testing.assert_array_equal(
        np.asarray(y), np.asarray(x) @ np.asarray(w))


def test_rank_errors():
    with pytest.raises(ValueError, match="batch dim"):
        ft_matmul(jnp.zeros((2, 2, 4, 8)), jnp.zeros((8, 8)))
    with pytest.raises(ValueError, match="2-D"):
        ft_matmul(jnp.zeros((4, 8)), jnp.zeros((2, 8, 8)))


# ---------------------------------------------------------------------------
# ft_dot_stats aggregation (regression: positional [::2] slicing)
# ---------------------------------------------------------------------------

def test_ft_dot_stats_traverses_by_key():
    """The old implementation sliced tree_leaves [::2], which silently
    mis-paired leaves once stats dicts grew beyond two keys or nested —
    aggregation must key off the dict KEY, not leaf position."""
    s1 = {"flagged": jnp.float32(2.0), "corrected": jnp.float32(1.0),
          "uncorrectable": jnp.float32(1.0), "score": jnp.float32(0.5)}
    s2 = {"flagged": jnp.ones((3,)), "corrected": jnp.zeros((3,)),
          "uncorrectable": jnp.zeros((3,)), "score": 0.25 * jnp.ones((3,))}
    agg = ft_dot_stats({"attn": s1, "moe": {"experts": s2}})
    assert float(agg["ft_flagged"]) == 5.0       # 2 + sum(ones(3))
    assert float(agg["ft_corrected"]) == 1.0
    assert float(agg["ft_max_score"]) == 0.5
    # alphabetical leaf order would pair ('corrected', 'flagged', ...) — a
    # positional [::2] slice over 4-key dicts counts corrected+score
    empty = ft_dot_stats({})
    assert float(empty["ft_flagged"]) == 0.0

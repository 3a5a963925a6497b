"""Two-sided ABFT for GEMM — the paper's scheme off the DFT special case.

The paper derives its ABFT from the GEMV view of the DFT (§2.2.2): W is a
*fixed, known* matrix, so the left encoding ``e1^T W`` is free to precompute.
A neural-network linear layer is the same situation — W is the weight matrix,
X the activations. This module protects ``Y = X @ W`` (through the
``core.gemm`` plan family):

    detect:  per-column   (e2^T X) W  vs  e2^T Y   over the token axis,
    locate:  the location checksum e3 = [1..T]: d3/d2 at a corrupted
             column equals (row + 1) — the two-side scheme,
    correct: add d2 back at the decoded (row, column); k concurrent SEUs in
             k distinct columns are corrected in one pass, two faults in the
             SAME column decode as uncorrectable (non-integer ratio).

Under SEU, detection costs two rank-1 GEMVs per tile and correction needs no
recomputation — delayed batched correction identical to the FFT case. The
same decode (:func:`decode_columns`) consumes the fused Pallas kernel's
checksum strips (``kernels.ft_matmul``), so the interpreter path and the
fused path agree on semantics by construction.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from .encoding import EPS

__all__ = ["ft_matmul", "ft_dot_stats", "decode_columns"]

# |d3/d2 - round(d3/d2)| above this is a non-integer location decode:
# more than one fault landed in the column (or the checksum row itself was
# hit) — classified uncorrectable rather than mis-corrected.
_LOC_TOL = 0.25


def _loc_vec(n: int, dtype) -> jax.Array:
    return jnp.arange(1, n + 1, dtype=dtype)


def decode_columns(y, d2, d3, scale, *, t: int, threshold: float,
                   with_correction: bool):
    """Two-side per-column decode shared by the interpreter and fused paths.

    ``d2 = pred2 - out2`` (== ``-eps`` at a corrupted column) and ``d3 =
    pred3 - out3`` are the (d_out,) checksum divergences; ``scale`` the
    output-checksum magnitude normalizer. Returns ``(y, stats)`` with
    float32 ``flagged`` (columns over threshold), ``corrected`` (columns
    with a valid single-fault location decode, applied when
    ``with_correction``), ``uncorrectable`` (flagged columns whose decode is
    non-integer or out of range — multi-SEU in one column), and ``score``
    (max per-column divergence, the detection statistic).
    """
    colmag = jnp.abs(d2) / scale
    score = jnp.max(colmag)
    hit = colmag > threshold
    ratio = d3 / jnp.where(jnp.abs(d2) > 0, d2, 1.0)
    row_f = jnp.round(ratio)
    valid = (hit & (jnp.abs(ratio - row_f) < _LOC_TOL)
             & (row_f >= 1) & (row_f <= t))
    if with_correction:
        row_hat = jnp.clip(row_f.astype(jnp.int32) - 1, 0, t - 1)
        upd = jnp.where(valid, d2, 0.0).astype(y.dtype)
        y = y.at[row_hat, jnp.arange(d2.shape[0])].add(upd)
    stats = {
        "flagged": jnp.sum(hit.astype(jnp.float32)),
        "corrected": (jnp.sum(valid.astype(jnp.float32))
                      if with_correction else jnp.zeros((), jnp.float32)),
        "uncorrectable": jnp.sum((hit & ~valid).astype(jnp.float32)),
        "score": score.astype(jnp.float32),
    }
    return y, stats


@functools.partial(jax.jit, static_argnames=("threshold", "with_correction"))
def _ft_matmul_2d(x, w, *, threshold, with_correction, inject=None):
    t, _ = x.shape
    xf = x.astype(jnp.float32)
    wf = w.astype(jnp.float32)

    # left-side input checksums over the token axis (rank-1 GEMVs)
    e2x = jnp.sum(xf, axis=0)              # e2^T X   (d_in,)
    e3x = _loc_vec(t, jnp.float32) @ xf    # e3^T X   (d_in,)
    y = jnp.dot(x, w, preferred_element_type=jnp.float32)
    if inject is not None:
        inj = jnp.atleast_2d(inject)       # (F, 3) rows of [row, col, eps]
        rows = inj[:, 0].astype(jnp.int32)
        cols = inj[:, 1].astype(jnp.int32)
        y = y.at[rows, cols].add(inj[:, 2].astype(y.dtype))
    # predicted output checksums vs the computed ones
    p2 = e2x @ wf                          # e2^T X W (d_out,)
    p3 = e3x @ wf
    o2 = jnp.sum(y.astype(jnp.float32), axis=0)
    o3 = _loc_vec(t, jnp.float32) @ y.astype(jnp.float32)
    d2 = p2 - o2                           # == -eps at the corrupted column
    d3 = p3 - o3
    scale = jnp.sqrt(jnp.mean(o2 * o2)) + EPS
    y, stats = decode_columns(y, d2, d3, scale, t=t, threshold=threshold,
                              with_correction=with_correction)
    return y.astype(x.dtype), stats


def ft_matmul(
    x: jax.Array,
    w: jax.Array,
    *,
    threshold: float = 1e-3,
    with_correction: bool = True,
    inject: jax.Array | None = None,
):
    """Checked ``y = x @ w``: ``(T, d_in)`` or batched ``(B, T, d_in)``
    activations against a 2-D ``(d_in, d_out)`` weight.

    Returns ``(y, stats)`` — see :func:`decode_columns` for the stats
    contract. ``inject`` is an optional ``(3,)`` array ``[row, col, eps]``
    (or ``(F, 3)`` for concurrent SEUs) adding eps to ``y[row, col]``
    *after* the product — simulating SEUs in the MAC units. On batched
    input the row indexes the flattened ``B * T`` token axis (the layout
    the checksums ride).

    The checksums ride in float32 regardless of the compute dtype (bf16
    accumulation noise would swamp detection otherwise).
    """
    if w.ndim != 2:
        raise ValueError(f"ft_matmul takes a 2-D (d_in, d_out) weight, "
                         f"got w.shape={tuple(w.shape)}")
    if x.ndim == 2:
        return _ft_matmul_2d(x, w, threshold=threshold,
                             with_correction=with_correction, inject=inject)
    if x.ndim == 3:
        b, t, k = x.shape
        y, stats = _ft_matmul_2d(x.reshape(b * t, k), w,
                                 threshold=threshold,
                                 with_correction=with_correction,
                                 inject=inject)
        return y.reshape(b, t, w.shape[-1]), stats
    raise ValueError(
        f"ft_matmul activations must be (T, d_in) or batched (B, T, d_in); "
        f"got rank-{x.ndim} x.shape={tuple(x.shape)} — reshape leading axes "
        f"into one batch dim first")


def ft_dot_stats(stats_tree) -> dict:
    """Aggregate a pytree of per-layer ABFT-GEMM stats dicts into run-level
    counters, traversing by dict KEY (``flagged`` / ``corrected`` /
    ``score``) — robust to arbitrary nesting and to extra keys, unlike
    positional leaf slicing."""
    flagged, corrected, scores = [], [], []
    for path, leaf in jax.tree_util.tree_flatten_with_path(stats_tree)[0]:
        key = None
        for entry in reversed(path):
            if isinstance(entry, jax.tree_util.DictKey):
                key = entry.key
                break
        if key == "flagged":
            flagged.append(jnp.sum(leaf))
        elif key == "corrected":
            corrected.append(jnp.sum(leaf))
        elif key == "score":
            scores.append(jnp.max(leaf))
    z = jnp.zeros((), jnp.float32)
    return {
        "ft_flagged": jnp.sum(jnp.stack(flagged)) if flagged else z,
        "ft_corrected": jnp.sum(jnp.stack(corrected)) if corrected else z,
        "ft_max_score": jnp.max(jnp.stack(scores)) if scores else z,
    }

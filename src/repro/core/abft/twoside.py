"""Two-sided ABFT: detect / locate / correct from checksum divergences.

Implements the paper's Figure 6 pipeline on *any* linear operator F (FFT here,
GEMM in ``gemm.py``), given the group checksums:

    cs2_in  = X e2 = sum_b x_b              (correction checksum)
    cs3_in  = X e3 = sum_b id_b * x_b       (location checksum)
    cs2_out = Y e2,  cs3_out = Y e3         (same over the computed outputs)

Under the SEU assumption (one corrupted signal y_s = y~_s + eps per detection
period), linearity gives

    F(cs2_in) - cs2_out = -eps                    -> correction value
    (F(cs3_in) - cs3_out) / (F(cs2_in) - cs2_out) = id_s  -> location

so the corrupted signal is repaired *without recomputation* — the delayed
batched correction that distinguishes two-sided from one-sided ABFT (Fig. 5).
"""
from __future__ import annotations

import dataclasses
from typing import Callable

import jax
import jax.numpy as jnp

from .encoding import EPS

__all__ = ["GroupChecksums", "Verdict", "detect_locate", "apply_correction"]


@dataclasses.dataclass
class GroupChecksums:
    """Complex (G, N) checksum arrays for G transaction groups."""

    cs2_in: jax.Array
    cs3_in: jax.Array
    cs2_out: jax.Array
    cs3_out: jax.Array

    @classmethod
    @jax.named_scope("abft.decode")
    def from_packed(cls, cs: jax.Array) -> "GroupChecksums":
        """From the kernel's packed (G, 8, N) float layout."""
        c = lambda j: cs[:, 2 * j] + 1j * cs[:, 2 * j + 1]
        return cls(cs2_in=c(0), cs3_in=c(1), cs2_out=c(2), cs3_out=c(3))


@dataclasses.dataclass
class Verdict:
    """Detection outcome per group."""

    error_score: jax.Array   # (G,) relative divergence of the e2 checksum
    flagged: jax.Array       # (G,) bool, error_score > threshold
    location: jax.Array      # (G,) int32 signal index: id_s - 1 (valid if flagged)
    correction: jax.Array    # (G, N) complex correction value (-eps)


@jax.named_scope("abft.decode")
def detect_locate(
    cs: GroupChecksums,
    forward: Callable[[jax.Array], jax.Array],
    threshold: float,
) -> Verdict:
    """Run detection + location on group checksums.

    ``forward`` is the protected linear operator applied to the (G, N) input
    checksums — one extra F per *group*, amortized over group_size signals
    (paper: "amortizing one ABFT checksum transaction along a batch").
    """
    d2 = forward(cs.cs2_in) - cs.cs2_out          # == -eps on the error
    d3 = forward(cs.cs3_in) - cs.cs3_out          # == -id_s * eps
    scale = jnp.sqrt(jnp.mean(jnp.abs(cs.cs2_out) ** 2, axis=-1)) + EPS
    score = jnp.sqrt(jnp.mean(jnp.abs(d2) ** 2, axis=-1)) / scale
    flagged = score > threshold
    # |d2|^2-weighted estimate of id_s = d3/d2 (robust to tiny elements)
    num = jnp.sum(d3 * jnp.conj(d2), axis=-1).real
    den = jnp.sum(jnp.abs(d2) ** 2, axis=-1) + EPS
    loc = jnp.round(num / den).astype(jnp.int32) - 1  # ids are 1-based
    return Verdict(error_score=score, flagged=flagged, location=loc,
                   correction=d2)


@jax.named_scope("abft.correct")
def apply_correction(y: jax.Array, verdict: Verdict) -> tuple[jax.Array, jax.Array]:
    """Add the correction value back onto the located signals (paper §4.1.2).

    y: (B, N) complex outputs; returns (corrected y, per-group applied mask).
    """
    b = y.shape[0]
    loc = jnp.clip(verdict.location, 0, b - 1)
    applied = verdict.flagged
    upd = jnp.where(applied[:, None], verdict.correction, 0.0)
    y = y.at[loc].add(upd.astype(y.dtype), mode="drop",
                      indices_are_sorted=False, unique_indices=False)
    return y, applied

"""Seeded traffic: random streams, open-loop arrival schedules, SEU schedules.

Every draw comes from ``--seed``, so the same seed gives the same inputs.
Each seed gives the same amount of work in another order: an open-loop
schedule has exactly ``round(rate * seconds)`` arrivals, spread as a Poisson
process conditioned on its count (sorted uniform times), and the request
kinds are dealt in equal shares before they are shuffled.
"""
from __future__ import annotations

import numpy as np


def rng(seed: int, stream: int = 0) -> np.random.Generator:
    """A NumPy generator for ``(seed, stream)``; any integer seed, negative
    or above 64 bits included."""
    return np.random.default_rng([int(seed) % (1 << 64), int(stream)])


def jax_key(seed: int, stream: int = 0):
    """A JAX PRNG key drawn from :func:`rng`."""
    import jax
    return jax.random.PRNGKey(int(rng(seed, stream).integers(0, 1 << 31)))


def open_loop(rate: float, seconds: float, kinds: int,
              gen: np.random.Generator) -> tuple[np.ndarray, np.ndarray]:
    """``(offsets_s, kind)`` of an open loop at ``rate`` requests per second
    over ``seconds``: sorted arrival offsets in ``[0, seconds)`` and each
    arrival's kind in ``range(kinds)``, in equal shares."""
    if rate <= 0 or seconds <= 0 or kinds < 1:
        raise ValueError(f"need rate > 0, seconds > 0, kinds >= 1; got "
                         f"{rate}, {seconds}, {kinds}")
    count = int(round(rate * seconds))
    offsets = np.sort(gen.uniform(0.0, seconds, size=count))
    kind = np.resize(np.arange(kinds), count)
    gen.shuffle(kind)
    return offsets, kind


def seu_descriptors(count: int, *, tiles: int, bs: int, n: int,
                    eps: tuple[float, float],
                    gen: np.random.Generator) -> np.ndarray:
    """``(count, 6)`` float32 fused-kernel SEU descriptors
    ``[tile, row, col, enable, eps_re, eps_im]``: uniform tile, row and
    column, and an error of magnitude uniform in ``eps`` at a uniform
    phase."""
    mag = gen.uniform(eps[0], eps[1], size=count)
    phase = gen.uniform(0.0, 2.0 * np.pi, size=count)
    return np.stack([gen.integers(0, tiles, size=count),
                     gen.integers(0, bs, size=count),
                     gen.integers(0, n, size=count),
                     np.ones(count),
                     mag * np.cos(phase), mag * np.sin(phase)],
                    axis=1).astype(np.float32)

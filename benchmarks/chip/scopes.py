"""Device time and host spans by program stage, for the per-layer readers.

The program names its stages. On the device path, ``jax.named_scope``:
``fft.split``, ``fft.join``, ``fft.layout``, ``fft.kernel``, ``fft.twiddle``,
``abft.decode`` and ``abft.correct``. On the serving workers,
``jax.profiler.TraceAnnotation`` spans: ``serve.execute`` and, inside it,
``serve.dispatch`` and ``serve.fetch``.

A trace's device ops carry only their instruction's name, so device time is
mapped to scopes through the compiled module of the executor the cell ran:
``FFTPlan.executor_hlo`` read by ``repro.analysis.hlo.op_scopes``. The
cell's plan is rebuilt from its files as ``drivers/batch.py`` builds it, so
the plan cache returns the same plan and the compile cache the same
executable. This runs after the window, in the traced run only.

Where the program has no such scopes or spans (before they were added),
these functions return None and the readers report nothing.
"""
from __future__ import annotations

import collections
import functools
import sys

import numpy as np


@functools.lru_cache(maxsize=4)
def _scope_map(b: int, n: int, dtype: str, natural_order: bool, chunks: int,
               ft: tuple | None):
    import jax
    import jax.numpy as jnp
    try:
        from repro.analysis.hlo import op_scopes
    except ImportError:
        return None
    from repro.core.fft.api import FFTSpec, FTConfig, plan

    spec = FFTSpec(shape=(b, n), dtype=dtype, natural_order=natural_order,
                   chunks=chunks,
                   ft=FTConfig(**dict(ft)) if ft is not None else None)
    p = plan(spec)
    if not hasattr(p, "executor_hlo"):
        return None
    x = jax.ShapeDtypeStruct((b, n), jnp.dtype(dtype))
    if ft is None:
        text = p.executor_hlo(x)
    else:   # the batch driver's SEU descriptors are (6,) float32
        text = p.executor_hlo(x, op="ft_fft", inject=jax.ShapeDtypeStruct(
            (6,), jnp.float32))
    return op_scopes(text) or None


def scope_map(run) -> dict | None:
    """``{instruction name: scope}`` of the one-chip batched cell's
    executor, or None."""
    cfg = run.ctx.config
    if run.ctx.chips != 1 or "kernels" not in cfg:
        return None
    ft = tuple(sorted(cfg["ft"].items())) if "ft" in cfg else None
    return _scope_map(int(run.record["b"]), int(run.record["n"]),
                      cfg["dtype"], cfg.get("natural_order", True),
                      cfg.get("chunks", 1), ft)


def scope_ns(run) -> collections.Counter | None:
    """Device ns of the window's ops on chip 0 by scope; ops that no scope
    covers count under None."""
    m = scope_map(run)
    if m is None:
        return None
    out = collections.Counter()
    for o in run.trace.ops_in(0):
        out[m.get(o.name)] += o.end - o.start
    return out


def scope_ms(run, *scopes) -> float | None:
    """Device ms per call of the ops under any of ``scopes``."""
    ns = scope_ns(run)
    if ns is None or not any(s in ns for s in scopes):
        return None
    return sum(ns[s] for s in scopes) / run.record["calls"] / 1e6


def report(run) -> None:
    """Print each scope's ms per call and the largest unscoped ops."""
    m, ns = scope_map(run), scope_ns(run)
    if ns is None:
        return
    calls = run.record["calls"]
    line = ", ".join(f"{s} {v / calls / 1e6:.4f}" for s, v in
                     sorted(ns.items(), key=lambda kv: -kv[1]) if s)
    rest = collections.Counter()
    for o in run.trace.ops_in(0):
        if o.name not in m:
            rest[o.label] += o.end - o.start
    top = ", ".join(f"{k} {v / calls / 1e6:.4f}"
                    for k, v in rest.most_common(5))
    print(f"scopes (ms a call): {line}; unscoped {ns[None] / calls / 1e6:.4f}"
          f"{': ' + top if top else ''}", file=sys.stderr)


def spans(run, name: str) -> list[tuple[int, int]]:
    """(start, end) ns of the host spans named ``name`` that start inside
    the window, end cut to the window's."""
    lo, hi = run.trace.span()
    return [(s, min(e, hi)) for n, s, e in run.trace.host
            if n == name and lo <= s < hi]


def median_span_ms(run, name: str) -> float | None:
    d = [e - s for s, e in spans(run, name)]
    return float(np.median(d)) / 1e6 if d else None

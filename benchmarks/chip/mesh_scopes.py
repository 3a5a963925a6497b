"""Device time by program stage in a batched cell on several chips.

``scopes.py`` reads the one-chip cells; this module reads a cell whose plan
is sharded over ``make_fft_mesh(chips)``: the pencil pipeline
(``core/fft/distributed.py``). It names its stages with
``jax.named_scope``: ``fft.local`` (the stage DFTs), ``fft.twiddle``,
``fft.exchange`` (the all-to-all) and ``fft.layout`` (the swaps and
reshapes, among them the ingest relayout of the contiguous blocks the
operand arrives in); the entry's complex-to-planes conversions count under
``fft.split``.

Every chip runs the same SPMD module, so one map serves all of them: it is
built from chip 0's executor, ``FFTPlan.executor_hlo`` read by
``repro.analysis.hlo.op_scopes``. The plan is rebuilt from the cell's files
as ``drivers/batch.py`` builds it (``make_fft_mesh(chips)``,
``natural_order`` and ``chunks`` from the configuration, the operand in
contiguous blocks over ``fft``), so the plan cache returns the same plan and
the compile cache the same executable. This runs after the window, in the
traced run only.

Where the program has no sharded ``executor_hlo`` (before it was added),
these functions return None and the readers report nothing.
"""
from __future__ import annotations

import collections
import functools
import sys

from benchmarks.chip.trace import is_collective


@functools.lru_cache(maxsize=4)
def _scope_map(chips: int, b: int, n: int, dtype: str, natural_order: bool,
               chunks: int):
    import jax
    import jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec
    from repro.analysis.hlo import op_scopes
    from repro.core.fft.api import FFTSpec, plan
    from repro.launch.mesh import make_fft_mesh

    mesh = make_fft_mesh(chips)
    p = plan(FFTSpec(shape=(b, n), dtype=dtype, mesh=mesh,
                     natural_order=natural_order, chunks=chunks))
    x = jax.ShapeDtypeStruct((b, n), jnp.dtype(dtype),
                             sharding=NamedSharding(mesh,
                                                    PartitionSpec(None, "fft")))
    try:
        text = p.executor_hlo(x)
    except ValueError:      # a program whose executor_hlo is local only
        return None
    return op_scopes(text) or None


def scope_map(run) -> dict | None:
    """``{instruction name: scope}`` of the sharded batched cell's plain
    executor, or None (one chip, ABFT, or no sharded ``executor_hlo``)."""
    cfg = run.ctx.config
    if run.ctx.chips == 1 or "ft" in cfg:
        return None
    return _scope_map(run.ctx.chips, int(run.record["b"]),
                      int(run.record["n"]), cfg["dtype"],
                      cfg.get("natural_order", True), cfg.get("chunks", 1))


def scope_ns(run, chip: int) -> collections.Counter | None:
    """Device ns of the window's ops on ``chip`` by scope; ops that no scope
    covers count under None."""
    m = scope_map(run)
    if m is None:
        return None
    out = collections.Counter()
    for o in run.trace.ops_in(chip):
        out[m.get(o.name)] += o.end - o.start
    return out


def scope_ms(run, scope: str) -> float | None:
    """Device ms per call of the ops under ``scope``, averaged over
    chips."""
    per_chip = [scope_ns(run, c) for c in range(len(run.trace.ops))]
    if per_chip[0] is None or not any(scope in ns for ns in per_chip):
        return None
    return (sum(ns[scope] for ns in per_chip) / len(per_chip)
            / run.record["calls"] / 1e6)


def report(run) -> None:
    """Print chip 0's ms per call by scope, its largest unscoped ops, and
    its collectives a call by kind and scope."""
    m, ns = scope_map(run), scope_ns(run, 0)
    if ns is None:
        return
    calls = run.record["calls"]
    line = ", ".join(f"{s} {v / calls / 1e6:.4f}" for s, v in
                     sorted(ns.items(), key=lambda kv: -kv[1]) if s)
    rest, coll = collections.Counter(), collections.Counter()
    for o in run.trace.ops_in(0):
        if o.name not in m:
            rest[o.label] += o.end - o.start
        if is_collective(o):
            coll[f"{o.opcode} under {m.get(o.name)}"] += 1
    top = ", ".join(f"{k} {v / calls / 1e6:.4f}"
                    for k, v in rest.most_common(5))
    kinds = ", ".join(f"{k} {v / calls:g}" for k, v in sorted(coll.items()))
    print(f"mesh scopes, chip 0 (ms a call): {line}; unscoped "
          f"{ns[None] / calls / 1e6:.4f}{': ' + top if top else ''}; "
          f"collectives a call: {kinds or 'none'}", file=sys.stderr)

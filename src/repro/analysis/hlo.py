"""Collective-op parser for post-partitioning HLO text.

Promoted from ``launch/dryrun.collective_bytes`` so the plan auditor, the
dry-run pipeline, and the distributed-FFT benchmark all read one parser.
This module imports nothing but the stdlib — in particular no ``jax`` —
because ``launch/dryrun`` mutates ``XLA_FLAGS`` at import time and the
auditor must be importable before jax picks a platform.

The module under inspection is the per-device program (lowered with a
``jax.sharding.Mesh``), so every byte count here is per-device wire
traffic. Async ``-start`` ops return ``(operand buffers..., result
buffers...)`` tuples; only the result half is transferred, so those are
deduped. All-reduce wire bytes carry the ring factor 2 (reduce-scatter +
all-gather phases); every other kind moves its payload once.
"""
from __future__ import annotations

import dataclasses
import re

__all__ = ["CollectiveOp", "COLLECTIVE_KINDS", "COMPLEX_CONVERSIONS",
           "DTYPE_BYTES", "PROGRAM_SCOPES", "WIRE_FACTOR", "op_scopes",
           "parse_collectives", "summarize", "root_signature"]

COLLECTIVE_KINDS = ("all-reduce", "all-gather", "reduce-scatter",
                    "all-to-all", "collective-permute")

COLLECTIVE_RE = re.compile(
    r"=\s*(?:\(.*?\)|[a-z0-9\[\]{},\s/]*?)\s*"
    r"(all-reduce|all-gather|reduce-scatter|all-to-all|collective-permute)"
    r"(-start)?\(")
SHAPE_RE = re.compile(r"(f64|f32|bf16|f16|s32|u32|s8|u8|pred|c64|c128)"
                      r"\[([0-9,]*)\]")
DTYPE_BYTES = {"f64": 8, "f32": 4, "bf16": 2, "f16": 2, "s32": 4, "u32": 4,
               "s8": 1, "u8": 1, "pred": 1, "c64": 8, "c128": 16}
# wire-bytes multiplier per collective kind (ring algorithms)
WIRE_FACTOR = {"all-reduce": 2.0, "all-gather": 1.0, "reduce-scatter": 1.0,
               "all-to-all": 1.0, "collective-permute": 1.0}

# the prefixes of the program's ``jax.named_scope`` names (kernels/ops.py,
# kernels/stockham*.py, core/abft/twoside.py)
PROGRAM_SCOPES = ("fft.", "abft.")
# The TPU compiler carries a 64-bit value as two 32-bit planes and lowers
# real/imag of a complex value to the custom calls X64SplitLow/High and
# complex() to X64Combine. Their metadata is not always their own: a split
# carries that of the value's producer (for an entry parameter, its name),
# and a combine carries the complex op's only where that op was the result,
# else none. So a conversion between a complex value and its planes whose
# metadata has no program scope, or (a split) names its producer's, takes
# the scope of the conversion it implements; one with a scope of its own
# keeps it. A split of a value made inside another scope therefore counts
# under fft.split. In the v5e compile of the 2^10 C2C and fused-ABFT
# executors the only such conversions are the splits of the entry parameter
# (op_name "x") and the final combine (no metadata).
COMPLEX_CONVERSIONS = {"X64SplitLow": "fft.split",
                       "X64SplitHigh": "fft.split",
                       "X64Combine": "fft.join"}

# [ROOT] %name = <result type> opcode(%operand, ...), attr=..., ...
INSTR_RE = re.compile(r"^\s*(?:ROOT\s+)?%?([\w\-\.]+)\s*=\s*(.*?)\s"
                      r"([a-z][\w\-]*)\((.*)$")
OP_NAME_RE = re.compile(r'op_name="([^"]*)"')
TARGET_RE = re.compile(r'custom_call_target="([^"]+)"')
OPERAND_RE = re.compile(r"%([\w\-\.]+)")
COMPLEX_RE = re.compile(r"^\(?c(?:64|128)\[")

# ENTRY %main.123 (arg0: c64[8,256]) -> (c64[8,256], f32[3]) {
ENTRY_RE = re.compile(r"^ENTRY\s+\S+\s*\(.*\)\s*->\s*(.*?)\s*\{?\s*$")


@dataclasses.dataclass(frozen=True)
class CollectiveOp:
    """One collective in program order.

    ``shapes`` is the result buffer list as ``(dtype, elems)`` pairs
    (post ``-start`` dedupe), ``payload_bytes`` their byte total, and
    ``wire_bytes`` the per-device wire traffic (payload x ring factor).
    """

    kind: str
    is_async: bool
    shapes: tuple[tuple[str, int], ...]
    payload_bytes: int
    wire_bytes: float

    @property
    def dtypes(self) -> tuple[str, ...]:
        return tuple(dt for dt, _ in self.shapes)

    @property
    def elems(self) -> int:
        return sum(n for _, n in self.shapes)


def _result_shapes(line: str, op: str, *, is_start: bool):
    # result type sits between ' = ' and the op name:
    #   %x = f32[64,128]{1,0} all-reduce(...)
    #   %y = (f32[8]{0}, f32[8]{0}) all-gather-start(...)
    # Async ``-start`` results are (operand buffers..., result buffers...)
    # tuples — the operand aliases duplicate the payload, so only the result
    # half of the tuple is transferred. Sync decomposed all-to-alls also
    # return tuples, but there every element IS payload: no dedupe.
    seg = line.split(" = ", 1)[1] if " = " in line else line
    seg = seg.split(op, 1)[0]
    shapes = []
    for m in SHAPE_RE.finditer(seg):
        dt, dims = m.group(1), m.group(2)
        n = 1
        if dims:
            for d in dims.split(","):
                n *= int(d)
        shapes.append((dt, n))
    if is_start and len(shapes) > 1:
        shapes = shapes[len(shapes) // 2:]
    return tuple(shapes)


def parse_collectives(hlo_text: str) -> list[CollectiveOp]:
    """All collectives in ``hlo_text``, program order, as structured
    records — kind, async-ness, per-buffer (dtype, elems), payload and
    wire bytes. This is the one classification point; every summary view
    (:func:`summarize`, ``launch.dryrun.collective_bytes``) derives from
    it."""
    ops = []
    for line in hlo_text.splitlines():
        m = COLLECTIVE_RE.search(line)
        if not m:
            continue
        kind = m.group(1)
        is_async = m.group(2) is not None
        shapes = _result_shapes(line, kind, is_start=is_async)
        payload = sum(n * DTYPE_BYTES[dt] for dt, n in shapes)
        ops.append(CollectiveOp(kind=kind, is_async=is_async, shapes=shapes,
                                payload_bytes=payload,
                                wire_bytes=payload * WIRE_FACTOR[kind]))
    return ops


def summarize(ops: list[CollectiveOp]) -> dict:
    """The legacy ``collective_bytes`` dict view of structured records:
    per-kind wire ``bytes`` / ``count``, program-order ``(kind, wire)``
    ``ops`` pairs, and the scalar ``total_bytes``."""
    out = {k: 0.0 for k in WIRE_FACTOR}
    count = {k: 0 for k in WIRE_FACTOR}
    pairs = []
    for op in ops:
        out[op.kind] += op.wire_bytes
        count[op.kind] += 1
        pairs.append((op.kind, op.wire_bytes))
    return {"bytes": out, "count": count, "ops": pairs,
            "total_bytes": float(sum(out.values()))}


def root_signature(hlo_text: str) -> tuple[str, ...]:
    """Dtype tokens of the ENTRY computation's result, in order.

    ``ENTRY %main (...) -> (c64[8,256], f32[3])`` yields ``("c64", "f32")``.
    Used by the auditor's downcast check: a ``complex128`` spec whose root
    signature carries ``c64`` buffers silently lost half its mantissa.
    Returns ``()`` when no ENTRY line parses (caller should not fail)."""
    for line in hlo_text.splitlines():
        m = ENTRY_RE.match(line.strip())
        if m:
            return tuple(mm.group(1) for mm in SHAPE_RE.finditer(m.group(1)))
    return ()


def op_scopes(text: str, prefixes=PROGRAM_SCOPES) -> dict[str, str]:
    """``{instruction name: program scope}`` for a compiled module's text
    (``jax.stages.Compiled.as_text()``).

    An instruction's scope is the innermost element of its metadata's
    ``op_name`` path (``jit(f)/jit(g)/fft.layout/reshape``) that starts with
    one of ``prefixes``. A fusion carries its root's metadata, so its whole
    time counts under the root's scope; so does a copy that layout
    assignment put in for an op's result. A complex <-> planes conversion
    whose metadata is not its own takes the scope of what it implements
    (see :data:`COMPLEX_CONVERSIONS`). Instructions with no scope
    (parameters, compiler-inserted transfers with no metadata) are left
    out. The names are those a profile's device ops carry, so the map reads
    an XProf trace of this executable by program stage.
    """
    rows = {}     # name -> (result type, rest of line, own scope, operand)
    for line in text.splitlines():
        m = INSTR_RE.match(line)
        if not m:
            continue
        name, rtype, _, rest = m.groups()
        meta = OP_NAME_RE.search(rest)
        scoped = [e for e in meta.group(1).split("/")
                  if e.startswith(prefixes)] if meta else []
        operand = OPERAND_RE.search(rest)
        rows[name] = (rtype, rest, scoped[-1] if scoped else None,
                      operand.group(1) if operand else None)
    out = {}
    for name, (rtype, rest, scope, operand) in rows.items():
        t = TARGET_RE.search(rest)
        conversion = COMPLEX_CONVERSIONS.get(t.group(1)) if t else None
        if conversion == "fft.join":
            if scope is None and COMPLEX_RE.match(rtype):
                scope = conversion
        elif conversion == "fft.split":
            src = rows.get(operand)
            if src is not None and COMPLEX_RE.match(src[0]) \
                    and scope in (None, src[2]):
                scope = conversion
        if scope is not None:
            out[name] = scope
    return out

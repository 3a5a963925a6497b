"""The program's stage scopes in compiled HLO: ``analysis.hlo.op_scopes``
on hand-written instruction lines, and on the modules that
``FFTPlan.executor_hlo`` compiles for a plain and an ABFT plan (CPU)."""
from __future__ import annotations

import collections

import jax
import jax.numpy as jnp
import pytest

from repro.analysis import hlo
from repro.core.fft import api

META = 'metadata={{op_name="{}" stack_frame_id=3}}'


def line(name, rest, op_name=None):
    meta = f", {META.format(op_name)}" if op_name is not None else ""
    return f"  {name} = {rest}{meta}"


@pytest.mark.parametrize("text,want", [
    # nested jit paths: the innermost element with a program prefix wins
    (line("%reshape.0", "f32[4096,128]{1,0} reshape(%custom-call.1)",
          "jit(_fft_impl)/jit(block_fft_pallas)/fft.layout/reshape"),
     {"reshape.0": "fft.layout"}),
    (line("%fusion.3", "f32[8]{0} fusion(%a), kind=kLoop, "
          "calls=%fused_computation.3",
          "jit(f)/abft.decode/jit(g)/abft.correct/select_n"),
     {"fusion.3": "abft.correct"}),
    # a fusion carries its root's metadata, and a ROOT line parses too
    (line("ROOT %copy_bitcast_fusion", "c64[64,256]{1,0} fusion(%a, %b), "
          "kind=kLoop, calls=%fused_computation",
          "jit(_fft_impl)/fft.join/complex"),
     {"copy_bitcast_fusion": "fft.join"}),
    # no metadata, or metadata outside the program's scopes: not mapped
    (line("%copy-start", "(f32[128,128]{1,0}, u32[]) copy-start("
          "%constant.5)"), {}),
    (line("%constant.5", "f32[128,128]{1,0} constant({...})",
          "jit(_fft_impl)/jit(block_fft_pallas)"), {}),
    # the TPU's complex <-> planes conversions whose metadata is not their
    # own take the scope of what they implement; a 64-bit integer's do not
    ("\n".join([
        line("%x.1", "c64[8,256]{1,0} parameter(0)", "x"),
        line("%custom-call.1", "f32[8,256]{1,0} custom-call(%x.1), "
             'custom_call_target="X64SplitLow"', "x"),
        line("%custom-call", "f32[8,256]{1,0} custom-call(%x.1), "
             'custom_call_target="X64SplitHigh"', "x"),
        line("%custom-call.2", "c64[8,256]{1,0} custom-call(%a, %b), "
             'custom_call_target="X64Combine"'),
        line("%custom-call.3", "s64[]{:T(128)} custom-call(%c, %d), "
             'custom_call_target="X64Combine"')]),
     {"custom-call.1": "fft.split", "custom-call": "fft.split",
      "custom-call.2": "fft.join"}),
    # a split that inherits its producer's scope implements fft.split
    ("\n".join([
        line("%multiply.2", "c64[8,256]{1,0} multiply(%a, %b)",
             "jit(f)/fft.twiddle/mul"),
        line("%custom-call.4", "f32[8,256]{1,0} custom-call(%multiply.2), "
             'custom_call_target="X64SplitLow"', "jit(f)/fft.twiddle/mul")]),
     {"multiply.2": "fft.twiddle", "custom-call.4": "fft.split"}),
    # a conversion with a program scope of its own keeps it
    ("\n".join([
        line("%multiply.2", "c64[8,256]{1,0} multiply(%a, %b)",
             "jit(f)/fft.twiddle/mul"),
        line("%custom-call.4", "f32[8,256]{1,0} custom-call(%multiply.2), "
             'custom_call_target="X64SplitLow"', "jit(f)/abft.decode/real"),
        line("%custom-call.5", "c64[8]{0} custom-call(%c, %d), "
             'custom_call_target="X64Combine"', "jit(f)/abft.decode/complex")]),
     {"multiply.2": "fft.twiddle", "custom-call.4": "abft.decode",
      "custom-call.5": "abft.decode"}),
], ids=["nested-jit", "innermost-scope", "fusion-root", "no-metadata",
        "no-program-scope", "complex-conversions", "split-inherits-producer",
        "conversion-own-scope"])
def test_op_scopes_lines(text, want):
    assert hlo.op_scopes(text) == want


def test_op_scopes_prefixes():
    text = line("%mul.1", "f32[8]{0} multiply(%a, %b)", "jit(f)/mine/mul")
    assert hlo.op_scopes(text) == {}
    assert hlo.op_scopes(text, prefixes=("mine",)) == {"mul.1": "mine"}


# a batch that is not a multiple of the tile (32 at n = 256) pads, so the
# padding's relayout and the result's slice show under fft.layout
SHAPE = (48, 256)
X = jax.ShapeDtypeStruct(SHAPE, jnp.complex64)
INJECT = jax.ShapeDtypeStruct((6,), jnp.float32)


def executor_scopes(ft: bool) -> dict:
    spec = api.FFTSpec(shape=SHAPE, dtype="complex64",
                       ft=api.FTConfig() if ft else None)
    p = api.plan(spec)
    if ft:
        return hlo.op_scopes(p.executor_hlo(X, op="ft_fft", inject=INJECT))
    return hlo.op_scopes(p.executor_hlo(X))


def test_executor_hlo_maps_every_stage():
    c2c, ft = executor_scopes(False), executor_scopes(True)
    assert {"fft.split", "fft.join", "fft.layout", "fft.kernel"} \
        <= set(c2c.values())
    assert {"fft.split", "fft.join", "fft.layout", "fft.kernel",
            "abft.decode", "abft.correct"} <= set(ft.values())
    assert "abft.decode" not in c2c.values()
    # the same executor compiles to the same instruction names
    assert executor_scopes(False) == c2c
    assert executor_scopes(True) == ft
    assert collections.Counter(ft.values())["abft.correct"] >= 1


def test_executor_hlo_refuses_other_executors():
    p = api.plan(api.FFTSpec(shape=SHAPE, dtype="complex64"))
    with pytest.raises(ValueError, match="op must be"):
        p.executor_hlo(X, op="ifft")
    with pytest.raises(ValueError, match="FTConfig"):
        p.executor_hlo(X, op="ft_fft")
    real = api.plan(api.FFTSpec(shape=SHAPE, dtype="complex64", real=True))
    with pytest.raises(ValueError, match="local rank-1 C2C"):
        real.executor_hlo(jax.ShapeDtypeStruct(SHAPE, jnp.float32))

"""Compile the Pallas kernels for a described TPU v5e, without running them.

The TPU compiler is installed even where no chip is attached: it compiles
for a topology that is only described. That catches what interpret mode
cannot — Mosaic layout refusals, 64-bit index types, VMEM over the scoped
limit — at the sizes the chip path runs. Each test asserts that the
compiled program holds a Mosaic kernel (``tpu_custom_call``), i.e. that
nothing fell back to interpret mode.

The topology is described inside a module-scoped fixture, never at import:
only one process at a time may load the TPU library, and every test worker
imports this file.
"""
import re

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import SingleDeviceSharding

from repro.analysis import hlo
from repro.core.fft import api
from repro.core.fft.plan import make_plan
from repro.kernels import ops
from repro.kernels.ft_matmul import _ft_matmul_pallas
from repro.kernels.stockham import block_fft_pallas
from repro.kernels.stockham_abft import abft_fft_pallas


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    # a compile for a described chip can be written to the persistent
    # cache but never read back without one: keep the cache out of it
    from jax.experimental.compilation_cache import compilation_cache
    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", prev)


@pytest.fixture(scope="module")
def four_chips(topo, one_chip):
    """The described v5e:2x2 as the pencil's ``fft`` mesh (the compile
    cache stays off, as for ``one_chip``)."""
    from repro.launch.mesh import make_mesh
    return make_mesh((4,), ("fft",), devices=topo.devices)


def _sds(shape, dtype, sharding):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _assert_kernel(compiled):
    assert "tpu_custom_call" in compiled.as_text()


def _kernel_operands(text):
    """The operand shapes of the program's one Mosaic kernel."""
    calls = [line for line in text.splitlines()
             if 'custom_call_target="tpu_custom_call"' in line]
    assert len(calls) == 1
    layouts = re.search(r"operand_layout_constraints=\{(.*?\})\}", calls[0])
    return re.findall(r"\w+\[[\d,]*\]", layouts.group(1))


# B * N = 2^26, the batch cells' operand: the natural path up to N = 2048
# (R = N / 128 <= 16), the blocks path above
KERNEL_SIZES = [(256, "natural"), (512, "natural"), (1024, "natural"),
                (2048, "natural"), (4096, "blocks"), (8192, "blocks")]


@pytest.mark.parametrize("n,layout", KERNEL_SIZES)
def test_block_fft_compiles(n, layout, one_chip):
    batch = (1 << 26) // n
    plan = make_plan(n, batch=batch)
    assert plan.io_layout == layout and f"io={layout}" in plan.describe()
    x = _sds((batch, n), jnp.float32, one_chip)
    compiled = block_fft_pallas.lower(x, x, bs=plan.bs,
                                      interpret=False).compile()
    _assert_kernel(compiled)
    # two planes, then the tables: the natural path's sublane stage is
    # butterflies and takes no K table
    operands = _kernel_operands(compiled.as_text())
    assert len(operands) == 2 + (4 if layout == "natural" else 6)


@pytest.mark.parametrize("n,batch,factors", [
    (1 << 17, 1 << 9, (512, 256)),
    (1 << 23, 8, (256, 256, 128)),
])
def test_multipass_fft_compiles(n, batch, factors, one_chip):
    assert make_plan(n, batch=batch).kernel_factors == factors
    x = _sds((batch, n), jnp.complex64, one_chip)
    compiled = ops._fft_impl.lower(x, inverse=False,
                                   interpret=False).compile()
    _assert_kernel(compiled)


@pytest.mark.parametrize("n,layout", KERNEL_SIZES)
@pytest.mark.parametrize("per_signal", [False, True])
def test_abft_fft_compiles(per_signal, n, layout, one_chip):
    batch = (1 << 26) // n
    assert make_plan(n, batch=batch).io_layout == layout
    x = _sds((batch, n), jnp.float32, one_chip)
    inj = _sds((6,), jnp.float32, one_chip)
    compiled = abft_fft_pallas.lower(
        x, x, transactions=4, per_signal=per_signal, interpret=False,
        inject=inj).compile()
    _assert_kernel(compiled)


def _operand_sized_ops(text, shape):
    """``(opcode, custom-call target, scope)`` of each ENTRY instruction
    whose result holds an array of ``shape``, leaving out those that only
    name a buffer (parameters, tuples, their elements, bitcasts)."""
    scopes = hlo.op_scopes(text)
    dims = "[" + ",".join(map(str, shape)) + "]"
    out = []
    for line in text[text.index("\nENTRY"):].splitlines()[1:]:
        m = hlo.INSTR_RE.match(line)
        if not m or dims not in m.group(2):
            continue
        name, _, opcode, rest = m.groups()
        if opcode in ("parameter", "tuple", "get-tuple-element", "bitcast"):
            continue
        target = hlo.TARGET_RE.search(rest)
        out.append((opcode, target.group(1) if target else None,
                    scopes.get(name)))
    return out


@pytest.mark.parametrize("ft", [False, True], ids=["c2c", "abft"])
def test_executor_moves_no_operand_sized_layout(ft, one_chip):
    """The batch cells' executors at (65536, 1024): the kernel reads and
    writes the natural planes, so between the complex operand and the
    complex result there are only the two splits, the kernel and the join
    (and, under ABFT, the correction's in-place scatter of flagged rows)."""
    shape = (1 << 16, 1 << 10)
    spec = api.FFTSpec(shape=shape, dtype="complex64", interpret=False,
                       ft=api.FTConfig() if ft else None)
    x = _sds(shape, jnp.complex64, one_chip)
    text = (api.plan(spec).executor_hlo(
        x, op="ft_fft", inject=_sds((6,), jnp.float32, one_chip)) if ft
        else api.plan(spec).executor_hlo(x))
    ops_ = _operand_sized_ops(text, shape)
    assert not [o for o in ops_ if o[0] in ("copy", "reshape", "transpose")]
    assert "fft.layout" not in {scope for *_, scope in ops_}
    core = sorted((t, s) for op, t, s in ops_ if op == "custom-call")
    assert core == sorted([("X64SplitLow", "fft.split"),
                           ("X64SplitHigh", "fft.split"),
                           ("tpu_custom_call", "fft.kernel"),
                           ("X64Combine", "fft.join")])
    rest = [o for o in ops_ if o[0] != "custom-call"]
    if ft:
        assert {s for *_, s in rest} <= {"abft.correct"}
    else:
        assert rest == []


def test_pencil_stage_dfts_are_kernel_passes(four_chips):
    """The four-chip pencil's per-device module at (4, 2^23), transposed
    order: n1 = 256, and pass 2 over 32768 = 256 x 128 takes two kernel
    passes. The stage DFTs are three Mosaic kernels on the planes, and the
    planes stay planes between them: four all-to-alls, one a float plane
    (the ingest relayout's and the exchange's), one split to planes at
    entry and one combine at the output."""
    from collections import Counter

    from jax.sharding import NamedSharding, PartitionSpec

    from repro.core.fft.distributed import _dist_fft_fn, make_dist_plan

    b, n = 4, 1 << 23
    p = make_dist_plan(n, 4)
    assert (p.n1, make_plan(p.n2).kernel_factors) == (256, (256, 128))
    x = _sds((b, n), jnp.complex64,
             NamedSharding(four_chips, PartitionSpec(None, "fft")))
    text = _dist_fft_fn(four_chips, "fft", False, False, None, 1,
                        False).lower(x).compile().as_text()
    scopes = hlo.op_scopes(text)
    ops_ = []
    for line in text.splitlines():
        m = hlo.INSTR_RE.match(line)
        if m:
            target = hlo.TARGET_RE.search(m.group(4))
            ops_.append((m.group(3), target.group(1) if target else None,
                         scopes.get(m.group(1))))
    assert [s for _, t, s in ops_ if t == "tpu_custom_call"] \
        == ["fft.kernel"] * 3
    assert Counter(s for op, _, s in ops_ if op == "all-to-all") \
        == {"fft.layout": 2, "fft.exchange": 2}
    assert Counter(t for _, t, _ in ops_ if t and t.startswith("X64")) \
        == {"X64SplitLow": 1, "X64SplitHigh": 1, "X64Combine": 1}
    # pass 2's swaps around its first kernel pass and its output swap
    assert Counter(s for op, _, s in ops_ if op == "copy")["fft.local"] >= 3


def test_ft_matmul_compiles(one_chip):
    m, k, n = 512, 4096, 512
    x = _sds((m, k), jnp.float32, one_chip)
    w = _sds((k, n), jnp.float32, one_chip)
    inj = _sds((1, 4), jnp.float32, one_chip)
    compiled = _ft_matmul_pallas.lower(x, w, inj, bm=128, bn=128, bk=128,
                                       interpret=False).compile()
    _assert_kernel(compiled)
    assert np.dtype(jnp.float32) == compiled.out_info.c.dtype

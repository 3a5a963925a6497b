"""FFT plans — the TPU analogue of the paper's template-based codegen.

The paper generates CUDA kernels from 7 parameters ``(N1, N2, N3, n1, n2, n3,
bs)``: the kernel-level cube (how many global-memory round trips) and the
threadblock-level cube (what fits in shared memory), plus the per-thread batch.

On TPU the same decisions are:

* ``kernel_factors`` — split N into 1-3 factors; each factor is one HBM round
  trip (a batched *block FFT* along that axis + twiddle + transpose), mirroring
  the paper's 1/2/3-kernel-launch regimes,
* ``block_radices``  — the mixed-radix decomposition of each factor executed
  entirely in VMEM. Radix choice is MXU-driven: prefer 128 (fills the systolic
  contraction dim), fall back to 64/32/16/8 (paper: registers prefer radix
  8/16; systolic arrays prefer 128),
* ``bs`` — signals per block (grid tile), sized so the VMEM working set
  (x, y, twiddles, checksum scratch) stays under a budget.

Plans are semi-empirical and overridable — ``Plan`` is a plain dataclass the
user can construct by hand, exactly like the paper's manual parameter search.
"""
from __future__ import annotations

import dataclasses
import functools
import math
from typing import Sequence

__all__ = ["Plan", "StagePlan", "make_plan", "block_radices", "io_layout",
           "PLAN_TABLE"]

# VMEM budget for a kernel's pipelined blocks (bytes). A v5e core has
# 128 MiB of VMEM (the allocation limit the TPU compiler reports for a
# described v5e), but a Pallas kernel is held to the compiler's default
# *scoped* limit of 16 MiB. Half of that goes to the blocks, leaving the
# rest for the chunk temporaries, constant tables and checksum scratch.
VMEM_BUDGET = 8 * 1024 * 1024

# TPU vreg lane width: the kernels' minor dimension (see kernels/stockham.py)
LANES = 128

# Largest signal length whose kernel reads and writes the natural (B, N)
# planes (R = N / 128 rows of a signal per 128-row chunk, R <= 16: a chunk
# then holds 128 / R >= 8 signals, a whole sublane tile of each).
MAX_NATURAL_N = 16 * LANES

# Largest signal length executed in a single VMEM-resident block FFT.
MAX_BLOCK_N = 1 << 13  # 8192 complex64 = 64 KiB per signal

# MXU-preferred radices, best first (paper: thread radix 2..32; TPU: 128).
_RADICES = (128, 64, 32, 16, 8, 4, 2)


@dataclasses.dataclass(frozen=True)
class StagePlan:
    """One VMEM-resident Stockham stage: contract with W_r and twiddle."""

    radix: int
    m: int  # remaining length after this stage: stage maps (r, m) -> (r, m)

    @property
    def n(self) -> int:
        return self.radix * self.m


@dataclasses.dataclass(frozen=True)
class Plan:
    """Full plan for an N-point batched FFT.

    ``kernel_factors``: product == N; one entry per HBM pass (paper's
    N1, N2, N3). ``stages[i]`` are the in-VMEM radix stages for factor i.
    ``bs`` is the number of signals per grid tile for the Pallas kernel.
    """

    n: int
    kernel_factors: tuple[int, ...]
    stages: tuple[tuple[StagePlan, ...], ...]
    bs: int
    inverse: bool = False

    @property
    def num_passes(self) -> int:
        return len(self.kernel_factors)

    @property
    def io_layout(self) -> str:
        """How the block kernel of every pass reads and writes HBM."""
        return io_layout(max(self.kernel_factors), self.bs)

    def describe(self) -> str:
        facs = "x".join(str(f) for f in self.kernel_factors)
        rads = ";".join(
            "*".join(str(s.radix) for s in st) for st in self.stages
        )
        return (f"Plan(N={self.n}={facs}, radices=[{rads}], bs={self.bs}, "
                f"io={self.io_layout})")


def io_layout(n: int, bs: int) -> str:
    """The block kernel's HBM layout for length-n signals in tiles of bs.

    ``"natural"``: the kernel reads and writes the ``(B, n)`` planes and
    permutes in VMEM; it needs whole sublane tiles of signals (bs a multiple
    of 8) and n <= MAX_NATURAL_N. ``"blocks"``: the planes are relaid out to
    ``(B * R, 128)`` rows in HBM around the kernel.
    """
    return "natural" if n <= MAX_NATURAL_N and bs % 8 == 0 else "blocks"


def block_radices(n: int) -> tuple[int, ...]:
    """Greedy mixed-radix decomposition of a power-of-two n, MXU-first."""
    if n & (n - 1):
        raise ValueError(f"only power-of-two sizes supported, got {n}")
    out: list[int] = []
    rem = n
    while rem > 1:
        for r in _RADICES:
            if rem % r == 0:
                # Avoid leaving a trailing factor smaller than 8 when we can
                # balance (e.g. 256 -> 16*16 rather than 128*2).
                q = rem // r
                if q == 1 or q >= 8 or q in (2, 4) and r <= 32:
                    out.append(r)
                    rem = q
                    break
        else:  # pragma: no cover - unreachable for powers of two
            raise AssertionError(n)
    # rebalance a trailing tiny radix (…,128,2) -> (…,64,4) style fixups
    while len(out) >= 2 and out[-1] < 8 and out[-2] > 8:
        out[-2] //= 2
        out[-1] *= 2
        out.sort(reverse=True)
    return tuple(out)


def _stage_plans(n: int) -> tuple[StagePlan, ...]:
    rads = block_radices(n)
    stages = []
    m = n
    for r in rads:
        m //= r
        stages.append(StagePlan(radix=r, m=m))
    return tuple(stages)


def _split_kernel_factors(n: int) -> tuple[int, ...]:
    """Split N into <=3 balanced factors (paper's 1/2/3-launch regimes).

    Regime boundaries follow the paper (§3.3.2): one pass for N <= 2^13, two
    passes for 2^14..2^22, three passes for 2^23..2^29. E.g. 2^23 ->
    (2^8, 2^8, 2^7), matching Table 1's (N1, N2, N3) = (2^8, 2^7, 2^8).
    """
    if n <= MAX_BLOCK_N:
        return (n,)
    log = n.bit_length() - 1
    if log <= 22:  # two passes, balanced
        l1 = (log + 1) // 2
        return (1 << l1, 1 << (log - l1))
    l1 = (log + 2) // 3
    l2 = (log - l1 + 1) // 2
    return (1 << l1, 1 << l2, 1 << (log - l1 - l2))


def _pick_bs(n_block: int, batch: int, itemsize: int) -> int:
    """Signals per grid tile: fill the VMEM budget, stay sublane-aligned."""
    # Per signal, the pipeline holds re/im x in/out blocks, each double-
    # buffered; a signal shorter than the lane width still fills 128 lanes.
    per_signal = 2 * 2 * 2 * itemsize * max(n_block, LANES)
    bs = max(1, VMEM_BUDGET // max(per_signal, 1))
    # sublane alignment: a multiple of 8 signals, a batch below 8 padded up
    if bs >= 8:
        bs = (bs // 8) * 8
    bs = min(bs, max(8, batch))
    # keep power-of-two-ish to divide batches evenly
    return 1 << (bs.bit_length() - 1) if bs > 0 else 1


@functools.lru_cache(maxsize=None)
def make_plan(
    n: int,
    batch: int = 1,
    itemsize: int = 4,
    *,
    inverse: bool = False,
    max_block_n: int = MAX_BLOCK_N,
) -> Plan:
    """Build the semi-empirical plan for an (batch, n) FFT workload."""
    if n <= 0 or n & (n - 1):
        raise ValueError(f"N must be a power of two, got {n}")
    factors = _split_kernel_factors(n) if n > max_block_n else (n,)
    stages = tuple(_stage_plans(f) for f in factors)
    bs = _pick_bs(max(factors), batch, itemsize)
    return Plan(n=n, kernel_factors=factors, stages=stages, bs=bs,
                inverse=inverse)


# The paper's Table 1 analogue: plans for representative sizes (T4 table shows
# N=2^10 -> 1 kernel, 2^17 -> 2 kernels, 2^23 -> 3 kernels). Our MAX_BLOCK_N
# (8192 = 2^13) reproduces the same 1/2/3-pass regime boundaries.
PLAN_TABLE = {
    1 << 10: make_plan(1 << 10, batch=1024),
    1 << 17: make_plan(1 << 17, batch=64),
    1 << 23: make_plan(1 << 23, batch=4),
}

"""Plan auditor: lower every reachable plan executor to HLO and diff the
parsed collectives against the analytic volume models — no data executed.

The paper's contracts (one all-to-all per 1-D transform, two for the
spectral round trip, the 3G+1-scalar verdict psum, zero all-gathers in
transposed order, C transactions when chunked) are encoded in
``collective_volume`` / ``spectral_volume`` / ``collective_volume_nd`` and
the GEMM checksum-flop model. This module checks, for a generated lattice
of ``FFTSpec`` / ``GEMMSpec`` configurations over the host-device meshes:

* collective COUNTS per kind (any unexpected all-gather, reduce-scatter
  or collective-permute fails);
* per-kind wire BYTES against the model's ``all_to_all_bytes`` /
  ``gather_hlo`` / ``psum_hlo`` terms and the ``hlo_bytes`` total;
* the verdict psum WIDTH (all-reduce buffers must carry the spec's real
  dtype — f32 for complex64, f64 for complex128);
* the exposed-communication fraction of chunked pipelines (``1/C``);
* the root HLO signature (a complex128 spec whose entry computation
  returns c64 buffers silently downcast);
* the GEMM flop model (``cost_analysis``: unchecked == ``2MKN`` exactly;
  the checked overhead within [0.5x, 2x] of the four-GEMV checksum model
  — XLA's counter includes the decode, the model does not).

Everything is lowered with ``jax.ShapeDtypeStruct`` stand-ins: the audit
compiles but never allocates or executes. ``benchmarks/fft_distributed.py``
calls :func:`check_cell` on the same code path, so the benchmark's
hard-asserts and the CI gate cannot disagree.
"""
from __future__ import annotations

import dataclasses

import numpy as np

import jax
import jax.numpy as jnp

from repro.analysis import hlo as hlolib
from repro.core.fft import distributed as dist
from repro.core.fft import multidim as md
from repro.core.fft import spectral as spectral_mod
from repro.core.fft.api import FFTSpec
from repro.core.gemm.api import GEMMSpec
from repro.core.plan import FTConfig, plan as build_plan
from repro.launch.mesh import make_mesh

__all__ = ["AuditError", "Finding", "CellReport", "AuditReport",
           "measure", "check_cell", "audit_plan", "audit_specs",
           "fft_lattice", "gemm_lattice", "lattice", "default_meshes",
           "run_audit"]

_COMPLEX_TOKEN = {"complex64": "c64", "complex128": "c128"}
_REAL_TOKEN = {"complex64": "f32", "complex128": "f64",
               "float32": "f32", "float64": "f64"}
_COMPLEX_TOKENS = frozenset(("c64", "c128"))
_FLOAT_TOKENS = frozenset(("f64", "f32", "bf16", "f16"))


@dataclasses.dataclass(frozen=True)
class Finding:
    """One contract violation in one audited cell."""

    tag: str
    check: str
    detail: str

    def __str__(self):
        return f"[{self.tag}] {self.check}: {self.detail}"


class AuditError(AssertionError):
    """Raised when an audited cell diverges from its analytic model."""

    def __init__(self, findings):
        self.findings = list(findings)
        super().__init__(
            "\n".join(str(f) for f in self.findings) or "audit failed")


@dataclasses.dataclass
class CellReport:
    """One lowered executor vs one model: measured summary + findings."""

    tag: str
    measured: dict
    collectives: list
    model: dict | None
    root: tuple
    findings: list


@dataclasses.dataclass
class AuditReport:
    specs: int
    cells: list
    findings: list

    def by_family(self) -> dict:
        fam: dict = {}
        for c in self.cells:
            fam.setdefault(c.tag.split(":", 1)[0], []).append(c)
        return fam


def _lower(fn, *args):
    """Compile ``fn`` on abstract operands — no data is ever allocated."""
    lowerable = fn if hasattr(fn, "lower") else jax.jit(fn)
    return lowerable.lower(*args).compile()


def measure(fn, *args) -> dict:
    """Lower + compile + parse: the legacy-shaped collective summary of
    ``fn``'s partitioned HLO (what ``benchmarks`` print as ``meas``)."""
    return hlolib.summarize(
        hlolib.parse_collectives(_lower(fn, *args).as_text()))


def _rel_off(got: float, want: float, rtol: float) -> bool:
    if want == 0:
        return got != 0
    return abs(got / want - 1.0) >= rtol


def _diff(tag, ops, root, model, *, rtol, check_exposed, dtype=None):
    """All findings for one cell (pure function of parsed artifacts)."""
    meas = hlolib.summarize(ops)
    count, by_kind = meas["count"], meas["bytes"]
    f = []

    def bad(check, detail):
        f.append(Finding(tag=tag, check=check, detail=detail))

    if model is None:
        # a local plan: the program must be collective-free, full stop
        for kind, c in count.items():
            if c:
                bad("unexpected-collective",
                    f"local plan lowered {c} {kind} op(s)")
    else:
        if "all_to_all_count" in model \
                and count["all-to-all"] != model["all_to_all_count"]:
            bad("all-to-all-count",
                f"hlo={count['all-to-all']} model={model['all_to_all_count']}")
        want_ag = int(model.get("all_gather_count", 0))
        if count["all-gather"] != want_ag:
            bad("all-gather-count",
                f"hlo={count['all-gather']} model={want_ag}"
                + (" (unexpected all-gather)"
                   if count["all-gather"] > want_ag else ""))
        if count["reduce-scatter"]:
            bad("unexpected-collective",
                f"{count['reduce-scatter']} reduce-scatter op(s); no "
                f"pipeline models any")
        if count["collective-permute"]:
            bad("unexpected-collective",
                f"{count['collective-permute']} collective-permute op(s); "
                f"no pipeline models any")
        for kind, key in (("all-to-all", "all_to_all_bytes"),
                          ("all-gather", "gather_hlo"),
                          ("all-reduce", "psum_hlo")):
            if key in model and _rel_off(by_kind[kind], model[key], rtol):
                bad(f"{kind}-bytes",
                    f"hlo={by_kind[kind]:.0f}B model={model[key]:.0f}B")
        if "hlo_bytes" in model and _rel_off(meas["total_bytes"],
                                             model["hlo_bytes"], rtol):
            bad("total-bytes", f"hlo={meas['total_bytes']:.0f}B "
                               f"model={model['hlo_bytes']:.0f}B")
        if check_exposed and "exposed_fraction" in model \
                and count["all-to-all"]:
            a2a = [w for k, w in meas["ops"] if k == "all-to-all"]
            exposed = max(a2a) / sum(a2a)
            if abs(exposed - model["exposed_fraction"]) >= 1e-9:
                bad("exposed-fraction",
                    f"hlo={exposed:.6f} model={model['exposed_fraction']:.6f}")

    if dtype is not None:
        ctoken = _COMPLEX_TOKEN.get(dtype)
        rtoken = _REAL_TOKEN.get(dtype)
        for op in ops:
            if op.kind == "all-reduce" and rtoken is not None:
                # the verdict psum width: f32 scalars under a complex128
                # spec would halve the detection mantissa. The ungrouped
                # ft pipeline also reduces native pred flags and an s32
                # location — those carry no mantissa, so they are exempt;
                # any FLOAT narrower than the spec real is still caught.
                wrong = set(op.dtypes) - {rtoken, "pred", "s32"}
                if wrong:
                    bad("psum-width", f"all-reduce carries {sorted(wrong)}, "
                                      f"spec wants {rtoken}")
            elif op.kind in ("all-to-all", "all-gather") \
                    and ctoken is not None:
                wrong = set(op.dtypes) - {ctoken}
                if wrong:
                    bad("collective-dtype",
                        f"{op.kind} carries {sorted(wrong)}, "
                        f"spec wants {ctoken}")
        token = _COMPLEX_TOKEN.get(dtype) or _REAL_TOKEN.get(dtype)
        fam = _COMPLEX_TOKENS if token in _COMPLEX_TOKENS else _FLOAT_TOKENS
        present = set(root) & fam
        if root and (token not in present or present - {token}):
            bad("root-dtype",
                f"entry returns {sorted(present) or ['none']} "
                f"of family {sorted(fam)}, spec wants {token}")
    return f, meas


def check_cell(fn, args, model, *, tag: str, rtol: float = 1e-3,
               check_exposed: bool = False, dtype: str | None = None,
               strict: bool = True) -> CellReport:
    """Lower one executor on abstract args and diff it against ``model``.

    This is the shared cell checker: the lattice sweep and the
    ``benchmarks/fft_distributed.py`` cells both call it, so a model==HLO
    assertion can only live here. ``model=None`` asserts a collective-free
    program (local plans). ``dtype`` (a spec dtype string) additionally
    checks collective widths and the root signature. Raises
    :class:`AuditError` with every finding when ``strict``."""
    compiled = _lower(fn, *args)
    text = compiled.as_text()
    ops = hlolib.parse_collectives(text)
    root = hlolib.root_signature(text)
    findings, meas = _diff(tag, ops, root, model, rtol=rtol,
                           check_exposed=check_exposed, dtype=dtype)
    rep = CellReport(tag=tag, measured=meas, collectives=ops, model=model,
                     root=root, findings=findings)
    if strict and findings:
        raise AuditError(findings)
    return rep


# ---------------------------------------------------------------------------
# per-plan audit cells
# ---------------------------------------------------------------------------

def _sds(shape, dtype):
    return jax.ShapeDtypeStruct(tuple(shape), jnp.dtype(dtype))


def _spec_tag(spec, p) -> str:
    if isinstance(spec, GEMMSpec):
        m, k, n = spec.shape
        return (f"gemm:{m}x{k}x{n}_{spec.dtype}_{p.backend}"
                + ("_ft" if spec.ft else ""))
    mesh = "local" if not p.sharded else (
        "x".join(f"{a}{p.mesh.shape[a]}" for a in p.mesh.axis_names))
    return (f"fft{'r' if spec.real else ''}{spec.rank}d:"
            f"{'x'.join(map(str, spec.shape))}_{spec.dtype}_{p.decomp}"
            f"_{mesh}"
            + ("_nat" if spec.natural_order else "_t")
            + (f"_g{p.groups}" if spec.ft else "")
            + (f"_c{p.chunks}" if p.chunks > 1 else ""))


def _fft_cells(p):
    """(tag, fn, args, model, check_exposed, rtol) cells for one FFTPlan.

    Each cell lowers the INNER jitted pipeline the plan executor is bound
    to (``_dist_fft_fn`` / ``_slab_fftn_fn`` / ...): the public wrappers
    may relayout eagerly, which would fold one-off ingest traffic into the
    steady-state contract under test.
    """
    spec, ft = p.spec, p.spec.ft
    tag = _spec_tag(spec, p)
    cdt = spec.dtype
    rdt = p._rdtype
    inj = _sds((1, 7), rdt)
    if spec.real:
        if p.rank == 1:
            if p.decomp != "pencil":
                return []
            # the packed half-length C2C is the executed transform
            n = p.tshape[0]
            fn = dist._dist_fft_fn(p.mesh, spec.axis, False, True, p.daxis, 1)
            x = _sds(spec.shape[:-1] + (n // 2,), cdt)
            return [(tag + ":fwd", fn, (x,), p.volume, False, 1e-3)]
        if p.decomp != md.DECOMP_SLAB:
            return []      # composed pencil real: no single nd model
        x = _sds(spec.shape, rdt)
        if ft is not None:
            fn = md._ft_rslab_fft2_fn(p.mesh, spec.axis, float(ft.threshold),
                                      bool(ft.correct), p.groups, p.daxis)
            return [(tag + ":fwd", fn, (x, inj), p.volume, False, 1e-3)]
        fn = md._rslab_fft2_fn(p.mesh, spec.axis, p.daxis)
        return [(tag + ":fwd", fn, (x,), p.volume, False, 1e-3)]

    x = _sds(spec.shape, cdt)
    if not p.sharded:
        return [(tag + ":fwd", jax.jit(p._fwd), (x,), None, False, 1e-3)]
    if p.rank == 1:
        if ft is not None:
            fn = dist._ft_dist_fft_fn(
                p.mesh, spec.axis, float(ft.threshold), bool(ft.correct),
                bool(spec.natural_order), p.groups, p.daxis, p.chunks)
            cells = [(tag + ":fwd", fn, (x, inj), p.volume, True, 1e-3)]
        else:
            fn = dist._dist_fft_fn(p.mesh, spec.axis, False,
                                   spec.natural_order, p.daxis, p.chunks)
            cells = [(tag + ":fwd", fn, (x,), p.volume, True, 1e-3)]
        # transposed-order non-ft plans feed the spectral round trip: audit
        # the fused convolve pair against spectral_volume too (2C a2a, 0
        # gathers). Kernel-batch 1 rides transaction 0, so the exposed-
        # fraction identity does not apply and chunked payloads are only
        # group-equal to ~2e-3 (the benchmark's historical tolerance).
        b, n = max(p.batch, 1), p.tshape[0]
        if ft is None and not spec.natural_order and p.daxis is None \
                and b % (p.shards * p.chunks) == 0:
            sfn = spectral_mod._spectral_pair_fn(p.mesh, spec.axis, None,
                                                 False, p.chunks)
            smodel = dist.spectral_volume(
                n, b, p.shards, kernel_batch=1,
                itemsize=spec.np_dtype.itemsize, chunks=p.chunks)
            cells.append((tag + ":spectral", sfn, (x, _sds((1, n), cdt)),
                          smodel, False, 2e-3))
        return cells
    if p.decomp == md.DECOMP_SLAB:
        if ft is not None:
            fn = md._ft_slab_fft2_fn(p.mesh, spec.axis, float(ft.threshold),
                                     bool(ft.correct), p.groups, p.daxis)
            return [(tag + ":fwd", fn, (x, inj), p.volume, False, 1e-3)]
        fn = md._slab_fftn_fn(p.mesh, spec.axis, p.rank, False, p.daxis)
        return [(tag + ":fwd", fn, (x,), p.volume, False, 1e-3)]
    fn = md._pencil_fftn_fn(p.mesh, spec.axis, p.rank, False,
                            bool(spec.natural_order), p.daxis, p.chunks)
    return [(tag + ":fwd", fn, (x,), p.volume, False, 1e-3)]


def _audit_gemm(p, *, strict=True):
    spec = p.spec
    tag = _spec_tag(spec, p)
    m, k, n = spec.shape
    x, w = _sds((m, k), spec.dtype), _sds((k, n), spec.dtype)
    fn = p.ft_matmul if spec.ft is not None else p.matmul
    compiled = _lower(jax.jit(fn), x, w)
    text = compiled.as_text()
    ops = hlolib.parse_collectives(text)
    root = hlolib.root_signature(text)
    findings, meas = _diff(tag, ops, root, None, rtol=1e-3,
                           check_exposed=False, dtype=spec.dtype)
    cost = compiled.cost_analysis()
    if isinstance(cost, list):        # pragma: no cover - backend-dependent
        cost = cost[0]
    flops = float(cost.get("flops", 0.0))
    want = float(p.volume["flops"])
    if spec.ft is None:
        if _rel_off(flops, want, 1e-6):
            findings.append(Finding(tag, "flops",
                                    f"hlo={flops:.0f} model={want:.0f}"))
    else:
        # XLA counts the decode on top of the four-GEMV model: gate the
        # measured overhead to [0.5x, 2x] of checksum_flops — wide enough
        # for the counter, tight enough to catch a broken/missing model
        extra = flops - want
        cs = float(p.volume["checksum_flops"])
        if not (0.5 * cs <= extra <= 2.0 * cs):
            findings.append(Finding(
                tag, "checksum-flops",
                f"hlo overhead={extra:.0f} model={cs:.0f} "
                f"(allowed [{0.5 * cs:.0f}, {2 * cs:.0f}])"))
    rep = CellReport(tag=tag, measured=meas, collectives=ops, model=p.volume,
                     root=root, findings=findings)
    if strict and findings:
        raise AuditError(findings)
    return [rep]


def audit_plan(p, *, strict: bool = True) -> list[CellReport]:
    """Audit every cell of one built plan. ``strict`` raises on the first
    cell with findings; otherwise findings accumulate on the reports."""
    if isinstance(p.spec, GEMMSpec):
        return _audit_gemm(p, strict=strict)
    reports = []
    for tag, fn, args, model, exposed, rtol in _fft_cells(p):
        reports.append(check_cell(fn, args, model, tag=tag, rtol=rtol,
                                  check_exposed=exposed,
                                  dtype=p.spec.dtype, strict=strict))
    return reports


# ---------------------------------------------------------------------------
# the spec lattice
# ---------------------------------------------------------------------------

def default_meshes(ndev: int | None = None):
    """Deterministic mesh templates for ``ndev`` host devices, largest
    first: 1-D ``(fft,)`` meshes of 2 and 4 shards plus 2-D ``data x fft``
    meshes — ``(2, 2)`` from 4 devices, ``(2, 4)`` from 8."""
    if ndev is None:
        ndev = len(jax.devices())
    out = []
    for shape, axes in (((2,), ("fft",)),
                        ((4,), ("fft",)),
                        ((2, 2), ("data", "fft")),
                        ((2, 4), ("data", "fft"))):
        if int(np.prod(shape)) <= ndev:
            out.append(make_mesh(shape, axes))
    return out


def fft_lattice(meshes) -> list[FFTSpec]:
    """Every audited FFT configuration: rank x decomp x real x ft/groups x
    chunks x dtype x mesh, small sizes so the sweep compiles fast. Purely
    deterministic in ``meshes`` — the CI gate audits the same lattice every
    run. Infeasible combinations are skipped at generation via the same
    validation ``plan()`` applies (a spec listed here MUST plan)."""
    specs: list[FFTSpec] = []
    g = FTConfig(groups=4)
    b, n = 8, 256
    for mesh in meshes:
        for nat in (True, False):
            for chunks in (1, 2):
                specs.append(FFTSpec(shape=(b, n), mesh=mesh,
                                     natural_order=nat, chunks=chunks))
        specs.append(FFTSpec(shape=(b, n), dtype="complex128", mesh=mesh))
        specs.append(FFTSpec(shape=(b, n), mesh=mesh, ft=g))
        specs.append(FFTSpec(shape=(b, n), mesh=mesh, ft=g,
                             natural_order=False, chunks=2))
        specs.append(FFTSpec(shape=(b, n), dtype="complex128", mesh=mesh,
                             ft=g))
        # rank-1 real: the packed half-length transform (natural only)
        specs.append(FFTSpec(shape=(b, 2 * n), mesh=mesh, real=True))
        # rank-2 slab + real slab (+ ft): needs shards | 32 and shards | 32
        specs.append(FFTSpec(shape=(b, 32, 64), rank=2, mesh=mesh,
                             decomp="slab"))
        specs.append(FFTSpec(shape=(b, 32, 64), rank=2, mesh=mesh,
                             decomp="slab", ft=g))
        specs.append(FFTSpec(shape=(b, 32, 64), rank=2, mesh=mesh,
                             decomp="slab", real=True))
        # rank-2 pencil, both orders (64 >= fft^2, 64 >= data^2)
        for nat in (True, False):
            specs.append(FFTSpec(shape=(b, 64, 64), rank=2, mesh=mesh,
                                 decomp="pencil", natural_order=nat))
        dd = dict(mesh.shape).get("data", 1)
        if dd > 1:
            # chunked pencil on the 2-D mesh (replicated batch rows split)
            specs.append(FFTSpec(shape=(b, 64, 64), rank=2, mesh=mesh,
                                 decomp="pencil", natural_order=False,
                                 chunks=2))
        else:
            # deeper lattice on the 1-D meshes: fp64 slab ft, real ft,
            # rank-3 pencil both orders
            specs.append(FFTSpec(shape=(b, 32, 64), rank=2,
                                 dtype="complex128", mesh=mesh,
                                 decomp="slab", ft=g))
            specs.append(FFTSpec(shape=(b, 32, 64), rank=2, mesh=mesh,
                                 decomp="slab", real=True, ft=g))
            # ungrouped ABFT: the native-scalar stats path (pred/s32
            # telemetry reduces), modeled separately from the grouped
            # stacked-block broadcast
            g1 = FTConfig(groups=1)
            specs.append(FFTSpec(shape=(b, n), mesh=mesh, ft=g1))
            specs.append(FFTSpec(shape=(b, n), dtype="complex128",
                                 mesh=mesh, ft=g1))
            specs.append(FFTSpec(shape=(b, 32, 64), rank=2, mesh=mesh,
                                 decomp="slab", ft=g1))
            specs.append(FFTSpec(shape=(b, 32, 64), rank=2, mesh=mesh,
                                 decomp="slab", real=True, ft=g1))
            for nat in (True, False):
                specs.append(FFTSpec(shape=(4, 16, 16, 64), rank=3,
                                     mesh=mesh, decomp="pencil",
                                     natural_order=nat))
    # local plans: collective-free by contract
    specs.append(FFTSpec(shape=(b, n)))
    specs.append(FFTSpec(shape=(b, n), dtype="complex128"))
    specs.append(FFTSpec(shape=(b, 32, 64), rank=2))
    return specs


def gemm_lattice() -> list[GEMMSpec]:
    """Checked and unchecked GEMMs (xla backend — host CI has no TPU)."""
    specs = []
    for shape in ((64, 32, 48), (128, 64, 32), (32, 128, 64)):
        for ft in (None, FTConfig()):
            specs.append(GEMMSpec(shape=shape, ft=ft, backend="xla"))
    specs.append(GEMMSpec(shape=(64, 64, 64), dtype="float64",
                          backend="xla"))
    return specs


def lattice(meshes=None) -> list:
    if meshes is None:
        meshes = default_meshes()
    return fft_lattice(meshes) + gemm_lattice()


def audit_specs(specs, *, strict: bool = True,
                progress=None) -> AuditReport:
    """Plan + audit every spec. With ``strict`` the first divergent cell
    raises :class:`AuditError`; otherwise all findings are collected."""
    cells: list[CellReport] = []
    findings: list[Finding] = []
    for s in specs:
        p = build_plan(s)
        reports = audit_plan(p, strict=strict)
        cells.extend(reports)
        for r in reports:
            findings.extend(r.findings)
        if progress is not None:
            progress(s, reports)
    return AuditReport(specs=len(specs), cells=cells, findings=findings)


def run_audit(*, meshes=None, strict: bool = True,
              progress=None) -> AuditReport:
    """Audit the full generated lattice on the visible devices."""
    return audit_specs(lattice(meshes), strict=strict, progress=progress)

"""Static-analysis layer: the HLO parser, the plan auditor, and the repo
lint (``repro.analysis`` — what ``python -m repro.analysis --strict``
gates CI on).

Three layers under test:

* ``analysis.hlo`` — the promoted op-classifying parser (stdlib-only):
  per-op kind/shape/bytes, async start/done dedupe, root signatures, and
  the legacy ``collective_bytes`` summary shape the dry-run still exposes;
* ``analysis.audit`` — the generated spec lattice is deterministic and
  covers every registered plan family; the auditor passes on the tree and
  HARD-FAILS when a volume model is broken under it (the acceptance
  demonstration: monkeypatch the model, watch the sweep catch it);
* ``analysis.lint`` — per-rule positive/negative fixtures on a synthetic
  tree, ``# noqa`` suppression, baseline round-trip (strict-on-new), and
  regression tests for every L003 site fixed to raise ValueError.
"""
from __future__ import annotations

import pathlib
import textwrap
import types

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro.analysis import audit, hlo, lint
from repro.launch.mesh import make_mesh

# ---------------------------------------------------------------------------
# hlo parser
# ---------------------------------------------------------------------------

_SAMPLE_HLO = textwrap.dedent("""\
    HloModule jit_fn, entry_computation_layout={(c64[8,4096]{1,0})->(c64[8,4096]{1,0}, f32[], pred[])}

    ENTRY main.42 (p0.1: c64[8,4096]) -> (c64[8,4096], f32[], pred[]) {
      %p0.1 = c64[8,4096]{1,0} parameter(0)
      %all-to-all-start = ((c64[8,4096]{1,0}), (c64[8,4096]{1,0})) all-to-all-start(%p0.1), replica_groups={{0,1,2,3}}
      %all-to-all-done = c64[8,4096]{1,0} all-to-all-done(%all-to-all-start)
      %ar = f32[3]{0} all-reduce(%bits), to_apply=%add
      %flag = pred[] all-reduce(%b0), to_apply=%or
      ROOT %t = (c64[8,4096]{1,0}, f32[], pred[]) tuple(%all-to-all-done, %s, %f)
    }
    """)


def test_hlo_parser_ops_and_async_dedupe():
    ops = hlo.parse_collectives(_SAMPLE_HLO)
    kinds = [o.kind for o in ops]
    assert kinds == ["all-to-all", "all-reduce", "all-reduce"]
    a2a = ops[0]
    # the async start tuple holds (operand, result): dedupe keeps ONE half
    assert a2a.is_async
    assert a2a.payload_bytes == 8 * 4096 * 8
    assert a2a.wire_bytes == a2a.payload_bytes  # factor 1.0 for a2a
    assert a2a.dtypes == ("c64",)
    ar = ops[1]
    assert ar.payload_bytes == 3 * 4
    assert ar.wire_bytes == 2.0 * 3 * 4  # ring factor for all-reduce
    assert ops[2].dtypes == ("pred",)


def test_hlo_root_signature():
    assert hlo.root_signature(_SAMPLE_HLO) == ("c64", "f32", "pred")
    assert hlo.root_signature("no entry line here") == ()


def test_hlo_summarize_legacy_shape():
    s = hlo.summarize(hlo.parse_collectives(_SAMPLE_HLO))
    assert set(s) == {"bytes", "count", "ops", "total_bytes"}
    assert s["count"]["all-to-all"] == 1
    assert s["count"]["all-reduce"] == 2
    assert s["total_bytes"] == pytest.approx(
        8 * 4096 * 8 + 2.0 * (3 * 4 + 1))


def test_dryrun_collective_bytes_is_compat_wrapper():
    """The dry-run's parser surface (what PR auditors and test_moe_ep
    import) now delegates to analysis.hlo with identical results."""
    from repro.launch import dryrun

    assert dryrun.collective_bytes(_SAMPLE_HLO) == hlo.summarize(
        hlo.parse_collectives(_SAMPLE_HLO))
    assert dryrun.COLLECTIVE_RE is hlo.COLLECTIVE_RE


# ---------------------------------------------------------------------------
# plan auditor
# ---------------------------------------------------------------------------

def _needs4():
    if len(jax.devices()) < 4:
        pytest.skip("needs >= 4 host devices (the CI mesh-8dev lane sets "
                    "XLA_FLAGS=--xla_force_host_platform_device_count=8)")


def test_lattice_deterministic():
    a, b = audit.lattice(), audit.lattice()
    assert [repr(s) for s in a] == [repr(s) for s in b]
    assert len(a) >= 10  # locals + gemm even on one device


def test_lattice_covers_every_registered_plan_family():
    """Every spec type in the shared plan registry appears in the audited
    lattice — a new plan family cannot ship un-audited."""
    from repro.core import plan as planbase

    covered = {type(s) for s in audit.lattice()}
    assert covered == set(planbase._PLAN_TYPES)


def test_audit_local_and_gemm_plans_single_device():
    """The device-independent lattice slice (local FFT + GEMM plans)
    audits clean anywhere — collective-free programs, exact flop model."""
    from repro.core.fft.api import FFTSpec
    from repro.core.gemm.api import GEMMSpec
    from repro.core.plan import FTConfig

    specs = [FFTSpec(shape=(8, 256)),
             GEMMSpec(shape=(64, 32, 48), backend="xla"),
             GEMMSpec(shape=(64, 32, 48), ft=FTConfig(), backend="xla")]
    rep = audit.audit_specs(specs, strict=True)
    assert rep.specs == 3 and not rep.findings


def test_check_cell_flags_missing_collective():
    """A model that promises collectives a program does not have (or vice
    versa) is a hard failure, not a warning."""
    fn = jax.jit(lambda x: x + 1)
    x = jax.ShapeDtypeStruct((8, 64), jnp.complex64)
    bad_model = {"all_to_all_count": 1, "all_gather_count": 0,
                 "hlo_bytes": 4096.0}
    with pytest.raises(audit.AuditError) as ei:
        audit.check_cell(fn, (x,), bad_model, tag="t")
    checks = {f.check for f in ei.value.findings}
    assert "all-to-all-count" in checks
    # and a local plan contract: any collective at all is a finding
    rep = audit.check_cell(fn, (x,), None, tag="t2", strict=False)
    assert not rep.findings


def test_check_cell_flags_root_dtype_downcast():
    fn = jax.jit(lambda x: jnp.abs(x).astype(jnp.float32))
    x = jax.ShapeDtypeStruct((8,), jnp.float64)
    with pytest.raises(audit.AuditError) as ei:
        audit.check_cell(fn, (x,), None, tag="t", dtype="float64")
    assert {f.check for f in ei.value.findings} == {"root-dtype"}


def test_audit_catches_broken_volume_model(monkeypatch):
    """THE acceptance demonstration: corrupt the analytic model the plan
    layer builds volumes from, clear the plan cache, and the sweep must
    fail the spec — the auditor is what stands between a silent model
    drift and CI."""
    _needs4()
    from repro.core.fft import api as fft_api
    from repro.core.fft.api import FFTSpec
    from repro.core.plan import plan_cache_clear

    real = fft_api.collective_volume

    def broken(*a, **kw):
        out = dict(real(*a, **kw))
        out["hlo_bytes"] *= 2          # model now claims double the bytes
        out["all_to_all_bytes"] *= 2
        return out

    monkeypatch.setattr(fft_api, "collective_volume", broken)
    plan_cache_clear()
    try:
        mesh = make_mesh((2,), ("fft",))
        spec = FFTSpec(shape=(8, 256), mesh=mesh)
        with pytest.raises(audit.AuditError) as ei:
            audit.audit_specs([spec], strict=True)
        checks = {f.check for f in ei.value.findings}
        assert checks & {"all-to-all-bytes", "total-bytes"}
    finally:
        plan_cache_clear()             # drop plans built on the broken model


def test_audit_full_lattice_sweep():
    """The CI gate itself: the whole generated lattice lowers and matches
    the analytic models with zero findings (mesh-8dev lane)."""
    _needs4()
    rep = audit.run_audit(strict=True)
    assert rep.specs >= 60
    assert len(rep.cells) >= rep.specs
    assert not rep.findings
    fams = rep.by_family()
    assert {"fft1d", "fft2d", "fftr1d", "fftr2d", "gemm"} <= set(fams)


# ---------------------------------------------------------------------------
# repo lint
# ---------------------------------------------------------------------------

def _tree(tmp_path, files: dict) -> pathlib.Path:
    for rel, body in files.items():
        p = tmp_path / rel
        p.parent.mkdir(parents=True, exist_ok=True)
        p.write_text(textwrap.dedent(body))
    return tmp_path


def _rules(findings):
    return [f.rule for f in findings]


def test_lint_l001_deprecated_kwargs(tmp_path):
    root = _tree(tmp_path, {"src/repro/x.py": """\
        from repro.kernels import ops
        from repro.kernels.ops import fft as kfft

        def f(x, mesh):
            a = ops.fft(x, mesh=mesh)            # positive: aliased module
            b = kfft(x, natural_order=False)     # positive: aliased entry
            c = ops.fft(x, bs=4)                 # negative: live kwarg
            d = ops.fft(x, mesh=mesh)  # noqa: L001
            return a, b, c, d
    """})
    fs = lint.lint_tree(root)
    assert _rules(fs) == ["L001", "L001"]
    assert fs[0].line == 5 and fs[1].line == 6


def test_lint_l002_raw_fft_scoped_to_core(tmp_path):
    body = """\
        import jax.numpy as jnp

        def f(x):
            return jnp.fft.fft(x)
    """
    root = _tree(tmp_path, {
        "src/repro/kernels/a.py": body,       # positive
        "src/repro/core/fft/b.py": body,      # negative: core/fft owns it
        "benchmarks/c.py": body,              # negative: out of L002 scope
    })
    fs = lint.lint_tree(root)
    assert _rules(fs) == ["L002"]
    assert fs[0].path == "src/repro/kernels/a.py"


def test_lint_l003_param_asserts(tmp_path):
    root = _tree(tmp_path, {"src/repro/v.py": """\
        def f(n, shards):
            assert n % shards == 0       # positive: validates params
            local = n // shards
            assert local * shards == n   # positive: n is a param
            m = local + 1
            assert m > 0                 # negative: locals only
            return m
    """})
    assert _rules(lint.lint_tree(root)) == ["L003", "L003"]


def test_lint_l004_serve_plan_lock(tmp_path):
    root = _tree(tmp_path, {"src/repro/serve/runtime.py": """\
        from repro.serve.specs import serve_plan

        class R:
            def run(self, plan, xb):
                if plan.sharded:
                    with self._mesh_lock:
                        serve_plan(plan, xb)     # ok: under the lock
                else:
                    serve_plan(plan, xb)         # ok: unsharded branch
                serve_plan(plan, xb)             # positive: bare dispatch
    """})
    fs = lint.lint_tree(root)
    assert _rules(fs) == ["L004"]
    assert fs[0].line == 10


def test_lint_l005_frozen_setattr(tmp_path):
    root = _tree(tmp_path, {"src/repro/s.py": """\
        class S:
            def __post_init__(self):
                object.__setattr__(self, "a", 1)   # ok

            def mutate(self):
                object.__setattr__(self, "a", 2)   # positive
    """})
    fs = lint.lint_tree(root)
    assert _rules(fs) == ["L005"]
    assert "mutate" in fs[0].message


def test_lint_real_tree_has_no_unbaselined_findings():
    """Acceptance: zero NEW lint findings in this repo — everything else
    was either fixed (L003) or explicitly grandfathered (the reference
    kernel's jnp.fft usage)."""
    new, old = lint.split_baseline(lint.lint_tree(), lint.load_baseline())
    assert new == []
    assert all(f.rule == "L002" for f in old)


def test_lint_baseline_roundtrip(tmp_path):
    root = _tree(tmp_path, {"src/repro/v.py": """\
        def f(n):
            assert n > 0
    """})
    fs = lint.lint_tree(root)
    assert _rules(fs) == ["L003"]
    base = tmp_path / "baseline.txt"
    lint.save_baseline(fs, base)
    loaded = lint.load_baseline(base)
    assert loaded == {f.fingerprint for f in fs}
    new, old = lint.split_baseline(fs, loaded)
    assert new == [] and old == fs
    # fingerprints are line-number-free: prepending code must not
    # resurrect a grandfathered finding
    p = root / "src/repro/v.py"
    p.write_text("import os\n\n\n" + p.read_text())
    new, old = lint.split_baseline(lint.lint_tree(root), loaded)
    assert new == [] and len(old) == 1


# ---------------------------------------------------------------------------
# the L003 fixes: every converted site raises ValueError with the value
# ---------------------------------------------------------------------------

def test_make_batch_rejects_indivisible_sharding():
    from repro.data.synthetic import make_batch

    with pytest.raises(ValueError, match="batch=7.*num_shards=2"):
        make_batch(0, 0, batch=7, seq_len=8, vocab_size=32, num_shards=2)


def test_make_dist_plan_rejects_unsplittable_n():
    from repro.core.fft.distributed import make_dist_plan

    # n=8 over 4 shards: both pencil factors must divide by 4 -> 4x4=16 != 8
    with pytest.raises(ValueError, match="N=8 too small for a 4-way"):
        make_dist_plan(8, 4)
    with pytest.raises(ValueError, match="power of two, got 5"):
        make_dist_plan(256, 5)


def test_fft_with_plan_rejects_multipass():
    from repro.core.fft.plan import make_plan
    from repro.core.fft.stockham import fft_with_plan

    plan = make_plan(1 << 22)  # beyond one VMEM pass
    assert plan.num_passes > 1
    with pytest.raises(ValueError, match="single-pass"):
        fft_with_plan(jnp.zeros((1, 1 << 22), jnp.complex64), plan)


def test_fft_large_rejects_wrong_plan():
    from repro.core.fft.large import fft_large
    from repro.core.fft.plan import make_plan

    with pytest.raises(ValueError, match="n=512"):
        fft_large(jnp.zeros((1, 256), jnp.complex64), make_plan(512))


def test_block_fft_pallas_rejects_bad_tile():
    from repro.kernels.stockham import block_fft_pallas

    xr = jnp.zeros((8, 64), jnp.float32)
    with pytest.raises(ValueError, match="bs=5"):
        block_fft_pallas(xr, xr, bs=5)


def test_abft_fft_pallas_rejects_bad_transactions():
    from repro.kernels.stockham_abft import abft_fft_pallas

    xr = jnp.zeros((8, 64), jnp.float32)
    with pytest.raises(ValueError, match="transactions=3"):
        abft_fft_pallas(xr, xr, bs=2, transactions=3)


def test_compress_allreduce_rejects_too_many_ranks():
    from repro.parallel.collectives import compress_allreduce_mean

    fake_mesh = types.SimpleNamespace(shape={"dp": 512})
    with pytest.raises(ValueError, match="512"):
        compress_allreduce_mean({}, {}, fake_mesh, ("dp",))


def test_deep_asserts_keep_internal_invariants():
    """The L003 pass converts PARAMETER validation only: purely internal
    invariant asserts (locals derived inside the function) stay asserts
    — the linter must not flag the surviving ones in this repo."""
    findings = [f for f in lint.lint_tree() if f.rule == "L003"]
    assert findings == []

"""Distributed FFT: per-pass collective volume + wall time vs single device.

For each (N, batch, shards) cell this measures three things:

* wall time of the sharded pipeline vs the single-device multi-pass driver,
* the all-to-all / psum wire bytes parsed from the post-partitioning HLO
  (repro.analysis.hlo — the same parser the LM dry-run uses),
* the analytic model ``core.fft.distributed.collective_volume`` — the two
  must agree, which is the point: ONE all-to-all per transform, ABFT adding
  only the 2/B checksum rows plus a 3-scalar psum.

Every model==HLO cell dispatches through the shared static auditor
(``repro.analysis.audit.check_cell`` — the same checker ``python -m
repro.analysis`` sweeps over the whole generated spec lattice), which
diffs per-op-kind counts AND bytes (all-to-all / all-gather / psum /
collective-permute), flags any unexpected collective kind, and checks the
psum scalar width against the spec dtype. The benchmark keeps what the
static sweep cannot do: wall-clock measurement, bitwise chunked==bulk
equality, and the rfft2-vs-fft2 byte-ratio headline.

The ABFT model==HLO assertion runs for BOTH complex64 and complex128 (the
verdict psum scalars are f32 vs f64 — the model derives their width from
``itemsize``) and for BOTH the single-group and the grouped
multi-transaction pipeline (G checksum groups -> 2G checksum rows on the
all-to-all + 3G+1 psum scalars). On a 2-D ``data x fft`` mesh the grouped
ft pipeline is additionally verified to shard the batch: model==HLO with
``data_shards`` and ZERO all-gathers in transposed order. The
transposed-order spectral pipeline (fft_convolve / round-trip ifft(fft)) is
verified to lower to exactly TWO all-to-alls and ZERO all-gathers, with
bytes matching ``spectral_volume``. ``run_multidim`` extends the same
contract to the 2-D transforms (core.fft.multidim): slab == one all-to-all
with free natural order (plus the grouped-ABFT checksum grids and psum,
fp32 and fp64), pencil == two all-to-alls (zero gathers transposed, the
modeled digit-restore gathers natural), and the fused 2-D convolution ==
two all-to-alls — all hard-asserted against ``collective_volume_nd``.

``run_overlap`` pins down the chunked multi-transaction pipelines: for each
chunk count C the 1-D, grouped-ABFT, and spectral pipelines must lower to
exactly C (resp. 2C) all-to-alls with unchanged total volume, the measured
exposed-communication fraction (largest single all-to-all / total) must
equal the model's ``1/C``, and every chunked output must be bitwise
identical to the bulk pipeline.

Standalone runs force a multi-device host platform:

    PYTHONPATH=src python -m benchmarks.fft_distributed
"""
from __future__ import annotations

import os

if __name__ == "__main__" and "xla_force_host_platform_device_count" not in \
        os.environ.get("XLA_FLAGS", ""):
    os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "") +
                               " --xla_force_host_platform_device_count=4")

import numpy as np

import jax
import jax.numpy as jnp

from repro.analysis import audit
from repro.core import fft as tfft
from repro.core.fft import distributed as dist
from repro.core.fft import spectral as spec
from repro.launch.mesh import make_mesh

from .common import emit, fft_gflops, timeit


def _check(tag, fn, args, model, **kw) -> dict:
    """Audit one lowered cell (hard-fails on any model==HLO divergence)
    and return the legacy collective summary for the emit lines."""
    return audit.check_cell(fn, args, model, tag=tag, **kw).measured


# the per-kind model keys check_cell diffs — a forward+inverse pair
# pipeline (fft_convolve round trip) is modeled by summing both directions
_PAIR_KEYS = ("all_to_all_count", "all_gather_count", "all_to_all_bytes",
              "gather_hlo", "psum_hlo", "hlo_bytes",
              "total_wire")


def _pair_model(fwd: dict, inv: dict) -> dict:
    return {k: fwd[k] + inv[k] for k in _PAIR_KEYS}


def grid(smoke: bool = True):
    if smoke:
        return [(14, 8), (17, 2)]
    return [(ln, b) for ln in (14, 17, 20, 23) for b in (1, 8, 64)]


def run(smoke: bool = True):
    ndev = min(4, len(jax.devices()))
    shards = 1 << (ndev.bit_length() - 1)  # largest power of two that fits
    if shards < 2:
        print("# fft_distributed: single device visible — skipping "
              "(set --xla_force_host_platform_device_count)")
        return []
    mesh = make_mesh((shards,), ("fft",))
    rng = np.random.default_rng(0)
    rows = []
    for ln, b in grid(smoke):
        n = 1 << ln
        x = (rng.standard_normal((b, n)) +
             1j * rng.standard_normal((b, n))).astype(np.complex64)
        xj = jnp.asarray(x)

        single = jax.jit(tfft.fft)
        t_1 = timeit(single, xj)
        t_d = timeit(lambda v: dist.distributed_fft(v, mesh), xj)
        t_ft = timeit(lambda v: dist.ft_distributed_fft(v, mesh).y, xj)

        # model==HLO for the natural-order, transposed-order, and ABFT
        # pipelines: check_cell hard-fails on any per-kind count/byte
        # divergence, psum-width, or root-dtype mismatch.
        # natural_order passed explicitly: lru_cache keys on the raw call
        # signature, so defaulting it here would double-compile the same
        # pipeline distributed_fft already built with 4 positional args
        tagp = f"distfft_N2^{ln}_b{b}"
        inj32 = jnp.zeros((1, 7), jnp.float32)
        inj64 = jnp.zeros((1, 7), jnp.float64)
        x128 = jnp.asarray(x.astype(np.complex128))
        model = dist.collective_volume(n, b, shards)
        model_t = dist.collective_volume(n, b, shards, natural_order=False)
        model_ft = dist.collective_volume(n, b, shards, ft=True)
        # fp64: the ABFT verdict psum carries f64 scalars — the model must
        # track the itemsize instead of assuming 4-byte reductions
        model_ft64 = dist.collective_volume(n, b, shards, ft=True,
                                            itemsize=16)
        meas = _check(f"{tagp}:natural",
                      dist._dist_fft_fn(mesh, "fft", False, True), (xj,),
                      model, check_exposed=True, dtype="complex64")
        meas_t = _check(f"{tagp}:transposed",
                        dist._dist_fft_fn(mesh, "fft", False, False), (xj,),
                        model_t, check_exposed=True, dtype="complex64")
        meas_ft = _check(f"{tagp}:ft",
                         dist._ft_dist_fft_fn(mesh, "fft", 1e-4, True),
                         (xj, inj32), model_ft, dtype="complex64")
        meas_ft64 = _check(f"{tagp}:ft_c128",
                           dist._ft_dist_fft_fn(mesh, "fft", 1e-4, True),
                           (x128, inj64), model_ft64, dtype="complex128")
        cells = [("natural", meas, model), ("transposed", meas_t, model_t),
                 ("ft", meas_ft, model_ft),
                 ("ft_c128", meas_ft64, model_ft64)]
        # grouped multi-transaction ABFT: G checksum groups ride as 2G rows
        # on the same all-to-all; the verdict is 3G+1 psum scalars. The
        # grouped verdict traffic must hold model==HLO in fp32 AND fp64.
        g = min(4, b)
        if b % g == 0 and g > 1:
            model_g = dist.collective_volume(n, b, shards, ft=True, groups=g)
            model_g64 = dist.collective_volume(n, b, shards, ft=True,
                                               groups=g, itemsize=16)
            cells += [
                (f"ft_g{g}", _check(
                    f"{tagp}:ft_g{g}",
                    dist._ft_dist_fft_fn(mesh, "fft", 1e-4, True, True, g),
                    (xj, inj32), model_g, dtype="complex64"), model_g),
                (f"ft_g{g}_c128", _check(
                    f"{tagp}:ft_g{g}_c128",
                    dist._ft_dist_fft_fn(mesh, "fft", 1e-4, True, True, g),
                    (x128, inj64), model_g64, dtype="complex128"),
                 model_g64)]
        # transposed-order round trip + fused convolve: exactly 2 all-to-alls
        # and zero all-gathers — count contracts the checker reads off the
        # spectral_volume model keys (the batch-split inverse needs D | batch
        # for a pad-free pipeline, so model==HLO only holds on those cells)
        if b % shards == 0:
            rt = jax.jit(lambda v: dist.distributed_ifft(
                dist.distributed_fft(v, mesh, natural_order=False), mesh,
                natural_order=False))
            model_rt = dist.spectral_volume(n, b, shards)
            vj = jnp.asarray((rng.standard_normal((1, n)) +
                              1j * rng.standard_normal((1, n))
                              ).astype(np.complex64))
            model_cv = dist.spectral_volume(n, b, shards, kernel_batch=1)
            cells += [
                ("spectral_rt", _check(f"{tagp}:spectral_rt", rt, (xj,),
                                       model_rt, dtype="complex64"),
                 model_rt),
                ("spectral_conv", _check(
                    f"{tagp}:spectral_conv",
                    spec._spectral_pair_fn(mesh, "fft", None, False),
                    (xj, vj), model_cv, dtype="complex64"), model_cv)]

        emit(f"distfft_N2^{ln}_b{b}_x{shards}", t_d * 1e6,
             f"{fft_gflops(n, b, t_d):.2f}GF/s;vs_single={t_1/t_d:.2f}x;"
             f"ft_overhead={(t_ft - t_d)/t_d:+.1%}")
        for tag, m, mdl in cells:
            got, want = m["total_bytes"], mdl["hlo_bytes"]
            emit(f"distfft_N2^{ln}_b{b}_wire_{tag}", got,
                 f"model={want:.0f}B;hlo/model={got/want:.3f};"
                 f"wire={mdl['total_wire']:.0f}B")
        rows.append((ln, b, t_1, t_d, t_ft, meas, model, meas_ft, model_ft))
    return rows


def run_multidim(smoke: bool = True):
    """Multi-dimensional (fft2) collective-volume model == HLO, both
    decompositions (core.fft.multidim):

    * slab — ONE all-to-all, zero all-gathers even in natural order (the
      sharding lands on a true array axis), grouped-ABFT cells in fp32 AND
      fp64 (checksum grids ride the transpose + the 3G+1-scalar psum);
    * pencil — TWO all-to-alls on a 2-D ``data x fft`` mesh (one per mesh
      axis) with zero all-gathers in transposed order; natural order adds
      the modeled digit-restore gathers (``full/data + full`` bytes);
    * the fused 2-D convolution round trip — exactly two all-to-alls and
      zero all-gathers, kernel spectra riding the forward transpose.
    """
    ndev = min(4, len(jax.devices()))
    shards = 1 << (ndev.bit_length() - 1)
    if shards < 2:
        print("# fft_multidim: single device visible — skipping")
        return []
    from repro.core.fft import multidim as md

    mesh = make_mesh((shards,), ("fft",))
    rng = np.random.default_rng(2)
    rows = []
    for rr, cc, b in [(128, 256, 8)] if smoke else [(128, 256, 8),
                                                    (512, 1024, 8)]:
        x = jnp.asarray((rng.standard_normal((b, rr, cc)) +
                         1j * rng.standard_normal((b, rr, cc))
                         ).astype(np.complex64))
        x128 = x.astype(jnp.complex128)
        g = 4
        tagp = f"fft2_{rr}x{cc}_b{b}"
        inj32 = jnp.zeros((1, 7), jnp.float32)
        inj64 = jnp.zeros((1, 7), jnp.float64)
        mdl_slab = md.collective_volume_nd((rr, cc), b, shards)
        mdl_ft = md.collective_volume_nd((rr, cc), b, shards, ft=True,
                                         groups=g)
        mdl_ft64 = md.collective_volume_nd((rr, cc), b, shards, ft=True,
                                           groups=g, itemsize=16)
        # slab (incl. ft) never all-gathers: natural order is free — the
        # checker reads the zero gather count off the model keys
        cells = [
            ("slab", _check(
                f"{tagp}:slab", md._slab_fftn_fn(mesh, "fft", 2, False,
                                                 None),
                (x,), mdl_slab, dtype="complex64"), mdl_slab),
            ("slab_ft", _check(
                f"{tagp}:slab_ft",
                md._ft_slab_fft2_fn(mesh, "fft", 1e-4, True, g, None),
                (x, inj32), mdl_ft, dtype="complex64"), mdl_ft),
            ("slab_ft_c128", _check(
                f"{tagp}:slab_ft_c128",
                md._ft_slab_fft2_fn(mesh, "fft", 1e-4, True, g, None),
                (x128, inj64), mdl_ft64, dtype="complex128"), mdl_ft64),
        ]
        # fused 2-D convolution: kernel rides the forward transpose, the
        # product comes back through the mirrored inverse — 2 a2a total
        vk = jnp.asarray((rng.standard_normal((1, rr, cc)) +
                          1j * rng.standard_normal((1, rr, cc))
                          ).astype(np.complex64))
        model_cv = _pair_model(
            md.collective_volume_nd((rr, cc), b + 1, shards),
            md.collective_volume_nd((rr, cc), b, shards))
        cells.append(("conv2", _check(
            f"{tagp}:conv2", md._conv2_pair_fn(mesh, "fft", None),
            (x, vk), model_cv, dtype="complex64"), model_cv))
        if len(jax.devices()) >= 4:
            mesh2 = make_mesh((2, 2), ("data", "fft"))
            for nat in (False, True):
                mdl_p = md.collective_volume_nd(
                    (rr, cc), b, 2, decomp="pencil", data_shards=2,
                    natural_order=nat)
                tag = f"pencil_{'nat' if nat else 'transposed'}"
                cells.append((tag, _check(
                    f"{tagp}:{tag}",
                    md._pencil_fftn_fn(mesh2, "fft", 2, False, nat, "data"),
                    (x,), mdl_p, dtype="complex64"), mdl_p))
            # grouped ABFT on the 2-D mesh: batch SHARDS over data, no
            # batch all-gather, verdict psum confined to the fft axis (the
            # replicated stats ride one modeled collective-permute)
            mdl_ft2 = md.collective_volume_nd((rr, cc), b, 2, ft=True,
                                              groups=g, data_shards=2)
            cells.append(("slab_ft_2d", _check(
                f"{tagp}:slab_ft_2d",
                md._ft_slab_fft2_fn(mesh2, "fft", 1e-4, True, g, "data"),
                (x, inj32), mdl_ft2, dtype="complex64"), mdl_ft2))
        for tag, m, mdl in cells:
            got, want = m["total_bytes"], mdl["hlo_bytes"]
            emit(f"fft2_{rr}x{cc}_b{b}_wire_{tag}", got,
                 f"model={want:.0f}B;hlo/model={got/want:.3f};"
                 f"wire={mdl['total_wire']:.0f}B")
        rows.append((rr, cc, b, cells))
    return rows


def run_mesh2d(smoke: bool = True):
    """Grouped ABFT on a 2-D ``data x fft`` mesh: the batch SHARDS over the
    data axis (each data shard owns G/data whole checksum groups), the
    verdict psum stays confined to the fft axis, and transposed order pays
    ZERO all-gathers — all asserted model==HLO with ``data_shards``."""
    if len(jax.devices()) < 4:
        print("# fft_distributed 2-D: needs 4 devices — skipping")
        return []
    mesh = make_mesh((2, 2), ("data", "fft"))
    rng = np.random.default_rng(1)
    rows = []
    for ln, b, g in [(14, 8, 4)] if smoke else [(14, 8, 4), (17, 16, 8)]:
        n = 1 << ln
        x = jnp.asarray((rng.standard_normal((b, n)) +
                         1j * rng.standard_normal((b, n))
                         ).astype(np.complex64))
        for nat in (True, False):
            tag = "nat" if nat else "transposed"
            mdl = dist.collective_volume(n, b, 2, ft=True, groups=g,
                                         data_shards=2, natural_order=nat)
            # the batch never all-gathers: transposed order has no gather
            # at all, natural order only the fft-axis spectrum gather —
            # the checker reads both count and bytes off the model keys
            meas = _check(f"distfft2d_N2^{ln}_b{b}_g{g}:{tag}",
                          dist._ft_dist_fft_fn(mesh, "fft", 1e-4, True,
                                               nat, g, "data"),
                          (x, jnp.zeros((1, 7), jnp.float32)), mdl,
                          dtype="complex64")
            got, want = meas["total_bytes"], mdl["hlo_bytes"]
            emit(f"distfft2d_N2^{ln}_b{b}_g{g}_wire_{tag}", got,
                 f"model={want:.0f}B;hlo/model={got/want:.3f}")
            rows.append((ln, b, g, nat, meas, mdl))
    return rows


def run_plan_reuse(smoke: bool = True):
    """Plan-cached dispatch vs per-call kwarg dispatch, on host-mesh wall
    clock. Both paths execute the SAME cached jitted pipeline (bitwise
    asserted), so the delta is pure dispatch: the legacy path rebuilds the
    spec and re-walks the deprecation/validation/plan-lookup machinery per
    call, while the plan executor is a straight bound call. The cell
    asserts (a) plan-cached dispatch is at least as fast, (b) the
    collective-volume model==HLO invariant holds when lowering THROUGH the
    plan executor (i.e. the single api.py dispatch path did not change the
    collectives), and (c) plan.volume IS that model."""
    import time as _time
    import warnings

    from repro.core.fft import FFTSpec, FTConfig, api, plan
    from repro.kernels import ops

    ndev = min(4, len(jax.devices()))
    shards = 1 << (ndev.bit_length() - 1)
    if shards < 2:
        print("# fft_plan_reuse: single device visible — skipping")
        return []
    mesh = make_mesh((shards,), ("fft",))
    rng = np.random.default_rng(3)
    rows = []
    # small N so wall clock is dispatch-dominated (the quantity under
    # test: both paths run the SAME cached jitted pipeline, so at large N
    # the compute equalizes them and the comparison is vacuous)
    for ln, b in [(10, 8)] if smoke else [(10, 8), (12, 64)]:
        n = 1 << ln
        x = jnp.asarray((rng.standard_normal((b, n)) +
                         1j * rng.standard_normal((b, n))
                         ).astype(np.complex64))
        p = plan(FFTSpec(shape=(b, n), mesh=mesh))
        xs = p.shard(x)

        def measure(fn, iters=20):
            jax.block_until_ready(fn())
            t0 = _time.perf_counter()
            r = None
            for _ in range(iters):
                r = fn()
            jax.block_until_ready(r)
            return (_time.perf_counter() - t0) / iters

        # INTERLEAVED min-of-reps: both paths run the same cached jitted
        # pipeline, so the delta under test is pure python dispatch —
        # alternating the measurements inside one rep loop cancels host
        # load drift, and min is the noise-robust estimator
        legacy_fn = lambda: ops.fft(xs, mesh=mesh)  # noqa: L001 — the legacy dispatch path IS the thing measured
        plan_fn = lambda: p.fft(xs)                 # plan-cached dispatch
        with warnings.catch_warnings():
            warnings.simplefilter("ignore", api.FFTKwargDeprecationWarning)
            y_legacy = legacy_fn()
            tl, tp = [], []
            for _ in range(10):
                tl.append(measure(legacy_fn))
                tp.append(measure(plan_fn))
            t_legacy, t_plan = min(tl), min(tp)
        y_plan = plan_fn()
        np.testing.assert_array_equal(np.asarray(y_plan),
                                      np.asarray(y_legacy))
        # the rewire must not cost throughput: cached dispatch >= legacy.
        # The typical margin (legacy's per-call spec build) is ~1-30% at
        # this size; the generous 1.5x slack keeps this a catastrophic-
        # regression guard (e.g. an executor re-resolving per call) rather
        # than a bet on shared-runner timer stability — the emitted
        # speedup column is the recorded comparison (EXPERIMENTS.md)
        assert t_plan <= t_legacy * 1.5, (t_plan, t_legacy)
        # model==HLO through the plan executor (the api.py dispatch path);
        # lowered with the uncommitted operand, like every other cell —
        # a block-committed input would add the one-off ingest relayout
        # (shard_signals docstring) on top of the pipeline's own traffic
        meas = _check(f"plan_reuse_N2^{ln}_b{b}:fwd", p._fwd, (x,),
                      p.volume, dtype="complex64")
        model = p.volume
        assert model == dist.collective_volume(n, b, shards)
        got, want = meas["total_bytes"], model["hlo_bytes"]
        # ft plan: same contract, grouped verdict traffic included —
        # audited per op kind against the plan's OWN volume dict
        # (plan.volume IS the model, contract (c) above)
        g = 4
        pf = plan(FFTSpec(shape=(b, n), mesh=mesh, ft=FTConfig(groups=g)))
        from repro.core.fft.distributed import _ft_dist_fft_fn
        _check(f"plan_reuse_N2^{ln}_b{b}:ft_g{g}",
               _ft_dist_fft_fn(mesh, "fft", 1e-4, True, True, g, None),
               (x, jnp.zeros((1, 7), jnp.float32)), pf.volume,
               dtype="complex64")
        emit(f"plan_reuse_N2^{ln}_b{b}_x{shards}", t_plan * 1e6,
             f"legacy={t_legacy*1e6:.1f}us;speedup={t_legacy/t_plan:.2f}x;"
             f"hlo/model={got/want:.3f}")
        rows.append((ln, b, t_plan, t_legacy, got, want))
    return rows


def run_overlap(smoke: bool = True):
    """Chunked multi-transaction (double-buffered) pipelines: the overlap
    model == HLO structure, hard-asserted.

    For each chunk count C the chunked 1-D pipeline must lower to exactly
    C all-to-alls whose TOTAL bytes equal ``collective_volume(chunks=C)``
    — chunking re-grains the transfer, it must not add volume — and the
    measured exposed-communication fraction (the largest single
    all-to-all's bytes over the total: only one transaction's transfer has
    no neighbouring local Stockham work to hide behind) must equal the
    model's ``exposed_fraction = 1/C``. Outputs are asserted bitwise
    identical to the bulk (C=1) pipeline — chunking is an execution
    schedule, not a numerical change. The ft cell runs the grouped ABFT
    chunked (whole checksum groups per transaction, each with its own
    verdict psum); the spectral cell the 2C-all-to-all convolution round
    trip. Wall clock per chunk count is emitted UNASSERTED: host-mesh
    collectives are shared-memory memcpys with nothing to overlap, so the
    latency win is a device-network property — the structural assertions
    (count, bytes, exposed fraction, bitwise identity) are the contract.
    """
    ndev = min(4, len(jax.devices()))
    shards = 1 << (ndev.bit_length() - 1)
    if shards < 2:
        print("# fft_overlap: single device visible — skipping")
        return []
    mesh = make_mesh((shards,), ("fft",))
    rng = np.random.default_rng(5)
    rows = []
    for ln, b in [(14, 8)] if smoke else [(14, 8), (17, 16)]:
        n = 1 << ln
        x = jnp.asarray((rng.standard_normal((b, n)) +
                         1j * rng.standard_normal((b, n))
                         ).astype(np.complex64))
        y_bulk = np.asarray(
            dist._dist_fft_fn(mesh, "fft", False, True, None, 1)(x))
        for c in (1, 2, 4):
            if b % c:
                continue
            fn = dist._dist_fft_fn(mesh, "fft", False, True, None, c)
            mdl = dist.collective_volume(n, b, shards, chunks=c)
            # exactly C all-to-alls, unchanged total volume, exposed
            # fraction == 1/C — all enforced inside the checker
            meas = _check(f"overlap_N2^{ln}_b{b}:c{c}", fn, (x,), mdl,
                          check_exposed=True, dtype="complex64")
            a2a = [w for k, w in meas["ops"] if k == "all-to-all"]
            got, want = meas["total_bytes"], mdl["hlo_bytes"]
            exposed = max(a2a) / sum(a2a)
            y_c = np.asarray(fn(x))
            np.testing.assert_array_equal(y_c, y_bulk)
            t_c = timeit(fn, x)
            emit(f"overlap_N2^{ln}_b{b}_c{c}", t_c * 1e6,
                 f"a2a={len(a2a)};exposed={exposed:.3f};"
                 f"model={mdl['exposed_fraction']:.3f};"
                 f"hlo/model={got/want:.3f}")
            rows.append((ln, b, c, t_c, exposed, got, want))
        # grouped ABFT, chunked: whole checksum groups per transaction,
        # one verdict psum each — telemetry AND outputs bitwise identical
        g = min(4, b)
        if g > 1 and b % g == 0:
            inj = jnp.zeros((1, 7), jnp.float32)
            bulk_ft = dist._ft_dist_fft_fn(mesh, "fft", 1e-4, True, True, g,
                                           None, 1)
            chunk_ft = dist._ft_dist_fft_fn(mesh, "fft", 1e-4, True, True, g,
                                            None, 2)
            mdl_ft = dist.collective_volume(n, b, shards, ft=True, groups=g,
                                            chunks=2)
            meas_ft = _check(f"overlap_N2^{ln}_b{b}:ft_g{g}_c2", chunk_ft,
                             (x, inj), mdl_ft, check_exposed=True,
                             dtype="complex64")
            a2a_ft = [w for k, w in meas_ft["ops"] if k == "all-to-all"]
            got, want = meas_ft["total_bytes"], mdl_ft["hlo_bytes"]
            exposed = max(a2a_ft) / sum(a2a_ft)
            rb, rc = bulk_ft(x, inj), chunk_ft(x, inj)
            np.testing.assert_array_equal(np.asarray(rb.y), np.asarray(rc.y))
            np.testing.assert_array_equal(np.asarray(rb.flagged),
                                          np.asarray(rc.flagged))
            emit(f"overlap_N2^{ln}_b{b}_ft_g{g}_c2", got,
                 f"a2a=2;exposed={exposed:.3f};hlo/model={got/want:.3f}")
        # spectral convolution round trip, chunked: 2C all-to-alls
        if b % (shards * 2) == 0:
            vj = jnp.asarray((rng.standard_normal((1, n)) +
                              1j * rng.standard_normal((1, n))
                              ).astype(np.complex64))
            bulk_cv = np.asarray(
                spec._spectral_pair_fn(mesh, "fft", None, False, 1)(x, vj))
            for c in (1, 2):
                fn = spec._spectral_pair_fn(mesh, "fft", None, False, c)
                mdl_cv = dist.spectral_volume(n, b, shards, kernel_batch=1,
                                              chunks=c)
                meas_cv = _check(f"overlap_conv_N2^{ln}_b{b}:c{c}", fn,
                                 (x, vj), mdl_cv, rtol=2e-3,
                                 dtype="complex64")
                a2a_cv = [w for k, w in meas_cv["ops"] if k == "all-to-all"]
                got, want = meas_cv["total_bytes"], mdl_cv["hlo_bytes"]
                np.testing.assert_array_equal(np.asarray(fn(x, vj)), bulk_cv)
                emit(f"overlap_conv_N2^{ln}_b{b}_c{c}", got,
                     f"a2a={len(a2a_cv)};hlo/model={got/want:.3f}")
    return rows


def run_real(smoke: bool = True):
    """Real-input (half-spectrum) pipelines: model == HLO, and the headline
    claim hard-asserted — the rfft2 slab moves <= 0.6x the all-to-all bytes
    of the equivalent C2C fft2 on the same grid (``(C/2 + D) / C`` exactly).

    Cells:

    * rslab forward — ONE all-to-all at the padded half width
      ``Cp = C/2 + D``, zero all-gathers, bytes ==
      ``collective_volume_nd(real=True)`` (measured on the inner jitted
      pipeline: the public wrapper's eager live-bin slice may relayout);
    * grouped-ABFT rslab in fp32 AND fp64 — the Hermitian-symmetric
      checksum grids ride the same transpose at half width plus the
      3G+1-scalar verdict psum;
    * 1-D packed rfft — the half-length C2C transform's bytes ==
      ``collective_volume(real=True)`` (exactly half the C2C model);
    * packed real convolution, 1-D and 2-D — two all-to-alls, zero
      all-gathers, the kernel riding the imaginary part (1-D: forward rows
      carry NO kernel payload at all) resp. the stacked half spectrum
      (2-D), bytes == ``spectral_volume(real=True)`` /
      ``collective_volume_nd(real=True)`` sums.
    """
    ndev = min(4, len(jax.devices()))
    shards = 1 << (ndev.bit_length() - 1)
    if shards < 2:
        print("# fft_real: single device visible — skipping")
        return []
    from repro.core.fft import multidim as md

    mesh = make_mesh((shards,), ("fft",))
    rng = np.random.default_rng(4)
    rows = []
    for rr, cc, b in [(128, 256, 8)] if smoke else [(128, 256, 8),
                                                    (512, 1024, 8)]:
        x = jnp.asarray(rng.standard_normal((b, rr, cc)).astype(np.float32))
        x64 = x.astype(jnp.float64)
        g = 4
        tagp = f"fft_real_{rr}x{cc}_b{b}"
        inj32 = jnp.zeros((1, 7), jnp.float32)
        inj64 = jnp.zeros((1, 7), jnp.float64)
        mdl_r = md.collective_volume_nd((rr, cc), b, shards, real=True)
        mdl_rft = md.collective_volume_nd((rr, cc), b, shards, ft=True,
                                          groups=g, real=True)
        mdl_rft64 = md.collective_volume_nd((rr, cc), b, shards, ft=True,
                                            groups=g, itemsize=16,
                                            real=True)
        # one all-to-all at the padded half width, zero all-gathers, the
        # half spectrum on the wire as c64/c128 — all checker-enforced
        # (the spec dtype of a real plan is its SPECTRUM dtype)
        cells = [
            ("rslab", _check(
                f"{tagp}:rslab", md._rslab_fft2_fn(mesh, "fft", None),
                (x,), mdl_r, dtype="complex64"), mdl_r),
            ("rslab_ft", _check(
                f"{tagp}:rslab_ft",
                md._ft_rslab_fft2_fn(mesh, "fft", 1e-4, True, g, None),
                (x, inj32), mdl_rft, dtype="complex64"), mdl_rft),
            ("rslab_ft_c128", _check(
                f"{tagp}:rslab_ft_c128",
                md._ft_rslab_fft2_fn(mesh, "fft", 1e-4, True, g, None),
                (x64, inj64), mdl_rft64, dtype="complex128"), mdl_rft64),
        ]
        # ---- the headline ratio: rfft2 <= 0.6x fft2 all-to-all bytes ----
        meas_r = cells[0][1]
        meas_c = audit.measure(md._slab_fftn_fn(mesh, "fft", 2, False,
                                                None),
                               x.astype(jnp.complex64))
        ratio = meas_r["total_bytes"] / meas_c["total_bytes"]
        assert ratio <= 0.6, (meas_r["total_bytes"], meas_c["total_bytes"])
        emit(f"rfft2_{rr}x{cc}_b{b}_vs_c2c", meas_r["total_bytes"],
             f"c2c={meas_c['total_bytes']:.0f}B;ratio={ratio:.3f}"
             f";model={(cc // 2 + shards) / cc:.3f}")
        # ---- packed real 2-D convolution: two a2a at the half width -----
        vk = jnp.asarray(rng.standard_normal((1, rr, cc)).astype(np.float32))
        model_cv = _pair_model(
            md.collective_volume_nd((rr, cc), b + 1, shards, real=True),
            md.collective_volume_nd((rr, cc), b, shards, real=True))
        # the round trip lands back on the REAL grid, so the root check
        # runs against f32 (the wire still carries the c64 half spectrum)
        cells.append(("rconv2", _check(
            f"{tagp}:rconv2", md._rconv2_pair_fn(mesh, "fft", None),
            (x, vk), model_cv, dtype="float32"), model_cv))
        # ---- 1-D: packed rfft + packed real convolution -----------------
        n1 = 1 << 14
        half = jnp.asarray((rng.standard_normal((b, n1 // 2)) +
                            1j * rng.standard_normal((b, n1 // 2))
                            ).astype(np.complex64))
        mdl_r1 = dist.collective_volume(n1, b, shards, real=True)
        cells.append(("rfft_packed", _check(
            f"{tagp}:rfft_packed",
            dist._dist_fft_fn(mesh, "fft", False, True), (half,), mdl_r1,
            dtype="complex64"), mdl_r1))
        packed = jnp.asarray((rng.standard_normal((b, n1)) +
                              1j * rng.standard_normal((b, n1))
                              ).astype(np.complex64))
        mdl_rc = dist.spectral_volume(n1, b, shards, kernel_batch=1,
                                      real=True)
        cells.append(("rconv1_packed", _check(
            f"{tagp}:rconv1_packed", spec._spectral_real_fn(mesh, "fft",
                                                            None),
            (packed,), mdl_rc, dtype="complex64"), mdl_rc))
        for tag, m, mdl in cells:
            got, want = m["total_bytes"], mdl["hlo_bytes"]
            emit(f"fft_real_{rr}x{cc}_b{b}_wire_{tag}", got,
                 f"model={want:.0f}B;hlo/model={got/want:.3f};"
                 f"wire={mdl['total_wire']:.0f}B")
        rows.append((rr, cc, b, ratio, cells))
    return rows


if __name__ == "__main__":
    print("name,us_per_call,derived")
    run(smoke=True)
    run_mesh2d(smoke=True)
    run_multidim(smoke=True)
    run_plan_reuse(smoke=True)
    run_overlap(smoke=True)
    run_real(smoke=True)

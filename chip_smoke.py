#!/usr/bin/env python3
"""Chip smoke: drive the FFT service's main path once on a TPU and check it.

    python chip_smoke.py              # one chip: plan C2C, plan FT, ServeRuntime
    python chip_smoke.py --chips 4    # four chips: 1-D pencil + grouped ABFT

One chip runs three phases at full size (512 MiB of complex64 per call):

1. C2C forward and inverse through ``plan(FFTSpec(shape=(B, N)))`` at
   N = 2^10, 2^17 and 2^23 (one, two and three HBM passes);
2. the fused-kernel ABFT through ``plan(FFTSpec(..., ft=FTConfig())).ft_fft``
   at N = 2^10 and 2^13: a clean input flags nothing, and each injected SEU
   is detected, located and corrected;
3. ``ServeRuntime`` under ``RuntimeConfig`` defaults: a few hundred mixed
   ``fft`` / ``spectrum`` / ``real`` / ``ft`` requests at off-grid sizes.

``--chips 4`` runs only the sharded path: the 1-D pencil transform of
``(8, 2^26)`` (1 GiB of input per chip) in natural and transposed order,
bulk and chunked, and the grouped two-side ABFT with four SEUs in four
groups.

Every output is compared with a float64 NumPy reference on the host at the
test suite's tolerance, ``4e-5 * max|ref|``. Any failed check raises and the
script exits non-zero. Printed times are smoke timings of single calls
(the first call includes compilation), not benchmark numbers. The last line
of standard output is one JSON object naming the device.

The compile cache follows ``JAX_COMPILATION_CACHE_DIR`` where it is set and
is kept in the checkout's ``.jax_cache`` otherwise.
"""
from __future__ import annotations

import argparse
import gc
import json
import os
import sys
import time

import numpy as np

TOL = 4e-5   # atol = TOL * max|reference| (tests/conftest.py ATOL)
# (N, B) per call: 2^26 complex64 elements = 512 MiB
C2C_CELLS = ((1 << 10, 1 << 16), (1 << 17, 1 << 9), (1 << 23, 8))
FT_CELLS = ((1 << 10, 1 << 16), (1 << 13, 1 << 13))
SERVE_SIZES = (1000, 1900, 5000, 7000, 40000)
SERVE_WAVES, SERVE_WAVE = 8, 40
PENCIL = (1 << 26, 8)      # (N, B): 1 GiB of input per chip on four chips
SRC = os.path.join(os.path.dirname(os.path.abspath(__file__)), "src")


class SmokeFailure(AssertionError):
    pass


def log(msg: str) -> None:
    print(msg, flush=True)


def check(cond, msg: str) -> None:
    if not cond:
        raise SmokeFailure(msg)


def close(got, want, what: str) -> float:
    """Assert ``|got - want| <= TOL * max|want|``; return the relative error."""
    got = np.asarray(got)
    scale = float(np.abs(want).max())
    err = float(np.abs(got - want).max())
    check(np.isfinite(got).all(), f"{what}: non-finite output")
    check(err <= TOL * scale,
          f"{what}: max error {err:.3e} exceeds {TOL} * max|ref| = "
          f"{TOL * scale:.3e}")
    return err / scale


def timed(fn, *args, **kw):
    import jax
    t0 = time.perf_counter()
    out = jax.block_until_ready(fn(*args, **kw))
    return out, time.perf_counter() - t0


def has_kernel(fn, *args) -> bool:
    """Whether ``fn``'s lowered program holds a compiled Pallas kernel (a
    Mosaic ``tpu_custom_call``), i.e. nothing fell back to interpret mode."""
    import jax
    return "tpu_custom_call" in jax.jit(fn).lower(*args).as_text()


def random_complex(key, shape, sharding=None):
    """Standard complex normal (B, N) complex64, made on the device."""
    import jax
    import jax.numpy as jnp

    def make(k):
        kr, ki = jax.random.split(k)
        return jax.lax.complex(jax.random.normal(kr, shape, jnp.float32),
                               jax.random.normal(ki, shape, jnp.float32))

    return jax.jit(make, out_shardings=sharding)(key)


def sample_rows(b: int) -> list[int]:
    return sorted({0, b // 3, b // 2 + 1, b - 1})


def fetch_rows(x, rows):
    return np.asarray(x[np.asarray(rows)])


def ref_fft(x_rows) -> np.ndarray:
    return np.fft.fft(np.asarray(x_rows, np.complex128), axis=-1)


def hbm_in_use() -> str:
    import jax
    stats = jax.devices()[0].memory_stats() or {}
    return (f"{stats.get('bytes_in_use', 0) / 2**30:.2f} GiB of "
            f"{stats.get('bytes_limit', 0) / 2**30:.2f} GiB")


def rel_max(a, b) -> float:
    import jax.numpy as jnp
    return float(jnp.max(jnp.abs(a - b)) / jnp.max(jnp.abs(b)))


# ---------------------------------------------------------------------------
# one chip
# ---------------------------------------------------------------------------

def phase_c2c(key):
    import jax
    from repro.core.fft.api import FFTSpec, plan
    from repro.core.fft.plan import make_plan

    for n, b in C2C_CELLS:
        key, sub = jax.random.split(key)
        x = random_complex(sub, (b, n))
        p = plan(FFTSpec(shape=(b, n)))
        tag = f"c2c N=2^{n.bit_length() - 1} B={b}"
        check(has_kernel(p.fft, x) and has_kernel(p.ifft, x),
              f"{tag}: executor holds no compiled Pallas kernel")
        y, t_first = timed(p.fft, x)
        y, t_second = timed(p.fft, x)
        rows = sample_rows(b)
        err = close(fetch_rows(y, rows), ref_fft(fetch_rows(x, rows)),
                    f"{tag} forward")
        z, t_inv = timed(p.ifft, y)
        rt = rel_max(z, x)
        check(rt <= TOL, f"{tag}: round trip error {rt:.3e} > {TOL}")
        log(f"smoke {tag}: path=pallas {make_plan(n, batch=b).describe()} "
            f"fwd first call {t_first:.3f}s (compile+run), "
            f"second {t_second:.4f}s; ifft first call {t_inv:.3f}s; "
            f"rel err {err:.2e}, round trip {rt:.2e}")
        del x, y, z
    return key


def phase_ft(key):
    import jax
    import jax.numpy as jnp
    from repro.core.fft.api import FFTSpec, FTConfig, plan
    from repro.core.fft.plan import make_plan

    for n, b in FT_CELLS:
        key, sub = jax.random.split(key)
        x = random_complex(sub, (b, n))
        p = plan(FFTSpec(shape=(b, n), ft=FTConfig()))
        tag = f"ft N=2^{n.bit_length() - 1} B={b}"
        check(has_kernel(lambda v: p.ft_fft(v).y, x),
              f"{tag}: executor holds no compiled Pallas kernel")
        res, t_clean = timed(p.ft_fft, x)
        check(not np.asarray(res.flagged).any() and int(res.corrected) == 0,
              f"{tag}: clean input flagged groups "
              f"{np.flatnonzero(np.asarray(res.flagged)).tolist()} "
              f"(max score {float(jnp.max(res.group_score)):.3e})")
        rows = sample_rows(b)
        err = close(fetch_rows(res.y, rows), ref_fft(fetch_rows(x, rows)),
                    f"{tag} clean")
        bs = make_plan(n, batch=b).bs
        tiles = b // bs
        seus = ((min(1, tiles - 1), 3, 5, 150.0, -40.0),
                (tiles // 2, bs - 1, n - 1, -90.0, 120.0),
                (tiles - 1, bs // 2, n // 3, 200.0, 0.0))
        times = []
        for tile, row, col, er, ei in seus:
            inj = jnp.asarray([tile, row, col, 1, er, ei], jnp.float32)
            r, t = timed(p.ft_fft, x, inject=inj)
            times.append(t)
            sig = tile * bs + row
            flagged = np.asarray(r.flagged)
            check(flagged.sum() == 1,
                  f"{tag}: SEU at signal {sig} flagged {flagged.sum()} "
                  f"groups")
            g = int(np.argmax(flagged))
            check(int(np.asarray(r.location)[g]) == sig,
                  f"{tag}: SEU at signal {sig} located at "
                  f"{int(np.asarray(r.location)[g])}")
            check(int(r.corrected) == 1, f"{tag}: SEU at {sig} not corrected")
            srows = sorted({sig, 0, b - 1})
            close(fetch_rows(r.y, srows), ref_fft(fetch_rows(x, srows)),
                  f"{tag} corrected SEU at signal {sig}")
        log(f"smoke {tag}: path=pallas fused ABFT bs={bs} "
            f"transactions={FTConfig().transactions}; clean first call "
            f"{t_clean:.3f}s (compile+run); SEU calls "
            f"{', '.join(f'{t:.3f}s' for t in times)} (first compiles); "
            f"{len(seus)} SEUs detected, located, corrected; "
            f"rel err {err:.2e}")
        del x, res
    return key


def phase_serve(seed: int):
    import jax.numpy as jnp
    from repro.core.fft.plan import MAX_BLOCK_N
    from repro.serve import Fault, RuntimeConfig, ServeRuntime
    from repro.serve.bucketing import next_pow2

    rng = np.random.default_rng(seed)
    sizes = SERVE_SIZES
    ft_sizes = tuple(s for s in sizes if next_pow2(s) <= MAX_BLOCK_N)
    kinds = ("fft", "spectrum", "real", "ft")
    waves, per_wave = SERVE_WAVES, SERVE_WAVE
    cfg = RuntimeConfig()
    t0 = time.perf_counter()
    faults_sent = 0
    with ServeRuntime(cfg) as rt:
        for w in range(waves):
            sent = []
            faulted = set()
            for i in range(per_wave):
                kind = kinds[i % 4]
                pool = ft_sizes if kind == "ft" else sizes
                size = pool[(i // 4 + w) % len(pool)]
                x = rng.standard_normal(size).astype(np.float32)
                kw = {"op": "spectrum"} if kind == "spectrum" else {
                    "real": kind == "real", "ft": kind == "ft"}
                # the local fused kernel takes one SEU per batch: fault at
                # most one request per ft bucket per wave
                nb = next_pow2(size)
                if kind == "ft" and nb not in faulted:
                    faulted.add(nb)
                    kw["faults"] = Fault(col=int(rng.integers(nb)),
                                         eps_re=float(rng.uniform(50, 300)),
                                         eps_im=float(rng.uniform(-50, 50)))
                    faults_sent += 1
                sent.append((kind, x, rt.submit(x, **kw)))
            for kind, x, h in sent:
                y = h.result(timeout=600.0)
                nb = next_pow2(x.shape[0])
                spec = np.fft.fft(x.astype(np.float64), nb)
                want = {"fft": spec, "ft": spec,
                        "spectrum": np.abs(spec) ** 2 / nb,
                        "real": spec[:nb // 2 + 1]}[kind]
                close(y, want, f"serve {kind} n={x.shape[0]}")
        stats = rt.stats()
        plans = dict(rt._plans)
    wall = time.perf_counter() - t0
    buckets = stats["buckets"]
    total = waves * per_wave
    check(sum(s["completed"] for s in buckets.values()) == total,
          f"serve: {total} requests sent, completion ledger {buckets}")
    check(all(s["failed"] == s["rejected"] == 0 for s in buckets.values()),
          f"serve: failed or rejected requests: {buckets}")
    inj = [s.get("injected", 0) for s in buckets.values()]
    check(sum(inj) == faults_sent, f"serve: {faults_sent} faults sent, "
          f"ledger counts {sum(inj)}")
    for label, s in buckets.items():
        if s.get("injected", 0):
            check(s["injected"] == s["detected"] == s["corrected"],
                  f"serve: FT ledger of {label} is not exact: {s}")
    paths = []
    for key, p in sorted(plans.items(), key=lambda kv: kv[0].label):
        xb = jnp.zeros((cfg.max_batch,) + key.tshape,
                       p._rdtype if p.spec.real else p.spec.dtype)
        if key.ft:
            kernel = has_kernel(lambda v, p=p: p.ft_fft(v).y, xb)
        elif key.op == "spectrum":
            kernel = has_kernel(p.power_spectrum, xb)
        elif key.real:
            kernel = has_kernel(p.rfft, xb)
        else:
            kernel = has_kernel(p.fft, xb)
        if key.op == "fft" and not key.real:
            check(kernel, f"serve: bucket {key.label} holds no compiled "
                          f"Pallas kernel")
        paths.append(f"{key.label}={'pallas' if kernel else 'xla'}")
    log(f"smoke serve: {total} requests ({faults_sent} with an SEU) over "
        f"{len(buckets)} buckets in {wall:.2f}s wall (admission compiles "
        f"included); paths: {' '.join(paths)}; FT ledger injected="
        f"{sum(inj)} detected={sum(s.get('detected', 0) for s in buckets.values())} "
        f"corrected={sum(s.get('corrected', 0) for s in buckets.values())}")


# ---------------------------------------------------------------------------
# four chips
# ---------------------------------------------------------------------------

def phase_pencil(key):
    import concurrent.futures

    import jax
    import jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec
    from repro.core.fft.api import FFTSpec, FTConfig, plan
    from repro.core.fft.distributed import make_dist_plan
    from repro.launch.mesh import make_fft_mesh

    mesh = make_fft_mesh(4)
    check(dict(mesh.shape) == {"fft": 4} and mesh.devices.size == 4,
          f"pencil: expected a 4-device fft mesh, got {dict(mesh.shape)}")
    n, b = PENCIL
    dp = make_dist_plan(n, 4)
    # made on the chips, each holding a contiguous quarter of every signal
    x = random_complex(key, (b, n),
                       NamedSharding(mesh, PartitionSpec(None, "fft")))

    # Each program at this size takes a minute or more to compile, so the
    # five executors compile side by side; they run one at a time (two
    # collective programs in flight at once could interleave on the chips).
    plans = {(nat, ch): plan(FFTSpec(shape=(b, n), mesh=mesh,
                                     natural_order=nat, chunks=ch))
             for nat in (True, False) for ch in (1, 0)}
    pft = plan(FFTSpec(shape=(b, n), mesh=mesh, ft=FTConfig(groups=4)))
    lowered = {v: jax.jit(p.fft).lower(x) for v, p in plans.items()}
    lowered["ft"] = jax.jit(lambda v, i: pft.ft_fft(v, inject=i)).lower(
        x, jnp.zeros((4, 7), jnp.float32))
    t0 = time.perf_counter()
    with concurrent.futures.ThreadPoolExecutor(len(lowered)) as pool:
        futures = {k: pool.submit(low.compile) for k, low in lowered.items()}
        run = {k: f.result() for k, f in futures.items()}
    del futures, lowered   # `run` alone holds each executable from here on
    t_compile = time.perf_counter() - t0
    log(f"smoke pencil: compiled {len(run)} executors in {t_compile:.1f}s "
        f"(in parallel)")

    # two of the four SEU signals of the FT step below
    rows = [2, 5]
    t0 = time.perf_counter()
    want = ref_fft(fetch_rows(x, rows))
    log(f"smoke pencil: float64 NumPy reference of {len(rows)} rows in "
        f"{time.perf_counter() - t0:.1f}s")
    for natural, chunks in list(plans):
        p = plans.pop((natural, chunks))
        exe = run.pop((natural, chunks))
        tag = (f"pencil N=2^{n.bit_length() - 1} B={b} "
               f"{'natural' if natural else 'transposed'} "
               f"chunks={chunks}->{p.chunks}")
        y, t_first = timed(exe, x)
        y, t_second = timed(exe, x)
        got = fetch_rows(y, rows)
        if not natural:   # y[k1*N2 + k2] = X[k1 + N1*k2]
            got = got.reshape(len(rows), dp.n1, dp.n2).transpose(
                0, 2, 1).reshape(len(rows), n)
        err = close(got, want, tag)
        log(f"smoke {tag}: path=xla shard_map ({dp.n1}x{dp.n2} split) "
            f"first run {t_first:.4f}s, second {t_second:.4f}s; "
            f"rel err {err:.2e}")
        # a natural-order output is replicated: 4 GiB on every chip
        del y, exe
    # The FT program takes 10.5 GiB of scratch plus a replicated 4 GiB
    # output on each chip's 16 GiB of HBM, so only x and the FT executor
    # may be left when it loads.
    gc.collect()
    log(f"smoke pencil: HBM in use on chip 0 before the FT step: "
        f"{hbm_in_use()}")
    pencil_ft(run.pop("ft"), x, want, rows,
              f"pencil ft N=2^{n.bit_length() - 1} B={b} G=4")


def pencil_ft(ft, x, want, rows, tag: str) -> None:
    """Grouped two-side ABFT on the compiled four-chip executor ``ft``
    (``ft(x, inject)``): a clean run, then four SEUs in four groups."""
    import jax.numpy as jnp

    mag = 1000.0
    inj_off = jnp.zeros((4, 7), jnp.float32)   # four disabled descriptors
    inj4 = jnp.asarray([[0, 1, 3, 1, 1, mag, mag / 4],
                        [1, 2, 5, 2, 1, -mag / 2, mag],
                        [2, 5, 7, 3, 1, mag, -mag / 3],
                        [3, 6, 2, 0, 1, mag / 2, mag / 2]], jnp.float32)
    res, t_clean = timed(ft, x, inj_off)
    check(not np.asarray(res.flagged).any(),
          f"{tag}: clean input flagged groups "
          f"{np.flatnonzero(np.asarray(res.flagged)).tolist()}")
    close(fetch_rows(res.y, rows), want, f"{tag} clean")
    # free the clean result (4 GiB on each chip) before the next call: the
    # program may have to load again, and its 10.5 GiB of scratch did not
    # fit beside it
    del res
    res, t_seu = timed(ft, x, inj4)
    check(np.asarray(res.flagged).all() and np.asarray(res.correctable).all(),
          f"{tag}: 4 SEUs in 4 groups: flagged "
          f"{np.asarray(res.flagged).tolist()}, correctable "
          f"{np.asarray(res.correctable).tolist()}")
    check(np.asarray(res.location).tolist() == [1, 2, 5, 6],
          f"{tag}: located {np.asarray(res.location).tolist()}")
    check(int(res.corrected) == 4, f"{tag}: corrected {int(res.corrected)}")
    err = close(fetch_rows(res.y, rows), want, f"{tag} corrected")
    log(f"smoke {tag}: path=xla shard_map grouped two-side ABFT; clean run "
        f"{t_clean:.4f}s, 4-SEU run {t_seu:.4f}s; 4 SEUs in 4 groups "
        f"detected, located, corrected in one pass; rel err {err:.2e}")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="1: plan, FT and serving phases on one chip; "
                         "4: the sharded pencil phase only")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    import jax
    devices = jax.devices()
    if devices[0].platform != "tpu":
        print(f"chip_smoke: no TPU found (JAX platform "
              f"{devices[0].platform!r}); this smoke runs only on the chip",
              file=sys.stderr)
        return 1
    if len(devices) < args.chips:
        print(f"chip_smoke: --chips {args.chips} needs {args.chips} TPU "
              f"devices, found {len(devices)}", file=sys.stderr)
        return 1
    if not os.path.isdir(os.path.join(SRC, "repro")):
        print(f"chip_smoke: the repro package is not at {SRC}",
              file=sys.stderr)
        return 1
    sys.path.insert(0, SRC)
    from repro.launch.compile_cache import enable_compile_cache
    cache = enable_compile_cache()
    dev = devices[0]
    log(f"# chip_smoke on {dev.device_kind} x{len(devices)}; compile cache "
        f"{cache}; timings are smoke timings, not benchmark numbers")

    key = jax.random.PRNGKey(args.seed)
    if args.chips == 4:
        phase_pencil(key)
    else:
        key = phase_c2c(key)
        key = phase_ft(key)
        phase_serve(args.seed)
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices)}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())

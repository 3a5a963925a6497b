"""Serving driver: batched greedy decode against the KV cache, plus a
batched sharded-FFT endpoint backed by the distributed transform.

    PYTHONPATH=src python -m repro.launch.serve --arch gemma3-1b --preset tiny \
        --batch 4 --prompt-len 16 --gen 32

    XLA_FLAGS=--xla_force_host_platform_device_count=4 \
    PYTHONPATH=src python -m repro.launch.serve --mode fft \
        --fft-n 65536 --batch 8 --fft-shards 4 --ft

    # the same worker described by ONE consolidated plan spec (the worker
    # builds a single FFTPlan from it at startup; the --fft-* flags are
    # sugar that provide the defaults the spec string overrides)
    XLA_FLAGS=--xla_force_host_platform_device_count=4 \
    PYTHONPATH=src python -m repro.launch.serve --mode fft \
        --fft-spec "n=65536,batch=8,shards=4,ft=1,groups=4"

    # transposed-order convolution on a 2-D batch x pencil mesh
    XLA_FLAGS=--xla_force_host_platform_device_count=4 \
    PYTHONPATH=src python -m repro.launch.serve --mode fft --fft-op convolve \
        --fft-n 16384 --batch 8 --fft-shards 2 --fft-data 2

    # distributed 2-D FFT (slab|pencil|auto) with grouped ABFT on the grids
    XLA_FLAGS=--xla_force_host_platform_device_count=4 \
    PYTHONPATH=src python -m repro.launch.serve --mode fft --fft-dims 2 \
        --fft-rows 256 --fft-cols 512 --batch 8 --fft-shards 4 \
        --fft-decomp slab --ft

    # the multi-tenant serving runtime (repro.serve): spec bucketing +
    # deadline batching over the plan cache, one string describing plan
    # geometry AND scheduler policy
    XLA_FLAGS=--xla_force_host_platform_device_count=4 \
    PYTHONPATH=src python -m repro.launch.serve --mode serve \
        --fft-spec "n=4096,shards=4,workers=2,max_batch=8,deadline_ms=2"
"""
from __future__ import annotations

import argparse
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs import get_config, get_smoke_config
from repro.configs.base import ParallelConfig, RunConfig
from repro.models import Model
from repro.train import make_serve_step


def decode(model: Model, params, prompts: jax.Array, gen: int,
           max_len: int | None = None, schedule=None):
    """Prefill via repeated decode steps, then generate ``gen`` tokens.

    ``schedule`` is an optional :class:`~repro.core.ft.FaultSchedule`: each
    host-loop step arms its GEMM fault descriptor
    (:meth:`~repro.core.ft.FaultSchedule.for_step_gemm`) into the jitted
    step — the descriptor is a traced array of fixed shape, so the armed
    and clean steps share ONE compiled program. Returns ``(tokens,
    FTStats)`` when a schedule is given (online ABFT telemetry summed over
    steps), else just ``tokens``.
    """
    from repro.core.ft import FTStats

    cfg = model.cfg
    b, p = prompts.shape
    max_len = max_len or (p + gen)
    run = RunConfig(model=cfg)
    step_fn = jax.jit(make_serve_step(model, run))
    cache = model.init_cache(batch=b, max_len=max_len)
    stats = FTStats.zeros()

    def inj(step):
        return None if schedule is None else schedule.for_step_gemm(step)

    def fold(aux):
        return stats.merge(FTStats(
            detected=aux["ft_flagged"], corrected=aux["ft_corrected"],
            max_score=aux["ft_max_score"],
            skipped_updates=jnp.zeros((), jnp.float32)))

    # teacher-forced prefill (decode-path; exercises the cache end-to-end)
    nxt = prompts[:, :1]
    for i in range(p):
        tok = prompts[:, i:i + 1]
        nxt, cache, aux = step_fn(params, cache, tok, jnp.int32(i), inj(i))
        stats = fold(aux)
    out = [nxt]
    for j in range(gen - 1):
        nxt, cache, aux = step_fn(params, cache, nxt, jnp.int32(p + j),
                                  inj(p + j))
        stats = fold(aux)
        out.append(nxt)
    toks = jnp.concatenate(out, axis=1)
    return toks if schedule is None else (toks, stats)


# The request-description layer lives in repro.serve.specs (shared with
# the multi-tenant runtime, repro.serve.runtime); these re-exports keep
# the historical launch.serve surface working.
from repro.serve.specs import (SPEC_KEYS as _SPEC_KEYS,  # noqa: E402
                               _ft_telemetry, _parse_bool, _parse_chunks,
                               apply_fft_spec_arg, build_fft_spec,
                               serve_plan)

SPEC_KEYS = _SPEC_KEYS


def serve_fft(x, *, shards: int | None = None, data: int = 1,
              ft: bool = False, threshold: float = 1e-4,
              op: str = "fft", kernel=None, mode: str = "same",
              natural_order: bool | None = None,
              groups: int | None = None, group_size: int | None = None,
              recompute_uncorrectable: bool = True,
              dims: int = 1, decomp: str = "auto", real: bool = False,
              chunks: int = 1):
    """Batched sharded FFT endpoint: one request = one (B, N) batch
    (``dims=2``: one (B, R, C) grid batch).

    Compat sugar over the plan API: builds the ``fft``-axis mesh — 2-D
    ``data x fft`` when ``data > 1`` — resolves the request into an
    :class:`~repro.core.fft.api.FFTSpec` via :func:`build_fft_spec`,
    LRU-hits the plan, and serves through :func:`serve_plan`. A production
    worker should build the plan ONCE at startup (what ``--mode fft`` now
    does) instead of re-describing it per request; the behavior is
    identical either way thanks to the plan cache.

    With ``ft=True`` the sharded grouped two-side ABFT runs online (one
    tolerated SEU per checksum group per request; multi-fault groups are
    recomputed in place when ``recompute_uncorrectable``, the FTPolicy
    default) and the telemetry carries the per-group verdict counts.
    Spectral requests (``op="convolve" | "correlate" | "spectrum"``) stay
    in the transposed digit order end-to-end — two all-to-alls, zero
    all-gathers (see core.fft.spectral / multidim).
    """
    from repro.core.fft import api
    from repro.launch.mesh import make_fft_mesh

    x = jnp.asarray(x)
    if dims not in (1, 2):
        raise ValueError(f"dims must be 1 or 2, got {dims}")
    if dims == 2 and x.ndim != 3:
        raise ValueError(f"dims=2 expects (B, R, C) batches, got {x.shape}")
    mesh = make_fft_mesh(shards, data)
    kshape = jnp.asarray(kernel).shape if kernel is not None else None
    if real and jnp.issubdtype(x.dtype, jnp.complexfloating):
        raise ValueError(f"real=True serves real-valued traffic, "
                         f"got {x.dtype}")
    if jnp.issubdtype(x.dtype, jnp.complexfloating):
        dt = x.dtype
    else:
        dt = jnp.complex128 if (real and x.dtype == jnp.float64) \
            else jnp.complex64
    spec = build_fft_spec(
        x.shape, mesh=mesh, op=op, kernel_shape=kshape, dims=dims,
        decomp=decomp, ft=ft, threshold=threshold, groups=groups,
        group_size=group_size,
        recompute_uncorrectable=recompute_uncorrectable,
        natural_order=natural_order, dtype=dt, real=real, chunks=chunks)
    return serve_plan(api.plan(spec), x, op=op, kernel=kernel, mode=mode)


def _main_fft(args):
    from repro.core.fft import api
    from repro.launch.mesh import make_fft_mesh

    if args.fft_spec:
        apply_fft_spec_arg(args, args.fft_spec)
    rng = np.random.default_rng(0)
    kernel = None
    if args.fft_dims == 2:
        shape = (args.batch, args.fft_rows, args.fft_cols)
        size_tag = f"{args.fft_rows}x{args.fft_cols}"
    else:
        shape = (args.batch, args.fft_n)
        size_tag = f"{args.fft_n}"
    if args.fft_op in ("convolve", "correlate"):
        x = rng.standard_normal(shape).astype(np.float32)
        kshape = ((args.fft_kernel_n, args.fft_kernel_n)
                  if args.fft_dims == 2 else (args.fft_kernel_n,))
        kernel = rng.standard_normal(kshape).astype(np.float32)
    elif args.fft_real:
        x = rng.standard_normal(shape).astype(np.float32)
    else:
        x = (rng.standard_normal(shape) +
             1j * rng.standard_normal(shape)).astype(np.complex64)
    # ONE plan per worker, built at startup: every request dispatches
    # through its cached executors (the cuFFT plan-once/exec-hot contract)
    mesh = make_fft_mesh(args.fft_shards, args.fft_data)
    spec = build_fft_spec(
        shape, mesh=mesh, op=args.fft_op,
        kernel_shape=kernel.shape if kernel is not None else None,
        dims=args.fft_dims, decomp=args.fft_decomp, ft=args.ft,
        threshold=args.fft_threshold, groups=args.fft_groups,
        natural_order=False if args.transposed else None,
        real=args.fft_real, chunks=args.fft_chunks)
    p = api.plan(spec)
    print(f"# {p}")
    call = lambda: serve_plan(p, x, op=args.fft_op, kernel=kernel)
    y, info = call()  # warmup
    t0 = time.time()
    for _ in range(args.fft_iters):
        y, info = call()
        jax.block_until_ready(y)
    dt = (time.time() - t0) / args.fft_iters
    y = np.asarray(y)
    nfft = int(np.prod(shape[1:]))
    if args.fft_real:
        fwd = np.fft.rfft2 if args.fft_dims == 2 else np.fft.rfft
    else:
        fwd = np.fft.fft2 if args.fft_dims == 2 else np.fft.fft
    if args.fft_op == "convolve":
        if args.fft_dims == 2:
            rr = shape[1] + kshape[0] - 1
            cc = shape[2] + kshape[1] - 1
            full = np.real(np.fft.ifft2(np.fft.fft2(x, s=(rr, cc)) *
                                        np.fft.fft2(kernel, s=(rr, cc))))
            r0 = (min(shape[1], kshape[0]) - 1) // 2
            c0 = (min(shape[2], kshape[1]) - 1) // 2
            ref = full[:, r0:r0 + max(shape[1], kshape[0]),
                       c0:c0 + max(shape[2], kshape[1])]
        else:
            ref = np.stack([np.convolve(r, kernel, "same") for r in x])
    elif args.fft_op == "correlate":
        ref = np.stack([np.correlate(r, kernel, "same") for r in x])
    elif args.fft_op == "spectrum":
        ref = np.abs(fwd(x)) ** 2 / nfft
        if info.get("order") == "transposed":
            # order-agnostic comparison over the flattened bins
            ref = np.sort(ref.reshape(ref.shape[0], -1), axis=-1)
            y = np.sort(y.reshape(y.shape[0], -1), axis=-1)
    elif args.transposed and info.get("order") == "transposed":
        ref = y   # digit-permuted; correctness is covered by the test suite
    else:
        ref = fwd(x)
    err = np.abs(y - ref).max() / (np.abs(ref).max() + 1e-30)
    print(f"{args.fft_op} batch={args.batch} N={size_tag} {info} "
          f"{dt*1e3:.2f}ms/req rel_err={err:.2e}")


def _main_serve(args):
    """Multi-tenant serving worker (``--mode serve``): stand up a
    :class:`~repro.serve.ServeRuntime` over the mesh, drive it with a
    short mixed-tenant self-test workload, and print the per-bucket
    telemetry — the operational smoke of the runtime the benchmark
    (benchmarks/fft_serving.py) measures properly."""
    import json

    from repro.launch.mesh import make_fft_mesh
    from repro.serve import RuntimeConfig, ServeRuntime

    if args.fft_spec:
        apply_fft_spec_arg(args, args.fft_spec)
    mesh = make_fft_mesh(args.fft_shards, 1)
    cfg = RuntimeConfig(
        max_batch=args.serve_max_batch, deadline_ms=args.serve_deadline_ms,
        queue_depth=args.serve_queue_depth, workers=args.serve_workers,
        timeout_ms=args.serve_timeout_ms, chunks=max(args.fft_chunks, 1))
    rng = np.random.default_rng(0)
    n = args.fft_n
    t0 = time.time()
    with ServeRuntime(cfg, mesh=mesh if mesh.shape.get("fft", 1) > 1
                      else None) as rt:
        handles = []
        for i in range(args.serve_requests):
            # mixed tenants: off-grid sizes, three request kinds
            sz = (n, max(2, n - n // 4), max(2, n // 2 + 1))[i % 3]
            x = rng.standard_normal(sz).astype(np.float32)
            kind = i % 4
            kw = ({"op": "fft"}, {"op": "spectrum"},
                  {"op": "fft", "real": True},
                  {"op": "fft", "ft": True})[kind if not args.ft else 3]
            handles.append(rt.submit(x, **kw))
        for h in handles:
            h.result(timeout=300.0)
        stats = rt.stats()
    dt = time.time() - t0
    print(f"# served {len(handles)} requests in {dt:.2f}s "
          f"({len(handles) / dt:.0f} rps) over "
          f"{dict(mesh.shape) if mesh is not None else 'single device'}")
    print(json.dumps(stats["buckets"], indent=2, sort_keys=True))
    print(f"# plan cache: {stats['plan_cache']}")


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--mode", default="lm", choices=["lm", "fft", "serve"])
    ap.add_argument("--arch", default="gemma3-1b")
    ap.add_argument("--preset", default="tiny", choices=["tiny", "full"])
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=16)
    ap.add_argument("--gen", type=int, default=32)
    ap.add_argument("--fft-n", type=int, default=1 << 16)
    ap.add_argument("--fft-shards", type=int, default=None)
    ap.add_argument("--fft-data", type=int, default=1,
                    help="batch-parallel mesh axis size (2-D data x fft mesh)")
    ap.add_argument("--fft-op", default="fft",
                    choices=["fft", "convolve", "correlate", "spectrum"],
                    help="spectral ops stay in transposed order end-to-end")
    ap.add_argument("--fft-dims", type=int, default=1, choices=[1, 2],
                    help="2 serves (batch, rows, cols) grids through the "
                         "multidim subsystem (core.fft.multidim)")
    ap.add_argument("--fft-decomp", default="auto",
                    choices=["auto", "slab", "pencil"],
                    help="multidim decomposition; auto = the "
                         "collective-volume heuristic (choose_decomp)")
    ap.add_argument("--fft-rows", type=int, default=256,
                    help="grid rows for --fft-dims 2")
    ap.add_argument("--fft-cols", type=int, default=256,
                    help="grid cols for --fft-dims 2")
    ap.add_argument("--fft-kernel-n", type=int, default=63,
                    help="kernel length for convolve/correlate")
    ap.add_argument("--fft-groups", type=int, default=None,
                    help="ABFT checksum groups (one tolerated SEU per "
                         "group); default: one group per data shard")
    ap.add_argument("--fft-threshold", type=float, default=1e-4,
                    help="ABFT detection threshold")
    ap.add_argument("--fft-chunks", type=_parse_chunks, default=1,
                    help="multi-transaction overlap: split the batch into "
                         "this many chunked all-to-all transactions (each "
                         "one's collective hides behind the next one's "
                         "local Stockham passes); 'auto' lets the plan "
                         "pick from the collective-volume model")
    ap.add_argument("--fft-spec", default=None,
                    help="consolidated plan description, e.g. "
                         "'n=65536,batch=8,shards=4,data=2,ft=1,groups=4' "
                         "(keys: " + ", ".join(sorted(_SPEC_KEYS)) + "); "
                         "overrides the individual --fft-* flags — the "
                         "worker builds ONE FFTPlan from it at startup")
    ap.add_argument("--fft-iters", type=int, default=5)
    ap.add_argument("--serve-workers", type=int, default=2,
                    help="serve mode: executor worker threads (sharded "
                         "dispatch is serialized on the mesh lock; extra "
                         "workers overlap batch assembly/scatter)")
    ap.add_argument("--serve-max-batch", type=int, default=8,
                    help="serve mode: coalescing limit = the bucket plans' "
                         "batch dimension")
    ap.add_argument("--serve-deadline-ms", type=float, default=2.0,
                    help="serve mode: max time a request waits for batch "
                         "companions before its partial batch closes")
    ap.add_argument("--serve-queue-depth", type=int, default=64,
                    help="serve mode: bounded pending-request queue "
                         "(backpressure: overflow is rejected, not "
                         "buffered)")
    ap.add_argument("--serve-timeout-ms", type=float, default=None,
                    help="serve mode: fail requests unbatched past this "
                         "age (default: never)")
    ap.add_argument("--serve-requests", type=int, default=64,
                    help="serve mode: self-test workload size")
    ap.add_argument("--transposed", action="store_true",
                    help="keep fft/spectrum output in transposed digit order")
    ap.add_argument("--fft-real", action="store_true",
                    help="serve real-valued traffic through the packed "
                         "half-spectrum pipelines (rfft/rfft2, one-sided "
                         "spectrum, packed convolve) — ~half the C2C "
                         "collective bytes on a mesh")
    ap.add_argument("--ft", action="store_true",
                    help="FFT mode: run the sharded two-side ABFT online. "
                         "LM mode: protect every linear with the checked "
                         "GEMM plan (core.gemm) and inject a demo "
                         "FaultSchedule of SEUs that the decode must "
                         "detect and correct online")
    ap.add_argument("--ft-threshold", type=float, default=1e-3,
                    help="LM-mode ABFT detection threshold (relative "
                         "per-column checksum divergence)")
    args = ap.parse_args()
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()

    if args.mode == "fft":
        _main_fft(args)
        return
    if args.mode == "serve":
        _main_serve(args)
        return

    cfg = (get_config if args.preset == "full" else get_smoke_config)(
        args.arch)
    schedule = None
    if args.ft:
        import dataclasses as _dc

        from repro.core.ft import FaultSchedule

        cfg = _dc.replace(cfg, ft=_dc.replace(
            cfg.ft, protect_linears=True, threshold=args.ft_threshold))
        # two SEUs the online ABFT must catch: one mid-prefill, one
        # mid-generation — (step, site, row<batch, col, eps_re, eps_im)
        schedule = FaultSchedule(entries=(
            (min(2, args.prompt_len - 1), 0, args.batch - 1, 3, 275.0, 0.0),
            (args.prompt_len + 1, 1, 0, 11, -310.0, 0.0),
        ))
    model = Model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    rng = np.random.default_rng(0)
    prompts = jnp.asarray(
        rng.integers(0, cfg.vocab_size, (args.batch, args.prompt_len)),
        jnp.int32)
    t0 = time.time()
    res = decode(model, params, prompts, args.gen, schedule=schedule)
    toks, stats = res if args.ft else (res, None)
    dt = time.time() - t0
    rate = args.batch * args.gen / dt
    print(f"generated {toks.shape} in {dt:.2f}s ({rate:.1f} tok/s)")
    if stats is not None:
        print(f"ft: injected={schedule.num_faults} "
              f"detected={float(stats.detected):.0f} "
              f"corrected={float(stats.corrected):.0f} "
              f"max_score={float(stats.max_score):.3f} "
              f"backend={cfg.ft.gemm_backend}")
    print(np.asarray(toks[:, :16]))


if __name__ == "__main__":
    main()

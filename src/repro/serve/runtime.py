"""Multi-tenant FFT serving runtime: bucketed admission, deadline batching,
and a worker pool over the cached plan executors.

Architecture (the layer ``launch.serve --mode serve`` is a thin CLI over)::

    client threads          scheduler                worker pool
    ─────────────          ──────────               ───────────
    submit(x, op=..) ──> SpecBucketer.key_for
                         admission: one FFTPlan per bucket (warmup once)
                         DeadlineBatcher.submit ──> per-bucket pending
                               │ close on max_batch or deadline_ms
                               ▼
                         ready batches ──────────> N worker threads:
                                                   pad + stack payloads,
                                                   serve_plan(plan, xb),
                                                   scatter rows to handles,
                                                   telemetry per bucket

Requests are SINGLE signals (``(n,)`` or ``(r, c)``); the runtime pads each
to its bucket's canonical transform shape (zero extension — the
``np.fft.fft(x, n)`` contract, see ``bucketing``) and zero-fills empty
batch slots. One plan per bucket is built and warmed at admission, so the
steady state never traces or resolves; the shared plan LRU
(``core.plan``, thread-safe) is what keeps restarted or evicted buckets
cheap to re-admit.

``ft=True`` buckets run the ABFT pipeline online: per-request SEU
descriptors (tests / fault-injection campaigns) ride
:class:`~repro.serve.scheduler.ServeRequest.inject` with signal indices
relative to the request, and the runtime offsets them to batch rows; the
per-bucket verdict telemetry (injected/detected/corrected/uncorrectable)
aggregates over every batch the bucket executed.
"""
from __future__ import annotations

import dataclasses
import threading

import numpy as np
from jax.profiler import TraceAnnotation

from repro.core.plan import FTConfig, plan_cache_info
from repro.serve.bucketing import BucketKey, SpecBucketer
from repro.serve.scheduler import (Batch, DeadlineBatcher, QueueFullError,
                                   RequestHandle, RequestTimeoutError,
                                   RuntimeClosedError, ServeRequest)
from repro.serve.specs import serve_plan
from repro.serve.telemetry import Telemetry

__all__ = ["RuntimeConfig", "ServeRuntime", "Fault"]


@dataclasses.dataclass(frozen=True)
class Fault:
    """One injected SEU, addressed relative to the carrying request:
    perturb the request's signal at transform coordinate (``row``,
    ``col``) by ``eps_re + i*eps_im`` inside the protected region. The
    runtime translates it to the executing pipeline's descriptor format
    (fused local kernel or sharded grouped ABFT) and to the request's
    batch row."""

    col: int = 1
    row: int = 1
    eps_re: float = 200.0
    eps_im: float = 0.0


@dataclasses.dataclass(frozen=True)
class RuntimeConfig:
    """Scheduler + pool policy for one :class:`ServeRuntime`.

    ``max_batch`` is both the coalescing limit and every bucket plan's
    batch dimension; ``deadline_ms`` bounds how long a lone request waits
    for companions; ``queue_depth`` is the backpressure bound over ALL
    pending requests; ``timeout_ms`` (None = never) fails requests that
    age out unbatched. ``ft`` is the FTConfig attached to ``ft=True``
    buckets at admission."""

    max_batch: int = 8
    deadline_ms: float = 2.0
    queue_depth: int = 64
    workers: int = 2
    timeout_ms: float | None = None
    chunks: int = 1
    ft: FTConfig = FTConfig(threshold=1e-4, correct=True,
                            recompute_uncorrectable=True)

    def __post_init__(self):
        if self.workers < 1:
            raise ValueError(f"workers must be >= 1, got {self.workers}")


class ServeRuntime:
    """The serving runtime: ``submit`` returns a
    :class:`~repro.serve.scheduler.RequestHandle`; ``close`` drains."""

    def __init__(self, config: RuntimeConfig | None = None, *, mesh=None):
        self.config = config or RuntimeConfig()
        self.mesh = mesh
        cfg = self.config
        self.bucketer = SpecBucketer(mesh=mesh, max_batch=cfg.max_batch,
                                     chunks=cfg.chunks)
        self.telemetry = Telemetry()
        self.batcher = DeadlineBatcher(
            max_batch=cfg.max_batch, deadline_ms=cfg.deadline_ms,
            queue_depth=cfg.queue_depth, timeout_ms=cfg.timeout_ms,
            on_timeout=self.telemetry.record_timeout)
        self._plans: dict[BucketKey, object] = {}
        self._admission = threading.Lock()
        # collective programs rendezvous across ALL mesh devices: two
        # worker threads launching sharded executors concurrently would
        # interleave their participants and deadlock the all-to-all, so
        # sharded dispatch is serialized (workers still overlap batch
        # assembly/scatter with the running collective)
        self._mesh_lock = threading.Lock()
        self._closed = False
        self._workers = [
            threading.Thread(target=self._worker_loop,
                             name=f"serve-worker-{i}", daemon=True)
            for i in range(cfg.workers)]
        for t in self._workers:
            t.start()

    # -- admission ---------------------------------------------------------

    def admit(self, key: BucketKey):
        """Resolve (once) the bucket's plan: build the padded batched
        FFTSpec, plan it through the shared cache, and warm the executor
        with a zero batch so the first real request never traces. Raises
        with the spec's validation error when the bucket is infeasible on
        this mesh — admission is where bad geometry surfaces."""
        p = self._plans.get(key)
        if p is not None:
            return p
        with self._admission:
            p = self._plans.get(key)
            if p is not None:
                return p
            from repro.core.fft import api
            spec = self.bucketer.spec_for(
                key, ft_config=self.config.ft if key.ft else None)
            p = api.plan(spec)
            xb = np.zeros((self.config.max_batch,) + key.tshape,
                          dtype=self._payload_dtype(p))
            if p.sharded:                       # see _mesh_lock
                with self._mesh_lock:
                    serve_plan(p, xb, op=key.op)    # warmup: trace + compile
            else:
                serve_plan(p, xb, op=key.op)
            self._plans[key] = p
            return p

    def _payload_dtype(self, plan) -> np.dtype:
        return np.dtype(plan._rdtype if plan.spec.real
                        else plan.spec.dtype)

    # -- client API --------------------------------------------------------

    def submit(self, x, *, op: str = "fft", real: bool = False,
               ft: bool = False, faults=None,
               timeout_ms: float | None = None) -> RequestHandle:
        """Admit one single-signal request; returns its handle.

        ``faults`` (ft buckets only): a :class:`Fault` or sequence of them
        to inject into THIS request's rows — the fault-injection campaign
        interface the serving benchmark drives from a ``FaultSchedule``.
        """
        if self._closed:
            raise RuntimeClosedError("serve runtime is closed")
        x = np.asarray(x)
        key = self.bucketer.key_for(x.shape, x.dtype, op=op, real=real,
                                    ft=ft)
        if faults is not None and not ft:
            raise ValueError("faults= requires an ft=True bucket")
        faults = ((faults,) if isinstance(faults, Fault)
                  else tuple(faults or ()))
        self.admit(key)
        handle = RequestHandle()
        req = ServeRequest(key=key, x=x, handle=handle, inject=faults,
                           timeout_ms=timeout_ms)
        self.telemetry.record_submit(key, injected=len(faults))
        try:
            self.batcher.submit(req)
        except (QueueFullError, RuntimeClosedError):
            self.telemetry.record_reject(key)
            raise
        return handle

    # -- worker pool -------------------------------------------------------

    def _worker_loop(self):
        while True:
            batch = self.batcher.next_batch()
            if batch is None:
                return
            try:
                # profiler spans (no-ops unless a trace is on): one batch
                # from its taking to its last result; inside _execute, the
                # executor call up to its async result (serve.dispatch) and
                # the device wait and copy to the host (serve.fetch)
                with TraceAnnotation("serve.execute"):
                    self._execute(batch)
            except BaseException as e:
                for r in batch.requests:
                    if not r.handle.done():
                        r.handle.set_error(e)
                self.telemetry.record_failed(batch.key, len(batch.requests))

    def _execute(self, batch: Batch):
        key = batch.key
        plan = self._plans[key]
        cfg = self.config
        fill = len(batch.requests)
        xb = np.zeros((cfg.max_batch,) + key.tshape,
                      dtype=self._payload_dtype(plan))
        pad = payload = 0
        for i, r in enumerate(batch.requests):
            sig = np.asarray(r.x)
            if key.rank == 1:
                xb[i, :sig.shape[0]] = sig
            else:
                xb[i, :sig.shape[0], :sig.shape[1]] = sig
            pad += self.bucketer.pad_elems(key, sig.shape)
            payload += int(sig.size)
        pad += (cfg.max_batch - fill) * int(np.prod(key.tshape,
                                                    dtype=np.int64))
        inject, bs = self._build_inject(plan, batch)
        if plan.sharded:
            with self._mesh_lock:
                with TraceAnnotation("serve.dispatch"):
                    y, info = serve_plan(plan, xb, op=key.op, inject=inject)
                with TraceAnnotation("serve.fetch"):
                    y = np.asarray(y)
        else:
            with TraceAnnotation("serve.dispatch"):
                if bs is not None:
                    y, info = self._ft_with_bs(plan, xb, inject, bs)
                else:
                    y, info = serve_plan(plan, xb, op=key.op, inject=inject)
            with TraceAnnotation("serve.fetch"):
                y = np.asarray(y)
        self.telemetry.record_batch(
            key, fill=fill, slots=cfg.max_batch, pad_elems=pad,
            payload_elems=payload)
        if key.ft:
            self._record_ft(key, info)
        base = {"bucket": key.label, "nfft": key.tshape,
                "batch_fill": fill}
        for i, r in enumerate(batch.requests):
            r.handle.set_result(y[i], {**base, **info})
            self.telemetry.record_done(key, latency_s=r.handle.latency_s,
                                       queue_s=r.handle.queue_s)

    def _ft_with_bs(self, plan, xb, inject, bs):
        """Local fused-kernel ft path with the runtime's fixed tile size
        (one tile = the whole batch), so injected rows address tiles
        deterministically."""
        import jax.numpy as jnp
        res = plan.ft_fft(plan.shard(xb), inject=inject, bs=bs)
        flagged = np.asarray(res.flagged)
        g = int(np.argmax(flagged)) if flagged.any() else -1
        info = {"op": "fft", "shards": plan.shards, "data": plan.dsize,
                "ft": True, "score": float(jnp.max(res.group_score)),
                "flagged": bool(flagged.any()),
                "location": int(np.asarray(res.location)[g]) if g >= 0
                else -1,
                "corrected": int(res.corrected)}
        return res.y, info

    def _build_inject(self, plan, batch: Batch):
        """Translate per-request :class:`Fault` descriptors into the
        executing pipeline's inject array (batch-row offsets applied).
        Returns ``(inject, bs)``; ``bs`` is non-None only on the local
        fused-kernel path (where the tile size must be pinned so ``tile =
        row // bs`` is well-defined)."""
        key = batch.key
        if not key.ft:
            return None, None
        rows = [(i, f) for i, r in enumerate(batch.requests)
                for f in r.inject]
        if not rows:
            return None, None
        if key.rank != 1:
            raise ValueError("runtime fault injection targets rank-1 ft "
                             "buckets (the serving campaign surface)")
        if plan.sharded:
            from repro.core.fft.distributed import make_dist_plan
            dp = make_dist_plan(key.tshape[0], plan.shards)
            n2l = dp.n2 // plan.shards
            out = []
            for brow, f in rows:
                c = f.col % dp.n2   # pass-1 output column (global n2 index)
                out.append([c // n2l, brow, f.row % dp.n1, c % n2l,
                            1.0, f.eps_re, f.eps_im])
            ftype = np.float64 if plan.spec.dtype == "complex128" \
                else np.float32
            return np.asarray(out, dtype=ftype), None
        # local fused kernel: ONE (6,) descriptor [tile, row, col, enable,
        # eps_re, eps_im]; pin bs = full batch so tile is always 0
        if len(rows) > 1:
            raise ValueError(
                "the local fused kernel injects at most one SEU per batch "
                "(single in-kernel descriptor) — space the campaign so "
                "batches carry one fault, or serve ft on a mesh")
        brow, f = rows[0]
        n = key.tshape[0]
        return (np.asarray([0, brow, f.col % n, 1, f.eps_re, f.eps_im],
                           dtype=np.float32),
                self.config.max_batch)

    def _record_ft(self, key, info: dict):
        detected = info.get("flagged", 0)
        self.telemetry.record_ft(
            key,
            detected=int(detected if not isinstance(detected, bool)
                         else detected),
            corrected=int(info.get("corrected", 0)),
            uncorrectable=int(info.get("uncorrectable", 0)),
            checksum_faults=int(info.get("checksum_faults", 0)),
            recomputed=int(info.get("recomputed", 0)))

    # -- introspection / lifecycle ----------------------------------------

    def stats(self) -> dict:
        """Telemetry snapshot + plan-cache stats + resolved bucket plans."""
        info = plan_cache_info()
        return {
            "buckets": self.telemetry.snapshot(),
            "plan_cache": {"hits": info.hits, "misses": info.misses,
                           "currsize": info.currsize},
            "plans": {k.label: repr(p) for k, p in self._plans.items()},
        }

    def drain(self):
        """Block until every pending request is batched and executed."""
        self.batcher.flush()
        while self.batcher.pending or self.batcher.ready:
            threading.Event().wait(0.002)

    def close(self, *, drain: bool = True):
        """Stop admissions; drain (or fail) pending work; join workers."""
        if self._closed:
            return
        self._closed = True
        self.batcher.close(drain=drain)
        for t in self._workers:
            t.join(timeout=30)

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        self.close(drain=not any(exc))
        return False

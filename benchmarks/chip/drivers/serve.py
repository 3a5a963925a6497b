"""Open-loop serving traffic through ``ServeRuntime.submit``.

Requests arrive as a Poisson process at the traffic's fixed ``rate``
(requests per second): exactly ``round(rate * seconds)`` arrivals, in equal
shares of the traffic's ``sizes``, at times and in an order drawn from the
seed. Each request is one complex64 signal taken from a pool of
``pool`` payloads per size, made from the seed before the window. One
thread, this one, submits each request when it is due; a request refused
by backpressure is counted as failed.

Each request's latency runs from when it was *due* to its handle's
``t_done``, so a stall delays every request behind it, and the generator's
own lateness (submit time minus due time) is recorded beside it. After the
window every request gets up to ``DRAIN_S`` more to finish.

The check compares every completed request's output with float64 NumPy of
its own payload, so padding, batch assembly and the scatter of each row
back to its request are all covered; a request that never completes is
lost.
"""
from __future__ import annotations

import time

import numpy as np

from benchmarks.chip import reference, schedule
from benchmarks.chip.trace import WINDOW

DRAIN_S = 60.0
# the window opens this long after the schedule is laid out
LEAD_S = 0.05


class Cell:
    def __init__(self, ctx):
        from repro.serve import RuntimeConfig, ServeRuntime

        tr = ctx.traffic
        self.ctx = ctx
        self.limits = tr["limits"]
        gen = schedule.rng(ctx.seed)
        self.sizes = [int(s) for s in tr["sizes"]]
        pool = int(tr["pool"])
        self.pools = [
            (gen.standard_normal((pool, n), np.float32)
             + 1j * gen.standard_normal((pool, n), np.float32)
             ).astype(np.complex64) for n in self.sizes]
        self.offsets, self.kind = schedule.open_loop(
            float(tr["rate"]), ctx.seconds, len(self.sizes), gen)
        self.payload = gen.integers(0, pool, size=len(self.offsets))
        self.rt = ServeRuntime(RuntimeConfig(**ctx.config["runtime"]))
        self._warm(int(tr["warm_requests"]))

    def _warm(self, per_size: int):
        handles = [self.rt.submit(self.pools[k][i % len(self.pools[k])])
                   for i in range(per_size) for k in range(len(self.sizes))]
        for h in handles:
            h.result(timeout=600.0)

    def _batches(self) -> tuple[int, int]:
        """(batches, filled slots) over all buckets so far."""
        snap = self.rt.stats()["buckets"]
        batches = sum(s["batches"] for s in snap.values())
        filled = sum(round(s["batch_occupancy"] * s["batches"]
                           * self.rt.config.max_batch) for s in snap.values())
        return batches, filled

    def window(self, seconds: float) -> dict:
        import jax
        from repro.serve.scheduler import QueueFullError

        count = len(self.offsets)
        handles = [None] * count
        submit = np.full(count, np.nan)
        b0, f0 = self._batches()
        with jax.profiler.TraceAnnotation(WINDOW):
            t0 = time.monotonic() + LEAD_S
            due = t0 + self.offsets
            for i in range(count):
                wait = due[i] - time.monotonic()
                if wait > 0:
                    time.sleep(wait)
                submit[i] = time.monotonic()
                try:
                    handles[i] = self.rt.submit(
                        self.pools[self.kind[i]][self.payload[i]])
                except QueueFullError:
                    pass
            t_close = t0 + seconds
            self.outputs = [None] * count
            for i, h in enumerate(handles):
                if h is None:
                    continue
                try:
                    self.outputs[i] = h.result(
                        timeout=max(0.0, t_close + DRAIN_S - time.monotonic()))
                except Exception:       # lost or failed: counted below
                    pass
        t_give_up = time.monotonic()
        b1, f1 = self._batches()
        done = np.array([o is not None for o in self.outputs])
        t_done = np.array([h.t_done if h is not None and o is not None
                           else t_give_up
                           for h, o in zip(handles, self.outputs)])
        lat_ms = (t_done - due) * 1e3
        queue_ms = np.array([h.queue_s * 1e3 for h, o in
                             zip(handles, self.outputs) if o is not None])
        self.refused = sum(h is None for h in handles)
        self.lost = int(count - self.refused - done.sum())
        completed_in = int(np.count_nonzero(done & (t_done <= t_close)))
        return {
            "attempted": count, "failed": int(count - done.sum()),
            "e2e": {"serve_p50_ms": float(np.percentile(lat_ms, 50)),
                    "serve_p95_ms": float(np.percentile(lat_ms, 95)),
                    "serve_rate": completed_in / seconds},
            "lat_ms": lat_ms,
            "queue_ms": queue_ms,
            "late_ms": (submit - due) * 1e3,
            "batch_fill": (f1 - f0) / max(1, (b1 - b0)
                                          * self.rt.config.max_batch),
            "batches": b1 - b0, "refused": self.refused, "lost": self.lost,
        }

    def check(self, control: dict | None = None) -> list[dict]:
        refs = [reference.fft64(p) for p in self.pools]
        if control:
            import jax.numpy as jnp
            outs = [np.asarray(reference.lowp_fft(jnp.asarray(p), **control))
                    for p in self.pools]
        err = 0.0
        for i, out in enumerate(self.outputs):
            if out is None:
                continue
            k, j = self.kind[i], self.payload[i]
            got = outs[k][j] if control else out
            err = max(err, reference.rel_err(np.asarray(got)[None],
                                             refs[k][j][None]))
        return [{"name": "rel_err", "value": err,
                 "limit": self.limits["rel_err"]},
                {"name": "lost", "value": self.lost, "limit": 0}]

    def close(self):
        self.rt.close()
        self.outputs = None

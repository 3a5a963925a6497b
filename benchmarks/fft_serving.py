"""Multi-tenant serving benchmark: open-loop Poisson load over mixed FFT
specs through ``repro.serve.ServeRuntime``.

Three experiments:

* ``run_load`` — open-loop Poisson arrivals at a sweep of offered rates
  over a mixed request population (sizes off the pow2 grid, fft +
  spectrum, real + complex). Per rate it reports goodput (completed/s),
  rejects (bounded-queue backpressure), and the p50/p95/p99 latency —
  the latency-vs-load curve for EXPERIMENTS.md.
* ``run_saturation`` — the headline assert: at saturation (every client
  submitting back-to-back), the deadline batcher (max_batch=B) must beat
  the same runtime configured unbatched (max_batch=1) on throughput.
  Both sides run the identical machinery — the delta is batch dispatch
  amortization, which is the point of the subsystem.
* ``run_ft_campaign`` — a ``FaultSchedule``-driven SEU campaign through
  ft buckets, paced one fault per batch (the load generator submits in
  closed batch-sized groups), so the per-bucket ABFT ledger must be
  EXACT: detected == corrected == injected, zero uncorrectable.

Standalone runs force a multi-device host platform:

    PYTHONPATH=src python -m benchmarks.fft_serving
"""
from __future__ import annotations

import argparse
import os
import time

if __name__ == "__main__" and "xla_force_host_platform_device_count" not in \
        os.environ.get("XLA_FLAGS", ""):
    os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "") +
                               " --xla_force_host_platform_device_count=4")

import numpy as np

import jax

from repro.core.ft.injection import FaultSchedule
from repro.launch.mesh import make_mesh
from repro.serve import (Fault, QueueFullError, RuntimeConfig, ServeRuntime,
                         percentiles)

from .common import emit


def _mesh():
    ndev = min(4, len(jax.devices()))
    shards = 1 << (ndev.bit_length() - 1)
    if shards < 2:
        return None
    return make_mesh((shards,), ("fft",))


def _request_pool(rng, smoke: bool):
    """The mixed-tenant population: off-grid sizes (so bucketing works for
    its living), two ops, real and complex traffic -> 4 buckets."""
    sizes = (1000, 1024, 700) if smoke else (1000, 1024, 700, 1800, 2048)
    pool = []
    for n in sizes:
        pool.append((rng.standard_normal(n).astype(np.float32),
                     dict(op="fft")))
        pool.append((rng.standard_normal(n).astype(np.float32),
                     dict(op="spectrum")))
        pool.append((rng.standard_normal(n).astype(np.float32),
                     dict(op="fft", real=True)))
    return pool


def run_load(smoke: bool = True, mesh=None):
    """Open-loop Poisson sweep: goodput + latency percentiles vs offered
    rate. Returns [(rate, goodput, rejected, p50, p95, p99), ...]."""
    rng = np.random.default_rng(0)
    pool = _request_pool(rng, smoke)
    duration = 2.0 if smoke else 6.0
    rates = (50, 200, 800) if smoke else (50, 200, 800, 2000, 4000)
    rows = []
    for rate in rates:
        cfg = RuntimeConfig(max_batch=8, deadline_ms=2.0, queue_depth=256,
                            workers=2)
        with ServeRuntime(cfg, mesh=mesh) as rt:
            for x, kw in pool:                      # warm every bucket
                rt.submit(x, **kw).result(timeout=120.0)
            handles, rejected = [], 0
            t0 = time.monotonic()
            next_arrival = t0
            while (now := time.monotonic()) - t0 < duration:
                if now < next_arrival:
                    time.sleep(min(next_arrival - now, 0.005))
                    continue
                next_arrival += rng.exponential(1.0 / rate)
                x, kw = pool[rng.integers(len(pool))]
                try:
                    handles.append(rt.submit(x, **kw))
                except QueueFullError:
                    rejected += 1                   # open loop: drop, note
            for h in handles:
                h.result(timeout=120.0)
            wall = time.monotonic() - t0
            lats = [h.latency_s for h in handles]
        goodput = len(handles) / wall
        p = percentiles(lats)
        emit(f"serve_load_r{rate}", p["p50_ms"] * 1e3,
             f"goodput={goodput:.0f}rps;offered={rate}rps;"
             f"rejected={rejected};p95={p['p95_ms']:.2f}ms;"
             f"p99={p['p99_ms']:.2f}ms")
        rows.append((rate, goodput, rejected, p["p50_ms"], p["p95_ms"],
                     p["p99_ms"]))
    return rows


def _pump(rt, xs, nreq: int, timeout: float = 300.0) -> float:
    """Saturation drive: submit ``nreq`` back-to-back (spinning on
    backpressure), wait for all, return wall seconds."""
    handles = []
    t0 = time.monotonic()
    for i in range(nreq):
        while True:
            try:
                handles.append(rt.submit(xs[i % len(xs)]))
                break
            except QueueFullError:
                time.sleep(0.0005)
    for h in handles:
        h.result(timeout=timeout)
    return time.monotonic() - t0


def run_saturation(smoke: bool = True, mesh=None):
    """Batched vs unbatched throughput at saturation, same machinery.
    Asserts the batched runtime wins — the subsystem's reason to exist."""
    rng = np.random.default_rng(1)
    n = 1024
    xs = [rng.standard_normal(n).astype(np.float32) for _ in range(32)]
    nreq = 256 if smoke else 2048
    thr = {}
    for label, max_batch in (("batched", 8), ("sequential", 1)):
        cfg = RuntimeConfig(max_batch=max_batch, deadline_ms=2.0,
                            queue_depth=256, workers=2)
        with ServeRuntime(cfg, mesh=mesh) as rt:
            rt.submit(xs[0]).result(timeout=120.0)   # warm the bucket
            wall = _pump(rt, xs, nreq)
            st = rt.stats()["buckets"][f"fft:{n}:c64"]
        thr[label] = nreq / wall
        emit(f"serve_saturation_{label}_b{max_batch}", wall / nreq * 1e6,
             f"throughput={thr[label]:.0f}rps;"
             f"occupancy={st['batch_occupancy']:.2f};"
             f"batches={st['batches']}")
    speedup = thr["batched"] / thr["sequential"]
    emit("serve_saturation_speedup", speedup, "batched/sequential")
    assert speedup > 1.0, (
        f"deadline batching must beat sequential at saturation: "
        f"{thr['batched']:.0f} vs {thr['sequential']:.0f} rps")
    return thr


def run_ft_campaign(smoke: bool = True, mesh=None):
    """SEU campaign through ft buckets off a ``FaultSchedule``: the load
    generator submits in closed groups of ``max_batch`` (so each group IS
    one batch) and attaches at most one scheduled fault per group — the
    per-bucket ABFT telemetry must then be exact."""
    rng = np.random.default_rng(2)
    max_batch = 4
    n = 256 if mesh is None else 1024   # mesh pencils need n >= shards^2
    groups = 8 if smoke else 32
    # one fault every other group, eps far above threshold (a detectability
    # floor keeps the ledger assert exact — near-zero flips are the ROC
    # experiment's business, not the serving ledger's)
    sched = FaultSchedule(entries=tuple(
        (g, 0, int(rng.integers(max_batch)), int(rng.integers(n)),
         float(rng.choice((-1, 1)) * (150.0 + rng.random() * 100.0)), 0.0)
        for g in range(0, groups, 2)))
    cfg = RuntimeConfig(max_batch=max_batch, deadline_ms=5.0, workers=1)
    with ServeRuntime(cfg, mesh=mesh) as rt:
        xs = [rng.standard_normal(n).astype(np.float32)
              for _ in range(max_batch)]
        rt.submit(xs[0], ft=True).result(timeout=300.0)  # warm
        t0 = time.monotonic()
        for g in range(groups):
            fault_by_row = {row: Fault(row=row, col=col, eps_re=er,
                                       eps_im=ei)
                            for (s, _t, row, col, er, ei) in sched.entries
                            if s == g}
            hs = [rt.submit(xs[i], ft=True, faults=fault_by_row.get(i))
                  for i in range(max_batch)]
            for h in hs:         # closed loop: this group = one batch
                h.result(timeout=300.0)
        wall = time.monotonic() - t0
        st = rt.stats()["buckets"][f"fft:{n}:c64:ft"]
    assert st["injected"] == sched.num_faults, st
    assert st["detected"] == sched.num_faults, st
    assert st["corrected"] == sched.num_faults, st
    assert st.get("uncorrectable", 0) == 0, st
    emit(f"serve_ft_campaign_n{n}_g{groups}",
         wall / (groups * max_batch) * 1e6,
         f"injected={st['injected']};detected={st['detected']};"
         f"corrected={st['corrected']};exact=1")
    return st


if __name__ == "__main__":
    ap = argparse.ArgumentParser()
    ap.add_argument("--smoke", action="store_true",
                    help="small grids / short sweeps (CI)")
    ap.add_argument("--local", action="store_true",
                    help="skip the mesh, serve single-device buckets")
    a = ap.parse_args()
    mesh = None if a.local else _mesh()
    print(f"# serving over "
          f"{'single device' if mesh is None else f'{mesh.shape} mesh'}")
    print("name,us_per_call,derived")
    run_saturation(smoke=a.smoke, mesh=mesh)
    run_load(smoke=a.smoke, mesh=mesh)
    run_ft_campaign(smoke=a.smoke, mesh=mesh)

"""Serving runtime unit tests: bucketing policy, deadline batching,
backpressure, and the end-to-end single-device runtime (padded-transform
correctness, telemetry, ABFT fault injection).

Everything here runs on one CPU device — the mesh serving paths are
covered by the saturation smoke in ``benchmarks/fft_serving.py`` (CI's
mesh-8dev lane).
"""
import sys
import threading
import time

import numpy as np
import pytest

from repro.core.fft import api
from repro.serve import (BucketKey, BucketStats, DeadlineBatcher, Fault,
                         QueueFullError, RequestHandle, RequestTimeoutError,
                         RuntimeClosedError, RuntimeConfig, ServeRequest,
                         ServeRuntime, SpecBucketer, Telemetry,
                         pad_transform_shape, percentiles)


@pytest.fixture(autouse=True)
def _fresh_cache():
    api.plan_cache_clear()
    yield
    api.plan_cache_clear()


# -- bucketing policy -------------------------------------------------------

def test_pad_transform_shape_pow2():
    assert pad_transform_shape((1000,)) == (1024,)
    assert pad_transform_shape((1024,)) == (1024,)
    assert pad_transform_shape((100, 60)) == (128, 64)


def test_pad_transform_shape_mesh_floors():
    # pencil feasibility: n >= shards^2; packed real pencils need n/2 >=
    # shards^2 (the half-length signal is what the pencil splits)
    assert pad_transform_shape((8,), shards=4) == (16,)
    assert pad_transform_shape((8,), shards=4, real=True) == (32,)
    assert pad_transform_shape((64,), shards=4) == (64,)
    # 2-D: first axis must be mesh-divisible for the slab
    assert pad_transform_shape((2, 8), shards=4) == (4, 16)


def test_pad_transform_shape_rejects_bad():
    with pytest.raises(ValueError):
        pad_transform_shape(())
    with pytest.raises(ValueError):
        pad_transform_shape((0,))


def test_key_for_canonicalizes():
    b = SpecBucketer(max_batch=4)
    k = b.key_for((1000,), np.float32, op="fft")
    assert k == BucketKey(tshape=(1024,), rank=1, dtype="complex64",
                          op="fft", real=False, ft=False)
    assert k.label == "fft:1024:c64"
    # same bucket regardless of request length within the pow2 band
    assert b.key_for((513,), np.complex64, op="fft") == k
    # real f64 keeps double precision, real f32 stays single
    assert b.key_for((1000,), np.float64, op="fft",
                     real=True).dtype == "complex128"
    assert b.key_for((1000,), np.float32, op="fft",
                     real=True).dtype == "complex64"
    assert "real" in b.key_for((8,), np.float32, op="fft", real=True).label


def test_key_for_rejections():
    b = SpecBucketer(max_batch=4)
    with pytest.raises(ValueError, match="convolve"):
        b.key_for((64,), np.complex64, op="convolve")
    with pytest.raises(ValueError, match="ft=True"):
        b.key_for((64,), np.complex64, op="spectrum", ft=True)
    with pytest.raises(ValueError, match="single signals"):
        b.key_for((2, 3, 4), np.complex64)
    with pytest.raises(ValueError, match="real=True"):
        b.key_for((64,), np.complex64, real=True)


def test_pad_elems():
    b = SpecBucketer(max_batch=4)
    k = b.key_for((1000,), np.complex64)
    assert b.pad_elems(k, (1000,)) == 24
    assert b.pad_elems(k, (1024,)) == 0


def test_spec_for_requires_ft_config():
    b = SpecBucketer(max_batch=4)
    k = b.key_for((64,), np.complex64, ft=True)
    with pytest.raises(ValueError, match="FTConfig"):
        b.spec_for(k)
    spec = b.spec_for(b.key_for((64,), np.complex64))
    assert spec.shape == (4, 64) and spec.ft is None


# -- scheduler: deadline batching + backpressure ----------------------------

def _req(key="k", timeout_ms=None):
    return ServeRequest(key=key, x=None, handle=RequestHandle(),
                        timeout_ms=timeout_ms)


def test_batcher_closes_on_max_batch():
    b = DeadlineBatcher(max_batch=3, deadline_ms=10_000, queue_depth=16)
    try:
        reqs = [_req() for _ in range(3)]
        for r in reqs:
            b.submit(r)
        batch = b.next_batch(timeout=1.0)
        assert batch is not None and len(batch.requests) == 3
        assert [r.handle for r in batch.requests] == [r.handle for r in reqs]
        assert b.pending == 0
    finally:
        b.close(drain=False)


def test_batcher_closes_on_deadline():
    b = DeadlineBatcher(max_batch=64, deadline_ms=20, queue_depth=16)
    try:
        t0 = time.monotonic()
        b.submit(_req())
        batch = b.next_batch(timeout=2.0)
        dt = time.monotonic() - t0
        assert batch is not None and len(batch.requests) == 1
        assert dt >= 0.015, f"closed before the deadline ({dt*1e3:.1f}ms)"
    finally:
        b.close(drain=False)


def test_batcher_backpressure():
    b = DeadlineBatcher(max_batch=64, deadline_ms=10_000, queue_depth=2)
    try:
        b.submit(_req())
        b.submit(_req())
        with pytest.raises(QueueFullError):
            b.submit(_req())
    finally:
        b.close(drain=False)


def test_batcher_request_timeout():
    b = DeadlineBatcher(max_batch=64, deadline_ms=10_000, queue_depth=4)
    try:
        timed_out = []
        b._on_timeout = timed_out.append
        r = _req(timeout_ms=20)
        b.submit(r)
        with pytest.raises(RequestTimeoutError):
            r.handle.result(timeout=2.0)
        assert timed_out == ["k"]
        assert b.pending == 0     # the slot returned to the queue budget
    finally:
        b.close(drain=False)


def test_batcher_close_drain_flushes_partials():
    b = DeadlineBatcher(max_batch=64, deadline_ms=10_000, queue_depth=4)
    b.submit(_req("a"))
    b.submit(_req("b"))
    b.close(drain=True)
    keys = {b2.key for b2 in iter(lambda: b.next_batch(timeout=0.2), None)}
    assert keys == {"a", "b"}
    with pytest.raises(RuntimeClosedError):
        b.submit(_req())


def test_batcher_close_nodrain_fails_pending():
    b = DeadlineBatcher(max_batch=64, deadline_ms=10_000, queue_depth=4)
    r = _req()
    b.submit(r)
    b.close(drain=False)
    with pytest.raises(RuntimeClosedError):
        r.handle.result(timeout=1.0)


# -- runtime end-to-end (single device) -------------------------------------

def test_runtime_padded_fft_roundtrip():
    rng = np.random.default_rng(0)
    with ServeRuntime(RuntimeConfig(max_batch=4, deadline_ms=5.0,
                                    workers=2)) as rt:
        xs = [rng.standard_normal(n).astype(np.float32)
              for n in (1000, 1024, 513, 700)]
        handles = [rt.submit(x) for x in xs]
        for x, h in zip(xs, handles):
            y = h.result(timeout=30.0)
            assert y.shape == (1024,)
            ref = np.fft.fft(x, 1024)    # trailing-zero extension contract
            np.testing.assert_allclose(y, ref, rtol=2e-3, atol=2e-3)
            assert h.info["bucket"] == "fft:1024:c64"
        stats = rt.stats()["buckets"]["fft:1024:c64"]
        assert stats["submitted"] == 4 and stats["completed"] == 4
        assert stats["pad_waste"] > 0       # 1000/513/700 all padded
        assert stats["p50_ms"] > 0
    # one bucket -> exactly one plan spec in the shared cache
    assert api.plan_cache_info().currsize == 1


def test_runtime_one_batch_when_full():
    rng = np.random.default_rng(1)
    with ServeRuntime(RuntimeConfig(max_batch=4, deadline_ms=10_000.0,
                                    workers=1)) as rt:
        hs = [rt.submit(rng.standard_normal(256).astype(np.float32))
              for _ in range(4)]
        for h in hs:
            h.result(timeout=30.0)
        st = rt.stats()["buckets"]["fft:256:c64"]
        assert st["batches"] == 1 and st["batch_occupancy"] == 1.0


def test_runtime_mixed_buckets():
    rng = np.random.default_rng(2)
    with ServeRuntime(RuntimeConfig(max_batch=2, deadline_ms=5.0)) as rt:
        h1 = rt.submit(rng.standard_normal(100).astype(np.float32))
        h2 = rt.submit(rng.standard_normal((20, 30)).astype(np.float32))
        h3 = rt.submit(rng.standard_normal(256).astype(np.float32),
                       op="spectrum")
        assert h1.result(timeout=30.0).shape == (128,)
        assert h2.result(timeout=30.0).shape == (32, 32)
        s = h3.result(timeout=30.0)
        assert s.shape == (256,) and s.dtype.kind == "f"
        buckets = rt.stats()["buckets"]
        assert set(buckets) == {"fft:128:c64", "fft:32x32:c64",
                                "spectrum:256:c64"}
    assert api.plan_cache_info().currsize == 3


def test_runtime_real_bucket():
    rng = np.random.default_rng(3)
    with ServeRuntime(RuntimeConfig(max_batch=2, deadline_ms=5.0)) as rt:
        x = rng.standard_normal(1000).astype(np.float32)
        y = rt.submit(x, real=True).result(timeout=30.0)
        assert y.shape == (513,)     # 1024-bucket half spectrum
        np.testing.assert_allclose(y, np.fft.rfft(x, 1024),
                                   rtol=2e-3, atol=2e-3)


def test_runtime_rejects_bad_requests():
    with ServeRuntime(RuntimeConfig(max_batch=2, deadline_ms=5.0)) as rt:
        with pytest.raises(ValueError, match="convolve"):
            rt.submit(np.zeros(64, np.complex64), op="convolve")
        with pytest.raises(ValueError, match="ft=True"):
            rt.submit(np.zeros(64, np.float32), faults=Fault())
    with pytest.raises(RuntimeClosedError):
        rt.submit(np.zeros(64, np.float32))


def test_runtime_backpressure_counts_rejects():
    # 1 worker wedged on a huge deadline-less queue: fill the bounded
    # queue and confirm the overflow surfaces as QueueFullError + telemetry
    with ServeRuntime(RuntimeConfig(max_batch=64, deadline_ms=10_000.0,
                                    queue_depth=2, workers=1)) as rt:
        x = np.zeros(128, np.float32)
        rt.submit(x)
        rt.submit(x)
        with pytest.raises(QueueFullError):
            rt.submit(x)
        st = rt.stats()["buckets"]["fft:128:c64"]
        assert st["rejected"] == 1
        rt.batcher.flush()


def test_runtime_ft_injection_local():
    """One SEU per batch through the local fused-kernel ABFT: detected,
    located, corrected — and the telemetry ledger is exact."""
    rng = np.random.default_rng(4)
    cfg = RuntimeConfig(max_batch=4, deadline_ms=10_000.0, workers=1)
    with ServeRuntime(cfg) as rt:
        xs = [rng.standard_normal(256).astype(np.float32) for _ in range(4)]
        faults = [None, Fault(col=7, eps_re=300.0), None, None]
        hs = [rt.submit(x, ft=True, faults=f)
              for x, f in zip(xs, faults)]
        ys = [h.result(timeout=60.0) for h in hs]
        for x, y in zip(xs, ys):
            np.testing.assert_allclose(y, np.fft.fft(x), rtol=2e-3,
                                       atol=2e-3)
        st = rt.stats()["buckets"]["fft:256:c64:ft"]
        assert st["injected"] == 1
        assert st["detected"] == 1
        assert st["corrected"] == 1
        assert st.get("uncorrectable", 0) == 0
        assert hs[1].info["flagged"] and hs[1].info["corrected"] == 1


def test_runtime_ft_local_single_seu_limit():
    # the fused kernel carries ONE in-kernel descriptor: two faulted
    # requests in the same batch must fail loudly, not silently drop one
    with ServeRuntime(RuntimeConfig(max_batch=2, deadline_ms=10_000.0,
                                    workers=1)) as rt:
        x = np.zeros(256, np.float32)
        h1 = rt.submit(x, ft=True, faults=Fault())
        h2 = rt.submit(x, ft=True, faults=Fault())
        with pytest.raises(ValueError, match="one SEU"):
            h1.result(timeout=30.0)
        with pytest.raises(ValueError, match="one SEU"):
            h2.result(timeout=30.0)
        assert rt.stats()["buckets"]["fft:256:c64:ft"]["failed"] == 2


def test_runtime_warmup_means_one_trace():
    # admission warms the executor; the serving batches then hit the same
    # jitted callable (no per-batch trace) — observable as a single plan
    # and stable latency across repeats
    with ServeRuntime(RuntimeConfig(max_batch=2, deadline_ms=2.0,
                                    workers=1)) as rt:
        x = np.zeros(512, np.float32)
        for _ in range(3):
            rt.submit(x).result(timeout=30.0)
        assert api.plan_cache_info().currsize == 1
        assert rt.stats()["buckets"]["fft:512:c64"]["batches"] >= 1


def test_percentiles_shape():
    assert percentiles([]) == {"p50_ms": 0.0, "p95_ms": 0.0, "p99_ms": 0.0}
    p = percentiles([0.001, 0.002, 0.100])
    assert p["p50_ms"] == pytest.approx(2.0)
    assert p["p99_ms"] > p["p50_ms"]


def test_runtime_concurrent_submitters():
    # many client threads, one runtime: every request gets its own answer
    rng = np.random.default_rng(5)
    xs = [rng.standard_normal(128).astype(np.float32) for _ in range(16)]
    results = [None] * 16
    with ServeRuntime(RuntimeConfig(max_batch=4, deadline_ms=2.0,
                                    workers=2)) as rt:
        def client(i):
            results[i] = rt.submit(xs[i]).result(timeout=60.0)
        ts = [threading.Thread(target=client, args=(i,)) for i in range(16)]
        for t in ts:
            t.start()
        for t in ts:
            t.join()
        st = rt.stats()["buckets"]["fft:128:c64"]
        assert st["completed"] == 16
    for x, y in zip(xs, results):
        np.testing.assert_allclose(y, np.fft.fft(x, 128), rtol=2e-3,
                                   atol=2e-3)


def test_telemetry_keeps_recent_samples():
    # the samples are a ring of the last SAMPLES requests a bucket: counts
    # stay exact, percentiles describe the recent requests, keys unchanged
    from repro.serve.telemetry import SAMPLES
    tel = Telemetry()
    for i in range(SAMPLES + 100):
        tel.record_done("b", latency_s=i * 1e-3, queue_s=0.5e-3)
    st = tel._buckets["b"]
    assert len(st.latencies_s) == len(st.queue_s) == SAMPLES
    snap = tel.snapshot()["b"]
    assert snap["completed"] == SAMPLES + 100
    recent = np.arange(100, SAMPLES + 100, dtype=np.float64)
    assert snap["p50_ms"] == pytest.approx(np.percentile(recent, 50))
    assert snap["queue_p50_ms"] == pytest.approx(0.5)
    assert set(snap) == set(BucketStats().snapshot())


def test_telemetry_snapshot_under_concurrent_records():
    # workers record while a reader snapshots: no update is lost and every
    # snapshot is consistent (the ring never exceeds its bound)
    from repro.serve.telemetry import SAMPLES
    tel, per, threads = Telemetry(), 3000, 6
    old = sys.getswitchinterval()
    sys.setswitchinterval(1e-6)
    try:
        def worker():
            for i in range(per):
                tel.record_done("b", latency_s=1e-3, queue_s=1e-4)
        ts = [threading.Thread(target=worker) for _ in range(threads)]
        for t in ts:
            t.start()
        while any(t.is_alive() for t in ts):
            snap = tel.snapshot().get("b")
            assert snap is None or snap["p50_ms"] == pytest.approx(1.0)
        for t in ts:
            t.join(timeout=30)
        assert not any(t.is_alive() for t in ts)
    finally:
        sys.setswitchinterval(old)
    assert tel.snapshot()["b"]["completed"] == per * threads
    assert len(tel._buckets["b"].latencies_s) == SAMPLES

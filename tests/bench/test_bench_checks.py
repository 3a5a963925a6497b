"""The correctness check of each one-chip cell, at a size a test run holds:
the program passes, the control (the plain reference one precision step
down, three bf16 passes) fails, and a run with the timed path broken
underneath comes out not correct. Runs on the CPU, skipping only the
harness's look for a chip; the limits are the cells' own."""
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks.chip import harness

SEED = 2 ** 31 + 99
SMALL = {
    "c2c_n1024": {"n": 256, "batch": 64},
    "abft_n1024": {"n": 256, "batch": 64, "seu_period": 2},
    "serve_lte": {"sizes": [256, 512, 1024], "rate": 150.0, "pool": 16,
                  "warm_requests": 2},
}


def one_chip_bench():
    """BENCHMARK.json with every cell on one chip: a cell that holds a
    whole host for steadiness still serves from one device."""
    bench = harness.load_json(harness.BENCH_FILE)
    for w in bench["workloads"]:
        w["chips"] = 1
    return bench


def run(cell, seconds=0.4, controls=()):
    return harness.run_cell(cell, seed=SEED, seconds=seconds, trace=False,
                            t_start=time.perf_counter(), require_chip=False,
                            bench=one_chip_bench(),
                            traffic_update=SMALL[cell], controls=controls)


@pytest.fixture
def fresh_programs():
    """Compiled programs traced with a fault must not outlive the test."""
    jax.clear_caches()
    yield
    jax.clear_caches()


@pytest.mark.parametrize("cell", sorted(SMALL))
def test_program_passes_and_control_fails(cell, fresh_programs):
    out = run(cell, controls=({"passes": 3},))
    assert out["correct"], out["checks"]
    assert out["failed"] == 0 and out["attempted"] > 0
    ctl = out["control_checks"][0]
    assert not ctl["correct"], ctl
    # the program reads well under the limit that the control breaks
    lim = out["checks"]["rel_err"]["limit"]
    assert out["checks"]["rel_err"]["value"] < lim < ctl["checks"]["rel_err"]


def _alter_one_answer(y):
    """One element of one row moved by 1e-4 of the row's scale."""
    r = y.shape[0] // 2
    return y.at[r, 3].add(1e-4 * jnp.max(jnp.abs(y[r])))


def _drop_half_the_batch(y):
    return y.at[y.shape[0] // 2:].set(0)


@pytest.mark.parametrize("fault", [_alter_one_answer, _drop_half_the_batch])
def test_c2c_broken_kernel_output_is_caught(fault, monkeypatch,
                                            fresh_programs):
    from repro.kernels import ops
    real = ops._block_fft_c
    monkeypatch.setattr(ops, "_block_fft_c",
                        lambda *a, **k: fault(real(*a, **k)))
    out = run("c2c_n1024")
    assert not out["correct"], out["checks"]


def test_abft_correction_left_out_is_caught(monkeypatch, fresh_programs):
    from repro.core import abft
    monkeypatch.setattr(abft, "apply_correction",
                        lambda y, verdict, **k: (y, None))
    out = run("abft_n1024")
    assert not out["correct"], out["checks"]
    assert out["checks"]["seu_row_rel_err"]["value"] > \
        out["checks"]["seu_row_rel_err"]["limit"]


def test_abft_kernel_output_altered_is_caught(monkeypatch, fresh_programs):
    from repro.kernels import ops
    real = ops.abft_fft_pallas

    def altered(*a, **k):
        yr, yi, delta, cs = real(*a, **k)
        return yr.at[5, 7].add(1e-3 * jnp.max(jnp.abs(yr))), yi, delta, cs
    monkeypatch.setattr(ops, "abft_fft_pallas", altered)
    out = run("abft_n1024")
    assert not out["correct"], out["checks"]


@pytest.mark.parametrize("fault", ["alter", "swap"])
def test_serve_broken_answers_are_caught(fault, monkeypatch, fresh_programs):
    from repro.serve import runtime
    real = runtime.serve_plan

    def broken(plan, x, **k):
        y, info = real(plan, x, **k)
        y = jnp.asarray(y)
        if fault == "alter":     # row 0 always holds a request
            y = y.at[0, 3].add(1e-4 * jnp.max(jnp.abs(y[0])))
        else:                    # row 0 is handed row 1's answer
            y = y.at[0].set(y[1])
        return y, info
    monkeypatch.setattr(runtime, "serve_plan", broken)
    out = run("serve_lte", seconds=1.0)
    assert not out["correct"], out["checks"]


def test_serve_latency_runs_from_the_due_time(monkeypatch):
    """With a server that answers at once and a generator that falls
    behind, every request's latency is its lateness: timed from when it was
    due, not from when it was submitted."""
    from benchmarks.chip.drivers import serve

    class Handle:
        def __init__(self, x):
            self.t_submit = self.t_batched = self.t_done = time.monotonic()
            self.y = np.fft.fft(x)

        @property
        def queue_s(self):
            return 0.0

        def result(self, timeout=None):
            return self.y

    class SlowClient:
        def __init__(self, config):
            self.config = config

        def submit(self, x):
            time.sleep(0.005)        # twice the arrival interval
            return Handle(x)

        def stats(self):
            return {"buckets": {}}

        def close(self):
            pass

    import repro.serve
    monkeypatch.setattr(repro.serve, "ServeRuntime", SlowClient)
    ctx = harness.Context(
        cell="serve_lte", config={"runtime": {"max_batch": 8}},
        traffic={"rate": 400.0, "sizes": [64], "pool": 4,
                 "warm_requests": 1, "limits": {"rel_err": 1e-6}},
        seed=SEED, seconds=0.5, chips=1, trace=False, devices=[])
    cell = serve.Cell(ctx)
    rec = cell.window(0.5)
    late = np.sort(rec["late_ms"])
    # 200 requests due every 2.5 ms, submitted every 5 ms: the last is
    # about half a second late, and its latency says so
    assert late[-1] > 300.0
    assert rec["e2e"]["serve_p95_ms"] == pytest.approx(
        np.percentile(late, 95), abs=10.0)
    assert rec["e2e"]["serve_p50_ms"] > 100.0

"""The readers of program scopes and spans: the device readers on a
hand-made trace whose op names come from the executor compiled on the CPU,
and the host readers on a real CPU profile of a short ServeRuntime run."""
import glob

import jax
import numpy as np
import pytest

from benchmarks.chip import harness, scopes, trace
from benchmarks.chip.trace import Op, Reduction

B, N, CALLS = 48, 256, 2
DEVICE = ("split_join_ms", "layout_ms", "abft_decode_ms", "scope_cover")
HOST = ("serve_exec_ms", "serve_dispatch_ms", "serve_fetch_ms",
        "serve_worker_util")


def read(metric, run):
    return harness.load_reader(metric).read(run)


def device_run(config, ops, chips=1):
    ctx = harness.Context(cell="t", config=config, traffic={}, seed=0,
                          seconds=0.0, chips=chips, trace=True, devices=[])
    red = Reduction(windows={trace.WINDOW: (0, 10_000)}, ops=[ops], host=[])
    return harness.RunView(ctx=ctx, record={"calls": CALLS, "b": B, "n": N},
                           trace=red, peaks=None)


def config(name):
    return harness.load_json(harness.HERE / "configs" / f"{name}.json")


def one_name_per_scope(cfg) -> dict:
    """{scope: an instruction name under it} of the cell's executor."""
    m = scopes.scope_map(device_run(cfg, []))
    out = {}
    for name, scope in sorted(m.items()):
        out.setdefault(scope, name)
    return out


@pytest.mark.parametrize("cell", ["c2c_fp32", "c2c_fp32_abft"])
def test_device_readers_sum_by_hand(cell):
    """One op of each scope, scope i lasting 100 * (i + 1) ns, an op no
    scope covers (70 ns) and one before the window: each reader sums its
    scopes by hand, per call."""
    cfg = config(cell)
    names = one_name_per_scope(cfg)
    ft = "ft" in cfg
    assert {"fft.split", "fft.join", "fft.layout", "fft.kernel"} \
        <= set(names)
    assert ("abft.decode" in names) == ft
    dur = {s: 100 * (i + 1) for i, s in enumerate(sorted(names))}
    ops, t = [Op(names["fft.kernel"], "custom-call", "k", -500, -400)], 0
    for s in sorted(names):
        ops.append(Op(names[s], "fusion", names[s], t, t + dur[s]))
        t += dur[s]
    ops.append(Op("not-in-this-module.9", "copy", "x", t, t + 70))
    run = device_run(cfg, ops)
    ms = lambda *ss: sum(dur.get(s, 0) for s in ss) / CALLS / 1e6  # noqa
    assert read("split_join_ms", run) == pytest.approx(
        ms("fft.split", "fft.join"))
    assert read("layout_ms", run) == pytest.approx(ms("fft.layout"))
    if ft:
        assert read("abft_decode_ms", run) == pytest.approx(
            ms("abft.decode", "abft.correct"))
    else:
        assert read("abft_decode_ms", run) is None
    total = sum(dur.values())
    assert read("scope_cover", run) == pytest.approx(
        100 * total / (total + 70))
    # every op scoped: full cover
    assert read("scope_cover", device_run(cfg, ops[:-1])) == 100.0


@pytest.mark.parametrize("cfg,chips", [
    (config("serve_lte"), 1),       # no plan executor in the cell
    (config("c2c_fp32"), 4),        # the accessor covers local plans
])
def test_device_readers_silent_off_their_cells(cfg, chips):
    run = device_run(cfg, [Op("fusion.1", "fusion", "f", 0, 10)], chips)
    assert [read(m, run) for m in DEVICE] == [None] * len(DEVICE)


@pytest.fixture(scope="module")
def serve_profile(tmp_path_factory):
    """A short ServeRuntime run (two workers) profiled on the CPU inside a
    ``bench_window`` annotation, reduced with no device planes."""
    from repro.serve import RuntimeConfig, ServeRuntime

    rng = np.random.default_rng(3)
    xs = [rng.standard_normal(256).astype(np.complex64) for _ in range(24)]
    tdir = tmp_path_factory.mktemp("serve_trace")
    with ServeRuntime(RuntimeConfig(max_batch=4, deadline_ms=2.0,
                                    workers=2)) as rt:
        rt.submit(xs[0]).result(timeout=120.0)      # admission and warm-up
        jax.profiler.start_trace(str(tdir))
        try:
            with jax.profiler.TraceAnnotation(trace.WINDOW):
                for h in [rt.submit(x) for x in xs]:
                    h.result(timeout=120.0)
        finally:
            jax.profiler.stop_trace()
    path = glob.glob(f"{tdir}/**/*.xplane.pb", recursive=True)
    assert path, "the profiler wrote no trace"
    return trace.reduce(path[0], chips=0)


def host_run(red):
    ctx = harness.Context(cell="serve_lte", config=config("serve_lte"),
                          traffic={}, seed=0, seconds=0.0, chips=1,
                          trace=True, devices=[])
    return harness.RunView(ctx=ctx, record={}, trace=red, peaks=None)


def test_serve_spans_nest(serve_profile):
    run = host_run(serve_profile)
    execute = scopes.spans(run, "serve.execute")
    inner = {n: scopes.spans(run, n) for n in ("serve.dispatch",
                                               "serve.fetch")}
    assert execute and all(inner.values())
    # one dispatch and one fetch a batch, each inside a batch's execute
    # (the two workers' spans interleave, so not necessarily the same one)
    for name, ss in inner.items():
        assert len(ss) == len(execute), name
        for s, e in ss:
            assert any(a <= s and e <= b for a, b in execute), name


def test_host_readers(serve_profile):
    run = host_run(serve_profile)
    vals = {m: read(m, run) for m in HOST}
    assert all(isinstance(v, float) and v > 0 for v in vals.values()), vals
    assert vals["serve_worker_util"] <= 100.0
    assert vals["serve_dispatch_ms"] <= vals["serve_exec_ms"]
    assert vals["serve_fetch_ms"] <= vals["serve_exec_ms"]
    # a trace without the program's spans: the readers report nothing
    bare = Reduction(windows=serve_profile.windows, ops=[],
                     host=[h for h in serve_profile.host
                           if not h[0].startswith("serve.")])
    assert [read(m, host_run(bare)) for m in HOST] == [None] * len(HOST)

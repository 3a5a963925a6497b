"""The trace reduction: interval arithmetic on hand-made events, and the
per-layer readers on a small trace recorded on a TPU v5 lite chip."""
import pathlib

import pytest

from benchmarks.chip import harness, trace
from benchmarks.chip.trace import Op, Reduction

TESTDATA = pathlib.Path(harness.HERE) / "testdata"


def op(name, start, end, opcode="fusion"):
    return Op(name, opcode, name, start, end)


def test_union_and_gaps_by_hand():
    ivs = [(0, 10), (5, 20), (30, 40), (35, 38), (50, 60)]
    assert trace.union_ns(ivs) == 20 + 10 + 10
    assert trace.gaps(ivs, 0, 70) == [(20, 30), (40, 50), (60, 70)]
    assert trace.gaps(ivs, 25, 45) == [(25, 30), (40, 45)]
    assert trace.gaps([], 3, 9) == [(3, 9)]


def test_parse_op_reads_name_opcode_and_target():
    o = trace.parse_op(
        '%block_fft.1 = (f32[4096,128]{1,0:T(8,128)S(1)}, f32[4096,128]'
        '{1,0:T(8,128)S(1)}) custom-call(f32[4096,128]{1,0:T(8,128)} %a), '
        'custom_call_target="tpu_custom_call"', 100, 50)
    assert (o.name, o.base, o.opcode) == ("block_fft.1", "block_fft",
                                          "custom-call")
    assert o.label == "block_fft.1 [tpu_custom_call]"
    assert (o.start, o.end) == (100, 150)
    c = trace.parse_op('%copy.4 = c64[512,1024]{1,0:T(8,128)} copy('
                       'c64[512,1024]{0,1:T(8,128)S(1)} %custom-call.2)',
                       0, 1)
    assert (c.base, c.opcode) == ("copy", "copy")
    a = trace.parse_op('%all-to-all.3 = f32[4,16,64]{2,1,0} all-to-all('
                       'f32[4,16,64]{2,1,0} %x), replica_groups={{0,1}}',
                       0, 1)
    assert trace.is_collective(a) and not trace.is_collective(c)


def test_window_sums_by_hand():
    """Two chips, a 100 ns window: chip 0 runs a kernel 10-40 and glue
    40-50 and 70-80; chip 1 a kernel 20-60. An op before the window does
    not count."""
    r = Reduction(
        windows={trace.WINDOW: (0, 100)},
        ops=[[op("early", -30, -10), op("block_fft.1", 10, 40),
              op("copy.2", 40, 50), op("copy.3", 70, 80)],
             [op("block_fft.1", 20, 60)]],
        host=[("dispatch", 0, 15), ("wait", 50, 100), ("outer", -5, 200)])
    assert r.window_s == 100e-9
    assert r.busy_s == pytest.approx((50 + 40) / 2 * 1e-9)
    assert r.idle_share() == pytest.approx(1 - 45 / 100)
    kern = r.op_ns(pred=lambda o: o.base == "block_fft")
    assert kern == (30 + 40) / 2
    assert r.op_ns(pred=lambda o: o.base != "block_fft") == (10 + 10) / 2
    b = r.breakdown()
    assert b["device_ops"][0] == ["block_fft.1", pytest.approx(35e-9)]
    # each gap goes to the host event covering most of it: chip 0 idles
    # 0-10 (dispatch), 50-70 and 80-100 (wait); chip 1 idles 0-20, where
    # outer covers all 20 and dispatch 15, and 60-100 (wait)
    gaps = dict((k, v) for k, v in b["idle_gaps"])
    assert gaps == {"wait": pytest.approx((20 + 20 + 40) / 2 * 1e-9),
                    "dispatch": pytest.approx(10 / 2 * 1e-9),
                    "outer": pytest.approx(20 / 2 * 1e-9)}


def test_missing_window_is_an_error():
    r = Reduction(windows={}, ops=[[]], host=[])
    with pytest.raises(KeyError):
        r.window_s


# Counted once from the raw events of the committed traces (every
# "XLA Ops" event of /device:TPU:0 that starts inside the host's
# bench_window / bench_plain annotation), by a loop that shares no code
# with trace.py. The traces: c2c_n1024 and abft_n1024 at (2048, 1024),
# a 20 ms window each, recorded on one TPU v5 lite chip.
C2C = {"calls": 43, "window_ns": 20793359, "kernel_ns": 6470321,
       "ops_ns": 9762400}
ABFT = {"calls": 23, "plain_calls": 48, "window_ns": 21455109,
        "kernel_ns": 4300132, "ops_ns": 6662627, "plain_ops_ns": 10898927}


def view(name, record, config):
    red = trace.reduce(str(TESTDATA / f"{name}_small.xplane.pb"), chips=1)
    ctx = harness.Context(cell=name, config=config, traffic={}, seed=0,
                          seconds=0.02, chips=1, trace=True, devices=[])
    return harness.RunView(ctx=ctx, record=record, trace=red, peaks=None)


def read(metric, run):
    return harness.load_reader(metric).read(run)


def test_recorded_c2c_trace():
    run = view("c2c_n1024", {"calls": C2C["calls"]},
               {"kernels": ["block_fft"]})
    assert run.trace.window_s == pytest.approx(C2C["window_ns"] * 1e-9)
    assert read("kernel_ms", run) == pytest.approx(
        C2C["kernel_ns"] / C2C["calls"] / 1e6)
    assert read("glue_ms", run) == pytest.approx(
        (C2C["ops_ns"] - C2C["kernel_ns"]) / C2C["calls"] / 1e6)
    idle = 100 * (1 - C2C["ops_ns"] / C2C["window_ns"])
    assert read("idle_share.batch", run) == pytest.approx(idle)
    assert run.trace.busy_s == pytest.approx(C2C["ops_ns"] * 1e-9)
    b = run.trace.breakdown()
    assert b["device_ops"][0] == ["block_fft.1 [tpu_custom_call]",
                                  pytest.approx(C2C["kernel_ns"] * 1e-9)]
    assert len(b["device_ops"]) == trace.TOP
    assert sum(v for _, v in b["device_ops"]) <= C2C["ops_ns"] * 1e-9
    idle_s = (C2C["window_ns"] - C2C["ops_ns"]) * 1e-9
    assert 0 < sum(v for _, v in b["idle_gaps"]) <= idle_s + 1e-12
    assert read("collective_ms", run) is None


def test_recorded_abft_trace():
    run = view("abft_n1024", {"calls": ABFT["calls"], "plain": True,
                              "plain_calls": ABFT["plain_calls"]},
               {"kernels": ["abft_fft"]})
    assert read("kernel_ms", run) == pytest.approx(
        ABFT["kernel_ns"] / ABFT["calls"] / 1e6)
    ft = ABFT["ops_ns"] / ABFT["calls"]
    plain = ABFT["plain_ops_ns"] / ABFT["plain_calls"]
    assert read("abft_overhead", run) == pytest.approx(100 * (ft / plain - 1))
    assert read("kernel_ms", view("abft_n1024", {"calls": 1},
                                  {"kernels": ["block_fft"]})) is None

"""``mesh_kernel_ms``, the device time a call of the sharded pipeline's
Pallas kernel passes (scope ``fft.kernel``), averaged over chips: read from
hand-made traces of four chips through a planted scope map, and silent
where the program's pencil has no kernel passes or the cell runs on one
chip."""
import pytest

from benchmarks.chip import harness, mesh_scopes, trace
from benchmarks.chip.trace import Op, Reduction

CALLS = 2

# chip c: two kernel passes of 300 * (c + 1) ns, a swap between them and a
# collective; one op before the window is left out
SCOPES = {"block_fft.3": "fft.kernel", "block_fft.5": "fft.kernel",
          "copy.54": "fft.local", "all-to-all.1": "fft.exchange"}


def _view(chips):
    cfg = harness.load_json(harness.HERE / "configs"
                            / "pencil_fp32_4chip.json")
    ctx = harness.Context(cell="t", config=cfg, traffic={}, seed=0,
                          seconds=0.0, chips=chips, trace=True, devices=[])
    ops = []
    for c in range(chips):
        k = 300 * (c + 1)
        ops.append([Op("block_fft.3", "custom-call", "k", -900, -800),
                    Op("block_fft.3", "custom-call", "k", 0, k),
                    Op("copy.54", "copy", "c", k, k + 50),
                    Op("block_fft.5", "custom-call", "k", k + 50, 2 * k + 50),
                    Op("all-to-all.1", "all-to-all", "a", 2 * k + 50,
                       2 * k + 90)])
    red = Reduction(windows={trace.WINDOW: (0, 100_000)}, ops=ops, host=[])
    return harness.RunView(ctx=ctx, record={"calls": CALLS, "b": 8,
                                            "n": 8192},
                           trace=red, peaks=None)


def test_reads_the_kernel_passes_averaged_over_chips(monkeypatch):
    monkeypatch.setattr(mesh_scopes, "scope_map", lambda run: SCOPES)
    got = harness.load_reader("mesh_kernel_ms").read(_view(4))
    want = sum(2 * 300 * (c + 1) for c in range(4)) / 4 / CALLS / 1e6
    assert got == pytest.approx(want)


def test_silent_without_kernel_passes(monkeypatch):
    """The XLA-einsum pencil maps nothing to ``fft.kernel``."""
    einsums = {k: ("fft.local" if v == "fft.kernel" else v)
               for k, v in SCOPES.items()}
    monkeypatch.setattr(mesh_scopes, "scope_map", lambda run: einsums)
    assert harness.load_reader("mesh_kernel_ms").read(_view(4)) is None


def test_silent_on_one_chip():
    assert harness.load_reader("mesh_kernel_ms").read(_view(1)) is None

"""One run of one benchmark cell: set up, measure, check, print one line.

Everything that belongs to one cell is data, found by name:

* ``BENCHMARK.json`` (at the checkout's root): the cell's configuration,
  traffic and chips, and which metrics each cell reports;
* ``configs/<config>.json``: the deployment (spec fields, guarantees);
* ``traffic/<traffic>.json``: the traffic mix, the name of the module in
  ``drivers/`` that plays it, and the limits of the correctness check;
* ``drivers/<driver>.py``: the code that plays a kind of traffic;
* ``layer_metrics/<metric>.py``: the reader of one per-layer metric.

A run fails, and prints no result, where JAX finds no TPU or fewer chips
than the cell asks for.
"""
from __future__ import annotations

import argparse
import dataclasses
import gc
import glob
import importlib
import importlib.util
import json
import pathlib
import shutil
import sys
import tempfile
import time

HERE = pathlib.Path(__file__).resolve().parent
ROOT = HERE.parents[1]
BENCH_FILE = ROOT / "BENCHMARK.json"


class BenchError(RuntimeError):
    """The cell cannot run here (no chip, unknown name, bad file)."""


def load_json(path) -> dict:
    return json.loads(pathlib.Path(path).read_text())


def find_cell(bench: dict, name: str) -> dict:
    for w in bench["workloads"]:
        if w["name"] == name:
            return w
    raise BenchError(f"no workload {name!r} in BENCHMARK.json; have "
                     f"{[w['name'] for w in bench['workloads']]}")


def metrics_for(bench: dict, cell: str, kind: str) -> list[dict]:
    """The ``end_to_end`` or ``per_layer`` entries that ``cell`` reports."""
    out = []
    for m in bench[kind]:
        cells = m.get("workloads")
        if cells is None or cell in cells:
            out.append(m)
    return out


def load_reader(name: str):
    """The per-layer reader ``layer_metrics/<name>.py`` (names may hold
    dots, so it is loaded from its path)."""
    path = HERE / "layer_metrics" / f"{name}.py"
    if not path.is_file():
        raise BenchError(f"no reader {path} for per-layer metric {name!r}")
    spec = importlib.util.spec_from_file_location(
        f"benchmarks.chip.layer_metrics.{name.replace('.', '_')}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def load_driver(name: str):
    if not (HERE / "drivers" / f"{name}.py").is_file():
        raise BenchError(f"no driver drivers/{name}.py")
    return importlib.import_module(f"benchmarks.chip.drivers.{name}")


@dataclasses.dataclass
class Context:
    """What a driver is given: the cell's files and the run's arguments."""

    cell: str
    config: dict
    traffic: dict
    seed: int
    seconds: float
    chips: int
    trace: bool
    devices: list


@dataclasses.dataclass
class RunView:
    """What a per-layer reader is given."""

    ctx: Context
    record: dict            # the traffic module's record of the window
    trace: object | None    # trace.Reduction of the traced window
    peaks: dict | None


def devices_for(chips: int, require_chip: bool) -> list:
    import jax
    devs = jax.devices()
    if require_chip and devs[0].platform != "tpu":
        raise BenchError(f"no TPU found (JAX platform "
                         f"{devs[0].platform!r}); the benchmark runs only "
                         f"on the chip")
    if len(devs) < chips:
        raise BenchError(f"the cell needs {chips} chips, JAX sees "
                         f"{len(devs)}")
    return devs[:chips]


def enable_cache() -> str:
    """JAX's persistent compile cache at the program's fixed place
    (``$JAX_COMPILATION_CACHE_DIR`` or ``<checkout>/.jax_cache``), keeping
    every program however fast it compiled."""
    import jax
    from repro.launch.compile_cache import enable_compile_cache
    path = enable_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return path


def memory_peak(devices) -> int:
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use", 0)
             for d in devices]
    return int(max(peaks))


class GcPauses:
    """Times the interpreter's garbage collections (a ``gc.callbacks``
    entry), so a stall in the window can be told apart from one of GC."""

    def __init__(self):
        self.count, self.total_s, self.max_s, self._t = 0, 0.0, 0.0, None

    def __call__(self, phase, info):
        if phase == "start":
            self._t = time.perf_counter()
        elif self._t is not None:
            d = time.perf_counter() - self._t
            self.count, self.total_s = self.count + 1, self.total_s + d
            self.max_s = max(self.max_s, d)


def run_cell(workload: str, *, seed: int, seconds: float, trace: bool,
             t_start: float, bench: dict | None = None,
             require_chip: bool = True, traffic_update: dict | None = None,
             controls: tuple = ()) -> dict:
    """One run of ``workload``; returns the result line as a dict.

    ``traffic_update`` (tests) overrides traffic parameters. For each
    entry of ``controls`` (``{"passes": 3}``: the control's precision) the
    driver's check runs once more with the control's outputs in place of
    the program's, under ``"control_checks"``.
    """
    bench = bench if bench is not None else load_json(BENCH_FILE)
    cell = find_cell(bench, workload)
    config = load_json(HERE / "configs" / f"{cell['config']}.json")
    traffic = load_json(HERE / "traffic" / f"{cell['traffic']}.json")
    traffic.update(traffic_update or {})
    driver = load_driver(traffic["driver"])
    e2e = metrics_for(bench, workload, "end_to_end")
    layer = metrics_for(bench, workload, "per_layer")
    readers = {m["name"]: load_reader(m["name"]) for m in layer} \
        if trace else {}

    devices = devices_for(cell["chips"], require_chip)
    t_devices = time.perf_counter() - t_start
    sys.path.insert(0, str(ROOT / "src"))
    import jax
    from benchmarks.chip import trace as trace_mod
    from benchmarks.chip import work
    cache = enable_cache() if require_chip else "off"
    dev = devices[0]
    peaks = work.load_peaks(dev.device_kind) if require_chip else None
    print(f"# {workload} seed={seed} on {dev.platform} {dev.device_kind} "
          f"x{len(devices)}; compile cache {cache}", file=sys.stderr,
          flush=True)

    ctx = Context(cell=workload, config=config, traffic=traffic, seed=seed,
                  seconds=seconds, chips=cell["chips"], trace=trace,
                  devices=devices)
    t_cell = time.perf_counter()
    run = driver.Cell(ctx)
    # what set-up made lives on: keep the collector from walking it again
    gc.collect()
    gc.freeze()
    setup_s = time.perf_counter() - t_start
    print(f"# set-up {setup_s:.3f} s: {t_devices:.3f} s to reach the "
          f"devices, {setup_s - (t_cell - t_start):.3f} s for inputs, "
          f"compile or cache load and warm-up", file=sys.stderr, flush=True)

    tdir = None
    if trace:
        tdir = tempfile.mkdtemp(prefix="bench_trace_")
        jax.profiler.start_trace(
            tdir, profiler_options=trace_mod.profile_options())
    pauses = GcPauses()
    gc.callbacks.append(pauses)
    try:
        record = run.window(seconds)
    finally:
        gc.callbacks.remove(pauses)
        gc.unfreeze()
        if trace:
            jax.profiler.stop_trace()
    print(f"# window: {pauses.count} garbage collections, {pauses.total_s:.4f}"
          f" s in all, the longest {pauses.max_s:.4f} s", file=sys.stderr,
          flush=True)
    mem = memory_peak(devices)
    reduction = None
    if trace:
        path = glob.glob(f"{tdir}/**/*.xplane.pb", recursive=True)
        if not path:
            raise BenchError(f"the profiler wrote no trace under {tdir}")
        reduction = trace_mod.reduce(path[0], chips=len(devices),
                                     window=trace_mod.WINDOW)
        shutil.rmtree(tdir, ignore_errors=True)
    checks = run.check()
    control_checks = [run.check(control=c) for c in controls]
    run.close()

    correct = all(c["value"] <= c["limit"] for c in checks)
    if trace:
        view = RunView(ctx=ctx, record=record, trace=reduction, peaks=peaks)
        metrics = {}
        for m in layer:
            v = readers[m["name"]].read(view)
            if v is not None:
                metrics[m["name"]] = {"value": float(v), "unit": m["unit"]}
    else:
        values = dict(record["e2e"], setup_s=setup_s)
        metrics = {m["name"]: {"value": float(values[m["name"]]),
                               "unit": m["unit"]}
                   for m in e2e if m["name"] in values}
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(devices), "memory_peak_bytes": mem}
    out = {"correct": correct, "attempted": int(record["attempted"]),
           "failed": int(record["failed"]), "metrics": metrics,
           "device": device}
    if reduction is not None:
        device["busy_s"] = reduction.busy_s
        device["window_s"] = reduction.window_s
        out["breakdown"] = reduction.breakdown()
    if controls:
        out["control_checks"] = [
            {"control": c, "correct": all(k["value"] <= k["limit"] for k in
                                          cc),
             "checks": {k["name"]: k["value"] for k in cc}}
            for c, cc in zip(controls, control_checks)]
    out["checks"] = {c["name"]: {"value": c["value"], "limit": c["limit"]}
                     for c in checks}
    return out


def main(argv=None, *, t_start: float | None = None) -> int:
    t_start = time.perf_counter() if t_start is None else t_start
    ap = argparse.ArgumentParser(
        description="Run one benchmark cell once on the chip and print one "
                    "JSON result line.")
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    try:
        out = run_cell(args.workload, seed=args.seed, seconds=args.seconds,
                       trace=bool(args.trace), t_start=t_start)
    except BenchError as e:
        print(f"benchmark: {e}", file=sys.stderr, flush=True)
        return 2
    for name, c in out["checks"].items():
        print(f"check {name} = {c['value']!r} (limit {c['limit']!r})",
              file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(out), flush=True)
    return 0

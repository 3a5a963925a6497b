"""BENCHMARK.json and the files it names: every name resolves to its own
file, every name and unit keeps to the allowed characters, a new cell is
picked up from files alone, and the command refuses to run without a TPU."""
import json
import os
import pathlib
import re
import shutil
import subprocess
import sys

from benchmarks.chip import harness

REPO = pathlib.Path(harness.ROOT)
CHIP = pathlib.Path(harness.HERE)
BENCH = json.loads((REPO / "BENCHMARK.json").read_text())
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
PATH = re.compile(r"^[A-Za-z0-9_.\-/]{1,200}$")
KEYS = {"command", "paths", "run_seconds", "configs", "workloads",
        "end_to_end", "per_layer"}


def _line_ok(text: str) -> bool:
    return 1 <= len(text) <= 200 and "\n" not in text and "\t" not in text


def test_top_level_shape():
    assert set(BENCH) == KEYS
    assert 1 <= BENCH["run_seconds"] <= 51
    assert isinstance(BENCH["run_seconds"], int)
    assert 1 <= len(BENCH["command"]) <= 32
    assert all(_line_ok(w) for w in BENCH["command"])
    assert 1 <= len(BENCH["paths"]) <= 16
    for p in BENCH["paths"]:
        assert PATH.match(p) and not p.startswith("/") and ".." not in p
        assert (REPO / p).is_dir()
    script = BENCH["command"][1]
    assert any(script.startswith(p + "/") for p in BENCH["paths"])
    assert len((REPO / "BENCHMARK.json").read_bytes()) <= 64 * 1024


def test_entries_have_just_their_keys():
    for c in BENCH["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
    for w in BENCH["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
    for m in BENCH["end_to_end"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "bound",
                                          "source"}
    for m in BENCH["per_layer"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better",
                                          "source", "layer", "moves"}


def test_names_and_units_use_allowed_characters():
    names = []
    for kind in ("configs", "workloads", "end_to_end", "per_layer"):
        for e in BENCH[kind]:
            assert NAME.match(e["name"]), e["name"]
            names.append((kind in ("end_to_end", "per_layer"), e["name"]))
    for w in BENCH["workloads"]:
        assert NAME.match(w["config"]) and NAME.match(w["traffic"])
        assert _line_ok(w["why"])
    for c in BENCH["configs"]:
        assert _line_ok(c["source"]) and _line_ok(c["why"])
        assert all(NAME.match(k) for k in c["reduced"])
    for m in BENCH["end_to_end"] + BENCH["per_layer"]:
        assert UNIT.match(m["unit"]), m["unit"]
        assert m["better"] in ("lower", "higher")
    for m in BENCH["per_layer"]:
        assert _line_ok(m["layer"])
    metric_names = [n for is_metric, n in names if is_metric]
    assert len(metric_names) == len(set(metric_names))
    for kind in ("configs", "workloads"):
        got = [e["name"] for e in BENCH[kind]]
        assert len(got) == len(set(got))
    for p in BENCH["paths"]:
        for f in (REPO / p).rglob("*"):
            if "__pycache__" in f.parts:
                continue
            rel = str(f.relative_to(REPO))
            assert PATH.match(rel), rel


def test_bounds_and_sources():
    for m in BENCH["end_to_end"]:
        assert 0.01 <= m["bound"] <= 0.25
        assert m["source"] in ("host_clock", "device_trace")
    assert any(m["name"] == "setup_s" and "workloads" not in m
               for m in BENCH["end_to_end"])
    for m in BENCH["per_layer"]:
        assert m["source"] in ("device_trace", "program_span",
                               "program_counter", "host_clock")


def test_every_cell_resolves_to_its_files():
    cells = {w["name"]: w for w in BENCH["workloads"]}
    used = {w["config"] for w in BENCH["workloads"]}
    configs = {c["name"]: c for c in BENCH["configs"]}
    assert used == set(configs)
    files = [c["file"] for c in BENCH["configs"]]
    assert len(files) == len(set(files))
    for c in BENCH["configs"]:
        path = REPO / c["file"]
        assert path == CHIP / "configs" / f"{c['name']}.json"
        data = json.loads(path.read_text())
        assert data["name"] == c["name"] and data["reduced"] == c["reduced"]
        assert data["source"] == c["source"]
    pairs = {(w["config"], w["traffic"]) for w in cells.values()}
    assert len(pairs) == len(cells)
    four = [w for w in cells.values() if w["chips"] == 4]
    assert all(w["chips"] in (1, 4) for w in cells.values())
    assert len(four) <= max(1, len(cells) // 2)
    for name, w in cells.items():
        traffic = json.loads(
            (CHIP / "traffic" / f"{w['traffic']}.json").read_text())
        assert (CHIP / "drivers" / f"{traffic['driver']}.py").is_file()
        assert harness.load_driver(traffic["driver"]).Cell
        e2e = {m["name"] for m in harness.metrics_for(BENCH, name,
                                                      "end_to_end")}
        layer = harness.metrics_for(BENCH, name, "per_layer")
        assert "setup_s" in e2e and len(e2e) >= 2, name
        assert layer, name
        for m in layer:
            assert m["moves"] in e2e, (name, m["name"])
            assert callable(harness.load_reader(m["name"]).read)


def test_per_layer_workloads_name_cells_and_layers_agree():
    cells = {w["name"] for w in BENCH["workloads"]}
    for m in BENCH["per_layer"] + BENCH["end_to_end"]:
        assert set(m.get("workloads", cells)) <= cells
    moves = {m["name"] for m in BENCH["end_to_end"]}
    assert all(m["moves"] in moves for m in BENCH["per_layer"])


def _no_tpu_env():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("PYTHONPATH", None)
    return env


def test_command_exits_nonzero_without_a_tpu():
    cmd = BENCH["command"] + ["--workload", BENCH["workloads"][0]["name"],
                              "--seed", str(2 ** 31 + 5), "--seconds", "1",
                              "--trace", "0"]
    out = subprocess.run([sys.executable] + cmd[1:], cwd=REPO,
                         env=_no_tpu_env(), capture_output=True, text=True,
                         timeout=300)
    assert out.returncode != 0
    assert "no TPU" in out.stderr
    assert '"correct"' not in out.stdout


def _copy_benchmark(dst: pathlib.Path):
    (dst / "benchmarks").mkdir()
    shutil.copytree(CHIP, dst / "benchmarks" / "chip",
                    ignore=shutil.ignore_patterns("__pycache__"))
    shutil.copy(REPO / "BENCHMARK.json", dst / "BENCHMARK.json")


def test_benchmark_alone_does_not_run(tmp_path):
    """A directory holding only BENCHMARK.json and the benchmark's own
    files has no program to measure: the run fails."""
    _copy_benchmark(tmp_path)
    code = ("import sys, time; sys.path.insert(0, '.');"
            "from benchmarks.chip import harness;"
            "harness.run_cell('c2c_n1024', seed=1, seconds=1, trace=False,"
            " t_start=time.perf_counter(), require_chip=False)")
    out = subprocess.run([sys.executable, "-c", code], cwd=tmp_path,
                         env=_no_tpu_env(), capture_output=True, text=True,
                         timeout=300)
    assert out.returncode != 0
    assert "repro" in out.stderr


def test_new_cell_is_picked_up_from_files_alone(tmp_path):
    """A cell added as one traffic file and one BENCHMARK.json entry runs
    with no edit to an existing file of the benchmark."""
    _copy_benchmark(tmp_path)
    before = {p: p.read_bytes()
              for p in (tmp_path / "benchmarks" / "chip").rglob("*.py")}
    (tmp_path / "benchmarks" / "chip" / "traffic" / "batch_n256.json"
     ).write_text(json.dumps({"driver": "batch", "n": 256, "batch": 32,
                              "limits": {"rel_err": 1e-6}}))
    bench = json.loads((tmp_path / "BENCHMARK.json").read_text())
    bench["workloads"].append({"name": "c2c_n256", "config": "c2c_fp32",
                               "traffic": "batch_n256", "chips": 1,
                               "why": "a small cell for this test"})
    for m in bench["end_to_end"] + bench["per_layer"]:
        if "c2c_n1024" in m.get("workloads", ()):
            m["workloads"].append("c2c_n256")
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(bench))
    code = ("import json, sys, time; sys.path.insert(0, '.');"
            f"sys.path.insert(1, {str(REPO / 'src')!r});"
            "from benchmarks.chip import harness;"
            "out = harness.run_cell('c2c_n256', seed=3, seconds=0.5,"
            " trace=False, t_start=time.perf_counter(), require_chip=False);"
            "print(json.dumps(out))")
    out = subprocess.run([sys.executable, "-c", code], cwd=tmp_path,
                         env=_no_tpu_env(), capture_output=True, text=True,
                         timeout=300)
    assert out.returncode == 0, out.stderr[-3000:]
    res = json.loads(out.stdout.strip().splitlines()[-1])
    assert res["correct"] and res["attempted"] > 0
    assert set(res["metrics"]) == {"throughput", "setup_s"}
    assert {p: p.read_bytes() for p in before} == before

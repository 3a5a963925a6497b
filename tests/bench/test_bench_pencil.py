"""The four-chip pencil cell's check on four virtual CPU devices, at a size
a test run holds: the program passes, the control fails, and a run with the
exchange between chips left out, or half the batch left out, comes out not
correct. Each case runs in a child process, which sets the device count
before JAX starts. The cell is not in BENCHMARK.json yet (it has not run
on four chips); its configuration and traffic files are, and the test
names the cell itself."""
import json
import os
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

CHILD = r"""
import json, sys, time
sys.path.insert(0, {repo!r}); sys.path.insert(1, {src!r})
import jax, jax.numpy as jnp
fault = {fault!r}
if fault == "no_exchange":
    real_a2a = jax.lax.all_to_all
    def local_only(x, axis_name, split_axis, concat_axis, **k):
        # what the all-to-all would do if every chip kept its own data
        d = jax.lax.psum(1, axis_name)
        return jnp.concatenate(jnp.split(x, d, axis=split_axis),
                               axis=concat_axis)
    jax.lax.all_to_all = local_only
elif fault == "half_batch":
    from repro.core.fft import api
    real_fft = api.FFTPlan.fft
    api.FFTPlan.fft = lambda self, x: real_fft(self, x).at[
        x.shape[0] // 2:].set(0)
from benchmarks.chip import harness
bench = harness.load_json(harness.BENCH_FILE)
bench["workloads"].append({{"name": "pencil4_n2e26",
                           "config": "pencil_fp32_4chip",
                           "traffic": "pencil_b16_n2e26", "chips": 4}})
for m in bench["end_to_end"]:
    m.pop("workloads", None)
out = harness.run_cell(
    "pencil4_n2e26", seed=2 ** 31 + 17, seconds=0.3, trace=False,
    t_start=time.perf_counter(), require_chip=False, bench=bench,
    traffic_update={{"n": 4096, "batch": 4, "transposed_split": [64, 64]}},
    controls=({controls}))
print(json.dumps(out))
"""


def run_child(fault: str, controls: str = ""):
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    env.pop("PYTHONPATH", None)
    code = CHILD.format(repo=REPO, src=os.path.join(REPO, "src"),
                        fault=fault, controls=controls)
    out = subprocess.run([sys.executable, "-c", code], cwd=REPO, env=env,
                         capture_output=True, text=True, timeout=600)
    assert out.returncode == 0, out.stderr[-3000:]
    return json.loads(out.stdout.strip().splitlines()[-1])


def test_program_passes_and_control_fails():
    out = run_child("", '{"passes": 3},')
    assert out["correct"], out["checks"]
    assert out["device"]["count"] == 4
    ctl = out["control_checks"][0]
    assert not ctl["correct"], ctl


@pytest.mark.parametrize("fault", ["no_exchange", "half_batch"])
def test_broken_pencil_is_caught(fault):
    out = run_child(fault)
    assert not out["correct"], out["checks"]

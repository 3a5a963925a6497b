"""The readers of the four-chip pencil cell: ``local_fft_ms``,
``mesh_scope_cover`` and ``collective_ms`` on hand-made traces of four chips
whose op names come from the sharded executor compiled on four virtual CPU
devices (in a child process, which sets the device count before JAX
starts), and their silence on one-chip cells and on a program whose
``executor_hlo`` refuses sharded plans."""
import json
import os
import subprocess
import sys

import pytest

from benchmarks.chip import harness, trace
from benchmarks.chip.trace import Op, Reduction

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
READERS = ("local_fft_ms", "mesh_scope_cover", "collective_ms")
CALLS = 2

CHILD = r"""
import json, sys
sys.path.insert(0, {repo!r}); sys.path.insert(1, {src!r})
from benchmarks.chip import harness, mesh_scopes, trace
from benchmarks.chip.trace import Op, Reduction
from repro.analysis.hlo import INSTR_RE

cfg = harness.load_json(harness.HERE / "configs" / "pencil_fp32_4chip.json")
ctx = harness.Context(cell="t", config=cfg, traffic={{}}, seed=0, seconds=0.0,
                      chips=4, trace=True, devices=[])
record = {{"calls": {calls}, "b": 8, "n": 8192}}

def view(ops):
    red = Reduction(windows={{trace.WINDOW: (0, 100_000)}}, ops=ops, host=[])
    return harness.RunView(ctx=ctx, record=record, trace=red, peaks=None)

def read(ops):
    return {{m: harness.load_reader(m).read(view(ops))
            for m in {readers!r}}}

m = mesh_scopes.scope_map(view([[]] * 4))
# one instruction of each scope, with its opcode from the compiled text
import jax, jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec
from repro.core.fft.api import FFTSpec, plan
from repro.launch.mesh import make_fft_mesh
mesh = make_fft_mesh(4)
text = plan(FFTSpec(shape=(8, 8192), mesh=mesh, natural_order=False)
            ).executor_hlo(jax.ShapeDtypeStruct(
                (8, 8192), jnp.complex64,
                sharding=NamedSharding(mesh, PartitionSpec(None, "fft"))))
opcode = {{g.group(1): g.group(3) for g in map(INSTR_RE.match,
                                                text.splitlines()) if g}}
names = {{}}
for name, scope in sorted(m.items()):
    names.setdefault(scope, name)
# chip c's op under scope i lasts 100 * (i + 1) * (c + 1) ns; an op no
# scope covers lasts 70 ns; one op before the window is left out
ops, dur = [], {{}}
for c in range(4):
    chip, t = [Op(names["fft.local"], "fusion", "f", -500, -400)], 0
    for i, s in enumerate(sorted(names)):
        d = 100 * (i + 1) * (c + 1)
        dur[(c, s)] = d
        chip.append(Op(names[s], opcode[names[s]], names[s], t, t + d))
        t += d
    chip.append(Op("not-in-this-module.9", "copy", "x", t, t + 70))
    ops.append(chip)
out = {{"opcodes": {{s: opcode[nm] for s, nm in names.items()}},
       "dur": [[c, s, d] for (c, s), d in dur.items()], "read": read(ops)}}

# a program whose executor_hlo covers local plans only
from repro.core.fft.api import FFTPlan
def local_only(self, x, **kw):
    raise ValueError("executor_hlo covers the local rank-1 C2C executors")
FFTPlan.executor_hlo = local_only
mesh_scopes._scope_map.cache_clear()
out["parent"] = read(ops)
print(json.dumps(out))
"""


@pytest.fixture(scope="module")
def child():
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    env.pop("PYTHONPATH", None)
    code = CHILD.format(repo=REPO, src=os.path.join(REPO, "src"),
                        calls=CALLS, readers=READERS)
    out = subprocess.run([sys.executable, "-c", code], cwd=REPO, env=env,
                         capture_output=True, text=True, timeout=600)
    assert out.returncode == 0, out.stderr[-3000:]
    return json.loads(out.stdout.strip().splitlines()[-1])


def test_readers_sum_the_planted_times(child):
    opcodes = child["opcodes"]
    assert {"fft.layout", "fft.local", "fft.twiddle", "fft.exchange"} \
        <= set(opcodes)
    # the first instruction by name under the exchange is its all-to-all
    assert opcodes["fft.exchange"] == "all-to-all"
    dur = {(c, s): d for c, s, d in child["dur"]}
    per_call = lambda ns: ns / CALLS / 1e6  # noqa: E731
    got = child["read"]
    assert got["local_fft_ms"] == pytest.approx(
        per_call(sum(dur[(c, "fft.local")] for c in range(4)) / 4))
    assert got["collective_ms"] == pytest.approx(per_call(sum(
        dur[(0, s)] for s, op in opcodes.items()
        if trace.is_collective(Op("o", op, "o", 0, 0)))))
    chip0 = sum(d for (c, _), d in dur.items() if c == 0)
    assert got["mesh_scope_cover"] == pytest.approx(
        100 * chip0 / (chip0 + 70))


def test_new_readers_silent_where_executor_hlo_is_local_only(child):
    """On a program without the sharded ``executor_hlo`` the scope readers
    report nothing; ``collective_ms`` reads opcodes, not scopes, so it still
    reads the planted all-to-all."""
    got = child["parent"]
    assert got["local_fft_ms"] is None
    assert got["mesh_scope_cover"] is None
    assert got["collective_ms"] == child["read"]["collective_ms"]


@pytest.mark.parametrize("config", ["c2c_fp32", "c2c_fp32_abft",
                                    "serve_lte", "pencil_fp32_4chip"])
def test_readers_silent_on_one_chip(config):
    cfg = harness.load_json(harness.HERE / "configs" / f"{config}.json")
    ctx = harness.Context(cell="t", config=cfg, traffic={}, seed=0,
                          seconds=0.0, chips=1, trace=True, devices=[])
    red = Reduction(windows={trace.WINDOW: (0, 10_000)},
                    ops=[[Op("fusion.1", "fusion", "f", 0, 10)]], host=[])
    run = harness.RunView(ctx=ctx, record={"calls": CALLS, "b": 8, "n": 8192},
                          trace=red, peaks=None)
    assert [harness.load_reader(m).read(run) for m in READERS] \
        == [None] * len(READERS)

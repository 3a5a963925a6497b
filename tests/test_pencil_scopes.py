"""The four-device pencil pipeline's stage scopes (CPU, four virtual devices).

At N = 2^13, B = 8 the split is 128 x 64, not square, so a swap of the digit
order shows. One child process (the device count is set before JAX starts)
runs the forward transform in natural and in transposed order, a copy of the
pipeline built with ``jax.named_scope`` made a no-op, and
``FFTPlan.executor_hlo`` on the sharded plans; the tests read its report:

* both orders match float64 NumPy, the transposed output read through
  ``y.reshape(n1, n2).T.reshape(n)``;
* the scopes leave the outputs bitwise as they were;
* ``op_scopes(plan.executor_hlo(x))`` puts the exchange's all-to-all under
  ``fft.exchange``, the Pallas block kernel's passes under ``fft.kernel``
  (interpreted on the CPU, so their matmuls are the entry's dots), the entry's
  conversion to planes under ``fft.split`` and pass 2's planes under
  ``fft.local``, and maps every instruction of the entry computation that
  carries metadata, other than the parameter and the constants (which run no
  device op).

Pass 2 here is one kernel pass; ``test_tpu_compile.py`` checks the swaps of
a two-factor pass 2 under ``fft.local``.
"""
import json

import pytest

from conftest import run_py

B, N, SPLIT = 8, 1 << 13, (128, 64)

CHILD = r"""
import contextlib, json
import jax, jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P
from repro.analysis.hlo import INSTR_RE, OP_NAME_RE, op_scopes
from repro.core.fft import distributed
from repro.core.fft.api import FFTSpec, plan
from repro.launch.mesh import make_fft_mesh

B, N = {b}, {n}
mesh = make_fft_mesh(4)
sharding = NamedSharding(mesh, P(None, "fft"))
rng = np.random.default_rng(2 ** 31 + 99)
xh = (rng.standard_normal((B, N))
      + 1j * rng.standard_normal((B, N))).astype(np.complex64)
x = jax.device_put(xh, sharding)
sds = jax.ShapeDtypeStruct((B, N), jnp.complex64, sharding=sharding)
ref = np.fft.fft(xh.astype(np.complex128), axis=-1)
out = {{}}

def rel_err(y, want):
    return float((np.abs(y - want).max(axis=-1)
                  / np.abs(want).max(axis=-1)).max())

def entry(text):
    body = text[text.index("\nENTRY"):].splitlines()[1:]
    rows = []
    for line in body:
        m = INSTR_RE.match(line)
        if m:
            rows.append((m.group(1), m.group(3),
                         OP_NAME_RE.search(m.group(4)) is not None))
    return rows

plans, ys = {{}}, {{}}
for nat in (True, False):
    p = plans[nat] = plan(FFTSpec(shape=(B, N), mesh=mesh,
                                  natural_order=nat))
    n1, n2 = p.dist_plan.n1, p.dist_plan.n2
    y = ys[nat] = np.asarray(p.fft(x))
    if not nat:
        y = y.reshape(B, n1, n2).transpose(0, 2, 1).reshape(B, N)
    text = p.executor_hlo(sds)
    scopes = op_scopes(text)
    rows = entry(text)
    out[str(nat)] = {{
        "split": [n1, n2], "rel_err": rel_err(y, ref),
        "same_text_from_array": p.executor_hlo(x) == text,
        "a2a": [scopes.get(nm) for nm, op, _ in rows
                if op.startswith("all-to-all")],
        "dots": [scopes.get(nm) for nm, op, _ in rows if op == "dot"],
        "unmapped": [nm for nm, op, meta in rows if meta
                     and op not in ("parameter", "constant")
                     and nm not in scopes],
        "scopes": sorted(set(scopes.values())),
    }}

# a copy of the pipeline with the scopes left out (the kernel's own jit
# cache would keep its traced scope)
jax.named_scope = lambda name: contextlib.nullcontext()
distributed._dist_fft_fn.cache_clear()
jax.clear_caches()
for nat in (True, False):
    fn = distributed._dist_fft_fn(mesh, "fft", False, nat, None, 1)
    text = fn.lower(sds).compile().as_text()
    y0 = np.asarray(fn(x))
    out[str(nat)]["bare_has_scopes"] = bool(op_scopes(text))
    out[str(nat)]["bitwise"] = bool(np.array_equal(
        y0.view(np.uint32), ys[nat].view(np.uint32)))

refused = []
for pl, kw in ((plans[False], dict(x=sds, op="ft_fft")),
               (plans[False], dict(x=jax.ShapeDtypeStruct((B, N),
                                                          jnp.complex64))),
               (plan(FFTSpec(shape=(B, N), mesh=mesh, real=True)),
                dict(x=jax.ShapeDtypeStruct((B, N), jnp.float32,
                                            sharding=sharding)))):
    try:
        pl.executor_hlo(**kw)
    except ValueError as e:
        refused.append(str(e)[:80])
out["refused"] = refused
print(json.dumps(out))
"""


@pytest.fixture(scope="module")
def report():
    text = run_py(CHILD.format(b=B, n=N), devices=4)
    return json.loads(text.strip().splitlines()[-1])


ORDERS = pytest.mark.parametrize("natural", [True, False],
                                 ids=["natural", "transposed"])


@ORDERS
def test_forward_matches_float64_reference(report, natural):
    r = report[str(natural)]
    assert tuple(r["split"]) == SPLIT
    # float32 kernel passes at HIGHEST over 13 radix-2 levels: a few 1e-7
    assert r["rel_err"] < 4e-6, r["rel_err"]


@ORDERS
def test_scopes_leave_outputs_bitwise(report, natural):
    r = report[str(natural)]
    assert not r["bare_has_scopes"]       # the copy really has no scopes
    assert r["bitwise"]


@ORDERS
def test_executor_hlo_maps_the_stages(report, natural):
    r = report[str(natural)]
    # the operand arrives in contiguous blocks: the ingest relayout is an
    # all-to-all carrying the ingest reshape's scope, then the exchange's
    assert r["a2a"] == ["fft.layout", "fft.exchange"], r["a2a"]
    assert r["dots"] and set(r["dots"]) == {"fft.kernel"}, r["dots"]
    assert r["unmapped"] == []
    assert {"fft.split", "fft.layout", "fft.kernel", "fft.local",
            "fft.twiddle", "fft.exchange"} <= set(r["scopes"])
    assert r["same_text_from_array"]


def test_executor_hlo_refuses_what_it_does_not_cover(report):
    ft, no_sharding, real = report["refused"]
    assert "'fft' executor" in ft
    assert "sharding" in no_sharding
    assert "rank-1 C2C" in real

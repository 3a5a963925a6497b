"""Distribution-layer tests on an 8-device host mesh (subprocess so the
XLA device-count flag doesn't leak into other tests)."""
import pytest

from conftest import run_py

pytestmark = pytest.mark.slow  # every test compiles on an 8-way subprocess


def test_param_specs_shard_and_run_training_step():
    """Real sharded train step on a 4x2 mesh: params FSDP+TP sharded, loss
    finite, and the result matches the single-device run bit-for-bit."""
    out = run_py("""
import dataclasses, numpy as np, jax, jax.numpy as jnp
from repro.launch.mesh import make_mesh
from jax.sharding import NamedSharding, PartitionSpec as P
from repro.configs import get_smoke_config
from repro.configs.base import RunConfig, ParallelConfig
from repro.models import Model
from repro.parallel import param_specs, batch_specs
from repro.train import make_train_step
from repro import optim
from repro.data import make_batch

cfg = dataclasses.replace(get_smoke_config('phi3_medium_14b'),
                          vocab_size=128, num_layers=2, dtype='float32')
model = Model(cfg)
mesh = make_mesh((4, 2), ('data', 'model'))
params = model.init(jax.random.PRNGKey(0))
specs = param_specs(jax.eval_shape(lambda: params), mesh)
sharded = jax.tree_util.tree_map(
    lambda l, sp: jax.device_put(l, NamedSharding(mesh, sp)), params, specs)
# at least one leaf actually sharded on each axis
flat = jax.tree_util.tree_leaves_with_path(specs)
names = set()
for kp, sp in flat:
    for part in sp:
        if part is not None:
            names.add(part if isinstance(part, str) else tuple(part))
assert 'model' in names, names
assert ('data',) in names or 'data' in names, names

run = RunConfig(model=cfg, parallel=ParallelConfig(remat='none'))
step_fn = make_train_step(model, run)
batch = {k: jnp.asarray(v) for k, v in
         make_batch(0, 0, batch=8, seq_len=32, vocab_size=128).items()}
opt = optim.init_state(params)
with mesh:
    p2, o2, m = jax.jit(step_fn)(sharded, opt, batch, jnp.int32(0))
print('sharded_loss', float(m['loss']))
p1, o1, m1 = jax.jit(step_fn)(params, opt, batch, jnp.int32(0))
print('single_loss', float(m1['loss']))
assert abs(float(m['loss']) - float(m1['loss'])) < 1e-3
print('OK')
""")
    assert "OK" in out


def test_cache_specs_seq_sharding_for_long_decode():
    out = run_py("""
import jax, jax.numpy as jnp
from repro.launch.mesh import make_mesh
from repro.parallel import cache_specs
mesh = make_mesh((4, 2), ('data', 'model'))
cache = {'scan': {'slot0': {'k': jax.ShapeDtypeStruct((3, 1, 1024, 2, 64),
                                                      jnp.bfloat16),
                            'v': jax.ShapeDtypeStruct((3, 1, 1024, 2, 64),
                                                      jnp.bfloat16)}}}
specs = cache_specs(cache, mesh, seq_shard=True)
sp = specs['scan']['slot0']['k']
assert sp[2] == 'data', sp   # batch=1 -> sequence axis sharded (SP)
print('OK', sp)
""")
    assert "OK" in out


def test_compressed_allreduce_close_to_exact():
    out = run_py("""
import numpy as np, jax, jax.numpy as jnp
from repro.launch.mesh import make_mesh
from jax.sharding import NamedSharding, PartitionSpec as P
from repro.parallel import compress_allreduce_mean
mesh = make_mesh((8,), ('data',))
rng = np.random.default_rng(0)
g_global = rng.standard_normal((8, 64, 64)).astype(np.float32)
# one different gradient shard per device: simulate with vmap-less loop
grads = {'w': jax.device_put(jnp.asarray(g_global),
                             NamedSharding(mesh, P('data')))}
res = {'w': jnp.zeros((8, 64, 64), jnp.float32)}
res = {'w': jax.device_put(res['w'], NamedSharding(mesh, P('data')))}

def f(g, r):
    return compress_allreduce_mean(g, r, mesh, ('data',))

with mesh:
    mean, new_res = jax.jit(f)(grads, res)
want = g_global.mean(axis=0, keepdims=True)
got = np.asarray(mean['w'])
# every shard got (approximately) the global mean
err = np.abs(got - want).max() / (np.abs(want).max() + 1e-9)
print('rel err', err)
assert err < 0.05, err
# error feedback residual carries the quantization error
assert np.abs(np.asarray(new_res['w'])).max() > 0
print('OK')
""")
    assert "OK" in out


def test_pipeline_stage_equivalence():
    out = run_py("""
import numpy as np, jax, jax.numpy as jnp
from repro.launch.mesh import make_mesh
from repro.parallel import pipeline_apply
mesh = make_mesh((4,), ('stage',))
rng = np.random.default_rng(0)
ws = jnp.asarray(rng.standard_normal((4, 16, 16)).astype(np.float32) * 0.3)
xs = jnp.asarray(rng.standard_normal((6, 8, 16)).astype(np.float32))

def fn_stage(w, x):
    return jnp.tanh(x @ w)

out = pipeline_apply(fn_stage, ws, xs, mesh, axis='stage')
# reference: sequential through all 4 stages
ref = xs
for i in range(4):
    ref = jnp.tanh(ref @ ws[i])
err = float(jnp.abs(out - ref).max())
print('err', err)
assert err < 1e-5
print('OK')
""")
    assert "OK" in out


def test_dryrun_cell_on_host_mesh():
    """End-to-end dry-run machinery on a small mesh: lower+compile a reduced
    config through the same code path as the production sweep."""
    out = run_py("""
import dataclasses, jax, jax.numpy as jnp, numpy as np
from repro.launch.mesh import make_mesh
from jax.sharding import NamedSharding, PartitionSpec as P
from repro.configs import get_smoke_config
from repro.configs.base import ParallelConfig, SHAPES, ShapeConfig
import repro.launch.dryrun as dr

mesh = make_mesh((4, 2), ('data', 'model'))
cfg = dataclasses.replace(get_smoke_config('gemma3_1b'), vocab_size=256)
shape = ShapeConfig('t', 64, 8, 'train')
par = ParallelConfig()
lowered, ntoks, n_params = dr._lower_cell(cfg, shape, mesh, par)
compiled = lowered.compile()
a = dr._analyze(compiled)
assert a['flops'] > 0
assert a['collectives']['total_bytes'] > 0
print('OK flops', a['flops'])
""", devices=8)
    assert "OK" in out

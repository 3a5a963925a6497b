"""Elastic restart: restoring a checkpoint onto a DIFFERENT (smaller) mesh.

Checkpoints are stored unsharded, so elasticity is a pure re-shard —
``elastic_restore`` must place params and optimizer moments by the NEW
mesh's param specs and replicate the step counter, regardless of the
geometry the checkpoint was written under.
"""
import numpy as np
import pytest

import jax
import jax.numpy as jnp

from conftest import run_py
from repro import optim
from repro.checkpoint import save_checkpoint
from repro.launch.elastic import HeartbeatMonitor, elastic_restore
from repro.launch.mesh import make_mesh


def _toy_state(seed=0):
    k = jax.random.PRNGKey(seed)
    params = {"layer": {"w": jax.random.normal(k, (16, 8)),
                        "b": jnp.zeros((8,))}}
    return params, optim.init_state(params)


def test_elastic_restore_single_device(tmp_path):
    """The restore path itself (fast lane, 1-device mesh): values survive
    the round trip and every leaf lands on the target mesh."""
    params, opt = _toy_state()
    opt = type(opt)(step=jnp.int32(7), mu=opt.mu, nu=opt.nu)
    save_checkpoint(str(tmp_path), 7, (params, opt))

    mesh = make_mesh((len(jax.devices()), 1), ("data", "model"))
    template = (jax.tree_util.tree_map(jnp.zeros_like, params), opt)
    (p_r, o_r), meta = elastic_restore(str(tmp_path), template, mesh)

    jax.tree_util.tree_map(np.testing.assert_allclose, p_r, params)
    jax.tree_util.tree_map(np.testing.assert_allclose, o_r.mu, opt.mu)
    assert int(o_r.step) == 7
    for leaf in jax.tree_util.tree_leaves(p_r):
        assert leaf.sharding.mesh.shape == mesh.shape


def test_heartbeat_monitor_flags_persistent_straggler():
    mon = HeartbeatMonitor(num_hosts=4, straggle_factor=2.0, patience=2)
    fast = np.array([1.0, 1.0, 1.0, 1.0])
    slow = np.array([1.0, 1.0, 5.0, 1.0])
    assert mon.observe(slow) == []          # first strike: not yet flagged
    assert mon.observe(slow) == [2]         # persistent -> excluded
    assert mon.observe(fast) == []          # recovery resets the strikes
    assert mon.observe(slow) == []


@pytest.mark.slow
def test_elastic_restore_smaller_mesh(tmp_path):
    """Write a checkpoint from an FSDP-sharded 4x2 run, lose half the
    devices, and restore onto 2x2: same values, shardings rebuilt for the
    smaller mesh (the fail-stop elasticity contract)."""
    out = run_py(f"""
import numpy as np, jax, jax.numpy as jnp
from repro.launch.mesh import make_mesh
from jax.sharding import NamedSharding
from repro import optim
from repro.checkpoint import save_checkpoint
from repro.launch.elastic import elastic_restore
from repro.parallel import param_specs

k = jax.random.PRNGKey(0)
params = {{'layer': {{'w': jax.random.normal(k, (16, 8)),
                      'b': jnp.zeros((8,))}}}}
opt = optim.init_state(params)

# the "before" job: 4x2 mesh, leaves sharded by its param specs
big = make_mesh((4, 2), ('data', 'model'))
specs = param_specs(params, big)
sharded = jax.tree_util.tree_map(
    lambda l, sp: jax.device_put(l, NamedSharding(big, sp)), params, specs)
save_checkpoint({str(tmp_path)!r}, 3, (sharded, opt))

# the "after" job: half the devices are gone
small = make_mesh((2, 2), ('data', 'model'))
template = (jax.tree_util.tree_map(jnp.zeros_like, params), opt)
(p_r, o_r), meta = elastic_restore({str(tmp_path)!r}, template, small)
assert meta['step'] == 3, meta

for l_r, l in zip(jax.tree_util.tree_leaves(p_r),
                  jax.tree_util.tree_leaves(params)):
    np.testing.assert_allclose(np.asarray(l_r), np.asarray(l))
    assert l_r.sharding.mesh.shape == small.shape, l_r.sharding
# optimizer moments follow the params; the step counter is replicated
for l in jax.tree_util.tree_leaves(o_r.mu):
    assert l.sharding.mesh.shape == small.shape
assert o_r.step.sharding.is_fully_replicated
assert int(o_r.step) == 0
print('OK')
""")
    assert "OK" in out

"""Elastic rescaling: a checkpoint written under one mesh topology must
restore onto a different topology (the node-loss recovery path), verified
on real multi-device meshes in a subprocess."""
import os
import subprocess
import sys

SCRIPT = r"""
import os, tempfile
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import jax, jax.numpy as jnp, numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P
from repro.checkpoint import store
from repro.configs import get_config
from repro.launch.mesh import make_mesh
from repro.models import api
from repro.parallel import sharding as shd

cfg = get_config("starcoder2-3b").reduced()
with tempfile.TemporaryDirectory() as d:
    # "before": params laid out on a 4x2 (data, model) mesh
    mesh_a = make_mesh((4, 2), ("data", "model"))
    specs_a = shd.param_pspecs(
        jax.eval_shape(lambda: api.init_params(jax.random.key(0), cfg)), mesh_a)
    shard_a = jax.tree.map(lambda s: NamedSharding(mesh_a, s), specs_a)
    with mesh_a:
        params = jax.jit(lambda: api.init_params(jax.random.key(0), cfg),
                         out_shardings=shard_a)()
    store.save(d, 7, params, meta={"mesh": "4x2"})

    # "after": two nodes lost -> restore onto a 2x2 mesh
    devs = np.array(jax.devices()[:4]).reshape(2, 2)
    mesh_b = jax.sharding.Mesh(devs, ("data", "model"))
    specs_b = shd.param_pspecs(params, mesh_b)
    shard_b = jax.tree.map(lambda s: NamedSharding(mesh_b, s), specs_b)
    restored = store.restore(d, 7, params, shard_b)

    for a, b in zip(jax.tree.leaves(params), jax.tree.leaves(restored)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    for leaf, sh in zip(jax.tree.leaves(restored), jax.tree.leaves(
            shard_b, is_leaf=lambda x: isinstance(x, NamedSharding))):
        assert leaf.sharding == sh
print("RESHARD_OK")
"""


def test_elastic_reshard_across_meshes():
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.abspath(
        os.path.join(os.path.dirname(__file__), "..", "src"))
    r = subprocess.run([sys.executable, "-c", SCRIPT], env=env,
                       capture_output=True, text=True, timeout=600)
    assert "RESHARD_OK" in r.stdout, f"stdout={r.stdout}\nstderr={r.stderr[-2000:]}"

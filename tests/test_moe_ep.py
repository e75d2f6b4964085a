"""MoE expert-parallel shard_map dispatch must match the dense dispatch
numerically (subprocess: needs a multi-device mesh)."""
import os
import subprocess
import sys

SCRIPT = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
import jax, jax.numpy as jnp, numpy as np
from repro.configs import get_config
from repro.launch.mesh import make_mesh
from repro.models import moe
from repro.parallel import act

cfg = get_config("kimi-k2-1t-a32b").reduced()   # 4 experts, top-2
params = moe.init_moe_mlp(jax.random.key(0), cfg)
x = jnp.asarray(np.random.default_rng(0).standard_normal((2, 16, cfg.d_model)),
                jnp.float32)

y_dense, aux_dense = moe.moe_mlp(x, params, cfg)

mesh = make_mesh((1, 4), ("data", "model"))
specs = act.default_specs(mesh)
specs["_ep_mesh"] = (mesh, "model")
with mesh, act.activation_specs(specs):
    y_ep, aux_ep = jax.jit(lambda x, p: moe.moe_mlp(x, p, cfg))(x, params)

np.testing.assert_allclose(np.asarray(y_ep), np.asarray(y_dense),
                           atol=2e-5, rtol=2e-5)
np.testing.assert_allclose(float(aux_ep), float(aux_dense), atol=1e-5)
print("MOE_EP_OK")
"""


def test_moe_ep_matches_dense():
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.abspath(
        os.path.join(os.path.dirname(__file__), "..", "src"))
    r = subprocess.run([sys.executable, "-c", SCRIPT], env=env,
                       capture_output=True, text=True, timeout=600)
    assert "MOE_EP_OK" in r.stdout, f"stdout={r.stdout}\nstderr={r.stderr[-3000:]}"

"""Pipeline-parallel equivalence on a real multi-device mesh, via a
subprocess with XLA_FLAGS host-device virtualization (the main test
process is locked to 1 CPU device)."""
import os
import subprocess
import sys

SCRIPT = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
import jax, jax.numpy as jnp, numpy as np
from repro.launch.mesh import make_mesh
from repro.parallel.pipeline import pipeline_apply, split_microbatches

mesh = make_mesh((4,), ("stage",))
d = 16
ws = jnp.asarray(np.random.default_rng(0).standard_normal((4, d, d)) * 0.3,
                 jnp.float32)
x = jnp.asarray(np.random.default_rng(1).standard_normal((8, d)), jnp.float32)

def stage(w, h):
    return jnp.tanh(h @ w)

seq = x
for i in range(4):
    seq = stage(ws[i], seq)
mbs = split_microbatches(x, 4)
out = pipeline_apply(stage, ws, mbs, mesh)
np.testing.assert_allclose(np.asarray(out.reshape(8, d)), np.asarray(seq),
                           atol=1e-5)

# differentiability: grads through the pipeline match sequential grads
def loss_pipe(ws):
    return pipeline_apply(stage, ws, mbs, mesh).sum()

def loss_seq(ws):
    h = x
    for i in range(4):
        h = stage(ws[i], h)
    return h.sum()

gp = jax.grad(loss_pipe)(ws)
gs = jax.grad(loss_seq)(ws)
np.testing.assert_allclose(np.asarray(gp), np.asarray(gs), atol=1e-4)
print("PIPELINE_OK")
"""


def test_pipeline_parallel_subprocess():
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.abspath(
        os.path.join(os.path.dirname(__file__), "..", "src"))
    r = subprocess.run([sys.executable, "-c", SCRIPT], env=env,
                       capture_output=True, text=True, timeout=300)
    assert "PIPELINE_OK" in r.stdout, f"stdout={r.stdout}\nstderr={r.stderr}"

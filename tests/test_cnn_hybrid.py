"""VGG-in-JAX + the hybrid (pipeline-head/generic-tail) execution plan."""
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.netinfo import _B, vgg16
from repro.models.cnn import HybridPlan, forward, hybrid_forward, init_vgg


def _tiny_net():
    b = _B("tiny", 16, 16, 8)
    b.conv(8, 3).conv(8, 3).pool(2).conv(16, 3)
    return b.done()


def test_vgg_forward_shapes():
    net = _tiny_net()
    params = init_vgg(jax.random.key(0), net)
    x = jnp.zeros((2, 8, 16, 16))
    y = forward(params, net, x)
    assert y.shape == (2, 16, 8, 8)


def test_vgg_pallas_conv_path_matches_lax():
    net = _tiny_net()
    params = init_vgg(jax.random.key(0), net)
    x = jnp.asarray(np.random.default_rng(0).standard_normal((1, 8, 16, 16)),
                    jnp.float32)
    y_lax = forward(params, net, x, use_pallas=False)
    y_pl = forward(params, net, x, use_pallas=True)
    np.testing.assert_allclose(np.asarray(y_pl), np.asarray(y_lax),
                               atol=1e-4, rtol=1e-4)


def test_hybrid_sequential_fallback_matches_forward():
    net = vgg16(32)
    params = init_vgg(jax.random.key(1), net)
    x = jnp.asarray(np.random.default_rng(1).standard_normal((2, 3, 32, 32)),
                    jnp.float32)
    ref = forward(params, net, x)
    out = hybrid_forward(params, net, x, HybridPlan(sp=4, n_micro=2), mesh=None)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=1e-5)


def test_hybrid_pipelined_subprocess():
    """The real pipelined head (4 stages) must match sequential execution —
    the examples script asserts this internally."""
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.abspath(
        os.path.join(os.path.dirname(__file__), "..", "src"))
    script = os.path.abspath(os.path.join(os.path.dirname(__file__), "..",
                                          "examples", "hybrid_vgg_pipeline.py"))
    r = subprocess.run([sys.executable, script], env=env,
                       capture_output=True, text=True, timeout=600)
    assert "OK" in r.stdout, f"stdout={r.stdout}\nstderr={r.stderr[-2000:]}"


def test_hybrid_forward_pallas_matches_forward():
    """use_pallas reaches every conv of the head and the tail."""
    net = _tiny_net()
    params = init_vgg(jax.random.key(2), net)
    x = jnp.asarray(np.random.default_rng(2).standard_normal((2, 8, 16, 16)),
                    jnp.float32)
    ref = forward(params, net, x)
    out = hybrid_forward(params, net, x, HybridPlan(sp=2, n_micro=1),
                         use_pallas=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=1e-4, rtol=1e-4)


def test_hybrid_mesh_rejects_heterogeneous_head():
    """A stage mesh over a head whose layers differ in shape raises; it
    never runs the head sequentially in silence."""
    import pytest
    from jax.sharding import AbstractMesh
    net = vgg16(32)     # conv 3->64, conv 64->64, pool, conv 64->128
    params = init_vgg(jax.random.key(1), net)
    x = jnp.zeros((4, 3, 32, 32))
    with pytest.raises(ValueError, match="one weight shape"):
        hybrid_forward(params, net, x, HybridPlan(sp=4, n_micro=2),
                       mesh=AbstractMesh((4,), ("stage",)))
    with pytest.raises(ValueError, match="one pipeline stage per head"):
        hybrid_forward(params, net, x, HybridPlan(sp=2, n_micro=2),
                       mesh=AbstractMesh((4,), ("stage",)))

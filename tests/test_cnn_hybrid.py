"""VGG-in-JAX + the hybrid (pipeline-head/generic-tail) execution plan."""
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

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


def _odd_net(h, w):
    b = _B("odd", h, w, 3)
    b.conv(16, 3).conv(16, 3).pool(2).conv(24, 3).pool(2).conv(8, 3)
    return b.done()


def _row_between_frames_net():
    """A 10000-wide frame: 8 -> 8 channels fit the frame kernel's VMEM,
    8 -> 64 do not (its frame, output and product take 83 MB in float32)
    and take the row kernel, 64 -> 8 fit again."""
    b = _B("rows_between", 8, 10000, 8)
    b.conv(8, 3).conv(64, 3).conv(8, 3).pool(2).conv(8, 3)
    return b.done()


# (net, input dtype, output-channel block cap or None for the default):
# vgg16 at 32 (C = 3 first), odd sizes whose pools floor, K > bk, and a
# row-kernel conv between frame convs
CHAIN_CASES = [
    pytest.param(lambda: vgg16(32), jnp.bfloat16, None, id="vgg16-32-bf16"),
    pytest.param(lambda: vgg16(32), jnp.float32, None, id="vgg16-32-f32"),
    pytest.param(lambda: _odd_net(7, 9), jnp.float32, None, id="odd-7x9"),
    pytest.param(lambda: _odd_net(15, 15), jnp.bfloat16, None,
                 id="odd-15x15"),
    pytest.param(lambda: _odd_net(15, 15), jnp.float32, 8, id="k-over-bk"),
    pytest.param(_row_between_frames_net, jnp.float32, None,
                 id="row-between-frames"),
]


@pytest.mark.parametrize("make_net,dtype,bk", CHAIN_CASES)
def test_frame_chain_is_the_per_layer_composition(make_net, dtype, bk,
                                                  monkeypatch):
    """The Pallas forward, which keeps frame convs' outputs in the frame
    layout, equals the per-layer NCHW composition (``ops.conv2d``, ReLU,
    ``reduce_window``) bit for bit, and the lax.conv forward within the
    kernels' tolerance."""
    import functools

    from repro.kernels.conv2d import ops
    from repro.models.cnn import layer_apply
    if bk is not None:
        for name in ("conv2d", "conv_frame", "conv2d_relu_frame"):
            monkeypatch.setattr(ops, name,
                                functools.partial(getattr(ops, name), bk=bk))
    net = make_net()
    params = init_vgg(jax.random.key(3), net, dtype=dtype)
    x = jax.random.normal(jax.random.key(4),
                          (2, net.input_c, *net.input_hw), dtype)
    if net.name == "rows_between":
        taken = [ops.conv_frame(w, (l.h, l.w), x.dtype) is not None
                 for w, l in zip(params, net.layers) if l.kind == "conv"]
        assert taken == [True, False, True, True]
    chained = forward(params, net, x, use_pallas=True)
    composed = x
    for w, l in zip(params, net.layers):
        composed = layer_apply(composed, w, l, use_pallas=True)
    assert chained.shape == composed.shape and chained.dtype == dtype
    np.testing.assert_array_equal(np.asarray(chained, np.float32),
                                  np.asarray(composed, np.float32))
    ref = forward(params, net, x)
    tol = 5e-2 if dtype == jnp.bfloat16 else 1e-4
    np.testing.assert_allclose(np.asarray(chained, np.float32),
                               np.asarray(ref, np.float32), atol=tol,
                               rtol=tol)

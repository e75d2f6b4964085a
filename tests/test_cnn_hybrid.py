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


def _rows_then_frames_net():
    """A 32x300 input: with the frame kernel's VMEM limit set between the
    two groups, conv1-conv4 (32 and 16 rows) take the row kernel and
    conv6-conv7 (8 rows) the frame kernel."""
    b = _B("rows_then_frames", 32, 300, 8)
    b.conv(8, 3).conv(8, 3).pool(2).conv(16, 3).pool(2).conv(16, 3) \
        .conv(8, 3)
    return b.done()


# (net, input dtype, output-channel block cap or None for the default,
# the layouts' conversions, the row convs that stack their taps): vgg16
# at 32 (C = 3 first), odd sizes whose pools floor, K > bk, a row-kernel
# conv between frame convs, and row convs then frame convs
CHAIN_CASES = [
    pytest.param(lambda: vgg16(32), jnp.bfloat16, None, 2, 0,
                 id="vgg16-32-bf16"),
    pytest.param(lambda: vgg16(32), jnp.float32, None, 2, 0,
                 id="vgg16-32-f32"),
    pytest.param(lambda: _odd_net(7, 9), jnp.float32, None, 2, 0,
                 id="odd-7x9"),
    pytest.param(lambda: _odd_net(15, 15), jnp.bfloat16, None, 2, 0,
                 id="odd-15x15"),
    pytest.param(lambda: _odd_net(15, 15), jnp.float32, 8, 2, 0,
                 id="k-over-bk"),
    pytest.param(_row_between_frames_net, jnp.float32, None, 4, 1,
                 id="row-between-frames"),
    pytest.param(_rows_then_frames_net, jnp.float32, None, 3, 3,
                 id="rows-then-frames"),
    pytest.param(_rows_then_frames_net, jnp.bfloat16, None, 3, 3,
                 id="rows-then-frames-bf16"),
]


@pytest.mark.parametrize("make_net,dtype,bk,relayouts,im2col", CHAIN_CASES)
def test_frame_chain_is_the_per_layer_composition(make_net, dtype, bk,
                                                  relayouts, im2col,
                                                  monkeypatch, tmp_path,
                                                  request):
    """The Pallas forward, which keeps each conv's output in its kernel's
    layout (frames, or the row kernel's padded rows), equals the
    per-layer NCHW composition (``ops.conv2d``, ReLU, ``reduce_window``)
    bit for bit, and the lax.conv forward within the kernels' tolerance;
    its ``cnn.relayouts`` gauge counts the layout conversions and its
    ``cnn.rows_im2col`` gauge the row convs that stack their taps."""
    import functools

    from repro import obs
    from repro.kernels.conv2d import conv2d as K
    from repro.kernels.conv2d import ops
    from repro.models.cnn import layer_apply
    if bk is not None:
        for name in ("conv2d", "layout_of", "conv2d_relu"):
            monkeypatch.setattr(ops, name,
                                functools.partial(getattr(ops, name), bk=bk))
    net = make_net()
    params = init_vgg(jax.random.key(3), net, dtype=dtype)
    x = jax.random.normal(jax.random.key(4),
                          (2, net.input_c, *net.input_hw), dtype)
    convs = [(w, l) for w, l in zip(params, net.layers) if l.kind == "conv"]
    if net.name == "rows_then_frames":
        # the limit the 8-row convs fit and the larger ones do not; the
        # NCHW conv's jitted dispatch decides at trace time, so its
        # compiled paths go with the limit
        monkeypatch.setattr(K, "FRAME_VMEM_LIMIT", max(
            K.frame_vmem_bytes(l.c, l.k, l.r, l.s, l.h, l.w,
                               x.dtype.itemsize) for _, l in convs[3:]))
        K.conv2d_same.clear_cache()
        request.addfinalizer(K.conv2d_same.clear_cache)
    taken = [type(ops.layout_of(w, (l.h, l.w), x.dtype)).__name__
             for w, l in convs]
    if net.name == "rows_between":
        assert taken == ["Frame", "Rows", "Frame", "Frame"]
    if net.name == "rows_then_frames":
        assert taken == ["Rows"] * 3 + ["Frame"] * 2
    events = tmp_path / "events.jsonl"
    with obs.Tracer(events, annotate=False):
        chained = forward(params, net, x, use_pallas=True)
    size = {"input": "x".join(map(str, net.input_hw))}
    for name, want in (("cnn.relayouts", relayouts),
                       ("cnn.rows_im2col", im2col)):
        gauges = [e for e in obs.load_events(events) if e["name"] == name]
        assert [(g["value"], g["attrs"]) for g in gauges] == [(want, size)]
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


@pytest.mark.parametrize("hw,relayouts,im2col", [((224, 224), 2, 0),
                                                 ((720, 1280), 3, 1)])
def test_vgg16_gauges_at_the_benchmark_sizes(hw, relayouts, im2col,
                                             tmp_path):
    """Traced (not run) at vgg16's benchmark sizes in bf16, the chain's
    gauges read what the kernels' rules give: at 224x224 every conv
    takes the frame kernel and none stacks row taps; at 720x1280
    conv1-conv13 take the row kernel and conv1 alone (C = 3, a stack 27
    deep) stacks its taps."""
    from repro import obs
    net = vgg16(*hw)
    params = [None if l.kind == "pool" else jax.ShapeDtypeStruct(
        (l.k, l.c, l.r, l.s), jnp.bfloat16) for l in net.layers]
    x = jax.ShapeDtypeStruct((2, 3, *hw), jnp.bfloat16)
    events = tmp_path / "events.jsonl"
    with obs.Tracer(events, annotate=False):
        jax.eval_shape(lambda p, x: forward(p, net, x, use_pallas=True),
                       params, x)
    got = {e["name"]: e["value"] for e in obs.load_events(events)
           if e["name"] in ("cnn.relayouts", "cnn.rows_im2col")}
    assert got == {"cnn.relayouts": relayouts, "cnn.rows_im2col": im2col}

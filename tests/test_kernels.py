"""Pallas kernel validation: shape/dtype sweeps vs the pure-jnp oracles,
run in Pallas interpret mode, which the wrappers pick on the CPU backend
(kernel body executed on CPU; tests/test_tpu_compile.py compiles the
same kernels for a TPU)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels.conv2d.ops import conv2d
from repro.kernels.conv2d.ref import conv2d_ref
from repro.kernels.flash_attention.ops import flash_attention
from repro.kernels.flash_attention.ref import attention_ref
from repro.kernels.matmul.ops import matmul
from repro.kernels.matmul.ref import matmul_ref
from repro.kernels.ssd.ops import ssd
from repro.kernels.ssd.ref import ssd_ref

try:
    from hypothesis import given, settings
    from hypothesis import strategies as st
    HAVE_HYP = True
except ImportError:  # pragma: no cover
    HAVE_HYP = False

RNG = np.random.default_rng(42)


def _tol(dtype):
    return dict(atol=2e-2, rtol=2e-2) if dtype == jnp.bfloat16 else \
        dict(atol=2e-4, rtol=2e-4)


# ---------------------------------------------------------------------------
# flash attention
# ---------------------------------------------------------------------------

ATTN_CASES = [
    # (b, s, h, kv, hd, causal, window)
    (1, 128, 4, 2, 64, True, None),
    (2, 96, 4, 4, 32, True, None),       # ragged seq len
    (1, 256, 8, 2, 64, True, 64),        # sliding window
    (1, 64, 2, 2, 64, False, None),      # bidirectional (whisper encoder)
    (1, 128, 6, 2, 48, True, None),      # non-pow2 head count/dim
]


@pytest.mark.parametrize("case", ATTN_CASES)
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_flash_attention_matches_ref(case, dtype):
    b, s, h, kv, hd, causal, win = case
    q = jnp.asarray(RNG.standard_normal((b, s, h, hd)), dtype)
    k = jnp.asarray(RNG.standard_normal((b, s, kv, hd)), dtype)
    v = jnp.asarray(RNG.standard_normal((b, s, kv, hd)), dtype)
    out = flash_attention(q, k, v, causal=causal, window=win, bq=32, bk=32)
    ref = attention_ref(q, k, v, causal=causal, window=win)
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(ref, np.float32), **_tol(dtype))


def test_flash_attention_matches_model_attention():
    """The kernel hook must agree with gqa_attention's einsum path."""
    from repro.kernels.flash_attention.ops import attn_fn
    from repro.models.layers import gqa_attention, init_attention
    d, h, kv = 64, 4, 2
    params = init_attention(jax.random.key(0), d, h, kv)
    x = jnp.asarray(RNG.standard_normal((2, 32, d)), jnp.float32)
    ref = gqa_attention(x, params, h, kv, rope=True)
    out = gqa_attention(x, params, h, kv, rope=True, attn_fn=attn_fn)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=2e-4, rtol=2e-4)


# ---------------------------------------------------------------------------
# matmul
# ---------------------------------------------------------------------------

MM_CASES = [(256, 512, 256), (100, 300, 50), (64, 64, 64), (128, 1, 128),
            (33, 65, 17)]


@pytest.mark.parametrize("mkn", MM_CASES)
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_matmul_matches_ref(mkn, dtype):
    m, k, n = mkn
    a = jnp.asarray(RNG.standard_normal((m, k)), dtype)
    b = jnp.asarray(RNG.standard_normal((k, n)), dtype)
    out = matmul(a, b, bm=64, bn=64, bk=128)
    ref = matmul_ref(a, b)
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(ref, np.float32),
                               atol=1e-2 if dtype == jnp.bfloat16 else 1e-3,
                               rtol=1e-2 if dtype == jnp.bfloat16 else 1e-4)


if HAVE_HYP:

    @given(st.integers(1, 200), st.integers(1, 200), st.integers(1, 200))
    @settings(max_examples=12, deadline=None)
    def test_matmul_property_random_shapes(m, k, n):
        a = jnp.asarray(RNG.standard_normal((m, k)), jnp.float32)
        b = jnp.asarray(RNG.standard_normal((k, n)), jnp.float32)
        out = matmul(a, b, bm=32, bn=32, bk=64)
        np.testing.assert_allclose(np.asarray(out), np.asarray(matmul_ref(a, b)),
                                   atol=1e-3, rtol=1e-3)


# ---------------------------------------------------------------------------
# ssd
# ---------------------------------------------------------------------------

SSD_CASES = [(2, 128, 4, 32, 16, 32), (1, 256, 2, 64, 32, 64),
             (1, 64, 8, 16, 64, 16)]


@pytest.mark.parametrize("case", SSD_CASES)
def test_ssd_matches_chunked_ref(case):
    b_, s, h, p, n, chunk = case
    x = jnp.asarray(RNG.standard_normal((b_, s, h, p)) * 0.5, jnp.float32)
    dt = jnp.asarray(RNG.uniform(0.1, 1.0, (b_, s, h)), jnp.float32)
    a_log = jnp.asarray(RNG.uniform(-1, 0.5, (h,)), jnp.float32)
    bb = jnp.asarray(RNG.standard_normal((b_, s, n)) * 0.3, jnp.float32)
    cc = jnp.asarray(RNG.standard_normal((b_, s, n)) * 0.3, jnp.float32)
    out = ssd(x, dt, a_log, bb, cc, chunk=chunk)
    ref = ssd_ref(x, dt, a_log, bb, cc, chunk=chunk)
    scale = float(jnp.abs(ref).max())
    np.testing.assert_allclose(np.asarray(out) / scale,
                               np.asarray(ref) / scale, atol=1e-5)


def test_ssd_chunk_invariance():
    """Chunk size is a tiling choice — results must not depend on it."""
    b_, s, h, p, n = 1, 128, 2, 16, 8
    x = jnp.asarray(RNG.standard_normal((b_, s, h, p)) * 0.5, jnp.float32)
    dt = jnp.asarray(RNG.uniform(0.1, 1.0, (b_, s, h)), jnp.float32)
    a_log = jnp.zeros((h,), jnp.float32)
    bb = jnp.asarray(RNG.standard_normal((b_, s, n)) * 0.3, jnp.float32)
    cc = jnp.asarray(RNG.standard_normal((b_, s, n)) * 0.3, jnp.float32)
    o32 = ssd(x, dt, a_log, bb, cc, chunk=32)
    o128 = ssd(x, dt, a_log, bb, cc, chunk=128)
    np.testing.assert_allclose(np.asarray(o32), np.asarray(o128), atol=1e-4)


def test_ssd_ref_matches_stepwise_recurrence():
    """The chunked oracle itself vs a token-by-token recurrence."""
    from repro.models.ssm import ssd_decode
    b_, s, h, p, n = 1, 32, 2, 8, 4
    x = jnp.asarray(RNG.standard_normal((b_, s, h, p)) * 0.5, jnp.float32)
    dt = jnp.asarray(RNG.uniform(0.1, 1.0, (b_, s, h)), jnp.float32)
    a_log = jnp.asarray(RNG.uniform(-1, 0.0, (h,)), jnp.float32)
    bb = jnp.asarray(RNG.standard_normal((b_, s, n)) * 0.3, jnp.float32)
    cc = jnp.asarray(RNG.standard_normal((b_, s, n)) * 0.3, jnp.float32)
    ref = ssd_ref(x, dt, a_log, bb, cc, chunk=8)
    state = jnp.zeros((b_, h, p, n), jnp.float32)
    outs = []
    for t in range(s):
        # both paths fold dt into the input term exactly once
        y, state = ssd_decode(state, x[:, t], dt[:, t],
                              a_log, bb[:, t], cc[:, t])
        outs.append(y)
    step = jnp.stack(outs, axis=1)
    np.testing.assert_allclose(np.asarray(step), np.asarray(ref), atol=1e-4)


# ---------------------------------------------------------------------------
# conv2d
# ---------------------------------------------------------------------------

# (n, c, h, w, k, r); every case is small enough to take the frame
# kernel. The last four are its corners: a 14x14 frame with K over bk, a
# non-square 7x9 frame, C = 3, and a K whose block (12) is no multiple of 8.
CONV_CASES = [(1, 16, 16, 16, 32, 3), (2, 3, 20, 24, 64, 5),
              (1, 8, 10, 10, 16, 1), (1, 64, 7, 9, 8, 7),
              (2, 16, 14, 14, 32, 3), (2, 8, 7, 9, 16, 3),
              (1, 3, 9, 9, 16, 3), (1, 8, 6, 6, 12, 3)]


def _conv_inputs(case, dtype):
    n_, c, hh, ww, kk, r = case
    x = jnp.asarray(RNG.standard_normal((n_, c, hh, ww)), dtype)
    w = jnp.asarray(RNG.standard_normal((kk, c, r, r)) * 0.1, dtype)
    return x, w


@pytest.mark.parametrize("case", CONV_CASES)
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_conv2d_matches_ref(case, dtype):
    x, w = _conv_inputs(case, dtype)
    out = conv2d(x, w, bk=16)
    ref = conv2d_ref(x, w)
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(ref, np.float32), **_tol(dtype))


@pytest.mark.parametrize("case", CONV_CASES)
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_conv2d_row_kernel_matches_ref(case, dtype):
    """The row kernel, which the dispatch keeps for frames too large for
    VMEM, on the same cases."""
    from repro.kernels.conv2d.conv2d import Rows, _block_k, conv2d_same_in
    x, w = _conv_inputs(case, dtype)
    out = conv2d_same_in(x, w, Rows(*x.shape[2:], *w.shape[2:]),
                         bk=_block_k(w.shape[0], 16), interpret=True)
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(conv2d_ref(x, w), np.float32),
                               **_tol(dtype))


@pytest.mark.parametrize("case", CONV_CASES)
def test_conv2d_cases_take_the_frame_kernel(case):
    from repro.kernels.conv2d.conv2d import frame_fits
    _, c, hh, ww, kk, r = case
    assert frame_fits(c, kk, r, r, hh, ww, 4, 16)


@pytest.mark.parametrize("relu", [False, True])
@pytest.mark.parametrize("case", CONV_CASES)
def test_conv2d_frame_writes_the_next_frame(case, relu):
    """The frame kernel's output is the conv (after ReLU if asked) laid
    out as the same frame: pixels where the contract puts them, and every
    other lane, margin, padding columns and spare rows, exactly zero."""
    from repro.kernels.conv2d.conv2d import (Frame, _w_cols, conv2d_frame,
                                             from_frame, to_frame)
    x, w = _conv_inputs(case, jnp.float32)
    g = Frame(*x.shape[2:], *w.shape[2:])
    out = conv2d_frame(to_frame(x, g), _w_cols(w), g, bk=w.shape[0],
                       relu=relu, interpret=True)
    want = conv2d_ref(x, w)
    if relu:
        want = jax.nn.relu(want)
    np.testing.assert_allclose(np.asarray(from_frame(out, g)),
                               np.asarray(want), **_tol(jnp.float32))
    np.testing.assert_array_equal(np.asarray(to_frame(from_frame(out, g), g)),
                                  np.asarray(out))


# (n, c, h, w, k, r, bk): C = 3 at W = 127 with bk < K, W = 254 (W + 2
# past a lane tile), W = 130, a 5x5 conv on a 9-wide row, a 1x1 conv,
# and a 256-wide row (no zero lane)
ROWS_CASES = [(1, 3, 5, 127, 16, 3, 8), (2, 8, 4, 254, 16, 3, 16),
              (1, 16, 3, 130, 24, 3, 8), (1, 8, 6, 9, 12, 5, 12),
              (1, 8, 3, 200, 16, 1, 8), (1, 3, 4, 256, 8, 3, 8)]


def _per_tap(x, w):
    """The row kernel's arithmetic in plain jnp: float32 sums of one
    product per tap, taps in (r, s) order."""
    k, c, rr, ss = w.shape
    h, wd = x.shape[2:]
    top, left = (rr - 1) // 2, (ss - 1) // 2
    xp = jnp.pad(x, ((0, 0), (0, 0), (top, rr - 1 - top),
                     (left, ss - 1 - left)))
    acc = jnp.zeros((x.shape[0], k, h, wd), jnp.float32)
    for r in range(rr):
        for s in range(ss):
            acc += jnp.einsum("kc,nchw->nkhw", w[:, :, r, s],
                              xp[:, :, r:r + h, s:s + wd],
                              precision=jax.lax.Precision.HIGHEST,
                              preferred_element_type=jnp.float32)
    return acc


@pytest.mark.parametrize("relu", [False, True])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("case", ROWS_CASES)
def test_conv2d_rows_writes_the_next_rows(case, dtype, relu):
    """The row kernel's output is the conv (after ReLU if asked) placed
    in the same row layout: pixels where the layout puts them, and every
    padding row and every lane past W exactly zero."""
    from repro.kernels.conv2d.conv2d import (Rows, _w_taps, conv2d_rows,
                                             from_rows, to_rows)
    n_, c, hh, ww, kk, r, bk = case
    x = jnp.asarray(RNG.standard_normal((n_, c, hh, ww)), dtype)
    w = jnp.asarray(RNG.standard_normal((kk, c, r, r)) * 0.1, dtype)
    g = Rows(hh, ww, r, r)
    out = conv2d_rows(to_rows(x, g), _w_taps(w), g, bk=bk, relu=relu,
                      interpret=True)
    assert out.shape == (n_, hh + r - 1, kk, g.lanes) and out.dtype == dtype
    want = _per_tap(x, w)
    if relu:
        want = jax.nn.relu(want)
    np.testing.assert_allclose(np.asarray(from_rows(out, g), np.float32),
                               np.asarray(want.astype(dtype), np.float32),
                               **_tol(dtype))
    np.testing.assert_array_equal(np.asarray(to_rows(from_rows(out, g), g)),
                                  np.asarray(out))


# (n, c, h, w, input rows' R, output rows' R): even and odd sizes, rows
# of 1280 and 257 lanes, the output in a 3x3 conv's rows or unpadded
ROWS_POOL_CASES = [(2, 16, 16, 16, 3, 3), (1, 8, 7, 9, 3, 3),
                   (1, 8, 15, 300, 3, 1), (1, 8, 5, 257, 3, 3),
                   (1, 16, 9, 6, 5, 3), (1, 8, 3, 2, 3, 1),
                   (1, 8, 4, 1280, 3, 3)]


@pytest.mark.parametrize("case", ROWS_POOL_CASES)
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_maxpool_rows_matches_reduce_window(case, dtype):
    """The row pool is the VALID 2x2/2 max pool, floor sizes included,
    bit for bit, written as the consumer's whole rows, padding zero."""
    from repro.kernels.conv2d import conv2d as K
    n, c, h, w, r_in, r_out = case
    x = jnp.asarray(RNG.standard_normal((n, c, h, w)), dtype)
    src, dst = K.Rows(h, w, r_in, r_in), K.Rows(h // 2, w // 2, r_out,
                                                 r_out)
    out = K.maxpool_rows(K.to_rows(x, src), src, dst, interpret=True)
    want = jax.lax.reduce_window(x, -jnp.inf, jax.lax.max, (1, 1, 2, 2),
                                 (1, 1, 2, 2), "VALID")
    np.testing.assert_array_equal(np.asarray(out, np.float32),
                                  np.asarray(K.to_rows(want, dst),
                                             np.float32))


@pytest.mark.parametrize("hw,rs", [((720, 1280), 3), ((90, 160), 3),
                                   ((7, 9), 5), ((6, 130), 1)])
def test_rows_geometry(hw, rs):
    """Pixel (h, w) at row top + h, lane w; lanes on a tile; zeros
    elsewhere."""
    from repro.kernels.conv2d.conv2d import Rows, from_rows, to_rows
    g = Rows(*hw, rs, rs)
    assert g.lanes % 128 == 0 and g.w <= g.lanes < g.w + 128
    assert g.hp == hw[0] + rs - 1 and g.top == (rs - 1) // 2
    x = jnp.arange(2 * hw[0] * hw[1], dtype=jnp.float32).reshape(1, 2, *hw)
    xr = to_rows(x, g)
    assert xr.shape == (1, g.hp, 2, g.lanes)
    assert float(xr[0, g.top + 1, 1, 2]) == float(x[0, 1, 1, 2])
    assert int((xr != 0).sum()) == int((x != 0).sum())
    np.testing.assert_array_equal(np.asarray(from_rows(xr, g)),
                                  np.asarray(x))


# (n, c, h, w, input frame's R, output frame's R, pool block bytes):
# even and odd sizes, the output in a 3x3 conv's frame or in the plain
# frame, and channel blocks of 8 (a pool block budget of one 8-channel
# block)
POOL_CASES = [(2, 16, 16, 16, 3, 3, None), (1, 8, 7, 9, 3, 3, None),
              (1, 8, 15, 15, 3, 1, None), (2, 24, 14, 14, 3, 3, 1),
              (1, 16, 9, 6, 5, 3, None), (1, 8, 10, 10, 1, 1, None),
              (1, 8, 3, 2, 3, 1, None)]


@pytest.mark.parametrize("case", POOL_CASES)
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_maxpool_frame_matches_reduce_window(case, dtype, monkeypatch):
    """The frame pool is the VALID 2x2/2 max pool, floor sizes included,
    bit for bit, written as the consumer's whole frame."""
    from repro.kernels.conv2d import conv2d as K
    n, c, h, w, r_in, r_out, block = case
    if block:
        monkeypatch.setattr(K, "POOL_BLOCK_BYTES", block)
        assert K.pool_block(c, K.Frame(h, w, r_in, r_in),
                            jnp.dtype(dtype).itemsize) == 8
    x = jnp.asarray(RNG.standard_normal((n, c, h, w)), dtype)
    src, dst = K.Frame(h, w, r_in, r_in), K.Frame(h // 2, w // 2, r_out,
                                                   r_out)
    out = K.maxpool_frame(K.to_frame(x, src), src, dst, interpret=True)
    want = jax.lax.reduce_window(x, -jnp.inf, jax.lax.max, (1, 1, 2, 2),
                                 (1, 1, 2, 2), "VALID")
    np.testing.assert_array_equal(np.asarray(out, np.float32),
                                  np.asarray(K.to_frame(want, dst),
                                             np.float32))


@pytest.mark.parametrize("hw,rs", [((224, 224), 3), ((14, 14), 3),
                                   ((45, 80), 3), ((7, 9), 5), ((6, 6), 1)])
def test_frame_geometry(hw, rs):
    """Pixel (h, w) at base + h * wp + w, base and length on lane tiles,
    and room for every tap of every computed lane."""
    from repro.kernels.conv2d.conv2d import Frame, from_frame, to_frame
    g = Frame(*hw, rs, rs)
    assert g.base % 128 == 0 and g.length % 128 == 0 and g.span % 128 == 0
    assert g.base >= (rs - 1) // 2 * g.wp + (rs - 1) // 2
    assert g.base + g.span + rs // 2 * g.wp + rs // 2 <= g.length
    x = jnp.arange(2 * hw[0] * hw[1], dtype=jnp.float32).reshape(1, 2, *hw)
    xf = to_frame(x, g)
    assert xf.shape == (1, 2, g.length)
    assert float(xf[0, 1, g.base + 1 * g.wp + 2]) == float(x[0, 1, 1, 2])
    assert int((xf != 0).sum()) == int((x != 0).sum())
    np.testing.assert_array_equal(np.asarray(from_frame(xf, g)),
                                  np.asarray(x))


def test_frame_vmem_counts_whole_frames():
    """The frame kernel's count holds both frames whole (margin and
    padding included) in both pipeline buffers and the float32 product
    over the span: conv2 at 224 in bf16 is 39,354,368 bytes (37.5 MiB);
    its compile for a v5e allocates 39.3 MiB of scoped VMEM."""
    from repro.kernels.conv2d.conv2d import Frame, frame_vmem_bytes
    g = Frame(224, 224, 3, 3)
    assert (g.base, g.span, g.length) == (256, 50688, 51200)
    assert frame_vmem_bytes(64, 64, 3, 3, 224, 224, 2) == \
        2 * (64 * g.length * 2 + 64 * 640 * 2 + 64 * g.length * 2) \
        + 64 * g.span * 4 == 39_354_368


def _vgg16_convs():
    from repro.core.netinfo import vgg16
    return [(hw, l) for hw in ((224, 224), (720, 1280))
            for l in vgg16(*hw).layers if l.kind == "conv"]


# the path each vgg16 conv takes at the default block cap, in bf16
VGG16_FRAME = {(224, 224): {"conv1", "conv2", "conv4", "conv5", "conv7",
                            "conv8", "conv9", "conv11", "conv12", "conv13",
                            "conv15", "conv16", "conv17"},
               (720, 1280): {"conv15", "conv16", "conv17"}}


@pytest.mark.parametrize("hw,layer", _vgg16_convs(),
                         ids=lambda v: v.name if hasattr(v, "name")
                         else "x".join(map(str, v)))
def test_conv2d_path_for_vgg16(hw, layer):
    """The frame kernel where its blocks fit the VMEM its call sets, the
    row kernel elsewhere: decided from the conv's shapes alone."""
    from repro.kernels.conv2d.conv2d import BK, frame_fits
    assert frame_fits(layer.c, layer.k, layer.r, layer.s, layer.h, layer.w,
                      2, BK) == (layer.name in VGG16_FRAME[hw])


# ---------------------------------------------------------------------------
# interpret mode follows the backend
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("backend,interpret", [("cpu", True), ("tpu", False),
                                               ("gpu", None)])
def test_interpret_mode_follows_backend(monkeypatch, backend, interpret):
    from repro import kernels
    monkeypatch.setattr(kernels.jax, "default_backend", lambda: backend)
    if interpret is None:
        with pytest.raises(RuntimeError, match="no Pallas TPU lowering"):
            kernels.interpret_mode()
    else:
        assert kernels.interpret_mode() is interpret

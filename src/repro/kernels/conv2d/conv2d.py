"""Direct conv2d Pallas kernels — the TPU analogue of the paper's pipeline
computation engine (Sec. 5.2.1) with DNNBuilder's row buffer.

'same' padding, stride 1 (the VGG workloads; pools are separate ops).
Two block schemes compute the same conv; :func:`frame_fits` picks one
from the conv's shapes alone.

**Frame** (``conv2d_rows_frame``), for a conv whose whole frame fits VMEM:
grid = (N, K/bk), or (K/bk, N) where the weight block outweighs the
frame, so that the larger of the two keeps its block index across
consecutive steps and is fetched once. The wrapper pads NCHW and
flattens each channel's padded frame, row after row, to one lane axis of
length (H + R) * Wp (Wp = W + S - 1; one spare padded row keeps the last
tap's slice in bounds). Output pixel (h, w) then sits at lane h * Wp + w,
and tap (r, s) reads lane h * Wp + w + r * Wp + s: one static lane slice
of the frame per tap. The kernel stacks the R*S slices on the sublane
axis (an im2col of the frame, in VMEM) and does one (bk, R*S*C) x
(R*S*C, H*Wp) matmul. The result (N, K, H*Wp) reshapes to (N, K, H, Wp),
and the S - 1 columns of each row that straddle the padding are dropped:
NCHW with no transpose. The MXU's N dimension is H*Wp lanes (224 for a
14x14 frame) instead of W, and a 14-wide conv takes N*K/bk grid steps
instead of N*K/bk*H.

**Rows** (``conv2d_rows``), for frames too large for VMEM:
grid = (N, K/bk, H): each step produces one output row for a block of bk
output channels. The wrapper re-lays the frame out row-major as
(N, H + R - 1, C, W + S - 1) and hands the kernel the same array R
times, the r-th copy's BlockSpec addressing padded row h + r: the R
input rows an output row needs arrive as R (C, W + S - 1) VMEM tiles —
the paper's row buffer (Sec. 5.2.2: "the next stage launches once the
first few rows are ready"), with no re-laid-out window copy in HBM.
Weights are pre-arranged as (R*S, K, C) so one tap is one (bk, C) tile;
each tap is a (bk, C) x (C, W) matmul, W lanes wide. The output is
emitted as (N, H, K, W) — a per-row (bk, W) tile — and transposed back
to NCHW by the wrapper.

Why both: a row of W lanes fills W/128 of the MXU's columns and pays a
grid step per row, so narrow rows (14 and 28 wide at 224x224) run far
below the chip's rate; folding the frame's rows into lanes fixes both,
but needs the whole padded frame, its im2col and the output block in
VMEM, which a 720x1280 frame does not fit. Where the frame fits, the
frame kernel is the faster of the two at every vgg16 shape measured.

Every block obeys the TPU tiling rule: its last two dims are either the
array's own or multiples of (8, 128)-compatible tiles (bk = K or a
multiple of 8). Operands stay in their storage dtype with fp32
accumulation: products of two bf16 values are exact in fp32, so both
schemes are the same arithmetic as the fp32 ``lax.conv`` oracle in
``ref.py`` up to summation order.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

#: The largest output-channel block either kernel takes by default.
BK = 512
#: Scoped VMEM the frame kernel asks the compiler for (a v5e core has
#: 128 MiB); a conv takes the frame kernel where its blocks fit in it.
FRAME_VMEM_LIMIT = 64 * 2**20


def _kernel(*refs, rr: int, ss: int, width: int):
    # refs: rr row tiles (1, 1, C, W + S - 1), weights (R*S, bk, C),
    # output (1, 1, bk, W)
    rows, w_ref, o_ref = refs[:rr], refs[rr], refs[rr + 1]
    acc = jnp.zeros((w_ref.shape[1], width), jnp.float32)
    for r in range(rr):
        row = rows[r][0, 0]                                  # (C, Wp)
        for s in range(ss):
            acc += jax.lax.dot_general(
                w_ref[r * ss + s], row[:, s:s + width],
                (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)
    o_ref[0, 0] = acc.astype(o_ref.dtype)


def conv2d_rows(xr, w_taps, *, rr: int, ss: int, bk: int,
                interpret: bool = False):
    """xr (N, H + R - 1, C, W + S - 1): padded frame, row-major;
    w_taps (R*S, K, C). Returns (N, H, K, W)."""
    n, hp, c, wp = xr.shape
    _, k, _ = w_taps.shape
    h, width = hp - rr + 1, wp - ss + 1
    if k % bk:
        raise ValueError(f"K {k} % bk {bk}")

    def row_spec(r):
        return pl.BlockSpec((1, 1, c, wp),
                            lambda ni, ki, hi: (ni, hi + r, 0, 0))

    kernel = functools.partial(_kernel, rr=rr, ss=ss, width=width)
    return pl.pallas_call(
        kernel,
        grid=(n, k // bk, h),
        in_specs=[row_spec(r) for r in range(rr)] + [
            pl.BlockSpec((rr * ss, bk, c), lambda ni, ki, hi: (0, ki, 0))],
        out_specs=pl.BlockSpec((1, 1, bk, width),
                               lambda ni, ki, hi: (ni, hi, ki, 0)),
        out_shape=jax.ShapeDtypeStruct((n, h, k, width), xr.dtype),
        interpret=interpret,
        name="conv2d_rows",
    )(*([xr] * rr), w_taps)


def _frame_kernel(x_ref, w_ref, o_ref, *, rr: int, ss: int, wp: int):
    # x (1, C, (H + R) * Wp) flattened padded frame; w (bk, R*S*C);
    # output (1, bk, H * Wp)
    length = o_ref.shape[2]
    x = x_ref[0]
    cols = jnp.concatenate([x[:, r * wp + s:r * wp + s + length]
                            for r in range(rr) for s in range(ss)], axis=0)
    o_ref[0] = jax.lax.dot_general(
        w_ref[...], cols, (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32).astype(o_ref.dtype)


def conv2d_frame(xf, w_cols, *, rr: int, ss: int, wp: int, bk: int,
                 interpret: bool = False):
    """xf (N, C, (H + R) * Wp): each channel's padded frame, flattened;
    w_cols (K, R*S*C), tap-major. Returns (N, K, H * Wp)."""
    n, c, lin = xf.shape
    k, rsc = w_cols.shape
    length = lin - rr * wp
    if k % bk:
        raise ValueError(f"K {k} % bk {bk}")
    # the larger of the two blocks is the one the grid keeps still
    weights_outer = bk * rsc > c * lin

    def order(i, j):                                     # -> (ni, ki)
        return (j, i) if weights_outer else (i, j)

    kernel = functools.partial(_frame_kernel, rr=rr, ss=ss, wp=wp)
    return pl.pallas_call(
        kernel,
        grid=(k // bk, n) if weights_outer else (n, k // bk),
        in_specs=[
            pl.BlockSpec((1, c, lin), lambda i, j: (order(i, j)[0], 0, 0)),
            pl.BlockSpec((bk, rsc), lambda i, j: (order(i, j)[1], 0))],
        out_specs=pl.BlockSpec((1, bk, length),
                               lambda i, j: (*order(i, j), 0)),
        out_shape=jax.ShapeDtypeStruct((n, k, length), xf.dtype),
        compiler_params=pltpu.CompilerParams(
            vmem_limit_bytes=FRAME_VMEM_LIMIT),
        interpret=interpret,
        name="conv2d_rows_frame",
    )(xf, w_cols)


def _block_k(k: int, bk: int) -> int:
    """Largest output-channel block <= bk that divides K and is either K
    itself or a multiple of 8 (the sublane tile)."""
    if k <= bk:
        return k
    for b in range(bk - bk % 8, 7, -8):
        if k % b == 0:
            return b
    return k


def _tile(n: int, m: int) -> int:
    return -(-n // m) * m


def frame_vmem_bytes(c: int, bk: int, rr: int, ss: int, h: int, w: int,
                     itemsize: int) -> int:
    """VMEM the frame kernel's blocks take at block bk, each rounded up to
    its (sublane, lane) tiles: the frame, weight and output blocks twice
    (the pipeline's double buffers) and the float32 product once. The
    im2col columns are not counted: the compiler does not hold them whole
    (a 224x224x64 frame's would take 58 MB; its kernel compiles for a
    v5e under 16 MiB)."""
    wp = w + ss - 1
    lin, length = (h + rr) * wp, h * wp
    sub = 32 // itemsize                     # a vreg's sublanes * packing
    frame = _tile(c, sub) * _tile(lin, 128) * itemsize
    weights = _tile(bk, sub) * _tile(rr * ss * c, 128) * itemsize
    out = _tile(bk, sub) * _tile(length, 128) * itemsize
    acc = _tile(bk, 8) * _tile(length, 128) * 4
    return 2 * (frame + weights + out) + acc


def frame_fits(c: int, k: int, rr: int, ss: int, h: int, w: int,
               itemsize: int, bk: int) -> bool:
    """Whether a conv of these shapes takes the frame kernel: its blocks
    at ``_block_k(k, bk)`` fit the scoped VMEM the frame call sets."""
    return frame_vmem_bytes(c, _block_k(k, bk), rr, ss, h, w, itemsize) \
        <= FRAME_VMEM_LIMIT


@functools.partial(jax.jit, static_argnames=("bk", "interpret"))
def conv2d_same(x, w, *, bk: int, interpret: bool):
    """x (N, C, H, W); w (K, C, R, S) -> (N, K, H, W), 'same' pad,
    stride 1, through :func:`conv2d_frame` where :func:`frame_fits`,
    else through :func:`conv2d_rows`, at output-channel blocks of the
    largest size <= bk that :func:`_block_k` allows."""
    k, c, rr, ss = w.shape
    h, width = x.shape[2:]
    path = conv2d_same_frame if frame_fits(
        c, k, rr, ss, h, width, x.dtype.itemsize, bk) else conv2d_same_rows
    return path(x, w, bk=_block_k(k, bk), interpret=interpret)


def _pad(x, rr: int, ss: int, extra_rows: int = 0):
    top, left = (rr - 1) // 2, (ss - 1) // 2
    return jnp.pad(x, ((0, 0), (0, 0), (top, rr - 1 - top + extra_rows),
                       (left, ss - 1 - left)))


def conv2d_same_frame(x, w, *, bk: int, interpret: bool):
    """:func:`conv2d_same` through the frame kernel at block ``bk``."""
    k, c, rr, ss = w.shape
    n, _, h, width = x.shape
    wp = width + ss - 1
    xf = _pad(x, rr, ss, extra_rows=1).reshape(n, c, (h + rr) * wp)
    w_cols = w.transpose(0, 2, 3, 1).reshape(k, rr * ss * c)
    out = conv2d_frame(xf, w_cols, rr=rr, ss=ss, wp=wp, bk=bk,
                       interpret=interpret)
    return out.reshape(n, k, h, wp)[..., :width]


def conv2d_same_rows(x, w, *, bk: int, interpret: bool):
    """:func:`conv2d_same` through the row kernel at block ``bk``."""
    k, c, rr, ss = w.shape
    xr = _pad(x, rr, ss).transpose(0, 2, 1, 3)          # (N, Hp, C, Wp)
    w_taps = w.transpose(2, 3, 0, 1).reshape(rr * ss, k, c)
    out = conv2d_rows(xr, w_taps, rr=rr, ss=ss, bk=bk, interpret=interpret)
    return out.transpose(0, 2, 1, 3)                    # (N, K, H, W)

"""Direct conv2d Pallas kernels — the TPU analogue of the paper's pipeline
computation engine (Sec. 5.2.1) with DNNBuilder's row buffer — and the
max pool between them.

'same' padding, stride 1 (the VGG workloads). Two block schemes compute
the same conv; :func:`frame_fits` picks one from the conv's shapes alone.

**The frame layout.** A :class:`Frame` (H, W, R, S) says where an
(H, W) activation lies when an R x S 'same' conv reads it: each channel
is one lane axis of ``length`` lanes, pixel (h, w) at lane
``base + h * wp + w`` with row pitch wp = W + S - 1, and every other
lane is zero. The zeros are the conv's padding: the S - 1 columns after
each row are the right padding of that row and the left padding of the
next, the lanes before ``base`` (the top padding rows, then a margin
that puts ``base`` on a 128-lane tile) are the top padding, and the
lanes past the last row (a spare row's worth, rounded up to a tile) are
the bottom padding.

**The row layout.** A :class:`Rows` (H, W, R, S) says where an (H, W)
activation lies when an R x S 'same' conv's row kernel reads it: an
(N, H + R - 1, C, ``lanes``) array, row-major, pixel (h, w) at padded
row top + h and lane w, ``lanes`` = W rounded up to a lane tile, and
every other entry zero. The zero rows are the top and bottom padding;
the padding columns are not stored, as the kernel reads each row
between two zero lane tiles in VMEM. The row axis is a major dim, so an
output row's R input rows are R blocks of one array, halo and all.

**The layouts' ends.** ``to_frame``/``from_frame`` and
``to_rows``/``from_rows`` convert NCHW to and from each layout. Each
kernel writes its output, ReLU applied where the model asks, in the
layout it read, and each pool writes the layout of the layer after it,
so the model (``models/cnn.py``) converts only where two neighbours'
layouts differ, where a layer needs NCHW (a pool the layout pools do
not compute), and at the network's input and output.

**Frame** (``conv2d_rows_frame``), for a conv whose frames fit VMEM:
grid = (N, K/bk), or (K/bk, N) where the weight block outweighs the
frame, so that the larger of the two keeps its block index across
consecutive steps and is fetched once. Output pixel (h, w) is product
lane p = h * wp + w, and tap (r, s) reads frame lane base + p + (r - top)
* wp + s - left: one static lane slice of the frame per tap. The kernel
stacks the R*S slices on the sublane axis (an im2col of the frame, in
VMEM) and does one (bk, R*S*C) x (R*S*C, span) matmul over ``span`` =
H * wp lanes rounded up to a tile. The MXU's N dimension is H * wp lanes
(224 for a 14x14 frame) instead of W, and a 14-wide conv takes N*K/bk
grid steps instead of N*K/bk*H. Its epilogue applies ReLU (in float32,
where the model asks), zeroes the S - 1 columns of each row that
straddle the padding and the lanes past the last row, and stores the
product at ``base``, a lane-aligned store, with zeros before and after:
the output is the next conv's input frame, with no op between the two
calls.

**Frame pool** (``maxpool_frame``): a 2x2 stride-2 VALID max pool from
one frame into another at half the size (floor), in the frame the
consumer reads. Four lane slices give each window's max at lane
2i * wp + 2j; a 0/1 selection matmul per output row takes the even
lanes and writes the row's zero padding columns (one non-zero term per
output, so it is exact); the rows are stored at the output frame's
pitch.

**Rows** (``conv2d_rows``), for frames too large for VMEM:
grid = (N, K/bk, H + R - 1): each step writes one padded output row for
a block of bk output channels. The kernel gets the input array R times,
the r-th copy's BlockSpec addressing padded row h + r: the R input rows
an output row needs arrive as R (C, lanes) VMEM tiles — the paper's row
buffer (Sec. 5.2.2: "the next stage launches once the first few rows
are ready"), with no window copy in HBM. Each row is read between two
zero lane tiles, its padding columns, and tap (r, s) of output lane p
reads lane p + s - left of input row r. How the taps are multiplied
depends on the conv's shapes alone (:func:`rows_im2col`). Where the
stacked contraction R*S*C fits in one pass of the MXU's 128 rows of
depth (vgg16's conv1: 27), the kernel stacks the R*S lane slices on the
sublane axis, tap-major (the row's im2col, in VMEM), and does one (bk,
R*S*C) x (R*S*C, lanes) matmul against weights arranged as (K, R*S*C)
(:func:`_w_cols`). Otherwise it does one (bk, C) x (C, lanes) matmul
per tap against weights arranged as (R*S, K, C) (:func:`_w_taps`), each
product added into a float32 accumulator. On a TPU v5e, vgg16's 720x1280
row convs (batch 32, bf16) take, in ms per call, per tap -> stacked:
conv1 (C = 3) 28.5 -> 16.3, conv2 (C = 64) 30.6 -> 34.3, conv4 (64)
14.4 -> 14.6, conv5 (128) 15.8 -> 22.7, conv7 (128) 8.6 -> 10.0, conv8
(256) 15.4 -> 16.8, conv11 (256) 10.3 -> 10.9, conv12 (512) 19.2 ->
19.5. Nine taps of depth 3 took nine MXU passes where the stack takes
one; deeper than one pass, building the (R*S*C, lanes) stack in VMEM
cost more than the passes and accumulator updates it saved. One matmul
per input row (S taps) was slower than both from C = 64 on, and padding
conv1's taps to 8 or 16 rows of depth gained nothing (16.4, 20.8).
Its epilogue applies ReLU (in float32, where the model asks), zeroes
the lanes past W and stores the whole (bk, lanes) row of the output,
which is in the input's layout; the steps of the padding rows store
zeros and skip the matmuls, their input rows clamped onto their
neighbours' so that they fetch nothing new.

**Row pool** (``maxpool_rows``): a 2x2 stride-2 VALID max pool from one
row layout into another at half the size (floor). Each step reads two
input rows and writes one padded output row: a max of the two rows and
of the row and its one-lane shift puts each window's max at lane 2j,
and a 0/1 selection matmul per 256-lane chunk takes the even lanes
(exact, as in the frame pool).

Why both: a row of W lanes fills W/128 of the MXU's columns and pays a
grid step per row, so narrow rows (14 and 28 wide at 224x224) run far
below the chip's rate; folding the frame's rows into lanes fixes both,
but needs the whole padded frame, its im2col and the output block in
VMEM, which a 720x1280 frame does not fit. Where the frame fits, the
frame kernel is the faster of the two at every vgg16 shape measured.
The frame kernel stacks its taps at every depth, since its product
spans the whole frame; the row kernel only where the stack is one MXU
pass deep, as a deeper stack built for one row cost more than it saved
(**Rows**).

Every block obeys the TPU tiling rule: its last two dims are either the
array's own or multiples of (8, 128)-compatible tiles (bk = K or a
multiple of 8; a frame block's lanes are the whole frame). Operands stay
in their storage dtype with fp32 accumulation: products of two bf16
values are exact in fp32, so both schemes are the same arithmetic as the
fp32 ``lax.conv`` oracle in ``ref.py`` up to summation order. ReLU and
max commute with rounding to the storage dtype, so the kernels' float32
ReLU and the layout pools give what ReLU and ``reduce_window`` give
after the kernel, bit for bit (up to the sign of a zero).
"""
from __future__ import annotations

import dataclasses
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
#: The largest input block, in bytes, the frame pool takes per grid step.
POOL_BLOCK_BYTES = 4 * 2**20


@dataclasses.dataclass(frozen=True)
class Frame:
    """Where an (H, W) activation's pixels lie in the flat frame that an
    R x S 'same' conv reads (see the module docstring): pixel (h, w) at
    lane ``base + h * wp + w``, every other lane zero."""
    h: int
    w: int
    rr: int = 1
    ss: int = 1

    @property
    def wp(self) -> int:
        """Row pitch: W and the S - 1 zero columns that pad the row."""
        return self.w + self.ss - 1

    @property
    def base(self) -> int:
        """Lane of pixel (0, 0): past the top padding rows and the left
        padding columns, rounded up to a lane tile so that the frame
        kernel's store, and its first load, are lane-aligned."""
        return _tile((self.rr - 1) // 2 * self.wp + (self.ss - 1) // 2, 128)

    @property
    def span(self) -> int:
        """Lanes the frame kernel computes: H rows of ``wp``, rounded up to
        a lane tile."""
        return _tile(self.h * self.wp, 128)

    @property
    def length(self) -> int:
        """Lanes of the whole frame: the span from ``base``, and the bottom
        and right padding that the last taps reach, rounded up to a lane
        tile."""
        below, right = self.rr // 2, self.ss // 2
        return _tile(self.base + self.span + below * self.wp + right, 128)


def _zero(o_ref, lo: int, hi: int):
    """Zero lanes [lo, hi) of the output block ``o_ref[0]``."""
    if hi > lo:
        o_ref[0, :, lo:hi] = jnp.zeros((o_ref.shape[1], hi - lo), o_ref.dtype)


def _frame_kernel(x_ref, w_ref, o_ref, *, g: Frame, relu: bool):
    # x (1, C, L) the input's frame; w (bk, R*S*C); output (1, bk, L) the
    # output's frame, in the same geometry g
    x = x_ref[0]
    top, left = (g.rr - 1) // 2, (g.ss - 1) // 2
    # the im2col, tap-major: tap (r, s) reads lane p + (r - top) * wp +
    # s - left for output lane p. Its first operand is the centre tap's
    # slice, which starts at the lane-aligned base, sliced off again:
    # with an unaligned first slice the compiler holds the whole im2col
    # in VMEM (80 MiB at 224x224x64, over the call's limit)
    taps = [x[:, g.base + (r - top) * g.wp + s - left:][:, :g.span]
            for r in range(g.rr) for s in range(g.ss)]
    cols = jnp.concatenate([x[:, g.base:g.base + g.span]] + taps,
                           axis=0)[x.shape[0]:]
    acc = jax.lax.dot_general(w_ref[...], cols, (((1,), (0,)), ((), ())),
                              preferred_element_type=jnp.float32)
    if relu:
        acc = jnp.maximum(acc, 0.0)
    # product lane p holds pixel (p // wp, p % wp): zero the S - 1 columns
    # that straddle the padding and the lanes past the last row, which
    # are the next conv's padding
    p = jax.lax.broadcasted_iota(jnp.int32, (1, g.span), 1)
    acc = jnp.where((p % g.wp < g.w) & (p < g.h * g.wp), acc, 0.0)
    _zero(o_ref, 0, g.base)
    o_ref[0, :, g.base:g.base + g.span] = acc.astype(o_ref.dtype)
    _zero(o_ref, g.base + g.span, g.length)


def conv2d_frame(xf, w_cols, g: Frame, *, bk: int, relu: bool,
                 interpret: bool = False):
    """xf (N, C, L): the input in frame ``g``; w_cols (K, R*S*C),
    tap-major. Returns (N, K, L): the output (after ReLU if ``relu``) in
    the same frame."""
    n, c, lin = xf.shape
    k, rsc = w_cols.shape
    if lin != g.length or rsc != g.rr * g.ss * c:
        raise ValueError(f"frame {xf.shape} / weights {w_cols.shape} "
                         f"do not match {g}")
    if k % bk:
        raise ValueError(f"K {k} % bk {bk}")
    # the larger of the two blocks is the one the grid keeps still
    weights_outer = bk * rsc > c * lin

    def order(i, j):                                     # -> (ni, ki)
        return (j, i) if weights_outer else (i, j)

    kernel = functools.partial(_frame_kernel, g=g, relu=relu)
    return pl.pallas_call(
        kernel,
        grid=(k // bk, n) if weights_outer else (n, k // bk),
        in_specs=[
            pl.BlockSpec((1, c, lin), lambda i, j: (order(i, j)[0], 0, 0)),
            pl.BlockSpec((bk, rsc), lambda i, j: (order(i, j)[1], 0))],
        out_specs=pl.BlockSpec((1, bk, lin),
                               lambda i, j: (*order(i, j), 0)),
        out_shape=jax.ShapeDtypeStruct((n, k, lin), xf.dtype),
        compiler_params=pltpu.CompilerParams(
            vmem_limit_bytes=FRAME_VMEM_LIMIT),
        interpret=interpret,
        name="conv2d_rows_frame",
    )(xf, w_cols)


def _pool_kernel(x_ref, o_ref, *, src: Frame, dst: Frame):
    # x (1, bc, src.length); output (1, bc, dst.length): a 2x2 stride-2
    # VALID max pool from frame src into frame dst
    ho, wo, wp, b = dst.h, dst.w, src.wp, src.base
    x = x_ref[0]
    # m[:, 2i * wp + 2j] is the max of output pixel (i, j)'s window
    n = 2 * (ho - 1) * wp + 2 * wo - 1
    m = jnp.maximum(jnp.maximum(x[:, b:b + n], x[:, b + 1:b + 1 + n]),
                    jnp.maximum(x[:, b + wp:b + wp + n],
                                x[:, b + wp + 1:b + wp + 1 + n]))
    # each output row's even lanes, compacted by a 0/1 selection matmul
    # that also writes the row's zero padding columns: one term per
    # output, so it is exact (in float32, at the MXU's full precision)
    rows = jnp.concatenate([m[:, 2 * i * wp:2 * i * wp + 2 * wo - 1]
                            for i in range(ho)], axis=0)
    q = jax.lax.broadcasted_iota(jnp.int32, (2 * wo - 1, dst.wp), 0)
    j = jax.lax.broadcasted_iota(jnp.int32, (2 * wo - 1, dst.wp), 1)
    pooled = jax.lax.dot_general(
        rows, (q == 2 * j).astype(x.dtype), (((1,), (0,)), ((), ())),
        precision=(jax.lax.Precision.HIGHEST if x.dtype == jnp.float32
                   else None),
        preferred_element_type=jnp.float32).astype(o_ref.dtype)
    bc = x.shape[0]
    _zero(o_ref, 0, dst.base)
    for i in range(ho):
        lo = dst.base + i * dst.wp
        o_ref[0, :, lo:lo + dst.wp] = pooled[i * bc:(i + 1) * bc]
    _zero(o_ref, dst.base + ho * dst.wp, dst.length)


def pool_block(c: int, src: Frame, itemsize: int) -> int:
    """Channel block of :func:`maxpool_frame`: the largest that
    ``_block_k`` allows with the input block within POOL_BLOCK_BYTES."""
    return _block_k(c, max(8, POOL_BLOCK_BYTES // (src.length * itemsize)))


def maxpool_frame(xf, src: Frame, dst: Frame, *, interpret: bool = False):
    """2x2 stride-2 VALID max pool of xf (N, C, src.length), in frame
    ``src``, into (N, C, dst.length) in frame ``dst`` (dst.h, dst.w =
    src.h // 2, src.w // 2; dst's R x S is the consumer's)."""
    n, c, lin = xf.shape
    if lin != src.length or (dst.h, dst.w) != (src.h // 2, src.w // 2) \
            or not (dst.h and dst.w):
        raise ValueError(f"cannot pool {xf.shape} in {src} into {dst}")
    bc = pool_block(c, src, xf.dtype.itemsize)
    kernel = functools.partial(_pool_kernel, src=src, dst=dst)
    return pl.pallas_call(
        kernel,
        grid=(n, c // bc),
        in_specs=[pl.BlockSpec((1, bc, lin), lambda i, j: (i, j, 0))],
        out_specs=pl.BlockSpec((1, bc, dst.length), lambda i, j: (i, j, 0)),
        out_shape=jax.ShapeDtypeStruct((n, c, dst.length), xf.dtype),
        compiler_params=pltpu.CompilerParams(
            vmem_limit_bytes=FRAME_VMEM_LIMIT),
        interpret=interpret,
        name="maxpool_frame",
    )(xf)


@dataclasses.dataclass(frozen=True)
class Rows:
    """Where an (H, W) activation lies in the row-major array that an
    R x S 'same' conv's row kernel reads (see the module docstring):
    (N, H + R - 1, C, ``lanes``), pixel (h, w) at row ``top + h``, lane
    w, every other entry zero."""
    h: int
    w: int
    rr: int = 1
    ss: int = 1

    @property
    def top(self) -> int:
        """Zero rows above the first pixel row: the top padding."""
        return (self.rr - 1) // 2

    @property
    def hp(self) -> int:
        """Rows of the array: H and the R - 1 padding rows."""
        return self.h + self.rr - 1

    @property
    def lanes(self) -> int:
        """Lanes of a row: W rounded up to a lane tile, zero past W. The
        left and right padding columns are not stored: the row kernel
        reads each row between two zero lane tiles."""
        return _tile(self.w, 128)


def _inside(g: Rows, hi):
    """Whether padded row ``hi`` of layout g holds pixels."""
    return (hi >= g.top) & (hi < g.top + g.h)


def _rows_kernel(*refs, g: Rows, relu: bool):
    # refs: rr input rows (1, 1, C, lanes), weights (bk, R*S*C) where the
    # taps are stacked or (R*S, bk, C) where they are not, output row
    # (1, 1, bk, lanes), all in layout g
    rows, w_ref, o_ref = refs[:g.rr], refs[g.rr], refs[g.rr + 1]
    inside = _inside(g, pl.program_id(2))
    left = (g.ss - 1) // 2

    def dot(w, x):
        return jax.lax.dot_general(w, x, (((1,), (0,)), ((), ())),
                                   preferred_element_type=jnp.float32)

    @pl.when(inside)
    def _():
        taps = []
        for r in range(g.rr):
            row = rows[r][0, 0]                              # (C, lanes)
            # the row between two zero lane tiles, its padding columns:
            # tap (r, s) reads lane p + s - left for output lane p
            zeros = jnp.zeros((row.shape[0], 128), row.dtype)
            ext = jnp.concatenate([zeros, row, zeros], axis=1)
            taps += [ext[:, 128 + s - left:][:, :g.lanes]
                     for s in range(g.ss)]
        if len(w_ref.shape) == 2:
            # the taps stacked on sublanes, tap-major: the row's im2col
            acc = dot(w_ref[...], jnp.concatenate(taps, axis=0))
        else:
            acc = jnp.zeros((w_ref.shape[1], g.lanes), jnp.float32)
            for t, tap in enumerate(taps):
                acc += dot(w_ref[t], tap)
        if relu:
            acc = jnp.maximum(acc, 0.0)
        if g.w < g.lanes:
            p = jax.lax.broadcasted_iota(jnp.int32, (1, g.lanes), 1)
            acc = jnp.where(p < g.w, acc, 0.0)
        o_ref[0, 0] = acc.astype(o_ref.dtype)

    @pl.when(jnp.logical_not(inside))
    def _():
        o_ref[...] = jnp.zeros(o_ref.shape, o_ref.dtype)


def _pixel_row(g: Rows, hi, r: int):
    """Input row that tap row r of padded output row ``hi`` reads, with
    the padding rows' steps clamped onto their neighbours', so that they
    fetch nothing new."""
    return jnp.clip(hi - g.top, 0, g.h - 1) + r


def conv2d_rows(xr, w, g: Rows, *, bk: int, relu: bool,
                interpret: bool = False):
    """xr (N, H + R - 1, C, lanes): the input in layout ``g``; w (K,
    R*S*C) from :func:`_w_cols`, for one matmul per row with the taps
    stacked, or (R*S, K, C) from :func:`_w_taps`, for one matmul per tap.
    Returns (N, H + R - 1, K, lanes): the output (after ReLU if ``relu``)
    in the same layout."""
    n, hp, c, lanes = xr.shape
    stacked = w.ndim == 2
    k = w.shape[0] if stacked else w.shape[1]
    want = (k, g.rr * g.ss * c) if stacked else (g.rr * g.ss, k, c)
    if (hp, lanes) != (g.hp, g.lanes) or w.shape != want:
        raise ValueError(f"rows {xr.shape} / weights {w.shape} "
                         f"do not match {g}")
    if k % bk:
        raise ValueError(f"K {k} % bk {bk}")

    def row_spec(r):
        return pl.BlockSpec((1, 1, c, lanes), lambda ni, ki, hi: (
            ni, _pixel_row(g, hi, r), 0, 0))

    kernel = functools.partial(_rows_kernel, g=g, relu=relu)
    return pl.pallas_call(
        kernel,
        grid=(n, k // bk, hp),
        in_specs=[row_spec(r) for r in range(g.rr)] + [
            pl.BlockSpec((bk, w.shape[1]), lambda ni, ki, hi: (ki, 0))
            if stacked else
            pl.BlockSpec((w.shape[0], bk, c), lambda ni, ki, hi: (0, ki, 0))],
        out_specs=pl.BlockSpec((1, 1, bk, lanes),
                               lambda ni, ki, hi: (ni, hi, ki, 0)),
        out_shape=jax.ShapeDtypeStruct((n, hp, k, lanes), xr.dtype),
        interpret=interpret,
        name="conv2d_rows",
    )(*([xr] * g.rr), w)


def _rows_pool_kernel(a_ref, b_ref, o_ref, *, src: Rows, dst: Rows):
    # a, b (1, 1, C, src.lanes): the two input rows of output row h;
    # output row (1, 1, C, dst.lanes): a 2x2 stride-2 VALID max pool from
    # layout src into layout dst
    inside = _inside(dst, pl.program_id(1))

    @pl.when(inside)
    def _():
        m = jnp.maximum(a_ref[0, 0], b_ref[0, 0])
        # lane 2j: the max of output pixel j's window
        zeros = jnp.zeros((m.shape[0], 128), m.dtype)
        m = jnp.maximum(m, jnp.concatenate([m, zeros], axis=1)[:, 1:][
            :, :src.lanes])
        # even lanes, compacted by a 0/1 selection matmul per chunk of 256
        # input lanes (one term per output, so it is exact: in float32 at
        # the MXU's full precision)
        outs = []
        for lo in range(0, 2 * dst.w, 256):
            chunk = m[:, lo:min(lo + 256, src.lanes)]
            q = jax.lax.broadcasted_iota(jnp.int32, (chunk.shape[1], 128), 0)
            j = jax.lax.broadcasted_iota(jnp.int32, (chunk.shape[1], 128), 1)
            outs.append(jax.lax.dot_general(
                chunk, (q == 2 * j).astype(m.dtype), (((1,), (0,)), ((), ())),
                precision=(jax.lax.Precision.HIGHEST
                           if m.dtype == jnp.float32 else None),
                preferred_element_type=jnp.float32))
        pooled = jnp.concatenate(outs, axis=1)
        p = jax.lax.broadcasted_iota(jnp.int32, (1, pooled.shape[1]), 1)
        pooled = jnp.where(p < dst.w, pooled, 0.0)
        o_ref[0, 0] = pooled.astype(o_ref.dtype)

    @pl.when(jnp.logical_not(inside))
    def _():
        o_ref[...] = jnp.zeros(o_ref.shape, o_ref.dtype)


def maxpool_rows(xr, src: Rows, dst: Rows, *, interpret: bool = False):
    """2x2 stride-2 VALID max pool of xr (N, src.hp, C, src.lanes), in
    layout ``src``, into (N, dst.hp, C, dst.lanes) in layout ``dst``
    (dst.h, dst.w = src.h // 2, src.w // 2; dst's R x S is the
    consumer's)."""
    n, hp, c, lanes = xr.shape
    if (hp, lanes) != (src.hp, src.lanes) \
            or (dst.h, dst.w) != (src.h // 2, src.w // 2) \
            or not (dst.h and dst.w):
        raise ValueError(f"cannot pool {xr.shape} in {src} into {dst}")

    def row_spec(r):
        return pl.BlockSpec((1, 1, c, lanes), lambda ni, hi: (
            ni, src.top + 2 * jnp.clip(hi - dst.top, 0, dst.h - 1) + r, 0, 0))

    kernel = functools.partial(_rows_pool_kernel, src=src, dst=dst)
    return pl.pallas_call(
        kernel,
        grid=(n, dst.hp),
        in_specs=[row_spec(0), row_spec(1)],
        out_specs=pl.BlockSpec((1, 1, c, dst.lanes),
                               lambda ni, hi: (ni, hi, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((n, dst.hp, c, dst.lanes), xr.dtype),
        interpret=interpret,
        name="maxpool_rows",
    )(xr, xr)


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
    its (sublane, lane) tiles: the input frame, weight and output frame
    blocks twice (the pipeline's double buffers; both frames whole, with
    their margins and padding, :attr:`Frame.length` lanes) and the float32
    product once (:attr:`Frame.span` lanes). The im2col columns are not
    counted: the compiler does not hold them whole (a 224x224x64 frame's
    would take 58 MB; its kernel compiles for a v5e under 16 MiB)."""
    g = Frame(h, w, rr, ss)
    sub = 32 // itemsize                     # a vreg's sublanes * packing
    frame = _tile(c, sub) * g.length * itemsize
    weights = _tile(bk, sub) * _tile(rr * ss * c, 128) * itemsize
    out = _tile(bk, sub) * g.length * itemsize
    acc = _tile(bk, 8) * g.span * 4
    return 2 * (frame + weights + out) + acc


def frame_fits(c: int, k: int, rr: int, ss: int, h: int, w: int,
               itemsize: int, bk: int) -> bool:
    """Whether a conv of these shapes takes the frame kernel: its blocks
    at ``_block_k(k, bk)`` fit the scoped VMEM the frame call sets."""
    return frame_vmem_bytes(c, _block_k(k, bk), rr, ss, h, w, itemsize) \
        <= FRAME_VMEM_LIMIT


def conv_layout(c: int, k: int, rr: int, ss: int, h: int, w: int,
                itemsize: int, bk: int) -> Frame | Rows:
    """The layout in which a conv of these shapes reads its (h, w) input
    and writes its output: a :class:`Frame` where :func:`frame_fits`,
    else :class:`Rows`."""
    layout = Frame if frame_fits(c, k, rr, ss, h, w, itemsize, bk) else Rows
    return layout(h, w, rr, ss)


@functools.partial(jax.jit, static_argnames=("bk", "interpret"))
def conv2d_same(x, w, *, bk: int, interpret: bool):
    """x (N, C, H, W); w (K, C, R, S) -> (N, K, H, W), 'same' pad,
    stride 1, through :func:`conv2d_frame` where :func:`frame_fits`,
    else through :func:`conv2d_rows`, at output-channel blocks of the
    largest size <= bk that :func:`_block_k` allows."""
    k, c, rr, ss = w.shape
    g = conv_layout(c, k, rr, ss, *x.shape[2:], x.dtype.itemsize, bk)
    return conv2d_same_in(x, w, g, bk=_block_k(k, bk), interpret=interpret)


def conv2d_same_in(x, w, g: Frame | Rows, *, bk: int, interpret: bool):
    """:func:`conv2d_same` through the kernel of layout ``g``'s scheme at
    block ``bk``: NCHW into ``g``, the kernel, and back."""
    return from_layout(_conv_in(to_layout(x, g), w, g, bk=bk, relu=False,
                                interpret=interpret), g)


def rows_im2col(c: int, rr: int, ss: int) -> bool:
    """Whether the row kernel stacks the taps of an R x S conv of input
    depth C into one matmul: where the stacked contraction, R*S*C, fits
    in one pass of the MXU's 128 rows of depth (see **Rows**)."""
    return rr * ss * c <= 128


def _w_rows(w):
    """w (K, C, R, S) arranged for the row kernel's scheme at its
    shapes (:func:`rows_im2col`)."""
    return _w_cols(w) if rows_im2col(*w.shape[1:]) else _w_taps(w)


def _conv_in(x, w, g: Frame | Rows, *, bk: int, relu: bool,
             interpret: bool):
    """The 'same' conv of x, in layout ``g``, by w (K, C, R, S): the kernel
    of g's scheme, its output in the same layout."""
    if isinstance(g, Frame):
        return conv2d_frame(x, _w_cols(w), g, bk=bk, relu=relu,
                            interpret=interpret)
    return conv2d_rows(x, _w_rows(w), g, bk=bk, relu=relu,
                       interpret=interpret)


@functools.partial(jax.jit, static_argnames=("g", "bk", "interpret"))
def conv2d_relu_in(x, w, *, g: Frame | Rows, bk: int, interpret: bool):
    """x in layout ``g`` (a :class:`Frame` or :class:`Rows`); w (K, C, R,
    S) -> ReLU of the 'same' conv in layout ``g``: the kernel with no
    relayout on either side, for a conv whose input and output stay in
    its layout."""
    return _conv_in(x, w, g, bk=_block_k(w.shape[0], bk), relu=True,
                    interpret=interpret)


def to_frame(x, g: Frame):
    """NCHW x (N, C, g.h, g.w) -> (N, C, g.length) in frame ``g``."""
    n, c, h, w = x.shape
    xf = jnp.pad(x, ((0, 0), (0, 0), (0, 0), (0, g.wp - w)))
    return jnp.pad(xf.reshape(n, c, h * g.wp),
                   ((0, 0), (0, 0), (g.base, g.length - g.base - h * g.wp)))


def from_frame(xf, g: Frame):
    """(N, C, g.length) in frame ``g`` -> NCHW (N, C, g.h, g.w)."""
    n, c, _ = xf.shape
    return xf[:, :, g.base:g.base + g.h * g.wp].reshape(
        n, c, g.h, g.wp)[..., :g.w]


def to_rows(x, g: Rows):
    """NCHW x (N, C, g.h, g.w) -> (N, g.hp, C, g.lanes) in layout ``g``."""
    xp = jnp.pad(x, ((0, 0), (0, 0), (g.top, g.rr - 1 - g.top),
                     (0, g.lanes - g.w)))
    return xp.transpose(0, 2, 1, 3)


def from_rows(xr, g: Rows):
    """(N, g.hp, C, g.lanes) in layout ``g`` -> NCHW (N, C, g.h, g.w)."""
    return xr[:, g.top:g.top + g.h, :, :g.w].transpose(0, 2, 1, 3)


def to_layout(x, g: Frame | Rows):
    """NCHW x -> layout ``g``."""
    return to_frame(x, g) if isinstance(g, Frame) else to_rows(x, g)


def from_layout(x, g: Frame | Rows):
    """Layout ``g`` -> NCHW."""
    return from_frame(x, g) if isinstance(g, Frame) else from_rows(x, g)


def _w_cols(w):
    """(K, C, R, S) -> (K, R*S*C), tap-major: the im2col's row order. The
    frame kernel reads it, and the row kernel where it stacks its taps."""
    k, c, rr, ss = w.shape
    return w.transpose(0, 2, 3, 1).reshape(k, rr * ss * c)


def _w_taps(w):
    """(K, C, R, S) -> (R*S, K, C): one (K, C) matrix per tap, which the
    row kernel reads where it runs one matmul per tap."""
    k, c, rr, ss = w.shape
    return w.transpose(2, 3, 0, 1).reshape(rr * ss, k, c)

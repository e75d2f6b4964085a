"""Direct conv2d Pallas kernel — the TPU analogue of the paper's pipeline
computation engine (Sec. 5.2.1) with DNNBuilder's row buffer.

'same' padding, stride 1 (the VGG workloads; pools are separate ops).
grid = (N, K/bk, H): each step produces one output row for a block of bk
output channels. The wrapper re-lays the frame out row-major as
(N, H + R - 1, C, W + S - 1) and hands the kernel the same array R
times, the r-th copy's BlockSpec addressing padded row h + r: the R
input rows an output row needs arrive as R (C, W + S - 1) VMEM tiles —
the paper's row buffer (Sec. 5.2.2: "the next stage launches once the
first few rows are ready"), with no re-laid-out window copy in HBM.
Weights are pre-arranged as (R*S, K, C) so one tap is one (bk, C) tile.

Every block obeys the TPU tiling rule: its last two dims are either the
array's own ((C, W + S - 1) rows, (bk, W) outputs at bk = K) or
multiples of (8, 128)-compatible tiles (bk a multiple of 8). The output
is emitted as (N, H, K, W) — a per-row (bk, W) tile — and transposed
back to NCHW by the wrapper.

The (r, s) taps are static python loops; each tap is an MXU
(bk, C) x (C, W) matmul with fp32 accumulation — CPF=C, KPF=bk in the
paper's terms. Operands stay in their storage dtype: products of two
bf16 values are exact in fp32, so this is the same arithmetic as the
fp32 ``lax.conv`` oracle in ``ref.py`` up to summation order.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl


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


def _block_k(k: int, bk: int) -> int:
    """Largest output-channel block <= bk that divides K and is either K
    itself or a multiple of 8 (the sublane tile)."""
    if k <= bk:
        return k
    for b in range(bk - bk % 8, 7, -8):
        if k % b == 0:
            return b
    return k


@functools.partial(jax.jit, static_argnames=("bk", "interpret"))
def conv2d_same(x, w, *, bk: int, interpret: bool):
    """x (N, C, H, W); w (K, C, R, S) -> (N, K, H, W), 'same' pad,
    stride 1, through :func:`conv2d_rows`."""
    k, c, rr, ss = w.shape
    xp = jnp.pad(x, ((0, 0), (0, 0),
                     ((rr - 1) // 2, rr // 2), ((ss - 1) // 2, ss // 2)))
    xr = xp.transpose(0, 2, 1, 3)                       # (N, Hp, C, Wp)
    w_taps = w.transpose(2, 3, 0, 1).reshape(rr * ss, k, c)
    out = conv2d_rows(xr, w_taps, rr=rr, ss=ss, bk=_block_k(k, bk),
                      interpret=interpret)
    return out.transpose(0, 2, 1, 3)                    # (N, K, H, W)

"""Public wrappers for the direct conv kernels ('same' padding, stride 1)
and the max pools between them: compiled on a TPU, interpreted on the
CPU."""
from __future__ import annotations

from repro.kernels import interpret_mode

from .conv2d import (BK, Frame, Rows, conv2d_relu_in, conv2d_same,
                     conv_layout, from_layout, maxpool_frame, maxpool_rows,
                     to_layout)

__all__ = ["Frame", "Rows", "conv2d", "conv2d_relu", "layout_of",
           "maxpool", "relayout"]


def conv2d(x, w, *, bk: int = BK):
    """x (N, C, H, W); w (K, C, R, S) -> (N, K, H, W), 'same' pad, stride 1."""
    return conv2d_same(x, w, bk=bk, interpret=interpret_mode())


def layout_of(w, hw, dtype, *, bk: int = BK) -> Frame | Rows:
    """The layout a conv of weights w (K, C, R, S) reads an ``hw`` input
    in: a :class:`Frame` where its shapes take the frame kernel, else
    :class:`Rows`."""
    k, c, rr, ss = w.shape
    return conv_layout(c, k, rr, ss, *hw, dtype.itemsize, bk)


def conv2d_relu(x, w, g: Frame | Rows, *, bk: int = BK):
    """ReLU of the 'same' conv of x, in layout ``g``, by w (K, C, R, S):
    the output in layout ``g``."""
    return conv2d_relu_in(x, w, g=g, bk=bk, interpret=interpret_mode())


def maxpool(x, src: Frame | Rows, dst: Frame | Rows):
    """2x2 stride-2 VALID max pool from layout ``src`` into ``dst``, both
    frames or both rows."""
    pool = maxpool_frame if isinstance(src, Frame) else maxpool_rows
    return pool(x, src, dst, interpret=interpret_mode())


def relayout(x, have: Frame | Rows | None, want: Frame | Rows | None):
    """x from layout ``have`` into ``want`` (None: NCHW) through NCHW."""
    if have is not None:
        x = from_layout(x, have)
    return x if want is None else to_layout(x, want)

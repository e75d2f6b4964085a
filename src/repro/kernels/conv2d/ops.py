"""Public wrappers for the direct conv kernels ('same' padding, stride 1)
and the frame max pool: compiled on a TPU, interpreted on the CPU."""
from __future__ import annotations

from repro.kernels import interpret_mode

from .conv2d import (BK, Frame, conv2d_relu_in_frame, conv2d_same,
                     frame_fits, from_frame, maxpool_frame, to_frame)

__all__ = ["Frame", "conv2d", "conv2d_relu_frame", "conv_frame",
           "from_frame", "maxpool", "to_frame"]


def conv2d(x, w, *, bk: int = BK):
    """x (N, C, H, W); w (K, C, R, S) -> (N, K, H, W), 'same' pad, stride 1."""
    return conv2d_same(x, w, bk=bk, interpret=interpret_mode())


def conv_frame(w, hw, dtype, *, bk: int = BK) -> Frame | None:
    """The frame a conv of weights w (K, C, R, S) reads an ``hw`` input in
    where its shapes take the frame kernel; None where they take the row
    kernel, which reads NCHW."""
    k, c, rr, ss = w.shape
    if not frame_fits(c, k, rr, ss, *hw, dtype.itemsize, bk):
        return None
    return Frame(*hw, rr, ss)


def conv2d_relu_frame(xf, w, g: Frame, *, bk: int = BK):
    """ReLU of the 'same' conv of xf (N, C, L) in frame ``g`` by w
    (K, C, R, S): (N, K, L) in frame ``g``."""
    return conv2d_relu_in_frame(xf, w, g=g, bk=bk,
                                interpret=interpret_mode())


def maxpool(xf, src: Frame, dst: Frame):
    """2x2 stride-2 VALID max pool from frame ``src`` into frame ``dst``."""
    return maxpool_frame(xf, src, dst, interpret=interpret_mode())

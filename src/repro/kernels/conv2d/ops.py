"""Public wrapper for the direct conv kernel ('same' padding, stride 1)."""
from __future__ import annotations

from repro.kernels import interpret_mode

from .conv2d import BK, conv2d_same


def conv2d(x, w, *, bk: int = BK):
    """x (N, C, H, W); w (K, C, R, S) -> (N, K, H, W), 'same' pad, stride 1."""
    return conv2d_same(x, w, bk=bk, interpret=interpret_mode())

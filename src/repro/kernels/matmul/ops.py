"""jit'd wrapper for the blocked matmul kernel (padding + block choice)."""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

from repro.kernels import interpret_mode

from .matmul import matmul_blocked


def _pad_to(x, mult: int, axis: int):
    pad = (-x.shape[axis]) % mult
    if pad == 0:
        return x
    widths = [(0, 0)] * x.ndim
    widths[axis] = (0, pad)
    return jnp.pad(x, widths)


@partial(jax.jit, static_argnames=("bm", "bn", "bk"))
def matmul(a, b, *, bm: int = 256, bn: int = 256, bk: int = 512):
    """General (M, K) @ (K, N) with auto padding to block multiples."""
    m, k = a.shape
    _, n = b.shape
    bm_, bn_, bk_ = (min(bm, 1 << max(3, (m - 1).bit_length())),
                     min(bn, 1 << max(3, (n - 1).bit_length())),
                     min(bk, 1 << max(3, (k - 1).bit_length())))
    ap = _pad_to(_pad_to(a, bm_, 0), bk_, 1)
    bp = _pad_to(_pad_to(b, bk_, 0), bn_, 1)
    out = matmul_blocked(ap, bp, bm=bm_, bn=bn_, bk=bk_,
                         interpret=interpret_mode())
    return out[:m, :n]

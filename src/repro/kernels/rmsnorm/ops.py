"""jit'd wrapper: model-native (B, S, D) RMSNorm over the fused kernel."""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

from repro.kernels import interpret_mode

from .rmsnorm import rmsnorm_rows


@partial(jax.jit, static_argnames=("eps", "bm"))
def rmsnorm(x, scale, *, eps: float = 1e-6, bm: int = 128):
    shape = x.shape
    d = shape[-1]
    xf = x.reshape(-1, d)
    n = xf.shape[0]
    bm_eff = min(bm, max(1, 1 << (n - 1).bit_length()))
    pad = (-n) % bm_eff
    if pad:
        xf = jnp.pad(xf, ((0, pad), (0, 0)))
    out = rmsnorm_rows(xf, scale, eps=eps, bm=bm_eff,
                       interpret=interpret_mode())
    return out[:n].reshape(shape)

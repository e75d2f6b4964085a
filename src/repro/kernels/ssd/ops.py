"""jit'd wrapper: model-native SSD interface over the Pallas chunk kernel.

Precomputes the elementwise decay terms (dt*A cumulative sums) in jnp and
hands MXU-shaped blocks to the kernel.
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

from repro.kernels import interpret_mode

from .ssd import ssd_scan


@partial(jax.jit, static_argnames=("chunk",))
def ssd(x, dt, a_log, b, c, *, chunk: int = 128):
    """Same contract as repro.models.ssm.ssd_chunked:
    x (B, S, H, P); dt (B, S, H); a_log (H,); b, c (B, S, N) -> (B, S, H, P).
    """
    bsz, s, h, p = x.shape
    n = b.shape[-1]
    q = min(chunk, s)
    assert s % q == 0, f"seq {s} % chunk {q} != 0"
    nc = s // q

    a = -jnp.exp(a_log.astype(jnp.float32))
    da = dt.astype(jnp.float32) * a[None, None, :]            # (B, S, H)
    dacum = jnp.cumsum(da.reshape(bsz, nc, q, h), axis=2)     # (B, NC, Q, H)

    xdt = (x * dt[..., None]).reshape(bsz, nc, q, h, p)

    # arrange to (B*H, NC, Q, ...)
    xdt_bh = xdt.transpose(0, 3, 1, 2, 4).reshape(bsz * h, nc, q, p)
    dacum_bh = dacum.transpose(0, 3, 1, 2).reshape(bsz * h, nc, q)
    b_bh = jnp.repeat(b.reshape(bsz, 1, nc, q, n), h, axis=1).reshape(
        bsz * h, nc, q, n)
    c_bh = jnp.repeat(c.reshape(bsz, 1, nc, q, n), h, axis=1).reshape(
        bsz * h, nc, q, n)

    y = ssd_scan(xdt_bh, dacum_bh, b_bh, c_bh, p=p, n=n,
                 interpret=interpret_mode())
    return y.reshape(bsz, h, nc, q, p).transpose(0, 2, 3, 1, 4).reshape(
        bsz, s, h, p).astype(x.dtype)

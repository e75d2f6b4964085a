"""jit'd public wrapper around the flash-attention Pallas kernel.

Accepts the model-native layout q (B, S, H, hd), k/v (B, Sk, KV, hd);
handles GQA head mapping, padding to block/lane multiples, and exposes
``attn_fn`` with the signature ``repro.models.layers.gqa_attention``
expects for its kernel hook.
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

from repro.kernels import interpret_mode

from .flash_attention import flash_attention_bhsd

_LANE = 128


def _pad_to(x, mult: int, axis: int):
    pad = (-x.shape[axis]) % mult
    if pad == 0:
        return x
    widths = [(0, 0)] * x.ndim
    widths[axis] = (0, pad)
    return jnp.pad(x, widths)


@partial(jax.jit, static_argnames=("causal", "window", "bq", "bk"))
def flash_attention(q, k, v, *, causal: bool = True, window: int | None = None,
                    bq: int = 256, bk: int = 256):
    """q (B, S, H, hd); k, v (B, Sk, KV, hd) -> (B, S, H, hd)."""
    b, s, h, hd = q.shape
    _, s_k, kv, _ = k.shape
    bq = min(bq, max(8, 1 << (s - 1).bit_length()))
    bk = min(bk, max(8, 1 << (s_k - 1).bit_length()))

    qt = _pad_to(_pad_to(q.transpose(0, 2, 1, 3).reshape(b * h, s, hd),
                         bq, 1), _LANE, 2)
    kt = _pad_to(_pad_to(k.transpose(0, 2, 1, 3).reshape(b * kv, s_k, hd),
                         bk, 1), _LANE, 2)
    vt = _pad_to(_pad_to(v.transpose(0, 2, 1, 3).reshape(b * kv, s_k, hd),
                         bk, 1), _LANE, 2)

    out = flash_attention_bhsd(qt, kt, vt, causal=causal, window=window,
                               scale=1.0 / (hd ** 0.5), s_k=s_k,
                               bq=bq, bk=bk, interpret=interpret_mode())
    out = out[:, :s, :hd].reshape(b, h, s, hd).transpose(0, 2, 1, 3)
    return out


def attn_fn(q, k, v, *, causal: bool = True, window: int | None = None):
    """Adapter matching gqa_attention's attn_fn hook: returns (B, S, H*hd)."""
    b, s, h, hd = q.shape
    out = flash_attention(q, k, v, causal=causal, window=window)
    return out.reshape(b, s, h * hd)

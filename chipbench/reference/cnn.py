"""Plain reference of a VGG feature extractor: 3x3 'same' convolutions with
ReLU and 2x2 max pools, in float32 at the highest matmul precision, from
the configuration's frozen layer shapes. It imports nothing of the system
under test.

``control=True`` computes the same net with every conv operand (the
activations and the weights) rounded to float8 e4m3 under a per-tensor
scale: the precision step below the bfloat16 the configuration serves
in, which the comparison has to reject.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

FP8 = jnp.float8_e4m3fn
FP8_MAX = 448.0


def init_weights(key, convs, dtype=jnp.bfloat16):
    """He-normal weights ``(K, C, R, S)`` for each ``[C, K, R, S]`` conv,
    made on the device in one jitted call."""
    def make(key):
        keys = jax.random.split(key, len(convs))
        return [(jax.random.normal(k, (co, ci, r, s), jnp.float32)
                 * (2.0 / (ci * r * s)) ** 0.5).astype(dtype)
                for k, (ci, co, r, s) in zip(keys, convs)]
    return jax.jit(make)(key)


def _fp8(x):
    scale = jnp.maximum(jnp.max(jnp.abs(x)), 1e-30) / FP8_MAX
    return (x / scale).astype(FP8).astype(jnp.float32) * scale


def forward(weights, pools_after, x, *, control: bool = False):
    """x (N, 3, H, W) -> features, in float32; ``pools_after`` are the
    indices of the convs a max pool follows."""
    x = x.astype(jnp.float32)
    for i, w in enumerate(weights):
        w = w.astype(jnp.float32)
        if control:
            x, w = _fp8(x), _fp8(w)
        x = jax.lax.conv_general_dilated(
            x, w, (1, 1), "SAME", dimension_numbers=("NCHW", "OIHW", "NCHW"),
            precision=jax.lax.Precision.HIGHEST)
        x = jax.nn.relu(x)
        if i in pools_after:
            x = jax.lax.reduce_window(x, -jnp.inf, jax.lax.max, (1, 1, 2, 2),
                                      (1, 1, 2, 2), "VALID")
    return x

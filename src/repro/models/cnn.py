"""The paper's own workload in JAX: VGG-like CNNs, executable either as a
plain jnp forward or through the DNNExplorer *hybrid* execution plan —
the first SP layers as dedicated pipeline stages (shard_map microbatch
pipeline = the paper's pipeline structure) and the rest through a single
reusable apply function (= the generic structure).

The conv compute can route through the Pallas direct-conv kernel
(``repro.kernels.conv2d``), which is the pipeline CE of the paper.
"""
from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp

from repro.core.netinfo import NetInfo


def init_vgg(key, net: NetInfo, dtype=jnp.float32):
    """Conv weights for every major layer of a netinfo VGG description."""
    params = []
    keys = jax.random.split(key, len(net.layers))
    for k, l in zip(keys, net.layers):
        if l.kind == "pool":
            params.append(None)
            continue
        w = jax.random.normal(k, (l.k, l.c, l.r, l.s), jnp.float32)
        w *= (2.0 / (l.c * l.r * l.s)) ** 0.5  # He init
        params.append(w.astype(dtype))
    return params


def _conv(x, w, use_pallas: bool):
    if use_pallas:
        from repro.kernels.conv2d.ops import conv2d
        return conv2d(x, w)
    return jax.lax.conv_general_dilated(
        x, w, (1, 1), "SAME", dimension_numbers=("NCHW", "OIHW", "NCHW"))


def layer_apply(x, w, layer, use_pallas: bool = False):
    """One major layer (+ fused ReLU) or pool, under the layer's name
    (``conv4``, ``pool6``) as its scope: the compiled program's ops, and
    so a device trace's, carry the name of the layer they belong to."""
    with jax.named_scope(layer.name):
        if layer.kind == "pool":
            return jax.lax.reduce_window(
                x, -jnp.inf, jax.lax.max,
                (1, 1, layer.r, layer.s), (1, 1, layer.stride, layer.stride),
                "VALID")
        return jax.nn.relu(_conv(x, w, use_pallas))


def forward(params, net: NetInfo, x, *, use_pallas: bool = False):
    """Plain sequential forward: x (N, 3, H, W) -> feature map."""
    for w, l in zip(params, net.layers):
        x = layer_apply(x, w, l, use_pallas)
    return x


# ---------------------------------------------------------------------------
# Hybrid execution: the paper's paradigm as a JAX execution plan
# ---------------------------------------------------------------------------


@dataclasses.dataclass
class HybridPlan:
    """Execution plan from an RAV: layers [0, sp) run as dedicated pipeline
    stages over a `stage` mesh axis; layers [sp, N) run recurrently through
    one generic apply (shared code path = the reusable MAC array)."""
    sp: int
    n_micro: int


def hybrid_forward(params, net: NetInfo, x, plan: HybridPlan, mesh=None, *,
                   use_pallas: bool = False):
    """Run the net under a hybrid plan. With a mesh (a ("stage",) axis),
    the head really pipelines via shard_map+ppermute; without one the
    same math runs on one device, head then tail. ``use_pallas`` routes
    every conv, head and tail, through the Pallas kernel."""
    layers = list(net.layers)
    sp = plan.sp

    if mesh is not None and sp > 1:
        from repro.parallel.pipeline import pipeline_apply, split_microbatches
        n_stages = mesh.shape["stage"]
        if sp != n_stages:
            raise ValueError(f"one pipeline stage per head layer: sp={sp}, "
                             f"{n_stages} stages")
        # pipeline_apply stacks stage params, so the stages must be
        # homogeneous (true for the paper's deepened VGG groups).
        shapes = {None if w is None else tuple(w.shape) for w in params[:sp]}
        if len(shapes) != 1 or None in shapes:
            raise ValueError(
                f"the pipelined head needs {sp} conv layers of one weight "
                f"shape; got {sorted(map(str, shapes))}")
        stacked = jnp.stack(params[:sp])

        def stage(w, h):
            return layer_apply(h, w, layers[0], use_pallas)

        mbs = split_microbatches(x, plan.n_micro)
        x = pipeline_apply(stage, stacked, mbs, mesh, axis="stage")
        x = x.reshape((-1,) + x.shape[2:])
    else:
        for w, l in zip(params[:sp], layers[:sp]):
            x = layer_apply(x, w, l, use_pallas)

    # generic structure: one reusable apply, recurrent over the tail
    for w, l in zip(params[sp:], layers[sp:]):
        x = layer_apply(x, w, l, use_pallas)
    return x

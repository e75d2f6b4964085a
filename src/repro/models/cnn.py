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


def _layer(x, w, layer, use_pallas: bool):
    if layer.kind == "pool":
        return jax.lax.reduce_window(
            x, -jnp.inf, jax.lax.max,
            (1, 1, layer.r, layer.s), (1, 1, layer.stride, layer.stride),
            "VALID")
    return jax.nn.relu(_conv(x, w, use_pallas))


def layer_apply(x, w, layer, use_pallas: bool = False):
    """One major layer (+ fused ReLU) or pool on NCHW, under the layer's
    name (``conv4``, ``pool6``) as its scope: the compiled program's ops,
    and so a device trace's, carry the name of the layer they belong to."""
    with jax.named_scope(layer.name):
        return _layer(x, w, layer, use_pallas)


def _frame_chain(params, layers, x):
    """The Pallas path over consecutive layers, each under its scope as
    in :func:`layer_apply`. Each conv reads its input, and writes its
    output with ReLU applied, in the layout of the kernel its shapes take
    (``repro.kernels.conv2d``): a flat frame where the frame kernel's
    blocks fit VMEM, else the row kernel's padded rows. A conv in the
    layout its predecessor wrote reads it as it is, and a 2x2/2 max pool
    writes the layout of the layer after it. The activation is converted
    only where two neighbours' layouts differ (through NCHW), where a
    layer needs NCHW (another pool) and at the ends. While it traces, the
    chain reports the number of conversions as the gauge
    ``cnn.relayouts`` on the current ``repro.obs`` tracer."""
    from repro import obs
    from repro.kernels.conv2d import ops
    have, relayouts = None, 0               # x's layout; None: x is NCHW
    size = "x".join(map(str, x.shape[2:]))

    def into(want):
        nonlocal x, have, relayouts
        if have != want:
            x = ops.relayout(x, have, want)
            have, relayouts = want, relayouts + 1

    for i, (w, l) in enumerate(zip(params, layers)):
        with jax.named_scope(l.name):
            hw = (have.h, have.w) if have else x.shape[2:]
            if l.kind == "conv":
                into(ops.layout_of(w, hw, x.dtype))
                x = ops.conv2d_relu(x, w, have)
            elif have and _pool2(l) and min(hw) >= 2:
                half = (hw[0] // 2, hw[1] // 2)
                nxt = i + 1 < len(layers) and layers[i + 1].kind == "conv" \
                    and ops.layout_of(params[i + 1], half, x.dtype)
                dst = nxt if type(nxt) is type(have) else type(have)(*half)
                x, have = ops.maxpool(x, have, dst), dst
            else:
                into(None)
                x = _layer(x, w, l, True)
            if i == len(layers) - 1:
                into(None)
    obs.current().gauge("cnn.relayouts", relayouts, input=size)
    return x


def _pool2(layer) -> bool:
    """A 2x2 stride-2 pool: the one the layout pools compute."""
    return layer.kind == "pool" and layer.r == layer.s == layer.stride == 2


def _apply(params, layers, x, use_pallas: bool):
    if use_pallas:
        return _frame_chain(params, layers, x)
    for w, l in zip(params, layers):
        x = layer_apply(x, w, l)
    return x


def forward(params, net: NetInfo, x, *, use_pallas: bool = False):
    """Plain sequential forward: x (N, 3, H, W) -> feature map."""
    return _apply(params, list(net.layers), x, use_pallas)


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
    every conv, head and tail, through the Pallas kernels; on one device
    the kernels' layouts carry across the head/tail boundary, while a
    pipelined head's stages take and give NCHW."""
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
        # one device: head and tail in turn, as one chain of layers
        return _apply(params, layers, x, use_pallas)

    # generic structure: one reusable apply, recurrent over the tail
    return _apply(params[sp:], layers[sp:], x, use_pallas)

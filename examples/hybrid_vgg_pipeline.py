"""The paper's paradigm, executed: a VGG-like conv group where the DSE's
split-point sends the first SP layers through a REAL pipeline (shard_map +
ppermute over a `stage` mesh axis) and the rest through the generic
(reusable) apply — then verifies the hybrid output matches the plain
sequential forward bit-for-bit.

Run with multiple virtual devices to see actual pipelining:
    XLA_FLAGS=--xla_force_host_platform_device_count=4 \
        PYTHONPATH=src python examples/hybrid_vgg_pipeline.py
"""
import os

if "xla_force_host_platform_device_count" not in os.environ.get("XLA_FLAGS", ""):
    os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "")
                               + " --xla_force_host_platform_device_count=4")

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.netinfo import _B
from repro.launch.mesh import make_mesh
from repro.models.cnn import HybridPlan, forward, hybrid_forward, init_vgg


def main():
    # A homogeneous conv group (the paper's deepened-VGG structure): 4
    # identical 32-ch 3x3 layers (the pipelined head) + pool + 2 more
    # (the generic tail).
    b = _B("vgg_group", 32, 32, 32)
    for _ in range(4):
        b.conv(32, 3)
    b.pool(2)
    b.conv(64, 3).conv(64, 3)
    net = b.done()

    params = init_vgg(jax.random.key(0), net)
    x = jnp.asarray(np.random.default_rng(0).standard_normal((8, 32, 32, 32)),
                    jnp.float32)

    ref = forward(params, net, x)

    plan = HybridPlan(sp=4, n_micro=4)
    n_dev = len(jax.devices())
    if n_dev < plan.sp:
        raise SystemExit(f"the {plan.sp}-stage head needs {plan.sp} devices, "
                         f"found {n_dev}")
    mesh = make_mesh((plan.sp,), ("stage",), devices=jax.devices()[:plan.sp])
    out = hybrid_forward(params, net, x, plan, mesh=mesh)

    err = float(jnp.abs(out - ref).max())
    print(f"hybrid (pipelined over {plan.sp} stages, SP={plan.sp}, "
          f"{plan.n_micro} microbatches) vs sequential: max |diff| = {err:.2e}")
    assert err < 1e-4
    print("OK — the paper's pipeline-head + generic-tail paradigm runs as a "
          "real JAX execution plan.")


if __name__ == "__main__":
    main()

"""Compile the main paths' device programs for a TPU v5e that is
described, not attached: what the chip's compiler refuses (block tiling,
scoped VMEM, memory) fails here at no chip time. Nothing runs, so these
say nothing about results or speed.

The topology is described inside a fixture, never at import: only one
process at a time may load the TPU library, and every test worker
imports this file.
"""
import re

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import SingleDeviceSharding

# (c, k, h or (h, w)): vgg16's first conv, its widest full-resolution
# conv, its deepest conv, the deep 56- and 28-wide convs at 224x224 (all
# on the frame kernel), and at 720x1280 the deepest conv (frame) and the
# first, the second and the 90x160 conv (rows)
VGG16_CONVS = [(3, 64, 224), (64, 64, 224), (512, 512, 14), (256, 256, 56),
               (512, 512, 28),
               pytest.param(512, 512, (45, 80), id="512-512-45x80"),
               pytest.param(3, 64, (720, 1280), id="3-64-720x1280"),
               pytest.param(512, 512, (90, 160), id="512-512-90x160"),
               pytest.param(64, 64, (720, 1280), id="64-64-720x1280")]


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache as cc

    # a described chip's executables can be written to the persistent
    # cache but not read back without the chip: keep the cache out
    enabled = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    try:
        with pytest.MonkeyPatch.context() as mp:
            mp.setenv("TPU_LOG_DIR", "disabled")
            topo = topologies.get_topology_desc(platform="tpu",
                                                topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 - whatever stops the description
        jax.config.update("jax_enable_compilation_cache", enabled)
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", enabled)


def _spec(x, sharding):
    return jax.ShapeDtypeStruct(np.shape(x), np.asarray(x).dtype,
                                sharding=sharding)


@pytest.mark.parametrize("c,k,hw", VGG16_CONVS)
def test_conv2d_compiles_for_v5e(one_chip, c, k, hw):
    """Within the scoped VMEM the call sets, through the kernel the
    shapes pick."""
    from repro.kernels.conv2d.conv2d import BK, conv2d_same, frame_fits
    h, w = hw if isinstance(hw, tuple) else (hw, hw)
    x = jax.ShapeDtypeStruct((8, c, h, w), jnp.bfloat16, sharding=one_chip)
    wt = jax.ShapeDtypeStruct((k, c, 3, 3), jnp.bfloat16, sharding=one_chip)
    text = conv2d_same.lower(x, wt, bk=BK, interpret=False).compile().as_text()
    assert "tpu_custom_call" in text
    assert ("conv2d_rows_frame" in text) == frame_fits(c, k, 3, 3, h, w, 2,
                                                       BK)


def test_screen_cells_compiles_for_v5e(one_chip):
    """The x64 cross-cell screen at the Table-3 campaign's shape:
    (12 cells, 4096 candidates, 5 position coordinates)."""
    from repro.core import screen_jax
    from repro.core.hw_specs import FPGAS
    from repro.core.netinfo import INPUT_CASES
    from repro.dse.campaign import build_net

    stacked = screen_jax.stack_cells([
        screen_jax.cell_tables(build_net("vgg16", h, w), FPGAS["ku115"])
        for h, w in INPUT_CASES])
    pos = np.zeros((len(INPUT_CASES), 4096, 5))
    with jax.enable_x64(True):
        compiled = screen_jax._kernel().lower(
            {k: _spec(v, one_chip) for k, v in stacked.items()},
            _spec(pos, one_chip)).compile()
        out = compiled.out_info
    assert out.shape == (12, 4096) and out.dtype == jnp.float64


def test_vgg16_kernels_carry_their_layer_scope(one_chip, monkeypatch):
    """Each conv kernel of the compiled hybrid forward names its layer in
    its metadata, so a device trace's ops are credited to layers."""
    from chipbench import attribution
    from repro.core.netinfo import vgg16
    from repro.kernels.conv2d import ops
    from repro.models.cnn import HybridPlan, hybrid_forward
    monkeypatch.setattr(ops, "interpret_mode", lambda: False)  # the chip's
    net = vgg16(32, 32)
    params = [None if l.kind == "pool" else jax.ShapeDtypeStruct(
        (l.k, l.c, l.r, l.s), jnp.bfloat16, sharding=one_chip)
        for l in net.layers]
    x = jax.ShapeDtypeStruct((2, 3, 32, 32), jnp.bfloat16, sharding=one_chip)
    compiled = jax.jit(lambda p, x: hybrid_forward(
        p, net, x, HybridPlan(sp=4, n_micro=1), use_pallas=True)).lower(
            params, x).compile()
    scopes = attribution.op_scopes(compiled.as_text())
    kernels = [attribution.layer_of(v) for k, v in scopes.items()
               if k.startswith("conv2d_rows")]
    convs = [l.name for l in net.layers if l.kind == "conv"]
    assert sorted(kernels) == sorted(convs)
    assert attribution.conv_layers(kernels) == convs


_ENTRY_OP = re.compile(r"^\s*(?:ROOT\s+)?%?([\w.-]+) = \(?\w+\[([\d,]*)\]"
                       r"\S* ([\w-]+)\(")


def _entry_ops(hlo_text: str) -> list[tuple[str, tuple, str, str]]:
    """``(name, result dims, opcode, op_name)`` of each top-level
    instruction of the compiled program, the ops a device trace names;
    the opcode of a Pallas call is ``pallas``."""
    out, entry = [], False
    for line in hlo_text.splitlines():
        if line.startswith("ENTRY "):
            entry = True
        elif entry and line.startswith("}"):
            break
        elif entry and (m := _ENTRY_OP.match(line)):
            op_name = re.search(r'op_name="([^"]*)"', line)
            dims = tuple(int(d) for d in m.group(2).split(",") if d)
            opcode = "pallas" if 'custom_call_target="tpu_custom_call"' \
                in line else m.group(3)
            out.append((m.group(1), dims, opcode,
                        op_name.group(1) if op_name else ""))
    return out


def _chain_ops(one_chip, monkeypatch, hw, batch=2):
    """vgg16 at ``hw`` and the top-level ops of its compiled hybrid
    forward on the Pallas path, with the chip's kernels."""
    from repro.core.netinfo import vgg16
    from repro.kernels.conv2d import ops
    from repro.models.cnn import HybridPlan, hybrid_forward
    monkeypatch.setattr(ops, "interpret_mode", lambda: False)  # the chip's
    net = vgg16(*hw)
    params = [None if l.kind == "pool" else jax.ShapeDtypeStruct(
        (l.k, l.c, l.r, l.s), jnp.bfloat16, sharding=one_chip)
        for l in net.layers]
    x = jax.ShapeDtypeStruct((batch, 3, *hw), jnp.bfloat16,
                             sharding=one_chip)
    text = jax.jit(lambda p, x: hybrid_forward(
        p, net, x, HybridPlan(sp=4, n_micro=1), use_pallas=True)).lower(
            params, x).compile().as_text()
    return net, _entry_ops(text)


def _relayouts(ops_, layers, batch=2):
    """The copies and transposes of an activation (a result led by the
    batch) under the scopes of ``layers``."""
    from chipbench import attribution
    names = {l.name for l in layers}
    return [(name, dims, attribution.layer_of(op_name))
            for name, dims, opcode, op_name in ops_
            if attribution.layer_of(op_name) in names
            and len(dims) >= 3 and dims[0] == batch
            and (opcode in ("copy", "transpose") or re.search(
                r"(^|_)(copy|transpose)", name))]


def _pallas_calls(ops_) -> dict[str, str]:
    """``{Pallas call: its layer scope}``."""
    from chipbench import attribution
    return {name: attribution.layer_of(op_name)
            for name, _, opcode, op_name in ops_ if opcode == "pallas"}


def test_vgg16_224_frame_chain_has_no_relayouts(one_chip, monkeypatch):
    """At 224x224 every conv takes the frame kernel, so between conv1's
    input and pool18's output the activation stays in frames: no copy or
    transpose of an activation (a result led by the batch) carries the
    scope of any other layer. Every conv's kernel is named with the
    ``conv2d_rows`` prefix the benchmark's readers match, each pool's is
    ``maxpool_frame``, and there is no other Pallas call."""
    net, ops_ = _chain_ops(one_chip, monkeypatch, (224, 224))
    assert _relayouts(ops_, net.layers[1:-1]) == []
    calls = _pallas_calls(ops_)
    assert calls and all(n.startswith(("conv2d_rows", "maxpool_frame"))
                         for n in calls)
    convs = [l.name for l in net.layers if l.kind == "conv"]
    pools = [l.name for l in net.layers if l.kind == "pool"]
    assert sorted(v for k, v in calls.items()
                  if k.startswith("conv2d_rows")) == sorted(convs)
    assert sorted(v for k, v in calls.items()
                  if k.startswith("maxpool_frame")) == sorted(pools)


def test_vgg16_720p_row_chain_has_no_relayouts(one_chip, monkeypatch):
    """At 720x1280 conv1-conv13 take the row kernel, so between conv1's
    input and pool14's output the activation stays in the row kernel's
    padded rows: no copy or transpose of an activation under the scopes
    conv2-pool14. Every conv's kernel (the row kernel's and the frame
    kernel's of conv15-conv17) is named with the ``conv2d_rows`` prefix,
    pool3-pool14 are ``maxpool_rows`` and pool18 ``maxpool_frame``, and
    there is no other Pallas call."""
    net, ops_ = _chain_ops(one_chip, monkeypatch, (720, 1280))
    names = [l.name for l in net.layers]
    assert _relayouts(ops_, net.layers[1:names.index("pool14") + 1]) == []
    calls = _pallas_calls(ops_)
    convs = [l.name for l in net.layers if l.kind == "conv"]
    assert sorted(v for k, v in calls.items()
                  if k.startswith("conv2d_rows")) == sorted(convs)
    assert sorted(v for k, v in calls.items()
                  if k.startswith("maxpool_rows")) == \
        ["pool10", "pool14", "pool3", "pool6"]
    assert [v for k, v in calls.items()
            if k.startswith("maxpool_frame")] == ["pool18"]
    assert all(n.startswith(("conv2d_rows", "maxpool_rows", "maxpool_frame"))
               for n in calls)

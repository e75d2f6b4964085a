"""Sharding-rule unit/property tests (fit_spec, param rules, batch specs)."""
import os
import subprocess
import sys

import jax
import pytest
from jax.sharding import PartitionSpec as P

from repro.configs import SHAPES, get_config
from repro.launch.mesh import make_mesh
from repro.launch.specs import input_specs, make_batch
from repro.models import api
from repro.parallel import sharding as shd

try:
    from hypothesis import given, settings
    from hypothesis import strategies as st
    HAVE_HYP = True
except ImportError:
    HAVE_HYP = False


@pytest.fixture(scope="module")
def mesh22():
    return make_mesh((1, 1), ("data", "model"))


def test_fit_spec_drops_nondivisible_axes(mesh22):
    mesh = make_mesh((1, 1), ("data", "model"))
    # mesh sizes are 1 -> everything divides; use a fake wider mesh below
    spec = shd.fit_spec(P("data", "model"), (7, 5), mesh)
    assert spec == P("data", "model")  # 1-way always divides


def test_fit_spec_wide_mesh_subprocess():
    """fit_spec with a 16-way mesh must drop axes on 51865-sized dims."""
    script = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=16"
import jax
from jax.sharding import PartitionSpec as P
from repro.launch.mesh import make_mesh
from repro.parallel import sharding as shd
mesh = make_mesh((4, 4), ("data", "model"))
assert shd.fit_spec(P("data", "model"), (51865, 512), mesh) == P(None, "model")
assert shd.fit_spec(P("data", "model"), (512, 51865), mesh) == P("data", None)
assert shd.fit_spec(P(("data", "model"),), (4,), mesh) == P("data",)  # partial
assert shd.fit_spec(P("data"), (1,), mesh) == P(None)
print("FIT_OK")
"""
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.abspath(
        os.path.join(os.path.dirname(__file__), "..", "src"))
    r = subprocess.run([sys.executable, "-c", script], env=env,
                       capture_output=True, text=True, timeout=300)
    assert "FIT_OK" in r.stdout, r.stderr[-1500:]


@pytest.mark.parametrize("arch", ["starcoder2-3b", "kimi-k2-1t-a32b",
                                  "zamba2-2.7b", "whisper-base", "xlstm-350m"])
def test_param_pspecs_cover_all_leaves(arch, mesh22):
    cfg = get_config(arch).reduced()
    shapes = jax.eval_shape(lambda: api.init_params(jax.random.key(0), cfg))
    specs = shd.param_pspecs(shapes, mesh22)
    leaves_s = jax.tree.leaves(shapes)
    leaves_p = jax.tree.leaves(specs, is_leaf=lambda x: isinstance(x, P))
    assert len(leaves_s) == len(leaves_p)
    for sh, sp in zip(leaves_s, leaves_p):
        assert isinstance(sp, P)
        assert len(sp) <= len(sh.shape)


def test_big_2d_weights_are_sharded(mesh22):
    cfg = get_config("starcoder2-3b")
    shapes = jax.eval_shape(lambda: api.init_params(jax.random.key(0), cfg))
    specs = shd.param_pspecs(shapes, mesh22)
    # embed (V, D) must carry both axes on the 1x1 mesh (everything divides)
    assert specs["embed"] == P("data", "model")
    # stacked block weights get leading None for the layer axis
    assert specs["blocks"]["attn"]["wq"][0] is None


def test_batch_pspecs_match_input_specs(mesh22):
    cfg = get_config("h2o-danube-3-4b")
    for shape_name in ("train_4k", "decode_32k"):
        shape = SHAPES[shape_name]
        specs = input_specs(cfg, shape)
        b = shd.batch_pspecs(cfg, shape, specs, mesh22)
        assert set(b) == set(specs)


def test_make_batch_matches_specs():
    cfg = get_config("whisper-base").reduced()
    from repro.configs.base import ShapeSpec
    shape = ShapeSpec("t", "train", 32, 2)
    specs = input_specs(cfg, shape)
    batch = make_batch(cfg, shape)
    for k, v in specs.items():
        got = jax.tree.map(lambda a: (a.shape, a.dtype), batch[k])
        want = jax.tree.map(lambda s: (s.shape, s.dtype), v)
        assert jax.tree.all(jax.tree.map(lambda a, b: a == b, got, want)), k


if HAVE_HYP:

    @given(st.integers(1, 64), st.integers(1, 64))
    @settings(max_examples=50, deadline=None)
    def test_fit_spec_never_violates_divisibility(a, b):
        mesh = make_mesh((1, 1), ("data", "model"))
        spec = shd.fit_spec(P("data", "model"), (a, b), mesh)
        for d, entry in enumerate(spec):
            if entry is None:
                continue
            axes = entry if isinstance(entry, tuple) else (entry,)
            total = 1
            for ax in axes:
                total *= mesh.shape[ax]
            assert (a, b)[d] % total == 0

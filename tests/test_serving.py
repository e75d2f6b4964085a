"""Serving subsystem: continuous batcher, int8 weight quantization, and
the hybrid LM execution plan."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config
from repro.models import api, transformer
from repro.serve.quant import dequantize_params, quantize_params, storage_bytes
from repro.serve.scheduler import ContinuousBatcher, Request


@pytest.fixture(scope="module")
def tiny_lm():
    cfg = get_config("starcoder2-3b").reduced()
    params = api.init_params(jax.random.key(0), cfg)
    return cfg, params


# ---------------------------------------------------------------------------
# continuous batching
# ---------------------------------------------------------------------------


def test_batcher_completes_all_requests(tiny_lm):
    cfg, params = tiny_lm
    b = ContinuousBatcher(cfg, params, slots=2, max_seq=64)
    rng = np.random.default_rng(0)
    reqs = [Request(rid=i, prompt=list(rng.integers(0, cfg.vocab, 5 + i)),
                    max_new=4) for i in range(5)]
    for r in reqs:
        b.submit(r)
    done = b.run()
    assert sorted(c.rid for c in done) == [0, 1, 2, 3, 4]
    assert all(len(c.tokens) == 4 for c in done)
    # continuous batching must overlap requests: 5 requests on 2 slots
    # cannot take 5x a single request's steps
    assert b.utilization > 0.5, f"utilization {b.utilization}"


def test_batcher_matches_single_request_decode(tiny_lm):
    """Tokens produced in a shared-slot run must equal an isolated run
    (slot reuse must not leak KV state between requests)."""
    cfg, params = tiny_lm
    prompt = [5, 7, 11, 13]

    solo = ContinuousBatcher(cfg, params, slots=1, max_seq=32)
    solo.submit(Request(rid=0, prompt=prompt, max_new=6))
    ref = solo.run()[0].tokens

    crowded = ContinuousBatcher(cfg, params, slots=2, max_seq=32)
    rng = np.random.default_rng(1)
    crowded.submit(Request(rid=9, prompt=list(rng.integers(0, cfg.vocab, 9)),
                           max_new=3))
    crowded.submit(Request(rid=0, prompt=prompt, max_new=6))
    crowded.submit(Request(rid=8, prompt=list(rng.integers(0, cfg.vocab, 3)),
                           max_new=3))
    out = {c.rid: c.tokens for c in crowded.run()}
    assert out[0] == ref


def test_batcher_eos_stops_early(tiny_lm):
    cfg, params = tiny_lm
    b = ContinuousBatcher(cfg, params, slots=1, max_seq=64)
    # figure out the first greedy token, then use it as EOS
    probe = ContinuousBatcher(cfg, params, slots=1, max_seq=64)
    probe.submit(Request(rid=0, prompt=[1, 2, 3], max_new=1))
    first = probe.run()[0].tokens[0]
    b.submit(Request(rid=0, prompt=[1, 2, 3], max_new=10, eos=first))
    done = b.run()
    assert done[0].tokens == [first]


# ---------------------------------------------------------------------------
# int8 weight-only quantization
# ---------------------------------------------------------------------------


def test_quantized_params_are_4x_smaller(tiny_lm):
    cfg, params = tiny_lm
    q = quantize_params(params)
    # 2-D+ weights dominate: expect close to 4x (fp32 -> int8 + small scales)
    ratio = storage_bytes(params) / storage_bytes(q)
    assert ratio > 3.0, f"only {ratio:.2f}x smaller"


def test_quantized_logits_close_and_top1_stable(tiny_lm):
    cfg, params = tiny_lm
    toks = jax.random.randint(jax.random.key(2), (2, 16), 0, cfg.vocab)
    full = transformer.forward(params, cfg, toks, compute_dtype=jnp.float32)
    deq = dequantize_params(quantize_params(params), dtype=jnp.float32)
    qlog = transformer.forward(deq, cfg, toks, compute_dtype=jnp.float32)
    # top-1 agreement on most positions (weight-only int8 is near-lossless)
    agree = (jnp.argmax(full, -1) == jnp.argmax(qlog, -1)).mean()
    assert agree > 0.9, f"top-1 agreement {agree}"


def test_quantize_preserves_norm_scales(tiny_lm):
    cfg, params = tiny_lm
    q = quantize_params(params)
    assert q["ln_f"]["scale"].dtype == params["ln_f"]["scale"].dtype


# ---------------------------------------------------------------------------
# hybrid LM plan
# ---------------------------------------------------------------------------


def test_hybrid_lm_matches_plain_forward(tiny_lm):
    from repro.train.hybrid import HybridLMPlan, hybrid_lm_forward
    cfg, params = tiny_lm
    toks = jax.random.randint(jax.random.key(3), (4, 16), 0, cfg.vocab)
    ref = transformer.forward(params, cfg, toks, compute_dtype=jnp.float32,
                              remat="none")
    plan = HybridLMPlan(sp=2, n_stages=2, n_micro=2)
    out = hybrid_lm_forward(params, cfg, toks, plan, mesh=None,
                            compute_dtype=jnp.float32)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=1e-4, rtol=1e-4)


def test_hybrid_lm_pipelined_subprocess():
    import os
    import subprocess
    import sys
    script = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=2"
import jax, jax.numpy as jnp, numpy as np
from repro.configs import get_config
from repro.launch.mesh import make_mesh
from repro.models import api, transformer
from repro.train.hybrid import HybridLMPlan, hybrid_lm_forward

cfg = get_config("starcoder2-3b").reduced()
params = api.init_params(jax.random.key(0), cfg)
toks = jax.random.randint(jax.random.key(3), (4, 16), 0, cfg.vocab)
ref = transformer.forward(params, cfg, toks, compute_dtype=jnp.float32,
                          remat="none")
mesh = make_mesh((2,), ("stage",))
plan = HybridLMPlan(sp=2, n_stages=2, n_micro=2)
out = hybrid_lm_forward(params, cfg, toks, plan, mesh=mesh,
                        compute_dtype=jnp.float32)
np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=1e-4,
                           rtol=1e-4)
# params laid out per stage: stage i's blocks on device i, same logits
from repro.train.hybrid import place_params
placed = place_params(params, plan, mesh)
for leaf in jax.tree.leaves(placed["head"]):
    for shard in leaf.addressable_shards:
        assert shard.device == mesh.devices.flat[shard.index[0].start]
out = hybrid_lm_forward(placed, cfg, toks, plan, mesh=mesh,
                        compute_dtype=jnp.float32)
np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=1e-4,
                           rtol=1e-4)
# gradients flow through the pipelined head
from repro.train.hybrid import hybrid_lm_loss
g = jax.grad(lambda p: hybrid_lm_loss(p, cfg, toks, toks, plan, mesh,
                                      compute_dtype=jnp.float32))(params)
assert all(np.isfinite(x).all() for x in jax.tree.leaves(g))
print("HYBRID_LM_OK")
"""
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.abspath(
        os.path.join(os.path.dirname(__file__), "..", "src"))
    r = subprocess.run([sys.executable, "-c", script], env=env,
                       capture_output=True, text=True, timeout=600)
    assert "HYBRID_LM_OK" in r.stdout, f"{r.stdout}\n{r.stderr[-2000:]}"


# ---------------------------------------------------------------------------
# rmsnorm kernel
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("shape", [(2, 16, 64), (1, 100, 128), (4, 7, 48)])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_rmsnorm_kernel_matches_ref(shape, dtype):
    from repro.kernels.rmsnorm.ops import rmsnorm
    from repro.kernels.rmsnorm.ref import rmsnorm_ref
    rng = np.random.default_rng(0)
    x = jnp.asarray(rng.standard_normal(shape), dtype)
    s = jnp.asarray(rng.standard_normal(shape[-1]), dtype)
    out = rmsnorm(x, s, bm=32)
    ref = rmsnorm_ref(x, s)
    tol = 2e-2 if dtype == jnp.bfloat16 else 1e-5
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(ref, np.float32), atol=tol, rtol=tol)

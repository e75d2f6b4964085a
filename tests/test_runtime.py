"""Distributed-runtime behaviour tests: data determinism, checkpoint
atomicity + resume + elastic reshard, failure injection, straggler monitor,
pipeline parallelism equivalence, gradient compression."""
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.checkpoint import store
from repro.configs import get_config
from repro.configs.base import ShapeSpec
from repro.data.pipeline import DataConfig, TokenPipeline
from repro.launch.mesh import make_mesh
from repro.optim import adamw
from repro.train.trainer import TrainConfig, Trainer


# ---------------------------------------------------------------------------
# data pipeline
# ---------------------------------------------------------------------------


def test_data_deterministic_and_stateless():
    cfg = DataConfig(vocab=512, seq_len=64, global_batch=8, seed=3)
    p1, p2 = TokenPipeline(cfg), TokenPipeline(cfg)
    a = p1.make(step=17, shard=2, n_shards=4)
    b = p2.make(step=17, shard=2, n_shards=4)
    np.testing.assert_array_equal(a["tokens"], b["tokens"])
    # labels are next-token shifted
    np.testing.assert_array_equal(a["tokens"][:, 1:], a["labels"][:, :-1])


def test_data_shards_partition_global_batch():
    cfg = DataConfig(vocab=512, seq_len=32, global_batch=8, seed=0)
    p = TokenPipeline(cfg)
    full = [p.make(5, shard=i, n_shards=4)["tokens"] for i in range(4)]
    assert all(f.shape == (2, 32) for f in full)
    # different shards differ
    assert not np.array_equal(full[0], full[1])


# ---------------------------------------------------------------------------
# checkpoint store
# ---------------------------------------------------------------------------


def _tree():
    return {"a": jnp.arange(6, dtype=jnp.float32).reshape(2, 3),
            "nested": {"b": jnp.ones((4,), jnp.int32)},
            "lst": [jnp.zeros((2,)), jnp.full((3,), 7.0)]}


def test_checkpoint_roundtrip(tmp_path):
    d = str(tmp_path)
    t = _tree()
    store.save(d, 3, t, meta={"k": "v"})
    assert store.latest_step(d) == 3
    like = jax.tree.map(jnp.zeros_like, t)
    out = store.restore(d, 3, like)
    for x, y in zip(jax.tree.leaves(t), jax.tree.leaves(out)):
        np.testing.assert_array_equal(np.asarray(x), np.asarray(y))
    assert store.meta(d, 3)["meta"]["k"] == "v"


def test_checkpoint_latest_survives_torn_write(tmp_path):
    d = str(tmp_path)
    store.save(d, 1, _tree())
    store.save(d, 2, _tree())
    # simulate a torn step_3: directory without manifest + stale LATEST
    os.makedirs(os.path.join(d, "step_00000003"))
    with open(os.path.join(d, "LATEST"), "w") as f:
        f.write("step_00000003")
    assert store.latest_step(d) == 2  # falls back to newest complete


def test_checkpoint_reshard_on_restore(tmp_path):
    d = str(tmp_path)
    mesh = make_mesh((1, 1), ("data", "model"))
    t = {"w": jnp.arange(16, dtype=jnp.float32).reshape(4, 4)}
    store.save(d, 1, t)
    from jax.sharding import NamedSharding, PartitionSpec as P
    sh = {"w": NamedSharding(mesh, P("data", "model"))}
    out = store.restore(d, 1, t, sh)
    assert out["w"].sharding == sh["w"]
    np.testing.assert_array_equal(np.asarray(out["w"]), np.asarray(t["w"]))


# ---------------------------------------------------------------------------
# trainer: loss goes down, resume, failure injection
# ---------------------------------------------------------------------------


@pytest.fixture(scope="module")
def tiny_setup():
    cfg = get_config("starcoder2-3b").reduced()
    shape = ShapeSpec("t", "train", 64, 4)
    return cfg, shape


def test_trainer_loss_decreases(tiny_setup, tmp_path):
    cfg, shape = tiny_setup
    tr = Trainer(cfg, shape, TrainConfig(steps=12, ckpt_every=100,
                                         ckpt_dir=str(tmp_path),
                                         log_every=100))
    tr.run()
    first = np.mean([s["loss"] for s in tr.stats[:3]])
    last = np.mean([s["loss"] for s in tr.stats[-3:]])
    assert last < first, f"loss did not decrease: {first} -> {last}"


def test_trainer_compile_ahead_of_time(tiny_setup, tmp_path):
    """compile() sizes the step before any parameter exists on the
    device, and run() then steps that executable: same losses as the
    jit path."""
    cfg, shape = tiny_setup
    tcfg = dict(steps=3, ckpt_every=100, log_every=100)
    aot = Trainer(cfg, shape, TrainConfig(ckpt_dir=str(tmp_path / "a"), **tcfg))
    m = aot.compile().memory_analysis()
    assert m.argument_size_in_bytes > 0 and m.alias_size_in_bytes > 0
    aot.run()
    jit = Trainer(cfg, shape, TrainConfig(ckpt_dir=str(tmp_path / "b"), **tcfg))
    jit.run()
    np.testing.assert_allclose([s["loss"] for s in aot.stats],
                               [s["loss"] for s in jit.stats], rtol=1e-6)


def test_trainer_failure_injection_recovers(tiny_setup, tmp_path):
    cfg, shape = tiny_setup
    tr = Trainer(cfg, shape, TrainConfig(steps=8, ckpt_every=2,
                                         ckpt_dir=str(tmp_path),
                                         log_every=100))
    tr.fail_at(5)
    tr.run()
    assert tr.step == 8
    assert tr._restarts == 1
    # steps replayed from the last checkpoint: all 8 steps were executed
    assert {s["step"] for s in tr.stats} == set(range(8))


def test_trainer_resume_from_checkpoint(tiny_setup, tmp_path):
    cfg, shape = tiny_setup
    t1 = Trainer(cfg, shape, TrainConfig(steps=4, ckpt_every=4,
                                         ckpt_dir=str(tmp_path), log_every=100))
    t1.run()
    t2 = Trainer(cfg, shape, TrainConfig(steps=8, ckpt_every=4,
                                         ckpt_dir=str(tmp_path), log_every=100))
    t2.run()
    # t2 resumed at 4, only ran 4..7
    assert min(s["step"] for s in t2.stats) == 4


# ---------------------------------------------------------------------------
# optimizer
# ---------------------------------------------------------------------------


def test_adamw_descends_quadratic():
    cfg = adamw.AdamWConfig(lr=0.1, weight_decay=0.0, warmup_steps=0,
                            total_steps=100)
    params = {"x": jnp.array([5.0, -3.0])}
    state = adamw.init(params)
    for _ in range(60):
        grads = {"x": 2 * params["x"]}
        params, state, _ = adamw.apply(grads, state, params, cfg)
    assert float(jnp.abs(params["x"]).max()) < 0.5


def test_adamw_clips_gradients():
    cfg = adamw.AdamWConfig(clip_norm=1.0)
    params = {"x": jnp.zeros(3)}
    state = adamw.init(params)
    _, _, stats = adamw.apply({"x": jnp.full((3,), 100.0)}, state, params, cfg)
    assert float(stats["grad_norm"]) > 100  # raw norm reported


# ---------------------------------------------------------------------------
# gradient compression
# ---------------------------------------------------------------------------


def test_compression_error_feedback_unbiased():
    from repro.parallel.collectives import compress_grads, init_error_feedback
    rng = np.random.default_rng(0)
    g = {"w": jnp.asarray(rng.standard_normal(1000), jnp.float32)}
    err = init_error_feedback(g)
    total_q = np.zeros(1000)
    for _ in range(50):
        q, err = compress_grads(g, err)
        total_q += np.asarray(q["w"])
    # long-run average of compressed grads converges to the true gradient
    np.testing.assert_allclose(total_q / 50, np.asarray(g["w"]), atol=2e-3)


# ---------------------------------------------------------------------------
# pipeline parallelism (uses >1 host device only if available)
# ---------------------------------------------------------------------------


def test_pipeline_apply_matches_sequential():
    from repro.parallel.pipeline import pipeline_apply, split_microbatches
    n_dev = len(jax.devices())
    if n_dev < 2:
        pytest.skip("needs >=2 devices for a pipeline mesh (see "
                    "tests/test_pipeline_multidev.py run via subprocess)")
    mesh = make_mesh((n_dev,), ("stage",))
    d = 16
    ws = jnp.asarray(np.random.default_rng(0).standard_normal((n_dev, d, d))
                     * 0.3, jnp.float32)
    x = jnp.asarray(np.random.default_rng(1).standard_normal((8, d)),
                    jnp.float32)

    def stage(w, h):
        return jnp.tanh(h @ w)

    seq = x
    for i in range(n_dev):
        seq = stage(ws[i], seq)
    mbs = split_microbatches(x, 4)
    out = pipeline_apply(stage, ws, mbs, mesh)
    np.testing.assert_allclose(np.asarray(out.reshape(8, d)),
                               np.asarray(seq), atol=1e-5)

"""Production training loop: checkpoint/auto-resume, heartbeat + straggler
monitoring, failure injection (for tests) and retry-with-restore.

Designed for the 1000+-node regime:
* every batch is a pure function of (seed, step, shard) — no data-loader
  state to lose on failover (repro.data.pipeline);
* checkpoints are atomic and reshardable — a job restarted on a different
  mesh keeps training (repro.checkpoint.store);
* the heartbeat monitor flags steps slower than ``straggler_factor`` x the
  EWMA — on multi-host deployments this is the signal to evict/replace a
  slow host; here it feeds the log and the test hooks;
* transient step failures restore the last checkpoint and replay
  (bounded by ``max_restarts``).
"""
from __future__ import annotations

import dataclasses
import logging
import time

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.checkpoint import store
from repro.configs.base import ArchConfig, ShapeSpec
from repro.data.pipeline import DataConfig, TokenPipeline
from repro.launch.mesh import make_local_mesh
from repro.models import api
from repro.optim import adamw
from repro.parallel import sharding as shd

log = logging.getLogger("repro.train")


@dataclasses.dataclass
class TrainConfig:
    steps: int = 100
    ckpt_every: int = 50
    ckpt_dir: str = "checkpoints"
    log_every: int = 10
    seed: int = 0
    straggler_factor: float = 3.0
    ewma: float = 0.9
    max_restarts: int = 3
    remat: str = "full"
    compute_dtype: str = "bfloat16"
    grad_compression: bool = False


class Trainer:
    def __init__(self, cfg: ArchConfig, shape: ShapeSpec, tcfg: TrainConfig,
                 mesh=None, ocfg: adamw.AdamWConfig | None = None):
        self.cfg, self.shape, self.tcfg = cfg, shape, tcfg
        self.ocfg = ocfg or adamw.AdamWConfig(total_steps=tcfg.steps)
        if mesh is None:
            mesh = make_local_mesh()
        self.mesh = mesh
        self.data = TokenPipeline(DataConfig(
            vocab=cfg.vocab, seq_len=shape.seq_len,
            global_batch=shape.global_batch, seed=tcfg.seed))
        self._build()
        self.step = 0
        self.stats: list[dict] = []
        self.straggler_events: list[int] = []
        self._fail_at: set[int] = set()  # test hook
        self._restarts = 0
        self._compiled = None

    # ------------------------------------------------------------------
    def _build(self):
        cfg, mesh = self.cfg, self.mesh
        params_shapes = jax.eval_shape(
            lambda: api.init_params(jax.random.key(self.tcfg.seed), cfg))
        self.p_specs = shd.param_pspecs(params_shapes, mesh)
        self.p_shard = jax.tree.map(lambda s: NamedSharding(mesh, s),
                                    self.p_specs)
        o_specs = adamw.OptState(mu=self.p_specs, nu=self.p_specs, count=P())
        self.o_shard = jax.tree.map(lambda s: NamedSharding(mesh, s), o_specs)
        dpa = shd.dp_axes(mesh)
        self.dpa = dpa if len(dpa) > 1 else dpa[0]
        self.b_shard = NamedSharding(mesh, P(self.dpa, None))

        ocfg, tcfg = self.ocfg, self.tcfg
        cd = jnp.bfloat16 if tcfg.compute_dtype == "bfloat16" else jnp.float32

        def train_step(params, opt_state, batch):
            loss, grads = jax.value_and_grad(api.loss_fn)(
                params, cfg, batch, remat=tcfg.remat, compute_dtype=cd)
            if tcfg.grad_compression:
                from repro.parallel.collectives import compress_grads
                grads, _ = compress_grads(
                    grads, jax.tree.map(jnp.zeros_like, grads))
            new_params, new_state, st = adamw.apply(grads, opt_state, params,
                                                    ocfg)
            return new_params, new_state, loss, st["grad_norm"]

        self.train_step = jax.jit(
            train_step,
            in_shardings=(self.p_shard, self.o_shard,
                          {"tokens": self.b_shard, "labels": self.b_shard}),
            out_shardings=(self.p_shard, self.o_shard,
                           NamedSharding(mesh, P()), NamedSharding(mesh, P())),
            donate_argnums=(0, 1))

    def compile(self):
        """Compile the train step ahead of time for this shape and mesh;
        :meth:`run` then steps the compiled executable. Returns it, so a
        caller can size a run from ``memory_analysis()`` before any
        parameter exists on the device."""
        def spec(shape, sharding):
            return jax.ShapeDtypeStruct(shape.shape, shape.dtype,
                                        sharding=sharding)

        p_shapes = jax.eval_shape(
            lambda: api.init_params(jax.random.key(self.tcfg.seed), self.cfg))
        o_shapes = jax.eval_shape(adamw.init, p_shapes)
        tok = jax.ShapeDtypeStruct(
            (self.shape.global_batch, self.shape.seq_len), jnp.int32,
            sharding=self.b_shard)
        self._compiled = self.train_step.lower(
            jax.tree.map(spec, p_shapes, self.p_shard),
            jax.tree.map(spec, o_shapes, self.o_shard),
            {"tokens": tok, "labels": tok}).compile()
        return self._compiled

    # ------------------------------------------------------------------
    def init_state(self):
        with self.mesh:
            params = jax.jit(
                lambda: api.init_params(jax.random.key(self.tcfg.seed),
                                        self.cfg),
                out_shardings=self.p_shard)()
            opt = jax.jit(adamw.init, out_shardings=self.o_shard)(params)
        return params, opt

    def restore_or_init(self):
        last = store.latest_step(self.tcfg.ckpt_dir)
        params, opt = self.init_state()
        if last is not None:
            log.info("resuming from checkpoint step %d", last)
            tree = store.restore(
                self.tcfg.ckpt_dir, last, {"params": params, "opt": opt},
                {"params": self.p_shard, "opt": self.o_shard})
            params, opt = tree["params"], tree["opt"]
            self.step = last
        return params, opt

    def _make_batch(self, step: int):
        b = self.data.make(step)
        return {k: jax.device_put(v, self.b_shard) for k, v in b.items()}

    # ------------------------------------------------------------------
    def fail_at(self, *steps: int):
        """Test hook: inject a simulated node failure at given steps."""
        self._fail_at.update(steps)

    def run(self):
        params, opt = self.restore_or_init()
        step_fn = self._compiled or self.train_step
        ewma_t = None
        while self.step < self.tcfg.steps:
            s = self.step
            t0 = time.perf_counter()
            try:
                if s in self._fail_at:
                    self._fail_at.discard(s)
                    raise RuntimeError(f"injected node failure @ step {s}")
                batch = self._make_batch(s)
                params, opt, loss, gnorm = step_fn(params, opt, batch)
                loss = float(loss)
            except Exception as e:  # noqa: BLE001 — failover path
                self._restarts += 1
                if self._restarts > self.tcfg.max_restarts:
                    raise
                log.warning("step %d failed (%s); restoring last checkpoint",
                            s, e)
                params, opt = self.restore_or_init()
                continue

            dt = time.perf_counter() - t0
            ewma_t = dt if ewma_t is None else (
                self.tcfg.ewma * ewma_t + (1 - self.tcfg.ewma) * dt)
            if dt > self.tcfg.straggler_factor * ewma_t and s > 2:
                self.straggler_events.append(s)
                log.warning("straggler: step %d took %.2fs (ewma %.2fs)",
                            s, dt, ewma_t)

            self.step = s + 1
            self.stats.append({"step": s, "loss": loss,
                               "grad_norm": float(gnorm), "time_s": dt})
            if s % self.tcfg.log_every == 0:
                log.info("step %d loss %.4f gnorm %.3f %.2fs",
                         s, loss, float(gnorm), dt)
            if self.step % self.tcfg.ckpt_every == 0 or \
                    self.step == self.tcfg.steps:
                store.save(self.tcfg.ckpt_dir, self.step,
                           {"params": params, "opt": opt},
                           meta={"arch": self.cfg.name, "loss": loss})
        return params, opt


# convenience for checkpoints saved by Trainer (params+opt under one tree)
def restore_trainer_state(trainer: Trainer, step: int):
    params, opt = trainer.init_state()
    tree = store.restore(trainer.tcfg.ckpt_dir, step,
                         {"params": params, "opt": opt},
                         {"params": trainer.p_shard, "opt": trainer.o_shard})
    return tree["params"], tree["opt"]

"""Hybrid execution plan for transformer LMs — the paper's paradigm
applied to the assigned architectures.

The DSE's split-point SP sends the first SP decoder blocks through
dedicated *pipeline stages* (one submesh slice per group of layers,
microbatches streaming via shard_map+ppermute — the paper's pipeline
structure) and the remaining blocks through the ordinary scanned
(generic, reusable) path. For uniform-layer LMs the DSE degenerates to
SP=0 (DESIGN.md §Arch-applicability); this module is what a nonzero SP
*executes*.
"""
from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.configs.base import ArchConfig
from repro.models import transformer
from repro.models.layers import rms_norm
from repro.parallel.pipeline import pipeline_apply, split_microbatches


@dataclasses.dataclass(frozen=True)
class HybridLMPlan:
    sp: int                 # blocks in the pipelined head
    n_stages: int           # pipeline stages (sp % n_stages == 0)
    n_micro: int            # microbatches

    @property
    def layers_per_stage(self) -> int:
        return self.sp // self.n_stages


def _split_head(params, plan: HybridLMPlan):
    """blocks (L, ...) -> head (n_stages, layers_per_stage, ...), tail."""
    head = jax.tree.map(lambda a: a[:plan.sp].reshape(
        (plan.n_stages, plan.layers_per_stage) + a.shape[1:]),
        params["blocks"])
    tail = jax.tree.map(lambda a: a[plan.sp:], params["blocks"])
    return head, tail


def place_params(params, plan: HybridLMPlan, mesh):
    """Lay ``params`` out for the pipelined forward on ``mesh``: the head
    split into stages and sharded over ``stage`` (stage i's blocks on the
    mesh's device i), everything else replicated. The result carries
    ``head``/``tail`` in place of ``blocks``;
    :func:`hybrid_lm_forward` takes either form."""
    head, tail = _split_head(params, plan)
    rest = {k: v for k, v in params.items() if k != "blocks"}
    return {"head": jax.device_put(head, NamedSharding(mesh, P("stage"))),
            **jax.device_put({"tail": tail, **rest},
                             NamedSharding(mesh, P()))}


def hybrid_lm_forward(params, cfg: ArchConfig, tokens, plan: HybridLMPlan,
                      mesh=None, *, compute_dtype=jnp.bfloat16):
    """Forward with a pipelined head. With ``mesh`` (a ("stage",) axis of
    size plan.n_stages) the head truly pipelines; without it the same
    math runs sequentially on one device."""
    x = params["embed"].astype(compute_dtype)[tokens]
    if "head" in params:               # laid out by place_params
        head, tail = params["head"], params["tail"]
    else:
        head, tail = _split_head(params, plan)

    def stage_fn(stage_params, h):
        def step(h, bp):
            return transformer.block_apply(h, bp, cfg), None
        h, _ = jax.lax.scan(step, h, stage_params)
        return h

    if mesh is not None and plan.sp > 0:
        mbs = split_microbatches(x, plan.n_micro)
        x = pipeline_apply(stage_fn, head, mbs, mesh, axis="stage")
        x = x.reshape((-1,) + x.shape[2:])
    else:
        for i in range(plan.n_stages):
            x = stage_fn(jax.tree.map(lambda a: a[i], head), x)

    def step(x, bp):
        return transformer.block_apply(x, bp, cfg), None

    x, _ = jax.lax.scan(step, x, tail)
    x = rms_norm(x, params["ln_f"])
    h = params["lm_head"] if "lm_head" in params else params["embed"].T
    return (x @ h.astype(compute_dtype)).astype(jnp.float32)


def hybrid_lm_loss(params, cfg: ArchConfig, tokens, labels,
                   plan: HybridLMPlan, mesh=None, **kw):
    logits = hybrid_lm_forward(params, cfg, tokens, plan, mesh, **kw)
    return transformer.softmax_xent(logits, labels)

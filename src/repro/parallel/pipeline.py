"""Microbatch pipeline parallelism over a mesh axis (shard_map + ppermute).

This is the TPU instantiation of the paper's *pipeline structure*: each
stage (a submesh slice along the ``stage`` axis) owns the dedicated
parameters of its layer range, and activations stream stage-to-stage the
way DNNBuilder's column buffers stream between RTL stages — the "column"
is a microbatch, the column buffer is the ppermute edge, and the
fine-grained launch-as-soon-as-first-column-arrives behavior is the
pipeline fill phase (GPipe fill/drain schedule).

``pipeline_apply`` is differentiable (ppermute transposes to the reverse
permutation), so it composes with jax.grad for training.
"""
from __future__ import annotations


import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P


def pipeline_apply(stage_fn, stage_params, x_microbatches, mesh: Mesh,
                   axis: str = "stage"):
    """Run ``stage_fn(params_i, x)`` over pipeline stages.

    stage_params: pytree stacked on a leading stage axis (sharded over
    ``axis``); x_microbatches: (n_micro, mb, ...) activations entering
    stage 0. Returns (n_micro, mb, ...) outputs of the last stage,
    replicated across stages for downstream use.
    """
    n_stages = mesh.shape[axis]
    n_micro = x_microbatches.shape[0]
    ticks = n_micro + n_stages - 1
    perm = [(i, i + 1) for i in range(n_stages - 1)]

    def per_stage(params, mbs):
        # params: this stage's slice (leading axis 1); mbs: full microbatch
        # stack (replicated input; only stage 0 consumes it).
        params = jax.tree.map(lambda a: a[0], params)
        idx = jax.lax.axis_index(axis)
        mb_shape = mbs.shape[1:]
        carry_in = jnp.zeros(mb_shape, mbs.dtype)
        outs = jnp.zeros((n_micro,) + mb_shape, mbs.dtype)

        def tick(state, t):
            carry, outs = state
            # stage 0 ingests microbatch t (during fill+steady phase)
            mb_idx = jnp.clip(t, 0, n_micro - 1)
            inj = jax.lax.dynamic_index_in_dim(mbs, mb_idx, 0, keepdims=False)
            x = jnp.where(idx == 0, inj, carry)
            y = stage_fn(params, x)
            # the last stage commits microbatch t - (n_stages - 1)
            out_idx = t - (n_stages - 1)
            valid = jnp.logical_and(idx == n_stages - 1, out_idx >= 0)
            outs = jax.lax.cond(
                valid,
                lambda o: jax.lax.dynamic_update_index_in_dim(
                    o, y, jnp.clip(out_idx, 0, n_micro - 1), 0),
                lambda o: o, outs)
            nxt = jax.lax.ppermute(y, axis, perm) if perm else y
            return (nxt, outs), None

        (carry, outs), _ = jax.lax.scan(tick, (carry_in, outs),
                                        jnp.arange(ticks))
        # replicate the last stage's outputs to every stage
        outs = jax.lax.psum(
            jnp.where(idx == n_stages - 1, outs, jnp.zeros_like(outs)), axis)
        return outs

    spec_params = jax.tree.map(lambda _: P(axis), stage_params)
    fn = jax.shard_map(per_stage, mesh=mesh,
                       in_specs=(spec_params, P()), out_specs=P(),
                       check_vma=False)
    return fn(stage_params, x_microbatches)


def split_microbatches(x, n_micro: int):
    """(B, ...) -> (n_micro, B // n_micro, ...)"""
    b = x.shape[0]
    assert b % n_micro == 0, f"batch {b} % microbatches {n_micro}"
    return x.reshape((n_micro, b // n_micro) + x.shape[1:])

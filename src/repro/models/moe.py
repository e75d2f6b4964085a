"""Mixture-of-Experts decoder (llama4-maverick, kimi-k2).

Dispatch design note (TPU adaptation): GShard-style one-hot einsum dispatch
costs O(T * E*C * d) *dense* FLOPs in XLA — at kimi-k2 scale that is ~1e16
FLOPs/layer of pure dispatch, drowning the real compute. We instead use a
scatter/gather dispatch: O(T*k*d) data movement, expert GEMMs are the only
large FLOPs, and expert-parallel sharding over the "model" axis lowers to
all-to-all-ish collectives under GSPMD. Tokens over capacity are dropped
(standard capacity-factor semantics); gates renormalize over kept experts.
"""
from __future__ import annotations

import math
from functools import partial

import jax
import jax.numpy as jnp

from repro.configs.base import ArchConfig
from repro.parallel.act import constrain
from .layers import (dense_init, embed_init, gqa_attention,
                     gqa_decode_attention, init_attention, init_mlp,
                     init_rmsnorm, mlp, rms_norm)
from .transformer import _stack, softmax_xent


def init_moe_mlp(key, cfg: ArchConfig, dtype=jnp.float32):
    e = cfg.moe
    ks = jax.random.split(key, 5)
    d, f = cfg.d_model, e.d_ff_expert
    scale = 1.0 / math.sqrt(d)

    def ew(k, a, b):
        return (jax.random.normal(k, (e.n_experts, a, b), jnp.float32)
                * (1.0 / math.sqrt(a))).astype(dtype)

    p = {
        "router": dense_init(ks[0], d, e.n_experts, dtype),
        "w_up": ew(ks[1], d, f),
        "w_gate": ew(ks[2], d, f),
        "w_down": ew(ks[3], f, d),
    }
    if e.n_shared:
        p["shared"] = init_mlp(ks[4], d, e.n_shared * f, gated=True, dtype=dtype)
    return p


def moe_mlp(x, params, cfg: ArchConfig):
    """x (B, S, d) -> (y (B, S, d), aux_loss scalar).

    If the activation-spec table advertises a mesh with a `model` axis
    (``_ep_mesh`` key), dispatch runs expert-parallel inside a shard_map
    that is *manual over model, auto over data*: every model shard routes
    the (data-sharded, model-replicated) tokens to its local experts and
    the partial outputs are psum'd over `model` — O(T*d) ICI traffic per
    layer instead of the gather-based exchange GSPMD derives for a global
    scatter (measured 12x heavier on kimi-k2; see EXPERIMENTS.md §Perf).
    """
    from repro.parallel.act import ep_mesh
    mesh_axis = ep_mesh()
    if mesh_axis is not None:
        return _moe_mlp_ep_shardmap(x, params, cfg, *mesh_axis)
    return _moe_mlp_dense(x, params, cfg)


def _moe_mlp_dense(x, params, cfg: ArchConfig):
    e = cfg.moe
    b, s, d = x.shape
    t = b * s
    cd = x.dtype
    xf = x.reshape(t, d)

    logits = (xf @ params["router"].astype(cd)).astype(jnp.float32)  # (T, E)
    probs = jax.nn.softmax(logits, axis=-1)
    gate_vals, expert_idx = jax.lax.top_k(probs, e.top_k)             # (T, k)
    gate_vals = gate_vals / jnp.maximum(gate_vals.sum(-1, keepdims=True), 1e-9)

    flat_e = expert_idx.T.reshape(-1)                                  # (k*T,)

    # Load-balance aux loss (Switch-style): E * sum_e f_e * p_e.
    me = probs.mean(0)
    counts = jnp.zeros((e.n_experts,), jnp.int32).at[flat_e].add(1)
    aux = e.n_experts * jnp.sum(me * counts.astype(jnp.float32)) / (t * e.top_k)

    capacity = int(math.ceil(t * e.top_k * e.capacity_factor / e.n_experts))
    capacity = max(capacity, 4)

    # Slot of each assignment within its expert. A (T*k, E) one-hot cumsum
    # would materialize O(T*E) ints (terabytes at kimi-k2 train scale), so
    # rank via a stable sort instead: O(T*k log T*k) and O(T*k) memory.
    kt = t * e.top_k
    order = jnp.argsort(flat_e, stable=True)                           # (k*T,)
    starts = jnp.cumsum(counts) - counts                               # (E,)
    slot_sorted = jnp.arange(kt, dtype=jnp.int32) - starts[flat_e[order]]
    slot = jnp.zeros((kt,), jnp.int32).at[order].set(slot_sorted)
    keep = (slot < capacity)
    slot = jnp.clip(slot, 0, capacity - 1)

    # Scatter tokens into per-expert buffers (dropped tokens contribute 0).
    buf_idx = flat_e * capacity + slot                                 # (k*T,)
    xk = constrain(jnp.tile(xf, (e.top_k, 1)) * keep[:, None].astype(cd),
                   "tokens_flat")
    base_buf = constrain(jnp.zeros((e.n_experts * capacity, d), cd),
                         "experts_flat")
    buffers = base_buf.at[buf_idx].add(xk)
    buffers = constrain(buffers.reshape(e.n_experts, capacity, d), "experts")

    # Expert GEMMs (the only large FLOPs): (E, C, d) x (E, d, f).
    up = jnp.einsum("ecd,edf->ecf", buffers, params["w_up"].astype(cd))
    gatep = jnp.einsum("ecd,edf->ecf", buffers, params["w_gate"].astype(cd))
    h = jax.nn.silu(up) * gatep
    out = constrain(jnp.einsum("ecf,efd->ecd", h, params["w_down"].astype(cd)),
                    "experts")
    out = out.reshape(e.n_experts * capacity, d)

    # Gather back and combine with renormalized gates.
    out = constrain(out, "experts_flat")
    yk = out[buf_idx] * (keep.astype(cd) * gate_vals.T.reshape(-1).astype(cd))[:, None]
    y = constrain(yk, "tokens_flat").reshape(e.top_k, t, d).sum(0)

    if "shared" in params:
        y = y + mlp(xf, params["shared"], "silu")
    return y.reshape(b, s, d), aux


def _expert_compute(xf, params, cfg: ArchConfig, n_local: int, e_offset,
                    gate_vals, expert_idx, capacity: int):
    """Dispatch xf (T, d) to `n_local` experts [e_offset, e_offset+n_local),
    run the expert GEMMs, and combine. Pure function of *local* expert
    weights — the shard_map EP body."""
    e = cfg.moe
    t, d = xf.shape
    cd = xf.dtype
    kt = t * e.top_k
    flat_e = expert_idx.T.reshape(-1) - e_offset                  # (k*T,)
    in_range = (flat_e >= 0) & (flat_e < n_local)
    flat_e = jnp.clip(flat_e, 0, n_local - 1)

    counts = jnp.zeros((n_local,), jnp.int32).at[flat_e].add(
        in_range.astype(jnp.int32))
    order = jnp.argsort(jnp.where(in_range, flat_e, n_local), stable=True)
    starts = jnp.cumsum(counts) - counts
    slot_sorted = jnp.arange(kt, dtype=jnp.int32) - starts[flat_e[order]]
    slot = jnp.zeros((kt,), jnp.int32).at[order].set(slot_sorted)
    keep = in_range & (slot < capacity)
    slot = jnp.clip(slot, 0, capacity - 1)

    buf_idx = flat_e * capacity + slot
    xk = jnp.tile(xf, (e.top_k, 1)) * keep[:, None].astype(cd)
    buffers = jnp.zeros((n_local * capacity, d), cd).at[buf_idx].add(xk)
    buffers = buffers.reshape(n_local, capacity, d)

    up = jnp.einsum("ecd,edf->ecf", buffers, params["w_up"].astype(cd))
    gatep = jnp.einsum("ecd,edf->ecf", buffers, params["w_gate"].astype(cd))
    h = jax.nn.silu(up) * gatep
    out = jnp.einsum("ecf,efd->ecd", h, params["w_down"].astype(cd))
    out = out.reshape(n_local * capacity, d)

    yk = out[buf_idx] * (keep.astype(cd)
                         * gate_vals.T.reshape(-1).astype(cd))[:, None]
    return yk.reshape(e.top_k, t, d).sum(0), counts


def _moe_mlp_ep_shardmap(x, params, cfg: ArchConfig, mesh, axis: str):
    """Expert-parallel MoE: shard_map manual over `axis` (model), auto over
    the data axes. Router + top-k run replicated per model shard; each
    shard computes only its local experts; partial y is psum'd."""
    from jax.sharding import PartitionSpec as P

    e = cfg.moe
    b, s, d = x.shape
    t = b * s
    cd = x.dtype
    ep = mesh.shape[axis]
    assert e.n_experts % ep == 0, f"experts {e.n_experts} % ep {ep}"
    n_local = e.n_experts // ep
    capacity = max(4, int(math.ceil(t * e.top_k * e.capacity_factor
                                    / e.n_experts)))

    def body(xf32, router, w_up, w_gate, w_down):
        idx = jax.lax.axis_index(axis)
        # xf enters in fp32: its cotangent is psum'd over the manual axis
        # in the backward pass, and XLA CPU's AllReducePromotion crashes
        # on bf16 all-reduce (TPU would take bf16 fine).
        xf = xf32.astype(cd)
        logits = (xf @ router.astype(cd)).astype(jnp.float32)
        probs = jax.nn.softmax(logits, axis=-1)
        gate_vals, expert_idx = jax.lax.top_k(probs, e.top_k)
        gate_vals = gate_vals / jnp.maximum(
            gate_vals.sum(-1, keepdims=True), 1e-9)
        lp = {"w_up": w_up, "w_gate": w_gate, "w_down": w_down}
        y, counts = _expert_compute(xf, lp, cfg, n_local, idx * n_local,
                                    gate_vals, expert_idx, capacity)
        # fp32 collectives only: XLA CPU's AllReducePromotion pass crashes
        # on bf16/int all-reduce at large device counts (fine on TPU).
        y = jax.lax.psum(y.astype(jnp.float32), axis).astype(cd)
        # aux loss: local slice of importance x local counts, psum'd
        me = probs.mean(0)                                 # (E,) per shard
        me_local = jax.lax.dynamic_slice(me, (idx * n_local,), (n_local,))
        partial = jnp.sum(me_local * counts.astype(jnp.float32))
        aux = e.n_experts * jax.lax.psum(partial, axis) / (t * e.top_k)
        return y, aux

    fn = jax.shard_map(body, mesh=mesh,
                       in_specs=(P(), P(), P(axis), P(axis), P(axis)),
                       out_specs=(P(), P()),
                       check_vma=False, axis_names=frozenset({axis}))

    xf = x.reshape(t, d)
    y, aux = fn(xf.astype(jnp.float32), params["router"], params["w_up"],
                params["w_gate"], params["w_down"])
    if "shared" in params:
        y = y + mlp(xf, params["shared"], "silu")
    return y.reshape(b, s, d), aux


def init_block(key, cfg: ArchConfig, dtype=jnp.float32):
    k1, k2 = jax.random.split(key)
    return {
        "ln1": init_rmsnorm(cfg.d_model, dtype),
        "attn": init_attention(k1, cfg.d_model, cfg.n_heads, cfg.n_kv,
                               cfg.head_dim, dtype),
        "ln2": init_rmsnorm(cfg.d_model, dtype),
        "moe": init_moe_mlp(k2, cfg, dtype),
    }


def init_lm(key, cfg: ArchConfig, dtype=jnp.float32):
    keys = jax.random.split(key, cfg.n_layers + 2)
    return {
        "embed": embed_init(keys[0], cfg.vocab, cfg.d_model, dtype),
        "lm_head": dense_init(keys[1], cfg.d_model, cfg.vocab, dtype),
        "blocks": _stack([init_block(keys[2 + i], cfg, dtype)
                          for i in range(cfg.n_layers)]),
        "ln_f": init_rmsnorm(cfg.d_model, dtype),
    }


def block_apply(carry, bp, cfg: ArchConfig, attn_fn=None):
    x, aux = carry
    x = x + gqa_attention(rms_norm(x, bp["ln1"]), bp["attn"], cfg.n_heads,
                          cfg.n_kv, rope=cfg.rope, rope_theta=cfg.rope_theta,
                          window=cfg.window, attn_fn=attn_fn)
    y, a = moe_mlp(rms_norm(x, bp["ln2"]), bp["moe"], cfg)
    return (x + y, aux + a)


def forward(params, cfg: ArchConfig, tokens, *, compute_dtype=jnp.bfloat16,
            remat: str = "full", attn_fn=None, unroll: bool = False):
    x = constrain(params["embed"].astype(compute_dtype)[tokens], "act")
    body = partial(block_apply, cfg=cfg, attn_fn=attn_fn)
    if remat == "full":
        body = jax.checkpoint(body)
    elif remat == "dots":
        body = jax.checkpoint(
            body, policy=jax.checkpoint_policies.checkpoint_dots_with_no_batch_dims)

    def step(carry, bp):
        x2, aux2 = body(carry, bp)
        return (constrain(x2, "act"), aux2), None

    (x, aux), _ = jax.lax.scan(step, (x, jnp.zeros((), jnp.float32)),
                               params["blocks"],
                               unroll=cfg.n_layers if unroll else 1)
    x = rms_norm(x, params["ln_f"])
    logits = constrain((x @ params["lm_head"].astype(compute_dtype))
                       .astype(jnp.float32), "logits")
    return logits, aux / cfg.n_layers


def loss_fn(params, cfg: ArchConfig, tokens, labels, aux_weight: float = 0.01,
            **kw):
    logits, aux = forward(params, cfg, tokens, **kw)
    return softmax_xent(logits, labels) + aux_weight * aux


def init_cache(cfg: ArchConfig, batch: int, s_max: int, dtype=jnp.bfloat16):
    shape = (cfg.n_layers, batch, s_max, cfg.n_kv, cfg.head_dim)
    return {"k": jnp.zeros(shape, dtype), "v": jnp.zeros(shape, dtype)}


def decode_step(params, cfg: ArchConfig, cache, tokens, pos, *,
                compute_dtype=jnp.bfloat16, unroll: bool = False):
    x = constrain(params["embed"].astype(compute_dtype)[tokens], "dec")

    def step(x, layer):
        bp, k_c, v_c = layer
        h = rms_norm(x, bp["ln1"])
        out, k_c, v_c = gqa_decode_attention(
            h, bp["attn"], cfg.n_heads, cfg.n_kv, k_c, v_c, pos,
            rope=cfg.rope, rope_theta=cfg.rope_theta)
        x = x + out
        y, _ = moe_mlp(rms_norm(x, bp["ln2"]), bp["moe"], cfg)
        return constrain(x + y, "dec"), (k_c, v_c)

    x, (k_new, v_new) = jax.lax.scan(step, x,
                                     (params["blocks"], cache["k"], cache["v"]),
                                     unroll=cfg.n_layers if unroll else 1)
    x = rms_norm(x, params["ln_f"])
    logits = (x[:, 0] @ params["lm_head"].astype(compute_dtype)).astype(jnp.float32)
    return logits, {"k": k_new, "v": v_new}

"""Cross-cell jax screening: the hyperband rung-0 relaxation for MANY
campaign cells in one jitted call.

:func:`repro.core.batch_eval.screen_rav_batch` vectorizes the screening
relaxation *within* one cell (one net x FPGA x precision instance). A
campaign, though, screens the same rung-0 budget for every cell, so the
natural batch axis is (cells x candidates): this module lifts the pure
array math of the NumPy screen to ``jax.numpy`` and ``vmap``s it across
cells, so a whole campaign's rung-0 triage is one XLA executable instead
of ``len(cells)`` NumPy passes.

The NumPy path stays the REFERENCE: the jax kernel mirrors its
expressions operation-for-operation in float64/int64 (``jax.enable_x64``
scoped to the call — never the global flag), and a bit-equivalence test
(``tests/test_jax_screen.py``) pins ``screen_cells`` to
``screen_rav_batch`` exactly. Per-cell tables of different lengths are
zero-padded to a common shape before stacking; the padding is never
gathered, because each lane's split point is clipped to its OWN cell's
``n_major`` and the padded ``seg_start`` repeats its terminal value.

    tables = [cell_tables(net, fpga, dw, ww) for ... each cell]
    stacked = stack_cells(tables)
    ips = screen_cells(stacked, positions)   # (cells, n, 5) -> (cells, n)
"""
from __future__ import annotations

from typing import Sequence

import jax
import jax.numpy as jnp
import numpy as np

from .hw_specs import FPGASpec, alpha_for
from .layer_arrays import pack_layers
from .netinfo import NetInfo

_compiled = None


def cell_tables(net: NetInfo, fpga: FPGASpec, dw: int = 16,
                ww: int = 16) -> dict:
    """One cell's screening inputs: the NumPy reference's cached
    prefix/suffix tables (:func:`repro.core.batch_eval._screen_tables`,
    shared — not recomputed) plus the hardware scalars its kernel
    closes over."""
    from .batch_eval import _screen_tables
    packed = pack_layers(net, dw, ww)
    t = _screen_tables(packed)
    return {
        "pipe_macs": t["pipe_macs"], "pipe_w": t["pipe_w"],
        "seg_start": t["seg_start"],
        "tail_macs": t["tail_macs"], "tail_w": t["tail_w"],
        "n_major": packed.n_major, "n_layers": packed.n_layers,
        "ifm0": float(packed.ifm0),
        "alpha": alpha_for(min(dw, ww)),
        "freq": float(fpga.freq),
        "bw_total": float(fpga.bw_gbps * 1e9),
        "dsp_usable": int(fpga.dsp_usable),
    }


def stack_cells(tables: Sequence[dict]) -> dict:
    """Pad per-cell tables to common lengths and stack to (cells, ...)
    arrays — the pytree one ``vmap`` lane reads per cell. Zero padding
    is sound: a lane's gathers are clipped to its own ``n_major`` /
    terminal ``seg_start``, so padded entries are never addressed."""
    lp = max(len(t["pipe_macs"]) for t in tables)
    lt = max(len(t["tail_macs"]) for t in tables)

    def padf(key: str, width: int) -> np.ndarray:
        out = np.zeros((len(tables), width), dtype=np.float64)
        for i, t in enumerate(tables):
            a = np.asarray(t[key], dtype=np.float64)
            out[i, :len(a)] = a
        return out

    seg = np.zeros((len(tables), lp), dtype=np.int64)
    for i, t in enumerate(tables):
        a = np.asarray(t["seg_start"], dtype=np.int64)
        seg[i, :len(a)] = a
        if len(a) < lp:
            seg[i, len(a):] = a[-1] if len(a) else 0
    return {
        "pipe_macs": padf("pipe_macs", lp), "pipe_w": padf("pipe_w", lp),
        "seg_start": seg,
        "tail_macs": padf("tail_macs", lt), "tail_w": padf("tail_w", lt),
        **{k: np.asarray([t[k] for t in tables], dtype=np.int64)
           for k in ("n_major", "n_layers", "alpha", "dsp_usable")},
        **{k: np.asarray([t[k] for t in tables], dtype=np.float64)
           for k in ("ifm0", "freq", "bw_total")},
    }


def _screen_one(tab: dict, arr):
    """One cell's screen in jax — a line-for-line port of the NumPy
    reference in :func:`repro.core.batch_eval.screen_rav_batch` (same
    dtypes, same rounding, same where-guards), kept textually parallel
    so the bit-equivalence test stays reviewable."""
    sp = jnp.clip(jnp.round(arr[:, 0]).astype(jnp.int64), 0, tab["n_major"])
    batch = jnp.maximum(1.0, jnp.round(arr[:, 1]))
    has_pipe = sp > 0
    dsp_p = jnp.where(has_pipe,
                      (tab["dsp_usable"] * arr[:, 2]).astype(jnp.int64), 0)
    bw_p = jnp.where(has_pipe, tab["bw_total"] * arr[:, 4], 0.0)

    pf_p = jnp.maximum(1, dsp_p * tab["alpha"] // 2).astype(jnp.float64)
    comp_p = batch * tab["pipe_macs"][sp] / (pf_p * tab["freq"])
    stream = tab["pipe_w"][sp] + batch * tab["ifm0"]
    mem_p = jnp.where(bw_p > 0, stream / bw_p,
                      jnp.where(stream > 0, jnp.inf, 0.0))
    lat_p = jnp.where(has_pipe, jnp.maximum(comp_p, mem_p), 0.0)

    start = tab["seg_start"][sp]
    tm, tw = tab["tail_macs"][start], tab["tail_w"][start]
    has_tail = start < tab["n_layers"]
    pf_g = jnp.maximum(
        1, jnp.maximum(0, tab["dsp_usable"] - dsp_p) * tab["alpha"] // 2
    ).astype(jnp.float64)
    comp_g = batch * tm / (pf_g * tab["freq"])
    bw_g = tab["bw_total"] - bw_p
    mem_g = jnp.where(bw_g > 0, tw / bw_g, jnp.where(tw > 0, jnp.inf, 0.0))
    lat_g = jnp.where(has_tail, jnp.maximum(comp_g, mem_g), 0.0)

    lat = jnp.maximum(lat_p, lat_g)
    return jnp.where((lat > 0) & jnp.isfinite(lat), batch / lat, 0.0)


def _kernel():
    global _compiled
    if _compiled is None:
        _compiled = jax.jit(jax.vmap(_screen_one, in_axes=(0, 0)))
    return _compiled


def screen_cells(stacked: dict, positions: np.ndarray) -> np.ndarray:
    """Screen (cells x candidates) in ONE jitted call.

    ``stacked`` is :func:`stack_cells` output; ``positions`` is the
    (cells, n, 5) rung-0 position block, one row of raw search-space
    positions per candidate. Returns (cells, n) relaxed img/s,
    bit-identical to running the NumPy ``screen_rav_batch`` per cell.
    float64 is enabled only inside this call (scoped ``enable_x64``),
    so the process-global jax config is untouched.
    """
    pos = np.asarray(positions, dtype=np.float64)
    if pos.ndim != 3 or pos.shape[2] != 5:
        raise ValueError(f"positions must be (cells, n, 5); "
                         f"got {pos.shape}")
    if pos.shape[0] != len(stacked["n_major"]):
        raise ValueError(
            f"positions batch {pos.shape[0]} != {len(stacked['n_major'])} "
            f"stacked cells")
    with jax.enable_x64(True):
        out = _kernel()(stacked, pos)
        return np.asarray(out)

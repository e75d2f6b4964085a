"""On-chip smoke run: the system's two hot paths, once each, on a TPU, at
real sizes, through the entry points a user calls.

    python chip_smoke.py              # one chip: phases dse, cnn, train
    python chip_smoke.py --chips 4    # four chips: phase pipeline only

Phases (one process owns the chip; no child process touches it):

- dse: the paper's Table-3 campaign (vgg16 x its 12 input cases x ku115
  x 16-bit x batch cap 1, hyperband) through the campaign CLI, once with
  ``--jax-screen`` (the device screen) and once on the NumPy screen with
  two host-only pool workers. Gate 1: the device screen's rung-0
  fitnesses against ``batch_eval.screen_rav_batch`` on the same blocks,
  and the promoted survivor sets. Gate 2: the two stores' records are
  equal, ``search_time_s`` aside.
- cnn: vgg16 at 224x224, bf16, batch 8, through ``hybrid_forward`` with
  a nonzero split point on the compiled Pallas conv kernel, against
  ``forward`` on ``lax.conv``.
- train: xlstm-350m at its published widths through ``Trainer`` for 3
  steps, the batch at train_4k's sequence length sized from the compiled
  step's ``memory_analysis()``.
- pipeline (``--chips 4``): starcoder2-3b's ``hybrid_lm_forward`` with an
  8-block head pipelined over a 4-stage mesh, against the same params
  and tokens on one device.

Each phase prints one JSON line with its compile seconds apart from its
run seconds. The last line is ``{"ok": true, "device": {...}}`` and is
printed only when every phase passed; finding no TPU is a failure.
"""
from __future__ import annotations

import argparse
import contextlib
import json
import math
import shutil
import sys
import time
import traceback
from pathlib import Path

ROOT = Path(__file__).resolve().parent
sys.path.insert(0, str(ROOT / "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from repro.launch.cache import enable_compilation_cache  # noqa: E402

SEED = 0


def _worker_backend(_) -> str:
    """Runs in a campaign pool worker: the backend JAX gives it."""
    return jax.default_backend()


def _rel_err(got, ref) -> dict:
    """Errors of ``got`` against ``ref``, relative to ref's scale."""
    got = np.asarray(got, np.float32)
    ref = np.asarray(ref, np.float32)
    diff = np.abs(got - ref)
    scale = float(np.abs(ref).max()) or 1.0
    return {"max_abs": float(diff.max()),
            "max_rel": float(diff.max()) / scale,
            "rel_l2": float(np.linalg.norm(got - ref)
                            / (np.linalg.norm(ref) or 1.0))}


def _timed(fn, *args):
    t0 = time.perf_counter()
    out = jax.block_until_ready(fn(*args))
    return out, time.perf_counter() - t0


def phase_dse(out: Path) -> dict:
    from repro.core.batch_eval import screen_rav_batch
    from repro.core.netinfo import INPUT_CASES
    from repro.core.search import hyperband_rung0, hyperband_survivors
    from repro.dse import cli
    from repro.dse.campaign import (cell_search, expand_cells, host_pool,
                                    prescreen_cells_jax)

    cells = expand_cells(["vgg16"], list(INPUT_CASES), ["ku115"], [16], [1])
    # gate 1: the device screen against the NumPy reference, same blocks
    t0 = time.perf_counter()
    prescreen_cells_jax(cells, base_seed=SEED)
    t_first = time.perf_counter() - t0
    t0 = time.perf_counter()
    fits = prescreen_cells_jax(cells, base_seed=SEED)
    t_warm = time.perf_counter() - t0
    max_rel, bit_equal, same_survivors = 0.0, 0, 0
    for c in cells:
        net, fpga, space, cfg = cell_search(c, base_seed=SEED,
                                            searcher="hyperband")
        block = hyperband_rung0(space, cfg)
        ref = screen_rav_batch(net, fpga, block, c.precision, c.precision)
        got = fits[c.key]
        max_rel = max(max_rel, float(np.max(
            np.abs(got - ref) / np.maximum(np.abs(ref), 1e-300))))
        bit_equal += bool(np.array_equal(got, ref))
        same_survivors += bool(np.array_equal(
            hyperband_survivors(space, cfg, block, got),
            hyperband_survivors(space, cfg, block, ref)))

    # gate 2: campaign records, device screen vs NumPy screen
    inputs = ",".join(f"{h}x{w}" for h, w in INPUT_CASES)
    argv = ["--nets", "vgg16", "--inputs", inputs, "--fpgas", "ku115",
            "--precisions", "16", "--batch-caps", "1",
            "--searcher", "hyperband", "--seed", str(SEED), "-q"]
    shutil.rmtree(out / "dse", ignore_errors=True)
    (out / "dse").mkdir(parents=True)
    with open(out / "dse" / "campaigns.log", "w") as log, \
            contextlib.redirect_stdout(log):
        t0 = time.perf_counter()
        jaxed = cli.main(argv + ["--jax-screen",
                                 "--store", str(out / "dse" / "jax.jsonl")])
        t_jax = time.perf_counter() - t0
        t0 = time.perf_counter()
        plain = cli.main(argv + ["--workers", "2",
                                 "--store", str(out / "dse" / "numpy.jsonl")])
        t_plain = time.perf_counter() - t0

    def strip(rec):
        return {k: v for k, v in rec.items() if k != "search_time_s"}

    records_equal = (len(jaxed.records) == len(plain.records) == len(cells)
                     and all(strip(a) == strip(b) for a, b in
                             zip(jaxed.records, plain.records)))
    # a campaign pool worker that touches JAX gets the CPU, never the chip
    with host_pool(2) as pool:
        worker_backends = sorted(set(
            pool.map(_worker_backend, range(2), timeout=300)))
    ok = (same_survivors == len(cells) and records_equal
          and cli.exit_code(jaxed) == 0 and cli.exit_code(plain) == 0
          and worker_backends == ["cpu"])
    return {"ok": ok, "cells": len(cells), "screen": [len(cells), 4096],
            "compile_s": t_first - t_warm, "screen_run_s": t_warm,
            "screen_max_rel_err": max_rel,
            "screen_bit_equal_cells": bit_equal,
            "survivor_sets_identical": same_survivors,
            "records_equal": records_equal,
            "campaign_jax_s": t_jax, "campaign_numpy_workers2_s": t_plain,
            "worker_backends": worker_backends}


def phase_cnn(out: Path, hw: int = 224, batch: int = 8) -> dict:
    from repro.core.netinfo import vgg16
    from repro.models.cnn import HybridPlan, forward, hybrid_forward, init_vgg

    net = vgg16(hw)
    plan = HybridPlan(sp=4, n_micro=1)
    params = init_vgg(jax.random.key(SEED), net, dtype=jnp.bfloat16)
    x = jax.random.normal(jax.random.key(SEED + 1), (batch, 3, hw, hw),
                          jnp.bfloat16)
    t0 = time.perf_counter()
    hybrid = jax.jit(lambda p, x: hybrid_forward(
        p, net, x, plan, use_pallas=True)).lower(params, x).compile()
    ref = jax.jit(lambda p, x: forward(p, net, x)).lower(params, x).compile()
    t_compile = time.perf_counter() - t0
    kernel_compiled = "tpu_custom_call" in hybrid.as_text()
    y, t_hybrid = _timed(hybrid, params, x)
    y_ref, t_ref = _timed(ref, params, x)
    err = _rel_err(y, y_ref)
    # bf16 activations are rounded after each of the 13 convs (2^-9
    # relative each); both paths accumulate in fp32, so only summation
    # order differs. A kernel that dropped to a narrower format (or
    # skipped a tap) would miss this by orders of magnitude.
    ok = (kernel_compiled and y.shape == y_ref.shape
          and bool(jnp.isfinite(y.astype(jnp.float32)).all())
          and err["rel_l2"] <= 1e-2 and err["max_rel"] <= 5e-2)
    return {"ok": ok, "net": net.name, "batch": batch, "dtype": "bfloat16",
            "sp": plan.sp, "out_shape": list(y.shape),
            "kernel_compiled": kernel_compiled, **err,
            "compile_s": t_compile, "hybrid_pallas_run_s": t_hybrid,
            "lax_conv_run_s": t_ref}


def phase_train(out: Path, cfg=None, seq: int | None = None,
                batches=(8, 6, 4, 2, 1), budget: float | None = None) -> dict:
    from repro.configs import SHAPES, get_config
    from repro.configs.base import ShapeSpec
    from repro.train.trainer import TrainConfig, Trainer

    cfg = cfg or get_config("xlstm-350m")
    seq = seq or SHAPES["train_4k"].seq_len
    dev = jax.devices()[0]
    budget = budget or 0.9 * dev.memory_stats()["bytes_limit"]
    ckpt = out / "train_ckpt"
    shutil.rmtree(ckpt, ignore_errors=True)
    tcfg = TrainConfig(steps=3, ckpt_every=3, ckpt_dir=str(ckpt),
                       log_every=1, seed=SEED)
    tried, compile_s, trainer = [], 0.0, None
    for batch in batches:   # largest first; keep the first fit
        tr = Trainer(cfg, ShapeSpec("chip_train", "train", seq, batch), tcfg)
        t0 = time.perf_counter()
        try:
            m = tr.compile().memory_analysis()
        except jax.errors.JaxRuntimeError as e:   # refused: does not fit
            compile_s += time.perf_counter() - t0
            tried.append({"batch": batch, "refused": str(e)[:200]})
            continue
        compile_s += time.perf_counter() - t0
        need = (m.argument_size_in_bytes + m.output_size_in_bytes
                - m.alias_size_in_bytes + m.temp_size_in_bytes)
        tried.append({"batch": batch, "bytes": need})
        if need <= budget:
            trainer = tr
            break
    if trainer is None:
        return {"ok": False, "tried": tried, "compile_s": compile_s}
    t0 = time.perf_counter()
    trainer.run()
    t_run = time.perf_counter() - t0
    losses = [s["loss"] for s in trainer.stats]
    ok = len(losses) == 3 and all(math.isfinite(v) for v in losses)
    return {"ok": ok, "arch": cfg.name, "params": cfg.param_count(),
            "batch": trainer.shape.global_batch, "seq": seq,
            "reduced": [f"batch: train_4k's global batch "
                        f"{SHAPES['train_4k'].global_batch} -> "
                        f"{trainer.shape.global_batch}, the largest of "
                        f"{[t['batch'] for t in tried]} whose compiled step "
                        f"fits 0.9 x this chip's bytes_limit"],
            "sizing": tried, "losses": losses,
            "step_s": [s["time_s"] for s in trainer.stats],
            "peak_bytes_in_use": (dev.memory_stats() or {}).get(
                "peak_bytes_in_use"),
            "memory_stats": dev.memory_stats(),
            "compile_s": compile_s, "run_s": t_run}


def phase_pipeline(out: Path, cfg=None, batch: int = 8,
                   seq: int = 1024) -> dict:
    from jax.sharding import NamedSharding, PartitionSpec as P

    from repro.configs import get_config
    from repro.launch.mesh import make_mesh
    from repro.models import api
    from repro.train.hybrid import HybridLMPlan, hybrid_lm_forward, place_params

    cfg = cfg or get_config("starcoder2-3b")
    plan = HybridLMPlan(sp=8, n_stages=4, n_micro=4)
    devs = jax.devices()[:plan.n_stages]
    mesh = make_mesh((plan.n_stages,), ("stage",), devices=devs)
    t0 = time.perf_counter()
    params = jax.block_until_ready(jax.jit(lambda: api.init_params(
        jax.random.key(SEED), cfg, jnp.bfloat16))())
    t_init = time.perf_counter() - t0
    tokens = jax.random.randint(jax.random.key(SEED + 1), (batch, seq), 0,
                                cfg.vocab)

    t0 = time.perf_counter()
    one = jax.jit(lambda p, t: hybrid_lm_forward(p, cfg, t, plan)).lower(
        params, tokens).compile()
    compile_s = time.perf_counter() - t0
    ref, t_one = _timed(one, params, tokens)
    ref = np.asarray(ref)

    t0 = time.perf_counter()
    placed = jax.block_until_ready(place_params(params, plan, mesh))
    t_place = time.perf_counter() - t0
    del params
    tokens = jax.device_put(tokens, NamedSharding(mesh, P()))
    # stage i's blocks live on the mesh's device i, not all on device 0
    stage_devices = [d.id for d in mesh.devices.flat]
    stage_on_own_device = len(set(stage_devices)) == plan.n_stages and all(
        shard.device.id == stage_devices[shard.index[0].start]
        for leaf in jax.tree.leaves(placed["head"])
        for shard in leaf.addressable_shards)
    t0 = time.perf_counter()
    pipe = jax.jit(lambda p, t: hybrid_lm_forward(p, cfg, t, plan, mesh)
                   ).lower(placed, tokens).compile()
    compile_s += time.perf_counter() - t0
    has_permute = "collective-permute" in pipe.as_text()
    got, t_pipe = _timed(pipe, placed, tokens)
    mem = [(d.memory_stats() or {}).get("bytes_in_use") for d in devs]
    err = _rel_err(np.asarray(got), ref)
    # same bf16 math; microbatching changes only matmul shapes and so
    # summation order, so logits agree to a few bf16 ulps
    ok = (stage_on_own_device and has_permute and got.shape == ref.shape
          and bool(np.isfinite(ref).all())
          and err["rel_l2"] <= 1e-2 and err["max_rel"] <= 5e-2)
    return {"ok": ok, "arch": cfg.name, "params_dtype": "bfloat16",
            "plan": {"sp": plan.sp, "n_stages": plan.n_stages,
                     "n_micro": plan.n_micro},
            "batch": batch, "seq": seq, "logits_err": err,
            "stage_device_ids": stage_devices,
            "stage_params_on_own_device": stage_on_own_device,
            "hlo_has_collective_permute": has_permute,
            "bytes_in_use_per_device": mem,
            "compile_s": compile_s, "init_s": t_init, "place_s": t_place,
            "one_device_run_s": t_one, "pipelined_run_s": t_pipe}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    ap.add_argument("--out", default=str(ROOT / "results" / "chip_smoke"),
                    help="campaign stores and checkpoints go here")
    args = ap.parse_args(argv)

    devices = jax.devices()
    if devices[0].platform != "tpu" or len(devices) < args.chips:
        print(f"chip_smoke: needs {args.chips} TPU chip(s); JAX found "
              f"{len(devices)} {devices[0].platform} device(s)",
              file=sys.stderr)
        return 2
    cache = enable_compilation_cache()
    out = Path(args.out)
    out.mkdir(parents=True, exist_ok=True)
    print(json.dumps({"phase": "setup", "jax": jax.__version__,
                      "compilation_cache": cache}), flush=True)

    phases = ([("pipeline", phase_pipeline)] if args.chips == 4 else
              [("dse", phase_dse), ("cnn", phase_cnn),
               ("train", phase_train)])
    all_ok = True
    for name, fn in phases:
        t0 = time.perf_counter()
        try:
            res = fn(out)
        except Exception as e:  # noqa: BLE001 - report, go on, fail at end
            traceback.print_exc()
            res = {"ok": False, "error": f"{type(e).__name__}: {e}"[:2000]}
        all_ok = all_ok and res["ok"]
        print(json.dumps({"phase": name, **res,
                          "phase_s": time.perf_counter() - t0}), flush=True)
    if not all_ok:
        return 1
    d = devices[0]
    print(json.dumps({"ok": True, "device": {
        "platform": d.platform, "kind": d.device_kind,
        "count": len(devices)}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())

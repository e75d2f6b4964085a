"""Benchmark harness: one function per paper table/figure.

Each benchmark returns rows ``{name, us_per_call, derived}`` where
``derived`` holds the headline metric(s) the paper's table/figure reports;
``main`` prints one CSV line per row:  name,us_per_call,derived.
``--json out.json`` additionally dumps the rows as structured JSON so
campaign/bench results can feed the ``BENCH_*.json`` perf trajectory.

    PYTHONPATH=src python -m benchmarks.run [--only fig11,table3] \\
        [--json out.json]

CI runs the cheap analytic subset and gates on ``benchmarks/compare.py``
against the committed ``benchmarks/baseline.json`` (see that module).
"""
from __future__ import annotations

import argparse
import json
import statistics
import time

from repro.core import (KU115, RAV, ZCU102, PSOConfig, dnnbuilder_design,
                        explore, generic_only_design)
from repro.core.local_opt import dpu_proxy_design
from repro.core.netinfo import INPUT_CASES, TABLE1_NETS, vgg16

from . import paper_data as paper

_CFG = PSOConfig(population=20, iterations=30, seed=1)


def _timed(fn, *a, **kw):
    t0 = time.perf_counter()
    out = fn(*a, **kw)
    return out, (time.perf_counter() - t0) * 1e6


def bench_fig1_ctc() -> list[dict]:
    """Fig. 1: CTC medians of VGG16 over the 12 input sizes."""
    rows = []
    for h, w in INPUT_CASES:
        net, us = _timed(vgg16, h, w)
        med = statistics.median(net.ctc_list())
        rows.append({"name": f"fig1_ctc_{h}x{w}", "us_per_call": us,
                     "derived": f"median_ctc={med:.0f}"})
    m32 = statistics.median(vgg16(32).ctc_list())
    m512 = statistics.median(vgg16(512).ctc_list())
    rows.append({"name": "fig1_ctc_scaling_32_to_512", "us_per_call": 0.0,
                 "derived": f"ratio={m512 / m32:.1f}x(paper~256x)"})
    return rows


def bench_table1_variance() -> list[dict]:
    """Table 1: V1/V2 CTC variance ratio per network."""
    rows = []
    for name, fn in TABLE1_NETS.items():
        net, us = _timed(fn)
        r = net.half_variance_ratio()
        rows.append({"name": f"table1_{name}", "us_per_call": us,
                     "derived": f"v1_over_v2={r:.1f}"
                                f"(paper={paper.TABLE1[name]})"})
    return rows


def bench_fig9_dsp_efficiency() -> list[dict]:
    """Fig. 9: DSP efficiency across the input cases; DNNExplorer vs the
    analytical paradigm-A baselines (HybridDNN / DPU proxies)."""
    rows = []
    for h, w in INPUT_CASES[:9]:
        net = vgg16(h, w)
        res, us = _timed(explore, net, KU115, cfg=_CFG)
        gen = generic_only_design(net, KU115)
        dpu = dpu_proxy_design(net, ZCU102)
        rows.append({
            "name": f"fig9_eff_{h}x{w}", "us_per_call": us,
            "derived": (f"explorer={res.design.dsp_eff:.3f};"
                        f"hybriddnn_proxy={gen.dsp_eff:.3f};"
                        f"dpu_proxy={dpu.dsp_eff:.3f}")})
    return rows


def bench_fig10_throughput() -> list[dict]:
    """Fig. 10 / Table 3: GOP/s on KU115 across the 12 input sizes."""
    rows = []
    for h, w in INPUT_CASES:
        net = vgg16(h, w)
        res, us = _timed(explore, net, KU115, cfg=_CFG)
        d = res.design
        pgops = paper.TABLE3[(h, w)][0]
        rows.append({
            "name": f"fig10_gops_{h}x{w}", "us_per_call": us,
            "derived": (f"gops={d.gops:.1f}(paper={pgops});"
                        f"sp={d.rav.sp};eff={d.dsp_eff:.3f};"
                        f"search_s={res.search_time_s:.2f}")})
    return rows


def bench_fig11_deeper() -> list[dict]:
    """Fig. 11: throughput vs depth (13/18/28/38-layer VGG-like, 224x224).
    Reports our DSE result, our analytical DNNBuilder baseline, and the
    ratio against the paper's *measured* DNNBuilder curve."""
    rows = []
    base = None
    for extra, layers in [(0, 13), (1, 18), (3, 28), (5, 38)]:
        net = vgg16(224, extra_per_group=extra)
        res, us = _timed(explore, net, KU115, cfg=_CFG)
        ours = res.design.gops
        builder_model = dnnbuilder_design(net, KU115).gops
        if base is None:
            base = ours
        builder_paper = base * paper.FIG11_DNNBUILDER_REL[layers]
        rows.append({
            "name": f"fig11_{layers}layers", "us_per_call": us,
            "derived": (f"explorer={ours:.1f};builder_model={builder_model:.1f};"
                        f"builder_paper={builder_paper:.1f};"
                        f"ratio_vs_paper_builder={ours / builder_paper:.2f}x")})
    return rows


def bench_table3_rav() -> list[dict]:
    """Table 3: full RAV + search-time reproduction at batch=1."""
    rows = []
    for h, w in INPUT_CASES:
        net = vgg16(h, w)
        res, us = _timed(explore, net, KU115, cfg=_CFG)
        d = res.design
        p_gops, p_ips, p_sp, p_dsp, p_eff, _ = paper.TABLE3[(h, w)]
        rows.append({
            "name": f"table3_{h}x{w}", "us_per_call": us,
            "derived": (f"gops={d.gops:.1f}/{p_gops};"
                        f"img_s={d.throughput_ips:.1f}/{p_ips};"
                        f"sp={d.rav.sp}/{p_sp};dsp={d.dsp_used}/{p_dsp};"
                        f"eff={d.dsp_eff:.3f}/{p_eff};"
                        f"evals={res.pso.evaluations}")})
    return rows


def bench_table4_batch() -> list[dict]:
    """Table 4: batch-size exploration for the small-input cases."""
    rows = []
    for (h, w), (p_batch, p_gops) in paper.TABLE4.items():
        net = vgg16(h, w)
        res, us = _timed(explore, net, KU115, batch_max=16,
                         cfg=PSOConfig(population=24, iterations=40, seed=1))
        d = res.design
        rows.append({
            "name": f"table4_{h}x{w}", "us_per_call": us,
            "derived": (f"gops={d.gops:.1f}(paper={p_gops});"
                        f"batch={d.rav.batch}(paper={p_batch})")})
    return rows


def bench_roofline() -> list[dict]:
    """§Roofline: summarized per-cell terms from the dry-run artifacts
    (full table in EXPERIMENTS.md; see benchmarks/roofline.py)."""
    from .roofline import load_cells, roofline_rows
    cells = load_cells("results/dryrun")
    rows = []
    for r in roofline_rows(cells):
        rows.append({
            "name": f"roofline_{r['arch']}_{r['shape']}_{r['mesh']}",
            "us_per_call": 0.0,
            "derived": (f"t_comp={r['t_compute']:.2e};t_mem={r['t_memory']:.2e};"
                        f"t_coll={r['t_collective']:.2e};bound={r['bound']};"
                        f"mfu_frac={r['roofline_frac']:.3f}")})
    if not rows:
        rows.append({"name": "roofline", "us_per_call": 0.0,
                     "derived": "no dryrun artifacts (run repro.launch.dryrun)"})
    return rows


def bench_dse_campaign() -> list[dict]:
    """repro.dse: a small (net x fpga x precision) campaign — wall time,
    memoized re-run time, and frontier size."""
    import tempfile

    from repro.dse import run_campaign
    from repro.dse.campaign import expand_cells

    cells = expand_cells(["vgg16"], [(64, 64), (224, 224)],
                         ["ku115", "zcu102"], [16, 8], [1])
    with tempfile.TemporaryDirectory() as td:
        store = f"{td}/bench.jsonl"
        rep, us = _timed(run_campaign, cells, store, population=20,
                         iterations=30)
        rerun, us2 = _timed(run_campaign, cells, store, population=20,
                            iterations=30)
    return [{
        "name": f"dse_campaign_{len(cells)}cells", "us_per_call": us,
        "derived": (f"evals={rep.new_evaluations};"
                    f"frontier={len(rep.frontier())};"
                    f"resume_us={us2:.0f};"
                    f"resume_evals={rerun.new_evaluations}")}]


def bench_fpga_campaign() -> list[dict]:
    """repro.dse fpga backend hot path: one campaign cell's PSO through the
    batched array-kernel engine vs the scalar reference path, same seed and
    trajectory, measured in the same run — plus an ``evaluate_rav_batch``
    microbench over a fixed random population."""
    import numpy as np

    from repro.core.batch_eval import evaluate_rav_batch
    from repro.core.local_opt import evaluate_rav
    from repro.core.pso import optimize

    net = vgg16(224)
    sp_max = len(net.major_layers)

    def batched_hook(ravs):
        return [d.fitness for d in evaluate_rav_batch(net, KU115, ravs)]

    def scalar_hook(ravs):
        return [evaluate_rav(net, KU115, r).fitness for r in ravs]

    # Warm both paths to campaign steady state (numpy.random import, packed
    # layer tables, per-split cycle caches) before timing anything.
    optimize(sp_max=sp_max, batch_max=1,
             cfg=PSOConfig(population=6, iterations=2, seed=0),
             batch_fitness_fn=batched_hook)
    scalar_hook([RAV(sp_max // 2, 1, 0.5, 0.5, 0.5)])

    res_b, us_b = _timed(optimize, sp_max=sp_max, batch_max=1, cfg=_CFG,
                         batch_fitness_fn=batched_hook)
    res_s, us_s = _timed(optimize, sp_max=sp_max, batch_max=1, cfg=_CFG,
                         batch_fitness_fn=scalar_hook)
    rows = [{
        "name": "campaign_fpga_vgg16_224_ku115", "us_per_call": us_b,
        "derived": (f"scalar_us={us_s:.0f};speedup={us_s / us_b:.1f}x;"
                    f"evals={res_b.evaluations};"
                    f"same_best={res_b.best_rav == res_s.best_rav};"
                    f"gops_fitness={res_b.best_fitness:.1f}")}]

    rng = np.random.default_rng(0)
    ravs = [RAV(int(rng.integers(0, sp_max + 1)), int(rng.integers(1, 5)),
                float(rng.uniform(0.05, 0.95)), float(rng.uniform(0.05, 0.95)),
                float(rng.uniform(0.05, 0.95))) for _ in range(128)]
    out_b, us_bt = _timed(evaluate_rav_batch, net, KU115, ravs)
    out_s, us_sc = _timed(lambda: [evaluate_rav(net, KU115, r) for r in ravs])
    agree = all(a.dsp_used == b.dsp_used and a.feasible == b.feasible
                for a, b in zip(out_s, out_b))
    rows.append({
        "name": "evaluate_rav_batch_128", "us_per_call": us_bt,
        "derived": (f"scalar_us={us_sc:.0f};speedup={us_sc / us_bt:.1f}x;"
                    f"n=128;agree={agree}")})

    # telemetry overhead: the same tiny campaign untraced vs --trace
    # (spans + sidecar merge); untraced is the gated
    # configuration, traced shows what --trace costs on top
    import tempfile

    from repro.dse import run_campaign
    from repro.dse.campaign import expand_cells
    from repro.obs import load_events

    cells = expand_cells(["vgg16"], [(64, 64)], ["zc706"], [16, 8], [1])
    with tempfile.TemporaryDirectory() as td:
        _, us_plain = _timed(run_campaign, cells, f"{td}/plain.jsonl",
                             population=6, iterations=4)
        traced, us_tr = _timed(run_campaign, cells, f"{td}/traced.jsonl",
                               population=6, iterations=4, trace=True)
        n_events = len(load_events(traced.events_path))
    rows.append({
        "name": "campaign_fpga_traced", "us_per_call": us_tr,
        "derived": (f"untraced_us={us_plain:.0f};"
                    f"overhead={us_tr / us_plain:.2f}x;"
                    f"events={n_events}")})

    # fault-path overhead: the same cell evaluations through the
    # resilience layer (execute_cell retry accounting + the UNARMED
    # injection harness, i.e. the shipped configuration) vs a bare
    # run_cell loop — gates the claim that an idle harness + retry
    # bookkeeping costs ~nothing
    from repro.dse.backends import run_cell_by_backend
    from repro.dse.resilience import RetryPolicy, execute_cell

    def attempt_fn(cell, attempt):
        return run_cell_by_backend("fpga", cell, 0, 6, 4, None, None,
                                   attempt=attempt)

    def bare_loop():
        return [run_cell_by_backend("fpga", c, 0, 6, 4, None, None)
                for c in cells]

    def resilient_loop():
        policy = RetryPolicy()
        return [execute_cell(c, attempt_fn, policy) for c in cells]

    resilient_loop()                       # warm both paths identically
    bare_loop()
    _, us_res = _timed(resilient_loop)
    _, us_bare = _timed(bare_loop)
    rows.append({
        "name": "campaign_fpga_faultpath", "us_per_call": us_res,
        "derived": (f"bare_us={us_bare:.0f};"
                    f"overhead={us_res / us_bare:.2f}x;"
                    f"harness=inert")})
    return rows


def bench_searcher_engines() -> list[dict]:
    """repro.core.search: every registered engine on the Table-3 flagship
    cell (vgg16/224/ku115), same population/iteration budget. Headline:
    hyperband must reach best-fitness parity with pure PSO at equal or
    lower wall-clock while triaging a ~100x larger candidate pool through
    the screening relaxation (``screened`` counts those candidates)."""
    from repro.core.search import searcher_names

    net = vgg16(224)
    # warm the packed-table / per-split cycle caches once so engine rows
    # measure search, not first-touch model building
    explore(net, KU115, cfg=PSOConfig(population=6, iterations=2, seed=1))

    rows, by_engine = [], {}
    for name in searcher_names():
        res, us = _timed(explore, net, KU115, cfg=_CFG, searcher=name)
        by_engine[name] = (res, us)
        p = res.pso
        rows.append({
            "name": f"searcher_{name}_vgg16_224_ku115", "us_per_call": us,
            "derived": (f"fitness={p.best_fitness:.3f};"
                        f"evals={p.evaluations};screened={p.screened};"
                        f"stop={p.stop_reason}")})

    (res_h, us_h), (res_p, us_p) = by_engine["hyperband"], by_engine["pso"]
    pool = res_h.pso.screened + res_h.pso.evaluations
    rows.append({
        "name": "campaign_fpga_hyperband", "us_per_call": us_h,
        "derived": (f"pso_us={us_p:.0f};wall_ratio={us_h / us_p:.2f}x;"
                    f"fitness={res_h.pso.best_fitness:.3f};"
                    f"pso_fitness={res_p.pso.best_fitness:.3f};"
                    f"parity={res_h.pso.best_fitness >= res_p.pso.best_fitness};"
                    f"screened={res_h.pso.screened};"
                    f"space_x={pool / max(1, res_p.pso.evaluations):.0f}x")})
    return rows


def bench_tpu_campaign() -> list[dict]:
    """repro.dse tpu backend: a small (arch x shape x chips x remat x mb)
    campaign — wall time, memoized re-run time, and frontier size/spread."""
    import tempfile

    from repro.dse import run_campaign
    from repro.dse.backends import get_backend

    be = get_backend("tpu")
    cells = be.expand_cells(archs=["starcoder2-3b", "xlstm-350m"],
                            shapes=["train_4k", "decode_32k"],
                            chips=[8, 16, 32], remats=("full", "none"),
                            microbatches=(1, 2))
    with tempfile.TemporaryDirectory() as td:
        store = f"{td}/bench_tpu.jsonl"
        rep, us = _timed(run_campaign, cells, store, backend="tpu")
        rerun, us2 = _timed(run_campaign, cells, store, backend="tpu")
    return [{
        "name": f"dse_campaign_tpu_{len(cells)}cells", "us_per_call": us,
        "derived": (f"evals={rep.new_evaluations};"
                    f"frontier={len(rep.frontier())};"
                    f"frontier_k4={len(rep.frontier(k=4))};"
                    f"resume_us={us2:.0f};"
                    f"resume_evals={rerun.new_evaluations}")}]


def bench_cuda_campaign() -> list[dict]:
    """repro.dse cuda backend: a small (arch x shape x GPU part x count)
    campaign — wall time, memoized re-run time, and frontier size/spread."""
    import tempfile

    from repro.dse import run_campaign
    from repro.dse.backends import get_backend

    be = get_backend("cuda")
    cells = be.expand_cells(archs=["starcoder2-3b", "xlstm-350m"],
                            shapes=["train_4k", "decode_32k"],
                            gpus=[8, 16, 32],
                            gpu_types=("a100-80g", "h100"),
                            remats=("full", "none"), microbatches=(1, 2))
    with tempfile.TemporaryDirectory() as td:
        store = f"{td}/bench_cuda.jsonl"
        rep, us = _timed(run_campaign, cells, store, backend="cuda")
        rerun, us2 = _timed(run_campaign, cells, store, backend="cuda")
    return [{
        "name": f"dse_campaign_cuda_{len(cells)}cells", "us_per_call": us,
        "derived": (f"evals={rep.new_evaluations};"
                    f"frontier={len(rep.frontier())};"
                    f"frontier_k4={len(rep.frontier(k=4))};"
                    f"resume_us={us2:.0f};"
                    f"resume_evals={rerun.new_evaluations}")}]


def bench_placement() -> list[dict]:
    """repro.dse.placement: tpu+cuda campaigns pooled into one store, then
    a budgeted multi-workload placement — campaign wall time, solve time
    for both solvers, and whether greedy matched the exact optimum."""
    import tempfile

    from repro.core.hw_specs import CostEnvelope
    from repro.dse import run_campaign
    from repro.dse.backends import get_backend
    from repro.dse.placement import place, pooled_records
    from repro.dse.store import open_store

    archs = ["starcoder2-3b", "xlstm-350m"]
    shapes = ["train_4k", "decode_32k"]
    with tempfile.TemporaryDirectory() as td:
        store = f"{td}/bench_place.jsonl"
        tpu_cells = get_backend("tpu").expand_cells(
            archs=archs, shapes=shapes, chips=[8, 16],
            remats=("full",), microbatches=(1,))
        cuda_cells = get_backend("cuda").expand_cells(
            archs=archs, shapes=shapes, gpus=[8, 16],
            gpu_types=("a100-80g", "h100"), remats=("full",),
            microbatches=(1,))
        _, us_tpu = _timed(run_campaign, tpu_cells, store, backend="tpu")
        _, us_cuda = _timed(run_campaign, cuda_cells, store, backend="cuda")
        records = pooled_records([open_store(store)])
        workloads = [f"{a}/{s}" for a in archs for s in shapes]
        budget = CostEnvelope(usd_per_hour=150.0, watts=40000.0)
        exact, us_exact = _timed(place, workloads, records, budget,
                                 solver="exact")
        greedy, us_greedy = _timed(place, workloads, records, budget,
                                   solver="greedy")
        agree = [a.candidate.cell_key for a in exact.assignments] == \
            [a.candidate.cell_key for a in greedy.assignments]
    return [{
        "name": f"dse_placement_{len(workloads)}workloads",
        "us_per_call": us_tpu + us_cuda + us_exact,
        "derived": (f"cells={len(tpu_cells) + len(cuda_cells)};"
                    f"value={exact.total_value:.1f};"
                    f"usd={exact.total_usd:.2f};"
                    f"exact_nodes={exact.explored};"
                    f"solve_us={us_exact:.0f};"
                    f"greedy_us={us_greedy:.0f};"
                    f"greedy_matches_exact={agree}")}]


def bench_campaign_100k() -> list[dict]:
    """Store v2 + FrontierIndex at report scale: 100k synthetic records
    bulk-written to a sharded store, then ONE streaming pass (offset
    index + iter_records + incremental frontier) timed against the full
    non-dominated re-sort the report historically ran per render. The
    re-sort is O(n^2) python — measured on a subsample and extrapolated
    quadratically (running it straight at 100k would take hours)."""
    import tempfile

    import numpy as np

    from repro.dse.frontier import FrontierIndex
    from repro.dse.pareto import non_dominated
    from repro.dse.store import open_store, shard_name, sharded_dir_for

    n, sub = 100_000, 800
    rng = np.random.default_rng(0)
    vals = rng.random((n, 3))
    with tempfile.TemporaryDirectory() as td:
        store_path = f"{td}/bench100k.d"
        d = sharded_dir_for(store_path)
        d.mkdir(parents=True)
        (d / "manifest.json").write_text(
            json.dumps({"store_format": 2}) + "\n")
        # bulk append, the shape a campaign worker's shard ends up in
        # (puts go through the same append path, plus fsync per record)
        with open(d / shard_name(0), "w") as f:
            for i in range(n):
                f.write(json.dumps(
                    {"cell_key": f"c{i}",
                     "objectives": {"a": vals[i, 0], "b": vals[i, 1],
                                    "c": vals[i, 2], "feasible": True}},
                    sort_keys=True) + "\n")

        def streaming_pass():
            s = open_store(store_path)
            fi = FrontierIndex()
            for rec in s.iter_records():
                o = rec["objectives"]
                fi.insert(rec["cell_key"], (o["a"], o["b"], o["c"]))
            return fi

        fi, us_stream = _timed(streaming_pass)
    sub_vecs = [tuple(v) for v in vals[:sub]]
    _, us_sub = _timed(non_dominated, sub_vecs)
    us_resort_est = us_sub * (n / sub) ** 2
    speedup = us_resort_est / us_stream
    return [{
        "name": "campaign_100k_synthetic",
        "us_per_call": us_stream,
        "derived": (f"records={len(fi)};front={fi.front_size()};"
                    f"stream_us={us_stream:.0f};"
                    f"resort_est_us={us_resort_est:.0f};"
                    f"speedup={speedup:.0f}x;ge5x={speedup >= 5.0}")}]


def bench_screen_cells_jax() -> list[dict]:
    """Cross-cell jax screening vs the per-cell NumPy reference: one
    jitted (cells x n) call against a python loop of screen_rav_batch."""
    import numpy as np

    from repro.core import screen_jax

    from repro.core.batch_eval import screen_rav_batch
    from repro.core.hw_specs import FPGAS
    from repro.core.search import SearchSpace
    from repro.dse.campaign import build_net

    cases = [("vgg16", h, w, fp, prec)
             for h, w in ((128, 128), (224, 224), (320, 320))
             for fp in ("ku115", "zcu102", "vu9p", "zc706")
             for prec in (16, 8)]
    n = 4096
    rng = np.random.default_rng(0)
    nets = [build_net(c[0], c[1], c[2]) for c in cases]
    tables = [screen_jax.cell_tables(net, FPGAS[c[3]], c[4], c[4])
              for net, c in zip(nets, cases)]
    blocks = np.stack([
        rng.uniform(sp.lo(), sp.hi(), size=(n, 5))
        for sp in (SearchSpace(sp_max=len(net.major_layers), batch_max=8)
                   for net in nets)])
    stacked = screen_jax.stack_cells(tables)

    def numpy_loop():
        return [screen_rav_batch(net, FPGAS[c[3]], blk, c[4], c[4])
                for net, c, blk in zip(nets, cases, blocks)]

    ref, us_np = _timed(numpy_loop)
    screen_jax.screen_cells(stacked, blocks)       # compile warmup
    out, us_jax = _timed(screen_jax.screen_cells, stacked, blocks)
    exact = all(np.array_equal(out[i], r) for i, r in enumerate(ref))
    return [{
        "name": f"screen_cells_jax_{len(cases)}x{n}",
        "us_per_call": us_jax,
        "derived": (f"cells={len(cases)};n={n};numpy_us={us_np:.0f};"
                    f"jax_us={us_jax:.0f};"
                    f"speedup={us_np / us_jax:.1f}x;bit_equal={exact}")}]


def bench_calib_fit() -> list[dict]:
    """repro.calib: fit per-part corrections on the committed fixture
    measurement set and render the error table — the docs-job smoke path.
    Headline: every part's calibrated error must come in at or under its
    raw error (the geomean fit guarantees it; ``all_improved`` gates)."""
    from repro.calib import error_rows, fit_corrections, fixture_measurements

    ms = fixture_measurements()
    cal, us = _timed(fit_corrections, ms)
    rows = error_rows(cal)
    improved = all(r["cal_err_pct"] <= r["raw_err_pct"] + 1e-9 for r in rows)
    worst = max((r["cal_err_pct"] for r in rows), default=0.0)
    return [{
        "name": "calib_fit", "us_per_call": us,
        "derived": (f"parts={len(cal.parts())};meas={len(ms)};"
                    f"fingerprint={cal.fingerprint()};"
                    f"worst_cal_err_pct={worst:.2f};"
                    f"all_improved={improved}")}]


BENCHES = {
    "fig1": bench_fig1_ctc,
    "table1": bench_table1_variance,
    "fig9": bench_fig9_dsp_efficiency,
    "fig10": bench_fig10_throughput,
    "fig11": bench_fig11_deeper,
    "table3": bench_table3_rav,
    "table4": bench_table4_batch,
    "campaign": bench_dse_campaign,
    "campaign_fpga": bench_fpga_campaign,
    "campaign_fpga_hyperband": bench_searcher_engines,
    "campaign_tpu": bench_tpu_campaign,
    "campaign_cuda": bench_cuda_campaign,
    "campaign_placement": bench_placement,
    "campaign_100k": bench_campaign_100k,
    "screen_jax": bench_screen_cells_jax,
    "calib_fit": bench_calib_fit,
    "roofline": bench_roofline,
}


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--only", default=None, metavar="NAMES",
                    help="comma list of benchmarks to run, from: "
                         + ",".join(BENCHES))
    ap.add_argument("--json", dest="json_path", default=None, metavar="OUT",
                    help="also write rows (grouped by benchmark) as JSON")
    args = ap.parse_args()
    if args.only:
        names = [n.strip() for n in args.only.split(",") if n.strip()]
        unknown = [n for n in names if n not in BENCHES]
        if unknown:
            ap.error(f"unknown benchmarks {unknown}; "
                     f"choose from {list(BENCHES)}")
    else:
        names = list(BENCHES)
    results: dict[str, list[dict]] = {}
    print("name,us_per_call,derived")
    for n in names:
        results[n] = BENCHES[n]()
        for row in results[n]:
            print(f"{row['name']},{row['us_per_call']:.1f},\"{row['derived']}\"")
    if args.json_path:
        with open(args.json_path, "w") as f:
            json.dump({"benchmarks": results}, f, indent=2, sort_keys=True)


if __name__ == "__main__":
    main()
